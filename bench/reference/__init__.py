"""Plain references: straightforward jax.numpy, importing nothing of `repro`."""
