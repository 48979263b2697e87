"""The one rule for how a Pallas kernel runs: compiled on TPU, interpreted
elsewhere.

Every kernel wrapper, `core.prox`, `core.rounds` and `run_batch` take
`interpret=None` and resolve it here, so a kernel on a TPU is always compiled
by Mosaic and a kernel on the CPU always runs in the Pallas interpreter.  An
explicit `True`/`False` overrides the rule (the TPU compile tests pass
`False` to compile for a described chip from a CPU process).
"""
from __future__ import annotations

import jax


def resolve_interpret(interpret: bool | None = None) -> bool:
    """`interpret` if given, else `True` unless the default backend is a TPU.

    Called while tracing, so it reads the backend of the process that
    compiles the kernel, never at import."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return bool(interpret)
