"""The main-path Pallas kernels compile for a TPU v5e, at real sweep widths.

Interpret mode accepts block shapes that Mosaic refuses (a `(1, 2)` block of
a `(B, 2)` operand passes every interpret-mode test and fails to lower for
any B > 1), so these tests compile each kernel for a described, unattached
v5e chip from this CPU process.  The topology is described inside a fixture,
never at import: only one process may load the TPU compiler's library, and
the suite's workers all import this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.logistic_prox import logistic_prox_gd_batched
from repro.kernels.prox_update import prox_update_batched

HBM_BYTES = 16 * 1024**3  # one v5e chip


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # A compile for a described chip is written to the persistent cache but
    # can never be read back without one: keep the cache out of it.
    from jax.experimental.compilation_cache import compilation_cache

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip) for s in shapes]
    # The suite runs with x64 on; with it, every Pallas kernel's grid indices
    # are i64, which Mosaic refuses whatever the operand dtypes.  Programs
    # meant for the chip therefore run with x64 off, as chip_smoke.py does.
    with jax.enable_x64(False):
        compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert used < HBM_BYTES, used
    return compiled


def test_topology_is_a_v5e(topo):
    assert topo.devices[0].device_kind == "TPU v5 lite"


@pytest.mark.parametrize("B,d", [(32, 512), (32, 123)])
def test_prox_update_batched_compiles(one_chip, B, d):
    def fn(y, g, z, lr, inv_eta):
        return prox_update_batched(y, g, z, lr, inv_eta, interpret=False)

    _compile(fn, one_chip, (B, d), (B, d), (B, d), (B,), (B,))


def test_logistic_prox_gd_batched_compiles(one_chip):
    B, n, d = 32, 1628, 123  # a9a's 123 features, 32,561 rows over 20 clients

    def fn(A, z, beta, inv_eta):
        return logistic_prox_gd_batched(A, z, beta, inv_eta, 0.1, 40, interpret=False)

    _compile(fn, one_chip, (B, n, d), (B, d), (B,), (B,))
