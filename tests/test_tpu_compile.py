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


@pytest.mark.parametrize("B,n,d", [
    (32, 1628, 123),  # a9a's 123 features, 32,561 rows over 20 clients
    (1, 1000, 256),  # d over one lane tile; neither n nor d a multiple of 128
])
def test_logistic_prox_gd_batched_compiles(one_chip, B, n, d):
    def fn(A, z, beta, inv_eta):
        return logistic_prox_gd_batched(A, z, beta, inv_eta, 0.1, 40, interpret=False)

    _compile(fn, one_chip, (B, n, d), (B, d), (B,), (B,))


def test_fused_sweep_kernel_keeps_its_name_under_the_prox_scope(one_chip):
    """The fused SVRP round compiled for the chip: the kernel's instruction
    keeps the name a profiler trace shows it by, and the kernel, the
    client-block gather and the pad that feed it all run under `prox`."""
    import re

    from repro.experiments import runner as R
    from repro.experiments.spec import RunSpec
    from repro.problems.logistic import LogisticProblem

    M, n, d, B = 4, 16, 8, 2
    with jax.enable_x64(False):
        prob = LogisticProblem(Z=jnp.zeros((M, n, d)), y=jnp.ones((M, n)), lam=0.1)
        rr = RunSpec("svrp", grid={"eta": [1.0, 0.5], "p": 0.2, "smoothness": 1.0}, seeds=1,
                     static=dict(num_steps=3, prox_solver="gd", prox_steps=3)).resolve(prob)
        runner = R._fused_runner("svrp", tuple(sorted(rr.cfg.items())), False)

        def fn(problem, x0, x_star, key_data, hp):
            return runner(problem, x0, x_star, jax.random.wrap_key_data(key_data), hp)

        args = (prob, rr.x0, rr.x_star, jax.random.key_data(rr.keys()), rr.device_hparams())
        shapes = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip), args)
        txt = jax.jit(fn).lower(*shapes).compile().as_text()
    lines = {m.group(2): ln for ln in txt.splitlines()
             if (m := re.match(r"\s*(ROOT )?%(\S+) = ", ln))}
    (kernel,) = [ln for ln in lines.values() if 'custom_call_target="tpu_custom_call"' in ln]
    assert re.match(r"\s*(ROOT )?%logistic_prox_gd_batched\.\d+ = ", kernel), kernel[:120]
    operands = re.search(r"custom-call\(([^)]*)\)", kernel).group(1)
    feeds = [lines[name] for name in re.findall(r"%(\S+?)[,)]", operands + ")")]
    assert any(re.search(r"= \S+ pad\(", ln) for ln in feeds), feeds
    for ln in [kernel] + [f for f in feeds if "op_name=" in f]:  # bitcasts carry none
        assert re.search(r'op_name="[^"]*/prox/', ln), ln[:200]
