"""The HLO program-cost analyzer behind §Roofline (loop-aware collectives)."""
import jax
import jax.numpy as jnp

from repro.launch import roofline as rl


def test_while_trip_count_multipliers():
    """A scan of length 8 and one of length 3: the analyzer must weight each
    body by its trip count (raw cost_analysis counts bodies once — the
    calibration bug this module exists to fix)."""

    def f(x, w):
        def body(x, _):
            return jnp.tanh(x @ w), None

        x, _ = jax.lax.scan(body, x, None, length=8)

        def body2(x, _):
            return x @ w, None

        x, _ = jax.lax.scan(body2, x, None, length=3)
        return x

    x = jnp.zeros((16, 32))
    w = jnp.zeros((32, 32))
    txt = jax.jit(f).lower(x, w).compile().as_text()
    mults = rl.computation_multipliers(txt)
    body_mults = sorted(
        m for name, m in mults.items()
        if name.startswith("region") and "cond" not in name and m > 1
    )
    assert 8.0 in body_mults and 3.0 in body_mults, mults


def test_collective_bytes_loop_weighted():
    """An all-reduce inside a scan body must be counted trip-count times."""
    import os
    import subprocess
    import sys

    code = r"""
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
import sys
sys.path.insert(0, "src")
from repro.launch import roofline as rl

from jax.sharding import AxisType
mesh = jax.make_mesh((4,), ("data",), axis_types=(AxisType.Auto,))

def f(x):
    def body(x, _):
        return jax.lax.pmean(x, "data"), None
    x, _ = jax.lax.scan(body, x, None, length=5)
    return x

sm = jax.shard_map(f, mesh=mesh, in_specs=(P(),), out_specs=P(),
                   axis_names={"data"}, check_vma=False)
c = jax.jit(sm).lower(jnp.zeros((8, 128))).compile()
txt = c.as_text()
by, counts = rl.collective_stats(txt)
total = sum(by.values())
# one all-reduce of 8*128 f32 = 4096 B, 5 trips, ring weight 2x => 40960
assert abs(total - 2 * 5 * 8 * 128 * 4) < 1e-6, (by, counts)
print("OK", total)
"""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    r = subprocess.run([sys.executable, "-c", code], capture_output=True, text=True,
                       env=env, cwd=".")
    assert "OK" in r.stdout, r.stdout + r.stderr


def test_analytic_cost_sane_for_known_config():
    from repro.configs import get_config

    cfg = get_config("llama3.2-3b")
    sc = rl.analytic_cost(cfg, "train_4k", kind="train", train_mode="adamw")
    # adamw train = 4x fwd; fwd matmul ~= 2 N tokens
    n_mm = cfg.active_param_count() - cfg.vocab_size * cfg.d_model
    tokens = 256 * 4096
    assert sc.flops > 4 * 2 * n_mm * tokens  # attention adds on top
    assert sc.flops < 10 * 2 * n_mm * tokens
    assert sc.hbm_bytes > 0

    dec = rl.analytic_cost(cfg, "decode_32k", kind="decode")
    # decode is dominated by weight streaming + cache traffic
    assert dec.detail["weight_stream_bytes"] > 0
    assert dec.detail["cache_bytes"] > 0


def test_model_flops_moe_uses_active_params():
    from repro.configs import get_config

    moe = get_config("qwen3-moe-235b-a22b")
    assert moe.active_param_count() < 0.25 * moe.param_count()
    mf = rl.model_flops(moe, "train_4k")
    assert mf == 6.0 * moe.active_param_count() * 256 * 4096
