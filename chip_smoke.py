#!/usr/bin/env python3
"""Bring-up smoke run of the SVRP engine on one TPU chip.

    python chip_smoke.py               # device, sweep, serve and model phases
    python chip_smoke.py --four-chips  # shard="clients" on 4 chips vs 1 chip

Drives the main path once through the entry points a user calls
(`run_batch`, `open_session`, `SessionPool`) at real sizes, in float32 (x64
stays off: a TPU has no native float64) and at the library's own matmul
precision (`repro.core.precision`), and checks every result:

1. device: a TPU must be present; there is no CPU fallback;
2. sweep: SVRP with the Algorithm-7 "gd" prox on the a9a-shaped logistic
   problem (the paper's section-9 workload) and on a synthetic quadratic, a
   4-stepsize x 8-seed grid (B = 32), once on the XLA path and once through
   the compiled Pallas kernels (`fused=True`); the two must agree;
3. serve: a few `step(k)` chunks of an `open_session`, and a 4-tenant
   `SessionPool` whose lanes must equal their standalone sessions;
4. model: 3 `deep_svrp` rounds of the 100m `FedLMProblem` preset with the
   quant8 channel; the loss must fall.

The last line of stdout is one JSON object naming the device.  Any failed
check raises, so the script exits non-zero and prints no such line.  Each
phase is a plain function of its sizes, so tests/test_chip_smoke.py runs it
on the CPU at tiny sizes.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

# Fused vs XLA agreement.  Both paths run the same float32 Algorithm-7
# arithmetic, but the kernels associate it differently (in-kernel sigmoid via
# tanh, lane-padded dot products), so each round differs by a few float32 ulps
# (eps = 1.2e-7).  SVRP contracts, so these differences do not compound: over
# 200 rounds of 20-40 prox steps they stay near 1e-4 relative.  Once dist_sq
# reaches float32's floor (the iterate's own rounding, about eps^2 ||x*||^2
# per coordinate) both paths read noise, hence the absolute term, set
# relative to the starting distance.
RTOL = 2e-3
ATOL_REL = 1e-9
# "Falls by several orders of magnitude": every trial's final dist_sq must be
# this many decades under its start.
MIN_DECADES = 5.0
# Stepsize grids around each problem's Theorem-2 stepsize mu / (2 delta^2),
# with p = 0.2 and 40 Algorithm-7 steps per prox: every trial of either grid
# gets past MIN_DECADES within 200 rounds (measured at reduced sizes on CPU).
A9A_ETAS = (4.0, 2.0, 1.0, 0.5)
QUAD_ETAS = (0.04, 0.03, 0.02, 0.01)


def _line(**fields) -> None:
    print("  ".join(f"{k}={v}" for k, v in fields.items()), flush=True)


def _ready(tree):
    return jax.block_until_ready(tree)


def phase_device(require_tpu: bool = True) -> dict:
    """Name the device; without a TPU, exit non-zero (no CPU fallback)."""
    devs = jax.devices()
    dev = devs[0]
    _line(phase="device", jax=jax.__version__, platform=dev.platform,
          kind=repr(dev.device_kind), count=len(devs))
    if require_tpu and dev.platform != "tpu":
        raise SystemExit(
            f"chip_smoke: no TPU found (jax.devices()[0].platform is "
            f"{dev.platform!r}); this script has no CPU fallback"
        )
    return {"platform": dev.platform, "kind": dev.device_kind, "count": len(devs)}


def kernels_compiled() -> bool:
    """Whether both main-path kernels lower to a Mosaic custom call (not the
    interpreter) on this backend under the default `interpret=None` rule."""
    from repro.kernels.logistic_prox import logistic_prox_gd_batched
    from repro.kernels.prox_update import prox_update_batched

    f32 = jnp.float32
    v = jax.ShapeDtypeStruct((8, 128), f32)
    s = jax.ShapeDtypeStruct((8,), f32)
    a = jax.ShapeDtypeStruct((8, 16, 128), f32)
    texts = [
        jax.jit(prox_update_batched).lower(v, v, v, s, s).as_text(),
        jax.jit(lambda A, z, b, e: logistic_prox_gd_batched(A, z, b, e, 0.1, 2))
        .lower(a, v, s, s).as_text(),
    ]
    return all("tpu_custom_call" in t for t in texts)


def sweep_phase(name: str, problem, *, etas, seeds: int, num_steps: int,
                prox_steps: int, p: float) -> dict:
    """SVRP over `etas` x `seeds` on the XLA path and on the fused kernels;
    asserts agreement and a fall of `MIN_DECADES` decades in every trial."""
    from repro.experiments import run_batch

    x0 = jnp.zeros(problem.dim, jnp.float32)
    x_star = _ready(problem.minimizer())
    grid = {"eta": list(etas), "p": p, "smoothness": float(problem.smoothness_max())}
    kw = dict(grid=grid, seeds=seeds, num_steps=num_steps, prox_solver="gd",
              prox_steps=prox_steps, x0=x0, x_star=x_star)
    out = {}
    for path, fused in (("xla", False), ("fused", True)):
        t0 = time.perf_counter()
        res = run_batch("svrp", problem, fused=fused, **kw)
        d2 = np.asarray(_ready(res.dist_sq))
        out[path] = (res, time.perf_counter() - t0)
        _line(phase=f"sweep/{name}", path=path, B=d2.shape[0], rounds=d2.shape[1],
              seconds=f"{out[path][1]:.3f}", final_dist_sq_median=f"{np.median(d2[:, -1]):.3e}",
              final_dist_sq_max=f"{d2[:, -1].max():.3e}")
    xla, fused = (np.asarray(out[k][0].dist_sq, np.float64) for k in ("xla", "fused"))
    d0 = float(jnp.sum((x0 - x_star) ** 2))
    assert np.all(np.isfinite(xla)) and np.all(np.isfinite(fused)), name
    np.testing.assert_array_equal(np.asarray(out["xla"][0].comm),
                                  np.asarray(out["fused"][0].comm))
    dev = np.abs(fused - xla) / (RTOL * xla + ATOL_REL * d0)
    _line(phase=f"sweep/{name}", fused_vs_xla_worst_fraction_of_tolerance=f"{dev.max():.3f}",
          dist_sq_start=f"{d0:.3e}")
    np.testing.assert_allclose(fused, xla, rtol=RTOL, atol=ATOL_REL * d0)
    decades = np.log10(d0 / np.maximum(xla[:, -1], np.finfo(np.float64).tiny))
    assert decades.min() >= MIN_DECADES, (name, decades.min())
    return {"xla_s": out["xla"][1], "fused_s": out["fused"][1],
            "min_decades": float(decades.min())}


def serve_phase(problem, *, eta: float, seeds: int, num_steps: int, chunk: int,
                chunks: int, tenants: int, prox_steps: int) -> dict:
    """`open_session` chunks, then a `tenants`-lane `SessionPool`; every
    pooled lane must equal its standalone session."""
    from repro.serve import SessionPool, open_session

    x_star = _ready(problem.minimizer())
    smooth = float(problem.smoothness_max())

    def kw(i):
        return dict(grid={"eta": eta * 2.0 ** -i, "p": 0.2, "smoothness": smooth},
                    seeds=seeds, num_steps=num_steps, prox_solver="gd",
                    prox_steps=prox_steps, x0=jnp.zeros(problem.dim, jnp.float32),
                    x_star=x_star)

    t0 = time.perf_counter()
    sess = open_session("svrp", problem, **kw(0))
    for _ in range(chunks):
        d2, _ = sess.step(chunk)
    d2 = np.asarray(_ready(d2))
    session_s = time.perf_counter() - t0
    traj = np.asarray(sess.dist_sq)
    assert np.all(np.isfinite(traj)) and np.all(traj[:, -1] < traj[:, 0]), traj[:, [0, -1]]
    _line(phase="serve/session", B=traj.shape[0], rounds=sess.t,
          seconds=f"{session_s:.3f}", final_dist_sq_median=f"{np.median(traj[:, -1]):.3e}")

    t0 = time.perf_counter()
    pool = SessionPool(capacity=tenants)
    ids = [pool.admit("svrp", problem, **kw(i)) for i in range(tenants)]
    for _ in range(chunks):
        pool.step(chunk)
    pooled = [pool.result(t) for t in ids]
    _ready([r.dist_sq for r in pooled])
    pool_s = time.perf_counter() - t0
    worst = 0.0
    for i, got in enumerate(pooled):
        ref = open_session("svrp", problem, **kw(i))
        for _ in range(chunks):
            ref.step(chunk)
        want = ref.result()
        np.testing.assert_array_equal(np.asarray(got.comm), np.asarray(want.comm))
        np.testing.assert_array_equal(got.comm_bytes, want.comm_bytes)
        g, w = (np.asarray(r.dist_sq, np.float64) for r in (got, want))
        # Same round body under one more vmap axis: XLA may fuse it
        # differently, so allow float32 rounding, not more.
        np.testing.assert_allclose(g, w, rtol=1e-4, atol=ATOL_REL * float(w[:, 0].max()))
        worst = max(worst, float(np.max(np.abs(g - w) / np.maximum(w, 1e-30))))
    _line(phase="serve/pool", tenants=tenants, ticks=chunks, rounds_per_tick=chunk,
          seconds=f"{pool_s:.3f}", lane_vs_session_max_rel_diff=f"{worst:.3e}")
    return {"session_s": session_s, "pool_s": pool_s}


def model_phase(cfg, *, clients: int, per_client_batch: int, seq_len: int,
                rounds: int) -> dict:
    """`rounds` deep_svrp rounds of `cfg` through `run_batch` with the quant8
    channel; the loss must fall.  Run twice: the first call compiles."""
    from repro.experiments import RunSpec, run_batch
    from repro.problems import make_fed_lm_problem

    problem, x0 = make_fed_lm_problem(cfg, num_clients=clients,
                                      per_client_batch=per_client_batch,
                                      seq_len=seq_len, seed=0)
    assert x0.dtype == jnp.float32, x0.dtype
    spec = RunSpec(
        "deep_svrp", grid={"eta": 1.0, "local_lr": 0.2, "anchor_prob": 0.25},
        seeds=[0], x0=x0, x_star=x0,  # FedLMProblem reports the mean loss
        static={"num_steps": rounds, "local_steps": 2, "channel": "quant8"},
    )
    times = []
    for _ in range(2):
        t0 = time.perf_counter()
        res = run_batch(spec, problem)
        loss = np.asarray(_ready(res.dist_sq))[0]
        times.append(time.perf_counter() - t0)
    stats = jax.devices()[0].memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    _line(phase="model", params=problem.dim, clients=clients, rounds=rounds,
          first_call_seconds=f"{times[0]:.3f}",
          seconds_per_round=f"{times[1] / rounds:.4f}",
          peak_bytes_in_use=peak if peak is not None else "not reported",
          loss=" -> ".join(f"{v:.4f}" for v in loss))
    assert np.all(np.isfinite(loss)) and loss[-1] < loss[0], loss
    return {"seconds_per_round": times[1] / rounds, "peak_bytes_in_use": peak}


def four_chip_phase(problem, *, etas, seeds: int, num_steps: int, p: float,
                    prox_steps: int, n_devices: int = 4) -> dict:
    """`run_batch(shard="clients")` over `n_devices` devices against the same
    sweep unsharded on one; trajectories and `comm` must agree, and the
    client blocks must really be spread over every device."""
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from repro.experiments import run_batch
    from repro.launch.mesh import make_client_mesh
    from repro.problems.client_shard import pad_clients

    devs = jax.devices()
    if len(devs) < n_devices:
        raise SystemExit(f"chip_smoke: --four-chips needs {n_devices} devices, found {len(devs)}")
    devs = devs[:n_devices]
    x_star = _ready(problem.minimizer())
    kw = dict(grid={"eta": list(etas), "p": p,
                    "smoothness": float(problem.smoothness_max())},
              seeds=seeds, num_steps=num_steps, prox_solver="gd",
              prox_steps=prox_steps, x0=jnp.zeros(problem.dim, jnp.float32),
              x_star=x_star)
    out = {}
    for label, extra in (("one_chip", {}),
                         ("four_chips", {"shard": "clients", "devices": devs})):
        t0 = time.perf_counter()
        res = run_batch("svrp", problem, **extra, **kw)
        _ready(res.dist_sq)
        out[label] = (res, time.perf_counter() - t0)
        _line(phase=f"four_chips/{label}", seconds=f"{out[label][1]:.3f}",
              final_dist_sq_median=f"{np.median(np.asarray(res.dist_sq)[:, -1]):.3e}")
    one, four = out["one_chip"][0], out["four_chips"][0]
    np.testing.assert_array_equal(np.asarray(one.comm), np.asarray(four.comm))
    a, b = (np.asarray(r.dist_sq, np.float64) for r in (one, four))
    d0 = float(jnp.sum(x_star ** 2))
    # The sharded round sums client blocks with a psum: a different float32
    # summation order than the one-device mean, nothing more.
    np.testing.assert_allclose(b, a, rtol=RTOL, atol=ATOL_REL * d0)

    # The engine's layout: client-major problem leaves split on ("clients",).
    M = problem.num_clients
    padded = pad_clients(problem, M + (-M) % n_devices)
    sharding = NamedSharding(make_client_mesh(devs), P("clients"))
    placed = jax.device_put(padded, sharding)
    for leaf in jax.tree.leaves(placed):
        owners = {s.device for s in leaf.addressable_shards}
        rows = {s.data.shape[0] for s in leaf.addressable_shards}
        assert owners == set(devs) and rows == {leaf.shape[0] // n_devices}, (owners, rows)
    _line(phase="four_chips", devices=n_devices, clients=M,
          clients_per_device=padded.num_clients // n_devices,
          max_rel_diff=f"{np.max(np.abs(b - a) / np.maximum(a, 1e-30)):.3e}")
    return {"one_chip_s": out["one_chip"][1], "four_chips_s": out["four_chips"][1]}


def a9a_problem(num_clients: int = 20, n_per_client: int = 1628, n_pool: int = 32561):
    """LIBSVM a9a's shape (32,561 rows, 123 features) split over the clients."""
    from repro.problems.logistic import make_a9a_like_problem

    return make_a9a_like_problem(num_clients=num_clients, n_per_client=n_per_client,
                                 n_pool=n_pool, dim=123, dtype=jnp.float32)


def quadratic_problem(num_clients: int = 20, dim: int = 512):
    from repro.problems import make_synthetic_quadratic

    return make_synthetic_quadratic(num_clients=num_clients, dim=dim, mu=1.0, L=100.0,
                                    delta=5.0, seed=0, dtype=jnp.float32)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help='only run_batch(shard="clients") on 4 chips vs 1 chip')
    args = ap.parse_args(argv)

    device = phase_device()
    from repro.utils.compile_cache import enable_compile_cache

    cache = enable_compile_cache(__file__)
    entries = len(list(Path(cache).glob("*"))) if Path(cache).is_dir() else 0
    _line(phase="cache", dir=cache, entries_at_start=entries)
    if args.four_chips:
        four_chip_phase(a9a_problem(), etas=[4.0, 2.0], seeds=4, num_steps=200,
                        p=0.2, prox_steps=20)
    else:
        t0 = time.perf_counter()
        compiled = kernels_compiled()
        _line(phase="kernels", compiled_by_mosaic=compiled)
        assert compiled, "a main-path Pallas kernel would run in the interpreter"
        sweep_phase("a9a_logistic", a9a_problem(), etas=A9A_ETAS, seeds=8,
                    num_steps=200, prox_steps=40, p=0.2)
        sweep_phase("quadratic", quadratic_problem(), etas=QUAD_ETAS,
                    seeds=8, num_steps=200, prox_steps=40, p=0.2)
        _line(phase="sweep", seconds=f"{time.perf_counter() - t0:.3f}")
        t0 = time.perf_counter()
        serve_phase(a9a_problem(), eta=4.0, seeds=8, num_steps=60, chunk=10,
                    chunks=3, tenants=4, prox_steps=20)
        _line(phase="serve", seconds=f"{time.perf_counter() - t0:.3f}")
        sys.path.insert(0, str(ROOT))
        from examples.fed_transformer import build_cfg

        cfg, bsz, seq = build_cfg("100m")
        t0 = time.perf_counter()
        model_phase(cfg, clients=4, per_client_batch=bsz, seq_len=seq, rounds=3)
        _line(phase="model", seconds=f"{time.perf_counter() - t0:.3f}")
    entries = len(list(Path(cache).glob("*"))) if Path(cache).is_dir() else 0
    _line(phase="cache", dir=cache, entries_at_end=entries)
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
