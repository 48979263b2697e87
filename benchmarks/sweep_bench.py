"""Batched sweep engine vs the per-trial Python loop it replaced.

The pre-engine benchmarks (fig1, minibatch_sweep) drove `run_svrp`/`run_sppm`
one trial at a time from Python — one full scan execution per (seed, eta)
combo, leaving the device idle on these tiny bandwidth-bound problems.
`repro.experiments.run_batch` runs the whole sweep as ONE vmapped jitted scan.

Quadratic track (SVRP), four timings (all warm, compile excluded; cold compile
reported separately):

* loop/exact      — the old path: per-trial jitted scan, LU prox
* loop/spectral   — per-trial scan with the hoisted-eigendecomposition prox
* batch/exact     — run_batch, LU prox (vmapped LAPACK still serializes on CPU)
* batch/spectral  — run_batch + spectral prox: the engine's fast path

Headline = loop/exact vs batch/spectral (what the benchmarks used to do vs
what they do now).  Acceptance floor: >= 5x at B >= 32 on CPU.  When more
than one device is visible (XLA_FLAGS=--xla_force_host_platform_device_count
or real accelerators) a `shard/spectral` timing of `run_batch(shard="data")`
is measured too.

Logistic track (SPPM, the paper's Algorithm 1 on an a9a-statistics-matched
problem — the approximate-prox regime the analysis is actually about):

* logistic_loop/fixed25   — the PRE-bugfix track this PR replaced: per-trial
  loop, raw 25-iteration Newton prox (no damping, no early exit) — faithfully
  re-registered here through the prox-solver registry as `newton-fixed25`
* logistic_loop/exact     — per-trial loop with the guarded early-exit Newton
* logistic_batch/newton   — run_batch + guarded Newton
* logistic_batch/newton-cg — run_batch + hvp-CG inexact Newton: the engine's
  non-quadratic fast path (no LAPACK in the hot loop, batches cleanly)

Headline = logistic_loop/fixed25 vs logistic_batch/newton-cg (old track vs
engine fast path, the construction mirroring the quadratic headline).
Acceptance floor: >= 5x on CPU (absolute, encoded in the baseline's
`absolute_floors`); measured ~17x idle alongside a ~6x win from the
early-exit bugfix alone.

SVRP-on-logistic caveat track (the refresh-bearing algorithm the old vmapped
engine ran at ~0.5x of its own loop, because the per-trial anchor-refresh
`lax.cond` linearized under vmap into a select paying `full_grad` for every
trial every step):

* logistic_svrp_loop/gd + logistic_svrp_batch/gd            — Algorithm-7 prox
* logistic_svrp_loop/newton-cg + logistic_svrp_batch/newton-cg

The batched side now runs the round-substrate layer's batch-aware execution
(`core.rounds.registry_batched_scan`: one batch-level `lax.cond(jnp.any(c))`
per step, full-gradient recompute only when some trial refreshes).
Acceptance: `logistic_svrp_batch_gd_vs_loop` >= 1x ABSOLUTE (the recorded
0.5x caveat must stay recovered); measured ~1.3x (gd) / ~1.1x (newton-cg)
idle.

Fused-substrate timing (quadratic minibatch SVRP, all B x b cohort proxes of
a step in one batched Pallas launch per GD step, interpret mode on CPU):
`minibatch_loop/gd` vs `minibatch_fused/gd`, recorded as
`minibatch_fused_vs_loop` — informational on CPU (interpret-mode kernel
emulation dominates; the compiled-kernel win is a real-TPU item).

Online-round-engine timing (`session/spectral`): the quadratic headline sweep
stepped 50 rounds at a time through `repro.serve.open_session` instead of one
fused scan, recorded as `session_step_vs_scan`.  Acceptance: >= 0.7x absolute
(encoded in the baseline's `absolute_floors`) — incremental stepping may cost
at most 30% of the scan's throughput, so early stopping and online serving
never mean abandoning the engine's speed.

Multi-tenant pool curve (`pool_scale` in the JSON, docs/SCALING.md): many
concurrent SPPM federations served at tick granularity (one round per tick —
the serving regime, where per-dispatch overhead, not FLOPs, is the cost),
aggregate rounds/sec for P in {1, 4, 8, 16} tenants through ONE `SessionPool`
dispatch per tick vs the same sessions stepped round-robin (P dispatches per
tick).  The gated ratio `pool_vs_roundrobin_8` = round-robin wall-clock /
pooled wall-clock at 8 tenants, with an absolute floor of 2.0x in the
baseline (the acceptance line: pooling must at least halve the serving cost
of 8 concurrent sessions).

Client-scale stress curve (`client_scale` in the JSON, docs/SCALING.md): SVRP
at its theory hyperparameters (eta = mu/(2 delta^2), p = 1/M) through
`run_batch(shard="clients")` for M in {64, 256, 1024, 3000}, recorded as
measured rounds/sec per M plus a fig1-style convergence record at M=3000
(final median dist-sq of the theory-stepsize run).  Informational, not gated:
the CI bench job runs a single CPU device, where the 1-device 'clients' mesh
measures substrate overhead, not scaling (docs/BENCHMARKS.md lists this with
the other CPU caveats).  `client_shard_vs_batch_M256` records the same-sweep
ratio against the plain batched engine.

Comm-bytes frontier (`comm_bytes` in the JSON): engine-measured
bytes-on-the-wire per round per (algo, channel) on a float32 quadratic at
dim=512 — large enough that quant8's blockwise-scale overhead amortizes to
its asymptotic 0.254x of the float32 wire.  The gated ratio
`deep_svrp_quant8_bytes_saving` = float32 bytes-per-round / quant8
bytes-per-round for deep SVRP, with an absolute floor of 3.704x in the
baseline (= the acceptance line "quant8 <= 0.27x float32 bytes-per-round").
Both sides are the engine's own int64 ledger (`BatchResult.comm_bytes`), not
a closed-form recomputation.

Real-model record (`fed_lm_20m`, written under ``--fed-lm`` / ``--full``):
the 20m-preset federated transformer (examples/fed_transformer.py's preset)
through `run_batch("deep_svrp", ...)` with channel="quant8" vs None — the
loss trajectories (the engine's dist_sq column is the across-client mean LM
loss) and the measured bytes ratio, recording that the quantized wire
CONVERGES on the real-model path, not just that it is small.

Perf accounting (`perf` in the JSON, docs/PERFORMANCE.md): every timed
section is priced by the analytic FLOP model (`repro.core.flops.sweep_flops`
— init + rounds x expected per-round cost + once-per-sweep hoisted prep) and
reported as `flops_per_round`, achieved `gflops_per_s` (analytic FLOPs over
the warm wall-clock) and `mfu` against `repro.utils.roofline.get_peak()` —
the same-host CALIBRATED matmul peak on CPU.  The `pool_scale/P*` entries
carry the pool tick's aggregate MFU (all tenants' FLOPs through one
dispatch); `client_scale/M*` the sharded stress curve's.

Prox roofline microbenchmark (`prox_roofline` in the JSON): the fused
batched quadratic gd-prox (`prox_gd_batched`, B=64/d=128/T=32, analytic
`T(2Bd^2 + 6Bd)` FLOPs) timed through XLA (`use_kernel=False`) and through
interpret-mode Pallas.  The XLA fraction of peak is the gate's ABSOLUTE
roofline floor (`quadratic_prox_roofline_frac` >= 0.2 in the baseline's
`absolute_floors`, a 4x derate of the measured ~0.8 — it fails when the
prox path stops being compute-shaped, not when the runner slows down, since
the calibrated peak moves with the host).  The Pallas-interpret fraction
prices the CPU emulation and is informational
(docs/PERFORMANCE.md#honest-caveats).

CLI (the CI bench job's entry point):

    python -m benchmarks.sweep_bench --json BENCH_sweep.json [--full] [--fed-lm]

writes the timings + speedup ratios + per-section perf block as
machine-readable JSON, gated against the checked-in baseline AND the
recorded repo-root trajectory by benchmarks/check_bench.py.
"""
from __future__ import annotations

import argparse
import json
import time

import jax

jax.config.update("jax_enable_x64", True)

import jax.numpy as jnp

from repro.core import theorem2_stepsize
from repro.core.flops import sweep_flops
from repro.core.prox import PROX_SOLVERS, ProxSolver
from repro.experiments import run_batch, run_sequential
from repro.problems import make_a9a_like_problem, make_synthetic_quadratic
from repro.serve import SessionPool, open_session
from repro.utils.roofline import get_peak


def _register_legacy_newton() -> None:
    """Re-register the PRE-bugfix logistic prox as a benchmark-only solver.

    `LogisticProblem.prox` used to take 25 raw Newton steps per call — no
    damping, no monotonicity guard, no early exit.  The production solver was
    fixed; this faithful copy exists ONLY so the benchmark keeps measuring
    the track the engine replaced (the registry being open for extension is
    exactly what makes that possible without re-introducing the bug).
    """
    if "newton-fixed25" in PROX_SOLVERS:
        return

    def _solve_fixed25(problem, hoisted, m, z, eta, *, smoothness, steps, tol):
        del hoisted, smoothness, steps, tol
        eye = jnp.eye(problem.dim, dtype=z.dtype)

        def body(_, x):
            g = problem.grad(m, x) + (x - z) / eta
            H = problem.hessian(m, x) + eye / eta
            return x - jnp.linalg.solve(H, g)

        return jax.lax.fori_loop(0, 25, body, z)

    PROX_SOLVERS["newton-fixed25"] = ProxSolver(
        "newton-fixed25", ("grad", "hessian"), False, lambda p: None, _solve_fixed25
    )


def _timed(fn, warm_reps: int = 3):
    """(cold_seconds, warm_seconds) — first call includes compile; warm is
    the BEST of `warm_reps` repeat calls (timeit's convention: the minimum is
    the least-noise estimate of the code's cost, everything above it is host
    scheduling jitter — docs/BENCHMARKS.md#methodology)."""
    t0 = time.perf_counter()
    jax.block_until_ready(fn())
    cold = time.perf_counter() - t0
    warm = []
    for _ in range(warm_reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        warm.append(time.perf_counter() - t0)
    return cold, min(warm)


def _logistic_variants(quick: bool) -> tuple[dict, dict]:
    """The logistic (non-quadratic) sweep variants: SPPM on an a9a-like
    problem, old fixed-25-Newton loop track vs the engine's batched solvers.
    Returns (variants, analytic FLOPs per timed call — repro.core.flops,
    guarded-Newton entries are iteration CEILINGS per docs/PERFORMANCE.md)."""
    _register_legacy_newton()
    M = 32
    num_steps = 400 if quick else 1000
    n_seeds = 8 if quick else 16
    lp = make_a9a_like_problem(
        num_clients=M, n_per_client=64, n_pool=1024, dim=16, nnz_per_row=5, seed=0
    )
    x_star = lp.minimizer()
    grid = {"eta": [2.0, 1.0, 4.0, 0.5]}
    common = dict(seeds=n_seeds, num_steps=num_steps, x_star=x_star)

    # SVRP caveat track: the refresh-bearing algorithm at its theory
    # hyperparameters (eta = mu/(2 delta^2), p = 1/M).
    mu = float(lp.strong_convexity())
    delta = float(lp.similarity_at(x_star))
    L = float(lp.smoothness_max())
    eta_svrp = theorem2_stepsize(mu, delta)
    sgrid = {"eta": [eta_svrp, eta_svrp / 2, 2 * eta_svrp, eta_svrp / 4], "p": 1 / M}
    sgrid_gd = {**sgrid, "smoothness": L}
    gd_kw = dict(prox_solver="gd", prox_steps=25)

    variants = {
        "logistic_loop/fixed25": lambda: run_sequential(
            "sppm", lp, grid=grid, prox_solver="newton-fixed25", **common
        ).dist_sq,
        "logistic_loop/exact": lambda: run_sequential(
            "sppm", lp, grid=grid, **common
        ).dist_sq,
        "logistic_batch/newton": lambda: run_batch(
            "sppm", lp, grid=grid, prox_solver="newton", **common
        ).dist_sq,
        "logistic_batch/newton-cg": lambda: run_batch(
            "sppm", lp, grid=grid, prox_solver="newton-cg", **common
        ).dist_sq,
        "logistic_svrp_loop/gd": lambda: run_sequential(
            "svrp", lp, grid=sgrid_gd, **gd_kw, **common
        ).dist_sq,
        "logistic_svrp_batch/gd": lambda: run_batch(
            "svrp", lp, grid=sgrid_gd, **gd_kw, **common
        ).dist_sq,
        "logistic_svrp_loop/newton-cg": lambda: run_sequential(
            "svrp", lp, grid=sgrid, prox_solver="newton-cg", **common
        ).dist_sq,
        "logistic_svrp_batch/newton-cg": lambda: run_batch(
            "svrp", lp, grid=sgrid, prox_solver="newton-cg", **common
        ).dist_sq,
    }
    B = 4 * n_seeds  # every grid above is 4 etas x n_seeds trials
    sppm = lambda **kw: sweep_flops(
        "sppm", lp, num_rounds=num_steps, num_trials=B, **kw
    )
    svrp = lambda **kw: sweep_flops(
        "svrp", lp, num_rounds=num_steps, num_trials=B, p=1.0 / M, **kw
    )
    flops = {
        "logistic_loop/fixed25": sppm(prox_solver="newton-fixed25"),
        "logistic_loop/exact": sppm(prox_solver="exact"),
        "logistic_batch/newton": sppm(prox_solver="newton"),
        "logistic_batch/newton-cg": sppm(prox_solver="newton-cg"),
        "logistic_svrp_loop/gd": svrp(**gd_kw),
        "logistic_svrp_batch/gd": svrp(**gd_kw),
        "logistic_svrp_loop/newton-cg": svrp(prox_solver="newton-cg"),
        "logistic_svrp_batch/newton-cg": svrp(prox_solver="newton-cg"),
    }
    return variants, flops


def _pool_scale(quick: bool, peak_flops: float) -> tuple[dict, dict]:
    """The multi-tenant serving section: aggregate rounds/sec vs pooled
    tenant count, plus the gated `pool_vs_roundrobin_8` ratio — 8 tenants
    through `SessionPool` (ONE jitted dispatch per tick) vs the same 8
    sessions stepped round-robin (8 dispatches per tick).  Tick = 1 round:
    the serving granularity the pool exists for.  Setup (session open, key
    materialization, admission) is excluded from the timed region on BOTH
    sides — the ratio prices steady-state serving, not tenancy churn.  The
    prox is the prep-free gd solver: a per-chunk prepare (spectral's eigh)
    re-runs EVERY tick at tick=1 on both sides and would swamp the dispatch
    cost the section exists to measure."""
    M, dim = 32, 16
    n_seeds = 2
    num_steps = 60 if quick else 200
    tenants = (1, 4, 8, 16)
    probs = [
        make_synthetic_quadratic(num_clients=M, dim=dim, mu=1.0, L=400.0,
                                 delta=6.0, seed=i)
        for i in range(max(tenants))
    ]
    # Distinct per-tenant hyperparameters: the pool's contract is shared
    # SHAPES, independent problems/hp/seeds — the bench exercises that.
    grids = [
        {"eta": 0.05 / (1.0 + 0.1 * i), "smoothness": float(p.smoothness_max())}
        for i, p in enumerate(probs)
    ]
    kw = dict(seeds=n_seeds, num_steps=num_steps,
              prox_solver="gd", prox_steps=20)

    def timed_fresh(setup, run, reps: int = 3):
        """(cold_s, warm_s) with a FRESH object per call (stepping consumes
        the horizon); only `run` is inside the timed region."""
        obj = setup()
        t0 = time.perf_counter()
        jax.block_until_ready(run(obj))
        cold = time.perf_counter() - t0
        warm = []
        for _ in range(reps):
            obj = setup()
            t0 = time.perf_counter()
            jax.block_until_ready(run(obj))
            warm.append(time.perf_counter() - t0)
        return cold, min(warm)

    curve = {}
    pool_warm = {}
    for P in tenants:
        def setup_pool(P=P):
            pool = SessionPool(capacity=P)
            for i in range(P):
                pool.admit("sppm", probs[i], grid=grids[i], **kw)
            return pool

        def run_pool(pool):
            d2 = None
            for _ in range(num_steps):
                d2, _ = pool.step(1)
            return d2

        cold, warm = timed_fresh(setup_pool, run_pool)
        pool_warm[P] = warm
        # Aggregate analytic FLOPs of one timed run: every tenant's whole
        # sweep (repro.core.flops) — the pool-curve MFU of docs/PERFORMANCE.md
        # (serving is dispatch-bound, so these fractions are tiny by design).
        total_flops = sum(
            sweep_flops("sppm", probs[i], num_rounds=num_steps,
                        num_trials=n_seeds, prox_solver="gd", prox_steps=20)
            for i in range(P)
        )
        curve[str(P)] = {
            "cold_s": cold,
            "warm_us": warm * 1e6,
            "aggregate_rounds_per_s": P * num_steps / warm,
            "flops_per_round": total_flops / num_steps,
            "gflops_per_s": total_flops / warm / 1e9,
            "mfu": total_flops / warm / peak_flops,
        }

    def setup_rr():
        return [
            open_session("sppm", probs[i], grid=grids[i], **kw)
            for i in range(8)
        ]

    def run_rr(sessions):
        outs = None
        for _ in range(num_steps):
            outs = [s.step(1)[0] for s in sessions]
        return outs

    rr_cold, rr_warm = timed_fresh(setup_rr, run_rr)
    record = {
        "algo": "sppm", "M": M, "dim": dim, "seeds": n_seeds,
        "num_steps": num_steps, "tick": 1,
        "aggregate_rounds_per_s_vs_tenants": curve,
        "roundrobin_8": {
            "cold_s": rr_cold,
            "warm_us": rr_warm * 1e6,
            "aggregate_rounds_per_s": 8 * num_steps / rr_warm,
        },
    }
    ratios = {"pool_vs_roundrobin_8": rr_warm / pool_warm[8]}
    return record, ratios


def _client_scale(quick: bool, peak_flops: float) -> tuple[dict, dict]:
    """The shard='clients' stress section: (client_scale record, extra
    speedup ratios).  Rounds/sec at each M is measured warm (second call of
    the cached shard-mapped runner), so it prices the steady-state round
    engine, not tracing."""
    Ms = (64, 256, 1024, 3000)
    num_steps = 60 if quick else 200
    n_seeds = 2
    curve = {}
    ratios = {}
    fig1 = {}
    for M in Ms:
        prob = make_synthetic_quadratic(num_clients=M, dim=16, mu=1.0, L=400.0,
                                        delta=6.0, seed=0)
        mu = float(prob.strong_convexity())
        delta = float(prob.similarity())
        grid = {"eta": theorem2_stepsize(mu, delta), "p": 1 / M}
        kw = dict(grid=grid, seeds=n_seeds, num_steps=num_steps)

        def clients_run(prob=prob, kw=kw):
            return run_batch("svrp", prob, shard="clients", **kw).dist_sq

        cold, warm = _timed(clients_run)
        # Refresh work scales with M while p = 1/M keeps ~1 refresh/round in
        # expectation — the stress curve's MFU should therefore grow with M
        # until the substrate overhead is amortized (docs/PERFORMANCE.md).
        total_flops = sweep_flops(
            "svrp", prob, num_rounds=num_steps, num_trials=n_seeds, p=1.0 / M
        )
        curve[str(M)] = {
            "cold_s": cold,
            "warm_us": warm * 1e6,
            "rounds_per_s": num_steps / warm,
            "flops_per_round": total_flops / num_steps,
            "gflops_per_s": total_flops / warm / 1e9,
            "mfu": total_flops / warm / peak_flops,
        }
        if M == 256:
            _, warm_batch = _timed(
                lambda: run_batch("svrp", prob, **kw).dist_sq
            )
            ratios["client_shard_vs_batch_M256"] = warm_batch / warm
        if M == 3000:
            d2 = run_batch("svrp", prob, shard="clients", **kw).dist_sq
            fig1 = {
                "eta": float(grid["eta"]),
                "p": grid["p"],
                "num_steps": num_steps,
                "final_dist_sq_median": float(jnp.median(d2[:, -1])),
                "initial_dist_sq_median": float(jnp.median(d2[:, 0])),
                "rounds_per_s": curve[str(M)]["rounds_per_s"],
            }
    record = {
        "algo": "svrp",
        "dim": 16,
        "seeds": n_seeds,
        "num_steps": num_steps,
        "rounds_per_s_vs_M": curve,
        "fig1_M3000": fig1,
    }
    return record, ratios


def _prox_roofline(peak_flops: float, peak_source: str) -> tuple[dict, dict]:
    """Absolute roofline-fraction microbench: the fused quadratic prox
    (Algorithm 7's batched GD update) at a compute-heavy shape, as a fraction
    of the calibrated peak — the gated floor `quadratic_prox_roofline_frac`.

    Two timings of the SAME math (held equal by tests/test_kernels_prox.py):

    * xla      — `prox_gd_batched(use_kernel=False)`, the XLA-compiled fused
      expression.  This is the gated number: a fixed-trip-count loop whose
      analytic FLOPs are exact, so achieved/peak is a true roofline fraction
      against the SAME calibration matmul's measured peak (same host, same
      dtype — the fraction ports across runner generations).
    * pallas_interpret — `use_kernel=True` on CPU runs the Pallas kernel
      under the interpreter; its "MFU" prices emulation overhead, not the
      kernel (docs/PERFORMANCE.md#honest-caveats).  Recorded informationally;
      the compiled-kernel fraction is a real-TPU item.
    """
    from repro.core.prox import prox_gd_batched

    B, d, T = 64, 128, 32
    key = jax.random.PRNGKey(0)
    G0 = jax.random.normal(key, (d, d))
    G = G0 @ G0.T / d + jnp.eye(d)  # PD, well-conditioned
    b = jax.random.normal(jax.random.PRNGKey(1), (B, d))
    z = jax.random.normal(jax.random.PRNGKey(2), (B, d))
    grad_fn = lambda y: y @ G - b  # (B, d) -> (B, d): one shared client Hessian
    L = float(jnp.linalg.eigvalsh(G)[-1])
    # Analytic FLOPs per call: T iterations of grad (2 B d^2 + B d) + the
    # 5-flop/element fused y-update (repro.core.flops prox_cost, gd branch).
    flops_per_call = T * (2.0 * B * d * d + B * d + 5.0 * B * d)

    xla_fn = jax.jit(
        lambda z: prox_gd_batched(grad_fn, z, 0.05, L, T, use_kernel=False)
    )
    _, warm = _timed(lambda: xla_fn(z))
    kern_fn = jax.jit(
        lambda z: prox_gd_batched(grad_fn, z, 0.05, L, T,
                                  use_kernel=True, interpret=True)
    )
    _, warm_kernel = _timed(lambda: kern_fn(z))

    frac = flops_per_call / warm / peak_flops
    frac_kernel = flops_per_call / warm_kernel / peak_flops
    record = {
        "B": B, "dim": d, "gd_steps": T,
        "flops_per_call": flops_per_call,
        "peak_gflops": peak_flops / 1e9,
        "peak_source": peak_source,
        "xla": {"warm_us": warm * 1e6,
                "gflops_per_s": flops_per_call / warm / 1e9,
                "roofline_frac": frac},
        "pallas_interpret": {"warm_us": warm_kernel * 1e6,
                             "gflops_per_s": flops_per_call / warm_kernel / 1e9,
                             "roofline_frac": frac_kernel},
    }
    ratios = {
        "quadratic_prox_roofline_frac": frac,
        "pallas_interpret_prox_roofline_frac": frac_kernel,
    }
    return record, ratios


def _comm_bytes_section() -> tuple[dict, dict]:
    """Bytes-on-the-wire per round per (algo, channel), from the engine's own
    int64 ledger on a float32 quadratic at dim=512 (quant8's block-scale
    overhead amortized to its asymptotic ratio).  Returns the record and the
    gated `deep_svrp_quant8_bytes_saving` ratio."""
    M, dim, steps, n_seeds = 8, 512, 30, 2
    prob = make_synthetic_quadratic(num_clients=M, dim=dim, mu=1.0, L=100.0,
                                    delta=4.0, seed=0, dtype=jnp.float32)
    mu = float(prob.strong_convexity())
    delta = float(prob.similarity())
    L = float(prob.smoothness_max())
    jobs = {
        "deep_svrp": dict(
            grid={"eta": 0.5, "local_lr": 0.8 / (L + 2.0), "anchor_prob": 0.25},
            local_steps=2,
        ),
        "svrp": dict(grid={"eta": theorem2_stepsize(mu, delta), "p": 1 / M},
                     prox_solver="spectral"),
        "sppm": dict(grid={"eta": 0.05}, prox_solver="spectral"),
    }
    bytes_per_round: dict[str, dict[str, float]] = {}
    for algo, kw in jobs.items():
        bytes_per_round[algo] = {}
        for channel in (None, "quant8", "cast"):
            res = run_batch(algo, prob, seeds=n_seeds, num_steps=steps,
                            channel=channel, **kw)
            total = jnp.median(jnp.asarray(res.comm_bytes[:, -1]))
            bytes_per_round[algo][channel or "none"] = float(total) / steps
    deep = bytes_per_round["deep_svrp"]
    ratios = {"deep_svrp_quant8_bytes_saving": deep["none"] / deep["quant8"]}
    record = {
        "M": M, "dim": dim, "num_steps": steps, "seeds": n_seeds,
        "dtype": "float32",
        "bytes_per_round": bytes_per_round,
        "deep_svrp_quant8_vs_f32_ratio": deep["quant8"] / deep["none"],
    }
    return record, ratios


def _fed_lm_20m() -> dict:
    """The real-model deep-SVRP payoff: the 20m-preset federated transformer
    through `run_batch(..., channel="quant8")` vs the float32 wire.  Records
    the loss trajectories (dist_sq = across-client mean LM loss) and the
    measured bytes ratio — convergence evidence, not just wire math."""
    import dataclasses

    from repro.configs import REGISTRY
    from repro.problems import make_fed_lm_problem

    rounds, clients = 6, 4
    cfg = dataclasses.replace(
        REGISTRY["llama3.2-3b"].reduced(),
        num_layers=6, d_model=384, num_heads=6, num_kv_heads=2, head_dim=64,
        d_ff=1024, vocab_size=8192, param_dtype="float32",
        compute_dtype="float32",
    )
    problem, x0 = make_fed_lm_problem(
        cfg, num_clients=clients, per_client_batch=2, seq_len=128,
        alpha=0.3, seed=0,
    )
    out: dict = {"preset": "20m", "dim": int(problem.dim), "rounds": rounds,
                 "clients": clients}
    for channel in ("quant8", None):
        res = run_batch(
            "deep_svrp", problem,
            grid={"eta": 1.0, "local_lr": 0.2, "anchor_prob": 0.25},
            seeds=[0], num_steps=rounds, local_steps=2, channel=channel,
            x0=x0, x_star=x0,
        )
        key = channel or "none"
        out[f"loss_{key}"] = [float(v) for v in jnp.asarray(res.dist_sq)[0]]
        out[f"total_bytes_{key}"] = int(res.comm_bytes[0, -1])
    out["bytes_ratio"] = out["total_bytes_quant8"] / out["total_bytes_none"]
    out["quant8_converges"] = out["loss_quant8"][-1] < out["loss_quant8"][0]
    return out


def run_structured(quick: bool = False, fed_lm: bool = False) -> dict:
    """All timings + derived speedup ratios as one JSON-ready dict."""
    M, dim = 32, 16
    num_steps = 400 if quick else 1000
    n_seeds = 8 if quick else 16
    prob = make_synthetic_quadratic(num_clients=M, dim=dim, mu=1.0, L=400.0,
                                    delta=6.0, seed=0)
    mu = float(prob.strong_convexity())
    delta = float(prob.similarity())
    eta = theorem2_stepsize(mu, delta)
    grid = {"eta": [eta, eta / 2, 2 * eta, eta / 4], "p": 1 / M}
    B = 4 * n_seeds

    variants = {
        "loop/exact": lambda: run_sequential(
            "svrp", prob, grid=grid, seeds=n_seeds, num_steps=num_steps
        ).dist_sq,
        "loop/spectral": lambda: run_sequential(
            "svrp", prob, grid=grid, seeds=n_seeds, num_steps=num_steps,
            prox_solver="spectral",
        ).dist_sq,
        "batch/exact": lambda: run_batch(
            "svrp", prob, grid=grid, seeds=n_seeds, num_steps=num_steps
        ).dist_sq,
        "batch/spectral": lambda: run_batch(
            "svrp", prob, grid=grid, seeds=n_seeds, num_steps=num_steps,
            prox_solver="spectral",
        ).dist_sq,
    }

    # Incremental-session timing: the SAME sweep stepped 100 rounds at a time
    # through `open_session` (the online round engine) instead of one fused
    # lax.scan.  Measures the overhead of holding the sweep open — per-chunk
    # dispatch, host-side chunk stitching — against the scan it must match.
    def _session_spectral():
        sess = open_session(
            "svrp", prob, grid=grid, seeds=n_seeds, num_steps=num_steps,
            prox_solver="spectral",
        )
        while sess.t < sess.horizon:
            sess.step(min(100, sess.horizon - sess.t))
        return sess.dist_sq

    variants["session/spectral"] = _session_spectral
    # Fused-substrate timing: minibatch SVRP, every cohort prox of every
    # trial through one batched Pallas launch per GD step (interpret on CPU).
    L = float(prob.smoothness_max())
    mb_grid = {"eta": [4 * eta, 2 * eta], "p": 4 / M, "smoothness": L}
    mb_kw = dict(
        seeds=n_seeds, num_steps=num_steps, batch_clients=4,
        prox_solver="gd", prox_steps=20,
    )
    variants["minibatch_loop/gd"] = lambda: run_sequential(
        "svrp_minibatch", prob, grid=mb_grid, **mb_kw
    ).dist_sq
    variants["minibatch_fused/gd"] = lambda: run_batch(
        "svrp_minibatch", prob, grid=mb_grid, fused=True, **mb_kw
    ).dist_sq

    # Analytic FLOPs per timed call (repro.core.flops; aggregate across the
    # B trials of one sweep) — the numerators of the perf section's MFU.
    q = lambda **kw: sweep_flops(
        "svrp", prob, num_rounds=num_steps, num_trials=B, p=1.0 / M, **kw
    )
    mb_flops = sweep_flops(
        "svrp_minibatch", prob, num_rounds=num_steps, num_trials=2 * n_seeds,
        p=4.0 / M, batch_clients=4, prox_solver="gd", prox_steps=20,
    )
    flops_total = {
        "loop/exact": q(prox_solver="exact"),
        "loop/spectral": q(prox_solver="spectral"),
        "batch/exact": q(prox_solver="exact"),
        "batch/spectral": q(prox_solver="spectral"),
        "session/spectral": q(prox_solver="spectral"),
        "minibatch_loop/gd": mb_flops,
        "minibatch_fused/gd": mb_flops,  # fused path: identical math
    }

    n_dev = len(jax.devices())
    if n_dev > 1:
        variants["shard/spectral"] = lambda: run_batch(
            "svrp", prob, grid=grid, seeds=n_seeds, num_steps=num_steps,
            prox_solver="spectral", shard="data",
        ).dist_sq
        flops_total["shard/spectral"] = q(prox_solver="spectral")
    logistic_variants, logistic_flops = _logistic_variants(quick)
    variants.update(logistic_variants)
    flops_total.update(logistic_flops)

    # Per-backend peak for MFU: datasheet on TPU/GPU, measured-matmul
    # calibration on CPU (float64 — the engine dtype under x64 here);
    # docs/PERFORMANCE.md#per-backend-peaks.
    peak = get_peak(dtype="float64")

    warm_us, cold_s = {}, {}
    for name, fn in variants.items():
        cold, w = _timed(fn)
        warm_us[name] = w * 1e6
        cold_s[name] = cold

    # Every timed section's roofline numbers: analytic FLOPs per round
    # (aggregate over the sweep's trials), achieved GFLOP/s, MFU.
    perf_sections = {
        name: {
            "flops_per_round": flops_total[name] / num_steps,
            "gflops_per_s": flops_total[name] / (warm_us[name] / 1e6) / 1e9,
            "mfu": flops_total[name] / (warm_us[name] / 1e6) / peak.flops,
        }
        for name in warm_us
    }

    speedups = {
        "batch_spectral_vs_loop_exact": warm_us["loop/exact"] / warm_us["batch/spectral"],
        "batch_spectral_vs_loop_spectral": (
            warm_us["loop/spectral"] / warm_us["batch/spectral"]
        ),
        "batch_exact_vs_loop_exact": warm_us["loop/exact"] / warm_us["batch/exact"],
        # Logistic track: headline = engine fast path vs the replaced
        # fixed-25-Newton loop; the exact-loop ratio isolates the batching
        # win, and early_exit_vs_fixed isolates the bugfix win.
        "logistic_batch_newton_cg_vs_loop_fixed": (
            warm_us["logistic_loop/fixed25"] / warm_us["logistic_batch/newton-cg"]
        ),
        "logistic_batch_newton_cg_vs_loop_exact": (
            warm_us["logistic_loop/exact"] / warm_us["logistic_batch/newton-cg"]
        ),
        "logistic_early_exit_vs_fixed": (
            warm_us["logistic_loop/fixed25"] / warm_us["logistic_loop/exact"]
        ),
        # SVRP-on-logistic caveat track: batch-aware anchor refresh must keep
        # the batched engine AT LEAST as fast as its own per-trial loop
        # (>= 1x absolute in the baseline; the old vmapped path sat at ~0.5x).
        "logistic_svrp_batch_gd_vs_loop": (
            warm_us["logistic_svrp_loop/gd"] / warm_us["logistic_svrp_batch/gd"]
        ),
        "logistic_svrp_batch_newton_cg_vs_loop": (
            warm_us["logistic_svrp_loop/newton-cg"]
            / warm_us["logistic_svrp_batch/newton-cg"]
        ),
        # Fused minibatch: informational on CPU (interpret-mode Pallas).
        "minibatch_fused_vs_loop": (
            warm_us["minibatch_loop/gd"] / warm_us["minibatch_fused/gd"]
        ),
        # Online round engine: incremental stepping vs the one-shot scan on
        # the quadratic headline.  Acceptance: >= 0.7x absolute — holding the
        # sweep open (chunked dispatch + host stitching) may cost at most 30%
        # of the scan's throughput.
        "session_step_vs_scan": (
            warm_us["batch/spectral"] / warm_us["session/spectral"]
        ),
    }
    if "shard/spectral" in warm_us:
        speedups["shard_spectral_vs_batch_spectral"] = (
            warm_us["batch/spectral"] / warm_us["shard/spectral"]
        )
    pool_scale, pool_ratios = _pool_scale(quick, peak.flops)
    speedups.update(pool_ratios)
    client_scale, client_ratios = _client_scale(quick, peak.flops)
    speedups.update(client_ratios)
    comm_bytes, byte_ratios = _comm_bytes_section()
    speedups.update(byte_ratios)
    prox_roofline, roofline_ratios = _prox_roofline(peak.flops, peak.source)
    speedups.update(roofline_ratios)
    for P, v in pool_scale["aggregate_rounds_per_s_vs_tenants"].items():
        perf_sections[f"pool_scale/P{P}"] = {
            k: v[k] for k in ("flops_per_round", "gflops_per_s", "mfu")
        }
    for Mc, v in client_scale["rounds_per_s_vs_M"].items():
        perf_sections[f"client_scale/M{Mc}"] = {
            k: v[k] for k in ("flops_per_round", "gflops_per_s", "mfu")
        }
    perf_sections["prox_roofline/xla"] = {
        "flops_per_round": prox_roofline["flops_per_call"] / prox_roofline["gd_steps"],
        "gflops_per_s": prox_roofline["xla"]["gflops_per_s"],
        "mfu": prox_roofline["xla"]["roofline_frac"],
    }
    perf_sections["prox_roofline/pallas_interpret"] = {
        "flops_per_round": prox_roofline["flops_per_call"] / prox_roofline["gd_steps"],
        "gflops_per_s": prox_roofline["pallas_interpret"]["gflops_per_s"],
        "mfu": prox_roofline["pallas_interpret"]["roofline_frac"],
    }

    out = {
        "bench": "sweep_bench",
        "algo": "svrp",
        "config": {"M": M, "dim": dim, "num_steps": num_steps, "seeds": n_seeds, "B": B},
        "env": {"platform": jax.devices()[0].platform, "device_count": n_dev,
                "jax": jax.__version__},
        "timings_us": warm_us,
        "cold_compile_s": cold_s,
        "speedups": speedups,
        "perf": {
            "peak_gflops": peak.flops / 1e9,
            "peak_source": peak.source,
            "sections": perf_sections,
        },
        "pool_scale": pool_scale,
        "client_scale": client_scale,
        "comm_bytes": comm_bytes,
        "prox_roofline": prox_roofline,
    }
    if fed_lm:
        out["fed_lm_20m"] = _fed_lm_20m()
    return out


def _rows_from(data: dict) -> list:
    """The legacy ``(name, us, derived)`` rows benchmarks/run.py prints."""
    B = data["config"]["B"]
    steps = data["config"]["num_steps"]
    rows = [
        (
            f"{'' if name.startswith('logistic') else 'svrp_'}{name}_B{B}",
            us,
            f"steps={steps};cold_s={data['cold_compile_s'][name]:.2f}",
        )
        for name, us in data["timings_us"].items()
    ]
    sp = data["speedups"]
    rows.append((
        f"svrp_speedup_B{B}", data["timings_us"]["batch/spectral"],
        f"batch_spectral_vs_loop_exact={sp['batch_spectral_vs_loop_exact']:.1f}x;"
        f"vs_loop_spectral={sp['batch_spectral_vs_loop_spectral']:.1f}x;"
        f"batch_exact_vs_loop_exact={sp['batch_exact_vs_loop_exact']:.1f}x",
    ))
    rows.append((
        f"logistic_speedup_B{B}", data["timings_us"]["logistic_batch/newton-cg"],
        f"batch_newton_cg_vs_loop_fixed={sp['logistic_batch_newton_cg_vs_loop_fixed']:.1f}x;"
        f"vs_loop_exact={sp['logistic_batch_newton_cg_vs_loop_exact']:.1f}x;"
        f"early_exit_vs_fixed={sp['logistic_early_exit_vs_fixed']:.1f}x",
    ))
    rows.append((
        f"logistic_svrp_caveat_B{B}", data["timings_us"]["logistic_svrp_batch/gd"],
        f"batch_gd_vs_loop={sp['logistic_svrp_batch_gd_vs_loop']:.2f}x;"
        f"batch_newton_cg_vs_loop={sp['logistic_svrp_batch_newton_cg_vs_loop']:.2f}x;"
        f"minibatch_fused_vs_loop={sp['minibatch_fused_vs_loop']:.2f}x",
    ))
    rows.append((
        f"session_B{B}", data["timings_us"]["session/spectral"],
        f"session_step_vs_scan={sp['session_step_vs_scan']:.2f}x",
    ))
    cb = data.get("comm_bytes")
    if cb:
        deep = cb["bytes_per_round"]["deep_svrp"]
        rows.append((
            "comm_bytes_deep_svrp",
            deep["quant8"],
            f"f32={deep['none']:.0f}B/round;quant8={deep['quant8']:.0f}B/round;"
            f"saving={sp['deep_svrp_quant8_bytes_saving']:.2f}x",
        ))
    fl = data.get("fed_lm_20m")
    if fl:
        rows.append((
            "fed_lm_20m_quant8",
            fl["total_bytes_quant8"],
            f"loss={fl['loss_quant8'][0]:.3f}->{fl['loss_quant8'][-1]:.3f};"
            f"bytes_ratio={fl['bytes_ratio']:.4f}",
        ))
    ps = data.get("pool_scale")
    if ps:
        pcurve = ps["aggregate_rounds_per_s_vs_tenants"]
        rows.append((
            "pool_scale_rounds_per_s",
            pcurve["8"]["warm_us"],
            ";".join(
                f"P{p}={v['aggregate_rounds_per_s']:.1f}/s"
                for p, v in pcurve.items()
            ) + f";pool_vs_roundrobin_8={sp['pool_vs_roundrobin_8']:.2f}x",
        ))
    cs = data.get("client_scale")
    if cs:
        curve = cs["rounds_per_s_vs_M"]
        rows.append((
            "client_scale_rounds_per_s",
            curve["3000"]["warm_us"],
            ";".join(f"M{m}={v['rounds_per_s']:.1f}/s" for m, v in curve.items()),
        ))
        f1 = cs["fig1_M3000"]
        rows.append((
            "client_fig1_M3000",
            curve["3000"]["warm_us"],
            f"eta={f1['eta']:.2e};final_d2_median={f1['final_dist_sq_median']:.3e}",
        ))
    return rows


def run(quick: bool = False):
    return _rows_from(run_structured(quick=quick))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true", help="paper-scale timing (slow)")
    ap.add_argument("--fed-lm", action="store_true",
                    help="also run the 20m-preset federated transformer "
                         "record (minutes on CPU; implied by --full)")
    ap.add_argument("--json", metavar="PATH", default=None,
                    help="write machine-readable results to PATH")
    args = ap.parse_args()

    data = run_structured(quick=not args.full, fed_lm=args.fed_lm or args.full)
    print("name,us_per_call,derived")
    for name, us, derived in _rows_from(data):
        print(f"{name},{us:.0f},{derived}")
    if args.json:
        with open(args.json, "w") as f:
            json.dump(data, f, indent=2, sort_keys=True)
            f.write("\n")
        print(f"# wrote {args.json}")


if __name__ == "__main__":
    from repro.utils.compile_cache import enable_compile_cache

    enable_compile_cache(__file__)
    main()
