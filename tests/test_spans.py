"""The engine's own tracing (`repro.utils.spans`).

Device side: every round hook's named scope reaches the compiled HLO's
`op_name` metadata, on the fused substrate and on the client-sharded one (4
virtual CPU devices, in a subprocess so the device-count flag stays there).
Host side: `run_batch`, the session and the pool write their spans into a
real profiler trace, nested and carrying their ids.
"""
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.experiments import run_batch
from repro.experiments import runner as R
from repro.experiments.spec import RunSpec
from repro.problems import make_synthetic_quadratic
from repro.problems.logistic import make_a9a_like_problem
from repro.serve import SessionPool, open_session
from repro.utils import spans

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def scopes_in(hlo_text: str) -> set:
    """Program scopes on the `op_name` paths of an HLO text (the path's last
    component is the primitive, never a scope)."""
    found = set()
    for path in re.findall(r'op_name="([^"]*)"', hlo_text):
        found.update(path.split("/")[:-1])
    return found & set(spans.SCOPES)


def _instructions(hlo_text: str) -> list:
    """Each HLO instruction's text, its metadata left out."""
    return [re.sub(r", metadata=\{[^}]*\}", "", ln)
            for ln in hlo_text.splitlines() if re.match(r"\s*(ROOT )?%", ln)]


def _step(scoped: bool):
    def step(x):
        if not scoped:
            return jnp.sin(x) @ x
        with spans.scope("refresh"):
            return jnp.sin(x) @ x

    return jax.jit(step)


def test_scope_does_not_change_the_program():
    x = jnp.ones((8, 8))
    plain = _step(False).lower(x).compile().as_text()
    scoped = _step(True).lower(x).compile().as_text()
    assert "refresh" not in plain and "/refresh/" in scoped
    assert _instructions(plain) == _instructions(scoped)
    np.testing.assert_array_equal(_step(False)(x), _step(True)(x))


def test_fused_svrp_hlo_carries_the_round_scopes():
    prob = make_a9a_like_problem(num_clients=4, n_per_client=16, n_pool=256, dim=8, nnz_per_row=3)
    rr = RunSpec("svrp", grid={"eta": [1.0, 0.5], "p": 0.3, "smoothness": 1.0}, seeds=2,
                 static=dict(num_steps=4, prox_solver="gd", prox_steps=3,
                             channel="quant8")).resolve(prob)
    runner = R._fused_runner("svrp", tuple(sorted(rr.cfg.items())), None)
    txt = runner.lower(prob, rr.x0, rr.x_star, rr.keys(), rr.device_hparams()).compile().as_text()
    want = {"sample", "grad", "prox", "channel", "refresh", "accounting"}
    assert scopes_in(txt) == want
    # The gather that feeds the local solve is the prox's own work.
    assert re.search(r'gather\([^\n]*op_name="[^"]*/prox/', txt)


_SHARDED = r"""
import re
import jax
import jax.numpy as jnp
from repro.experiments import runner as R
from repro.experiments.spec import RunSpec
from repro.problems import make_synthetic_quadratic
from repro.problems.client_shard import pad_clients

assert len(jax.devices()) == 4, jax.devices()
prob = make_synthetic_quadratic(num_clients=6, dim=5, mu=1.0, L=20.0, delta=2.0, seed=2)
M = prob.num_clients
padded = pad_clients(prob, M + (-M) % 4)
valid = jnp.arange(padded.num_clients) < M
spec = RunSpec("deep_svrp", grid={"eta": 0.1, "local_lr": 0.05, "anchor_prob": 0.3}, seeds=2,
               static=dict(num_steps=3, local_steps=2, channel="quant8"))
rr = spec.resolve(prob)
body = R._client_body("deep_svrp", tuple(sorted(rr.cfg.items())), M, True, None)
runner = R._client_runner(body, tuple(jax.devices()), jax.tree.structure(padded))
txt = runner.lower(padded, valid, rr.x0, rr.x_star, jax.random.key_data(rr.keys()),
                   rr.device_hparams()).compile().as_text()
paths = re.findall(r'op_name="([^"]*)"', txt)
print("SCOPES", sorted({c for p in paths for c in p.split("/")[:-1]}))
ar = [p for p in re.findall(r'all-reduce(?:-start)?\([^\n]*op_name="([^"]*)"', txt)]
print("ALLREDUCE", ar)
"""


def test_client_sharded_hlo_carries_psum_and_client_mean():
    env = dict(os.environ, XLA_FLAGS="--xla_force_host_platform_device_count=4", PYTHONPATH="src")
    r = subprocess.run([sys.executable, "-c", _SHARDED], capture_output=True, text=True,
                       env=env, cwd=ROOT, timeout=300)
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-3000:]
    line = next(ln for ln in r.stdout.splitlines() if ln.startswith("SCOPES"))
    found = set(eval(line[len("SCOPES "):])) & set(spans.SCOPES)
    assert found == set(spans.SCOPES)
    allreduce = eval(next(ln for ln in r.stdout.splitlines() if ln.startswith("ALLREDUCE"))[10:])
    # Every collective is the substrate's `psum`, wherever the round calls it.
    assert allreduce and all("/psum/" in p for p in allreduce), allreduce


# ------------------------------------------------------------ host spans
def _program_spans(trace_dir) -> list:
    """`(name, start, end, ids)` of every `repro:` event in the newest trace."""
    from pathlib import Path

    from jax.profiler import ProfileData

    f = max(Path(trace_dir).rglob("*.xplane.pb"), key=lambda p: p.stat().st_mtime)
    out = []
    for plane in ProfileData.from_file(str(f)).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(spans.PREFIX):
                    out.append((e.name[len(spans.PREFIX):], e.start_ns,
                                e.start_ns + e.duration_ns, dict(e.stats)))
    return out


def _inside(child, parent) -> bool:
    return parent[1] <= child[1] and child[2] <= parent[2]


@pytest.fixture(scope="module")
def quad():
    return make_synthetic_quadratic(num_clients=6, dim=5, mu=1.0, L=20.0, delta=2.0, seed=0)


def test_run_batch_spans_nest_and_carry_the_call(quad, tmp_path):
    kw = dict(grid={"eta": [0.05, 0.02], "p": 0.3}, seeds=2, num_steps=6)
    run_batch("svrp", quad, **kw)  # compile outside the trace
    with jax.profiler.trace(str(tmp_path)):
        for _ in range(2):
            run_batch("svrp", quad, **kw)
    got = _program_spans(tmp_path)
    outer = [s for s in got if s[0] == "run_batch"]
    assert len(outer) == 2
    calls = [s[3]["call"] for s in outer]
    assert calls[0] != calls[1]
    for s in outer:
        assert s[3]["trials"] == 4 and s[3]["rounds"] == 6
        kids = [k for k in got if k[0].startswith("run_batch.") and k[3].get("call") == s[3]["call"]]
        assert sorted(k[0] for k in kids) == ["run_batch.dispatch", "run_batch.prepare",
                                              "run_batch.wait"]
        assert all(_inside(k, s) for k in kids)
        order = sorted(kids, key=lambda k: k[1])
        assert [k[0] for k in order] == ["run_batch.prepare", "run_batch.dispatch",
                                         "run_batch.wait"]


def test_stop_eps_run_batch_holds_its_session_steps(quad, tmp_path):
    kw = dict(grid={"eta": 0.05, "p": 0.3}, seeds=2, num_steps=8, stop_eps=1e-30)
    run_batch("svrp", quad, **kw)
    with jax.profiler.trace(str(tmp_path)):
        res = run_batch("svrp", quad, **kw)
    got = _program_spans(tmp_path)
    (outer,) = [s for s in got if s[0] == "run_batch"]
    assert outer[3]["rounds"] == res.dist_sq.shape[1] == 8
    steps = [s for s in got if s[0] == "session.step"]
    assert steps and all(_inside(s, outer) for s in steps)


def test_pool_spans_carry_the_tick(quad, tmp_path):
    spec = RunSpec("svrp", grid={"eta": 0.05, "p": 0.3}, seeds=2, static=dict(num_steps=6))
    pool = SessionPool(capacity=2)
    a = pool.admit(spec, quad)
    pool.step(1)  # compile outside the trace
    with jax.profiler.trace(str(tmp_path)):
        b = pool.admit(spec, quad)
        pool.step(1)
        pool.step(1)
        pool.evict(a)
    got = _program_spans(tmp_path)
    names = [s[0] for s in got]
    assert names.count("pool.admit") == 1 and names.count("pool.evict") == 1
    steps = sorted((s for s in got if s[0] == "pool.step"), key=lambda s: s[1])
    assert [s[3]["tick"] for s in steps] == [1, 2]
    for s in steps:
        kids = [k for k in got if k[0] in ("pool.step.dispatch", "pool.step.drain")
                and k[3]["tick"] == s[3]["tick"]]
        assert sorted(k[0] for k in kids) == ["pool.step.dispatch", "pool.step.drain"]
        assert all(_inside(k, s) for k in kids)
    assert b != a and pool.num_resident == 1


def test_session_step_span(quad, tmp_path):
    sess = open_session(RunSpec("svrp", grid={"eta": 0.05, "p": 0.3}, seeds=2,
                                substrate="batched", static=dict(num_steps=6)), quad)
    sess.step(2)
    with jax.profiler.trace(str(tmp_path)):
        sess.step(3)
    (s,) = [s for s in _program_spans(tmp_path) if s[0] == "session.step"]
    assert s[3] == {"t": 2, "n": 3}


def test_span_cost_without_a_profiler():
    """With no profiler session an annotation is about a microsecond; the
    bound here only catches a span that does real work when nothing listens."""
    import timeit

    def one():
        with spans.span("run_batch.prepare", call=1):
            pass

    per = min(timeit.repeat(one, number=2000, repeat=5)) / 2000
    assert per < 50e-6, per
