"""Distributed tests on an 8-device host mesh (subprocess-isolated so the
XLA device-count flag never leaks into other tests).

Each test compiles a multi-device program in a fresh subprocess (minutes of
wall-clock total), so the whole module is marked slow: run with --runslow.
"""
import os
import subprocess
import sys

import pytest

pytestmark = pytest.mark.slow

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _run(body: str) -> str:
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               PYTHONPATH=SRC, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, "-c", body], capture_output=True,
                       text=True, env=env, timeout=420)
    assert r.returncode == 0, r.stdout + "\n" + r.stderr
    return r.stdout


def test_sharded_train_step_matches_single_device():
    out = _run("""
import jax, jax.numpy as jnp, numpy as np
from repro.models import lm
from repro.core.api import QuantConfig
from repro.optim import OptConfig, init_opt_state, opt_update
from repro.distributed.sharding import (Rules, use_rules, param_specs,
    filter_mesh_axes, enforce_divisible, named_shardings, batch_specs)
from repro.launch.mesh import make_test_mesh

cfg = lm.LMConfig(name='t', n_layers=2, d_model=32, n_heads=4, kv_heads=2,
                  d_ff=64, vocab=64, dtype='float32', q_chunk=16, remat=False)
params = lm.init_params(jax.random.PRNGKey(0), cfg)
batch = {'tokens': jax.random.randint(jax.random.PRNGKey(1), (8, 16), 0, 64),
         'labels': jax.random.randint(jax.random.PRNGKey(1), (8, 16), 0, 64)}
loss_single = jax.jit(lambda p, b: lm.lm_loss(p, b, cfg)[0])(params, batch)

mesh = make_test_mesh((2, 4), ('data', 'model'))
pspecs = enforce_divisible(filter_mesh_axes(param_specs(params), mesh),
                           params, mesh)
bspecs = batch_specs(batch, ('data',))
with mesh, use_rules(Rules(batch=('data',))):
    f = jax.jit(lambda p, b: lm.lm_loss(p, b, cfg)[0],
                in_shardings=(named_shardings(pspecs, mesh),
                              named_shardings(bspecs, mesh)))
    loss_sharded = f(params, batch)
np.testing.assert_allclose(float(loss_single), float(loss_sharded),
                           rtol=2e-5)
print('OK', float(loss_single), float(loss_sharded))
""")
    assert "OK" in out


def test_compressed_allreduce_error_feedback():
    out = _run("""
import jax, jax.numpy as jnp, numpy as np
from jax.experimental.shard_map import shard_map
from jax.sharding import PartitionSpec as P
from repro.distributed.compression import compress_psum, init_error_buffer
from repro.launch.mesh import make_test_mesh

mesh = make_test_mesh((8,), ('data',))
g = jax.random.normal(jax.random.PRNGKey(0), (8, 64))   # per-shard grads
grads = {'w': g}
err = {'w': jnp.zeros((8, 64))}

def f(gs, es):
    out, new_e = compress_psum({'w': gs['w'][0]}, {'w': es['w'][0]},
                               ('data',), bits=8)
    return {'w': out['w'][None]}, {'w': new_e['w'][None]}

fm = shard_map(f, mesh=mesh, in_specs=(P('data'), P('data')),
               out_specs=(P('data'), P('data')), check_rep=False)
mean_q, new_err = fm(grads, err)
true_mean = jnp.mean(g, axis=0)
err0 = float(jnp.max(jnp.abs(mean_q['w'][0] - true_mean)))
# int8 grid error bound: amax/127 (sum of per-shard quant errors averaged)
bound = float(jnp.max(jnp.abs(g)) / 127)
assert err0 <= bound * 1.5, (err0, bound)
# error feedback: residuals nonzero and bounded by one grid step
assert float(jnp.max(jnp.abs(new_err['w']))) <= bound * 1.01
print('OK', err0, bound)
""")
    assert "OK" in out


def test_pipeline_matches_sequential():
    out = _run("""
import jax, jax.numpy as jnp, numpy as np
from repro.distributed.pipeline import pipeline_forward
from repro.launch.mesh import make_test_mesh

mesh = make_test_mesh((4,), ('stage',))
n_stages, n_micro, mb, d = 4, 8, 2, 16
ws = jax.random.normal(jax.random.PRNGKey(0), (n_stages, d, d)) * 0.3

def stage_fn(w, x):
    return jnp.tanh(x @ w)

xs = jax.random.normal(jax.random.PRNGKey(1), (n_micro, mb, d))
out = pipeline_forward(stage_fn, ws, xs, mesh, axis='stage')

ref = xs
for s in range(n_stages):
    ref = jnp.tanh(ref @ ws[s])
np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-4,
                           atol=2e-5)
print('OK')
""")
    assert "OK" in out


def test_elastic_remesh_roundtrip():
    out = _run("""
import jax, jax.numpy as jnp, numpy as np
from repro.distributed.elastic import best_mesh, reshard_to
from repro.models import lm

cfg = lm.LMConfig(name='t', n_layers=2, d_model=32, n_heads=4, kv_heads=2,
                  d_ff=64, vocab=64, dtype='float32', remat=False)
params = lm.init_params(jax.random.PRNGKey(0), cfg)
m8 = best_mesh(8)
assert m8.devices.size == 8
p8 = reshard_to(params, m8)
# simulate losing 2 devices -> re-carve to 6
m6 = best_mesh(6)
assert m6.devices.size == 6
p6 = reshard_to(jax.device_get(p8), m6)
for a, b in zip(jax.tree_util.tree_leaves(params),
                jax.tree_util.tree_leaves(p6)):
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
print('OK')
""")
    assert "OK" in out


def test_dryrun_cell_multipod_smoke():
    """End-to-end dry-run machinery on a small mesh inside the subprocess:
    proves lower+compile+analysis runs for a multi-axis mesh."""
    out = _run("""
import jax
from repro.launch import hlo_analysis
from repro.models import lm
from repro.core.api import QuantConfig
from repro.distributed.sharding import (Rules, use_rules, param_specs,
    filter_mesh_axes, enforce_divisible, named_shardings, batch_specs)
from repro.launch.mesh import make_test_mesh

cfg = lm.LMConfig(name='t', n_layers=2, d_model=64, n_heads=4, kv_heads=2,
                  d_ff=128, vocab=128, dtype='float32', remat=False,
                  quant=QuantConfig(mode='fake'))
mesh = make_test_mesh((2, 2, 2), ('pod', 'data', 'model'))
key = jax.random.PRNGKey(0)
params_abs = jax.eval_shape(lambda k: lm.init_params(k, cfg), key)
batch_abs = {'tokens': jax.ShapeDtypeStruct((8, 32), 'int32'),
             'labels': jax.ShapeDtypeStruct((8, 32), 'int32')}
pspecs = enforce_divisible(filter_mesh_axes(param_specs(params_abs), mesh),
                           params_abs, mesh)
bspecs = batch_specs(batch_abs, ('pod', 'data'))
with mesh, use_rules(Rules()):
    j = jax.jit(lambda p, b: lm.lm_loss(p, b, cfg)[0],
                in_shardings=(named_shardings(pspecs, mesh),
                              named_shardings(bspecs, mesh)))
    lowered = j.lower(params_abs, batch_abs)
    compiled = lowered.compile()
    cb = hlo_analysis.collective_bytes(compiled.as_text())
    cost = hlo_analysis.cost_dict(compiled)
assert cost.get('flops', 0) > 0
assert sum(cb.values()) > 0   # TP+DP must produce collectives
print('OK', cb)
""")
    assert "OK" in out


def test_moe_a2a_matches_dense_dispatch():
    out = _run("""
import jax, jax.numpy as jnp, numpy as np
from repro.layers.moe import MoEConfig, moe_ffn, init_moe
from repro.distributed.sharding import Rules, use_rules
mesh = jax.make_mesh((2, 4), ('data', 'model'))
mcfg = MoEConfig(n_experts=8, top_k=2, capacity_factor=8.0)
p = init_moe(jax.random.PRNGKey(0), 32, 64, mcfg, dtype=jnp.float32)
x = jax.random.normal(jax.random.PRNGKey(1), (64, 32))
y_ref, _ = moe_ffn(x, p, mcfg, None)
rules = Rules(batch=('data',), mesh=mesh, moe_a2a=True)
with mesh, use_rules(rules):
    y_a2a, _ = jax.jit(lambda x, p: moe_ffn(x, p, mcfg, None))(x, p)
np.testing.assert_allclose(np.asarray(y_ref), np.asarray(y_a2a), atol=2e-5)
# gradients flow through the explicit a2a
with mesh, use_rules(rules):
    g = jax.grad(lambda p: jnp.sum(moe_ffn(x, p, mcfg, None)[0] ** 2))(p)
assert all(bool(jnp.all(jnp.isfinite(l))) for l in jax.tree_util.tree_leaves(g))
print('OK')
""")
    assert "OK" in out
