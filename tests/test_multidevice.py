"""Multi-device integration tests.

pytest itself runs on 1 CPU device (the assignment's smoke contract), so
these tests spawn subprocesses with ``--xla_force_host_platform_device_count``
to exercise real GSPMD partitioning + shard_map collectives on 8 host
devices: sharded-vs-single-device numerical equivalence, the shard_map
MoE dispatch, and elastic checkpoint restore across mesh shapes.
"""

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_sub(code: str) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run(
        [sys.executable, "-c", code], env=env, capture_output=True,
        text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


@pytest.mark.slow
def test_sharded_loss_matches_single_device():
    """One train-step loss on a (2,4) mesh == the unsharded loss —
    the distribution layer must not change the math."""
    run_sub("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import AxisType
AUTO2 = (AxisType.Auto,) * 2
from repro.configs import get_smoke_config
from repro.models import lm
from repro.sharding import Rules, tree_specs
from repro.runtime.steps import train_state_specs
from repro.optim import adamw

cfg = get_smoke_config('yi-34b')
key = jax.random.PRNGKey(0)
params = lm.init_params(key, cfg)
tokens = jax.random.randint(key, (8, 64), 0, cfg.vocab_size)
batch = {'tokens': tokens, 'labels': tokens}

loss_ref, _ = jax.jit(
    lambda p, b: lm.lm_loss(p, b, cfg, Rules.null()))(params, batch)

mesh = jax.make_mesh((2, 4), ('data', 'model'), axis_types=AUTO2)
rules = Rules.for_mesh(mesh)
with mesh:
    loss_sh, _ = jax.jit(
        lambda p, b: lm.lm_loss(p, b, cfg, rules))(params, batch)
np.testing.assert_allclose(float(loss_ref), float(loss_sh),
                           rtol=2e-2, atol=2e-2)
print('OK', float(loss_ref), float(loss_sh))
""")


@pytest.mark.slow
def test_shard_map_moe_matches_einsum():
    run_sub("""
import dataclasses
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import AxisType
AUTO2 = (AxisType.Auto,) * 2
from repro.configs import get_smoke_config
from repro.models.moe import moe_params, moe_apply, moe_apply_shard_map
from repro.sharding import Rules

cfg = get_smoke_config('deepseek-moe-16b')
cfg = dataclasses.replace(cfg, moe=dataclasses.replace(
    cfg.moe, capacity_factor=8.0, n_experts=8, top_k=2))
mesh = jax.make_mesh((2, 4), ('data', 'model'), axis_types=AUTO2)
rules = Rules.for_mesh(mesh)
key = jax.random.PRNGKey(0)
p = moe_params(key, cfg, jnp.float32)
x = jax.random.normal(jax.random.fold_in(key, 1), (4, 8, cfg.d_model))
with mesh:
    out_sm, aux_sm = jax.jit(
        lambda p, x: moe_apply_shard_map(p, x, cfg, rules))(p, x)
cfg_e = dataclasses.replace(cfg, moe=dataclasses.replace(
    cfg.moe, dispatch='einsum'))
out_e, aux_e = jax.jit(
    lambda p, x: moe_apply(p, x, cfg_e, Rules.null()))(p, x)
np.testing.assert_allclose(np.asarray(out_sm), np.asarray(out_e),
                           rtol=2e-3, atol=2e-3)
np.testing.assert_allclose(float(aux_sm), float(aux_e), rtol=1e-3)
print('OK')
""")


@pytest.mark.slow
def test_elastic_restore_across_meshes():
    """Save under a (2,4) mesh, restore onto (4,2) and (8,1) — values
    identical (node-failure → re-mesh recovery path)."""
    run_sub("""
import tempfile, os
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import AxisType
AUTO2 = (AxisType.Auto,) * 2
from repro.checkpoint import save_pytree, restore_on_mesh
from repro.sharding import Rules

key = jax.random.PRNGKey(0)
tree = {'w': jax.random.normal(key, (16, 8)),
        'emb': jax.random.normal(jax.random.fold_in(key, 1), (32, 8))}
spec = {'w': ('fsdp', 'ffn'), 'emb': ('vocab', None)}

mesh_a = jax.make_mesh((2, 4), ('data', 'model'), axis_types=AUTO2)
placed = jax.device_put(tree['w'], jax.sharding.NamedSharding(
    mesh_a, jax.sharding.PartitionSpec('data', 'model')))
path = os.path.join(tempfile.mkdtemp(), 'ck')
save_pytree(path, {'w': placed, 'emb': tree['emb']})

for shape in ((4, 2), (8, 1), (1, 8)):
    mesh_b = jax.make_mesh(shape, ('data', 'model'), axis_types=AUTO2)
    restored, _ = restore_on_mesh(path, tree, spec, mesh_b)
    np.testing.assert_array_equal(np.asarray(restored['w']),
                                  np.asarray(tree['w']))
    np.testing.assert_array_equal(np.asarray(restored['emb']),
                                  np.asarray(tree['emb']))
print('OK')
""")


@pytest.mark.slow
def test_decode_sharded_matches_null_rules():
    """Sharded serve_step logits == single-device logits (linear backend
    with padded state heads)."""
    run_sub("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import AxisType
AUTO2 = (AxisType.Auto,) * 2
from repro.configs import get_smoke_config
from repro.models import lm
from repro.sharding import Rules

cfg = get_smoke_config('yi-34b').with_backend('linear')
key = jax.random.PRNGKey(0)
params = lm.init_params(key, cfg)
tok = jnp.zeros((8,), jnp.int32)

st0 = lm.init_decode_state(cfg, 8, max_len=16)
ref, _ = jax.jit(lambda p, s, t: lm.decode_step(
    p, s, t, jnp.int32(0), cfg, Rules.null()))(params, st0, tok)

mesh = jax.make_mesh((2, 4), ('data', 'model'), axis_types=AUTO2)
rules = Rules.for_mesh(mesh, overrides={'fsdp': None})
st1 = lm.init_decode_state(cfg, 8, max_len=16, rules=rules)
with mesh:
    out, _ = jax.jit(lambda p, s, t: lm.decode_step(
        p, s, t, jnp.int32(0), cfg, rules))(params, st1, tok)
np.testing.assert_allclose(np.asarray(ref, np.float32),
                           np.asarray(out, np.float32),
                           rtol=5e-2, atol=5e-2)
print('OK')
""")


@pytest.mark.slow
def test_gpipe_matches_plain_loss():
    """GPipe (stage=2, data=2, model=2) loss + grads == the plain model
    — pipeline parallelism composes with TP/SP without changing math."""
    run_sub("""
import jax, jax.numpy as jnp, numpy as np
from repro.configs import get_smoke_config
from repro.models import lm
from repro.pipeline import gpipe_loss_fn, make_pipeline_mesh
from repro.sharding import Rules

cfg = get_smoke_config('yi-34b')
mesh = make_pipeline_mesh(stages=2, data=2, model=2)
rules = Rules.for_mesh(mesh)
key = jax.random.PRNGKey(0)
params = lm.init_params(key, cfg)
tokens = jax.random.randint(key, (8, 32), 0, cfg.vocab_size)
batch = {'tokens': tokens, 'labels': tokens}
ref, _ = jax.jit(lambda p, b: lm.lm_loss(p, b, cfg, Rules.null()))(params, batch)
loss_fn = gpipe_loss_fn(cfg, rules, mesh, n_micro=4)
with mesh:
    pp = jax.jit(loss_fn)(params, batch)
np.testing.assert_allclose(float(ref), float(pp), rtol=3e-2, atol=3e-2)
with mesh:
    g = jax.jit(jax.grad(lambda p: loss_fn(p, batch)))(params)
for a in jax.tree.leaves(g):
    assert bool(jnp.all(jnp.isfinite(a.astype(jnp.float32))))
print('OK')
""")
