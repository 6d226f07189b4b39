"""Multi-device sharding tests (subprocess: forces 8 host devices so the
main pytest process keeps its single-device view)."""

import json
import os
import subprocess
import sys

import pytest

pytestmark = pytest.mark.multidevice

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ENV = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"),
           REPRO_DRYRUN_DEVICES="8", JAX_PLATFORMS="cpu")


def _run(code: str, timeout=600):
    return subprocess.run(
        [sys.executable, "-c", code], env=ENV, capture_output=True,
        text=True, timeout=timeout,
    )


def test_tm_sharded_matches_unsharded():
    r = _run("""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from repro.core import tm, packetizer, sharding
from repro.kernels import ops

cfg = tm.TMConfig(n_features=32, n_classes=4, clauses_per_class=16,
                  clause_pad_multiple=8)
state = tm.init(cfg, jax.random.PRNGKey(0))
mesh = jax.make_mesh((2, 4), ("data", "model"))
X = np.random.default_rng(0).integers(0, 2, (16, 32)).astype(np.uint8)

pred_ref = np.asarray(tm.predict(cfg, state, jnp.asarray(X)))
fn = sharding.sharded_predict_fn(cfg, mesh)
inc = packetizer.pack_include_masks(state.ta_state)
votes = tm.vote_matrix(cfg)
nonempty = jnp.any(state.ta_state >= 0, -1).astype(jnp.uint8)
lits = packetizer.pack_bits(tm.literals(jnp.asarray(X)))
pred_sh = np.asarray(fn(inc, votes, nonempty, lits))
np.testing.assert_array_equal(pred_ref, pred_sh)

# sharded train step == single-device kernel-path step (same hash RNG)
y = np.random.default_rng(1).integers(0, 4, 16).astype(np.int32)
ta_ref, _ = ops.tm_train_step_kernel(cfg, state.ta_state, jnp.asarray(X),
                                     jnp.asarray(y), jnp.uint32(5))
step = sharding.sharded_train_step_fn(cfg, mesh)
ta_sh = step(state.ta_state, jnp.asarray(X), jnp.asarray(y), jnp.uint32(5))
np.testing.assert_array_equal(np.asarray(ta_ref), np.asarray(ta_sh))
print("TM_SHARDED_OK")
""")
    assert "TM_SHARDED_OK" in r.stdout, r.stdout + r.stderr


def test_lm_sharded_loss_matches_unsharded():
    r = _run("""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from repro.configs import get_smoke_config
from repro.models import transformer, sharding as shd
from repro.models.transformer import RunCtx

cfg = get_smoke_config("tinyllama-1.1b")
params = transformer.init_params(cfg, jax.random.PRNGKey(0))
B, S = 4, 32
rng = np.random.default_rng(0)
batch = {"tokens": jnp.asarray(rng.integers(0, cfg.vocab_size, (B, S)), jnp.int32),
         "labels": jnp.asarray(rng.integers(0, cfg.vocab_size, (B, S)), jnp.int32)}
loss_1dev = float(transformer.loss_fn(cfg, params, batch, remat=False))

mesh = jax.make_mesh((2, 4), ("data", "model"))
ctx = RunCtx(mesh=mesh)
p_specs = shd.param_specs(cfg, params, mesh, train=True)
p_sh = jax.device_put(params, shd.to_named(p_specs, mesh))
b_specs = shd.batch_specs(cfg, batch, mesh)
b_sh = jax.device_put(batch, shd.to_named(b_specs, mesh))
loss_sh = float(jax.jit(lambda p, b: transformer.loss_fn(cfg, p, b, ctx=ctx, remat=False))(p_sh, b_sh))
assert abs(loss_1dev - loss_sh) < 2e-2, (loss_1dev, loss_sh)
print("LM_SHARDED_OK", loss_1dev, loss_sh)
""")
    assert "LM_SHARDED_OK" in r.stdout, r.stdout + r.stderr


def test_moe_shard_map_matches_local():
    r = _run("""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from repro.configs import get_smoke_config
from repro.models import moe

import dataclasses
cfg = get_smoke_config("qwen3-moe-235b-a22b")
# capacity high enough that no tokens drop -> paths must agree exactly
cfg = dataclasses.replace(cfg, capacity_factor=100.0)
params = moe.init_moe(jax.random.PRNGKey(0), cfg, jnp.float32)
x = jnp.asarray(np.random.default_rng(0).normal(size=(2, 16, cfg.d_model)), jnp.float32)
out_local = moe.moe_ff(cfg, params, x, mesh=None)
mesh = jax.make_mesh((2, 4), ("data", "model"))
out_sh = jax.jit(lambda p, xx: moe.moe_ff(cfg, p, xx, mesh=mesh, dp_axes=("data",)))(params, x)
err = float(jnp.abs(out_local - out_sh).max())
scale = float(jnp.abs(out_local).max())
assert err < 1e-3 * scale + 1e-5, (err, scale)
print("MOE_OK", err, scale)
""")
    assert "MOE_OK" in r.stdout, r.stdout + r.stderr


def test_compressed_allreduce_multidevice():
    r = _run("""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.optim import compress

mesh = jax.make_mesh((8,), ("data",))
g_all = jnp.asarray(np.random.default_rng(0).normal(size=(8, 64)), jnp.float32)

def f(g, e):
    out, ne = compress.quantize_psum(g[0], e[0], "data")
    return out[None], ne[None]

out, err = jax.jit(jax.shard_map(f, mesh=mesh, in_specs=(P("data"), P("data")),
                   out_specs=(P("data"), P("data")), check_vma=False))(
    g_all, jnp.zeros_like(g_all))
exact = np.asarray(g_all).mean(0)
got = np.asarray(out)[0]
scale = np.abs(np.asarray(g_all)).max() / 127.0
assert np.abs(got - exact).max() < scale + 1e-5, np.abs(got - exact).max()
print("COMPRESS_OK")
""")
    assert "COMPRESS_OK" in r.stdout, r.stdout + r.stderr


@pytest.mark.slow
def test_dryrun_smoke_cells():
    """The dry-run machinery end-to-end on a small mesh with smoke configs."""
    r = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun", "--smoke",
         "--arch", "recurrentgemma-2b", "--shape", "train_4k", "--mesh", "2x4"],
        env=ENV, capture_output=True, text=True, timeout=600,
    )
    assert '"status": "ok"' in r.stdout, r.stdout + r.stderr
