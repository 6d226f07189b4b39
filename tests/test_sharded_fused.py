"""Clause-sharded fused TM paths vs the single-device ref.py oracle.

The PR 3 invariant: ``core/sharding.py``'s explicit ``shard_map`` schedules
(fused Pallas pipeline per ``model`` shard + one int32 class-sum psum) are
BIT-identical to the single-device oracle — exact TA-state and class-sum
equality on an emulated multi-device mesh, for every engine and mesh shape.

Subprocess pattern (like test_sharding.py): each test forces its own host
device count via XLA_FLAGS before jax init, so the main pytest process
keeps its single-device view.
"""

import os
import subprocess
import sys

import pytest

pytestmark = pytest.mark.multidevice

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ENV = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"),
           JAX_PLATFORMS="cpu")


def _run(code: str, timeout=600):
    return subprocess.run(
        [sys.executable, "-c", code], env=ENV, capture_output=True,
        text=True, timeout=timeout, cwd=REPO,
    )


_PRELUDE = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, jax.numpy as jnp, numpy as np
from repro.core import tm, sharding, packetizer
from repro.kernels import ops, ref

cfg = tm.TMConfig(n_features=32, n_classes=4, clauses_per_class=16,
                  clause_pad_multiple=8, threshold=15, s=5.0)
state = tm.init(cfg, jax.random.PRNGKey(0))
rng = np.random.default_rng(0)
X = jnp.asarray(rng.integers(0, 2, (24, 32), dtype=np.uint8))
y = jnp.asarray(rng.integers(0, 4, 24, dtype=np.int32))
seed = jnp.uint32(5)
"""


def test_clause_sharded_fused_train_bit_identical():
    """The tentpole acceptance test: the clause-sharded fused train step on
    an emulated 4-device mesh reproduces the single-device ``ref.py``
    oracle's TA state EXACTLY (int8 equality, every automaton), on both a
    pure-model mesh and a (data x model) mesh, fused kernel and oracle
    engines, including batch-chunked ragged tails."""
    r = _run(_PRELUDE + """
ta_ref, _ = ops.tm_train_step_kernel(cfg, state.ta_state, X, y, seed,
                                     use_kernel=False)
for shape, axes in (((4,), ("model",)), ((2, 2), ("data", "model"))):
    mesh = jax.make_mesh(shape, axes)
    for kw in (dict(use_kernel=True, interpret=True),       # fused Pallas
               dict(use_kernel=False,)):                    # oracle engine
        step = sharding.sharded_train_step_fn(cfg, mesh, engine="kernel", **kw)
        ta_sh = np.asarray(step(state.ta_state, X, y, seed))
        np.testing.assert_array_equal(np.asarray(ta_ref), ta_sh)
# chunked with ragged tail (24 local = 12/shard, chunk 5 -> 2 full + tail 2)
mesh = jax.make_mesh((2, 2), ("data", "model"))
step = sharding.sharded_train_step_fn(cfg, mesh, batch_chunk=5,
                                      engine="kernel", use_kernel=True,
                                      interpret=True)
np.testing.assert_array_equal(
    np.asarray(ta_ref), np.asarray(step(state.ta_state, X, y, seed)))
print("SHARDED_TRAIN_BITEXACT_OK")
""")
    assert "SHARDED_TRAIN_BITEXACT_OK" in r.stdout, r.stdout + r.stderr


def test_clause_sharded_fused_forward_sums_exact():
    """Class sums from the clause-sharded fused inference kernel (partial
    per-shard adder banks + psum) equal the oracle's int32 sums exactly,
    and the sharded predict fn matches tm.predict."""
    r = _run(_PRELUDE + """
iw = packetizer.pack_include_masks(state.ta_state)
votes = tm.vote_matrix(cfg)
ne = jnp.any(state.ta_state >= 0, -1).astype(jnp.uint8)
lw = packetizer.pack_bits(tm.literals(X))
sums_ref = (ref.clause_fire_ref(lw, iw).astype(jnp.int32)
            * ne[None, :].astype(jnp.int32)) @ votes
mesh = jax.make_mesh((2, 2), ("data", "model"))
fwd = sharding.sharded_forward_fn(mesh, use_kernel=True, interpret=True)
np.testing.assert_array_equal(np.asarray(sums_ref),
                              np.asarray(fwd(iw, votes, ne, lw)))
pred = sharding.sharded_predict_fn(cfg, mesh, use_kernel=True, interpret=True)
np.testing.assert_array_equal(
    np.asarray(tm.predict(cfg, state, X)),
    np.asarray(pred(iw, votes, ne, lw)))
print("SHARDED_FORWARD_OK")
""")
    assert "SHARDED_FORWARD_OK" in r.stdout, r.stdout + r.stderr


def test_fit_on_mesh_matches_single_device():
    """train.fit(engine='kernel', mesh=...) is a pure layout change: same
    shuffle stream, same seeds, bit-identical final automata."""
    r = _run("""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, jax.numpy as jnp, numpy as np
from repro.core import tm, train
from repro.data import make_noisy_xor

X, y = make_noisy_xor(64, noise=0.05, seed=3)
cfg = tm.TMConfig(n_features=12, n_classes=2, clauses_per_class=8,
                  clause_pad_multiple=4)
st0 = tm.init(cfg, jax.random.PRNGKey(0))
ta0 = np.asarray(st0.ta_state)
st_a = train.fit(cfg, st0, jnp.asarray(X), jnp.asarray(y), epochs=2,
                 batch_size=16, rng=jax.random.PRNGKey(7), engine="kernel")
st0b = tm.TMState(ta_state=jnp.asarray(ta0), steps=jnp.zeros((), jnp.int32))
mesh = jax.make_mesh((2, 2), ("data", "model"))
st_b = train.fit(cfg, st0b, jnp.asarray(X), jnp.asarray(y), epochs=2,
                 batch_size=16, rng=jax.random.PRNGKey(7), engine="kernel",
                 mesh=mesh)
np.testing.assert_array_equal(np.asarray(st_a.ta_state),
                              np.asarray(st_b.ta_state))
print("FIT_MESH_OK")
""")
    assert "FIT_MESH_OK" in r.stdout, r.stdout + r.stderr


def test_launch_train_and_serve_mesh_wiring():
    """`--mesh model=2` end-to-end through the launchers (tiny runs)."""
    env = dict(ENV, XLA_FLAGS="--xla_force_host_platform_device_count=2")
    r = subprocess.run(
        [sys.executable, "-m", "repro.launch.train", "--arch", "tm-mnist",
         "--steps", "2", "--batch-size", "32", "--n-train", "128",
         "--mesh", "model=2", "--log-every", "10"],
        env=env, capture_output=True, text=True, timeout=600, cwd=REPO,
    )
    assert r.returncode == 0, r.stdout + r.stderr
    assert "clause axis sharded over model=2" in r.stdout, r.stdout + r.stderr
    r = subprocess.run(
        [sys.executable, "-m", "repro.launch.serve", "--arch", "tm-mnist",
         "--requests", "64", "--bucket", "32", "--epochs", "1",
         "--n-train", "128", "--mesh", "model=2"],
        env=env, capture_output=True, text=True, timeout=600, cwd=REPO,
    )
    assert "clause-sharded" in r.stdout, r.stdout + r.stderr
    assert "inf/s" in r.stdout, r.stdout + r.stderr


def test_parse_mesh_spec_validation():
    """Spec parsing + a clear too-few-devices error (single-device proc)."""
    from repro.launch.mesh import parse_mesh_spec

    m = parse_mesh_spec("model=1")
    assert tuple(m.axis_names) == ("model",)
    with pytest.raises(ValueError, match="device_count"):
        parse_mesh_spec("model=64")
    with pytest.raises(ValueError, match="bad --mesh spec"):
        parse_mesh_spec("modl=2")
