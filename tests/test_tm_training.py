"""TM training semantics (Type I/II feedback) + convergence."""

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import feedback, tm, train
from repro.data import make_noisy_xor
from repro.kernels import ops, ref


def test_type2_only_increments_excluded_zero_literals():
    cfg = tm.TMConfig(n_features=3, n_classes=2, clauses_per_class=2, s=10.0)
    ta = jnp.asarray(np.array([[-3, -3, 5, -3, -3, -3]] * 4, np.int8))
    lits = jnp.asarray(np.array([[1, 0, 1, 0, 1, 0]], np.uint8))
    fire = jnp.ones((1, 4), jnp.uint8)
    ftype = jnp.full((1, 4), 2, jnp.uint8)          # all Type II
    d = np.asarray(
        ref.ta_delta_ref(ta, lits, fire, ftype, jnp.uint32(0), p_act=1.0,
                         p_inact=0.1)
    )
    assert (d >= 0).all()
    # literal=1 positions and included positions unchanged
    assert d[0, 0] == 0 and d[0, 2] == 0 and d[0, 4] == 0
    # literal=0, excluded positions incremented deterministically
    assert d[0, 1] == 1 and d[0, 3] == 1 and d[0, 5] == 1


def test_type1_rewards_matching_literals():
    cfg = tm.TMConfig(n_features=2, n_classes=2, clauses_per_class=2, s=1e9,
                      boost_true_positive=True)
    ta = jnp.zeros((4, 4), jnp.int8)
    lits = jnp.asarray(np.array([[1, 1, 0, 0]], np.uint8))
    fire = jnp.ones((1, 4), jnp.uint8)
    ftype = jnp.full((1, 4), 1, jnp.uint8)
    d = np.asarray(
        ref.ta_delta_ref(ta, lits, fire, ftype, jnp.uint32(3), p_act=1.0,
                         p_inact=0.0)
    )
    np.testing.assert_array_equal(d, np.tile([1, 1, 0, 0], (4, 1)))


def test_states_clamped():
    cfg = tm.TMConfig(n_features=2, n_classes=2, clauses_per_class=2, n_states=128)
    ta = jnp.full((4, 4), 127, jnp.int8)
    new = feedback.apply_delta(cfg, ta, jnp.full((4, 4), 100, jnp.int32))
    assert int(np.asarray(new).max()) == 127
    new = feedback.apply_delta(cfg, jnp.full((4, 4), -128, jnp.int8),
                               jnp.full((4, 4), -100, jnp.int32))
    assert int(np.asarray(new).min()) == -128


def test_padded_clauses_stay_empty():
    cfg = tm.TMConfig(n_features=4, n_classes=3, clauses_per_class=3,
                      clause_pad_multiple=8)
    assert cfg.n_clauses_total == 16 and cfg.n_clauses_raw == 9
    st = tm.init(cfg, jax.random.PRNGKey(0))
    x = jnp.asarray(np.random.default_rng(0).integers(0, 2, (16, 4), dtype=np.uint8))
    y = jnp.asarray(np.random.default_rng(1).integers(0, 3, 16, dtype=np.int32))
    st2, _ = train.train_step(cfg, st, x, y, jax.random.PRNGKey(2))
    pad = np.asarray(st2.ta_state)[9:]
    assert (pad < 0).all(), "padded clauses must remain all-exclude"
    assert np.asarray(tm.polarity(cfg))[9:].sum() == 0


def test_xor_convergence_jnp_path():
    X, y = make_noisy_xor(3000, noise=0.05, seed=0)
    Xte, yte = make_noisy_xor(500, noise=0.0, seed=1)
    cfg = tm.TMConfig(n_features=12, n_classes=2, clauses_per_class=20,
                      threshold=15, s=3.9)
    st = tm.init(cfg, jax.random.PRNGKey(0))
    st = train.fit(cfg, st, jnp.asarray(X), jnp.asarray(y), epochs=12,
                   batch_size=50, rng=jax.random.PRNGKey(1))
    acc = float(tm.accuracy(cfg, st, jnp.asarray(Xte), jnp.asarray(yte)))
    assert acc > 0.85, acc


def test_xor_convergence_kernel_path():
    X, y = make_noisy_xor(3000, noise=0.05, seed=2)
    Xte, yte = make_noisy_xor(500, noise=0.0, seed=3)
    cfg = tm.TMConfig(n_features=12, n_classes=2, clauses_per_class=20,
                      threshold=15, s=3.9)
    ta = tm.init(cfg, jax.random.PRNGKey(0)).ta_state
    rng = np.random.default_rng(0)
    for ep in range(12):
        perm = rng.permutation(3000)
        for i in range(3000 // 50):
            idx = perm[i * 50 : (i + 1) * 50]
            ta, _ = ops.tm_train_step_kernel(
                cfg, ta, jnp.asarray(X[idx]), jnp.asarray(y[idx]),
                jnp.uint32(ep * 1000 + i),
            )
    st = tm.TMState(ta_state=ta, steps=jnp.int32(0))
    acc = float(tm.accuracy(cfg, st, jnp.asarray(Xte), jnp.asarray(yte)))
    assert acc > 0.85, acc


def test_trained_model_is_sparse():
    """The paper's central empirical claim: trained TMs are include-sparse."""
    X, y = make_noisy_xor(2000, noise=0.05, seed=4)
    cfg = tm.TMConfig(n_features=12, n_classes=2, clauses_per_class=20,
                      threshold=15, s=3.9)
    st = tm.init(cfg, jax.random.PRNGKey(0))
    st = train.fit(cfg, st, jnp.asarray(X), jnp.asarray(y), epochs=8,
                   batch_size=50, rng=jax.random.PRNGKey(1))
    include_frac = float((np.asarray(st.ta_state) >= 0).mean())
    assert include_frac < 0.35, include_frac
