"""Boolean-to-silicon compiler: equivalence + compaction properties.

``hypothesis`` is optional: when installed, the two central properties run
as real property tests; otherwise fixed-seed parametrized fallbacks keep
the same checks in the tier-1 suite.
"""

import os
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:  # tier-1 container has no hypothesis
    HAVE_HYPOTHESIS = False

from repro.core import compiler, packetizer, tm


def _random_tm(n_features, n_classes, cpc, include_density, seed):
    rng = np.random.default_rng(seed)
    C = n_classes * cpc
    ta = np.where(
        rng.random((C, 2 * n_features)) < include_density,
        rng.integers(0, 127, (C, 2 * n_features)),
        rng.integers(-128, 0, (C, 2 * n_features)),
    ).astype(np.int8)
    cfg = tm.TMConfig(n_features=n_features, n_classes=n_classes, clauses_per_class=cpc)
    return cfg, ta


def _check_compiled_equals_dense(n_features, n_classes, cpc, density, seed):
    """The central correctness property: the compacted artifact classifies
    identically to dense inference, for any automata state."""
    cfg, ta = _random_tm(n_features, n_classes, cpc, density, seed)
    comp = compiler.compile_tm(cfg, ta)
    x = jnp.asarray(
        np.random.default_rng(seed + 1).integers(0, 2, (16, n_features), dtype=np.uint8)
    )
    state = tm.TMState(ta_state=jnp.asarray(ta), steps=jnp.int32(0))
    dense_sums = tm.class_sums(cfg, state.ta_state, tm.literals(x), training=False)
    comp_sums = compiler.run_compiled(comp, packetizer.pack_literals(x))
    np.testing.assert_array_equal(np.asarray(dense_sums), np.asarray(comp_sums))


def _check_dont_touch_equals_optimized(seed):
    """Fig. 8 analog: disabling the optimizations changes resources, never
    results."""
    cfg, ta = _random_tm(40, 3, 8, 0.1, seed)
    x = jnp.asarray(np.random.default_rng(seed).integers(0, 2, (8, 40), dtype=np.uint8))
    xp = packetizer.pack_literals(x)
    opt = compiler.compile_tm(cfg, ta)
    dt = compiler.compile_tm(cfg, ta, dedup=False, prune_words=False)
    np.testing.assert_array_equal(
        np.asarray(compiler.run_compiled(opt, xp)),
        np.asarray(compiler.run_compiled(dt, xp)),
    )
    assert opt.n_unique <= dt.n_unique
    assert opt.n_words_active <= dt.n_words_active


if HAVE_HYPOTHESIS:
    @pytest.mark.hypothesis_optional
    @settings(max_examples=20, deadline=None)
    @given(
        n_features=st.integers(3, 80),
        n_classes=st.integers(2, 5),
        cpc=st.integers(2, 12),
        density=st.floats(0.0, 0.3),
        seed=st.integers(0, 10_000),
    )
    def test_compiled_equals_dense(n_features, n_classes, cpc, density, seed):
        _check_compiled_equals_dense(n_features, n_classes, cpc, density, seed)

    @pytest.mark.hypothesis_optional
    @settings(max_examples=20, deadline=None)
    @given(seed=st.integers(0, 1000))
    def test_dont_touch_equals_optimized(seed):
        _check_dont_touch_equals_optimized(seed)


@pytest.mark.parametrize(
    "n_features,n_classes,cpc,density,seed",
    [
        (3, 2, 2, 0.0, 0),         # tiny + all-empty bank
        (17, 3, 5, 0.05, 11),      # sparse ragged
        (80, 5, 12, 0.3, 4242),    # dense upper corner
        (33, 2, 7, 0.15, 977),
    ],
)
def test_compiled_equals_dense_fixed(n_features, n_classes, cpc, density, seed):
    """Fixed-seed fallback for the central property (always runs)."""
    _check_compiled_equals_dense(n_features, n_classes, cpc, density, seed)


@pytest.mark.parametrize("seed", [0, 7, 123])
def test_dont_touch_equals_optimized_fixed(seed):
    """Fixed-seed fallback for the Fig. 8 property (always runs)."""
    _check_dont_touch_equals_optimized(seed)


def test_stats_invariants():
    cfg, ta = _random_tm(60, 4, 10, 0.05, 0)
    comp = compiler.compile_tm(cfg, ta)
    s = comp.stats
    assert s.n_clauses_unique <= s.n_clauses_nonempty <= s.n_clauses_dense
    assert s.n_words_active <= s.n_words_dense
    assert 0.0 <= s.include_sparsity <= 1.0
    assert comp.votes.shape == (comp.n_unique, cfg.n_classes)


def test_vote_folding_counts_multiplicity():
    """Two identical clauses with + polarity in the same class => vote 2."""
    cfg = tm.TMConfig(n_features=4, n_classes=1, clauses_per_class=3)
    ta = np.full((3, 8), -1, np.int8)
    ta[0, 0] = 1   # clause 0 (+): include literal 0
    ta[2, 0] = 1   # clause 2 (+): identical
    ta[1, 1] = 1   # clause 1 (-): include literal 1
    comp = compiler.compile_tm(cfg, ta)
    assert comp.n_unique == 2
    assert sorted(comp.votes[:, 0].tolist()) == [-1, 2]


def test_empty_model_compiles():
    cfg = tm.TMConfig(n_features=8, n_classes=2, clauses_per_class=2)
    ta = np.full((4, 16), -5, np.int8)
    comp = compiler.compile_tm(cfg, ta)
    x = jnp.asarray(np.zeros((3, 8), np.uint8))
    sums = compiler.run_compiled(comp, packetizer.pack_literals(x))
    np.testing.assert_array_equal(np.asarray(sums), 0)


def test_save_load_roundtrip():
    cfg, ta = _random_tm(30, 3, 6, 0.1, 7)
    comp = compiler.compile_tm(cfg, ta)
    with tempfile.TemporaryDirectory() as d:
        path = os.path.join(d, "model.npz")
        comp.save(path)
        back = compiler.CompiledTM.load(path)
    np.testing.assert_array_equal(comp.include_words, back.include_words)
    np.testing.assert_array_equal(comp.votes, back.votes)
    np.testing.assert_array_equal(comp.word_ids, back.word_ids)
    assert back.stats.n_clauses_dense == comp.stats.n_clauses_dense


def test_kernel_path_equivalence():
    """oracle == the kernel ladder's head == the fused dense kernel."""
    from repro.kernels import ops

    cfg, ta = _random_tm(100, 4, 16, 0.08, 3)
    comp = compiler.compile_tm(cfg, ta)
    x = jnp.asarray(np.random.default_rng(0).integers(0, 2, (12, 100), dtype=np.uint8))
    a = compiler.predict_compiled(comp, x, engine="oracle")
    b = compiler.predict_compiled(
        comp, x, engine=ops.engine_levels(comp, use_kernel=True)[0],
        interpret=True)
    c = compiler.predict_compiled(comp, x, engine="dense", interpret=True)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(np.asarray(a), np.asarray(c))


def test_run_compiled_dispatch_defaults():
    """run_compiled defers to ops.engine_levels: off-TPU defaults to the
    oracle path with interpret resolved (no unconditional interpret=True),
    and the kernel ladder's head matches it bit-for-bit."""
    from repro.kernels import ops

    cfg, ta = _random_tm(24, 3, 6, 0.12, 9)
    comp = compiler.compile_tm(cfg, ta)
    xp = packetizer.pack_literals(
        jnp.asarray(np.random.default_rng(1).integers(0, 2, (9, 24), dtype=np.uint8))
    )
    _, it = ops.kernel_dispatch()
    default = compiler.run_compiled(comp, xp)
    explicit = compiler.run_compiled(
        comp, xp, engine=ops.engine_levels(comp)[0], interpret=it)
    kernel = compiler.run_compiled(
        comp, xp, engine=ops.engine_levels(comp, use_kernel=True)[0],
        interpret=True)
    np.testing.assert_array_equal(np.asarray(default), np.asarray(explicit))
    np.testing.assert_array_equal(np.asarray(default), np.asarray(kernel))
