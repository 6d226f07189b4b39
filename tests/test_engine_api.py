"""The unified engine/tuning API: the engine-name contract of
run_compiled, ``"auto"`` as the head of ``ops.engine_levels``, the sharded
builders' kernel dispatch, the `autotune.tune` facade's three policies, and
the zero-timing-run plan_engine cold-start path."""

from __future__ import annotations

import numpy as np
import pytest

import jax.numpy as jnp

from repro.core import compiler, packetizer, tm
from repro.kernels import autotune, ops


def _random_tm(n_features, n_classes, cpc, include_density, seed):
    rng = np.random.default_rng(seed)
    C = n_classes * cpc
    ta = np.where(
        rng.random((C, 2 * n_features)) < include_density,
        rng.integers(0, 127, (C, 2 * n_features)),
        rng.integers(-128, 0, (C, 2 * n_features)),
    ).astype(np.int8)
    cfg = tm.TMConfig(n_features=n_features, n_classes=n_classes,
                      clauses_per_class=cpc)
    return cfg, ta


@pytest.fixture(scope="module")
def artifact():
    cfg, ta = _random_tm(48, 3, 8, 0.10, 7)
    comp = compiler.compile_tm(cfg, ta)
    x = jnp.asarray(np.random.default_rng(1).integers(
        0, 2, (11, 48), dtype=np.uint8))
    return comp, packetizer.pack_literals(x)


def _sharing_artifact():
    """Every clause carries the same two-word core: the measured term
    sharing clears the factorize threshold."""
    cfg = tm.TMConfig(n_features=64, n_classes=2, clauses_per_class=8)
    C, L = 16, 128
    ta = np.full((C, L), -5, np.int8)
    ta[:, 3] = 3
    ta[:, 40] = 3
    for c in range(C):
        ta[c, 64 + ((c * 4) % 64)] = 3
    return compiler.compile_tm(cfg, ta)


# ---------------------------------------------------------------------------
# the engine-name contract
# ---------------------------------------------------------------------------

def test_engine_spec_validation(artifact):
    """An engine is one of ops.ENGINE_NAMES; anything else raises."""
    comp, xp = artifact
    with pytest.raises(ValueError, match="unknown engine"):
        compiler.run_compiled(comp, xp, engine="bogus")
    with pytest.raises(ValueError, match="unknown engine"):
        compiler.run_compiled(comp, xp, engine="fastest")
    with pytest.raises(TypeError, match="convolutional"):
        compiler.run_compiled(comp, xp, engine="conv")


def test_engine_spec_coerce(artifact):
    """Only a name selects an engine: the switches that used to stand in
    for one are refused."""
    comp, xp = artifact
    with pytest.raises(ValueError, match="unknown engine"):
        compiler.run_compiled(comp, xp, engine=None)
    for legacy in ("use_kernel", "fuse", "sparse", "factorize"):
        with pytest.raises(TypeError):
            compiler.run_compiled(comp, xp, **{legacy: True})
    with pytest.raises(TypeError):
        ops.engine_levels(comp, True, sparse=False)


def test_engine_spec_resolve_interpret_precedence(artifact):
    """A named kernel engine runs whatever the platform default; a
    call-site ``interpret`` wins over the platform's (interpret off a
    TPU, compiled on one)."""
    assert ops.kernel_dispatch(None, False)[1] is False
    assert ops.kernel_dispatch(None, True)[1] is True
    assert ops.kernel_dispatch()[1] is (not ops._ON_TPU)
    comp, xp = artifact
    oracle = np.asarray(compiler.run_compiled(comp, xp, engine="oracle"))
    got = compiler.run_compiled(comp, xp, engine="sparse")
    np.testing.assert_array_equal(oracle, np.asarray(got))


@pytest.mark.parametrize("engine, key", [
    ("sparse", "block_t"), ("sparse", "block_b"), ("dense", "block_j"),
    ("factorized", "block_w")])
def test_engine_refuses_a_tiling_it_does_not_take(artifact, engine, key):
    comp, xp = artifact
    with pytest.raises(TypeError, match="takes no tiling"):
        compiler.run_compiled(comp, xp, engine=engine, interpret=True,
                              **{key: 8})


def test_oracle_ignores_kernel_tilings(artifact):
    comp, xp = artifact
    want = np.asarray(compiler.run_compiled(comp, xp, engine="oracle"))
    got = compiler.run_compiled(comp, xp, engine="oracle", block_t=8,
                                block_b=8)
    np.testing.assert_array_equal(want, np.asarray(got))


# ---------------------------------------------------------------------------
# run_compiled engine dispatch
# ---------------------------------------------------------------------------

def test_all_named_engines_bit_identical(artifact):
    comp, xp = artifact
    oracle = np.asarray(compiler.run_compiled(comp, xp, engine="oracle"))
    for name in ("factorized", "sparse", "dense", "auto"):
        got = compiler.run_compiled(comp, xp, engine=name, interpret=True)
        np.testing.assert_array_equal(oracle, np.asarray(got), err_msg=name)


@pytest.mark.parametrize("kind, want", [
    ("shared", "factorized"), ("unshared", "sparse"), ("conv", "conv")])
def test_auto_is_the_head_of_the_ladder(kind, want, monkeypatch):
    """``engine="auto"`` serves ``engine_levels(compiled)[0]`` and nothing
    else: on the kernel path, the factorized kernel above the sharing
    threshold, the sparse one below it, and a convolutional artifact's
    own kernel."""
    rng = np.random.default_rng(3)
    if kind == "conv":
        cfg = tm.ConvTMConfig(image_h=12, image_w=12, window=4,
                              n_clauses=16, n_classes=3)
        words = packetizer.pack_bits_np(
            (rng.random((9, cfg.n_features)) < 0.3).astype(np.uint8))
        ta, weights = tm.conv_bank(cfg, words, 0)
        comp = compiler.compile_tm(cfg, ta, weights=weights)
        xp = jnp.asarray(words)
    else:
        if kind == "shared":
            comp = _sharing_artifact()
        else:
            cfg, ta = _random_tm(24, 2, 4, 0.08, 0)
            comp = compiler.compile_tm(cfg, ta)
        xp = packetizer.pack_literals(jnp.asarray(rng.integers(
            0, 2, (9, comp.n_features), dtype=np.uint8)))
        shares = (comp.stats.partial_term_sharing
                  >= compiler.FACTORIZE_SHARING_THRESHOLD)
        assert shares == (kind == "shared")
    assert ops.engine_levels(comp, use_kernel=True)[0] == want
    monkeypatch.setattr(ops, "_DEFAULT_USE_KERNEL", True)
    assert ops.engine_levels(comp)[0] == want
    auto = compiler.run_compiled(comp, xp, engine="auto", interpret=True)
    named = compiler.run_compiled(comp, xp, engine=want, interpret=True)
    oracle = compiler.run_compiled(comp, xp, engine="oracle")
    np.testing.assert_array_equal(np.asarray(auto), np.asarray(named))
    np.testing.assert_array_equal(np.asarray(auto), np.asarray(oracle))


# ---------------------------------------------------------------------------
# sharding builders
# ---------------------------------------------------------------------------

def test_sharding_engine_dispatch_rules(monkeypatch):
    """The sharded forward builders take no engine name: each names its
    kernel, and whether that kernel or the oracle runs is
    ``ops.kernel_dispatch(use_kernel, interpret)`` alone, the platform
    default when ``use_kernel`` is not given."""
    import jax
    from jax.sharding import Mesh

    from repro.core import sharding

    cfg, ta = _random_tm(48, 3, 8, 0.10, 7)
    ta = jnp.asarray(ta)
    inc = packetizer.pack_include_masks(ta)
    votes = tm.vote_matrix(cfg)
    ne = jnp.any(ta >= 0, -1).astype(jnp.uint8)
    lw = packetizer.pack_bits(tm.literals(jnp.asarray(
        np.random.default_rng(1).integers(0, 2, (11, 48), dtype=np.uint8))))
    want = np.asarray(ops.tm_forward_packed(lw, inc, votes, ne,
                                            use_kernel=False))

    seen, real = [], ops.kernel_dispatch
    monkeypatch.setattr(ops, "kernel_dispatch", lambda uk=None, it=None: (
        seen.append((uk, it)) or real(uk, it)))
    mesh = Mesh(np.array(jax.devices()[:1]), ("model",))
    for uk in (True, False, None):
        fwd = sharding.sharded_forward_fn(mesh, use_kernel=uk, interpret=True)
        np.testing.assert_array_equal(np.asarray(fwd(inc, votes, ne, lw)),
                                      want)
    assert seen == [(True, True), (False, True), (None, True)]

    for build, kw in ((sharding.sharded_forward_fn, {}),
                      (sharding.sharded_schedule_forward_fn,
                       dict(block_c=32, block_j=8)),
                      (sharding.sharded_factorized_forward_fn,
                       dict(block_t=32, block_c=32, block_j=4))):
        with pytest.raises(TypeError, match="engine"):
            build(mesh, engine="dense", **kw)
    with pytest.raises(TypeError, match="engine"):
        sharding.sharded_predict_fn(cfg, mesh, engine="dense")


# ---------------------------------------------------------------------------
# autotune.tune facade
# ---------------------------------------------------------------------------

@pytest.fixture()
def tune_env(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(tmp_path / "tune.json"))
    monkeypatch.setenv("REPRO_TUNE_DATA", str(tmp_path / "data.json"))
    from repro.kernels import cost_model
    cost_model._invalidate_model_cache()
    yield tmp_path
    cost_model._invalidate_model_cache()


_CANDS = ((8, 128, 1), (4, 64, 1), (2, 32, 1), (8, 64, 1), (4, 128, 1))


def test_tune_rejects_unknown(tune_env):
    with pytest.raises(ValueError, match="unknown kernel"):
        autotune.tune("warp_drive", B=1, C=1, W=1, K=1, interpret=True)
    with pytest.raises(ValueError, match="unknown policy"):
        autotune.tune("fused_infer", B=1, C=1, W=1, K=1, interpret=True,
                      policy="guess")


def test_predict_policy_zero_timing_runs(tune_env):
    before = autotune.TIMING_RUNS
    blocks = autotune.tune(
        "fused_infer", B=9, C=17, W=1, K=2, interpret=True,
        policy="predict", candidates=_CANDS)
    assert autotune.TIMING_RUNS == before, "predict policy must not time"
    assert set(blocks) == {"block_b", "block_c", "block_w"}
    # memoized: second call (and a fresh process-cache miss) stays free
    again = autotune.tune(
        "fused_infer", B=9, C=17, W=1, K=2, interpret=True,
        policy="predict", candidates=_CANDS)
    assert again == blocks
    assert autotune.TIMING_RUNS == before


def test_verify_policy_times_only_topk(tune_env):
    reps = 1
    before = autotune.TIMING_RUNS
    blocks = autotune.tune(
        "fused_infer", B=9, C=17, W=1, K=2, interpret=True,
        policy="verify", top_k=3, candidates=_CANDS, reps=reps)
    spent = autotune.TIMING_RUNS - before
    # <= top_k shortlisted candidates x (1 warmup + reps) each; the full
    # 5-candidate sweep would have cost 5 x (1 + reps)
    assert 0 < spent <= 3 * (1 + reps)
    assert set(blocks) == {"block_b", "block_c", "block_w"}


def test_sweep_policy_feeds_sidecar_and_shares_legacy_key(tune_env):
    from repro.kernels import cost_model

    cands = ((8, 128, 1), (4, 64, 1))
    blocks = autotune.tune(
        "fused_infer", B=9, C=17, W=1, K=2, interpret=True,
        policy="sweep", candidates=cands, reps=1)
    rows = cost_model.load_observations()
    assert len(rows) == len(cands)
    for row in rows:
        assert row["kernel"] == "fused_infer"
        assert row["measured_us"] > 0
        assert row["basis"]["steps"] > 0
    # the legacy wrapper answers from the SAME cache entry (no re-sweep)
    before = autotune.TIMING_RUNS
    legacy = autotune.autotune_fused_blocks(
        9, 17, 1, 2, interpret=True, candidates=cands, reps=1)
    assert legacy == blocks
    assert autotune.TIMING_RUNS == before


def test_plan_engine_cold_start(tune_env):
    """plan_engine on a freshly loaded artifact: engine by the sharing
    heuristic, tiling by the cost model, ZERO timing runs."""
    cfg, ta = _random_tm(24, 2, 4, 0.08, 0)
    comp = compiler.compile_tm(cfg, ta)
    assert comp.stats.partial_term_sharing \
        < compiler.FACTORIZE_SHARING_THRESHOLD
    before = autotune.TIMING_RUNS
    engine, blocks = autotune.plan_engine(comp, 32, interpret=True)
    assert engine == "sparse"
    assert set(blocks) == {"block_c", "block_j", "block_s"}
    assert autotune.TIMING_RUNS == before

    # high-sharing artifact routes factorized
    comp2 = _sharing_artifact()
    engine2, blocks2 = autotune.plan_engine(comp2, 32, interpret=True)
    assert engine2 == "factorized"
    assert "block_t" in blocks2
    assert autotune.TIMING_RUNS == before
