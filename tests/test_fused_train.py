"""Fused single-pass training kernel vs the ref.py oracle composition.

The fused kernel (kernels/fused_train.py) must be bit-identical to the
unfused three-dispatch path (clause_fire -> feedback_plan -> ta_delta) and
to the pure-jnp oracle, in every calling mode: unchunked, batch-chunked
(even and ragged tails), and offset (b_offset/c_offset != 0 — the sharded
caller's view of a clause/batch shard).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import packetizer, tm, train
from repro.kernels import fused_train, ops, ref

RNG = np.random.default_rng(123)
KW = dict(use_kernel=True, interpret=True)


def _problem(B=13, F=17, K=3, cpc=7, threshold=9, s=4.0, seed=0):
    rng = np.random.default_rng(seed)
    cfg = tm.TMConfig(n_features=F, n_classes=K, clauses_per_class=cpc,
                      threshold=threshold, s=s)
    ta = jnp.asarray(
        rng.integers(-30, 30, (cfg.n_clauses_total, cfg.n_literals),
                     dtype=np.int8))
    x = jnp.asarray(rng.integers(0, 2, (B, F), dtype=np.uint8))
    y = jnp.asarray(rng.integers(0, K, B, dtype=np.int32))
    return cfg, ta, x, y


def _steps(cfg, ta, x, y, seed, **kw):
    new_ta, delta = ops.tm_train_step_kernel(cfg, ta, x, y, seed, **kw)
    return np.asarray(new_ta), np.asarray(delta)


@pytest.mark.parametrize("B,F,K,cpc", [
    (13, 17, 3, 7),      # everything ragged
    (8, 64, 4, 32),      # C = 128 exactly one clause block
    (33, 9, 2, 50),      # binary, wide bank, B ragged vs block_b
])
def test_fused_step_matches_unfused_and_oracle(B, F, K, cpc):
    cfg, ta, x, y = _problem(B=B, F=F, K=K, cpc=cpc, seed=B)
    seed = jnp.uint32(77)
    ta_o, d_o = _steps(cfg, ta, x, y, seed, use_kernel=False)
    ta_u, d_u = _steps(cfg, ta, x, y, seed, fuse=False, **KW)
    ta_f, d_f = _steps(cfg, ta, x, y, seed, fuse=True, **KW)
    np.testing.assert_array_equal(d_o, d_u)
    np.testing.assert_array_equal(d_o, d_f)
    np.testing.assert_array_equal(ta_o, ta_f)
    assert np.abs(d_o).sum() > 0   # the step actually trained something


@pytest.mark.parametrize("blocks", [
    dict(block_b=8, block_c=128, block_w=1),
    dict(block_b=16, block_c=128, block_w=2),
])
def test_fused_step_blockings(blocks):
    """Ragged shapes vs explicit tilings: results must not depend on blocks."""
    cfg, ta, x, y = _problem(B=21, F=19, K=3, cpc=11, seed=5)
    seed = jnp.uint32(9)
    _, d_o = _steps(cfg, ta, x, y, seed, use_kernel=False)
    _, d_f = _steps(cfg, ta, x, y, seed, fuse=True, blocks=blocks, **KW)
    np.testing.assert_array_equal(d_o, d_f)


@pytest.mark.parametrize("B,chunk", [
    (24, 8),    # even split
    (21, 8),    # ragged tail: 2 full chunks + padded 5-sample tail
    (13, 4),    # ragged tail
])
def test_chunked_matches_unchunked_all_engines(B, chunk):
    """batch_chunk must be a pure memory knob: bit-identical results,
    including the padded+masked ragged tail (the old code silently ran
    the full batch when B % chunk != 0)."""
    cfg, ta, x, y = _problem(B=B, seed=B + chunk)
    seed = jnp.uint32(31)
    _, d_ref = _steps(cfg, ta, x, y, seed, use_kernel=False)
    for kw in (dict(use_kernel=False), dict(fuse=False, **KW),
               dict(fuse=True, **KW)):
        _, d_c = _steps(cfg, ta, x, y, seed, batch_chunk=chunk, **kw)
        np.testing.assert_array_equal(d_ref, d_c)


def _polarity(kind, C, n_pad, rng):
    """+1/-1 per clause by ``kind``, 0 on the last ``n_pad`` clauses."""
    pol = {"alternating": np.where(np.arange(C) % 2 == 0, 1, -1),
           "random": rng.choice([-1, 1], C),
           "all_plus": np.ones(C, int)}[kind].astype(np.int32)
    pol[C - n_pad:] = 0
    return jnp.asarray(pol)


# Each case: bank and batch sizes, the polarity array, the local slice
# (c_off, n_loc) of the bank the kernel sees, and whether it hashes
# automata by GLOBAL clause id (c_total); class boundaries fall at
# multiples of cpc, so cpc=100 puts them mid-block at an even clause and
# cpc=101 at an odd one (the kernel's even and odd row halves split at
# different rows).
DELTA_CASES = {
    "offsets": dict(B=11, F=23, K=3, cpc=9, pol="alternating", n_pad=0,
                    b_off=37, c_off=10, n_loc=11, c_total=False, blocks={}),
    "random_pol_padded_c128": dict(
        B=13, F=20, K=3, cpc=100, pol="random", n_pad=20, b_off=5, c_off=0,
        n_loc=300, c_total=False, blocks=dict(block_b=8, block_c=128)),
    "all_plus_c256": dict(
        B=13, F=20, K=3, cpc=100, pol="all_plus", n_pad=0, b_off=0, c_off=0,
        n_loc=300, c_total=False, blocks=dict(block_c=256)),
    "odd_class_boundary_c256": dict(
        B=13, F=20, K=3, cpc=101, pol="alternating", n_pad=3, b_off=0,
        c_off=0, n_loc=303, c_total=False, blocks=dict(block_b=8)),
    "shard_odd_offset_global_ids": dict(
        B=9, F=20, K=3, cpc=100, pol="alternating", n_pad=0, b_off=3,
        c_off=37, n_loc=150, c_total=True,
        blocks=dict(block_b=8, block_c=128)),
}


@pytest.mark.parametrize("case", list(DELTA_CASES), ids=list(DELTA_CASES))
def test_fused_delta_offsets_match_composed_oracle(case):
    """The fused kernel reproduces feedback_select + ta_delta_ref on the
    slice of the bank it is given, for any polarity array, class layout,
    tiling and batch: the selection hash on GLOBAL (sample, clause) ids,
    the automaton hash on (global sample, local clause), or on the global
    clause id in a bank of ``c_total`` clauses."""
    p = DELTA_CASES[case]
    rng = np.random.default_rng(len(case))
    cfg, ta, x, y = _problem(B=p["B"], F=p["F"], K=p["K"], cpc=p["cpc"],
                             seed=p["B"])
    # mostly excluded automata, so clauses fire and both types fold
    ta = jnp.where(jnp.asarray(rng.random(ta.shape) < 0.9),
                   -jnp.abs(ta) - 1, ta).astype(jnp.int8)
    T = cfg.threshold
    C = cfg.n_clauses_total
    seed = jnp.uint32(55)
    b_off, c_off, n_loc = p["b_off"], p["c_off"], p["n_loc"]

    lits = tm.literals(x)
    lw = packetizer.pack_bits(lits)
    iw = packetizer.pack_include_masks(ta)
    votes = tm.vote_matrix(cfg)
    cls = jnp.clip(jnp.arange(C) // cfg.clauses_per_class,
                   0, cfg.n_classes - 1)
    pol = _polarity(p["pol"], C, p["n_pad"], rng)

    # per-sample scalars from the FULL clause bank's class sums
    sums = jnp.clip(ref.clause_fire_ref(lw, iw).astype(jnp.int32) @ votes,
                    -T, T)
    kn, p_t, p_n = ops.feedback_probs(sums, y, cfg.n_classes, T, seed,
                                      b_offset=b_off)

    sl = slice(c_off, c_off + n_loc)
    c_total = C if p["c_total"] else None
    fire_loc = ref.clause_fire_ref(lw, iw[sl]).astype(jnp.uint8)
    ftype_loc = ops.feedback_select(y, kn, p_t, p_n, cls[sl], pol[sl], seed,
                                    b_offset=b_off, c_offset=c_off)
    d_ref = ref.ta_delta_ref(ta[sl], lits, fire_loc, ftype_loc, seed,
                             p_act=1.0, p_inact=0.25, b_offset=b_off,
                             c_offset=c_off, c_total=c_total)
    d_k = fused_train.fused_tm_train_delta(
        ta[sl], lits, lw, iw[sl], y, kn, p_t, p_n, cls[sl], pol[sl], seed,
        p_act=1.0, p_inact=0.25, b_offset=b_off, c_offset=c_off,
        c_total=c_total, interpret=True, **p["blocks"])
    np.testing.assert_array_equal(np.asarray(d_ref), np.asarray(d_k))
    ft = np.asarray(ftype_loc)
    assert (ft == 1).any() and (ft == 2).any()
    assert int(np.abs(np.asarray(d_ref)).sum()) > 0


def test_fold_engagement_on_a_hand_built_plan():
    """Per (sample, half-block): a half with a Type I row takes the hashed
    path, one with Type II rows only the Type II path, one with no
    feedback neither.  Half-blocks are the even and odd rows of a block."""
    ft = np.zeros((3, 512), np.uint8)
    ft[0, :256] = 2          # sample 0: block 0 all Type II, block 1 none
    ft[1, 10] = 1            # sample 1: one Type I row in block 0's evens
    ft[2, 300] = 1           # sample 2: block 1's evens mixed I and II,
    ft[2, 302] = 2           #           its odds Type II only
    ft[2, 301] = 2
    got = fused_train.fold_engagement(ft, block_c=256)
    assert got == {"hashed": 2 / 12, "type2_only": 3 / 12,
                   "neither": 7 / 12}
    # a smaller bank is tiled as the kernel tiles it: one 128-row block
    assert fused_train.fold_engagement(np.zeros((2, 100), np.uint8)) == {
        "hashed": 0.0, "type2_only": 0.0, "neither": 1.0}


def test_fused_clause_shards_reassemble_full_delta():
    """Two clause shards evaluated with c_offset stitch together into the
    full-bank unfused delta (the clause-sharded trainer's invariant)."""
    cfg, ta, x, y = _problem(B=9, F=15, K=2, cpc=12, seed=8)
    T = cfg.threshold
    seed = jnp.uint32(13)
    C = cfg.n_clauses_total
    half = C // 2

    _, d_full = _steps(cfg, ta, x, y, seed, use_kernel=False)

    lits = tm.literals(x)
    lw = packetizer.pack_bits(lits)
    iw = packetizer.pack_include_masks(ta)
    votes = tm.vote_matrix(cfg)
    cls = jnp.clip(jnp.arange(C) // cfg.clauses_per_class, 0,
                   cfg.n_classes - 1)
    pol = tm.polarity(cfg)
    sums = jnp.clip(ref.clause_fire_ref(lw, iw).astype(jnp.int32) @ votes,
                    -T, T)
    kn, p_t, p_n = ops.feedback_probs(sums, y, cfg.n_classes, T, seed)
    p_act = 1.0 if cfg.boost_true_positive else (cfg.s - 1.0) / cfg.s

    parts = []
    for c_off in (0, half):
        sl = slice(c_off, c_off + half)
        # NB the sharded ta_delta hashes (global sample, LOCAL clause):
        # the shard must present the same local clause count as the full
        # bank's ta_delta stream does per shard — here the full-bank
        # oracle is recomputed per shard for the comparison.
        ftype_loc = ops.feedback_select(y, kn, p_t, p_n, cls[sl], pol[sl],
                                        seed, c_offset=c_off)
        fire_loc = ref.clause_fire_ref(lw, iw[sl]).astype(jnp.uint8)
        d_shard = fused_train.fused_tm_train_delta(
            ta[sl], lits, lw, iw[sl], y, kn, p_t, p_n, cls[sl], pol[sl],
            seed, p_act=p_act, p_inact=1.0 / cfg.s, c_offset=c_off,
            interpret=True)
        np.testing.assert_array_equal(
            np.asarray(d_shard),
            np.asarray(ref.ta_delta_ref(ta[sl], lits, fire_loc, ftype_loc,
                                        seed, p_act=p_act,
                                        p_inact=1.0 / cfg.s)))
        parts.append(np.asarray(d_shard))
    # the selection hash is global-id-indexed, so shard 0's ftype equals
    # the full bank's left half: stitching shards reproduces full ftype
    ft_full = ops.feedback_select(y, kn, p_t, p_n, cls, pol, seed)
    ft_stitched = np.concatenate([
        np.asarray(ops.feedback_select(y, kn, p_t, p_n, cls[:half],
                                       pol[:half], seed, c_offset=0)),
        np.asarray(ops.feedback_select(y, kn, p_t, p_n, cls[half:],
                                       pol[half:], seed, c_offset=half)),
    ], axis=1)
    np.testing.assert_array_equal(np.asarray(ft_full), ft_stitched)


def test_feedback_plan_refactor_unchanged():
    """feedback_plan (probs + select split) still returns the original
    (ftype, sums) contract."""
    cfg, ta, x, y = _problem(B=7, seed=2)
    lits = tm.literals(x)
    lw = packetizer.pack_bits(lits)
    iw = packetizer.pack_include_masks(ta)
    votes = tm.vote_matrix(cfg)
    cls = jnp.clip(jnp.arange(cfg.n_clauses_total) // cfg.clauses_per_class,
                   0, cfg.n_classes - 1)
    fire = ref.clause_fire_ref(lw, iw).astype(jnp.uint8)
    seed = jnp.uint32(4)
    ftype, sums = ops.feedback_plan(fire, y, votes, cls, tm.polarity(cfg),
                                    cfg.threshold, seed)
    assert ftype.shape == fire.shape and ftype.dtype == jnp.uint8
    expect_sums = jnp.clip(fire.astype(jnp.int32) @ votes,
                           -cfg.threshold, cfg.threshold)
    np.testing.assert_array_equal(np.asarray(sums), np.asarray(expect_sums))
    assert set(np.unique(np.asarray(ftype))) <= {0, 1, 2}


def test_autotune_train_roundtrip(tmp_path, monkeypatch):
    """The training-shape autotuner memoizes under its own cache key and
    tuned blocks preserve bit-exactness of the fused step."""
    from repro.kernels import autotune

    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(tmp_path / "tune.json"))
    cands = ((128, 256, 64), (8, 128, 1))
    blocks = autotune.autotune_fused_train_blocks(
        13, 21, 2, 34, 3, interpret=True, candidates=cands, reps=1)
    assert set(blocks) == {"block_b", "block_c", "block_w"}
    again = autotune.autotune_fused_train_blocks(
        13, 21, 2, 34, 3, interpret=True, candidates=cands, reps=1)
    assert again == blocks

    cfg, ta, x, y = _problem()
    seed = jnp.uint32(6)
    _, d_o = _steps(cfg, ta, x, y, seed, use_kernel=False)
    _, d_f = _steps(cfg, ta, x, y, seed, fuse=True, blocks=blocks, **KW)
    np.testing.assert_array_equal(d_o, d_f)


def test_autotune_cache_schema_invalidation(tmp_path, monkeypatch):
    """Pre-schema (v1 flat) or corrupt cache files are invalidated on load
    instead of crashing or silently answering with stale blocks."""
    import json

    from repro.kernels import autotune

    path = tmp_path / "tune.json"
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(path))

    # old flat-format cache (schema 1): must be treated as empty
    path.write_text(json.dumps({
        "fused_infer:v1:cpu:interp:B1:C1:W1:K1:cands[8x128x1]":
            {"blocks": {"block_b": 999, "block_c": 999, "block_w": 999}},
    }))
    assert autotune._load_cache() == {}

    # corrupt file: also empty, no crash
    path.write_text("{not json")
    assert autotune._load_cache() == {}

    # a sweep rewrites the file with the current schema and round-trips
    cands = ((8, 128, 1),)
    blocks = autotune.autotune_fused_blocks(
        9, 17, 1, 2, interpret=True, candidates=cands, reps=1)
    on_disk = json.loads(path.read_text())
    assert on_disk["schema"] == autotune._SCHEMA_VERSION
    assert any(k.startswith("fused_infer:") for k in on_disk["entries"])
    assert autotune.autotune_fused_blocks(
        9, 17, 1, 2, interpret=True, candidates=cands, reps=1) == blocks


def test_fit_kernel_engine_matches_manual_loop():
    """train.fit(engine="kernel") reproduces the manual ops loop bit-for-bit
    (pre-shuffle + donation are pure perf changes)."""
    from repro.data import make_noisy_xor

    X, y = make_noisy_xor(120, noise=0.05, seed=11)
    cfg = tm.TMConfig(n_features=12, n_classes=2, clauses_per_class=10,
                      threshold=15, s=3.9)
    st0 = tm.init(cfg, jax.random.PRNGKey(0))
    ta0 = np.asarray(st0.ta_state)   # snapshot: fit donates st0's buffers
    rng = jax.random.PRNGKey(7)
    bs, epochs = 30, 2

    st = train.fit(cfg, st0, jnp.asarray(X), jnp.asarray(y), epochs=epochs,
                   batch_size=bs, rng=rng, engine="kernel")

    # manual replay: same shuffle stream, same per-step seeds
    ta = jnp.asarray(ta0)
    r = rng
    gstep = 0
    for ep in range(epochs):
        r, rp = jax.random.split(r)
        perm = jax.random.permutation(rp, 120)
        xs, ys = jnp.asarray(X)[perm], jnp.asarray(y)[perm]
        for i in range(120 // bs):
            r, _ = jax.random.split(r)
            ta, _d = ops.tm_train_step_kernel(
                cfg, ta, xs[i * bs:(i + 1) * bs], ys[i * bs:(i + 1) * bs],
                jnp.uint32(gstep))
            gstep += 1
    np.testing.assert_array_equal(np.asarray(st.ta_state), np.asarray(ta))
    assert int(st.steps) == gstep
