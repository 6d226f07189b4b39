"""Per-kernel shape/dtype sweeps: Pallas (interpret mode) vs ref.py oracles."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import packetizer, tm
from repro.kernels import ops, ref

RNG = np.random.default_rng(42)
KW = dict(use_kernel=True, interpret=True)


def _sparse_includes(C, W, density=0.05):
    m = RNG.random((C, W * 32)) < density
    return packetizer.pack_bits_np(m.astype(np.uint8))


@pytest.mark.parametrize("B,O,W,pad", [(4, 6, 2, 0), (33, 65, 4, 13), (128, 256, 8, 31)])
def test_xnor_popcount_sweep(B, O, W, pad):
    n_bits = W * 32 - pad
    # real packers zero the padding bits; emulate that
    a_bits = RNG.integers(0, 2, (B, n_bits), dtype=np.uint8)
    w_bits = RNG.integers(0, 2, (O, n_bits), dtype=np.uint8)
    a = jnp.asarray(packetizer.pack_bits_np(a_bits))
    w = jnp.asarray(packetizer.pack_bits_np(w_bits))
    r = ref.xnor_popcount_ref(a, w, n_bits)
    k = ops.xnor_dot(a, w, n_bits, **KW)
    np.testing.assert_array_equal(np.asarray(r), np.asarray(k))
    # oracle-of-oracle: ±1 dot product
    pm_a = 2.0 * a_bits - 1
    pm_w = 2.0 * w_bits - 1
    np.testing.assert_array_equal(np.asarray(r), (pm_a @ pm_w.T).astype(np.int32))


def test_hash_rng_uniformity():
    """The kernel RNG should be close to uniform (coarse sanity)."""
    idx = jnp.arange(100_000, dtype=jnp.uint32)
    r = np.asarray(ref.hash_u32(idx, jnp.uint32(7)))
    frac = (r < ref.prob_to_u32(0.3)).mean()
    assert abs(frac - 0.3) < 0.01


# ---------------------------------------------------------------------------
# fused single-pass inference kernel (fused_infer.py)
# ---------------------------------------------------------------------------

def _fused_expect(lit, inc, votes, nonempty):
    """Oracle composition the fused kernel must match bit-for-bit."""
    fired = ref.clause_fire_ref(lit, inc)
    if nonempty is not None:
        fired = fired * nonempty[None, :].astype(fired.dtype)
    return ref.class_sum_ref(fired, votes)


@pytest.mark.parametrize(
    "B,C,W,K",
    [
        (1, 1, 1, 1),        # single-class, single-clause edge
        (7, 13, 3, 2),       # everything ragged
        (33, 257, 5, 10),    # C not a multiple of 128
        (64, 300, 8, 1),     # single class with a wide bank
        (130, 128, 2, 4),    # B not a multiple of block_b
        (64, 128, 8, 4),     # exactly one default clause block
        (3, 7, 1, 2),        # the adder bank on a tiny bank
        (65, 300, 4, 10),    # ... on a ragged bank
        (128, 512, 2, 32),   # ... with many classes
    ],
)
@pytest.mark.parametrize("masked", [True, False])
def test_fused_infer_sweep(B, C, W, K, masked):
    lit = jnp.asarray(RNG.integers(0, 2**32, (B, W), dtype=np.uint32))
    inc = jnp.asarray(_sparse_includes(C, W))
    votes = jnp.asarray(RNG.integers(-9, 10, (C, K), dtype=np.int32))
    ne = jnp.asarray(RNG.integers(0, 2, (C,), dtype=np.uint8)) if masked else None
    expect = _fused_expect(lit, inc, votes, ne)
    got = ops.tm_forward_packed(lit, inc, votes, ne, **KW)
    np.testing.assert_array_equal(np.asarray(expect), np.asarray(got))


@pytest.mark.parametrize(
    "blocks",
    [dict(), dict(block_b=8, block_c=128, block_w=2),
     dict(block_b=16, block_c=256, block_w=1)],
)
def test_fused_infer_blockings(blocks):
    """Ragged shapes vs every block tiling: B/C/W not multiples of blocks."""
    lit = jnp.asarray(RNG.integers(0, 2**32, (17, 5), dtype=np.uint32))
    inc = jnp.asarray(_sparse_includes(39, 5, density=0.08))
    votes = jnp.asarray(RNG.integers(-3, 4, (39, 3), dtype=np.int32))
    ne = jnp.asarray(RNG.integers(0, 2, (39,), dtype=np.uint8))
    expect = _fused_expect(lit, inc, votes, ne)
    got = ops.tm_forward_packed(lit, inc, votes, ne, **KW, **blocks)
    np.testing.assert_array_equal(np.asarray(expect), np.asarray(got))


def test_fused_infer_all_empty_bank():
    """All-exclude clause bank: every clause fires vacuously but the
    nonempty mask zeroes the sums (inference semantics, paper §III)."""
    B, C, W, K = 9, 40, 3, 4
    lit = jnp.asarray(RNG.integers(0, 2**32, (B, W), dtype=np.uint32))
    inc = jnp.zeros((C, W), jnp.uint32)
    votes = jnp.asarray(RNG.integers(-5, 6, (C, K), dtype=np.int32))
    ne = jnp.zeros((C,), jnp.uint8)
    got = ops.tm_forward_packed(lit, inc, votes, ne, **KW)
    np.testing.assert_array_equal(np.asarray(got), 0)
    # unmasked (training semantics): vacuous fire = 1 -> column sums of votes
    got_unmasked = ops.tm_forward_packed(lit, inc, votes, None, **KW)
    np.testing.assert_array_equal(
        np.asarray(got_unmasked),
        np.broadcast_to(np.asarray(votes).sum(0), (B, K)),
    )


def test_fused_infer_randomized_property():
    """Randomized fixed-seed property sweep: fused == oracle composition."""
    prng = np.random.default_rng(7)
    for _ in range(10):
        B = int(prng.integers(1, 70))
        C = int(prng.integers(1, 400))
        W = int(prng.integers(1, 9))
        K = int(prng.integers(1, 12))
        density = float(prng.uniform(0.0, 0.2))
        lit = jnp.asarray(prng.integers(0, 2**32, (B, W), dtype=np.uint32))
        m = prng.random((C, W * 32)) < density
        inc = jnp.asarray(packetizer.pack_bits_np(m.astype(np.uint8)))
        votes = jnp.asarray(prng.integers(-9, 10, (C, K), dtype=np.int32))
        ne = jnp.asarray(prng.integers(0, 2, (C,), dtype=np.uint8))
        expect = _fused_expect(lit, inc, votes, ne)
        got = ops.tm_forward_packed(lit, inc, votes, ne, **KW)
        np.testing.assert_array_equal(np.asarray(expect), np.asarray(got))


def test_autotuner_cache_roundtrip(tmp_path, monkeypatch):
    """The block autotuner returns a valid clipped tiling and memoizes it."""
    from repro.kernels import autotune

    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(tmp_path / "tune.json"))
    cands = ((128, 128, 64), (8, 128, 2))
    blocks = autotune.autotune_fused_blocks(
        17, 39, 5, 3, interpret=True, candidates=cands, reps=1
    )
    assert set(blocks) == {"block_b", "block_c", "block_w"}
    assert (tmp_path / "tune.json").exists()
    again = autotune.autotune_fused_blocks(
        17, 39, 5, 3, interpret=True, candidates=cands, reps=1
    )
    assert again == blocks
    # tuned blocks must preserve bit-exactness
    lit = jnp.asarray(RNG.integers(0, 2**32, (17, 5), dtype=np.uint32))
    inc = jnp.asarray(_sparse_includes(39, 5))
    votes = jnp.asarray(RNG.integers(-3, 4, (39, 3), dtype=np.int32))
    expect = _fused_expect(lit, inc, votes, None)
    got = ops.tm_forward_packed(lit, inc, votes, None, **KW, **blocks)
    np.testing.assert_array_equal(np.asarray(expect), np.asarray(got))


def test_predict_kernel_path_matches_dense():
    """tm.predict wired through the fused packed path == dense XLA path."""
    cfg = tm.TMConfig(n_features=37, n_classes=4, clauses_per_class=9)
    state = tm.init(cfg, jax.random.PRNGKey(3))
    x = jnp.asarray(RNG.integers(0, 2, (25, 37), dtype=np.uint8))
    dense = tm.predict(cfg, state, x, use_kernel=False)
    fused = tm.predict(cfg, state, x, use_kernel=True, interpret=True)
    np.testing.assert_array_equal(np.asarray(dense), np.asarray(fused))


def test_tm_forward_packed_matches_dense():
    cfg = tm.TMConfig(n_features=50, n_classes=3, clauses_per_class=12)
    state = tm.init(cfg, jax.random.PRNGKey(0))
    x = jnp.asarray(RNG.integers(0, 2, (20, 50), dtype=np.uint8))
    lits = tm.literals(x)
    dense = tm.class_sums(cfg, state.ta_state, lits, training=False)
    lw = packetizer.pack_bits(lits)
    iw = packetizer.pack_include_masks(state.ta_state)
    nonempty = jnp.any(state.ta_state >= 0, axis=-1).astype(jnp.uint8)
    packed = ops.tm_forward_packed(lw, iw, tm.vote_matrix(cfg), nonempty, **KW)
    np.testing.assert_array_equal(np.asarray(dense), np.asarray(packed))


@pytest.mark.parametrize("B,S,H,hd,bq,bkv", [(2, 64, 3, 16, 16, 16), (1, 128, 2, 32, 32, 64)])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_kernel(B, S, H, hd, bq, bkv, causal):
    from repro.kernels.flash_attention import flash_forward

    q = jnp.asarray(RNG.normal(size=(B, S, H, hd)), jnp.float32)
    k = jnp.asarray(RNG.normal(size=(B, S, H, hd)), jnp.float32)
    v = jnp.asarray(RNG.normal(size=(B, S, H, hd)), jnp.float32)
    out = flash_forward(q, k, v, causal=causal, block_q=bq, block_kv=bkv,
                        interpret=True)
    expect = ref.flash_ref(q, k, v, causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect), atol=2e-5)
