"""Pure-jnp oracles for every Pallas kernel in this package.

Each function here defines the *exact* semantics its kernel must reproduce
(tests/test_kernels.py sweeps shapes/dtypes and asserts equality).  The
training oracle uses the same integer hash RNG as the kernel so results match
bit-for-bit (DESIGN.md §2: the TPU analog of the paper's LFSR-based FPGA
random number generators, refs [20][21]).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

# ---------------------------------------------------------------------------
# Counter-based RNG (xxhash-style avalanche) — identical in kernel and oracle.
# Constants are *numpy* scalars so the hash traces inside Pallas kernels
# without becoming captured jax-array constants.
# ---------------------------------------------------------------------------

_H1 = np.uint32(2654435761)
_H2 = np.uint32(2246822519)
_H3 = np.uint32(3266489917)


def hash_u32(idx: jax.Array, seed: jax.Array) -> jax.Array:
    """Deterministic uint32 hash of (index, seed) — the kernel's RNG."""
    return hash_finish(idx.astype(jnp.uint32) * _H1 + seed.astype(jnp.uint32))


def hash_finish(x: jax.Array) -> jax.Array:
    """The avalanche of :func:`hash_u32` after its first round
    ``x = idx * H1 + seed``.  That round is affine mod 2**32, so a kernel
    can hoist the part of it that does not change from draw to draw."""
    x = x ^ (x >> 16)
    x = x * _H2
    x = x ^ (x >> 13)
    x = x * _H3
    x = x ^ (x >> 16)
    return x


def hash_unit(r: jax.Array) -> jax.Array:
    """uint32 hash -> float32 in [0, 1]: ``float32(r) / 2**32``.

    Built from the two exact 16-bit halves, so the only rounding is the one
    in the final add — the same correctly rounded value as a direct cast,
    which Mosaic cannot lower (it has no uint32 -> float32 conversion).
    """
    hi = (r >> 16).astype(jnp.int32).astype(jnp.float32)
    lo = (r & np.uint32(0xFFFF)).astype(jnp.int32).astype(jnp.float32)
    return (hi * np.float32(65536.0) + lo) / np.float32(2**32)


def prob_to_u32(p: float) -> np.uint32:
    """Threshold such that P[hash < t] == p (up to 2^-32)."""
    return np.uint32(min(int(round(p * 2**32)), 2**32 - 1))


# ---------------------------------------------------------------------------
# clause_fire: bitpacked clause evaluation (the HCB chain)
# ---------------------------------------------------------------------------

def clause_fire_ref(lit_words: jax.Array, inc_words: jax.Array) -> jax.Array:
    """(B, W) uint32 literals x (C, W) uint32 includes -> (B, C) int8 fire.

    fire[b, c] = 1 iff every include bit of clause c sees literal 1:
    AND_w ((inc[c, w] & ~lit[b, w]) == 0).  Vacuous AND (empty clause) = 1;
    empty-clause masking is the caller's concern (inference drops them).
    """
    viol = inc_words[None, :, :] & ~lit_words[:, None, :]      # (B, C, W)
    return (~jnp.any(viol != 0, axis=-1)).astype(jnp.int8)


# ---------------------------------------------------------------------------
# class_sum: polarity-weighted vote tally (the class-sum adder bank)
# ---------------------------------------------------------------------------

def class_sum_ref(fired: jax.Array, votes: jax.Array) -> jax.Array:
    """(B, C) {0,1} x (C, K) int32 -> (B, K) int32."""
    return fired.astype(jnp.int32) @ votes.astype(jnp.int32)


# ---------------------------------------------------------------------------
# conv_class_sums: convolutional coalesced TM (ConvCoTM) inference
# ---------------------------------------------------------------------------

def conv_class_sums_ref(lits: jax.Array, include: jax.Array,
                        votes: jax.Array) -> jax.Array:
    """(B, P, Lp) {0,1} patch literals x (C, Lp) {0,1} include bits x
    (C, K) weights -> (B, K) int32.

    Clause ``c`` fires on patch ``p`` iff none of its included literals is
    0; it fires on the image iff it fires on some patch, and an empty
    clause never fires.  Integer throughout (an int8 dot with int32
    accumulation counts the violations)."""
    B, P, L = lits.shape
    zero = (1 - lits.reshape(B * P, L)).astype(jnp.int8)
    viol = jnp.dot(zero, include.astype(jnp.int8).T,
                   preferred_element_type=jnp.int32)            # (B*P, C)
    fire = jnp.any(viol.reshape(B, P, -1) == 0, axis=1)
    fire = fire & jnp.any(include != 0, axis=1)[None, :]
    return fire.astype(jnp.int32) @ votes.astype(jnp.int32)


# ---------------------------------------------------------------------------
# ta_delta: batched Type I/II feedback deltas (training hot loop)
# ---------------------------------------------------------------------------

def ta_delta_ref(
    ta: jax.Array,        # (C, L) int8 automata states
    lits: jax.Array,      # (B, L) uint8 {0,1}
    fire: jax.Array,      # (B, C) uint8 clause outputs (training semantics)
    ftype: jax.Array,     # (B, C) uint8: 0 = none, 1 = Type I, 2 = Type II
    seed: jax.Array,      # uint32 scalar
    *,
    p_act: float,
    p_inact: float,
    b_offset=0,           # global index of lits[0] (batch-chunked training)
    c_offset=0,           # global index of ta[0] (clause-sharded training)
    c_total: int | None = None,  # global clause count when ta is a shard
) -> jax.Array:
    """Summed feedback delta over the batch -> (C, L) int32.

    Random draws use ``hash_u32(global_index, seed)`` with
    global_index = ((b + b_offset) * Cg + c + c_offset) * L + l  (uint32,
    wraps — fine for RNG); ``b_offset`` makes chunked evaluation
    bit-identical to unchunked.  ``c_total`` (with ``c_offset``) switches
    the clause index to GLOBAL ids in a bank of ``c_total`` clauses, so a
    clause shard reproduces exactly the full-bank stream's draws for its
    rows; the default (``c_total=None``) keeps local indexing, as a step
    over the shard alone would.
    """
    B, L = lits.shape
    C = ta.shape[0]
    Cg = C if c_total is None else c_total
    t_act = prob_to_u32(p_act)
    t_inact = prob_to_u32(p_inact)

    b_idx = (
        jnp.arange(B, dtype=jnp.uint32) + jnp.uint32(b_offset)
    )[:, None, None]
    c_idx = jnp.arange(C, dtype=jnp.uint32)[None, :, None]
    if c_total is not None:
        c_idx = c_idx + jnp.uint32(c_offset)
    l_idx = jnp.arange(L, dtype=jnp.uint32)[None, None, :]
    gidx = (b_idx * jnp.uint32(Cg) + c_idx) * jnp.uint32(L) + l_idx
    r = hash_u32(gidx, seed)                                   # (B, C, L)

    lit_on = (lits[:, None, :] == 1)                           # (B, 1->C, L)
    fire_b = (fire[:, :, None] == 1)                           # (B, C, 1->L)
    excl = (ta[None, :, :] < 0)

    act = r < t_act
    inact = r < t_inact
    d1 = jnp.where(
        fire_b,
        jnp.where(lit_on, act.astype(jnp.int32), -inact.astype(jnp.int32)),
        -inact.astype(jnp.int32),
    )
    d2 = (fire_b & ~lit_on & excl).astype(jnp.int32)

    ft = ftype[:, :, None]
    d = jnp.where(ft == 1, d1, jnp.where(ft == 2, d2, 0))
    return jnp.sum(d, axis=0, dtype=jnp.int32)


# ---------------------------------------------------------------------------
# xnor_popcount: binarized matmul (FINN-style BNN baseline layer)
# ---------------------------------------------------------------------------

def xnor_popcount_ref(a_words: jax.Array, w_words: jax.Array, n_bits: int) -> jax.Array:
    """(B, W) uint32 x (O, W) uint32 -> (B, O) int32 of +1/-1 dot products.

    Bits encode {-1:0, +1:1}; dot = matches - mismatches
    = 2 * popcount(~(a ^ w)) - n_bits  (padding bits cancelled by caller
    passing the true n_bits).
    """
    x = ~(a_words[:, None, :] ^ w_words[None, :, :])           # (B, O, W)
    pop = jnp.sum(jax.lax.population_count(x), axis=-1, dtype=jnp.int32)
    pad_bits = a_words.shape[-1] * 32 - n_bits
    matches = pop - pad_bits                                   # padding: ~(0^0) = all ones
    return 2 * matches - n_bits


# ---------------------------------------------------------------------------
# flash_attention forward (LM substrate kernel)
# ---------------------------------------------------------------------------

def flash_ref(q: jax.Array, k: jax.Array, v: jax.Array, causal: bool = True):
    """(B,S,H,hd) x (B,T,H,hd) x (B,T,H,dv) -> (B,S,H,dv) dense oracle."""
    B, S, H, hd = q.shape
    T = k.shape[1]
    s = jnp.einsum(
        "bqhd,bthd->bhqt", q.astype(jnp.float32), k.astype(jnp.float32)
    ) * (hd**-0.5)
    if causal:
        mask = jnp.arange(T)[None, :] <= jnp.arange(S)[:, None]
        s = jnp.where(mask[None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhqt,bthv->bqhv", p, v.astype(jnp.float32))
    return out.astype(q.dtype)
