"""Pallas TPU kernel: fused single-pass TM inference (clause eval + class sum).

This is the whole MATADOR inference datapath of paper Fig. 5 in ONE
``pallas_call`` — the Hard-Coded Clause Block chain feeding the class-sum
adder bank with no off-chip traffic in between.  A clause-eval kernel
followed by a vote-tally kernel would materialize the full ``(B, C)``
fired matrix in HBM; the eFPGA (arXiv:2502.07823) and 65-nm ASIC
(arXiv:2501.19347) TM accelerators both keep clause outputs on-chip, and so
does this kernel: the clause state lives in VMEM scratch and is folded into
the class-sum accumulator the moment its word chain completes.

Grid-axis map onto the paper's Fig. 5 stages:

  * axis 0 (``b``, parallel)   — datapoint packets: the Packetizer stream.
    Each step owns a ``(block_b,)`` slab of requests.
  * axis 1 (``c``, arbitrary)  — clause banks: which slice of the clause
    array (HCB column) is being evaluated.  Sequential, because every bank
    accumulates into the same ``(block_b, K)`` class-sum output block —
    this is the 2xCL adder bank being time-multiplexed.
  * axis 2 (``w``, arbitrary)  — the HCB chain itself: each step ORs one
    ``block_w``-word literal window's violations into the carried clause
    state (``Clause In``/``Clause Out`` in Fig. 5), held in VMEM scratch.
    HCB 0 starts with no violation (every clause alive).

TPU layout: the clause state is ``(block_c, block_b)`` — clauses on
sublanes, samples on lanes — so one chain step combines a static lane
slice of the include block (a clause column) with a sublane row of the
word-major literal block.  Both operands arrive in 3-D blocks whose two
trailing dims are whole array dims, so any ``block_w`` satisfies Mosaic's
block-shape rule.  On the last chain step the violation-free clauses fold
into the int32 class sums through int8 MXU dots (:func:`fold_votes`) — the
fired matrix never exists in HBM at any block size.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Votes enter the MXU as two int8 limbs, v = hi * VOTE_RADIX + lo with
# lo in [0, VOTE_RADIX): int8 x int8 products accumulate exactly in int32,
# so the fold is exact for every |v| < VOTE_BOUND.
VOTE_RADIX = 128
VOTE_BOUND = 128 * VOTE_RADIX


def vote_limbs(votes: jax.Array, rows: int, cols: int):
    """(C, K) int32 votes -> zero-padded ``(rows, cols)`` int8 (hi, lo)
    limbs for :func:`fold_votes` (exact for ``|v| < VOTE_BOUND``)."""
    v = _pad2(votes.astype(jnp.int32), rows, cols)
    return (v >> 7).astype(jnp.int8), (v & (VOTE_RADIX - 1)).astype(jnp.int8)


def fold_votes(fired: jax.Array, hi: jax.Array, lo: jax.Array) -> jax.Array:
    """``fired (C, N) {0,1} @ votes (C, K)`` contracted over clauses ->
    (N, K) int32, as two int8 MXU dots with int32 accumulation."""
    f = fired.astype(jnp.int32).astype(jnp.int8)
    dn = (((0,), (0,)), ((), ()))
    return (jax.lax.dot_general(f, hi, dn, preferred_element_type=jnp.int32)
            * VOTE_RADIX
            + jax.lax.dot_general(f, lo, dn, preferred_element_type=jnp.int32))


def hcb_violations(viol: jax.Array, inc: jax.Array, lit_t: jax.Array):
    """One HCB chain window: ``viol[c, b] |= inc[c, w] & ~lit[b, w]`` for
    every word ``w`` of the window.  ``inc`` is ``(block_c, block_w)``,
    ``lit_t`` the word-major ``(block_w, block_b)`` literals.  The word loop
    is unrolled in Python: Mosaic slices lanes only at static offsets."""
    for i in range(inc.shape[1]):
        viol = viol | (inc[:, i:i + 1] & ~lit_t[i:i + 1, :])
    return viol


def chain_blocks(lit_words, inc_words, *, Bp, Cp, block_b, block_w):
    """Pad and re-lay the packed operands for the HCB chain kernels:
    literals -> ``(Bp // block_b, Wp, block_b)`` word-major slabs, include
    words -> ``(Wp // block_w, Cp, block_w)`` word windows.  Zero literal
    words are harmless padding; zero include words never violate."""
    W = lit_words.shape[1]
    Wp = _rup(W, block_w)
    lit_t = _pad2(lit_words, Bp, Wp).reshape(Bp // block_b, block_b, Wp)
    inc = _pad2(inc_words, Cp, Wp).reshape(Cp, Wp // block_w, block_w)
    return lit_t.transpose(0, 2, 1), inc.transpose(1, 0, 2)


def _fused_infer_kernel(
    lit_ref,    # (block_w, block_b) uint32 word-major literal words
    inc_ref,    # (block_c, block_w) uint32 include words
    hi_ref,     # (block_c, Kp) int8 high vote limb
    lo_ref,     # (block_c, Kp) int8 low vote limb
    out_ref,    # (block_b, Kp) int32 class-sum accumulator
    viol_ref,   # VMEM scratch (block_c, block_b) uint32 carried violations
):
    c = pl.program_id(1)
    w = pl.program_id(2)
    nw = pl.num_programs(2)

    @pl.when((c == 0) & (w == 0))
    def _init_out():
        out_ref[...] = jnp.zeros_like(out_ref)

    @pl.when(w == 0)
    def _init_chain():  # HCB 0: no violation yet, every clause alive
        viol_ref[...] = jnp.zeros_like(viol_ref)

    viol_ref[...] = hcb_violations(viol_ref[...], inc_ref[...], lit_ref[...])

    @pl.when(w == nw - 1)
    def _fold():  # adder bank: accumulate the finished clause block
        out_ref[...] += fold_votes(viol_ref[...] == 0, hi_ref[...], lo_ref[...])


@functools.partial(
    jax.jit,
    static_argnames=("block_b", "block_c", "block_w", "interpret"),
)
def fused_tm_forward(
    lit_words: jax.Array,           # (B, W) uint32
    inc_words: jax.Array,           # (C, W) uint32
    votes: jax.Array,               # (C, K) int32
    nonempty: jax.Array | None = None,   # (C,) {0,1}; None = no masking
    *,
    block_b: int = 128,
    block_c: int = 128,
    block_w: int = 64,
    interpret: bool = False,
) -> jax.Array:
    """Packed literals -> (B, K) int32 class sums, single fused pass.

    Bit-identical to ``class_sum_ref(clause_fire_ref(lit, inc) * nonempty,
    votes)``; with ``nonempty=None`` to the unmasked (training-semantics)
    composition.  ``|votes|`` must stay below :data:`VOTE_BOUND`.
    """
    B, W = lit_words.shape
    C, Wc = inc_words.shape
    K = votes.shape[1]
    assert W == Wc, (W, Wc)
    assert votes.shape[0] == C, (votes.shape, C)

    votes = votes.astype(jnp.int32)
    if nonempty is not None:   # masking the fired bit == zeroing its votes
        votes = votes * nonempty.astype(jnp.int32)[:, None]

    block_b = min(block_b, _rup(B, 8))
    block_c = min(block_c, _rup(C, 128))
    block_w = min(block_w, W)

    Bp, Cp = _rup(B, block_b), _rup(C, block_c)
    Kp = _rup(K, 128)
    lit_t, inc = chain_blocks(lit_words, inc_words, Bp=Bp, Cp=Cp,
                              block_b=block_b, block_w=block_w)
    hi, lo = vote_limbs(votes, Cp, Kp)   # padded clauses vote 0

    grid = (Bp // block_b, Cp // block_c, inc.shape[0])
    out = pl.pallas_call(
        _fused_infer_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, block_w, block_b), lambda b, c, w: (b, w, 0)),
            pl.BlockSpec((None, block_c, block_w), lambda b, c, w: (w, c, 0)),
            pl.BlockSpec((block_c, Kp), lambda b, c, w: (c, 0)),
            pl.BlockSpec((block_c, Kp), lambda b, c, w: (c, 0)),
        ],
        out_specs=pl.BlockSpec((block_b, Kp), lambda b, c, w: (b, 0)),
        out_shape=jax.ShapeDtypeStruct((Bp, Kp), jnp.int32),
        scratch_shapes=[pltpu.VMEM((block_c, block_b), jnp.uint32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")
        ),
        interpret=interpret,
    )(lit_t, inc, hi, lo)
    return out[:B, :K]


def vmem_limit_bytes(need: int) -> int:
    """Scoped-VMEM limit for a kernel whose blocks and scratch take about
    ``need`` bytes: 2x headroom, never below the compiler's 16 MiB default,
    at most 100 MiB (one v5e core holds 128 MiB)."""
    return int(min(max(2 * need, 16 << 20), 100 << 20))


def _rup(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def _pad2(x: jax.Array, d0: int, d1: int) -> jax.Array:
    return jnp.pad(x, ((0, d0 - x.shape[0]), (0, d1 - x.shape[1])))
