"""Pallas TPU kernel: shared-term FACTORIZED compiled TM inference.

The block-sparse chain kernel (``sparse_infer.py``) walks each clause's
include BITS — but on a trained bank the same word-level AND term (an
(active-word, include-pattern) pair) appears in many clauses: MATADOR's
Fig. 5 logic absorption collapses those to ONE gate, and
``CompileStats.partial_term_sharing`` measures exactly that opportunity.
This kernel *exploits* it with a two-level factorized execution schedule
emitted by ``core/compiler.py``:

  * **term table** — the unique nonzero ``(word, include-value)`` pairs
    across the deduped clause bank, each compiled into a literal-bit chain
    of ``<= 32`` steps (one packed word's worth of include bits);
  * **clause chains** — every clause is rewritten as a compacted chain of
    *term ids* (one id per active word), tiled into the same CSR-like
    per-clause-block table the sparse kernel uses.

Execution is ONE ``pallas_call`` over grid ``(sample-word-block, tile)``
with two in-VMEM stages per sample block, driven by a scalar-prefetched
tile table (``tile_stage`` flags term vs clause tiles; term tiles come
first so the flat tile walk is stage 1 then stage 2):

  * **stage 1** (term tiles): each unique term is evaluated ONCE against
    the bit-transposed literals — AND the term's literal rows — into a
    ``(Tp, block_s)`` uint32 bitvector scratch (row ``t`` = term ``t`` of
    32 samples per word, the same sample-parallel layout as the clause
    state);
  * **stage 2** (clause tiles): the carried ``(block_c, block_s)`` clause
    state ANDs in TERM rows from the scratch — one step per *active word*,
    not per include bit — then the last tile of each clause block folds
    the fired bits into the multiplicity x polarity votes (int8 MXU dots).

Both stages read rows by scalar id: the tiles' chain ids ride in SMEM
blocks, and each chain step is a dynamic single-row read (the sparse
kernel's :func:`~repro.kernels.sparse_infer.chain_and`).

Work therefore scales with the artifact's UNIQUE include structure: a term
shared by ``n`` clauses costs its bit chain once plus ``n`` single-row
gathers, instead of ``n`` full bit chains.  Exactness contract matches the
sparse kernel: padding terms (rows past ``n_terms``) have empty bit chains
and evaluate to constant 1, so sentinel-padded clause chains are exact,
all-zero clause rows fire vacuously, and their votes must be zero (true
for every ``compile_tm`` artifact).

Validated bit-exactly against the jnp oracle in Pallas interpret mode
(tests/test_term_infer.py) and compiled for a described TPU v5e by
``tests/test_tpu_lowering.py``.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import packetizer
from repro.kernels.fused_infer import _rup, vmem_limit_bytes, vote_limbs
from repro.kernels.sparse_infer import (artifact_tag, certify_slab, chain_and,
                                        fold_sample_bits, literal_slabs,
                                        smem_tiles, unslab_sums, walk_chains)

# default factorized tiling: 1024-clause banks, 64-term chain tiles,
# 512-term stage-1 tiles, 16-word (512-sample) slabs — see
# kernels/autotune.py for the swept alternatives; small artifacts clip.
# Both chain tiles ride in SMEM (1 MiB on v5e, double-buffered), which
# bounds block_c * block_j + block_t * term_w (autotune.SMEM_TILE_WORDS).
DEFAULT_BLOCK_C = 1024
DEFAULT_BLOCK_J = 64
DEFAULT_BLOCK_T = 512
DEFAULT_BLOCK_S = 16


@dataclasses.dataclass(frozen=True, eq=False)
class FactorizedSchedule:
    """Two-level factorized execution schedule for one clause bank.

    ``term_chain[t, i]`` is the literal BIT id of term ``t``'s ``i``-th
    include bit (sentinel ``n_lit_bits`` past the term's popcount — the
    all-ones transposed literal row); rows past ``n_terms`` are all-
    sentinel padding terms that evaluate to constant 1.  ``clause_chain[c,
    j]`` is the TERM id of clause ``c``'s ``j``-th active word (sentinel
    ``n_terms`` — a padding term — past the clause's active-word count).
    The flat scalar-prefetched tile table walks stage-1 term tiles first
    (``tile_stage == 0``, ``tile_tb`` selects the term block) then stage-2
    clause tiles (``tile_stage == 1``; ``tile_cb``/``tile_jb``/
    ``tile_first``/``tile_last`` as in ``SparseSchedule``); ``counts``/
    ``indptr`` are the CSR view over CLAUSE tiles per clause block.
    Identity-hashed (``eq=False``) so a schedule works as a jit static
    argument, like ``SparseSchedule``.
    """

    block_c: int
    block_j: int                # term-chain positions per clause tile
    block_t: int                # term rows per stage-1 tile
    term_w: int                 # bit-chain positions per term row
    n_rows: int                 # unique clauses covered (pre-padding)
    n_terms: int                # unique (word, value) terms (pre-padding)
    n_lit_bits: int             # literal-bit sentinel id
    term_word: np.ndarray       # (n_terms,) int32 active-word index per term
    term_val: np.ndarray        # (n_terms,) uint32 include-word value
    term_chain: np.ndarray      # (Tp, term_w) int32 literal bit ids
    clause_chain: np.ndarray    # (Cp, Jp) int32 term ids
    tile_stage: np.ndarray      # (T,) int32 0 = term tile, 1 = clause tile
    tile_tb: np.ndarray         # (T,) int32 term-block id (stage-1 tiles)
    tile_cb: np.ndarray         # (T,) int32 clause-block id (stage-2 tiles)
    tile_jb: np.ndarray         # (T,) int32 chain-block id (stage-2 tiles)
    tile_first: np.ndarray      # (T,) int32 1 = first clause tile of block
    tile_last: np.ndarray       # (T,) int32 1 = last clause tile of block
    counts: np.ndarray          # (n_cblocks,) int32 clause tiles per block
    indptr: np.ndarray          # (n_cblocks + 1,) int32 CSR row pointers

    @property
    def n_tiles(self) -> int:
        return int(self.tile_stage.shape[0])

    @property
    def n_term_tiles(self) -> int:
        return int((self.tile_stage == 0).sum())

    @property
    def n_cblocks(self) -> int:
        return int(self.counts.shape[0])

    @property
    def n_term_refs(self) -> int:
        """Total term references across all clause chains — the number of
        term evaluations a non-factorized executor would pay."""
        # clause_chain rows past n_rows are all-sentinel padding
        return int((self.clause_chain[: self.n_rows] != self.n_terms).sum())

    @property
    def realized_term_sharing(self) -> float:
        """Fraction of per-word AND terms this schedule does NOT evaluate:
        1 - terms_evaluated / terms_pre_factorization.  The *realized*
        counterpart of ``CompileStats.partial_term_sharing`` (equal for
        ``compile_tm`` artifacts when no term splits — the compiler stat
        quantifies exactly the sharing this schedule exploits; with fat
        terms split into pieces both counts are piece-granular)."""
        dense = self.n_term_refs
        if dense == 0:
            return 0.0
        return 1.0 - self.n_terms / dense

    def as_dict(self) -> dict:
        return dict(
            block_c=self.block_c, block_j=self.block_j, block_t=self.block_t,
            term_w=self.term_w, n_terms=self.n_terms, n_tiles=self.n_tiles,
            n_term_tiles=self.n_term_tiles,
            realized_term_sharing=self.realized_term_sharing,
        )


def pick_term_width(include_words: np.ndarray) -> int:
    """Auto bit-chain width for an artifact's term table: the smallest
    power of two covering the 95th-percentile popcount of its unique
    (word, value) terms, clipped to [2, 32].  Trained TM terms are mostly
    1-2 bits, so a narrow fixed row keeps stage-1 gather work ~2 rows per
    term; the rare fat term (thermometer-run includes) splits into pieces
    instead of widening every row."""
    iw = np.ascontiguousarray(np.asarray(include_words, dtype=np.uint32))
    act_c, act_w = np.nonzero(iw)
    if act_c.size == 0:
        return 2
    key = (act_w.astype(np.uint64) << np.uint64(32)) \
        | iw[act_c, act_w].astype(np.uint64)
    vals = (np.unique(key) & np.uint64(0xFFFFFFFF)).astype(np.uint32)
    pcs = np.array([int(v).bit_count() for v in vals])
    p95 = int(np.percentile(pcs, 95))
    w = 2
    while w < min(max(p95, 2), 32):
        w *= 2
    return w


def build_factorized_schedule(
    include_words: np.ndarray,
    *,
    block_c: int = DEFAULT_BLOCK_C,
    block_j: int = DEFAULT_BLOCK_J,
    block_t: int = DEFAULT_BLOCK_T,
    term_w: int | None = None,
    pad_tiles_to: int | None = None,
) -> FactorizedSchedule:
    """Compile ``(U, Wa)`` packed include rows into a factorized schedule.

    Rows are taken in the given order (``compile_tm`` has already applied
    ``cluster_order``).  Terms are ordered by (word, value) so the term
    table inherits the words' DMA locality.  A (word, value) term whose
    popcount exceeds ``term_w`` (default: :func:`pick_term_width`) is
    split into deduped PIECES of ``<= term_w`` bits — a piece is itself a
    (word, sub-pattern) AND term, two fat terms sharing a sub-pattern
    share its piece, and the owning clauses chain every piece, so the
    factorization stays exact.  ``pad_tiles_to`` appends no-op clause
    tiles so shards of one artifact can share a common tile-table shape
    (the cross-shard equalizer, as in ``build_schedule``).
    """
    iw = np.ascontiguousarray(np.asarray(include_words, dtype=np.uint32))
    U, Wa = iw.shape
    n_lit_bits = Wa * 32
    if term_w is None:
        term_w = pick_term_width(iw)

    # word-term table: unique nonzero (word, value) pairs, (word, value)
    # sorted; then split into <= term_w-bit pieces, deduped by bit pattern
    act_c, act_w = np.nonzero(iw)
    vals = iw[act_c, act_w]
    key = (act_w.astype(np.uint64) << np.uint64(32)) | vals.astype(np.uint64)
    uniq_key, wterm_of_entry = np.unique(key, return_inverse=True)
    piece_id: dict = {}
    term_word_l: list = []
    term_val_l: list = []
    term_chain_l: list = []
    pieces_of_wterm: list = []
    for k in uniq_key:
        w = int(k >> np.uint64(32))
        v = int(k & np.uint64(0xFFFFFFFF))
        bits = [i for i in range(32) if v >> i & 1]
        ids = []
        for lo in range(0, len(bits), term_w):
            chunk = tuple(bits[lo:lo + term_w])
            pk = (w, chunk)
            if pk not in piece_id:
                piece_id[pk] = len(term_chain_l)
                term_word_l.append(w)
                term_val_l.append(sum(1 << b for b in chunk))
                term_chain_l.append([32 * w + b for b in chunk])
            ids.append(piece_id[pk])
        pieces_of_wterm.append(ids)
    n_terms = len(term_chain_l)
    term_word = np.asarray(term_word_l, np.int32).reshape(-1)
    term_val = np.asarray(term_val_l, np.uint32).reshape(-1)

    block_t = max(min(block_t, _rup(max(n_terms + 1, 1), 8)), 1)
    Tp = _rup(n_terms + 1, block_t)   # >= 1 all-ones padding term (sentinel)
    term_chain = np.full((Tp, term_w), n_lit_bits, np.int32)
    for t, lids in enumerate(term_chain_l):
        term_chain[t, : len(lids)] = lids

    # clause chains over term (piece) ids — one step per active word piece
    chain_of_clause: list = [[] for _ in range(U)]
    for c, wt in zip(act_c, wterm_of_entry.reshape(-1)):
        chain_of_clause[c].extend(pieces_of_wterm[wt])
    block_c = max(min(block_c, _rup(max(U, 1), 8)), 1)
    Cp = _rup(max(U, 1), block_c)
    per_clause = np.zeros(Cp, np.int32)
    for c in range(U):
        per_clause[c] = len(chain_of_clause[c])

    n_cblocks = Cp // block_c
    counts = np.zeros(n_cblocks, np.int32)
    for b in range(n_cblocks):
        j_max = int(per_clause[b * block_c:(b + 1) * block_c].max())
        counts[b] = -(-j_max // block_j)
    indptr = np.concatenate([[0], np.cumsum(counts)]).astype(np.int32)

    n_term_tiles = Tp // block_t
    T_clause_real = int(counts.sum())
    T_real = n_term_tiles + T_clause_real
    T = max(T_real, pad_tiles_to or 0)
    n_jblocks = int(counts.max()) if T_clause_real else 0
    pad_jblock = n_jblocks if T > T_real or n_jblocks == 0 else None
    if pad_jblock is not None:
        n_jblocks += 1                    # all-sentinel block for no-op tiles
    Jp = n_jblocks * block_j

    clause_chain = np.full((Cp, max(Jp, block_j)), n_terms, np.int32)
    for c in range(U):
        ids = chain_of_clause[c]
        clause_chain[c, : len(ids)] = sorted(ids)

    tile_stage = np.ones(max(T, 1), np.int32)
    tile_tb = np.zeros(max(T, 1), np.int32)
    tile_cb = np.zeros(max(T, 1), np.int32)
    tile_jb = np.zeros(max(T, 1), np.int32)
    tile_first = np.zeros(max(T, 1), np.int32)
    tile_last = np.zeros(max(T, 1), np.int32)
    # stage 1 first: every term is in scratch before any clause tile reads it
    for t in range(n_term_tiles):
        tile_stage[t] = 0
        tile_tb[t] = t
    t = n_term_tiles
    for b in range(n_cblocks):
        n = int(counts[b])
        for j in range(n):
            tile_cb[t], tile_jb[t] = b, j
            tile_first[t] = int(j == 0)
            tile_last[t] = int(j == n - 1)
            t += 1
    # no-op padding tiles: all-sentinel clause chain block, never first/last
    for tt_ in range(t, T):
        tile_jb[tt_] = pad_jblock if pad_jblock is not None else 0

    return FactorizedSchedule(
        block_c=block_c, block_j=block_j, block_t=block_t, term_w=term_w,
        n_rows=U, n_terms=n_terms, n_lit_bits=n_lit_bits,
        term_word=term_word, term_val=term_val,
        term_chain=term_chain, clause_chain=clause_chain,
        tile_stage=tile_stage[:T] if T else tile_stage[:0],
        tile_tb=tile_tb[:T] if T else tile_tb[:0],
        tile_cb=tile_cb[:T] if T else tile_cb[:0],
        tile_jb=tile_jb[:T] if T else tile_jb[:0],
        tile_first=tile_first[:T] if T else tile_first[:0],
        tile_last=tile_last[:T] if T else tile_last[:0],
        counts=counts, indptr=indptr,
    )


# identity-hashed jit static args: repeated builds for the same artifact +
# tiling must return the SAME object (see sparse_infer._SCHEDULE_CACHE)
_FSCHEDULE_CACHE: dict = {}


def build_factorized_schedule_cached(
    include_words: np.ndarray,
    *,
    block_c: int = DEFAULT_BLOCK_C,
    block_j: int = DEFAULT_BLOCK_J,
    block_t: int = DEFAULT_BLOCK_T,
    term_w: int | None = None,
) -> FactorizedSchedule:
    """Content-memoized :func:`build_factorized_schedule` for callers
    without a :class:`CompiledTM` to memoize on."""
    if term_w is None:
        term_w = pick_term_width(include_words)
    key = (artifact_tag(include_words), block_c, block_j, block_t, term_w)
    if key not in _FSCHEDULE_CACHE:
        _FSCHEDULE_CACHE[key] = build_factorized_schedule(
            np.asarray(include_words, dtype=np.uint32),
            block_c=block_c, block_j=block_j, block_t=block_t,
            term_w=term_w)
    return _FSCHEDULE_CACHE[key]


def _term_infer_kernel(
    *refs,
    # positional refs: tstage, ttb, tcb, tjb, tfirst, tlast, [tmargin,]
    # litT, tchain, cchain, hi, lo -> out, term scratch, ok scratch
    # [, done scratch]
    #   tstage       (T,) scalar-prefetch: 0 = term tile, 1 = clause tile
    #   ttb          (T,) scalar-prefetch: term-block id per stage-1 tile
    #   tcb/tjb      (T,) scalar-prefetch: clause-/chain-block id (stage 2)
    #   tfirst/tlast (T,) scalar-prefetch: first/last clause tile of block
    #   tmargin      (T,) scalar-prefetch: residual vote swing after tile t
    #   litT         (L + 1, block_s) uint32 bit-transposed literal slab
    #   tchain       SMEM (1, block_t * term_w) literal ids of the term tile
    #   cchain       SMEM (1, block_c * block_j) term ids of the clause tile
    #   hi/lo        (block_c, Kp) int8 vote limbs (fused_infer.vote_limbs)
    #   out          (32, block_s, Kp) int32 class sums (sample-bit major)
    #   term         VMEM scratch (Tp, block_s) uint32 term bitvectors
    #   ok           VMEM scratch (block_c, block_s) uint32 carried bits
    #   done         SMEM scratch (1,) int32 — slab certified, skip tiles
    block_t: int,
    block_j: int,
    term_w: int,
    n_classes: int = 0,
    n_samples: int = 0,
    early_exit: bool = False,
):
    if early_exit:
        (tstage_ref, ttb_ref, tcb_ref, tjb_ref, tfirst_ref, tlast_ref,
         tmargin_ref, litT_ref, tchain_ref, cchain_ref, hi_ref, lo_ref,
         out_ref, term_ref, ok_ref, done_ref) = refs
    else:
        (tstage_ref, ttb_ref, tcb_ref, tjb_ref, tfirst_ref, tlast_ref,
         litT_ref, tchain_ref, cchain_ref, hi_ref, lo_ref,
         out_ref, term_ref, ok_ref) = refs
        tmargin_ref = done_ref = None
    t = pl.program_id(1)
    slab = pl.program_id(0)   # hoisted: program_id can't lower inside pl.when

    @pl.when(t == 0)
    def _init_out():
        out_ref[...] = jnp.zeros_like(out_ref)
        if early_exit:
            done_ref[0] = 0

    active = done_ref[0] == 0 if early_exit else True

    stage0 = tstage_ref[t] == 0
    if early_exit:   # a certified slab skips every remaining tile
        stage0 = jnp.logical_and(stage0, active)

    @pl.when(stage0)
    def _eval_terms():
        # stage 1: each of the tile's block_t unique terms is evaluated
        # once for the whole sample slab; sentinel ids land on the
        # all-ones row, so padding terms come out constant 1 (the
        # clause-chain AND identity)
        base = ttb_ref[t] * block_t
        ones = jnp.full((1, term_ref.shape[1]), 0xFFFFFFFF, jnp.uint32)

        def body(r, carry):
            term_ref[pl.ds(base + r, 1), :] = chain_and(
                ones, litT_ref, tchain_ref, r * term_w, term_w)
            return carry

        jax.lax.fori_loop(0, block_t, body, 0)

    stage1 = tstage_ref[t] == 1
    if early_exit:
        stage1 = jnp.logical_and(stage1, active)

    @pl.when(stage1)
    def _clause_tile():
        @pl.when(tfirst_ref[t] == 1)
        def _init_ok():   # chain start: every clause alive for every sample
            ok_ref[...] = jnp.full_like(ok_ref, 0xFFFFFFFF)

        # stage 2: one chain step per ACTIVE WORD — a single-row read of
        # the term's precomputed bitvector instead of its bit chain; a
        # slab whose clauses are all dead already skips the chain
        @pl.when(jnp.any(ok_ref[...] != 0))
        def _chain():
            walk_chains(ok_ref, term_ref, cchain_ref, block_j)

        @pl.when(tlast_ref[t] == 1)
        def _fold():    # adder bank: fold each sample bit's votes
            fold_sample_bits(out_ref, ok_ref[...], hi_ref[...], lo_ref[...])
            if early_exit:
                certify_slab(out_ref, done_ref, tmargin_ref[t], slab,
                             n_classes, n_samples)


@functools.partial(
    jax.jit,
    static_argnames=("schedule", "block_s", "interpret"),
)
def factorized_tm_forward(
    lit_words: jax.Array,       # (B, W) uint32 packed literals
    votes: jax.Array,           # (U, K) int32 — rows aligned with schedule
    schedule: FactorizedSchedule,
    *,
    block_s: int = DEFAULT_BLOCK_S,
    interpret: bool = False,
    tile_margin: jax.Array | None = None,   # (T,) residual swing after tile t
) -> jax.Array:
    """Packed literals -> (B, K) int32 class sums via the factorized
    schedule.  Bit-identical to the sparse chain kernel (and the dense
    oracle) for the include rows the schedule was built from.

    With ``tile_margin`` (see :mod:`repro.kernels.anytime`) the kernel
    runs in exact early-exit mode — argmax-identical to the full walk,
    sums possibly truncated once a slab certifies.
    """
    B, W = lit_words.shape
    U, K = votes.shape
    assert U <= schedule.clause_chain.shape[0], (U, schedule.clause_chain.shape)
    assert schedule.n_lit_bits == W * 32, (schedule.n_lit_bits, W)
    if schedule.n_tiles == 0:   # degenerate all-empty schedule: nothing votes
        return jnp.zeros((B, K), jnp.int32)

    Cp = schedule.clause_chain.shape[0]
    vts = jnp.pad(votes.astype(jnp.int32), ((0, Cp - U), (0, 0)))
    tiles = jnp.asarray(np.stack([
        schedule.tile_stage, schedule.tile_tb, schedule.tile_cb,
        schedule.tile_jb, schedule.tile_first, schedule.tile_last,
    ]))   # padded clauses fire vacuously but vote 0
    return factorized_tm_forward_tables(
        lit_words, jnp.asarray(schedule.term_chain),
        jnp.asarray(schedule.clause_chain), vts, tiles,
        block_t=schedule.block_t, block_c=schedule.block_c,
        block_j=schedule.block_j, block_s=block_s, interpret=interpret,
        tile_margin=tile_margin,
    )   # term_w rides on term_chain.shape[1]


def factorized_tm_forward_tables(
    lit_words: jax.Array,       # (B, W) uint32
    term_chain: jax.Array,      # (Tp, term_w) int32
    clause_chain: jax.Array,    # (Cp, Jp) int32
    votes: jax.Array,           # (Cp, K) int32 (already padded rows)
    tiles: jax.Array,           # (6, T) int32 — stage, tb, cb, jb, first, last
    *,
    block_t: int,
    block_c: int,
    block_j: int,
    block_s: int = DEFAULT_BLOCK_S,
    interpret: bool = False,
    tile_margin: jax.Array | None = None,
) -> jax.Array:
    """Traced-table twin of :func:`factorized_tm_forward` for ``shard_map``
    bodies: term/clause/tile tables arrive as (sharded) arrays instead of a
    static schedule, so one jit serves every shard."""
    B, W = lit_words.shape
    Tp, term_w = term_chain.shape
    Cp = clause_chain.shape[0]
    K = votes.shape[1]
    Kp = _rup(K, 128)
    block_s = max(min(block_s, packetizer.n_words(B)), 1)

    litT = literal_slabs(lit_words, block_s)
    tchain = smem_tiles(term_chain, block_t, term_w)
    cchain = smem_tiles(clause_chain, block_c, block_j)
    hi, lo = vote_limbs(votes, Cp, Kp)

    early_exit = tile_margin is not None
    scratch = [
        pltpu.VMEM((Tp, block_s), jnp.uint32),
        pltpu.VMEM((block_c, block_s), jnp.uint32),
    ]
    if early_exit:
        scratch.append(pltpu.SMEM((1,), jnp.int32))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=7 if early_exit else 6,
        grid=(litT.shape[0], tiles.shape[1]),
        in_specs=[
            pl.BlockSpec((None, W * 32 + 1, block_s),
                         lambda s, t, *refs: (s, 0, 0)),
            pl.BlockSpec((None, None, 1, block_t * term_w),
                         lambda s, t, stg, tb, *refs: (tb[t], 0, 0, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((None, None, 1, block_c * block_j),
                         lambda s, t, stg, tb, cb, jb, *refs:
                         (cb[t], jb[t], 0, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((block_c, Kp),
                         lambda s, t, stg, tb, cb, jb, *refs: (cb[t], 0)),
            pl.BlockSpec((block_c, Kp),
                         lambda s, t, stg, tb, cb, jb, *refs: (cb[t], 0)),
        ],
        out_specs=pl.BlockSpec((None, 32, block_s, Kp),
                               lambda s, t, *refs: (s, 0, 0, 0)),
        scratch_shapes=scratch,
    )
    prefetch = [tiles[0], tiles[1], tiles[2], tiles[3], tiles[4], tiles[5]]
    if early_exit:
        prefetch.append(jnp.asarray(tile_margin, jnp.int32))
    out = pl.pallas_call(
        functools.partial(
            _term_infer_kernel,
            block_t=block_t, block_j=block_j, term_w=term_w,
            n_classes=K, n_samples=B, early_exit=early_exit,
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((litT.shape[0], 32, block_s, Kp),
                                       jnp.int32),
        # the whole-table term scratch grows with the artifact (its
        # block_s lanes pad to 128), so a large bank outgrows the default
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=vmem_limit_bytes(
                4 * _rup(block_s, 128) * (Tp + block_c + 2 * (W * 32 + 1))
                + 2 * 2 * block_c * Kp + 2 * 4 * 32 * block_s * Kp)),
        interpret=interpret,
    )(*prefetch, litT, tchain, cchain, hi, lo)
    return unslab_sums(out, B, K)


def factorized_class_sums_ref(
    lit_words: jax.Array,       # (B, W) uint32
    term_chain: jax.Array,      # (Tp, term_w) int32 (sentinel = W * 32)
    clause_chain: jax.Array,    # (Cp, Jp) int32 (sentinel = a padding term)
    votes: jax.Array,           # (Cp, K) int32
) -> jax.Array:
    """jnp oracle over the factorized tables (the non-kernel engine of the
    sharded factorized path): terms fire iff every chain literal is 1
    (sentinel ids read constant 1), clauses fire iff every chained term
    fires.  Bit-identical to the Pallas factorized kernel."""
    B, W = lit_words.shape
    bits = packetizer.unpack_bits(lit_words, W * 32)          # (B, L)
    padded = jnp.concatenate(
        [bits, jnp.ones((B, 1), bits.dtype)], axis=1)         # lit sentinel
    tg = jnp.take(padded, term_chain.reshape(-1), axis=1)
    term_bits = jnp.all(
        tg.reshape(B, *term_chain.shape) != 0, axis=2)        # (B, Tp)
    cg = jnp.take(term_bits, clause_chain.reshape(-1), axis=1)
    fired = jnp.all(cg.reshape(B, *clause_chain.shape), axis=2)
    return fired.astype(jnp.int32) @ votes.astype(jnp.int32)


def stack_shard_factorized(
    include_words: np.ndarray,      # (U, Wa) — compile_tm row order
    votes: np.ndarray,              # (U, K)
    n_shards: int,
    *,
    block_c: int = DEFAULT_BLOCK_C,
    block_j: int = DEFAULT_BLOCK_J,
    block_t: int = DEFAULT_BLOCK_T,
    term_w: int | None = None,
):
    """Clause-shard a factorized schedule: each shard carries its OWN term
    table (terms are extracted from the shard's local rows — cross-shard
    sharing would need a replicated global table, more wire than it saves)
    plus its own tile table, all padded to common shapes so the stacks
    shard over ``model``.  ``term_w`` defaults to the FULL artifact's
    :func:`pick_term_width`, so every shard's term rows share one width.

    Returns ``(schedules, term_stack, chain_stack, votes_stack, tile_stack,
    C_loc)``: per-shard :class:`FactorizedSchedule` objects, the
    ``(n_shards, Tp, term_w)`` term-chain stack, the ``(n_shards, C_loc_p,
    Jp)`` clause-chain stack, the matching vote stack, and the ``(n_shards,
    6, T)`` tile table.  Shards with fewer tiles ride on no-op padding tiles;
    partial class sums compose exactly through one int32 ``psum``.
    """
    iw = np.ascontiguousarray(np.asarray(include_words, dtype=np.uint32))
    U, Wa = iw.shape
    K = votes.shape[1]
    if term_w is None:
        term_w = pick_term_width(iw)
    C_loc = -(-max(U, 1) // n_shards)
    C_loc = _rup(C_loc, 8)
    Up = C_loc * n_shards
    iw = np.pad(iw, ((0, Up - U), (0, 0)))
    vt = np.pad(np.asarray(votes, np.int32), ((0, Up - U), (0, 0)))

    def build_all(bt, pad=None):
        return [
            build_factorized_schedule(iw[s * C_loc:(s + 1) * C_loc],
                                      block_c=block_c, block_j=block_j,
                                      block_t=bt, term_w=term_w,
                                      pad_tiles_to=pad)
            for s in range(n_shards)
        ]

    # one static block_t must serve every shard's term tiles: take the
    # smallest post-clip value (a shard with fewer terms clips harder),
    # then rebuild all shards at it so tile tables stay consistent
    block_t = min(s.block_t for s in build_all(block_t))
    schedules = build_all(block_t)
    T = max(max(s.n_tiles for s in schedules), 1)
    schedules = build_all(block_t, pad=T)
    Tp = max(s.term_chain.shape[0] for s in schedules)
    Jp = max(s.clause_chain.shape[1] for s in schedules)
    Cp = max(s.clause_chain.shape[0] for s in schedules)

    term_stack = np.full((n_shards, Tp, term_w), Wa * 32, np.int32)
    chain_stack = np.zeros((n_shards, Cp, Jp), np.int32)
    votes_stack = np.zeros((n_shards, Cp, K), np.int32)
    tile_stack = np.zeros((n_shards, 6, T), np.int32)
    for s, sched in enumerate(schedules):
        tp = sched.term_chain.shape[0]
        cp, jp = sched.clause_chain.shape
        term_stack[s, :tp] = sched.term_chain
        # padding term rows (>= tp) are all-sentinel: they evaluate to
        # constant 1, so a shorter shard's sentinel ids stay exact
        chain_stack[s] = sched.n_terms   # shard-local sentinel everywhere
        chain_stack[s, :cp, :jp] = sched.clause_chain
        votes_stack[s, :C_loc] = vt[s * C_loc:(s + 1) * C_loc]
        tile_stack[s, 0] = sched.tile_stage
        tile_stack[s, 1] = sched.tile_tb
        tile_stack[s, 2] = sched.tile_cb
        tile_stack[s, 3] = sched.tile_jb
        tile_stack[s, 4] = sched.tile_first
        tile_stack[s, 5] = sched.tile_last
    return schedules, term_stack, chain_stack, votes_stack, tile_stack, C_loc
