"""Pallas TPU kernel: block-sparse compiled TM inference over a chain schedule.

The dense fused kernel (``fused_infer.py``) streams EVERY literal word for
every clause block — on a trained model that is almost all wasted work:
MATADOR's central observation (paper §II) is that a trained clause includes
a miniscule fraction of its literals, so its AND chain needs only the
included bits.  This kernel executes a **compiled chain schedule** emitted
by ``core/compiler.py``:

  * unique clauses are clustered by (chain length, active-word signature) so
    clauses with similar include structure land in the same clause block;
  * each clause's include BITS become a compacted chain — a sorted list of
    literal ids, padded with a sentinel id whose literal column is constant
    1 (an AND identity, so ragged chains stay exact);
  * per clause block, the chain splits into ``(block_c, block_j)`` tiles and
    a CSR-like table records each block's tile count; the flattened tile
    list (clause-block id, chain-block id, first/last flags) is
    scalar-prefetched so the grid only visits tiles that exist — the
    block-sparse flash-attention pattern, with the ragged inner grid driven
    by ``PrefetchScalarGridSpec`` index maps.

The datapath is bit-parallel over SAMPLES (the hardware trick of the TM
accelerators the paper cites): literals are bit-transposed so row ``l`` of
``litT`` packs literal ``l`` of 32 consecutive datapoints into one uint32.
The carried clause state (``Clause In``/``Clause Out`` of paper Fig. 5) is
then a (block_c, block_s) bitvector in VMEM scratch, and one chain step is
``ok[c] &= litT[chain_id]`` — work scales with the number of INCLUDE BITS
in the artifact, not with ``C x W``.  The tile's chain ids ride in SMEM, so
every step is a scalar-indexed single-row read of the literal slab (Mosaic
lowers no vector gather).  A tile whose carried clause state is already
all-zero (every clause in the block dead for every sample in the slab)
skips its chain entirely.

On the last tile of a block the finished clause bits are folded, one
sample bit at a time, into the int32 class sums through the deduped
multiplicity x polarity vote matrix (int8 MXU dots, exact) — dedup fan-out
stays in the kernel, and the fired matrix never exists in HBM.

Correctness contract: all-zero include rows (clause-padding and the
degenerate all-empty artifact) FIRE under this kernel (vacuous AND), so
their vote rows must be zero — true for every ``compile_tm`` artifact
(empty clauses are dropped at compile time).  Do not point this kernel at
a raw (uncompiled) model whose empty clauses carry votes.

The schedule path is validated bit-exactly against the jnp oracle in
Pallas interpret mode, and compiled for a described TPU v5e by
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
from repro.kernels.fused_infer import _rup, fold_votes, vote_limbs

# default chain tiling: 512-clause banks, 32-bit chain tiles, 16-word
# (512-sample) slabs — see kernels/autotune.py for the swept alternatives
DEFAULT_BLOCK_C = 512
DEFAULT_BLOCK_J = 32
DEFAULT_BLOCK_S = 16


# eq=False: identity hashing, so a schedule works as a jit static argument
# (its ndarray fields are unhashable by value); compile memoizes schedules
# per artifact, so identity is stable across calls.
@dataclasses.dataclass(frozen=True, eq=False)
class SparseSchedule:
    """Compiled block-sparse execution schedule for one clause bank.

    ``chain_ids[c, j]`` is the literal BIT id of clause ``c``'s ``j``-th
    chain step in the packed-word bit layout (literal ``32*w + i`` = bit
    ``i`` of word ``w``); entries past the clause's include count hold
    ``sentinel`` (= ``n_lit_bits``), whose transposed literal row is
    constant 1.  ``counts``/``indptr`` are the CSR view over chain tiles
    per clause block; ``tile_*`` are the flattened (scalar-prefetched)
    tile table the kernel's ragged grid walks.  Tiles with
    ``tile_first == tile_last == 0`` and an all-sentinel chain block are
    no-op padding (used to equalize tile counts across shards).
    """

    block_c: int
    block_j: int
    n_rows: int                 # unique clauses covered (pre-padding)
    n_lit_bits: int             # sentinel id == index of the all-ones row
    chain_ids: np.ndarray       # (Cp, Jp) int32
    tile_cb: np.ndarray         # (T,) int32 clause-block id per tile
    tile_jb: np.ndarray         # (T,) int32 chain-block id per tile
    tile_first: np.ndarray      # (T,) int32 1 = first tile of its block
    tile_last: np.ndarray       # (T,) int32 1 = last tile of its block
    counts: np.ndarray          # (n_cblocks,) int32 tiles per clause block
    indptr: np.ndarray          # (n_cblocks + 1,) int32 CSR row pointers

    @property
    def n_tiles(self) -> int:
        return int(self.tile_cb.shape[0])

    @property
    def n_cblocks(self) -> int:
        return int(self.counts.shape[0])

    @property
    def n_tiles_dense(self) -> int:
        """Tiles a dense chain over the full literal space would visit."""
        per_block = -(-self.n_lit_bits // self.block_j)
        return self.n_cblocks * per_block

    @property
    def tile_sparsity(self) -> float:
        """Fraction of the dense (clause-block x chain-block) grid skipped."""
        dense = self.n_tiles_dense
        real = int(self.counts.sum())   # padding tiles are not chain work
        return 1.0 - real / dense if dense else 0.0

    def as_dict(self) -> dict:
        return dict(
            block_c=self.block_c, block_j=self.block_j,
            n_tiles=self.n_tiles, n_tiles_dense=self.n_tiles_dense,
            tile_sparsity=self.tile_sparsity,
        )


def cluster_order(include_words: np.ndarray) -> np.ndarray:
    """Clause permutation that clusters rows by chain structure.

    Primary key: include-bit count (chain length), so clause blocks are
    chain-length homogeneous and the per-block padded chain ``Jp`` tracks
    the block's own clauses instead of the global maximum.  Secondary:
    active-word signature then word values, lexicographic — clauses sharing
    sub-chains become block neighbours (DMA locality, and the whole block's
    carried state dies together for the early-exit).
    """
    iw = np.ascontiguousarray(include_words)
    U, Wa = iw.shape
    if U <= 1:
        return np.arange(U)
    act = iw != 0
    nbits = packetizer.unpack_bits_np(iw, Wa * 32).sum(axis=1)
    # np.lexsort: LAST key is primary
    keys = [iw[:, j] for j in range(Wa - 1, -1, -1)]
    keys += [act[:, j].astype(np.uint8) for j in range(Wa - 1, -1, -1)]
    keys.append(nbits)
    return np.lexsort(keys)


def artifact_tag(include_words) -> str:
    """Content hash of an artifact's include rows — THE identity of a
    compiled bank for schedule memoization and autotune cache keys (two
    same-shape artifacts with different sparsity must never share)."""
    import hashlib

    iw = np.ascontiguousarray(np.asarray(include_words, dtype=np.uint32))
    h = hashlib.sha1(iw.tobytes())
    h.update(str(iw.shape).encode())
    return h.hexdigest()


# schedules are identity-hashed jit static args, so repeated builds for the
# same artifact+tiling must return the SAME object or every call re-lowers
# the kernel; keyed by the artifact content hash.
_SCHEDULE_CACHE: dict = {}


def build_schedule_cached(
    include_words: np.ndarray,
    *,
    block_c: int = DEFAULT_BLOCK_C,
    block_j: int = DEFAULT_BLOCK_J,
) -> SparseSchedule:
    """Content-memoized :func:`build_schedule` for callers without a
    :class:`CompiledTM` to memoize on (e.g. ``ops.tm_forward_schedule``
    called with raw include rows in a serving loop)."""
    key = (artifact_tag(include_words), block_c, block_j)
    if key not in _SCHEDULE_CACHE:
        _SCHEDULE_CACHE[key] = build_schedule(
            np.asarray(include_words, dtype=np.uint32),
            block_c=block_c, block_j=block_j)
    return _SCHEDULE_CACHE[key]


def build_schedule(
    include_words: np.ndarray,
    *,
    block_c: int = DEFAULT_BLOCK_C,
    block_j: int = DEFAULT_BLOCK_J,
    pad_tiles_to: int | None = None,
) -> SparseSchedule:
    """Compile ``(U, Wa)`` packed include rows into a chain schedule.

    Rows are taken in the given order (``compile_tm`` has already applied
    :func:`cluster_order`).  ``pad_tiles_to`` appends no-op tiles so
    shards of one artifact can share a common tile-table shape.
    """
    iw = np.ascontiguousarray(np.asarray(include_words, dtype=np.uint32))
    U, Wa = iw.shape
    n_lit_bits = Wa * 32
    block_c = max(min(block_c, _rup(max(U, 1), 8)), 1)
    Cp = _rup(max(U, 1), block_c)
    bits = np.zeros((Cp, n_lit_bits), np.uint8)
    if U:
        bits[:U] = packetizer.unpack_bits_np(iw, n_lit_bits)

    n_cblocks = Cp // block_c
    counts = np.zeros(n_cblocks, np.int32)
    per_clause = bits.sum(axis=1)
    for b in range(n_cblocks):
        j_max = int(per_clause[b * block_c:(b + 1) * block_c].max())
        counts[b] = -(-j_max // block_j)
    indptr = np.concatenate([[0], np.cumsum(counts)]).astype(np.int32)

    T_real = int(counts.sum())
    T = max(T_real, pad_tiles_to or 0)
    n_jblocks = int(counts.max()) if T_real else 0
    pad_jblock = n_jblocks if T > T_real or n_jblocks == 0 else None
    if pad_jblock is not None:
        n_jblocks += 1                    # all-sentinel block for no-op tiles
    Jp = n_jblocks * block_j

    chain_ids = np.full((Cp, max(Jp, block_j)), n_lit_bits, np.int32)
    for c in range(Cp):
        (lids,) = np.nonzero(bits[c])
        chain_ids[c, : lids.shape[0]] = lids

    tile_cb = np.zeros(max(T, 1), np.int32)
    tile_jb = np.zeros(max(T, 1), np.int32)
    tile_first = np.zeros(max(T, 1), np.int32)
    tile_last = np.zeros(max(T, 1), np.int32)
    t = 0
    for b in range(n_cblocks):
        n = int(counts[b])
        for j in range(n):
            tile_cb[t], tile_jb[t] = b, j
            tile_first[t] = int(j == 0)
            tile_last[t] = int(j == n - 1)
            t += 1
    # no-op padding tiles: all-sentinel chain block, never first/last
    for tt in range(t, T):
        tile_cb[tt] = 0
        tile_jb[tt] = pad_jblock if pad_jblock is not None else 0

    return SparseSchedule(
        block_c=block_c, block_j=block_j, n_rows=U, n_lit_bits=n_lit_bits,
        chain_ids=chain_ids,
        tile_cb=tile_cb[:T] if T else tile_cb[:0],
        tile_jb=tile_jb[:T] if T else tile_jb[:0],
        tile_first=tile_first[:T] if T else tile_first[:0],
        tile_last=tile_last[:T] if T else tile_last[:0],
        counts=counts, indptr=indptr,
    )


def build_schedule_incremental(
    include_words: np.ndarray,
    prev: SparseSchedule,
    prev_include_words: np.ndarray,
    *,
    block_c: int = DEFAULT_BLOCK_C,
    block_j: int = DEFAULT_BLOCK_J,
) -> tuple[SparseSchedule, dict]:
    """Rebuild a chain schedule, reusing ``prev``'s chain rows where the
    include bits did not move.

    The expensive part of :func:`build_schedule` is the per-clause
    ``nonzero`` loop that compacts include bits into literal-id chains;
    online drift touches a small fraction of clauses, so rows whose packed
    include words are identical to ``prev_include_words`` copy their chain
    straight out of ``prev.chain_ids`` (sentinel padding is layout-
    compatible because the literal space and tiling are checked first).
    The tile table and CSR counts are always rebuilt — they are cheap and
    depend on the global chain-length maximum.

    Returns ``(schedule, info)`` where ``info`` reports ``rows_reused`` /
    ``rows_rebuilt`` / ``tiles_reused`` (tiles of clause blocks with no
    changed row).  The result is bit-exact against a from-scratch
    :func:`build_schedule`; incompatible layouts (different row count,
    word count, or effective tiling) fall back to the full build with
    zero reuse.
    """
    iw = np.ascontiguousarray(np.asarray(include_words, dtype=np.uint32))
    piw = np.ascontiguousarray(np.asarray(prev_include_words, dtype=np.uint32))
    U, Wa = iw.shape
    n_lit_bits = Wa * 32
    eff_block_c = max(min(block_c, _rup(max(U, 1), 8)), 1)
    if (piw.shape != iw.shape
            or prev.block_c != eff_block_c or prev.block_j != block_j
            or prev.n_rows != U or prev.n_lit_bits != n_lit_bits):
        full = build_schedule(iw, block_c=block_c, block_j=block_j)
        return full, dict(rows_reused=0, rows_rebuilt=U, tiles_reused=0)

    Cp = _rup(max(U, 1), eff_block_c)
    bits = np.zeros((Cp, n_lit_bits), np.uint8)
    if U:
        bits[:U] = packetizer.unpack_bits_np(iw, n_lit_bits)

    n_cblocks = Cp // eff_block_c
    counts = np.zeros(n_cblocks, np.int32)
    per_clause = bits.sum(axis=1)
    for b in range(n_cblocks):
        j_max = int(per_clause[b * eff_block_c:(b + 1) * eff_block_c].max())
        counts[b] = -(-j_max // block_j)
    indptr = np.concatenate([[0], np.cumsum(counts)]).astype(np.int32)

    T_real = int(counts.sum())
    T = T_real
    n_jblocks = int(counts.max()) if T_real else 0
    pad_jblock = n_jblocks if n_jblocks == 0 else None
    if pad_jblock is not None:
        n_jblocks += 1
    Jp = n_jblocks * block_j

    row_same = np.zeros(Cp, bool)
    row_same[:U] = (iw == piw).all(axis=1)
    row_same[U:] = True                  # padding rows are sentinel in both

    width = max(Jp, block_j)
    chain_ids = np.full((Cp, width), n_lit_bits, np.int32)
    copy_w = min(width, prev.chain_ids.shape[1])
    # a reused row's chain fits the new width: its include count bounds the
    # new global j_max, and entries past the chain are sentinel either way
    chain_ids[row_same, :copy_w] = prev.chain_ids[row_same, :copy_w]
    for c in np.nonzero(~row_same)[0]:
        (lids,) = np.nonzero(bits[c])
        chain_ids[c, :lids.shape[0]] = lids

    tile_cb = np.zeros(max(T, 1), np.int32)
    tile_jb = np.zeros(max(T, 1), np.int32)
    tile_first = np.zeros(max(T, 1), np.int32)
    tile_last = np.zeros(max(T, 1), np.int32)
    t = 0
    for b in range(n_cblocks):
        n = int(counts[b])
        for j in range(n):
            tile_cb[t], tile_jb[t] = b, j
            tile_first[t] = int(j == 0)
            tile_last[t] = int(j == n - 1)
            t += 1

    block_clean = row_same.reshape(n_cblocks, eff_block_c).all(axis=1)
    sched = SparseSchedule(
        block_c=eff_block_c, block_j=block_j, n_rows=U, n_lit_bits=n_lit_bits,
        chain_ids=chain_ids,
        tile_cb=tile_cb[:T] if T else tile_cb[:0],
        tile_jb=tile_jb[:T] if T else tile_jb[:0],
        tile_first=tile_first[:T] if T else tile_first[:0],
        tile_last=tile_last[:T] if T else tile_last[:0],
        counts=counts, indptr=indptr,
    )
    info = dict(
        rows_reused=int(row_same[:U].sum()),
        rows_rebuilt=int(U - row_same[:U].sum()),
        tiles_reused=int(counts[block_clean].sum()),
    )
    return sched, info


def bit_transpose_literals(lit_words: jax.Array, n_lit_bits: int) -> jax.Array:
    """(B, W) packed literal words -> (n_lit_bits + 1, ceil(B/32)) uint32.

    Row ``l`` packs literal ``l`` of 32 consecutive samples per word
    (LSB-first, matching ``packetizer.pack_bits``); the appended final row
    is constant 1 — the chain sentinel's AND identity.  Padding samples
    beyond ``B`` read as literal 0, so any clause with at least one include
    reports 0 for them (and all-zero rows only ever carry zero votes).
    """
    bits = packetizer.unpack_bits(lit_words, n_lit_bits)      # (B, L)
    lit_t = packetizer.pack_bits(bits.T)                      # (L, Sw)
    ones = jnp.full((1, lit_t.shape[1]), 0xFFFFFFFF, jnp.uint32)
    return jnp.concatenate([lit_t, ones], axis=0)


# Sentinel for masking padded class columns in the early-exit margin
# check: far below any real class sum (|sums| <= total vote mass) while
# keeping top1 - second inside int32.
_NEG_SUM = -(2 ** 28)


def _slab_lead_margin(sums, n_classes):
    """Per-sample top1 - top2 over the real class columns (the last axis);
    ties -> 0."""
    col = jax.lax.broadcasted_iota(jnp.int32, sums.shape, sums.ndim - 1)
    masked = jnp.where(col < n_classes, sums, jnp.int32(_NEG_SUM))
    top1 = jnp.max(masked, axis=-1)
    is_top = masked == top1[..., None]
    second = jnp.max(jnp.where(is_top, jnp.int32(_NEG_SUM), masked), axis=-1)
    tied = jnp.sum(is_top.astype(jnp.int32), axis=-1) > 1
    return jnp.where(tied, jnp.int32(0), top1 - second)


def literal_slabs(lit_words: jax.Array, block_s: int):
    """Bit-transposed literals cut into sample slabs: ``(Swp // block_s,
    L + 1, block_s)`` uint32, so a slab block spans the whole trailing two
    dims (Mosaic's block-shape rule holds for any ``block_s``)."""
    B, W = lit_words.shape
    litT = bit_transpose_literals(lit_words, W * 32)
    Swp = _rup(litT.shape[1], block_s)
    litT = jnp.pad(litT, ((0, 0), (0, Swp - litT.shape[1])))
    return litT.reshape(W * 32 + 1, Swp // block_s, block_s).transpose(1, 0, 2)


def smem_tiles(ids: jax.Array, rows: int, cols: int) -> jax.Array:
    """``(R, J)`` int32 id table -> ``(R // rows, J // cols, 1, rows *
    cols)``: one flat row per ``(rows, cols)`` tile, row-major within the
    tile — the layout a tile's SMEM block takes whole."""
    R, J = ids.shape
    t = ids.reshape(R // rows, rows, J // cols, cols).transpose(0, 2, 1, 3)
    return t.reshape(R // rows, J // cols, 1, rows * cols)


def chain_and(row, src_ref, ids_ref, base: int, width: int):
    """AND ``width`` rows of ``src_ref`` into ``row``: one HCB chain step
    per id ``ids_ref[0, base + j]``.  Ids are SMEM scalars, so each step
    is a dynamic single-row (sublane) read — the form of gather Mosaic
    lowers."""
    for j in range(width):
        row = row & src_ref[pl.ds(ids_ref[0, base + j], 1), :]
    return row


def walk_chains(ok_ref, src_ref, ids_ref, width: int):
    """``ok[r] &= AND_j src[ids[r, j]]`` for every row of ``ok_ref``."""
    def body(r, carry):
        ok_ref[pl.ds(r, 1), :] = chain_and(
            ok_ref[pl.ds(r, 1), :], src_ref, ids_ref, r * width, width)
        return carry

    jax.lax.fori_loop(0, ok_ref.shape[0], body, 0)


def fold_sample_bits(out_ref, ok, hi, lo):
    """Adder bank: ``out[i, s] += votes^T @ bit_i(ok[:, s])`` for each of
    the 32 sample bits packed in a word — ``out_ref`` is ``(32, block_s,
    Kp)``, sample ``32 * word + i`` at ``[i, word]``."""
    for i in range(32):
        out_ref[i] += fold_votes((ok >> i) & 1, hi, lo)


def certify_slab(out_ref, done_ref, margin, slab, n_classes, n_samples):
    """Exact early exit: mark the slab done once every real sample's lead
    STRICTLY beats ``margin`` (the residual swing) — no remaining tile can
    change any argmax in it.  Padding sample slots sum to 0 forever and
    count as certified."""
    lead = _slab_lead_margin(out_ref[...], n_classes)       # (32, block_s)
    bit = jax.lax.broadcasted_iota(jnp.int32, lead.shape, 0)
    word = jax.lax.broadcasted_iota(jnp.int32, lead.shape, 1)
    row = (slab * lead.shape[1] + word) * 32 + bit
    lead = jnp.where(row < n_samples, lead, jnp.int32(-_NEG_SUM))
    done_ref[0] = jnp.where(jnp.all(lead > margin), 1, done_ref[0])


def unslab_sums(out, B: int, K: int):
    """``(n_slabs, 32, block_s, Kp)`` kernel output -> ``(B, K)``."""
    n, _, bs, Kp = out.shape
    return out.transpose(0, 2, 1, 3).reshape(n * bs * 32, Kp)[:B, :K]


def _sparse_infer_kernel(
    *refs,
    # positional refs: tcb, tjb, tfirst, tlast, [tmargin,] litT, chain,
    # hi, lo -> out, ok scratch [, done scratch]
    #   tcb/tjb     (T,) scalar-prefetch: clause-/chain-block id per tile
    #   tfirst/tlast (T,) scalar-prefetch: first/last tile of its clause block
    #   tmargin     (T,) scalar-prefetch: residual vote swing after tile t
    #   litT        (L + 1, block_s) uint32 bit-transposed literal slab
    #   chain       SMEM (1, block_c * block_j) int32 literal ids of the tile
    #   hi/lo       (block_c, Kp) int8 vote limbs (fused_infer.vote_limbs)
    #   out         (32, block_s, Kp) int32 class sums (sample-bit major)
    #   ok          VMEM scratch (block_c, block_s) uint32 carried clause bits
    #   done        SMEM scratch (1,) int32 — slab certified, skip tiles
    block_j: int,
    n_classes: int = 0,
    n_samples: int = 0,
    early_exit: bool = False,
):
    if early_exit:
        (tcb_ref, tjb_ref, tfirst_ref, tlast_ref, tmargin_ref,
         litT_ref, chain_ref, hi_ref, lo_ref, out_ref, ok_ref, done_ref) = refs
    else:
        (tcb_ref, tjb_ref, tfirst_ref, tlast_ref,
         litT_ref, chain_ref, hi_ref, lo_ref, out_ref, ok_ref) = refs
        tmargin_ref = done_ref = None
    t = pl.program_id(1)
    slab = pl.program_id(0)   # hoisted: program_id can't lower inside pl.when

    @pl.when(t == 0)
    def _init_out():
        out_ref[...] = jnp.zeros_like(out_ref)
        if early_exit:
            done_ref[0] = 0

    active = done_ref[0] == 0 if early_exit else True

    @pl.when(tfirst_ref[t] == 1)
    def _init_ok():   # chain start: every clause alive for every sample
        ok_ref[...] = jnp.full_like(ok_ref, 0xFFFFFFFF)

    # early exit: the whole slab of clauses is already dead — skip the
    # chain (Clause-Out all zero propagates unchanged); in exact early-exit
    # mode a certified slab skips every remaining tile.  Sentinel ids land
    # on the all-ones row.
    live = jnp.any(ok_ref[...] != 0)

    @pl.when(jnp.logical_and(live, active) if early_exit else live)
    def _chain():
        walk_chains(ok_ref, litT_ref, chain_ref, block_j)

    fold_pred = tlast_ref[t] == 1
    if early_exit:
        fold_pred = jnp.logical_and(fold_pred, active)

    @pl.when(fold_pred)
    def _fold():    # adder bank: fold each sample bit's multiplicity votes
        fold_sample_bits(out_ref, ok_ref[...], hi_ref[...], lo_ref[...])
        if early_exit:
            certify_slab(out_ref, done_ref, tmargin_ref[t], slab,
                         n_classes, n_samples)


@functools.partial(
    jax.jit,
    static_argnames=("schedule", "block_s", "interpret"),
)
def sparse_tm_forward(
    lit_words: jax.Array,       # (B, W) uint32 packed literals
    votes: jax.Array,           # (U, K) int32 — rows aligned with schedule
    schedule: SparseSchedule,
    *,
    block_s: int = DEFAULT_BLOCK_S,
    interpret: bool = False,
    tile_margin: jax.Array | None = None,   # (T,) residual swing after tile t
) -> jax.Array:
    """Packed literals -> (B, K) int32 class sums via the chain schedule.

    Bit-identical to ``class_sum_ref(clause_fire_ref(lit, include_words),
    votes)`` for the include rows the schedule was built from (vacuous-AND
    semantics: all-zero rows fire, so their votes must be zero — guaranteed
    by ``compile_tm``).

    With ``tile_margin`` (see :mod:`repro.kernels.anytime`) the kernel
    runs in exact early-exit mode: a sample slab stops folding once every
    sample's lead strictly exceeds the residual swing.  Argmax over the
    result is identical to the full walk; the sums themselves may be
    truncated.
    """
    B, W = lit_words.shape
    U, K = votes.shape
    assert U <= schedule.chain_ids.shape[0], (U, schedule.chain_ids.shape)
    assert schedule.n_lit_bits == W * 32, (schedule.n_lit_bits, W)
    if schedule.n_tiles == 0:   # degenerate all-empty schedule: nothing votes
        return jnp.zeros((B, K), jnp.int32)

    Cp = schedule.chain_ids.shape[0]
    vts = jnp.pad(votes.astype(jnp.int32), ((0, Cp - U), (0, 0)))
    tiles = jnp.asarray(np.stack([
        schedule.tile_cb, schedule.tile_jb,
        schedule.tile_first, schedule.tile_last,
    ]))   # padded clauses fire vacuously but vote 0
    return sparse_tm_forward_tables(
        lit_words, jnp.asarray(schedule.chain_ids), vts, tiles,
        block_c=schedule.block_c, block_j=schedule.block_j,
        block_s=block_s, interpret=interpret, tile_margin=tile_margin,
    )


def stack_shard_schedules(
    include_words: np.ndarray,      # (U, Wa) — compile_tm row order
    votes: np.ndarray,              # (U, K)
    n_shards: int,
    *,
    block_c: int = DEFAULT_BLOCK_C,
    block_j: int = DEFAULT_BLOCK_J,
):
    """Clause-shard a compiled schedule: each shard carries its own tile
    table, padded to common shapes so the stacks shard over ``model``.

    Returns ``(schedules, chain_stack, votes_stack, tile_stack, C_loc)``:
    per-shard :class:`SparseSchedule` objects (CSR metadata), the
    ``(n_shards, C_loc_p, Jp)`` chain-id stack, the matching vote stack,
    and the ``(n_shards, 4, T)`` tile table (cb, jb, first, last).  Shards
    with fewer real tiles ride on no-op padding tiles, so every shard runs
    the same grid — partial class sums then compose exactly through one
    int32 ``psum``.
    """
    iw = np.ascontiguousarray(np.asarray(include_words, dtype=np.uint32))
    U, Wa = iw.shape
    K = votes.shape[1]
    C_loc = -(-max(U, 1) // n_shards)
    C_loc = _rup(C_loc, 8)
    Up = C_loc * n_shards
    iw = np.pad(iw, ((0, Up - U), (0, 0)))
    vt = np.pad(np.asarray(votes, np.int32), ((0, Up - U), (0, 0)))

    schedules = [
        build_schedule(iw[s * C_loc:(s + 1) * C_loc],
                       block_c=block_c, block_j=block_j)
        for s in range(n_shards)
    ]
    T = max(max(s.n_tiles for s in schedules), 1)
    Jp = max(max(s.chain_ids.shape[1] for s in schedules), block_j)
    schedules = [
        build_schedule(iw[s * C_loc:(s + 1) * C_loc],
                       block_c=block_c, block_j=block_j, pad_tiles_to=T)
        for s in range(n_shards)
    ]
    Jp = max(max(s.chain_ids.shape[1] for s in schedules), Jp)
    Cp = max(s.chain_ids.shape[0] for s in schedules)

    chain_stack = np.full((n_shards, Cp, Jp), Wa * 32, np.int32)
    votes_stack = np.zeros((n_shards, Cp, K), np.int32)
    tile_stack = np.zeros((n_shards, 4, T), np.int32)
    for s, sched in enumerate(schedules):
        cp, jp = sched.chain_ids.shape
        chain_stack[s, :cp, :jp] = sched.chain_ids
        votes_stack[s, :C_loc] = vt[s * C_loc:(s + 1) * C_loc]
        tile_stack[s, 0] = sched.tile_cb
        tile_stack[s, 1] = sched.tile_jb
        tile_stack[s, 2] = sched.tile_first
        tile_stack[s, 3] = sched.tile_last
    return schedules, chain_stack, votes_stack, tile_stack, C_loc


def sparse_tm_forward_tables(
    lit_words: jax.Array,       # (B, W) uint32
    chain_ids: jax.Array,       # (Cp, Jp) int32
    votes: jax.Array,           # (Cp, K) int32 (already padded rows)
    tiles: jax.Array,           # (4, T) int32 — cb, jb, first, last
    *,
    block_c: int,
    block_j: int,
    block_s: int = DEFAULT_BLOCK_S,
    interpret: bool = False,
    tile_margin: jax.Array | None = None,
) -> jax.Array:
    """Traced-table twin of :func:`sparse_tm_forward` for ``shard_map``
    bodies: the chain/tile tables arrive as (sharded) arrays instead of a
    static schedule, so one jit serves every shard."""
    B, W = lit_words.shape
    Cp = chain_ids.shape[0]
    K = votes.shape[1]
    Kp = _rup(K, 128)
    block_s = max(min(block_s, packetizer.n_words(B)), 1)

    litT = literal_slabs(lit_words, block_s)
    chain = smem_tiles(chain_ids, block_c, block_j)
    hi, lo = vote_limbs(votes, Cp, Kp)

    early_exit = tile_margin is not None
    scratch = [pltpu.VMEM((block_c, block_s), jnp.uint32)]
    if early_exit:
        scratch.append(pltpu.SMEM((1,), jnp.int32))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5 if early_exit else 4,
        grid=(litT.shape[0], tiles.shape[1]),
        in_specs=[
            pl.BlockSpec((None, W * 32 + 1, block_s),
                         lambda s, t, *refs: (s, 0, 0)),
            pl.BlockSpec((None, None, 1, block_c * block_j),
                         lambda s, t, cb, jb, *refs: (cb[t], jb[t], 0, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((block_c, Kp),
                         lambda s, t, cb, jb, *refs: (cb[t], 0)),
            pl.BlockSpec((block_c, Kp),
                         lambda s, t, cb, jb, *refs: (cb[t], 0)),
        ],
        out_specs=pl.BlockSpec((None, 32, block_s, Kp),
                               lambda s, t, *refs: (s, 0, 0, 0)),
        scratch_shapes=scratch,
    )
    prefetch = [tiles[0], tiles[1], tiles[2], tiles[3]]
    if early_exit:
        prefetch.append(jnp.asarray(tile_margin, jnp.int32))
    out = pl.pallas_call(
        functools.partial(
            _sparse_infer_kernel, block_j=block_j,
            n_classes=K, n_samples=B, early_exit=early_exit,
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((litT.shape[0], 32, block_s, Kp),
                                       jnp.int32),
        interpret=interpret,
    )(*prefetch, litT, chain, hi, lo)
    return unslab_sums(out, B, K)


def schedule_class_sums_ref(
    lit_words: jax.Array,       # (B, W) uint32
    chain_ids: jax.Array,       # (Cp, Jp) int32 (sentinel = W * 32)
    votes: jax.Array,           # (Cp, K) int32
) -> jax.Array:
    """jnp oracle over chain tables (the non-kernel engine of the sharded
    schedule path): fire iff every chain literal is 1, sentinel ids read
    constant 1.  Bit-identical to the Pallas schedule kernel."""
    B, W = lit_words.shape
    bits = packetizer.unpack_bits(lit_words, W * 32)          # (B, L)
    padded = jnp.concatenate(
        [bits, jnp.ones((B, 1), bits.dtype)], axis=1)         # sentinel col
    g = jnp.take(padded, chain_ids.reshape(-1), axis=1)
    fired = jnp.all(g.reshape(B, *chain_ids.shape) != 0, axis=2)
    return fired.astype(jnp.int32) @ votes.astype(jnp.int32)
