"""Pallas TPU kernel: fused single-pass TM training step delta.

The unfused training step runs three dispatches with two ``(B, C)`` HBM
round-trips in between::

    clause_fire -> fire (HBM) -> feedback_plan -> ftype (HBM) -> ta_delta

Here the whole chain runs in ONE ``pallas_call``: the clause-fire word
chain is evaluated into VMEM scratch (exactly the fused-inference HCB
chain), the per-(sample, clause) feedback type is computed inline from
per-sample probabilities using the same counter-based hash RNG as
``ref.py`` (the TPU analog of the LFSR feedback blocks in the FPGA online
trainers, arXiv 2306.01027), and the int32 TA delta is accumulated
directly into the ``(C, L)`` output block — ``fire`` and ``ftype`` never
leave VMEM.

Grid: ``(clause-block, batch-block, word-chain)``.  The clause axis is
OUTERMOST (not the batch axis) so each ``(block_c, L)`` delta accumulator
block stays resident in VMEM across the entire batch sweep and is written
to HBM exactly once — with the batch axis outermost every batch block
would flush and re-fetch the whole ``(C, L)`` accumulator.

  * axis 0 (``c``, parallel)   — clause banks; owns one output block.
  * axis 1 (``b``, arbitrary)  — datapoint packets, accumulated into the
    resident output block.
  * axis 2 (``w``, arbitrary)  — the HCB word chain; carried clause state
    in VMEM scratch, same as ``fused_infer.py``.

**The per-sample delta fold.**  On the last chain step the finished fire
block is turned into feedback types, and a loop over the block's samples
folds each sample's Type I/II delta into the accumulator.  It does per
sample only the work that depends on the sample, and only where that
sample's feedback lands:

  * *Hoisted.*  Each automaton's draw is ``hash_u32(gidx, seed)`` with
    ``gidx = (b * C + c) * L + l``.  The hash's first round is affine mod
    2**32, so ``gidx * H1 + seed = b * (C * L * H1) + pre[c, l]`` with
    ``pre = (c * L + l) * H1 + seed``.  ``pre`` and the exclusion mask
    ``ta < 0`` are built once per clause block, into VMEM scratch, when
    its batch sweep starts; a sample then adds one scalar to ``pre`` and
    runs :func:`ref.hash_finish`.  Every draw is unchanged.
  * *Split by row parity.*  Feedback type follows polarity, and polarity
    alternates by clause (``tm.polarity``) inside class-major banks: for
    one sample, the even rows of a class all take one type and its odd
    rows the other.  The wrapper hands the kernel each clause block in a
    static row order, its even rows then its odd rows
    (:func:`_parity_order`), and un-permutes the delta.  Each half-block
    of a sample then takes the hashed Type I path only if one of its rows
    takes Type I feedback, the hash-free Type II path if its rows take
    Type II feedback only, and nothing if none of its rows takes feedback
    (TM feedback is sparse: per sample only the target class and one
    sampled negative class, thinned by the clause-selection draw).
  * *Only the rows it lands on.*  A class is a run of rows in each half,
    so a path walks only the groups of ``_GROUP`` rows from the first to
    the last row with feedback, and reads the sample's codes for those
    rows alone.  Which paths a sample takes and its row range in each
    half are worked out for the whole batch block at once, packed into
    one int32 a sample, and copied into SMEM, so the loop reads a
    sample's plan with one scalar load and skips a sample whose feedback
    does not land in the block at all.

The kernel reads each row's class and polarity from ``clause_class`` and
``clause_pol``, so it is bit-exact for any polarity array; with the
alternating one most half-blocks are of one feedback type, which is what
makes the split pay.  :func:`fold_engagement` counts, for a feedback plan,
how many (sample, half-block) pairs take each path.

TPU layout: the clause state is ``(block_c, block_b)`` as in
``fused_infer.py``; the per-sample fold selects sample ``i``'s codes on a
row group with a lane mask and a lane reduction, and reads its literal
row and the group's rows at dynamic sublane offsets — Mosaic slices lanes
only at static offsets.

Per-sample scalars (target class, sampled negative class, Type I/II
selection probabilities) are computed by the caller from the class sums of
a cheap fused-inference first pass (``ops.tm_train_step_kernel``), so one
training step is two kernel launches total instead of three plus the HBM
intermediates.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import ref as kref
from repro.kernels.fused_infer import (_pad2, _rup, chain_blocks,
                                       hcb_violations, vmem_limit_bytes)

# hash-stream constants — MUST match ops.feedback_select / ops.feedback_plan
_SEL_MIX = np.uint32(0x9E3779B1)
_SEL_XOR = np.uint32(0x85EBCA6B)

# rows of one step of the per-sample fold: a sample's delta is folded on
# the groups of _GROUP rows that span where its feedback lands
_GROUP = 16


def _block_rows(shape, half: int) -> jax.Array:
    """Local clause index of each row of a clause block laid out in the
    kernel's row order (even clauses in rows ``[0, half)``, odd ones in
    ``[half, 2 * half)``)."""
    r = jax.lax.broadcasted_iota(jnp.uint32, shape, 0)
    return jnp.where(r < half, 2 * r, 2 * (r - half) + 1)


def _parity_order(x: jax.Array, block_c: int) -> jax.Array:
    """Rows of every ``block_c`` block of ``x`` as the kernel lays them
    out: the block's even rows, then its odd rows."""
    n = x.shape[0]
    return (x.reshape(n // block_c, block_c // 2, 2, *x.shape[1:])
            .swapaxes(1, 2).reshape(x.shape))


def _natural_order(x: jax.Array, block_c: int) -> jax.Array:
    """Inverse of :func:`_parity_order`."""
    n = x.shape[0]
    return (x.reshape(n // block_c, 2, block_c // 2, *x.shape[1:])
            .swapaxes(1, 2).reshape(x.shape))


def _fused_train_kernel(
    scal_ref,   # SMEM (3,) int32: uint32 bits of [seed, b_offset, c_offset]
    lit_ref,    # (block_w, block_b) uint32 word-major literal words
    inc_ref,    # (block_c, block_w) uint32 packed include words
    lits_ref,   # (block_b, Lp) int32 unpacked literals
    ta_ref,     # (block_c, Lp) int8 automata states
    yk_ref,     # (2, block_b) int32: [target class; sampled negative class]
    pp_ref,     # (2, block_b) float32: [p_type1; p_type2] selection probs
    cm_ref,     # (block_c, 2) int32: [clause class, clause polarity]
    out_ref,    # (block_c, Lp) int32 delta accumulator
    viol_ref,   # VMEM scratch (block_c, block_b) uint32 carried violations
    pre_ref,    # VMEM scratch (block_c, Lp) uint32 hoisted hash round
    excl_ref,   # VMEM scratch (block_c, Lp) int32: 1 where ta < 0
    plan_vmem,  # VMEM scratch (1, block_b) int32: the samples' fold plan
    plan_smem,  # SMEM scratch (1, block_b) int32: the same, for scalar reads
    plan_sem,   # DMA semaphore of the copy between the two
    *,
    block_b: int,
    block_c: int,
    c_dim: int,
    l_dim: int,
    t_act,
    t_inact,
    global_clause: bool,
):
    b = pl.program_id(1)
    w = pl.program_id(2)
    nw = pl.num_programs(2)
    half = block_c // 2
    # program_id must be read at the kernel top level (the interpret-mode
    # evaluator does not rewrite it inside pl.when/cond sub-jaxprs)
    b0 = (b * block_b).astype(jnp.uint32)
    c0 = (pl.program_id(0) * block_c).astype(jnp.uint32)

    @pl.when((b == 0) & (w == 0))
    def _init_block():
        # the clause block's sample-independent work, once per batch sweep:
        # the automaton hash's first round, (c * L + l) * H1 + seed, indexed
        # by LOCAL clause id — matching the unfused composition, where
        # ta_delta runs on the local shard — unless ``global_clause`` (the
        # clause-sharded trainer), which indexes by GLOBAL clause id so every
        # shard reproduces exactly the full bank's draws for its rows.
        out_ref[...] = jnp.zeros_like(out_ref)
        shape = out_ref.shape                              # (block_c, Lp)
        c_idx = c0 + _block_rows(shape, half)
        if global_clause:
            c_idx = c_idx + scal_ref[2].astype(jnp.uint32)
        l_idx = jax.lax.broadcasted_iota(jnp.uint32, shape, 1)
        pre_ref[...] = ((c_idx * jnp.uint32(l_dim) + l_idx) * kref._H1
                        + scal_ref[0].astype(jnp.uint32))
        excl_ref[...] = (ta_ref[...].astype(jnp.int32) < 0).astype(jnp.int32)

    @pl.when(w == 0)
    def _init_chain():  # HCB 0: no violation yet (training semantics)
        viol_ref[...] = jnp.zeros_like(viol_ref)

    viol_ref[...] = hcb_violations(viol_ref[...], inc_ref[...], lit_ref[...])

    @pl.when(w == nw - 1)
    def _feedback():
        seed = scal_ref[0].astype(jnp.uint32)
        b_off = scal_ref[1].astype(jnp.uint32)
        c_off = scal_ref[2].astype(jnp.uint32)
        fire = viol_ref[...] == 0                        # (block_c, block_b)

        # ---- inline feedback plan: bit-identical to ops.feedback_select.
        # Clause-selection randomness is hashed on GLOBAL (sample, clause)
        # ids (b_offset / c_offset) so chunked and sharded callers reproduce
        # the unsharded stream exactly.
        bg = b0 + b_off + jax.lax.broadcasted_iota(jnp.uint32, fire.shape, 1)
        cg = c0 + c_off + _block_rows(fire.shape, half)
        r_sel = kref.hash_unit(kref.hash_u32(bg * _SEL_MIX + cg,
                                             seed ^ _SEL_XOR))

        cls = cm_ref[:, 0:1]             # (block_c, 1)
        pol = cm_ref[:, 1:2]
        is_t = cls == yk_ref[0:1, :]     # (block_c, block_b)
        is_n = cls == yk_ref[1:2, :]
        p = jnp.where(is_t, pp_ref[0:1, :],
                      jnp.where(is_n, pp_ref[1:2, :], 0.0))
        pos = pol > 0
        neg = pol < 0
        ftype = jnp.where(is_t & pos, 1, jnp.where(is_t & neg, 2,
                jnp.where(is_n & pos, 2, jnp.where(is_n & neg, 1, 0))))
        ft = jnp.where(r_sel < p, ftype, 0)
        # per (clause, sample): 2 * ftype + fire, 0 where no feedback
        code = jnp.where(ft != 0, 2 * ft + fire.astype(jnp.int32), 0)
        # where each sample's feedback lands, per row half h, packed in one
        # int32 a sample: bit 2h if a row takes Type I feedback (the hashed
        # path), bit 2h + 1 if a fired row takes Type II (the only Type II
        # rows with a delta); the first row group holding either at bit
        # 4 + 12h, one past the last at bit 10 + 12h (6 bits each)
        halves = (slice(0, half), slice(half, block_c))
        type1 = (ft == 1).astype(jnp.int32)
        type2 = ((ft == 2) & fire).astype(jnp.int32)
        r_half = jax.lax.broadcasted_iota(jnp.int32, (half, block_b), 0)
        plan = 0
        for h, rows in enumerate(halves):
            lands = (type1[rows] | type2[rows]) != 0
            first = jnp.min(jnp.where(lands, r_half, half), axis=0,
                            keepdims=True) // _GROUP
            end = (jnp.max(jnp.where(lands, r_half + 1, 0), axis=0,
                           keepdims=True) + _GROUP - 1) // _GROUP
            plan = (plan
                    | jnp.max(type1[rows], axis=0, keepdims=True) << 2 * h
                    | jnp.max(type2[rows], axis=0, keepdims=True) << 2 * h + 1
                    | first << 4 + 12 * h | end << 10 + 12 * h)  # (1, block_b)
        # into SMEM, so each sample's plan is one scalar load
        plan_vmem[...] = plan
        copy = pltpu.make_async_copy(plan_vmem, plan_smem, plan_sem)
        copy.start()
        # the chain is done: its scratch holds the codes from here on, so a
        # row group can read its rows of them at a dynamic offset
        viol_ref[...] = code.astype(jnp.uint32)
        lane = jax.lax.broadcasted_iota(jnp.int32, (_GROUP, block_b), 1)
        # sample b's first hash round is pre + b * step (mod 2**32)
        step = np.uint32((c_dim * l_dim * int(kref._H1)) % 2**32)

        copy.wait()

        # ---- TA delta fold: bit-identical to ref.ta_delta_ref
        def fold(i, carry):
            g = plan_smem[0, i]

            @pl.when((g & 15) != 0)      # skip samples with no feedback here
            def _sample():
                lit_on = lits_ref[pl.ds(i, 1), :] == 1     # (1, Lp)
                x0 = (b0 + b_off + i.astype(jnp.uint32)) * step

                def fold_group(rows, hashed):
                    # sample i's codes on the group's rows, selected and
                    # lane-reduced (Mosaic slices lanes only at static
                    # offsets)
                    col = jnp.sum(jnp.where(
                        lane == i, viol_ref[rows, :].astype(jnp.int32), 0),
                        axis=1, keepdims=True)             # (_GROUP, 1)
                    ft_c = col >> 1
                    fire_c = (col & 1) == 1
                    # Type II: +1 on fired clauses' excluded automata of
                    # 0 literals
                    d = ((fire_c & (ft_c == 2) & ~lit_on).astype(jnp.int32)
                         & excl_ref[rows, :])
                    if hashed:
                        r = kref.hash_finish(pre_ref[rows, :] + x0)
                        act = (r < t_act).astype(jnp.int32)
                        inact = (r < t_inact).astype(jnp.int32)
                        d = jnp.where(ft_c == 1,
                                      jnp.where(fire_c & lit_on, act, -inact),
                                      d)
                    out_ref[rows, :] += d

                for h in range(2):
                    paths = (g >> 2 * h) & 3
                    first = (g >> 4 + 12 * h) & 63
                    end = (g >> 10 + 12 * h) & 63
                    for hashed, taken in ((True, (paths & 1) == 1),
                                          (False, paths == 2)):
                        @pl.when(taken)
                        def _walk(h=h, first=first, end=end, hashed=hashed):
                            def group(j, c):
                                rows = pl.ds(pl.multiple_of(
                                    h * half + j * _GROUP, _GROUP), _GROUP)
                                fold_group(rows, hashed)
                                return c

                            jax.lax.fori_loop(first, end, group, 0)

            return carry

        jax.lax.fori_loop(0, block_b, fold, 0)


@functools.partial(
    jax.jit,
    static_argnames=("p_act", "p_inact", "block_b", "block_c", "block_w",
                     "interpret", "c_total"),
)
def fused_tm_train_delta(
    ta: jax.Array,            # (C, L) int8 automata states
    lits: jax.Array,          # (B, L) uint8 {0,1} literals (unpacked)
    lit_words: jax.Array,     # (B, W) uint32 packed literals
    inc_words: jax.Array,     # (C, W) uint32 packed include masks
    y: jax.Array,             # (B,) int32 target class (-1 = padded sample)
    kn: jax.Array,            # (B,) int32 sampled negative class
    p_t: jax.Array,           # (B,) float32 Type-I-side selection prob
    p_n: jax.Array,           # (B,) float32 Type-II-side selection prob
    clause_class: jax.Array,  # (C,) int32 class id per clause
    clause_pol: jax.Array,    # (C,) int32 +1/-1 polarity (0 = padded)
    seed: jax.Array,          # uint32 scalar
    *,
    p_act: float,
    p_inact: float,
    b_offset=0,               # global index of sample 0 (runtime scalar ok)
    c_offset=0,               # global index of clause 0 (runtime scalar ok)
    c_total: int | None = None,  # global clause count (clause-sharded caller)
    block_b: int = 128,
    block_c: int = 256,
    block_w: int = 64,
    interpret: bool = False,
) -> jax.Array:
    """Batch-summed feedback delta -> (C, L) int32, single fused pass.

    Bit-identical to the unfused three-dispatch composition::

        fire  = clause_fire_ref(lit_words, inc_words)
        ftype = feedback_select(y, kn, p_t, p_n, clause_class, clause_pol,
                                seed, b_offset, c_offset)  # masked by fire
        delta = ta_delta_ref(ta, lits, fire, ftype, seed,
                             p_act=p_act, p_inact=p_inact, b_offset=b_offset)

    ``b_offset``/``c_offset`` are runtime scalars (traced values from a
    ``lax.scan`` chunk loop or a shard_map body are fine): the selection
    hash is indexed by global (sample, clause) id and the automaton hash by
    (global sample, local clause, local literal), so chunked, sharded, and
    unsharded callers produce identical bits.  ``c_total`` (static)
    switches the automaton hash too onto GLOBAL clause ids in a bank of
    ``c_total`` clauses — with it, a clause shard's delta equals the
    corresponding rows of the FULL-bank delta (the clause-sharded
    ``shard_map`` trainer's invariant), not just the per-shard composition.
    """
    C, L = ta.shape
    B, W = lit_words.shape
    assert lits.shape == (B, L), (lits.shape, (B, L))
    assert inc_words.shape == (C, W), (inc_words.shape, (C, W))

    block_b = min(block_b, _rup(B, 8))
    block_c = min(block_c, _rup(C, 128))
    block_w = min(block_w, W)
    # row halves of whole groups, group indices in the plan's 6-bit fields
    assert block_c % (2 * _GROUP) == 0 and block_c // 2 // _GROUP < 64, block_c

    Bp, Cp = _rup(B, block_b), _rup(C, block_c)
    Lp = _rup(L, 128)
    nb = Bp // block_b

    # clause-indexed operands in the kernel's row order (even, then odd
    # rows of each clause block); the delta is put back in natural order
    lit_t, inc = chain_blocks(
        lit_words, _parity_order(_pad2(inc_words, Cp, W), block_c),
        Bp=Bp, Cp=Cp, block_b=block_b, block_w=block_w)
    lits_p = _pad2(lits.astype(jnp.int32), Bp, Lp)
    ta_p = _parity_order(
        jnp.pad(ta, ((0, Cp - C), (0, Lp - L)), constant_values=-1), block_c)
    # padded samples get class -1, padded clauses class -1 / polarity 0:
    # any (padded, padded) class match still yields ftype 0 via polarity 0,
    # and padded rows/cols are sliced off the output anyway.
    yk = jnp.stack([
        jnp.pad(y.astype(jnp.int32), (0, Bp - B), constant_values=-1),
        jnp.pad(kn.astype(jnp.int32), (0, Bp - B), constant_values=-1),
    ]).reshape(2, nb, block_b).transpose(1, 0, 2)
    pp = jnp.stack([
        jnp.pad(p_t.astype(jnp.float32), (0, Bp - B)),
        jnp.pad(p_n.astype(jnp.float32), (0, Bp - B)),
    ]).reshape(2, nb, block_b).transpose(1, 0, 2)
    cm = _parity_order(jnp.stack([
        jnp.pad(clause_class.astype(jnp.int32), (0, Cp - C),
                constant_values=-1),
        jnp.pad(clause_pol.astype(jnp.int32), (0, Cp - C)),
    ], axis=1), block_c)
    scal = jax.lax.bitcast_convert_type(jnp.stack([
        jnp.asarray(seed).astype(jnp.uint32),
        jnp.asarray(b_offset).astype(jnp.uint32),
        jnp.asarray(c_offset).astype(jnp.uint32),
    ]), jnp.int32)

    grid = (Cp // block_c, nb, inc.shape[0])
    out = pl.pallas_call(
        functools.partial(
            _fused_train_kernel,
            block_b=block_b, block_c=block_c,
            c_dim=C if c_total is None else c_total, l_dim=L,
            t_act=kref.prob_to_u32(p_act),
            t_inact=kref.prob_to_u32(p_inact),
            global_clause=c_total is not None,
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),                   # scal
            pl.BlockSpec((None, block_w, block_b),
                         lambda c, b, w: (b, w, 0)),                 # lit
            pl.BlockSpec((None, block_c, block_w),
                         lambda c, b, w: (w, c, 0)),                 # inc
            pl.BlockSpec((block_b, Lp), lambda c, b, w: (b, 0)),     # lits
            pl.BlockSpec((block_c, Lp), lambda c, b, w: (c, 0)),     # ta
            pl.BlockSpec((None, 2, block_b),
                         lambda c, b, w: (b, 0, 0)),                 # y/kn
            pl.BlockSpec((None, 2, block_b),
                         lambda c, b, w: (b, 0, 0)),                 # probs
            pl.BlockSpec((block_c, 2), lambda c, b, w: (c, 0)),      # cls/pol
        ],
        out_specs=pl.BlockSpec((block_c, Lp), lambda c, b, w: (c, 0)),
        out_shape=jax.ShapeDtypeStruct((Cp, Lp), jnp.int32),
        scratch_shapes=[pltpu.VMEM((block_c, block_b), jnp.uint32),
                        pltpu.VMEM((block_c, Lp), jnp.uint32),
                        pltpu.VMEM((block_c, Lp), jnp.int32),
                        pltpu.VMEM((1, block_b), jnp.int32),
                        pltpu.SMEM((1, block_b), jnp.int32),
                        pltpu.SemaphoreType.DMA],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=vmem_limit_bytes(
                2 * 4 * (block_b * Lp + 2 * block_c * Lp)
                + 4 * block_c * (block_b + 2 * Lp)),
        ),
        interpret=interpret,
    )(scal, lit_t, inc, lits_p, ta_p, yk, pp, cm)
    return _natural_order(out, block_c)[:C, :L]


def fold_engagement(ftype, block_c: int = 256) -> dict:
    """Which path of the per-sample delta fold each (sample, half-block)
    pair takes, for a feedback plan ``ftype`` ((B, C): 0 none, 1 Type I,
    2 Type II, as ``ops.feedback_plan`` returns it) at tiling ``block_c``.

    A diagnostic, run on the host off the timed path: returns the shares
    of pairs that take the hashed path (some row takes Type I feedback),
    the Type II-only path, and neither.  Half-blocks are the kernel's: the
    even or the odd rows of one clause block."""
    ft = np.asarray(ftype)
    B, C = ft.shape
    block_c = min(block_c, _rup(C, 128))
    ft = np.pad(ft, ((0, 0), (0, _rup(C, block_c) - C)))
    halves = ft.reshape(B, -1, block_c // 2, 2)    # (B, block, row, parity)
    type1 = (halves == 1).any(axis=2)
    type2 = (halves == 2).any(axis=2) & ~type1
    return {"hashed": float(type1.mean()),
            "type2_only": float(type2.mean()),
            "neither": float((~type1 & ~type2).mean())}
