"""Pallas TPU kernel: fused single-pass TM training step delta.

PR 1 fused inference so the ``(B, C)`` fired matrix never touches HBM; this
kernel does the same for the *training* hot loop.  The unfused path runs
three dispatches with two ``(B, C)`` HBM round-trips in between::

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

On the last chain step the finished fire block is turned into feedback
types and folded into the delta.  TM feedback is *sparse by construction*
(per sample only the target class and one sampled negative class receive
feedback — 2/K of all clauses, further thinned by the clause-selection
probability), so the per-sample delta fold is guarded by a ``pl.when``
that skips the hash-field evaluation for (sample, clause block) pairs
with no feedback at all.  The skip is bit-exact: a zero ``ftype`` row
contributes exactly zero delta.

TPU layout: the clause state is ``(block_c, block_b)`` as in
``fused_infer.py``; the per-sample fold selects sample ``i``'s clause
column with a lane mask and a lane reduction, and reads its literal row
with a dynamic sublane index — Mosaic slices lanes only at static
offsets.

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
    # program_id must be read at the kernel top level (the interpret-mode
    # evaluator does not rewrite it inside pl.when/cond sub-jaxprs)
    b0 = (b * block_b).astype(jnp.uint32)
    c0 = (pl.program_id(0) * block_c).astype(jnp.uint32)

    @pl.when((b == 0) & (w == 0))
    def _init_out():
        out_ref[...] = jnp.zeros_like(out_ref)

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
        cg = c0 + c_off + jax.lax.broadcasted_iota(jnp.uint32, fire.shape, 0)
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
        lane = jax.lax.broadcasted_iota(jnp.int32, code.shape, 1)

        # ---- TA delta fold: bit-identical to ref.ta_delta_ref.  The
        # per-automaton hash is indexed by LOCAL (c, l) — matching the
        # unfused composition, where ta_delta runs on the local shard —
        # unless ``global_clause`` (the clause-sharded trainer), which
        # indexes by GLOBAL clause id so every shard reproduces exactly the
        # full bank's draws for its rows.
        shape = out_ref.shape                              # (block_c, Lp)
        c_idx = c0 + jax.lax.broadcasted_iota(jnp.uint32, shape, 0)
        if global_clause:
            c_idx = c_idx + c_off
        l_idx = jax.lax.broadcasted_iota(jnp.uint32, shape, 1)

        def fold(i, carry):
            # sample i's clause column, selected and lane-reduced (Mosaic
            # slices lanes only at static offsets)
            col = jnp.sum(jnp.where(lane == i, code, 0), axis=1,
                          keepdims=True)                   # (block_c, 1)

            # feedback sparsity skip (bit-exact: ftype == 0 -> delta == 0)
            @pl.when(jnp.any(col != 0))
            def _dense():
                ft_c = col >> 1
                fire_c = (col & 1) == 1
                bu = b0 + b_off + i.astype(jnp.uint32)
                gidx = (bu * jnp.uint32(c_dim) + c_idx) \
                    * jnp.uint32(l_dim) + l_idx
                r = kref.hash_u32(gidx, seed)
                act = (r < t_act).astype(jnp.int32)
                inact = (r < t_inact).astype(jnp.int32)
                lit_on = lits_ref[pl.ds(i, 1), :] == 1     # (1, Lp)
                d1 = jnp.where(fire_c,
                               jnp.where(lit_on, act, -inact), -inact)
                excl = ta_ref[...].astype(jnp.int32) < 0
                d2 = (fire_c & ~lit_on & excl).astype(jnp.int32)
                out_ref[...] += jnp.where(ft_c == 1, d1,
                                          jnp.where(ft_c == 2, d2, 0))

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

    Bp, Cp = _rup(B, block_b), _rup(C, block_c)
    Lp = _rup(L, 128)
    nb = Bp // block_b

    lit_t, inc = chain_blocks(lit_words, inc_words, Bp=Bp, Cp=Cp,
                              block_b=block_b, block_w=block_w)
    lits_p = _pad2(lits.astype(jnp.int32), Bp, Lp)
    ta_p = jnp.pad(ta, ((0, Cp - C), (0, Lp - L)), constant_values=-1)
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
    cm = jnp.stack([
        jnp.pad(clause_class.astype(jnp.int32), (0, Cp - C),
                constant_values=-1),
        jnp.pad(clause_pol.astype(jnp.int32), (0, Cp - C)),
    ], axis=1)
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
        scratch_shapes=[pltpu.VMEM((block_c, block_b), jnp.uint32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=vmem_limit_bytes(
                2 * 4 * (block_b * Lp + 2 * block_c * Lp)
                + 4 * block_c * block_b),
        ),
        interpret=interpret,
    )(scal, lit_t, inc, lits_p, ta_p, yk, pp, cm)
    return out[:C, :L]
