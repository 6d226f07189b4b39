"""Pallas TPU kernel: batched Tsetlin Automata feedback deltas.

The training hot loop touches every (clause, literal) automaton per sample —
a purely memory-bound elementwise pass over the (C, L) state bank.  The FPGA
trainers the paper cites ([19]-[21]) feed it from on-chip LFSRs; here the
randomness is a counter-based integer hash generated *inside* the kernel
(kernels/ref.py:hash_u32), so no (B, C, L) random tensor ever exists in HBM.

Grid tiles (C, L); the batch is an in-kernel loop so each (block_c, block_l)
state tile is read once and its int32 delta accumulator stays in registers/
VMEM for all B samples — arithmetic intensity scales with B.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


from repro.kernels import ref as kref


def _ta_delta_kernel(
    scal_ref, ta_ref, lit_ref, fire_ref, ft_ref, out_ref,
    *, n_batch: int, c_dim: int, l_dim: int, block_c: int, block_l: int,
    t_act, t_inact, global_clause: bool,
):
    c0 = pl.program_id(0) * block_c
    l0 = pl.program_id(1) * block_l

    c_idx = c0 + jax.lax.broadcasted_iota(jnp.uint32, (block_c, block_l), 0)
    l_idx = l0 + jax.lax.broadcasted_iota(jnp.uint32, (block_c, block_l), 1)
    seed = scal_ref[0, 0]
    b_off = scal_ref[0, 1]   # runtime scalar: chunk loops pass traced offsets
    if global_clause:        # clause-sharded caller: hash on GLOBAL clause id
        c_idx = c_idx + scal_ref[0, 2]

    excl = ta_ref[...] < 0                                    # (bc, bl)

    def body(b, acc):
        bu = jnp.uint32(b) + b_off
        gidx = (bu * jnp.uint32(c_dim) + c_idx) * jnp.uint32(l_dim) + l_idx
        r = kref.hash_u32(gidx, seed)
        act = (r < t_act).astype(jnp.int32)
        inact = (r < t_inact).astype(jnp.int32)

        lit_on = jax.lax.dynamic_slice_in_dim(lit_ref[...], b, 1, 0) == 1   # (1, bl)
        fire_b = jax.lax.dynamic_slice_in_dim(fire_ref[...], b, 1, 0) == 1  # (1, bc)
        ft = jax.lax.dynamic_slice_in_dim(ft_ref[...], b, 1, 0)             # (1, bc)
        fire_c = fire_b.reshape(block_c, 1)
        ft_c = ft.reshape(block_c, 1)

        d1 = jnp.where(fire_c, jnp.where(lit_on, act, -inact), -inact)
        d2 = (fire_c & ~lit_on & excl).astype(jnp.int32)
        d = jnp.where(ft_c == 1, d1, jnp.where(ft_c == 2, d2, 0))
        return acc + d

    out_ref[...] = jax.lax.fori_loop(
        0, n_batch, body, jnp.zeros((block_c, block_l), jnp.int32)
    )


@functools.partial(
    jax.jit,
    static_argnames=("p_act", "p_inact", "block_c", "block_l", "interpret",
                     "c_total"),
)
def ta_delta(
    ta: jax.Array,       # (C, L) int8
    lits: jax.Array,     # (B, L) uint8
    fire: jax.Array,     # (B, C) uint8
    ftype: jax.Array,    # (B, C) uint8 (0 none / 1 Type I / 2 Type II)
    seed: jax.Array,     # uint32 scalar
    *,
    p_act: float,
    p_inact: float,
    b_offset: int = 0,
    c_offset=0,
    c_total: int | None = None,
    block_c: int = 256,
    block_l: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """(C, L) int32 batch-summed feedback delta == kernels/ref.py:ta_delta_ref.

    ``c_total`` (static, with runtime ``c_offset``) switches the automaton
    hash to GLOBAL clause ids in a bank of ``c_total`` clauses — the
    clause-sharded trainer's indexing; the default keeps local ids.
    """
    C, L = ta.shape
    B = lits.shape[0]
    block_c = min(block_c, _rup(C, 8))
    block_l = min(block_l, _rup(L, 128))
    Cp, Lp = _rup(C, block_c), _rup(L, block_l)

    ta_p = jnp.pad(ta, ((0, Cp - C), (0, Lp - L)), constant_values=-1)
    lit_p = jnp.pad(lits, ((0, 0), (0, Lp - L)))
    fire_p = jnp.pad(fire, ((0, 0), (0, Cp - C)))
    ft_p = jnp.pad(ftype, ((0, 0), (0, Cp - C)))
    scal = jnp.stack([
        jnp.asarray(seed).astype(jnp.uint32),
        jnp.asarray(b_offset).astype(jnp.uint32),
        jnp.asarray(c_offset).astype(jnp.uint32),
    ]).reshape(1, 3)

    grid = (Cp // block_c, Lp // block_l)
    out = pl.pallas_call(
        functools.partial(
            _ta_delta_kernel,
            n_batch=B, c_dim=C if c_total is None else c_total, l_dim=L,
            block_c=block_c, block_l=block_l,
            t_act=kref.prob_to_u32(p_act), t_inact=kref.prob_to_u32(p_inact),
            global_clause=c_total is not None,
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 3), lambda c, l: (0, 0)),            # seed/offs
            pl.BlockSpec((block_c, block_l), lambda c, l: (c, l)),  # ta
            pl.BlockSpec((B, block_l), lambda c, l: (0, l)),        # lits
            pl.BlockSpec((B, block_c), lambda c, l: (0, c)),        # fire
            pl.BlockSpec((B, block_c), lambda c, l: (0, c)),        # ftype
        ],
        out_specs=pl.BlockSpec((block_c, block_l), lambda c, l: (c, l)),
        out_shape=jax.ShapeDtypeStruct((Cp, Lp), jnp.int32),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
    )(scal, ta_p, lit_p, fire_p, ft_p)
    return out[:C, :L]


def _rup(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m
