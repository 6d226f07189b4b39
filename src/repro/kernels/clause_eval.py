"""Pallas TPU kernel: bitpacked Tsetlin clause evaluation.

This is the MATADOR accelerator datapath (paper §III) re-tiled for a TPU:

  * A "packet" is a VMEM block of ``block_w`` uint32 literal words
    (32 literals per word, packetizer.py layout).
  * Each grid step along the word axis is one **Hard-Coded Clause Block**:
    it evaluates the partial clauses for its literal window and carries the
    running clause state to the next step through the output block
    (``Clause In`` / ``Clause Out`` in paper Fig. 5) — the word axis is an
    ``arbitrary`` (sequential) grid dimension, exactly the HCB chain.
  * HCB 0 initializes all clauses to 1 (paper: "starts with the assumption
    that all clause outputs are 1"); each block ANDs in
    ``(include & ~literal) == 0`` for its window.

Tiling: literals (block_b, block_w) and includes (block_c, block_w) blocks
stream through VMEM; the (block_b, block_c) clause accumulator lives in the
output block across the word-axis steps.  All matmul-free VPU bit ops;
``block_c`` sits on the 128-lane axis.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu



def _clause_fire_kernel(lit_ref, inc_ref, out_ref, *, block_w: int):
    w = pl.program_id(2)
    nw = pl.num_programs(2)

    @pl.when(w == 0)
    def _init():  # HCB 0: all clauses start at 1
        out_ref[...] = jnp.ones_like(out_ref)

    lit = lit_ref[...]          # (block_b, block_w) uint32
    inc = inc_ref[...]          # (block_c, block_w) uint32

    def body(i, ok):
        l_w = jax.lax.dynamic_slice_in_dim(lit, i, 1, axis=1)   # (bb, 1)
        i_w = jax.lax.dynamic_slice_in_dim(inc, i, 1, axis=1)   # (bc, 1)
        viol = jnp.bitwise_and(i_w.reshape(1, -1), ~l_w)        # (bb, bc)
        return ok & (viol == 0)

    ok = jax.lax.fori_loop(
        0, block_w, body, out_ref[...] != 0, unroll=True
    )
    out_ref[...] = ok.astype(out_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("block_b", "block_c", "block_w", "interpret"),
)
def clause_fire(
    lit_words: jax.Array,   # (B, W) uint32
    inc_words: jax.Array,   # (C, W) uint32
    *,
    block_b: int = 128,
    block_c: int = 128,
    block_w: int = 64,
    interpret: bool = False,
) -> jax.Array:
    """(B, C) int8 clause outputs; semantics of kernels/ref.py:clause_fire_ref."""
    B, W = lit_words.shape
    C, Wc = inc_words.shape
    assert W == Wc, (W, Wc)

    block_b = min(block_b, _rup(B, 8))
    block_c = min(block_c, _rup(C, 128))
    block_w = min(block_w, W)

    Bp, Cp, Wp = _rup(B, block_b), _rup(C, block_c), _rup(W, block_w)
    lit = _pad2(lit_words, Bp, Wp)
    inc = _pad2(inc_words, Cp, Wp)   # zero include words never violate

    grid = (Bp // block_b, Cp // block_c, Wp // block_w)
    out = pl.pallas_call(
        functools.partial(_clause_fire_kernel, block_w=block_w),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_b, block_w), lambda b, c, w: (b, w)),
            pl.BlockSpec((block_c, block_w), lambda b, c, w: (c, w)),
        ],
        out_specs=pl.BlockSpec((block_b, block_c), lambda b, c, w: (b, c)),
        out_shape=jax.ShapeDtypeStruct((Bp, Cp), jnp.int8),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(lit, inc)
    return out[:B, :C]


def _rup(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def _pad2(x: jax.Array, d0: int, d1: int) -> jax.Array:
    return jnp.pad(x, ((0, d0 - x.shape[0]), (0, d1 - x.shape[1])))
