"""Pallas TPU kernel: XNOR-popcount binarized matmul (BNN baseline layer).

The paper benchmarks MATADOR against FINN BNNs whose core op is the
XNOR-popcount dot product over {-1,+1} packed into bits.  We implement that
baseline with the same bitpacked streaming structure as the clause chain of
``fused_infer`` (shared word-axis "packet" decomposition), so the Table-I comparison is like-for-like
on this substrate too.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu



def _xnor_kernel(a_ref, w_ref, out_ref, *, block_w: int):
    w = pl.program_id(2)

    @pl.when(w == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    a = a_ref[...]      # (bb, bw) uint32
    b = w_ref[...]      # (bo, bw) uint32

    def body(i, acc):
        a_w = jax.lax.dynamic_slice_in_dim(a, i, 1, axis=1)     # (bb, 1)
        b_w = jax.lax.dynamic_slice_in_dim(b, i, 1, axis=1)     # (bo, 1)
        x = ~(jnp.bitwise_xor(b_w.reshape(1, -1), a_w))         # (bb, bo)
        return acc + jax.lax.population_count(x).astype(jnp.int32)

    out_ref[...] = jax.lax.fori_loop(
        0, block_w, body, out_ref[...], unroll=True
    )


@functools.partial(
    jax.jit, static_argnames=("n_bits", "block_b", "block_o", "block_w", "interpret")
)
def xnor_popcount(
    a_words: jax.Array,   # (B, W) uint32 packed {-1:0,+1:1} activations
    w_words: jax.Array,   # (O, W) uint32 packed weights
    n_bits: int,
    *,
    block_b: int = 128,
    block_o: int = 128,
    block_w: int = 64,
    interpret: bool = False,
) -> jax.Array:
    """(B, O) int32 +1/-1 dot products == kernels/ref.py:xnor_popcount_ref."""
    B, W = a_words.shape
    O = w_words.shape[0]
    block_b = min(block_b, _rup(B, 8))
    block_o = min(block_o, _rup(O, 128))
    block_w = min(block_w, W)
    Bp, Op, Wp = _rup(B, block_b), _rup(O, block_o), _rup(W, block_w)

    a = jnp.pad(a_words, ((0, Bp - B), (0, Wp - W)))
    w = jnp.pad(w_words, ((0, Op - O), (0, Wp - W)))

    grid = (Bp // block_b, Op // block_o, Wp // block_w)
    pop = pl.pallas_call(
        functools.partial(_xnor_kernel, block_w=block_w),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_b, block_w), lambda b, o, w: (b, w)),
            pl.BlockSpec((block_o, block_w), lambda b, o, w: (o, w)),
        ],
        out_specs=pl.BlockSpec((block_b, block_o), lambda b, o, w: (b, o)),
        out_shape=jax.ShapeDtypeStruct((Bp, Op), jnp.int32),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(a, w)[:B, :O]

    # padded words contribute ~(0^0) = 32 ones each; fold them out with the
    # true-bit correction so the result matches the unpadded oracle exactly.
    matches = pop - (Wp * 32 - n_bits)
    return 2 * matches - n_bits


def _rup(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m
