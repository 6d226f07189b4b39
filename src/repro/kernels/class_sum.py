"""Pallas TPU kernel: polarity-weighted class-sum vote tally.

The paper's class-sum stage is a bank of 2xCL adders behind the HCB chain
(Fig. 5), pipelined against clause evaluation.  On TPU it is an integer
matmul of the fired-clause matrix against the (clause x class) vote matrix;
this kernel tiles the clause (reduction) axis so it streams behind the
clause_eval kernel's output blocks.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu



def _class_sum_kernel(fired_ref, votes_ref, out_ref):
    c = pl.program_id(1)

    @pl.when(c == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    f = fired_ref[...].astype(jnp.int32)     # (bb, bc)
    v = votes_ref[...]                        # (bc, K)
    out_ref[...] += jax.lax.dot_general(
        f, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.int32
    )


@functools.partial(jax.jit, static_argnames=("block_b", "block_c", "interpret"))
def class_sum(
    fired: jax.Array,   # (B, C) int8/uint8 {0,1}
    votes: jax.Array,   # (C, K) int32
    *,
    block_b: int = 256,
    block_c: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """(B, K) int32 class sums == kernels/ref.py:class_sum_ref."""
    B, C = fired.shape
    K = votes.shape[1]
    block_b = min(block_b, _rup(B, 8))
    block_c = min(block_c, _rup(C, 128))
    Bp, Cp, Kp = _rup(B, block_b), _rup(C, block_c), _rup(K, 128)

    f = jnp.pad(fired, ((0, Bp - B), (0, Cp - C)))
    v = jnp.pad(votes, ((0, Cp - C), (0, Kp - K)))

    grid = (Bp // block_b, Cp // block_c)
    out = pl.pallas_call(
        _class_sum_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_b, block_c), lambda b, c: (b, c)),
            pl.BlockSpec((block_c, Kp), lambda b, c: (c, 0)),
        ],
        out_specs=pl.BlockSpec((block_b, Kp), lambda b, c: (b, 0)),
        out_shape=jax.ShapeDtypeStruct((Bp, Kp), jnp.int32),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(f, v)
    return out[:B, :K]


def _rup(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m
