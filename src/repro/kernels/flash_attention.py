"""Pallas TPU kernel: causal flash attention (forward).

The §Perf analysis (EXPERIMENTS.md) shows XLA-level flash streams its score
tiles through HBM, leaving prefill/train attention memory-bound; this kernel
keeps the (block_q x block_kv) tiles and the online-softmax accumulators in
VMEM — the real-TPU fix, behind the same semantics as
models/attention.flash_attention's forward (ref: kernels/ref.py:flash_ref).

Grid: (batch*heads, q blocks, kv blocks); the kv axis is the sequential
("arbitrary") dimension carrying (m, l, acc) scratch across iterations.
Backward on TPU uses the recomputing custom-VJP in models/attention.py (the
kernel slots in as its forward via ops.flash_forward when on TPU).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _flash_kernel(
    q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr,
    *, block_q: int, block_kv: int, scale: float, causal: bool,
):
    kv_i = pl.program_id(2)
    nkv = pl.num_programs(2)

    @pl.when(kv_i == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0].astype(jnp.float32)              # (block_q, hd)
    k = k_ref[0].astype(jnp.float32)              # (block_kv, hd)
    v = v_ref[0]                                   # (block_kv, dv)

    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale                                      # (block_q, block_kv)
    if causal:
        q_i = pl.program_id(1)
        q_pos = q_i * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_kv), 0
        )
        k_pos = kv_i * block_kv + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_kv), 1
        )
        s = jnp.where(k_pos <= q_pos, s, NEG_INF)

    m_prev, l_prev, acc_prev = m_scr[...], l_scr[...], acc_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_new = l_prev * corr + jnp.sum(p, axis=-1, keepdims=True)
    acc_new = acc_prev * corr + jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    m_scr[...], l_scr[...], acc_scr[...] = m_new, l_new, acc_new

    @pl.when(kv_i == nkv - 1)
    def _finish():
        o_ref[0] = (acc_new / jnp.maximum(l_new, 1e-30)).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("causal", "block_q", "block_kv", "interpret")
)
def flash_forward(
    q: jax.Array,   # (B, S, H, hd)
    k: jax.Array,   # (B, T, H, hd)  (kv pre-expanded to H heads)
    v: jax.Array,   # (B, T, H, dv)
    *,
    causal: bool = True,
    block_q: int = 512,
    block_kv: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """Causal attention forward == kernels/ref.py:flash_ref."""
    B, S, H, hd = q.shape
    T, dv = k.shape[1], v.shape[-1]
    block_q = min(block_q, S)
    block_kv = min(block_kv, T)
    while S % block_q:
        block_q //= 2
    while T % block_kv:
        block_kv //= 2

    # (B*H, S, hd) layout: one grid row per (batch, head)
    qr = q.transpose(0, 2, 1, 3).reshape(B * H, S, hd)
    kr = k.transpose(0, 2, 1, 3).reshape(B * H, T, hd)
    vr = v.transpose(0, 2, 1, 3).reshape(B * H, T, dv)

    grid = (B * H, S // block_q, T // block_kv)
    out = pl.pallas_call(
        functools.partial(
            _flash_kernel, block_q=block_q, block_kv=block_kv,
            scale=hd**-0.5, causal=causal,
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, hd), lambda b, qi, ki: (b, qi, 0)),
            pl.BlockSpec((1, block_kv, hd), lambda b, qi, ki: (b, ki, 0)),
            pl.BlockSpec((1, block_kv, dv), lambda b, qi, ki: (b, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, dv), lambda b, qi, ki: (b, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, S, dv), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, dv), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
    )(qr, kr, vr)
    return out.reshape(B, H, S, dv).transpose(0, 2, 1, 3)
