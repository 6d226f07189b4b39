"""Bandwidth-driven literal packing — the TPU analog of MATADOR's Packetizer.

The paper streams each datapoint to the FPGA as 64-bit AXI packets
(Fig. 4a): least-significant-bit first, zero-padded final packet.  On TPU the
"channel" is the HBM->VMEM DMA, and the packet is a 32-bit vector lane: we
pack the 2F literals of each datapoint into ``ceil(2F/32)`` uint32 words,
bit i of word w = literal ``32*w + i`` (LSB-first, matching Fig. 4a), with
zero padding in the final word.

Zero padding is safe by construction: include masks are packed with the same
layout, padding bits of the include mask are 0, and a clause violation is
``include & ~literal`` — a zero include bit can never produce a violation.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

WORD_BITS = 32


def n_words(n_bits: int, word_bits: int = WORD_BITS) -> int:
    return (n_bits + word_bits - 1) // word_bits


def pack_bits(bits: jax.Array, word_bits: int = WORD_BITS) -> jax.Array:
    """Pack a {0,1} array along its last axis into uint32 words (LSB-first).

    (..., L) -> (..., ceil(L/word_bits)) uint32.
    """
    L = bits.shape[-1]
    W = n_words(L, word_bits)
    pad = W * word_bits - L
    b = bits.astype(jnp.uint32)
    if pad:
        b = jnp.pad(b, [(0, 0)] * (b.ndim - 1) + [(0, pad)])
    b = b.reshape(b.shape[:-1] + (W, word_bits))
    weights = (jnp.uint32(1) << jnp.arange(word_bits, dtype=jnp.uint32))
    return jnp.sum(b * weights, axis=-1, dtype=jnp.uint32)


def unpack_bits(words: jax.Array, n_bits: int, word_bits: int = WORD_BITS) -> jax.Array:
    """Inverse of :func:`pack_bits`. (..., W) uint32 -> (..., n_bits) uint8."""
    shifts = jnp.arange(word_bits, dtype=jnp.uint32)
    bits = (words[..., None] >> shifts) & jnp.uint32(1)
    bits = bits.reshape(words.shape[:-1] + (words.shape[-1] * word_bits,))
    return bits[..., :n_bits].astype(jnp.uint8)


def pack_literals(x: jax.Array, word_bits: int = WORD_BITS) -> jax.Array:
    """(B, F) {0,1} features -> (B, ceil(2F/32)) packed literal words."""
    from repro.core.tm import literals

    return pack_bits(literals(x), word_bits)


def pack_include_masks(ta_state: jax.Array, word_bits: int = WORD_BITS) -> jax.Array:
    """(C, L) int8 automata -> (C, W) packed include masks."""
    inc = (ta_state >= 0).astype(jnp.uint8)
    return pack_bits(inc, word_bits)


def patch_literals(img_words: jax.Array, geom) -> jax.Array:
    """(B, Wr) packed images -> (B, P, Lp) uint8 patch literals of a
    convolutional TM (``kernels/conv_infer.Geometry``), made on the device.

    Patch ``p = py * Pw + px`` reads its ``win x win`` pixels (row-major),
    then ``py`` and ``px`` thermometer-coded in ``H - win`` and ``W - win``
    bits (bit ``i`` is 1 iff the coordinate exceeds ``i``), then the
    negations of all of them.  The serving kernel never forms this array;
    the oracle rung and the tests do."""
    from repro.kernels import conv_infer

    g = geom
    x = unpack_bits(img_words, g.H * g.W)                     # (B, H*W)
    py, px = np.divmod(np.arange(g.positions), g.Pw)
    dy, dx = np.divmod(np.arange(g.win * g.win), g.win)
    idx = (py[:, None] + dy) * g.W + px[:, None] + dx           # (P, win^2)
    where = np.concatenate([conv_infer.thermometer(g.Ph, g.H - g.win)[py],
                            conv_infer.thermometer(g.Pw, g.W - g.win)[px]],
                           axis=1)                              # (P, bits)
    feats = jnp.concatenate(
        [x[:, idx], jnp.broadcast_to(where, (x.shape[0],) + where.shape)],
        axis=-1)
    return jnp.concatenate([feats, 1 - feats], axis=-1)


# -- numpy twins (host-side "Packetizer" used by the offline compiler) -------

def pack_bits_np(bits: np.ndarray, word_bits: int = WORD_BITS) -> np.ndarray:
    L = bits.shape[-1]
    W = n_words(L, word_bits)
    pad = W * word_bits - L
    b = bits.astype(np.uint64)
    if pad:
        b = np.pad(b, [(0, 0)] * (b.ndim - 1) + [(0, pad)])
    b = b.reshape(b.shape[:-1] + (W, word_bits))
    weights = (np.uint64(1) << np.arange(word_bits, dtype=np.uint64))
    return (b * weights).sum(axis=-1).astype(np.uint32)


def unpack_bits_np(words: np.ndarray, n_bits: int, word_bits: int = WORD_BITS) -> np.ndarray:
    shifts = np.arange(word_bits, dtype=np.uint32)
    bits = (words[..., None] >> shifts) & np.uint32(1)
    bits = bits.reshape(words.shape[:-1] + (words.shape[-1] * word_bits,))
    return bits[..., :n_bits].astype(np.uint8)
