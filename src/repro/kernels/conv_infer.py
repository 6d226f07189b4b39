"""Pallas TPU kernel: convolutional coalesced TM inference (ConvCoTM).

The model of the 65-nm accelerator (Tunheim et al., arXiv:2501.19347): a
``win x win`` window slides with stride 1 over an ``H x W`` boolean image.
Patch ``p = (py, px)`` has ``win*win`` pixel features plus its row and
column thermometer-coded in ``H - win`` and ``W - win`` bits (bit ``i`` is
1 iff the coordinate exceeds ``i``; ``core/packetizer.patch_literals``),
and its literals are those features and their negations.  ``C`` clauses
are shared by all ``K`` classes: clause ``j`` fires on the image iff it
fires on at least one patch (an empty clause never fires), and class ``k``
sums the signed weights ``w[j, k]`` of the clauses that fire.

The kernel never forms the ``(P, Lp)`` patch literals.  A clause fires on
a patch iff it has no violated literal, and its violations there are

    npos[j] + sum_{dy,dx} D[j, dy, dx] * X[py + dy, px + dx] + pos[j, py, px]

with ``D = include(~x) - include(x)`` in {-1, 0, 1}, ``npos[j]`` the count
of its included plain pixel literals, and ``pos`` the included position
literals that read 0 at ``(py, px)``.  For one output row ``py`` the count
of every column ``px`` and clause ``j`` is one int8 MXU matmul of a banded
bank, ``band[(px, j), (dy, x)] = D[j, dy, x - px]`` (the clause bank laid
out at every column offset), with a window vector: the ``win`` image rows
from ``py`` on, then a 1 that picks the ``npos`` column and a one-hot of
``py`` that picks ``pos``.  :func:`conv_operands` builds the band once per
artifact.  The kernel ORs the zero counts over ``px`` and ``py`` into a
``(Cp, block_b)`` clause vector and folds it into the class sums through
:func:`fused_infer.fold_votes`.

Grid: one axis over slabs of ``block_b`` images (images on lanes).  Each
step unpacks its slab's words into a VMEM scratch of pixel rows (one word
becomes 32 sublanes), lays out the ``Ph`` window vectors, and runs ``Ph``
matmuls of ``(Pw*Cp, Kc) x (Kc, block_b)``.  The band keeps one block
index, so it is copied in once per call.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.fused_infer import _rup, fold_votes, vmem_limit_bytes
from repro.kernels.fused_infer import vote_limbs

# a clause whose count never reaches 0: padded and empty clauses
NEVER = 1
# the clause axis is padded to whole MXU tiles
CLAUSE_ALIGN = 128


class Geometry(NamedTuple):
    """Static sizes of a ConvCoTM: image ``H x W``, square window ``win``,
    stride 1."""

    H: int
    W: int
    win: int

    @property
    def Ph(self) -> int:
        return self.H - self.win + 1

    @property
    def Pw(self) -> int:
        return self.W - self.win + 1

    @property
    def positions(self) -> int:
        return self.Ph * self.Pw

    @property
    def features(self) -> int:
        """Per patch: its pixels, then row and column thermometer bits."""
        return self.win * self.win + (self.H - self.win) + (self.W - self.win)

    @property
    def literals(self) -> int:
        return 2 * self.features

    @property
    def contraction(self) -> int:
        """A window vector: ``win`` image rows, the ``npos`` 1 and the
        one-hot of ``py``, padded to the MXU's 128."""
        return _rup(self.win * self.W + 1 + self.Ph, 128)

    @property
    def image_words(self) -> int:
        return -(-self.H * self.W // 32)


def thermometer(n_pos: int, n_bits: int) -> np.ndarray:
    """(n_pos, n_bits) uint8: row ``c`` has bit ``i`` set iff ``c > i``."""
    return (np.arange(n_pos)[:, None] > np.arange(n_bits)[None, :]).astype(
        np.uint8)


def conv_operands(include: np.ndarray, votes: np.ndarray, geom: Geometry):
    """The kernel's bank: ``(band, votes)`` from ``include (C, Lp)`` {0,1}
    patch-literal include bits and ``votes (C, K)``.

    ``band`` is ``(Pw * Cp, Kc)`` int8: row ``px * Cp + j``; columns
    ``dy * W + x`` hold ``D``, column ``win * W`` holds ``npos`` and column
    ``win * W + 1 + py`` the position count at ``(py, px)``.  ``votes`` is
    ``(Cp, K)`` int32 with the clause axis zero-padded to ``Cp``.  Empty
    and padded clauses count one violation everywhere, so they never
    fire."""
    g = geom
    inc = np.asarray(include).astype(bool)
    C, Lp = inc.shape
    if Lp != g.literals:
        raise ValueError(f"include rows have {Lp} literals; the geometry "
                         f"has {g.literals}")
    F, npix, ny = g.features, g.win * g.win, g.H - g.win
    pos, neg = inc[:, :F], inc[:, F:]
    Cp = _rup(max(C, 1), CLAUSE_ALIGN)

    def pos_count(p_inc, n_inc, n_pos):
        # included bits that read 0 at each coordinate: x_i where the
        # thermometer bit is 0, ~x_i where it is 1
        t = thermometer(n_pos, p_inc.shape[1]).astype(np.int32)   # (n, bits)
        return p_inc.astype(np.int32) @ (1 - t).T + n_inc.astype(np.int32) @ t.T

    ys, xs = slice(npix, npix + ny), slice(npix + ny, F)
    cy = pos_count(pos[:, ys], neg[:, ys], g.Ph)                  # (C, Ph)
    cx = pos_count(pos[:, xs], neg[:, xs], g.Pw)                  # (C, Pw)
    npos = np.where(inc.any(axis=1), pos[:, :npix].sum(axis=1), NEVER)
    d = neg[:, :npix].astype(np.int8) - pos[:, :npix].astype(np.int8)
    band = np.zeros((g.Pw, Cp, g.contraction), np.int8)
    band[:, C:, g.win * g.W] = NEVER
    for px in range(g.Pw):
        pix = np.zeros((C, g.win, g.W), np.int8)
        pix[:, :, px:px + g.win] = d.reshape(C, g.win, g.win)
        band[px, :C, :g.win * g.W] = pix.reshape(C, -1)
        band[px, :C, g.win * g.W] = npos
        band[px, :C, g.win * g.W + 1:g.win * g.W + 1 + g.Ph] = (
            cx[:, px:px + 1] + cy)
    v = np.zeros((Cp, votes.shape[1]), np.int32)
    v[:C] = votes
    return band.reshape(g.Pw * Cp, g.contraction), v


def _conv_infer_kernel(
    img_ref,    # (Wimg, block_b) uint32 word-major image words
    band_ref,   # (Pw * Cp, Kc) int8 banded clause bank
    hi_ref,     # (Cp, Kp) int8 high weight limb
    lo_ref,     # (Cp, Kp) int8 low weight limb
    out_ref,    # (block_b, Kp) int32 class sums
    x_ref,      # VMEM scratch (32 * Wimg, block_b) int32 pixel rows
    win_ref,    # VMEM scratch (Ph, Kc, block_b) int32 window vectors
    *, W: int, win: int, Pw: int,
):
    n_words, block_b = img_ref.shape
    Ph, Kc, _ = win_ref.shape
    Cp = hi_ref.shape[0]
    rows = win * W
    shift = jax.lax.broadcasted_iota(jnp.uint32, (32, block_b), 0)
    for w in range(n_words):          # one word -> 32 pixel rows
        bits = (img_ref[w:w + 1, :] >> shift) & jnp.uint32(1)
        x_ref[32 * w:32 * (w + 1), :] = bits.astype(jnp.int32)
    tail = jax.lax.broadcasted_iota(jnp.int32, (Kc - rows, block_b), 0)
    for py in range(Ph):              # rows py.., the npos 1, one-hot py
        win_ref[py, :rows, :] = x_ref[W * py:W * py + rows, :]
        win_ref[py, rows:, :] = ((tail == 0) | (tail == 1 + py)).astype(
            jnp.int32)
    band = band_ref[...]

    def row(py, fired):
        count = jnp.dot(band, win_ref[py].astype(jnp.int8),
                        preferred_element_type=jnp.int32)
        hit = (count == 0).astype(jnp.int32).reshape(Pw, Cp, block_b)
        return jnp.maximum(fired, jnp.max(hit, axis=0))

    fired = jax.lax.fori_loop(0, Ph, row,
                              jnp.zeros((Cp, block_b), jnp.int32))
    out_ref[...] = fold_votes(fired, hi_ref[...], lo_ref[...])


@functools.partial(
    jax.jit, static_argnames=("geom", "block_b", "interpret"))
def conv_tm_forward(
    img_words: jax.Array,     # (B, Wimg) uint32 packed images, row-major
    band: jax.Array,          # (Pw * Cp, Kc) int8, from conv_operands
    votes: jax.Array,         # (Cp, K) int32
    *,
    geom: Geometry,
    block_b: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """Packed images -> (B, K) int32 class sums, in one ``pallas_call``.

    Bit-identical to ``ref.conv_class_sums_ref`` over the same bank;
    ``|votes|`` must stay below ``fused_infer.VOTE_BOUND``."""
    g = geom
    B, Wimg = img_words.shape
    Cp, K = votes.shape
    rows, Kc = band.shape
    assert (rows, Kc) == (g.Pw * Cp, g.contraction), (band.shape, g)
    assert Wimg == g.image_words, (Wimg, g)
    block_b = min(block_b, _rup(B, 128))
    Bp = _rup(B, block_b)
    Kp = _rup(K, 128)
    img = jnp.pad(img_words, ((0, Bp - B), (0, 0)))
    img_t = img.reshape(Bp // block_b, block_b, Wimg).transpose(0, 2, 1)
    hi, lo = vote_limbs(votes, Cp, Kp)
    need = (2 * (Wimg * block_b * 4 + rows * Kc + 2 * Cp * Kp
                 + block_b * Kp * 4)
            + (32 * Wimg + g.Ph * Kc) * block_b * 4 + 3 * rows * block_b * 4)
    out = pl.pallas_call(
        functools.partial(_conv_infer_kernel, W=g.W, win=g.win, Pw=g.Pw),
        grid=(Bp // block_b,),
        in_specs=[
            pl.BlockSpec((None, Wimg, block_b), lambda b: (b, 0, 0)),
            pl.BlockSpec((rows, Kc), lambda b: (0, 0)),
            pl.BlockSpec((Cp, Kp), lambda b: (0, 0)),
            pl.BlockSpec((Cp, Kp), lambda b: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block_b, Kp), lambda b: (b, 0)),
        out_shape=jax.ShapeDtypeStruct((Bp, Kp), jnp.int32),
        scratch_shapes=[pltpu.VMEM((32 * Wimg, block_b), jnp.int32),
                        pltpu.VMEM((g.Ph, Kc, block_b), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=vmem_limit_bytes(need)),
        interpret=interpret,
    )(img_t, band, hi, lo)
    return out[:B, :K]
