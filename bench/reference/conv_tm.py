"""Plain reference of the convolutional coalesced TM (ConvCoTM) of
Tunheim et al. (arXiv:2501.19347): patch literals, clauses, class sums.

Straight ``jax.numpy`` over the images' pixels, the clauses' include bits
and the weights, with nothing of the program imported: no packing, no
compiler, no kernel.

* An ``H x W`` boolean image; a ``win x win`` window at stride 1 gives
  ``(H - win + 1) x (W - win + 1)`` patches, row-major.
* Patch ``(py, px)`` has ``win * win`` pixel features (row-major), then
  ``py`` and ``px`` each thermometer-coded in ``H - win`` and ``W - win``
  bits (bit ``i`` is 1 iff the coordinate is greater than ``i``); its
  literals are those features, then their negations.
* Clause ``j`` on patch ``p`` is the AND of its included literals; its
  output is the OR over every patch; an empty clause outputs 0.
* The class sum is ``v_k = sum_j w[j, k] * c_j``; the answer is the first
  class with the largest sum.

Integer throughout: an int8 dot with int32 accumulation counts each
clause's violated literals, so no float path exists to set a matmul
precision for.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def geometry(cfg: dict) -> tuple:
    return int(cfg["image_h"]), int(cfg["image_w"]), int(cfg["window"])


def patch_literals(x, geom: tuple):
    """(B, H*W) {0,1} pixels -> (B, P, Lp) uint8 patch literals; numpy in,
    numpy out, else ``jax.numpy``."""
    H, W, win = geom
    Ph, Pw = H - win + 1, W - win + 1
    xp = np if isinstance(x, np.ndarray) else jnp
    img = xp.asarray(x, np.uint8).reshape(x.shape[0], H, W)
    # the window's columns, then its rows: (B, Ph, Pw, win_dy, win_dx)
    cols = xp.stack([img[:, :, dx:dx + Pw] for dx in range(win)], axis=-1)
    pix = xp.stack([cols[:, dy:dy + Ph] for dy in range(win)], axis=-2)
    pix = pix.reshape(x.shape[0], Ph * Pw, win * win)
    where = np.array([np.concatenate([py > np.arange(H - win),
                                      px > np.arange(W - win)])
                      for py in range(Ph) for px in range(Pw)], np.uint8)
    feats = xp.concatenate(
        [pix, xp.broadcast_to(where, (x.shape[0],) + where.shape)], axis=2)
    return xp.concatenate([feats, 1 - feats], axis=2)


def clause_outputs(x, include, geom: tuple):
    """(B, C) bool: each clause ANDed over each patch, ORed over patches;
    an empty clause never fires."""
    zero = (1 - patch_literals(x, geom)).astype(jnp.int8)    # (B, P, Lp)
    inc = jnp.asarray(include, jnp.int8)                    # (C, Lp)
    viol = jnp.einsum("bpl,cl->bpc", zero, inc,
                      preferred_element_type=jnp.int32)
    return jnp.any(viol == 0, axis=1) & jnp.any(inc != 0, axis=1)[None, :]


@functools.partial(jax.jit, static_argnames=("geom",))
def _block(x, include, weights, geom):
    fire = clause_outputs(x, include, geom)
    sums = jnp.dot(fire.astype(jnp.int32), weights.astype(jnp.int32))
    return fire, sums


def run(x, include, weights, geom: tuple, block: int = 1024):
    """(fire (N, C) bool, class sums (N, K) int32) of every row of ``x``,
    in blocks of rows, as numpy arrays."""
    inc, w = jnp.asarray(include), jnp.asarray(weights)
    fires, sums = [], []
    for i in range(0, x.shape[0], block):
        f, s = _block(x[i:i + block], inc, w, geom)
        fires.append(np.asarray(f))
        sums.append(np.asarray(s))
    return np.concatenate(fires), np.concatenate(sums)


def predict(sums) -> np.ndarray:
    """The first class with the largest sum."""
    return np.argmax(np.asarray(sums), axis=-1)
