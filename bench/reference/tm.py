"""Plain reference of the Tsetlin machine: class sums and training step.

Straight ``jax.numpy`` over the automata, with nothing of the program
imported: no compiler, no schedule, no kernel.  The training step is the
math of ``kernels/ref.py`` and of ``ops.feedback_probs`` /
``feedback_select``, written out again (the same counter hash, so a
correct program matches it bit for bit), with the batch cut into chunks
so that its ``(chunk, C, L)`` hash field fits the chip's memory.

All arithmetic is on integers, except the feedback probabilities, which
are float32 as in the program; every comparison is exact.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

_H1 = np.uint32(2654435761)
_H2 = np.uint32(2246822519)
_H3 = np.uint32(3266489917)


def hash_u32(idx, seed):
    x = idx.astype(jnp.uint32) * _H1 + seed.astype(jnp.uint32)
    x = x ^ (x >> 16)
    x = x * _H2
    x = x ^ (x >> 13)
    x = x * _H3
    return x ^ (x >> 16)


def hash_unit(r):
    """uint32 -> float32 in [0, 1], ``float32(r) / 2**32``, from the two
    exact 16-bit halves."""
    hi = (r >> 16).astype(jnp.int32).astype(jnp.float32)
    lo = (r & np.uint32(0xFFFF)).astype(jnp.int32).astype(jnp.float32)
    return (hi * np.float32(65536.0) + lo) / np.float32(2**32)


def prob_to_u32(p: float) -> np.uint32:
    return np.uint32(min(int(round(p * 2**32)), 2**32 - 1))


def literals(x):
    """(B, F) {0,1} -> (B, 2F): each feature and its negation."""
    x = x.astype(jnp.uint8)
    return jnp.concatenate([x, 1 - x], axis=-1)


def clause_meta(sz: dict):
    """(class (C,), polarity (C,), votes (C, K)) of the padded bank:
    polarity alternates +1/-1, padded clauses vote 0."""
    j = jnp.arange(sz["C"])
    cls = jnp.clip(j // sz["cpc"], 0, sz["K"] - 1)
    pol = jnp.where(j < sz["C_raw"], jnp.where(j % 2 == 0, 1, -1), 0)
    votes = (cls[:, None] == jnp.arange(sz["K"])[None, :]) * pol[:, None]
    return cls, pol.astype(jnp.int32), votes.astype(jnp.int32)


def class_sums(ta, x, sz: dict, *, training: bool):
    """(B, K) int32 vote sums.  A clause fires when none of its included
    literals is 0 (an empty clause fires in training and is dropped at
    inference)."""
    inc = (ta >= 0).astype(jnp.int8)                       # (C, L)
    zero = (1 - literals(x)).astype(jnp.int8)              # (B, L)
    viol = jnp.dot(zero, inc.T, preferred_element_type=jnp.int32)
    fire = viol == 0
    if not training:
        fire = fire & (inc.sum(axis=1) > 0)[None, :]
    _, _, votes = clause_meta(sz)
    return jnp.dot(fire.astype(jnp.int8), votes.astype(jnp.int8),
                   preferred_element_type=jnp.int32)


@functools.partial(jax.jit, static_argnames=("szt",))
def _predict_block(ta, x, szt):
    return jnp.argmax(class_sums(ta, x, dict(szt), training=False), axis=-1)


def predict(ta, x, sz: dict, block: int = 8192) -> np.ndarray:
    """Argmax class of every row of ``x``, in blocks of rows."""
    szt = tuple(sorted(sz.items()))
    out = [np.asarray(_predict_block(ta, x[i:i + block], szt))
           for i in range(0, x.shape[0], block)]
    return np.concatenate(out)


def _feedback(sums, y, seed, sz: dict):
    """(B, C) feedback type: 0 none, 1 Type I, 2 Type II."""
    B, K, T = y.shape[0], sz["K"], sz["T"]
    b_idx = jnp.arange(B, dtype=jnp.uint32)
    # the negative class: hashed uniformly from the K - 1 others
    r_neg = hash_u32(b_idx, seed ^ jnp.uint32(0x9E3779B9))
    kn = (r_neg % jnp.uint32(K - 1)).astype(jnp.int32)
    kn = kn + (kn >= y)
    sum_t = jnp.take_along_axis(sums, y[:, None], axis=1)[:, 0]
    sum_n = jnp.take_along_axis(sums, kn[:, None], axis=1)[:, 0]
    p_t = (T - sum_t).astype(jnp.float32) / (2.0 * T)
    p_n = (T + sum_n).astype(jnp.float32) / (2.0 * T)

    cls, pol, _ = clause_meta(sz)
    c_idx = jnp.arange(sz["C"], dtype=jnp.uint32)[None, :]
    r_sel = hash_unit(hash_u32(b_idx[:, None] * jnp.uint32(0x9E3779B1) + c_idx,
                               seed ^ jnp.uint32(0x85EBCA6B)))
    is_t = cls[None, :] == y[:, None]
    is_n = cls[None, :] == kn[:, None]
    p = jnp.where(is_t, p_t[:, None], jnp.where(is_n, p_n[:, None], 0.0))
    pos, neg = pol[None, :] > 0, pol[None, :] < 0
    ftype = jnp.where(is_t & pos, 1, jnp.where(is_t & neg, 2,
                      jnp.where(is_n & pos, 2, jnp.where(is_n & neg, 1, 0))))
    return jnp.where(r_sel < p, ftype, 0).astype(jnp.uint8)


def _delta(ta, lits, fire, ftype, b_off, seed, sz: dict):
    """Summed Type I / Type II automaton delta of one chunk of samples."""
    B, L = lits.shape
    C = ta.shape[0]
    p_act = 1.0 if sz["boost"] else (sz["s"] - 1.0) / sz["s"]
    b_idx = (jnp.arange(B, dtype=jnp.uint32) + b_off)[:, None, None]
    c_idx = jnp.arange(C, dtype=jnp.uint32)[None, :, None]
    l_idx = jnp.arange(L, dtype=jnp.uint32)[None, None, :]
    r = hash_u32((b_idx * jnp.uint32(C) + c_idx) * jnp.uint32(L) + l_idx, seed)
    lit_on = lits[:, None, :] == 1
    fire_b = fire[:, :, None]
    excl = ta[None, :, :] < 0
    act = (r < prob_to_u32(p_act)).astype(jnp.int32)
    inact = (r < prob_to_u32(1.0 / sz["s"])).astype(jnp.int32)
    d1 = jnp.where(fire_b, jnp.where(lit_on, act, -inact), -inact)
    d2 = (fire_b & ~lit_on & excl).astype(jnp.int32)
    ft = ftype[:, :, None]
    d = jnp.where(ft == 1, d1, jnp.where(ft == 2, d2, 0))
    return jnp.sum(d, axis=0, dtype=jnp.int32)


@functools.partial(jax.jit, static_argnames=("szt", "chunk", "lo", "hi"))
def _train_step(ta, x, y, seed, szt, chunk, lo, hi):
    sz = dict(szt)
    T = sz["T"]
    sums = jnp.clip(class_sums(ta, x, sz, training=True), -T, T)
    ftype = _feedback(sums, y, seed, sz)
    lits = literals(x)
    inc = (ta >= 0).astype(jnp.int8)
    B = x.shape[0]
    n = B // chunk

    def body(acc, i):
        sl = lambda a: jax.lax.dynamic_slice_in_dim(a, i * chunk, chunk, 0)
        lc = sl(lits)
        viol = jnp.dot((1 - lc).astype(jnp.int8), inc.T,
                       preferred_element_type=jnp.int32)
        fire = viol == 0                                   # training semantics
        off = (i * chunk).astype(jnp.uint32)
        return acc + _delta(ta, lc, fire, sl(ftype), off, seed, sz), None

    delta, _ = jax.lax.scan(body, jnp.zeros(ta.shape, jnp.int32),
                            jnp.arange(n))
    return jnp.clip(ta.astype(jnp.int32) + delta, lo, hi).astype(jnp.int8)


def train_step(ta, x, y, seed: int, sz: dict, *, chunk: int = 16,
               state_bits: int = 8):
    """One batch step; the automata after it, (C, L) int8.

    ``state_bits`` is the automata's width: 8 as the configuration states
    (``n_states`` 128 a side); 4 is the control, the same step with the
    states held in int4."""
    assert x.shape[0] % chunk == 0, (x.shape, chunk)
    n_states = sz["n_states"] if state_bits == 8 else 2 ** (state_bits - 1)
    return _train_step(ta, jnp.asarray(x), jnp.asarray(y),
                       jnp.uint32(seed), tuple(sorted(sz.items())), chunk,
                       -n_states, n_states - 1)
