"""Tsetlin Machine core — paper-faithful definition (MATADOR / Granmo'18).

The TM model is a bank of Tsetlin Automata, one per (class, clause, literal).
``int8`` states centered at zero; action = *include* iff state >= 0.  A clause
is the AND of its included literals; class sums are polarity-weighted clause
votes; classification is the argmax over class sums.

Everything here is a pure function over a ``TMState`` pytree so it composes
with jit / vmap / shard_map.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class TMConfig:
    """Hyperparameters of a (multiclass, vanilla) Tsetlin Machine.

    Mirrors the knobs MATADOR's GUI exposes: clauses per class, threshold T,
    specificity s, number of automata states.
    """

    n_features: int
    n_classes: int
    clauses_per_class: int
    n_states: int = 128          # states per action -> int8 in [-128, 127]
    threshold: int = 15          # T
    s: float = 10.0              # specificity
    boost_true_positive: bool = True
    # Pad the flattened clause axis to a multiple of this (sharding alignment;
    # padded clauses are permanently empty and vote 0).
    clause_pad_multiple: int = 1

    @property
    def n_literals(self) -> int:
        return 2 * self.n_features

    @property
    def n_clauses_total(self) -> int:
        raw = self.n_classes * self.clauses_per_class
        m = self.clause_pad_multiple
        return ((raw + m - 1) // m) * m

    @property
    def n_clauses_raw(self) -> int:
        return self.n_classes * self.clauses_per_class

    def replace(self, **kw: Any) -> "TMConfig":
        return dataclasses.replace(self, **kw)


@dataclasses.dataclass(frozen=True)
class ConvTMConfig:
    """A convolutional coalesced TM (ConvCoTM; Tunheim et al.,
    arXiv:2501.19347): a ``window x window`` patch slides with stride 1
    over an ``image_h x image_w`` boolean image, ``n_clauses`` clauses are
    shared by all classes, each with a signed integer weight per class.

    Patch literals (``core/packetizer.patch_literals``): the patch's
    pixels, then its row and column thermometer-coded in ``image_h -
    window`` and ``image_w - window`` bits, then the negations of all of
    them.  A clause fires on the image iff it fires on some patch; an empty
    clause never fires.
    """

    image_h: int
    image_w: int
    window: int
    n_clauses: int
    n_classes: int

    @property
    def geometry(self):
        from repro.kernels.conv_infer import Geometry

        return Geometry(self.image_h, self.image_w, self.window)

    @property
    def n_features(self) -> int:
        """Features of one request: the image's pixels."""
        return self.image_h * self.image_w


# ConvCoTM weights are signed int8, symmetric
CONV_WEIGHT_MAX = 127


def conv_bank(config: ConvTMConfig, img_words, seed: int):
    """A seeded ConvCoTM bank, ``(ta_state (C, Lp) int8, weights (C, K)
    int32)``.  Clause ``j`` includes ``k`` in [8, 24] of the true
    literals of one seeded image of ``img_words`` (packed rows) at one
    seeded position, so it fires on that image: three tenths of them
    (rounded up) from the patch's lit pixels, the rest from its other true
    literals.  Fewer lit pixels give clauses that fire on nearly every
    image; half of them, clauses that fire on so few that most images fire
    none.  Weights are uniform over the signed range.  Automata read 0
    (include) or -1 (exclude)."""
    from repro.core import packetizer

    rng = np.random.default_rng(seed)
    C, g = config.n_clauses, config.geometry
    img = rng.integers(0, img_words.shape[0], C)
    pos = rng.integers(0, g.positions, C)
    lits = np.asarray(packetizer.patch_literals(
        jnp.asarray(np.asarray(img_words)[img]), g))[np.arange(C), pos]
    npix = g.win * g.win
    ta = np.full((C, g.literals), -1, np.int8)
    for j in range(C):
        k = int(rng.integers(8, 25))
        lit = np.flatnonzero(lits[j, :npix])
        rest = np.setdiff1d(np.flatnonzero(lits[j]), lit)
        n_lit = min(len(lit), -(-3 * k // 10))
        ta[j, rng.choice(lit, n_lit, replace=False)] = 0
        ta[j, rng.choice(rest, k - n_lit, replace=False)] = 0
    w = CONV_WEIGHT_MAX
    return ta, rng.integers(-w, w + 1, (C, config.n_classes)).astype(np.int32)


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class TMState:
    """Trainable state: the automata bank, flattened over (class, clause)."""

    ta_state: jax.Array  # int8 (n_clauses_total, n_literals)
    steps: jax.Array     # int32 scalar

    @property
    def dtype(self):
        return self.ta_state.dtype


def init(config: TMConfig, rng: jax.Array) -> TMState:
    """Random init in {-1, 0}: automata sit just either side of the decision
    boundary, per standard TM initialization."""
    shape = (config.n_clauses_total, config.n_literals)
    st = jax.random.randint(rng, shape, minval=-1, maxval=1, dtype=jnp.int8)
    if config.n_clauses_total != config.n_clauses_raw:
        # padded clauses are pinned to all-exclude (empty) forever
        pad_from = config.n_clauses_raw
        st = st.at[pad_from:].set(jnp.int8(-config.n_states))
    return TMState(ta_state=st, steps=jnp.zeros((), jnp.int32))


# ---------------------------------------------------------------------------
# Literals & clauses
# ---------------------------------------------------------------------------

def literals(x: jax.Array) -> jax.Array:
    """(B, F) {0,1} -> (B, 2F): each feature contributes x and ~x (Fig. 1b)."""
    x = x.astype(jnp.uint8)
    return jnp.concatenate([x, 1 - x], axis=-1)


def include_mask(ta_state: jax.Array) -> jax.Array:
    """Boolean include/exclude actions of each automaton."""
    return ta_state >= 0


def clause_outputs(
    ta_state: jax.Array, lits: jax.Array, *, training: bool
) -> jax.Array:
    """Dense clause evaluation (the ``ref`` semantics the kernels must match).

    clause fires iff no included literal is 0.  Empty clauses output 1 during
    training (vacuous AND) and 0 at inference (they are dropped from the
    compiled circuit, paper §III).

    Args:
      ta_state: (C, L) int8.
      lits: (B, L) {0,1}.
    Returns:
      (B, C) uint8 clause outputs.
    """
    inc = include_mask(ta_state)                       # (C, L)
    viol = inc[None, :, :] & (lits[:, None, :] == 0)    # (B, C, L)
    fire = ~jnp.any(viol, axis=-1)                      # (B, C)
    if not training:
        nonempty = jnp.any(inc, axis=-1)                # (C,)
        fire = fire & nonempty[None, :]
    return fire.astype(jnp.uint8)


def polarity(config: TMConfig) -> jax.Array:
    """+1/-1 alternating within each class; 0 on padded clauses."""
    j = jnp.arange(config.n_clauses_total)
    pol = jnp.where(j % 2 == 0, 1, -1).astype(jnp.int32)
    return jnp.where(j < config.n_clauses_raw, pol, 0)


def vote_matrix(config: TMConfig) -> jax.Array:
    """(C_total, n_classes) int32: class-sum = clause_outputs @ vote_matrix.

    This is the class-sum adder bank of the paper's accelerator expressed as
    an (MXU-friendly) int matmul.
    """
    c = jnp.arange(config.n_clauses_total)
    cls = jnp.clip(c // config.clauses_per_class, 0, config.n_classes - 1)
    onehot = (cls[:, None] == jnp.arange(config.n_classes)[None, :])
    return onehot.astype(jnp.int32) * polarity(config)[:, None]


def class_sums(
    config: TMConfig, ta_state: jax.Array, lits: jax.Array, *, training: bool
) -> jax.Array:
    """(B, n_classes) int32 polarity-weighted clause votes."""
    out = clause_outputs(ta_state, lits, training=training)   # (B, C)
    return out.astype(jnp.int32) @ vote_matrix(config)


def predict(
    config: TMConfig,
    state: TMState,
    x: jax.Array,
    *,
    use_kernel: bool | None = None,
    interpret: bool | None = None,
    **blocks,
) -> jax.Array:
    """Argmax classification (binary-tree comparison in the paper).

    When the kernel path is active (``use_kernel=True`` or
    ``REPRO_USE_PALLAS=1``) the sums come from the fused single-pass Pallas
    kernel over packed literals (kernels/fused_infer.py); otherwise the
    dense XLA path below.
    """
    from repro.kernels import ops

    uk, it = ops.kernel_dispatch(use_kernel, interpret)
    if uk:
        from repro.core import packetizer

        lw = packetizer.pack_literals(x)
        iw = packetizer.pack_include_masks(state.ta_state)
        nonempty = jnp.any(state.ta_state >= 0, axis=-1).astype(jnp.uint8)
        sums = ops.tm_forward_packed(
            lw, iw, vote_matrix(config), nonempty,
            use_kernel=uk, interpret=it, **blocks,
        )
    else:
        sums = class_sums(config, state.ta_state, literals(x), training=False)
    return jnp.argmax(sums, axis=-1)


def accuracy(config: TMConfig, state: TMState, x: jax.Array, y: jax.Array) -> jax.Array:
    return jnp.mean((predict(config, state, x) == y).astype(jnp.float32))
