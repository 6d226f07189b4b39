"""Inputs made from the seed, on the device, in one jitted call each.

The samples follow the class-prototype booleans of ``data/synthetic.py``
(each class lights a sparse set of prototype pixels with high probability
over a noisy background), with one change: the prototypes are part of the
configuration (``dataset.prototype_seed``), as a real dataset is fixed,
and ``--seed`` draws the samples, their labels and their order.  So every
seed sees the same task and trains banks of the same statistics.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from bench import core


def sizes(cfg: dict) -> dict:
    """The TM's sizes from a configuration file, with the clause axis
    padded as the program pads it (padded clauses stay empty)."""
    raw = cfg["n_classes"] * cfg["clauses_per_class"]
    m = cfg["clause_pad_multiple"]
    return dict(F=cfg["n_features"], K=cfg["n_classes"], L=2 * cfg["n_features"],
                cpc=cfg["clauses_per_class"], C_raw=raw, C=-(-raw // m) * m,
                T=cfg["threshold"], s=float(cfg["s"]), n_states=cfg["n_states"],
                boost=bool(cfg["boost_true_positive"]))


@functools.partial(jax.jit, static_argnames=("n", "F", "K", "density", "on",
                                              "bg"))
def _samples(proto_key, key, *, n, F, K, density, on, bg):
    protos = jax.random.uniform(proto_key, (K, F)) < density
    ky, kx = jax.random.split(key)
    y = jax.random.randint(ky, (n,), 0, K, dtype=jnp.int32)
    p = jnp.where(protos[y], on, bg)
    x = (jax.random.uniform(kx, (n, F)) < p).astype(jnp.uint8)
    return x, y


def samples(cfg: dict, seed: int, salt: int, n: int):
    """(x (n, F) uint8, y (n,) int32) on the device."""
    d = cfg["dataset"]
    return _samples(
        jax.random.PRNGKey(d["prototype_seed"]), core.seed_key(seed, salt),
        n=n, F=cfg["n_features"], K=cfg["n_classes"],
        density=d["prototype_density"], on=d["on_prob"],
        bg=d["background_prob"])


@functools.partial(jax.jit, static_argnames=("C", "C_raw", "L", "n_states"))
def _automata(key, *, C, C_raw, L, n_states):
    ta = jax.random.randint(key, (C, L), -1, 1, dtype=jnp.int8)
    # padded clauses are all-exclude, as the program's init pins them
    return jnp.where(jnp.arange(C)[:, None] < C_raw, ta, jnp.int8(-n_states))


def initial_automata(cfg: dict, seed: int, salt: int):
    """The standard TM start: every automaton in {-1, 0}, beside the
    decision boundary; padded clauses excluded."""
    s = sizes(cfg)
    return _automata(core.seed_key(seed, salt), C=s["C"], C_raw=s["C_raw"],
                     L=s["L"], n_states=s["n_states"])
