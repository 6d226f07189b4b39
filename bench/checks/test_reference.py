"""The plain reference agrees with the program's own oracles at tm-tiny
size on the CPU (``kernels/ref.py`` through ``ops.tm_train_step_kernel``
with the kernels off, and ``core/tm.py``'s dense class sums), bit for
bit; and its batch chunking changes nothing.

    JAX_PLATFORMS=cpu python -m pytest -q bench/checks
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import data
from bench.checks import tiny
from bench.reference import tm as reference


def _setup(seed):
    from bench import core
    from bench.systems.training import tm_config

    cfg = dict(core.config("tm-mnist"), **tiny.TINY)
    x, y = data.samples(cfg, seed, 1, 64)
    return cfg, tm_config(cfg), data.sizes(cfg), x, y, data.initial_automata(
        cfg, seed, 2)


@pytest.mark.parametrize("seed", [0, 2**31 + 5, 77])
def test_train_step_matches_program_oracle(seed):
    from repro.kernels import ops

    cfg, tmc, sz, x, y, ta = _setup(seed)
    for step in range(3):
        s = (seed * 7 + step) & 0xFFFFFFFF
        want, _ = ops.tm_train_step_kernel(tmc, ta, x, y, jnp.uint32(s),
                                           use_kernel=False)
        got = reference.train_step(ta, x, y, s, sz, chunk=16)
        assert np.array_equal(np.asarray(got), np.asarray(want)), step
        assert np.count_nonzero(np.asarray(got) != np.asarray(ta)) > 0
        ta = got


def test_chunking_is_exact():
    _, _, sz, x, y, ta = _setup(3)
    a = reference.train_step(ta, x, y, 11, sz, chunk=8)
    b = reference.train_step(ta, x, y, 11, sz, chunk=64)
    assert np.array_equal(np.asarray(a), np.asarray(b))


def test_class_sums_match_program():
    from repro.core import tm

    cfg, tmc, sz, x, y, ta = _setup(5)
    for _ in range(3):
        ta = reference.train_step(ta, x, y, 9, sz)
    for training in (True, False):
        want = tm.class_sums(tmc, ta, tm.literals(x), training=training)
        got = reference.class_sums(ta, x, sz, training=training)
        assert np.array_equal(np.asarray(got), np.asarray(want))
    pred = reference.predict(ta, x, sz, block=16)
    assert np.array_equal(pred, np.asarray(tm.predict(tmc, tm.TMState(
        ta_state=ta, steps=jnp.int32(0)), x, use_kernel=False)))


def test_int4_states_clip():
    _, _, sz, x, y, ta = _setup(7)
    for _ in range(3):
        ta = reference.train_step(ta, x, y, 4, sz, state_bits=4)
    a = np.asarray(ta)
    assert a.min() >= -8 and a.max() <= 7
    jax.block_until_ready(ta)
