"""A run with its timed path broken underneath must come out not correct.

CPU, tm-tiny sizes (``bench/checks/tiny.py``): the whole run is driven
with the chip check skipped and one stage replaced.  Run by hand:

    JAX_PLATFORMS=cpu python -m pytest -q bench/checks
"""

import jax.numpy as jnp
import numpy as np
import pytest

from bench.checks import tiny
from bench.reference import tm as reference

SERVE = ["mnist-serve-poisson", "mnist-serve-closed"]
TRAIN = "fmnist-train"


def _numbers(out):
    return {k: c["value"] for k, c in out["checks"].items()}


@pytest.mark.parametrize("cell", SERVE + [TRAIN])
def test_sound_run_is_correct(cell):
    out = tiny.run_cell(cell)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert list(out)[-1] == "info" and list(out)[-2] == "checks"


@pytest.mark.parametrize("cell", SERVE)
def test_altered_answer_is_caught(cell):
    """An answer altered where it is produced: the first prediction of
    every bucket is moved to the next class."""
    def patch(stage, fn):
        if stage != "runner":
            return fn

        def runner(tenant, rows, quality=0):
            preds, info = fn(tenant, rows, quality=quality)
            preds = np.array(preds)
            preds[0] = (preds[0] + 1) % 3
            return preds, info
        return runner

    out = tiny.run_cell(cell, patch=patch)
    assert not out["correct"]
    assert _numbers(out)["answers_wrong"] > 0


def _train_patch(make):
    return lambda stage, fn: make(fn) if stage == "train_step" else fn


def test_unchanged_state_is_caught():
    """A step that returns its state unchanged."""
    out = tiny.run_cell(TRAIN, patch=_train_patch(
        lambda fn: lambda cfg, ta, x, y, seed, **kw: (ta, None)))
    assert not out["correct"]
    assert min(_numbers(out).values()) > 0


def test_half_batch_is_caught():
    """Half of the batch left out."""
    def make(fn):
        def step(cfg, ta, x, y, seed, **kw):
            h = x.shape[0] // 2
            return fn(cfg, ta, x[:h], y[:h], seed, **kw)
        return step

    out = tiny.run_cell(TRAIN, patch=_train_patch(make))
    assert not out["correct"]
    assert min(_numbers(out).values()) > 0


@pytest.mark.parametrize("cell", SERVE + [TRAIN])
def test_int4_control_is_caught(cell):
    """The control: the reference step with its automata held in int4, put
    in the program's place.  A serving cell trains the bank it serves with
    it, so the answers served come from an int4 bank."""
    sz = tiny.sizes()

    def step(cfg, ta, x, y, seed, **kw):
        return reference.train_step(ta, x, y, int(seed), sz, state_bits=4), None

    out = tiny.run_cell(cell, patch=_train_patch(lambda fn: step))
    assert not out["correct"]
    assert max(_numbers(out).values()) > 0


def test_trace_run_reports_checks():
    out = tiny.run_cell("mnist-serve-closed", trace=1)
    assert out["correct"]
    assert out["device"]["window_s"] > 0
    assert "breakdown" in out
    assert jnp.isfinite(out["metrics"]["bucket_ms.closed"]["value"])
