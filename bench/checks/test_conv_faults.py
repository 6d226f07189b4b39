"""The convolutional TM's cell: a sound run is correct, and a run with its
served path broken underneath comes out not correct.

CPU, at a tiny size (``bench/checks/tiny_cells.py``), on the oracle rung
that serves there by default and on the conv kernel's rung (interpret
mode), which serves on the chip.  Three faults: the weights held in int4
(the control), the last row of patch positions dropped, and the
thermometer code off by one.  Run by hand:

    JAX_PLATFORMS=cpu python -m pytest -q bench/checks
"""

import contextlib

import numpy as np
import pytest

from bench.checks import conv_control, tiny_cells

CELL = "convcotm-serve-closed"


def _numbers(out):
    return {k: c["value"] for k, c in out["checks"].items()}


@pytest.mark.parametrize("cell, use_kernel", [
    (CELL, False), (CELL, True), ("mnist-serve-burst8", False)])
@pytest.mark.parametrize("trace", [0, 1])
def test_sound_run_is_correct(cell, use_kernel, trace, monkeypatch):
    from repro.kernels import ops

    monkeypatch.setattr(ops, "_DEFAULT_USE_KERNEL", use_kernel)
    out = tiny_cells.run_cell(cell, trace=trace)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert all(v == 0 for v in _numbers(out).values())
    assert out["metrics"], out


def test_bank_meets_its_recipe():
    """Every class is the reference's answer for at least the floor of the
    pool, and every clause fires on some image."""
    info = tiny_cells.run_cell(CELL)["info"]["bank"]
    assert min(info["class_share"]) >= tiny_cells.CONV["bank"]["class_floor"]
    assert info["clause_fire_share"]["q0"] > 0


def test_bank_leaves_few_rows_all_zero():
    """Rows whose class sums are all 0 answer class 0 whatever the clauses
    do; the checks see the clauses through the other rows."""
    info = tiny_cells.run_cell(CELL)["info"]["bank"]
    assert info["zero_sum_share"] < 0.1, info
    assert info["clauses_fired_per_image"]["q10"] >= 1, info


def _thermometer_off_by_one(monkeypatch):
    from repro.kernels import conv_infer

    monkeypatch.setattr(conv_infer, "thermometer", lambda n, bits: (
        np.arange(n)[:, None] >= np.arange(bits)[None, :]).astype(np.uint8))


@pytest.mark.parametrize("rung", ["oracle", "conv"])
@pytest.mark.parametrize("fault", ["int4_weights", "drop_last_row",
                                   "thermometer_off_by_one"])
def test_fault_is_caught(fault, rung, monkeypatch):
    from repro.kernels import ops

    monkeypatch.setattr(ops, "_DEFAULT_USE_KERNEL", rung == "conv")
    patch = None
    if fault == "int4_weights":
        patch = conv_control.int4_weights
    elif fault == "thermometer_off_by_one":
        _thermometer_off_by_one(monkeypatch)
    with (conv_control.last_row_dropped() if fault == "drop_last_row"
          else contextlib.nullcontext()):
        out = tiny_cells.run_cell(CELL, patch=patch)
    assert out["info"]["serve"]["engine"] == rung
    assert out["info"]["serve"]["engine_buckets"][rung] > 0
    assert not out["correct"]
    assert _numbers(out)["class_sums_differ"] > 0
