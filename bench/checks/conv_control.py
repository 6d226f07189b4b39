"""Readings that set the convolutional cell's limits: the program's, and
the control's, on the chip.

    python3 bench/checks/conv_control.py --workload convcotm-serve-closed \
        --seeds 1,2,3 --seconds 10 [--program] [--control] [--drop-row]

One process runs the cell's whole run once per seed and mode, at the
cell's own size and load (``bench/run.py:run``), and prints each run's
compared numbers on a ``READING`` line:

* ``program``: the program as it is (the lower readings);
* ``control``: the bank's weights held in int4 (clipped to [-8, 7]), the
  precision below the configuration's int8, compiled and served in their
  place (the upper readings);
* ``drop_row``: the last row of patch positions dropped on both rungs,
  the kernel's (its banked operands) and the oracle's (its patch
  literals), so the fault reaches whichever rung serves.
"""

import argparse
import contextlib
import json
import sys
from pathlib import Path
from unittest import mock

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def int4_weights(stage, fn):
    """The control: ``compile_tm`` given the weights clipped to int4."""
    import numpy as np

    if stage != "compile":
        return fn
    return lambda config, ta, *, weights, **kw: fn(
        config, ta, weights=np.clip(weights, -8, 7), **kw)


@contextlib.contextmanager
def last_row_dropped():
    """The last row of patch positions dropped: no clause fires there,
    on the kernel rung or on the oracle rung."""
    from repro.core import packetizer
    from repro.kernels import conv_infer

    real_lits, real_ops = packetizer.patch_literals, conv_infer.conv_operands

    def patch_literals(img_words, geom):
        lits = real_lits(img_words, geom)
        return lits[:, :geom.positions - geom.Pw]

    def conv_operands(include, votes, geom):
        band, v = real_ops(include, votes, geom)
        band = band.copy()
        # the one-hot column of the last row: one violation for every
        # clause there
        band[:, geom.win * geom.W + geom.Ph] += 1
        return band, v

    with mock.patch.object(packetizer, "patch_literals", patch_literals), \
            mock.patch.object(conv_infer, "conv_operands", conv_operands):
        yield


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--program", action="store_true")
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--drop-row", action="store_true")
    args = ap.parse_args()

    from bench import run

    modes = ([("program", None)] if args.program else []) + (
        [("control", int4_weights)] if args.control else []) + (
        [("drop_row", None)] if args.drop_row else [])
    for seed in [int(s) for s in args.seeds.split(",")]:
        for mode, patch in modes:
            a = run.parse(["--workload", args.workload, "--seed", str(seed),
                           "--seconds", str(args.seconds), "--trace", "0"])
            with (last_row_dropped() if mode == "drop_row"
                  else contextlib.nullcontext()):
                out = run.run(a, patch=patch, log=lambda s: None)
            print("READING " + json.dumps(dict(
                cell=args.workload, seed=seed, mode=mode,
                correct=out["correct"], attempted=out["attempted"],
                checks={k: c["value"] for k, c in out["checks"].items()},
                metrics={k: m["value"] for k, m in out["metrics"].items()},
                device=out["device"]["kind"],
                bank=out["info"].get("bank"),
                serve=out["info"].get("serve"))), flush=True)


if __name__ == "__main__":
    main()
