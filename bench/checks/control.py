"""Readings that set each limit: the program's, and the control's, on the chip.

    python3 bench/checks/control.py --workload <cell> --seeds 1,2,3 \
        --seconds 10 [--program] [--control] [--faults]

One process runs the cell's whole run once per seed and mode, at the
cell's own size and load (``bench/run.py:run``), and prints each run's
compared numbers on a ``READING`` line:

* ``program``: the program as it is (the lower readings);
* ``control``: the plain reference with its automata held in int4, the
  precision below the configuration's int8, in the place of the
  program's training step (the upper readings).  A training cell steps
  it in its window; a serving cell trains the bank it serves with it at
  set-up, and the program compiles and serves that bank;
* ``faults`` (training cells): a step that returns its state unchanged,
  and a step that leaves out half of the batch.
"""

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def train_patches(cfg):
    from bench import data
    from bench.reference import tm as reference

    sz = data.sizes(cfg)

    def int4(stage, fn):
        if stage != "train_step":
            return fn
        return lambda c, ta, x, y, seed, **kw: (
            reference.train_step(ta, x, y, int(seed), sz, state_bits=4), None)

    def unchanged(stage, fn):
        if stage != "train_step":
            return fn
        return lambda c, ta, x, y, seed, **kw: (ta, None)

    def half(stage, fn):
        if stage != "train_step":
            return fn

        def step(c, ta, x, y, seed, **kw):
            h = x.shape[0] // 2
            return fn(c, ta, x[:h], y[:h], seed, **kw)
        return step

    return {"control": int4, "fault_unchanged": unchanged,
            "fault_half_batch": half}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--program", action="store_true")
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--faults", action="store_true")
    args = ap.parse_args()

    from bench import core, run

    spec = core.benchmark()
    cell = core.workload(spec, args.workload)
    cfg = core.config(cell["config"])
    train = core.traffic(cell["traffic"])["kind"] == "train_loop"
    modes = []
    if args.program:
        modes.append(("program", None))
    if args.control:
        modes.append(("control", train_patches(cfg)["control"]))
    if args.faults and train:
        p = train_patches(cfg)
        modes += [(m, p[m]) for m in ("fault_unchanged", "fault_half_batch")]
    for seed in [int(s) for s in args.seeds.split(",")]:
        for mode, patch in modes:
            a = run.parse(["--workload", args.workload, "--seed", str(seed),
                           "--seconds", str(args.seconds), "--trace", "0"])
            out = run.run(a, patch=patch, log=lambda s: None)
            print("READING " + json.dumps(dict(
                cell=args.workload, seed=seed, mode=mode,
                correct=out["correct"], attempted=out["attempted"],
                checks={k: c["value"] for k, c in out["checks"].items()},
                device=out["device"]["kind"],
                bank=out["info"].get("bank"))), flush=True)


if __name__ == "__main__":
    main()
