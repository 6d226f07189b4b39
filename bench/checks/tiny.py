"""Drive a cell at a tiny size on the CPU, with the chip check skipped.

The self-checks use it: the whole run (set-up, window, reference, checks,
reducers) at tm-tiny's sizes, with a stage of the timed path replaced
through ``Context.patch`` where a check breaks it on purpose.
"""

from __future__ import annotations

from bench import core

# tm-tiny's sizes (configs/matador_tm.py), a small bank and small mixes
TINY = dict(n_features=32, n_classes=3, clauses_per_class=8, threshold=8,
            s=4.0, clause_pad_multiple=1,
            bank={"n_train": 256, "epochs": 1, "batch": 64})
MIXES = {"open_loop": dict(rate=2000, pool=1024, bucket=64),
         "closed_loop": dict(clients=128, pool=1024, bucket=64),
         "train_loop": dict(batch=64, n_train=512, warm_steps=2)}


def run_cell(cell: str, *, seed: int = 2**31 + 17, seconds: float = 1.0,
             trace: int = 0, patch=None) -> dict:
    from bench import run

    spec = core.benchmark()
    w = core.workload(spec, cell)
    cfg = dict(core.config(w["config"]), **TINY)
    mix = core.traffic(w["traffic"])
    mix.update(MIXES[mix["kind"]])
    orig = core.traffic
    core.traffic = lambda name: mix
    try:
        args = run.parse(["--workload", cell, "--seed", str(seed),
                          "--seconds", str(seconds), "--trace", str(trace)])
        return run.run(args, require_chip=False, cfg=cfg, patch=patch,
                       peaks_kind="TPU v5 lite", log=lambda s: None)
    finally:
        core.traffic = orig


def sizes() -> dict:
    from bench import data

    return data.sizes(dict(core.config("tm-mnist"), **TINY))
