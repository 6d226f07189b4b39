"""Drive the cells added with the convolutional TM at a tiny size on the
CPU, with the chip check skipped (the pattern of ``tiny.py``, whose mixes
cover the first benchmark's generators only)."""

from __future__ import annotations

from bench import core
from bench.checks import tiny

# a 12x12 image, a 4x4 window (81 positions, 64 literals a patch), 16
# clauses over 3 classes, and a bank recipe scaled to 32 true literals
CONV = dict(image_h=12, image_w=12, window=4, positions=81,
            patch_features=32, patch_literals=64, n_clauses=16, n_classes=3,
            n_features=144,
            bank={"k_min": 3, "k_max": 8, "lit_pixel_share": 0.5,
                  "weight_max": 127, "class_floor": 0.02, "max_draws": 1000})
MIXES = {"conv_closed_loop": dict(clients=128, pool=1024, bucket=64),
         "burst_open_loop": dict(rate=2000, pool=1024)}


def run_cell(cell: str, *, seed: int = 2**31 + 17, seconds: float = 1.0,
             trace: int = 0, patch=None) -> dict:
    from bench import run

    spec = core.benchmark()
    w = core.workload(spec, cell)
    cfg = core.config(w["config"])
    cfg.update(CONV if "image_h" in cfg else tiny.TINY)
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
