"""Training: fused-kernel steps as fast as the program takes them.

Mix parameters: ``batch``, ``n_train`` (samples made from the seed and
cycled through in epochs by ``ShardedBatcher``) and ``warm_steps``.  Set-up
builds one trainer, drives it through its first three steps (kept for the
check) and ``warm_steps`` more, and hands the same trainer to the window.
At most two steps are in flight, so dispatch never runs far ahead of the
device; the window ends once the last step dispatched in it has finished
(``block_until_ready``), and its length is measured to that point.
"""

from __future__ import annotations

import gc
import time

import numpy as np

from bench import core, data
from bench.reference import tm as reference
from bench.systems import training

CHECKED_STEPS = 3


def run(ctx: core.Context, tracer) -> dict:
    import jax

    mix = ctx.mix
    batch = int(mix["batch"])
    tr = training.Trainer(ctx, int(mix["n_train"]), batch)
    kept = []
    for _ in range(CHECKED_STEPS):
        kept.append(np.asarray(tr.step(keep=True)))
    for _ in range(int(mix["warm_steps"])):
        tr.step()
    jax.block_until_ready(tr.ta)
    core.settle()

    tracer.start()
    win = jax.profiler.TraceAnnotation("bench.window")
    t0 = time.perf_counter()
    setup_s = t0 - ctx.t_start
    t_end = t0 + float(ctx.seconds)
    win.__enter__()
    steps, prev = 0, [tr.ta, tr.ta]
    while time.perf_counter() < t_end:
        with jax.profiler.TraceAnnotation("bench.step"):
            jax.block_until_ready(prev[0])     # at most two steps in flight
            prev = [prev[1], tr.step()]
        steps += 1
    jax.block_until_ready(tr.ta)
    t1 = time.perf_counter()
    win.__exit__(None, None, None)
    trace = tracer.stop()
    core.unsettle()
    ctx.info["train"] = dict(warm_steps=int(mix["warm_steps"]),
                             window_steps=steps, window_s=t1 - t0)

    ta0, record = tr.ta0, tr.record
    tr.close()
    del tr, prev
    gc.collect()
    sz = data.sizes(ctx.cfg)
    t = time.perf_counter()
    checks = {}
    ta = ta0
    for i, (xb, yb, seed) in enumerate(record):
        ta = reference.train_step(ta, xb, yb, seed, sz)
        if i in (0, CHECKED_STEPS - 1):
            checks[f"step{i + 1}_cells_differ"] = (
                int((np.asarray(ta) != kept[i]).sum()), 0)
    ctx.info["reference"] = dict(seconds=time.perf_counter() - t)
    return dict(kind="train_loop", setup_s=setup_s, t0=t0, window_s=t1 - t0,
                steps=steps, batch=batch, samples=steps * batch, trace=trace,
                attempted=steps, failed=0, checks=checks)
