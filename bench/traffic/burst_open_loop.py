"""Open loop in bursts: bursts arrive as a Poisson process, and each is
1 to ``burst_max`` single-image requests (uniform) due at the same
instant, as edge sensors and cameras send short runs of frames.

Mix parameters: ``rate`` (requests/s), ``burst_max``, the gateway's
``bucket`` and ``max_wait_ms``, and ``pool`` (distinct requests made from
the seed and cycled through).  The schedule has exactly ``rate *
seconds`` requests, so every seed offers the same amount of work: burst
sizes are drawn until they cover it (the last one cut), and the bursts'
times are uniform order statistics over the window.  ``run`` is
``open_loop.run`` with this schedule in place of ``open_loop.schedule``;
``open_loop.window`` offers every request that is due at each wake, a
burst at once, and times each request from when it was due.
"""

from __future__ import annotations

import asyncio

import numpy as np

from bench import core
from bench.systems import serving
from bench.traffic import open_loop


def schedule(seed: int, rate: float, seconds: float, pool: int,
             burst_max: int):
    """(due times from the window's start, pool row of each request)."""
    n = int(round(rate * seconds))
    rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, 9])
    sizes = rng.integers(1, burst_max + 1, max(n, 1))
    ends = np.cumsum(sizes)
    nb = int(np.searchsorted(ends, n)) + 1 if n else 0
    sizes = sizes[:nb]
    if nb:
        sizes[-1] -= ends[nb - 1] - n
    due = np.repeat(np.sort(rng.uniform(0.0, seconds, nb)), sizes)
    return due, rng.integers(0, pool, n)


def run(ctx: core.Context, tracer) -> dict:
    stack = serving.Stack(ctx)
    S = float(ctx.seconds)
    due_rel, idx = schedule(ctx.seed, float(ctx.mix["rate"]), S,
                            len(stack.xp), int(ctx.mix["burst_max"]))
    rec: dict = {}

    async def main():
        gw = await stack.gateway()
        await stack.warm(gw)
        core.settle()
        tracer.start()
        rec.update(await open_loop.window(
            gw, stack, due_rel, idx, S,
            on_start=lambda t0: rec.update(setup_s=t0 - ctx.t_start)))
        rec["trace"] = tracer.stop()
        rec["gateway"] = await gw.drain()

    asyncio.run(main())
    core.unsettle()
    t0 = rec["t0"]
    rec.update(kind="open_loop", bucket=stack.bucket, all_spans=stack.spans,
               spans=[s for s in stack.spans if t0 <= s[0] < t0 + S])
    ctx.info["window"] = open_loop.summary(rec, rec["spans"])
    ctx.info["window"]["bursts"] = int(len(np.unique(due_rel)))
    rec["checks"] = stack.check(rec.pop("pred"), rec["ok"], idx)
    return rec
