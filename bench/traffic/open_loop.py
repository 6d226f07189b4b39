"""Open loop: Poisson arrivals at a fixed rate into the serving gateway.

Mix parameters: ``rate`` (requests/s), the gateway's ``bucket`` and
``max_wait_ms``, and ``pool`` (distinct requests made from the seed and
cycled through).  The schedule has exactly ``rate * seconds`` arrivals,
spread as a Poisson process over the window (uniform order statistics),
so every seed offers the same amount of work.

Each wake of the generator offers every request that is due, and stamps
when it was due, when it was offered and when its answer resolved.  A
request is timed from when it was due, so a stall of the server or of the
generator shows in the latency of every request it delays.  Adapted from
``benchmarks/serve_gateway.py:_open_loop``, which slept once per request
and did not report how late it ran.
"""

from __future__ import annotations

import asyncio
import functools
import time

import numpy as np

from bench import core
from bench.systems import serving

GIVE_UP_S = 60.0          # wait this long past the window for answers


def schedule(seed: int, rate: float, seconds: float, pool: int):
    """(due times from the window's start, pool row of each request)."""
    n = int(round(rate * seconds))
    rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, 7])
    return np.sort(rng.uniform(0.0, seconds, n)), rng.integers(0, pool, n)


async def window(gw, stack, due_rel, idx, seconds: float, on_start=None):
    """Offer the schedule, wait for every answer; the window's stamps."""
    import jax

    n = len(due_rel)
    offered = np.full(n, np.nan)
    done = np.full(n, np.nan)
    ok = np.zeros(n, bool)
    pred = np.full(n, -1, np.int64)
    loop = asyncio.get_running_loop()
    finished = loop.create_future()
    left = [n]

    def on_done(j, fut):
        done[j] = time.perf_counter()
        r = fut.result()
        if r.ok:
            ok[j] = True
            pred[j] = r.pred
        left[0] -= 1
        if left[0] == 0 and not finished.done():
            finished.set_result(None)

    c0 = stack.counters(gw)
    win = jax.profiler.TraceAnnotation("bench.window")
    t0 = time.perf_counter() + 0.005
    if on_start is not None:
        on_start(t0)
    due = t0 + due_rel
    win.__enter__()
    gcw = core.GcWatch().__enter__()
    i = 0
    xp = stack.xp
    while i < n:
        j = int(np.searchsorted(due, time.perf_counter(), side="right"))
        with jax.profiler.TraceAnnotation("bench.offer"):
            while i < j:
                fut = gw.offer("t0", xp[idx[i]])
                offered[i] = time.perf_counter()
                fut.add_done_callback(functools.partial(on_done, i))
                i += 1
        if i < n:
            await asyncio.sleep(max(due[i] - time.perf_counter(), 0.0))
    await asyncio.sleep(max(t0 + seconds - time.perf_counter(), 0.0))
    win.__exit__(None, None, None)
    gcw.__exit__()
    c1 = stack.counters(gw)
    if n:
        try:
            await asyncio.wait_for(asyncio.shield(finished), GIVE_UP_S)
        except asyncio.TimeoutError:
            pass
    failed = ~ok
    lat = np.where(failed, np.inf,
                   np.nan_to_num(done, nan=np.inf) - due)
    return dict(t0=t0, window_s=seconds, due=due, offered=offered, done=done,
                ok=ok, pred=pred, latency_s=lat, counters=(c0, c1),
                attempted=n, failed=int(failed.sum()), gc=gcw.summary())


def summary(rec: dict, spans) -> dict:
    """Latency and runner percentiles of a window, for the run's info."""
    from bench.metrics._stats import nearest_rank

    lat = rec["latency_s"]
    late = rec["offered"] - rec["due"]
    b = np.array([e - s for s, e, _ in spans]) if spans else np.zeros(1)
    q = (50, 90, 99, 99.9, 100)
    return dict(
        latency_ms={str(p): 1e3 * nearest_rank(lat, p) for p in q},
        gen_late_ms={str(p): 1e3 * nearest_rank(late[np.isfinite(late)], p)
                     for p in q},
        bucket_ms={str(p): 1e3 * nearest_rank(b, p) for p in q},
        buckets=len(spans), gc=rec["gc"])


def run(ctx: core.Context, tracer) -> dict:
    stack = serving.Stack(ctx)
    S = float(ctx.seconds)
    due_rel, idx = schedule(ctx.seed, float(ctx.mix["rate"]), S,
                            len(stack.xp))
    rec: dict = {}

    async def main():
        gw = await stack.gateway()
        await stack.warm(gw)
        core.settle()
        tracer.start()
        rec.update(await window(
            gw, stack, due_rel, idx, S,
            on_start=lambda t0: rec.update(setup_s=t0 - ctx.t_start)))
        rec["trace"] = tracer.stop()
        rec["gateway"] = await gw.drain()

    asyncio.run(main())
    core.unsettle()
    t0 = rec["t0"]
    rec.update(kind="open_loop", bucket=stack.bucket, all_spans=stack.spans,
               spans=[s for s in stack.spans if t0 <= s[0] < t0 + S])
    ctx.info["window"] = summary(rec, rec["spans"])
    rec["checks"] = stack.check(rec.pop("pred"), rec["ok"], idx)
    return rec
