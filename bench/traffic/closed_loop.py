"""Closed loop: a fixed number of clients, each sending its next request
when its answer comes back, through the serving gateway.

Mix parameters: ``clients``, the gateway's ``bucket`` and ``max_wait_ms``,
and ``pool`` (distinct requests made from the seed; the order in which
clients draw them comes from the seed too).  The window counts the
requests answered inside it.  Adapted from
``benchmarks/serve_gateway.py:_closed_loop``; clients are done-callbacks
rather than coroutines, so the client side costs the host little.
"""

from __future__ import annotations

import asyncio
import functools
import time

import numpy as np

from bench import core
from bench.systems import serving

GIVE_UP_S = 60.0


def run(ctx: core.Context, tracer) -> dict:
    import jax

    stack = serving.Stack(ctx)
    S = float(ctx.seconds)
    clients = int(ctx.mix["clients"])
    rng = np.random.default_rng([ctx.seed & 0xFFFFFFFF, ctx.seed >> 32, 8])
    draw = rng.integers(0, len(stack.xp), 1 << 22)
    offered, done, ok, pred, idx = [], [], [], [], []
    rec: dict = {}

    async def main():
        gw = await stack.gateway()
        await stack.warm(gw)
        loop = asyncio.get_running_loop()
        finished = loop.create_future()
        state = dict(k=0, out=0, t_end=np.inf)
        xp = stack.xp
        core.settle()

        def send(c):
            k = state["k"]
            state["k"] = k + 1
            j = int(draw[k & (len(draw) - 1)])
            fut = gw.offer("t0", xp[j])
            offered.append(time.perf_counter())
            done.append(np.nan)
            ok.append(False)
            pred.append(-1)
            idx.append(j)
            state["out"] += 1
            fut.add_done_callback(functools.partial(on_done, c, k))

        def on_done(c, k, fut):
            t = time.perf_counter()
            done[k] = t
            r = fut.result()
            if r.ok:
                ok[k] = True
                pred[k] = r.pred
            state["out"] -= 1
            if t < state["t_end"]:
                send(c)
            elif state["out"] == 0 and not finished.done():
                finished.set_result(None)

        tracer.start()
        win = jax.profiler.TraceAnnotation("bench.window")
        t0 = time.perf_counter()
        rec["setup_s"] = t0 - ctx.t_start
        state["t_end"] = t0 + S
        win.__enter__()
        gcw = core.GcWatch().__enter__()
        c0 = stack.counters(gw)
        for c in range(clients):
            send(c)
        await asyncio.sleep(max(t0 + S - time.perf_counter(), 0.0))
        win.__exit__(None, None, None)
        gcw.__exit__()
        rec["gc"] = gcw.summary()
        c1 = stack.counters(gw)
        try:
            await asyncio.wait_for(asyncio.shield(finished), GIVE_UP_S)
        except asyncio.TimeoutError:
            pass
        rec["trace"] = tracer.stop()
        rec["gateway"] = await gw.drain()
        rec.update(t0=t0, window_s=S, counters=(c0, c1))

    asyncio.run(main())
    core.unsettle()
    t0, S = rec["t0"], rec["window_s"]
    offered_a, done_a = np.asarray(offered), np.asarray(done)
    ok_a, pred_a, idx_a = np.asarray(ok), np.asarray(pred), np.asarray(idx)
    spans = [s for s in stack.spans if t0 <= s[0] < t0 + S]
    b = np.array([e - s for s, e, _ in spans]) if spans else np.zeros(1)
    ctx.info["window"] = dict(
        buckets=len(spans), gc=rec["gc"],
        bucket_ms={str(p): 1e3 * float(np.percentile(b, p))
                   for p in (50, 90, 99, 100)})
    rec.update(kind="closed_loop", offered=offered_a, done=done_a, ok=ok_a,
               spans=spans, all_spans=stack.spans, bucket=stack.bucket,
               answered_in_window=int((ok_a & (done_a <= t0 + S)).sum()),
               attempted=len(offered), failed=int((~ok_a).sum()))
    rec["checks"] = stack.check(pred_a, ok_a, idx_a)
    return rec
