"""Closed loop over the convolutional TM's serving stack: a fixed number of
clients, each sending its next request when its answer comes back,
through the serving gateway.

Mix parameters: ``clients``, the gateway's ``bucket`` and ``max_wait_ms``,
and ``pool`` (distinct images made from the seed; the order in which
clients draw them comes from the seed too).  The window counts the
requests answered inside it.  ``run`` is ``closed_loop.run`` over
``conv_serving.Stack`` in place of ``serving.Stack``, with the bank's
pass of the reference over the pool left out of ``setup_s``; a traced
run adds the kernel's share of the device's busy time to the run's info.
"""

from __future__ import annotations

import asyncio
import functools
import time

import numpy as np

from bench import core
from bench.metrics._stats import kernel_time
from bench.systems import conv_serving
from bench.traffic.closed_loop import GIVE_UP_S

KERNELS = ("conv_tm_forward",)


def run(ctx: core.Context, tracer) -> dict:
    import jax

    stack = conv_serving.Stack(ctx)
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
        # the reference's pass over the pool, which the bank needs, is
        # the reference's work and not the program's set-up
        rec["setup_s"] = t0 - ctx.t_start - stack.reference_s
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
    tr = rec["trace"]
    if tr and tr["busy_s"] > 0:
        secs, calls = kernel_time(tr, KERNELS)
        ctx.info["window"].update(kernel_calls=calls,
                                  kernel_busy_share=secs / tr["busy_s"])
    rec.update(kind="closed_loop", offered=offered_a, done=done_a, ok=ok_a,
               spans=spans, all_spans=stack.spans, bucket=stack.bucket,
               answered_in_window=int((ok_a & (done_a <= t0 + S)).sum()),
               attempted=len(offered), failed=int((~ok_a).sum()))
    rec["checks"] = stack.check(pred_a, ok_a, idx_a)
    return rec
