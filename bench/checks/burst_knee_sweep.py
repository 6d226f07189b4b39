"""Knee sweep of a burst mix: the highest rate the system sustains when
requests come in bursts (``bench/traffic/burst_open_loop.py``).

    python3 bench/checks/burst_knee_sweep.py --config tm-mnist \
        --mix burst8-mnist --seed 0 --seconds 4 --rates 2000,4000,6000

One process, one set-up (the bank, the engines, the gateway), then one
open-loop window per rate, in the order given.  For each rate it prints
the latency percentiles, how late the generator ran, and the backlog:
requests offered by the window's close and not yet answered then, and how
long after the close the last answer came.  The knee is the highest rate
whose backlog stays within a bucket or two and drains at once; below it
the backlog does not grow over the window.  The cell's rate is then set
to a fixed fraction of the knee, once, in the mix file.  ``knee_sweep.py``
with the burst schedule in place of the Poisson one.
"""

import argparse
import asyncio
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--mix", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=4.0)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args()

    import numpy as np

    from bench import core, run
    from bench.metrics._stats import nearest_rank
    from bench.systems import serving
    from bench.traffic import burst_open_loop, open_loop

    jax = run.setup_jax()
    dev = jax.devices()[0]
    ctx = core.Context(cell="knee-sweep", seed=args.seed, seconds=args.seconds,
                       trace=False, cfg=core.config(args.config),
                       mix=core.traffic(args.mix), t_start=time.perf_counter(),
                       peaks={})
    stack = serving.Stack(ctx)
    print("BANK " + json.dumps(ctx.info["bank"]), flush=True)

    async def sweep():
        gw = await stack.gateway()
        await stack.warm(gw)
        core.settle()
        for rate in [float(r) for r in args.rates.split(",")]:
            due, idx = burst_open_loop.schedule(
                args.seed, rate, args.seconds, len(stack.xp),
                int(ctx.mix["burst_max"]))
            r = await open_loop.window(gw, stack, due, idx, args.seconds)
            close = r["t0"] + args.seconds
            by_close = int((r["offered"] <= close).sum())
            answered = int((r["done"] <= close).sum())
            row = dict(
                rate=rate, device=dev.device_kind, requests=r["attempted"],
                failed=r["failed"],
                p50_ms=1e3 * nearest_rank(r["latency_s"], 50),
                p90_ms=1e3 * nearest_rank(r["latency_s"], 90),
                p99_ms=1e3 * nearest_rank(r["latency_s"], 99),
                gen_late_p99_ms=1e3 * nearest_rank(
                    r["offered"] - r["due"], 99),
                backlog_at_close=by_close - answered,
                drain_after_close_ms=1e3 * (np.nanmax(r["done"]) - close))
            print("KNEE " + json.dumps(row), flush=True)
        await gw.drain()

    asyncio.run(sweep())


if __name__ == "__main__":
    main()
