"""Online-learning service benchmark -> BENCH_online.json.

    PYTHONPATH=src python -m benchmarks.online_update [--out BENCH_online.json]

Drives the full online-update loop of ``runtime/online.py`` under live
gateway load: labeled feedback streams into a live automata bank beside
the serving artifact, include-bit drift arms incremental recompiles, and
promotions hot-swap the zoo entry atomically while an open-loop Poisson
request stream keeps arriving.  Reported per row:

  * ``req_per_s``      — steady-state answered throughput UNDER online
                         updating (training, drift checks, rebuilds, and
                         swaps all share the machine with serving).
  * ``swap_pause_p99_ms`` [the gated scalar, also ``us_per_call``] — p99
                         wall-time of the first bucket served after each
                         promotion: the pause a hot-swap actually imposes
                         on the request stream (rebound engines re-trace
                         here).  The zero-drop invariant is asserted, so
                         this pause is a LATENCY cost, never a loss.
  * ``p99_ms``         — end-to-end request p99 across the whole run.
  * ``drift_to_promotion_ms`` (derived) — p50 latency from the drift
                         threshold crossing to the committed swap.

The lead ``online_steady_*`` row runs ``swap_policy="immediate"`` (every
rebuild promotes — the swap machinery is exercised maximally); the second
row runs the shadow-canary pipeline with a mirrored-bucket agreement
verdict before each swap.  scripts/check_bench.py gates the lead row on
BOTH ``swap_pause_p99_ms`` and ``req_per_s`` (pause regression or
throughput collapse >2x fails), mirroring the serve-gateway rule.
"""

from __future__ import annotations

import argparse
import asyncio
import itertools
import json
import platform
import sys
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.matador_tm import TM_CONFIGS
from repro.core import compiler, packetizer, tm, train
from repro.data import make_boolean_classification
from repro.kernels import ops
from repro.runtime.gateway import Gateway
from repro.runtime.online import OnlineConfig, OnlineUpdater
from repro.runtime.zoo import ArtifactZoo

TENANT = "t0"
BUCKET = 64


def _build(arch: str = "tm-tiny"):
    config = TM_CONFIGS[arch]
    X, y = make_boolean_classification(
        512, config.n_features, config.n_classes, seed=0)
    state = tm.init(config, jax.random.PRNGKey(0))
    state = train.fit(config, state, jnp.asarray(X), jnp.asarray(y),
                      epochs=1, batch_size=64, rng=jax.random.PRNGKey(1))
    return config, state, compiler.compile_tm(config, state.ta_state), X, y


async def _open_loop(gw, xp, rate: float, n: int, futs: list) -> None:
    rng = np.random.default_rng(0)
    gaps = rng.exponential(1.0 / rate, size=n)
    t_next = time.perf_counter()
    for j in range(n):
        t_next += gaps[j]
        delay = t_next - time.perf_counter()
        if delay > 0:
            await asyncio.sleep(delay)
        futs.append(gw.offer(TENANT, xp[j % len(xp)]))


def _percentile(xs, q):
    if not xs:
        return 0.0
    return float(np.percentile(np.asarray(xs, np.float64), q))


def run_policy(policy: str, *, rate: float, n: int,
               drift_threshold: float = 0.02,
               canary_agreement: float = 0.75) -> dict:
    """One full online-serving run under ``swap_policy=policy``.

    The canary row lowers the agreement bar: the 1-epoch bench bank still
    moves fast, and the row measures the canary PIPELINE cost, not the
    verdict policy (a production bar belongs in serve.py's flags).
    """
    # feedback pool = the TRAINING distribution (continued learning of the
    # same task): the bank keeps refining, so drift crosses and candidates
    # stay canary-agreeable — a distribution SHIFT canary-failure drill
    # lives in tests/test_online.py, not in a gated throughput number
    config, state, compiled, Xf, yf = _build()
    xp = np.asarray(packetizer.pack_literals(jnp.asarray(Xf)))
    W = xp.shape[1]

    current = {"compiled": compiled}
    swap_pauses: list = []
    post_swap = threading.Event()     # armed by on_promote, consumed by
    counter = itertools.count()       # the next bucket's wall-time record

    def build_engine(name):
        art = current["compiled"]
        if name == "dense":
            return jax.jit(lambda xw: compiler.run_compiled(
                art, xw, engine="dense").argmax(-1))
        return jax.jit(lambda xw: compiler.run_compiled(
            art, xw, engine="oracle").argmax(-1))

    levels = ["dense", "oracle"]
    ladder = ops.EngineLadder(
        [(nm, (lambda n2=nm: build_engine(n2))) for nm in levels])
    ladder.run(lambda: jnp.zeros((BUCKET, W), jnp.uint32),
               bucket="warm", count=False)

    def run_rows(rows):
        i = next(counter)
        t_b = time.perf_counter()
        padded = np.zeros((BUCKET, W), np.uint32)
        padded[:len(rows)] = rows
        out = ladder.run(lambda: jnp.asarray(padded), bucket=i)
        preds = np.asarray(out)[:len(rows)]
        if post_swap.is_set():
            post_swap.clear()
            swap_pauses.append(time.perf_counter() - t_b)
        return preds

    def _nbytes(c):
        return int(c.include_words.nbytes + c.word_ids.nbytes
                   + c.votes.nbytes)

    def make_obj(c):
        return {"compiled": c, "run": run_rows}, _nbytes(c)

    zoo = ArtifactZoo(lambda tenant: make_obj(current["compiled"]),
                      max_entries=1)
    runner = zoo.runner(lambda obj, rows: obj["run"](rows))

    def canary_serve(obj, rows):
        fn = obj.get("_canary_fn")
        if fn is None:
            c = obj["compiled"]
            fn = obj["_canary_fn"] = jax.jit(
                lambda xw: compiler.run_compiled(
                    c, xw, engine="oracle").argmax(-1))
        padded = np.zeros((BUCKET, W), np.uint32)
        padded[:len(rows)] = rows
        return np.asarray(fn(jnp.asarray(padded)))[:len(rows)]

    def on_promote(cand):
        current["compiled"] = cand
        ladder.rebind(
            [(nm, (lambda n2=nm: build_engine(n2))) for nm in levels])
        post_swap.set()

    upd = OnlineUpdater(
        config, state.ta_state, compiled,
        cfg=OnlineConfig(drift_threshold=drift_threshold,
                         swap_policy=policy, canary_frac=0.5, canary_min=2,
                         canary_agreement=canary_agreement),
        zoo=zoo, tenant=TENANT, make_obj=make_obj, serve_fn=canary_serve,
        deployed_obj={"compiled": compiled, "run": run_rows},
        deployed_nbytes=_nbytes(compiled), on_promote=on_promote)

    stop_online = threading.Event()

    def online_loop():
        feed = iter(range(n))
        while not stop_online.is_set():
            progressed = False
            for _ in range(upd.cfg.batch_size):
                j = next(feed, None)
                if j is None:
                    break
                upd.ingest(Xf[j % len(Xf)], int(yf[j % len(yf)]))
                progressed = True
            progressed = upd.step() or progressed
            if not progressed:
                time.sleep(0.001)

    async def go():
        gw = await Gateway(runner, bucket=BUCKET, max_wait=0.005,
                           mirror=upd.mirror).start()
        th = threading.Thread(target=online_loop, daemon=True)
        th.start()
        t0 = time.perf_counter()
        futs: list = []
        await _open_loop(gw, xp, rate, n, futs)
        health = await gw.drain()
        wall = time.perf_counter() - t0
        stop_online.set()
        th.join(timeout=10)
        await asyncio.gather(*futs)
        return health, wall

    health, wall = asyncio.run(go())
    oh = upd.health()
    assert health["unaccounted"] == 0, health
    assert oh["promotions"] >= 1, (
        f"online bench made no promotions (drift {oh['drift']:.3f}) — "
        "the swap-pause row would be vacuous", oh)
    pause_p99 = _percentile(swap_pauses, 99) * 1e3
    d2p_p50 = _percentile(oh["drift_to_promotion_ms"], 50)
    return dict(
        name=f"online_steady_{policy}_r{int(rate)}_b{BUCKET}",
        us_per_call=pause_p99 * 1e3,
        swap_pause_p99_ms=pause_p99,
        p99_ms=health["latency_ms"]["p99"] or 0.0,
        req_per_s=health["answered"] / wall if wall > 0 else 0.0,
        derived=(f"promotions={oh['promotions']};"
                 f"incremental={oh['incremental_rebuilds']};"
                 f"full={oh['full_rebuilds']};"
                 f"canary_passes={oh['canary']['passes']};"
                 f"canary_failures={oh['canary']['failures']};"
                 f"drift_to_promotion_p50_ms={d2p_p50:.2f};"
                 f"swaps={zoo.health()['swaps']};"
                 f"answered={health['answered']};"
                 f"mirrored={health['mirrored']}"),
    )


def run(rate: float = 1200.0, n: int = 1200) -> list:
    rows = [run_policy("immediate", rate=rate, n=n)]
    rows.append(run_policy("canary", rate=rate, n=n))
    return rows


def write_report(rows: list, path: str = "BENCH_online.json") -> None:
    report = dict(
        benchmark="online_update",
        backend=jax.default_backend(),
        interpret_mode=jax.default_backend() != "tpu",
        jax_version=jax.__version__,
        platform=platform.platform(),
        rows=rows,
    )
    with open(path, "w") as f:
        json.dump(report, f, indent=1)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--out", default="BENCH_online.json")
    ap.add_argument("--rate", type=float, default=1200.0,
                    help="open-loop Poisson offered rate (req/s)")
    ap.add_argument("--requests", type=int, default=1200)
    args = ap.parse_args()
    rows = run(rate=args.rate, n=args.requests)
    write_report(rows, args.out)
    for r in rows:
        print(f"{r['name']},{r['us_per_call']:.3f},"
              f"swap_pause_p99_ms={r['swap_pause_p99_ms']:.2f};"
              f"req_per_s={r['req_per_s']:.0f};{r['derived']}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
