"""Serving-gateway load benchmark + chaos harness -> BENCH_serve.json.

    PYTHONPATH=src python -m benchmarks.serve_gateway [--out BENCH_serve.json]
    PYTHONPATH=src python -m benchmarks.serve_gateway --chaos

Drives the resilient async gateway (``runtime/gateway.py``) + artifact zoo
(``runtime/zoo.py``) with live mixed-tenant load against a trained+compiled
tiny TM artifact served through the engine ladder:

  * ``serve_openloop_*`` [the lead row] — open-loop Poisson arrivals at a
    fixed offered rate over 3 round-robin tenants: the latency a client
    actually observes (queueing + batching + engine), reported as
    p50/p99 ms and achieved req/s.  Open loop does not slow down when the
    server does, so backlog and shedding are REAL, not masked by client
    back-pressure.
  * ``serve_closedloop_*`` — N concurrent clients, each submit->await->
    submit: the saturated-throughput shape.

``--chaos`` turns the same Poisson run into a fault drill: one injected
fault per class (admission: ``gateway.queue_overflow``; zoo:
``zoo.load_fail@2`` targeting tenant t2; engine: ``kernel.dense`` demoting
the ladder mid-stream), a mid-stream atomic hot-swap on tenant t0 (plus an
injected ``zoo.swap_abort`` killing t1's swap pre-commit — t1 must keep
serving version 1), and a real mid-stream SIGTERM that triggers the
graceful drain.  The run then asserts the gateway's contract — every
offered request was answered or shed with a typed reason (``unaccounted ==
0``), the quarantined tenant's sheds are typed while healthy tenants keep
serving, and the drained process exits 0 — and exits non-zero on any
violation.  CI runs this as the ``gateway`` job's acceptance drill.

``--chaos`` additionally runs the BROWNOUT OVERLOAD drill: the same
Poisson stream at ~2x the runner's modeled capacity, once with the
brownout controller and once without.  The brownout run must shed
STRICTLY fewer requests than the baseline (degrading quality buys real
capacity), keep p99 under a hard cap, keep every quality tier's agreement
with the exact predictions above a floor, and account for 100% of offered
requests in both runs.  Two more sites are drilled alongside:
``anytime.margin_corrupt`` (a tampered margin table must be REJECTED at
artifact load, never served) and ``gateway.brownout_stuck`` (a wedged
step-down path must be recovered by the controller's low-pressure
watchdog).

Rows carry ``us_per_call`` (= p99 latency, the gated scalar) plus explicit
``p99_ms`` / ``req_per_s`` fields; scripts/check_bench.py gates the lead
row on BOTH (p99 regression or throughput collapse >2x fails).
"""

from __future__ import annotations

import argparse
import asyncio
import itertools
import json
import os
import platform
import signal
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
from repro.runtime import faults
from repro.runtime.gateway import (BrownoutConfig, BrownoutController,
                                   Gateway)
from repro.runtime.zoo import ArtifactZoo

TENANTS = ("t0", "t1", "t2")
BUCKET = 64


def _build_compiled(arch: str = "tm-tiny"):
    config = TM_CONFIGS[arch]
    X, y = make_boolean_classification(
        512, config.n_features, config.n_classes, seed=0)
    state = tm.init(config, jax.random.PRNGKey(0))
    state = train.fit(config, state, jnp.asarray(X), jnp.asarray(y),
                      epochs=1, batch_size=64, rng=jax.random.PRNGKey(1))
    return config, compiler.compile_tm(config, state.ta_state)


def _build_runner(compiled, bucket: int, W: int, warm: bool = True):
    """Zoo-wrapped gateway runner over a dense-kernel -> oracle ladder.

    The dense engine runs the Pallas kernel (compiled on a TPU, in
    interpret mode elsewhere) so the ``kernel.dense`` chaos fault
    exercises the REAL demotion path; the oracle level keeps every bucket
    answerable after the demotion.
    """
    ladder = ops.EngineLadder([
        ("dense", lambda: jax.jit(lambda xw: compiler.run_compiled(
            compiled, xw, engine="dense").argmax(-1))),
        ("oracle", lambda: jax.jit(lambda xw: compiler.run_compiled(
            compiled, xw, engine="oracle").argmax(-1))),
    ])
    counter = itertools.count()

    def run_rows(rows):
        i = next(counter)
        padded = np.zeros((bucket, W), np.uint32)
        padded[:len(rows)] = rows
        out = ladder.run(lambda: jnp.asarray(padded), bucket=i)
        return np.asarray(out)[:len(rows)]

    if warm:
        # warm probe: both ladder levels pay their jit trace BEFORE the
        # load stream so the measured latencies are serving, not
        # compilation.  The chaos drill must NOT pre-trace: the
        # kernel.dense fault site runs at trace time, so a warmed dense
        # engine would never see the injected fault.
        ladder.run(lambda: jnp.zeros((bucket, W), jnp.uint32),
                   bucket="warm", count=False)
        ladder._run_at(1, lambda: jnp.zeros((bucket, W), jnp.uint32))
    nbytes = int(compiled.include_words.nbytes + compiled.votes.nbytes)
    zoo = ArtifactZoo(lambda tenant: (tenant, nbytes),
                      max_entries=len(TENANTS) - 1, breaker_threshold=3)
    return zoo.runner(lambda obj, rows: run_rows(rows)), ladder, zoo


def _requests(n: int, config):
    Xr, _ = make_boolean_classification(
        n, config.n_features, config.n_classes, seed=2)
    return np.asarray(packetizer.pack_literals(jnp.asarray(Xr)))


async def _drive(gw: Gateway, offer_all, *,
                 sigterm_after: float | None = None):
    """Run ``offer_all(futs)`` to completion (or SIGTERM), then drain.

    Returns (responses, final_health, sigterm_seen).  The offer coroutine
    may keep offering after the drain starts — those offers shed
    ``shutting_down`` and the FINAL health (taken after it finishes) still
    accounts for them.
    """
    loop = asyncio.get_running_loop()
    stop = asyncio.Event()
    try:
        loop.add_signal_handler(signal.SIGTERM, stop.set)
    except (NotImplementedError, RuntimeError):
        pass
    if sigterm_after is not None:
        threading.Timer(sigterm_after,
                        lambda: os.kill(os.getpid(), signal.SIGTERM)).start()
    futs: list = []
    done = asyncio.ensure_future(offer_all(futs))
    sig = asyncio.ensure_future(stop.wait())
    await asyncio.wait({done, sig}, return_when=asyncio.FIRST_COMPLETED)
    await gw.drain()
    sig.cancel()
    await done
    responses = await asyncio.gather(*futs)
    return responses, gw.health(), stop.is_set()


async def _open_loop(gw, xp, rate: float, n: int, deadline: float | None,
                     futs: list) -> None:
    """Poisson arrivals at ``rate`` req/s, round-robin over TENANTS."""
    rng = np.random.default_rng(0)
    gaps = rng.exponential(1.0 / rate, size=n)
    t_next = time.perf_counter()
    for j in range(n):
        t_next += gaps[j]
        delay = t_next - time.perf_counter()
        if delay > 0:
            await asyncio.sleep(delay)
        futs.append(gw.offer(TENANTS[j % len(TENANTS)], xp[j % len(xp)],
                             deadline=deadline))


async def _closed_loop(gw, xp, n_clients: int, per_client: int,
                       futs: list) -> None:
    async def client(c):
        for k in range(per_client):
            j = (c * per_client + k) % len(xp)
            fut = gw.offer(TENANTS[(c + k) % len(TENANTS)], xp[j])
            futs.append(fut)
            await fut
    await asyncio.gather(*(client(c) for c in range(n_clients)))


def _row(name: str, health: dict, wall: float) -> dict:
    p99 = health["latency_ms"]["p99"] or 0.0
    answered = health["answered"]
    shed_frac = health["shed_total"] / max(health["offered"], 1)
    return dict(
        name=name,
        us_per_call=p99 * 1e3,
        p99_ms=p99,
        req_per_s=answered / wall if wall > 0 else 0.0,
        derived=(f"p50_ms={health['latency_ms']['p50'] or 0.0:.3f};"
                 f"offered={health['offered']};answered={answered};"
                 f"shed_frac={shed_frac:.4f};buckets={health['buckets']}"),
    )


def run(rate: float = 1500.0, n: int = 1200, clients: int = 32,
        per_client: int = 25) -> list:
    config, compiled = _build_compiled()
    xp = _requests(512, config)
    rows = []

    runner, _, _ = _build_runner(compiled, BUCKET, xp.shape[1])

    async def open_run():
        gw = await Gateway(runner, bucket=BUCKET, max_wait=0.005).start()
        t0 = time.perf_counter()
        res, h, _ = await _drive(
            gw, lambda futs: _open_loop(gw, xp, rate, n, None, futs))
        return res, h, time.perf_counter() - t0

    _, h, wall = asyncio.run(open_run())
    assert h["unaccounted"] == 0, h
    rows.append(_row(f"serve_openloop_poisson_r{int(rate)}_t{len(TENANTS)}"
                     f"_b{BUCKET}", h, wall))

    runner, _, _ = _build_runner(compiled, BUCKET, xp.shape[1])

    async def closed_run():
        gw = await Gateway(runner, bucket=BUCKET, max_wait=0.005).start()
        t0 = time.perf_counter()
        res, h, _ = await _drive(
            gw, lambda futs: _closed_loop(gw, xp, clients, per_client, futs))
        return res, h, time.perf_counter() - t0

    _, h, wall = asyncio.run(closed_run())
    assert h["unaccounted"] == 0, h
    rows.append(_row(f"serve_closedloop_c{clients}_t{len(TENANTS)}"
                     f"_b{BUCKET}", h, wall))
    return rows


def chaos(rate: float = 1500.0, n: int = 1200) -> int:
    """Poisson run with one injected fault per class + mid-stream SIGTERM.

    Also drills the hot-swap path mid-stream: tenant t0 gets a REAL
    ``zoo.swap`` while its requests keep flowing (in-flight buckets finish
    on the old version, later ones on the new — zero drops either way),
    and tenant t1 gets a swap that the injected ``zoo.swap_abort`` site
    kills before its commit point (t1 must keep serving version 1).

    Returns 0 when every gateway invariant holds, 1 otherwise.
    """
    from repro.runtime.zoo import SwapAborted

    config, compiled = _build_compiled()
    xp = _requests(512, config)
    runner, ladder, zoo = _build_runner(compiled, BUCKET, xp.shape[1],
                                        warm=False)
    nbytes = int(compiled.include_words.nbytes + compiled.votes.nbytes)
    # prime t0 so the mid-stream swap bumps a LIVE entry (1 -> 2) instead
    # of cold-installing version 1
    with zoo.lease("t0"):
        pass
    swap_log: dict = {}

    def midstream_swaps():
        try:
            swap_log["t0"] = zoo.swap("t0", ("t0-v2", nbytes), nbytes)
        except Exception as e:           # pragma: no cover - drill fails
            swap_log["t0_error"] = repr(e)
        try:
            zoo.swap("t1", ("t1-v2", nbytes), nbytes)
            swap_log["t1_error"] = "swap committed despite zoo.swap_abort"
        except SwapAborted:
            swap_log["t1_aborted"] = True

    async def go():
        # hot-swaps land ~20% through the arrivals, well before SIGTERM
        threading.Timer(0.2 * n / rate, midstream_swaps).start()
        gw = await Gateway(runner, bucket=BUCKET, max_queue=512,
                           max_wait=0.005, drain_timeout=10.0).start()
        # SIGTERM lands mid-stream (~40% through the planned arrivals)
        return await _drive(
            gw, lambda futs: _open_loop(gw, xp, rate, n, 5.0, futs),
            sigterm_after=0.4 * n / rate)

    with faults.injected("gateway.queue_overflow*5, zoo.load_fail@2*3, "
                         "kernel.dense*1, zoo.swap_abort@1*1"):
        responses, h, sigtermed = asyncio.run(go())

    failures = []
    if h["unaccounted"] != 0:
        failures.append(f"unaccounted != 0: {h['unaccounted']}")
    untyped = [r for r in responses if not r.ok and not r.reason]
    if untyped:
        failures.append(f"{len(untyped)} sheds carry no typed reason")
    if len(responses) != h["offered"]:
        failures.append(f"{h['offered']} offered but only "
                        f"{len(responses)} responses resolved")
    if h["shed"].get("queue_full", 0) < 1:
        failures.append("queue_overflow drill produced no queue_full shed")
    t2 = h["tenants"].get("t2", {}).get("shed", {})
    if t2.get("load_failed", 0) + t2.get("tenant_quarantined", 0) < 1:
        failures.append("zoo.load_fail@2 produced no typed shed on t2")
    healthy = [t for t in ("t0", "t1") if
               h["tenants"].get(t, {}).get("answered", 0) > 0]
    if len(healthy) < 2:
        failures.append(f"healthy tenants stopped serving: {healthy}")
    if not ladder.demotions:
        failures.append("kernel.dense drill produced no ladder demotion")
    if not sigtermed:
        failures.append("SIGTERM was never delivered")
    if not h["draining"]:
        failures.append("SIGTERM did not put the gateway in drain")
    zh = zoo.health()
    if swap_log.get("t0") != 2:
        failures.append(f"mid-stream hot-swap did not commit t0 at "
                        f"version 2: {swap_log}")
    if zoo.version("t0") != 2:
        failures.append(f"t0 serves version {zoo.version('t0')}, not the "
                        "swapped version 2")
    if not swap_log.get("t1_aborted"):
        failures.append(f"zoo.swap_abort@1 did not abort t1's swap: "
                        f"{swap_log}")
    if zh["swap_aborts"] != 1:
        failures.append(f"expected exactly 1 swap abort, saw "
                        f"{zh['swap_aborts']}")
    if zoo.version("t1") not in (1, None):
        failures.append(f"aborted swap left t1 half-promoted at version "
                        f"{zoo.version('t1')}")
    if h["tenants"].get("t0", {}).get("answered", 0) < 1:
        failures.append("t0 stopped serving across its hot-swap")

    h["zoo"] = zoo.health()
    h["ladder"] = dict(final_engine=ladder.engine,
                       demotions=ladder.demotions)
    print("GATEWAY_HEALTH " + json.dumps(h))

    # brownout drills: overload (2x capacity, brownout vs baseline),
    # tampered margin metadata, wedged step-down recovery
    failures += overload_drill(config, compiled)
    failures += margin_corrupt_drill(compiled)
    failures += brownout_stuck_drill()

    if failures:
        for f in failures:
            print("CHAOS_FAIL " + f)
        return 1
    print(f"CHAOS_OK offered={h['offered']} answered={h['answered']} "
          f"shed={h['shed']} (all typed, zero silent drops; brownout "
          "overload/margin-corrupt/stuck drills passed)")
    return 0


# -- brownout overload drill -------------------------------------------------

# tm-tiny at this tiling has ~80 schedule tiles, so the quality prefixes
# actually truncate (the serving default of one giant tile would make
# every tier identical to exact)
_OVERLOAD_BLOCKS = dict(block_c=4, block_j=1)
_P99_CAP_MS = 2000.0      # brownout p99 hard cap under 2x overload
_AGREE_FLOOR = 0.9        # per-tier agreement with exact predictions


def _build_anytime_runner(compiled, xp, base_service: float):
    """Quality-aware gateway runner with a MODELED service time.

    Per-tier predictions are precomputed on the canned request set with
    the REAL budgeted kernels (the gateway serves genuine prefix answers
    and their bounds); the worker then sleeps the modeled per-bucket
    service time scaled by the tier's tile-prefix fraction — degrading
    quality buys capacity exactly the way the tile walk does, and the
    drill's capacity math stays deterministic on a noisy CI container.
    """
    levels = compiled.quality_levels(engine="sparse", **_OVERLOAD_BLOCKS)
    n_full = levels[0]["n_tiles"]
    lit = jnp.asarray(xp)
    preds, frac, bound = {}, {}, {}
    for q in levels:
        lvl = q["level"]
        sums = compiler.run_compiled(compiled, lit, engine="sparse",
                                     quality=lvl,
                                     **_OVERLOAD_BLOCKS)
        preds[lvl] = np.asarray(sums.argmax(-1))
        frac[lvl] = q["n_tiles"] / n_full
        bound[lvl] = q["bound"]
    idx = {xp[i].tobytes(): i for i in range(len(xp))}

    def runner(tenant, rows, quality=0):
        lvl = min(int(quality), max(preds))
        out = np.array([preds[lvl][idx[np.asarray(r).tobytes()]]
                        for r in rows])
        time.sleep(base_service * frac[lvl])
        return out, dict(quality=lvl,
                         err_bound=bound[lvl] if lvl else None)

    return runner, preds[0]


def _run_overload(runner, xp, *, brownout: bool, rate: float, n: int,
                  bucket: int):
    async def go():
        gw = await Gateway(
            runner, bucket=bucket, max_queue=4 * bucket, max_wait=0.005,
            drain_timeout=10.0,
            brownout=BrownoutController() if brownout else None).start()
        return await _drive(
            gw, lambda futs: _open_loop(gw, xp, rate, n, 1.0, futs))

    res, h, _ = asyncio.run(go())
    return res, h


def overload_drill(config, compiled, n: int = 1200, bucket: int = 16,
                   base_service: float = 0.02) -> list:
    """2x-capacity Poisson overload, brownout vs no-brownout baseline.

    Returns the list of contract violations (empty = drill passed).
    """
    failures = []
    xp = _requests(512, config)
    runner, exact = _build_anytime_runner(compiled, xp, base_service)
    rate = 2.0 * bucket / base_service      # 2x the exact-tier capacity
    res_b, h_b = _run_overload(runner, xp, brownout=True, rate=rate,
                               n=n, bucket=bucket)
    res_0, h_0 = _run_overload(runner, xp, brownout=False, rate=rate,
                               n=n, bucket=bucket)

    for tag, res, h in (("brownout", res_b, h_b), ("baseline", res_0, h_0)):
        if h["unaccounted"] != 0:
            failures.append(f"{tag}: unaccounted != 0: {h['unaccounted']}")
        if len(res) != h["offered"]:
            failures.append(f"{tag}: {h['offered']} offered but "
                            f"{len(res)} responses resolved")
        untyped = [r for r in res if not r.ok and not r.reason]
        if untyped:
            failures.append(f"{tag}: {len(untyped)} sheds with no reason")

    if h_b["shed_total"] >= h_0["shed_total"]:
        failures.append(
            f"brownout shed {h_b['shed_total']} >= baseline "
            f"{h_0['shed_total']} — degrading bought no capacity")
    p99 = h_b["latency_ms"]["p99"] or 0.0
    if p99 > _P99_CAP_MS:
        failures.append(f"brownout p99 {p99:.0f}ms over the "
                        f"{_P99_CAP_MS:.0f}ms cap")
    if h_b["answered_degraded"] < 1:
        failures.append("brownout never served a degraded answer under "
                        "2x overload")
    if (h_b.get("brownout") or {}).get("escalations", 0) < 1:
        failures.append("brownout controller never escalated")
    for tier in sorted({r.quality for r in res_b if r.ok}):
        hits = [int(r.pred == exact[j % len(xp)])
                for j, r in enumerate(res_b) if r.ok and r.quality == tier]
        agree = float(np.mean(hits))
        if agree < _AGREE_FLOOR:
            failures.append(f"tier {tier} agreement with exact "
                            f"{agree:.3f} < {_AGREE_FLOOR} floor "
                            f"({len(hits)} answers)")
    bad = [r for r in res_b if r.ok and r.quality > 0 and r.err_bound is None]
    if bad:
        failures.append(f"{len(bad)} degraded answers carry no err_bound")

    print("BROWNOUT_HEALTH " + json.dumps(dict(
        offered_rate=rate, brownout=h_b,
        baseline=dict(shed_total=h_0["shed_total"],
                      answered=h_0["answered"],
                      p99_ms=h_0["latency_ms"]["p99"]))))
    return failures


def margin_corrupt_drill(compiled) -> list:
    """anytime.margin_corrupt: tampered margin metadata must be REJECTED
    at load (validate_artifact's vote-table consistency check), and the
    clean artifact must still load once the site disarms."""
    import tempfile

    failures = []
    with tempfile.TemporaryDirectory(prefix="anytime_art_") as d:
        path = compiled.save(os.path.join(d, "art.npz"))
        with faults.injected("anytime.margin_corrupt"):
            try:
                compiler.CompiledTM.load(path)
                failures.append("anytime.margin_corrupt: tampered margins "
                                "were accepted at load")
            except compiler.ArtifactError as e:
                if "margin" not in str(e).lower():
                    failures.append(
                        f"margin tamper rejected with wrong error: {e}")
        try:
            compiler.CompiledTM.load(path)
        except compiler.ArtifactError as e:
            failures.append(f"clean artifact rejected after drill: {e}")
    return failures


def brownout_stuck_drill() -> list:
    """gateway.brownout_stuck: with the primary step-down path wedged,
    the low-pressure watchdog must still recover exact serving."""
    failures = []
    c = BrownoutController(BrownoutConfig(watchdog_evals=4))
    with faults.injected("gateway.brownout_stuck*8"):
        c.update(0.9)                  # escalate straight to level 3
        for _ in range(4):
            c.update(0.05)             # calm, but step-down is wedged
    if c.level != 0 or c.watchdog_resets != 1:
        failures.append(
            f"brownout_stuck: watchdog did not recover (level={c.level}, "
            f"watchdog_resets={c.watchdog_resets})")
    return failures


def write_report(rows: list, path: str = "BENCH_serve.json") -> None:
    report = dict(
        benchmark="serve_gateway",
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
    ap.add_argument("--out", default="BENCH_serve.json")
    ap.add_argument("--rate", type=float, default=1500.0,
                    help="open-loop Poisson offered rate (req/s)")
    ap.add_argument("--requests", type=int, default=1200)
    ap.add_argument("--chaos", action="store_true",
                    help="fault-injection drill instead of the benchmark: "
                         "one fault per class + mid-stream SIGTERM, exits "
                         "non-zero on any gateway-contract violation")
    args = ap.parse_args()
    if args.chaos:
        return chaos(rate=args.rate, n=args.requests)
    rows = run(rate=args.rate, n=args.requests)
    write_report(rows, args.out)
    for r in rows:
        print(f"{r['name']},{r['us_per_call']:.3f},req_per_s="
              f"{r['req_per_s']:.0f};{r['derived']}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
