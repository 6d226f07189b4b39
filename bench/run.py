"""Run one benchmark cell once, on the chip this process finds.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell comes from ``BENCHMARK.json``; its configuration, traffic mix and
metrics from files under ``bench/`` found by name (``bench/core.py``).
Set-up makes the inputs and the bank from ``--seed``, compiles and warms
every shape the window uses; then the window runs for ``--seconds``.
Once it has closed and the program's state is freed, the answers are
compared with the plain reference (``bench/reference``).

Standard output ends with one JSON line: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics, or with
``--trace 1`` its per-layer ones), ``device``, with ``--trace 1``
``breakdown``, and last ``checks``: each number compared, with its limit.
The same numbers are the last lines of standard error.  A run that finds
no TPU, or fewer chips than the cell asks for, exits non-zero and prints
no result.  JAX's compile cache lives in ``.jax_cache`` in the checkout.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def setup_jax():
    """Compile cache at a fixed path inside the checkout, whatever the
    environment says (the path is part of every entry's key)."""
    import jax

    cache = ROOT / ".jax_cache"
    cache.mkdir(exist_ok=True)     # JAX writes entries but makes no dir
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(cache)
    jax.config.update("jax_compilation_cache_dir", str(cache))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return jax


def run(args, *, require_chip: bool = True, patch=None, cfg=None,
        peaks_kind: str | None = None, log=None) -> dict:
    """One run; returns the result object.  The test hooks (``patch``,
    ``cfg``, ``peaks_kind``, ``require_chip=False``) let the checks in
    ``bench/checks`` drive a run on the CPU with the timed path broken."""
    from bench import core, trace

    spec = core.benchmark()
    cell = core.workload(spec, args.workload)
    jax = setup_jax()
    devices = jax.devices()
    dev = devices[0]
    if require_chip and (dev.platform != "tpu" or len(devices) < cell["chips"]):
        raise SystemExit(
            f"bench: cell {cell['name']} needs {cell['chips']} TPU chip(s); "
            f"JAX found {len(devices)} {dev.platform} device(s)")
    peak_table = core.load_json(core.BENCH / "peaks.json")["devices"]
    kind = peaks_kind or dev.device_kind
    if kind not in peak_table:
        raise SystemExit(f"bench: device kind {kind!r} is not in "
                         "bench/peaks.json")
    ctx = core.Context(
        cell=cell["name"], seed=args.seed, seconds=args.seconds,
        trace=bool(args.trace), cfg=cfg or core.config(cell["config"]),
        mix=core.traffic(cell["traffic"]), t_start=T_START,
        peaks=peak_table[kind], patch=patch,
        log=log or (lambda s: print(s, flush=True)))
    gen = core.traffic_kind(ctx.mix["kind"])
    tracer = trace.Tracer(ctx.trace, core.TRACE_DIR / cell["name"])
    rec = gen.run(ctx, tracer)
    rec.update(cfg=ctx.cfg, peaks=ctx.peaks, mix=ctx.mix)

    stats = dev.memory_stats() or {}
    peak_mem = stats.get("peak_bytes_in_use")
    metrics = {}
    for m in core.metrics_of(spec, cell["name"], per_layer=ctx.trace):
        v = core.reducer(m["name"]).value(rec)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    checks = rec["checks"]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": peak_mem}
    out = {"correct": all(v <= lim for v, lim in checks.values()),
           "attempted": rec["attempted"], "failed": rec["failed"],
           "metrics": metrics, "device": device}
    if ctx.trace:
        tr = rec["trace"]
        device.update(busy_s=tr["busy_s"], window_s=tr["window_s"])
        out["breakdown"] = trace.breakdown(tr)
        ctx.info["trace"] = {k: tr[k] for k in ("op_n", "span_n", "gap_n")}
    out["checks"] = {k: {"value": v, "limit": lim}
                     for k, (v, lim) in checks.items()}
    out["info"] = ctx.info
    return out


def main(argv=None) -> int:
    args = parse(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    out = run(args)
    info = out.pop("info")
    print("BENCH_INFO " + json.dumps(info, default=str), flush=True)
    for k, c in out["checks"].items():
        print(f"check {k} = {c['value']} (limit {c['limit']})",
              file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
