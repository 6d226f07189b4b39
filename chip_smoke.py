"""Chip smoke test: the TM main path once on a TPU, checked against oracles.

    python chip_smoke.py            # one chip: train, then serve on every engine
    python chip_smoke.py --chips 4  # four chips: only the --mesh model=4 paths

One process, one JAX import, no child process.  It exits non-zero, printing
no result, unless JAX's first device is a TPU and the script sits in a
checkout of the repo.  Every phase goes through the launchers' entry points
(``launch/train.py:train_tm``, ``launch/serve.py:serve_tm``) at the paper's
tm-mnist width (784 features, 10 classes, 200 clauses per class):

  one chip
    train   5 fused-kernel training steps; one step's (new_ta, delta) equal,
            bit for bit, the same step on the XLA oracle path.
    serve   2048 synthetic requests in buckets of 512, once per pinned
            engine (factorized, sparse, dense, sparse with --early-exit):
            no demotion, no failed probe, no shed, the pinned engine serves
            every bucket, and every prediction equals the oracle engine's.
  four chips (--chips 4)
    mesh serve  the same checks with --mesh model=4 on each schedule
                engine, against the one-device oracle.
    mesh train  3 --mesh model=4 kernel steps equal 3 unsharded steps.

Per-phase wall time (compilation included), engine and health are printed;
the last line is one JSON object: {"ok": true, "device": {...}}.
"""

from __future__ import annotations

import argparse
import json
import shutil
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
ARCH = "tm-mnist"
N_REQUESTS = 2048
BUCKET = 512


def fail(msg: str):
    raise SystemExit(f"chip_smoke: FAILED: {msg}")


def check(cond, msg: str):
    if not cond:
        fail(msg)


def timed(name: str, fn):
    t0 = time.perf_counter()
    out = fn()
    print(f"[{name}] {time.perf_counter() - t0:.2f} s (compile included)",
          flush=True)
    return out


def serve_args(workdir: Path, *flags: str):
    from repro.launch import serve

    return serve.build_parser().parse_args([
        "--arch", ARCH, "--requests", str(N_REQUESTS),
        "--bucket", str(BUCKET), "--epochs", "1", "--n-train", "2000",
        "--artifact", str(workdir / f"{ARCH}.npz"), *flags])


def oracle_preds(workdir: Path):
    """The XLA oracle engine's argmax on serve_tm's request stream, from
    the artifact the first serve run saved."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs.matador_tm import TM_CONFIGS
    from repro.core import compiler, packetizer
    from repro.data import make_boolean_classification

    config = TM_CONFIGS[ARCH]
    art = compiler.CompiledTM.load(str(workdir / f"{ARCH}.npz"))
    Xr, _ = make_boolean_classification(
        N_REQUESTS, config.n_features, config.n_classes, seed=2)
    xp = np.asarray(packetizer.pack_literals(jnp.asarray(Xr)))
    run = jax.jit(lambda xw: compiler.run_compiled(
        art, xw, engine="oracle").argmax(-1))
    return np.concatenate([np.asarray(run(jnp.asarray(xp[i:i + BUCKET])))
                           for i in range(0, N_REQUESTS, BUCKET)])


def check_serve(res, engine: str, want_preds):
    import numpy as np

    h, g = res["serve"], res["gateway"]
    print(f"  engine={h['final_engine']} buckets={h['engine_buckets']} "
          f"demotions={h['demotions']} probe_failures={h['probe_failures']}")
    print(f"  gateway offered={g['offered']} answered={g['answered']} "
          f"shed={g['shed']} unaccounted={g['unaccounted']}")
    check(h["ladder"][0] == engine and h["final_engine"] == engine,
          f"{engine}: served on {h['final_engine']} (ladder {h['ladder']})")
    check(not h["demotions"], f"{engine}: demoted {h['demotions']}")
    check(not h["probe_failures"],
          f"{engine}: probe failures {h['probe_failures']}")
    check(h["engine_buckets"][engine] == g["buckets"] > 0,
          f"{engine}: served {h['engine_buckets']} of {g['buckets']} buckets")
    check(g["offered"] == g["answered"] == N_REQUESTS
          and g["shed_total"] == 0 and g["unaccounted"] == 0,
          f"{engine}: gateway {g}")
    preds = res["preds"]
    check(preds.shape == want_preds.shape
          and np.array_equal(preds, want_preds),
          f"{engine}: {int((preds != want_preds).sum())} predictions differ "
          "from the oracle engine")
    print(f"  predictions == oracle on all {preds.shape[0]} requests")


def phase_train():
    import jax.numpy as jnp
    import numpy as np

    from repro.configs.matador_tm import TM_CONFIGS
    from repro.data import make_boolean_classification
    from repro.kernels import ops
    from repro.launch import train

    check(ops.kernel_dispatch() == (True, False),
          f"kernel path is not the compiled default: {ops.kernel_dispatch()}")
    config = TM_CONFIGS[ARCH]
    args = train.build_parser().parse_args([
        "--arch", ARCH, "--steps", "5", "--batch-size", "256",
        "--n-train", "2048", "--log-every", "5"])
    res = timed("train 5 steps", lambda: train.train_tm(args))
    ta = np.asarray(res["ta"])
    print(f"  TRAIN_HEALTH {res['health']}")
    check(res["health"]["steps"] == 5, f"train health {res['health']}")
    check(ta.shape == (config.n_clauses_total, config.n_literals)
          and ta.dtype == np.int8, f"automata {ta.shape} {ta.dtype}")

    X, y = make_boolean_classification(
        256, config.n_features, config.n_classes, seed=3)
    x, y = jnp.asarray(X), jnp.asarray(y)

    def step(use_kernel, batch_chunk=None):
        new_ta, delta = ops.tm_train_step_kernel(
            config, res["ta"], x, y, jnp.uint32(7), batch_chunk,
            use_kernel=use_kernel)
        return np.asarray(new_ta), np.asarray(delta)

    k_ta, k_delta = timed("train step, fused kernels", lambda: step(True))
    # the oracle materializes (batch, C, L) hash fields: chunk the batch
    # (bit-identical by construction) to stay inside HBM
    o_ta, o_delta = timed("train step, XLA oracle",
                          lambda: step(False, batch_chunk=32))
    check(np.count_nonzero(o_delta) > 0, "oracle step gave no feedback")
    check(np.array_equal(k_delta, o_delta),
          f"kernel delta differs in {int((k_delta != o_delta).sum())} cells")
    check(np.array_equal(k_ta, o_ta), "kernel new_ta differs from oracle")
    print(f"  kernel step == oracle step bit for bit "
          f"({np.count_nonzero(o_delta)} nonzero delta cells)")


def phase_serve(workdir: Path):
    from repro.launch import serve

    runs = [("factorized", ("--factorize",)),
            ("sparse", ("--no-factorize",)),
            ("dense", ("--no-sparse",)),
            ("sparse", ("--no-factorize", "--early-exit"))]
    want = None
    for engine, flags in runs:
        name = f"serve {engine} {' '.join(flags)}"
        res = timed(name, lambda: serve.serve_tm(serve_args(workdir, *flags)))
        if want is None:   # the first run trained and saved the artifact
            want = timed("oracle predictions", lambda: oracle_preds(workdir))
        check_serve(res, engine, want)


def phase_mesh(workdir: Path):
    import numpy as np

    from repro.launch import serve, train

    want = None
    for engine, flags in [("factorized", ("--factorize",)),
                          ("sparse", ("--no-factorize",)),
                          ("dense", ("--no-sparse",))]:
        res = timed(f"serve mesh-{engine} model=4", lambda: serve.serve_tm(
            serve_args(workdir, "--mesh", "model=4", *flags)))
        if want is None:
            want = timed("oracle predictions (one device)",
                         lambda: oracle_preds(workdir))
        check_serve(res, f"mesh-{engine}", want)

    base = ["--arch", ARCH, "--steps", "3", "--batch-size", "256",
            "--n-train", "2048", "--log-every", "1000"]
    p = train.build_parser()
    sharded = timed("train 3 steps, --mesh model=4", lambda: train.train_tm(
        p.parse_args(base + ["--mesh", "model=4"])))
    single = timed("train 3 steps, one device",
                   lambda: train.train_tm(p.parse_args(base)))
    a, b = np.asarray(sharded["ta"]), np.asarray(single["ta"])
    check(np.array_equal(a, b),
          f"sharded automata differ in {int((a != b).sum())} cells")
    print("  --mesh model=4 training == one-device training bit for bit")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the --mesh model=4 serve and train "
                         "paths on a four-chip host")
    args = ap.parse_args()
    if not (ROOT / "src" / "repro").is_dir():
        raise SystemExit("chip_smoke: run it from a checkout of the repo "
                         f"(no src/repro next to {Path(__file__).name})")
    sys.path.insert(0, str(ROOT / "src"))

    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU; JAX found {dev.platform}")
    if len(devices) < args.chips:
        raise SystemExit(f"chip_smoke: --chips {args.chips} needs "
                         f"{args.chips} devices; JAX found {len(devices)}")

    from repro.launch.compile_cache import enable_compile_cache

    print(f"device {dev.device_kind} x{len(devices)}; compile cache "
          f"{enable_compile_cache()}", flush=True)
    workdir = ROOT / ".smoke"
    shutil.rmtree(workdir, ignore_errors=True)
    workdir.mkdir()
    try:
        if args.chips == 4:
            phase_mesh(workdir)
        else:
            phase_train()
            phase_serve(workdir)
    finally:
        shutil.rmtree(workdir, ignore_errors=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()
