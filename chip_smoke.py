"""Chip smoke test: the TM main path once on a TPU, checked against oracles.

    python chip_smoke.py            # one chip: train, serve, every engine
    python chip_smoke.py --chips 4  # four chips: only the model=4 mesh paths

One process, one JAX import, no child process.  It exits non-zero, printing
no result, unless JAX's first device is a TPU and the script sits in a
checkout of the repo.  At the paper's tm-mnist width (784 features, 10
classes, 200 clauses per class):

  one chip
    train    5 fused-kernel training steps through ``launch/train.py:
             train_tm``; one step's (new_ta, delta) equal, bit for bit,
             the same step on the XLA oracle path.
    serve    2048 synthetic requests in buckets of 512 through
             ``launch/serve.py:serve_tm`` on the default ladder
             (``ops.engine_levels`` of the artifact): no demotion, no
             failed probe, no shed, the ladder's head serves every bucket,
             and every prediction equals the oracle engine's.
    engines  each named kernel engine (factorized, sparse, dense) through
             ``compiler.run_compiled`` on the same requests: class sums
             equal the oracle's, and with early exit the predictions do.
  four chips (--chips 4)
    mesh     each clause-sharded forward builder of ``core/sharding``
             (factorized, sparse and dense schedules over model=4) equals
             the one-device oracle's class sums; one ``--mesh model=4``
             serve run on the default ladder passes the serve checks.
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


def requests():
    """serve_tm's request stream, packed, as (N_REQUESTS, W) uint32."""
    import jax.numpy as jnp
    import numpy as np

    from repro.configs.matador_tm import TM_CONFIGS
    from repro.core import packetizer
    from repro.data import make_boolean_classification

    config = TM_CONFIGS[ARCH]
    Xr, _ = make_boolean_classification(
        N_REQUESTS, config.n_features, config.n_classes, seed=2)
    return np.asarray(packetizer.pack_literals(jnp.asarray(Xr)))


def class_sums(fn, xp):
    """``fn`` over the request stream, bucket by bucket."""
    import jax.numpy as jnp
    import numpy as np

    return np.concatenate([np.asarray(fn(jnp.asarray(xp[i:i + BUCKET])))
                           for i in range(0, N_REQUESTS, BUCKET)])


def oracle_sums(art, xp):
    """The XLA oracle engine's class sums on the request stream."""
    import jax

    from repro.core import compiler

    return class_sums(jax.jit(lambda xw: compiler.run_compiled(
        art, xw, engine="oracle")), xp)


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


def load_artifact(workdir: Path):
    from repro.core import compiler

    return compiler.CompiledTM.load(str(workdir / f"{ARCH}.npz"))


def phase_serve(workdir: Path):
    from repro.kernels import ops
    from repro.launch import serve

    res = timed("serve, default ladder",
                lambda: serve.serve_tm(serve_args(workdir)))
    art = load_artifact(workdir)   # the serve run trained and saved it
    levels = ops.engine_levels(art)
    check(res["serve"]["ladder"] == levels,
          f"serve ladder {res['serve']['ladder']} != {levels}")
    want = timed("oracle class sums",
                 lambda: oracle_sums(art, requests())).argmax(-1)
    check_serve(res, levels[0], want)


def phase_engines(workdir: Path):
    import jax
    import numpy as np

    from repro.core import compiler

    art = load_artifact(workdir)
    xp = requests()
    want = oracle_sums(art, xp)
    for engine in ("factorized", "sparse", "dense"):
        for early_exit in (False, True):
            name = f"{engine}{' early-exit' if early_exit else ''}"
            fn = jax.jit(lambda xw, e=engine, x=early_exit:
                         compiler.run_compiled(art, xw, engine=e,
                                               early_exit=x))
            got = timed(f"run_compiled {name}", lambda: class_sums(fn, xp))
            if early_exit:   # argmax-identical, not sum-identical
                same = np.array_equal(got.argmax(-1), want.argmax(-1))
            else:
                same = np.array_equal(got, want)
            check(same, f"{name}: differs from the oracle engine")
            print(f"  {name} == oracle on all {N_REQUESTS} requests")


def phase_mesh(workdir: Path):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core import sharding
    from repro.kernels import sparse_infer, term_infer
    from repro.launch import serve, train
    from repro.launch.mesh import parse_mesh_spec

    res = timed("serve, --mesh model=4", lambda: serve.serve_tm(
        serve_args(workdir, "--mesh", "model=4")))
    art = load_artifact(workdir)
    xp = requests()
    want = timed("oracle class sums (one device)",
                 lambda: oracle_sums(art, xp))
    check_serve(res, res["serve"]["ladder"][0], want.argmax(-1))

    mesh = parse_mesh_spec("model=4")

    def place(stack):
        return jax.device_put(stack, NamedSharding(
            mesh, P("model", *[None] * (np.ndim(stack) - 1))))

    word_ids = jnp.asarray(art.word_ids)
    fs, terms, chains, votes, tiles, _ = term_infer.stack_shard_factorized(
        art.include_words, art.votes, 4)
    fwd = sharding.sharded_factorized_forward_fn(
        mesh, block_t=fs[0].block_t, block_c=fs[0].block_c,
        block_j=fs[0].block_j)
    stacks = tuple(map(place, (terms, chains, votes, tiles)))
    variants = {"factorized": jax.jit(
        lambda xw: fwd(*stacks, xw[:, word_ids]))}
    ss, schains, svotes, stiles, _ = sparse_infer.stack_shard_schedules(
        art.include_words, art.votes, 4)
    sfwd = sharding.sharded_schedule_forward_fn(
        mesh, block_c=ss[0].block_c, block_j=ss[0].block_j)
    sstacks = tuple(map(place, (schains, svotes, stiles)))
    variants["sparse"] = jax.jit(lambda xw: sfwd(*sstacks, xw[:, word_ids]))
    U = art.n_unique
    Up = -(-U // 4) * 4
    inc = place(np.pad(art.include_words, ((0, Up - U), (0, 0))))
    dv = place(np.pad(art.votes, ((0, Up - U), (0, 0))))
    ne = place(np.ones((Up,), np.uint8))
    dfwd = sharding.sharded_forward_fn(mesh)
    variants["dense"] = jax.jit(lambda xw: dfwd(inc, dv, ne, xw[:, word_ids]))
    for name, fn in variants.items():
        got = timed(f"mesh {name} model=4", lambda: class_sums(fn, xp))
        check(np.array_equal(got, want),
              f"mesh {name}: class sums differ from the one-device oracle")
        print(f"  mesh {name} == oracle on all {N_REQUESTS} requests")

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
            phase_engines(workdir)
    finally:
        shutil.rmtree(workdir, ignore_errors=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()
