"""Clause-sharded fused TM step scaling benchmark -> BENCH_sharded.json.

    PYTHONPATH=src python -m benchmarks.sharded_step [--devices 4] [--reps 3]

Times the clause-sharded ``shard_map`` schedules of PR 3 (fused Pallas
pipeline per ``model`` shard, one int32 class-sum psum) against the
single-device fused step on the same problem, on an EMULATED host-device
mesh (``--xla_force_host_platform_device_count``, set before jax init —
this module must therefore be its own process; ``scripts/bench_smoke.py``
and ``benchmarks/run.py`` keep their single-device view and never import
it).

On CPU the kernels run in Pallas interpret mode, so absolute numbers are
not TPU throughput — the point of the file is the cross-PR trajectory of
(a) the sharded-vs-single overhead factor (collective + shard_map plumbing
cost on a fixed problem) and (b) that the schedule runs at all on every
jax bump.  On a real TPU runner the same flags produce compiled scaling
numbers.

Rows (``name,us_per_call,derived``):
  * shardedtrain_1dev_*   — single-device fused train step
  * shardedtrain_mesh_*   — model=N clause-sharded fused train step
  * shardedinfer_1dev_*   — single-device fused forward
  * shardedinfer_mesh_*   — model=N clause-sharded fused forward
"""

from __future__ import annotations

import argparse
import json
import os
import platform
import sys
import time


def _early_arg(flag: str, default: str) -> str:
    for i, a in enumerate(sys.argv):
        if a == flag and i + 1 < len(sys.argv):
            return sys.argv[i + 1]
        if a.startswith(flag + "="):
            return a.split("=", 1)[1]
    return default


_N_DEVICES = int(_early_arg("--devices", os.environ.get("REPRO_BENCH_DEVICES", "4")))
# MUST precede any jax import: device count locks on first init.
os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "")
    + f" --xla_force_host_platform_device_count={_N_DEVICES}"
).strip()

import jax                      # noqa: E402
import jax.numpy as jnp         # noqa: E402
import numpy as np              # noqa: E402

from repro.core import packetizer, sharding, tm   # noqa: E402
from repro.kernels import ops, ref                # noqa: E402

# (B, n_features, n_classes, clauses_per_class) — sized so the interpret-mode
# CI smoke stays ~a minute; --full adds the BENCH_fused_train lead shape.
SHAPES = [
    (256, 128, 8, 128),     # C = 1024, L = 256
]
FULL_SHAPES = SHAPES + [
    (512, 128, 8, 512),     # C = 4096: the fused-train lead shape
]


def _time(fn, reps: int) -> float:
    fn().block_until_ready()
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        fn().block_until_ready()
        best = min(best, time.perf_counter() - t0)
    return best


def run(n_model: int, reps: int = 3, full: bool = False) -> list:
    interpret = jax.default_backend() != "tpu"
    rng = np.random.default_rng(0)
    rows = []
    mesh = jax.make_mesh((n_model,), ("model",))
    for B, F, K, cpc in (FULL_SHAPES if full else SHAPES):
        cfg = tm.TMConfig(n_features=F, n_classes=K, clauses_per_class=cpc,
                          threshold=40, s=8.0, clause_pad_multiple=n_model)
        C, L = cfg.n_clauses_total, cfg.n_literals
        ta = jnp.asarray(rng.integers(-64, 64, (C, L), dtype=np.int8))
        X = jnp.asarray(rng.integers(0, 2, (B, F), dtype=np.uint8))
        y = jnp.asarray(rng.integers(0, K, B, dtype=np.int32))
        seed = jnp.uint32(3)
        tag = f"b{B}_c{C}_l{L}_m{n_model}"

        one = jax.jit(lambda t, xx, yy, s: ops.tm_train_step_kernel(
            cfg, t, xx, yy, s, use_kernel=True, interpret=interpret)[0])
        step_sh = sharding.sharded_train_step_fn(
            cfg, mesh, engine="kernel", use_kernel=True, interpret=interpret)
        # equality gate: the bench refuses to record numbers for a schedule
        # that drifted off the oracle
        np.testing.assert_array_equal(
            np.asarray(one(ta, X, y, seed)),
            np.asarray(step_sh(ta, X, y, seed)))

        t1 = _time(lambda: one(ta, X, y, seed), reps)
        tm_ = _time(lambda: step_sh(ta, X, y, seed), reps)
        rows.append((f"shardedtrain_1dev_{tag}", t1 * 1e6,
                     f"samples_s={B / t1:,.0f}"))
        rows.append((f"shardedtrain_mesh_{tag}", tm_ * 1e6,
                     f"samples_s={B / tm_:,.0f};vs_1dev={t1 / tm_:.2f}x"))

        iw = packetizer.pack_include_masks(ta)
        votes = tm.vote_matrix(cfg)
        ne = jnp.any(ta >= 0, -1).astype(jnp.uint8)
        lw = packetizer.pack_bits(tm.literals(X))
        one_f = jax.jit(lambda l, i, v, n: ops.tm_forward_packed(
            l, i, v, n, use_kernel=True, interpret=interpret))
        fwd_sh = sharding.sharded_forward_fn(
            mesh, use_kernel=True, interpret=interpret)
        np.testing.assert_array_equal(
            np.asarray(one_f(lw, iw, votes, ne)),
            np.asarray(fwd_sh(iw, votes, ne, lw)))

        t1 = _time(lambda: one_f(lw, iw, votes, ne), reps)
        tm_ = _time(lambda: fwd_sh(iw, votes, ne, lw), reps)
        rows.append((f"shardedinfer_1dev_{tag}", t1 * 1e6,
                     f"inf_s={B / t1:,.0f}"))
        rows.append((f"shardedinfer_mesh_{tag}", tm_ * 1e6,
                     f"inf_s={B / tm_:,.0f};vs_1dev={t1 / tm_:.2f}x"))
    return rows


def write_report(rows: list, n_model: int,
                 path: str = "BENCH_sharded.json") -> None:
    report = dict(
        benchmark="sharded_step",
        backend=jax.default_backend(),
        interpret_mode=jax.default_backend() != "tpu",
        n_devices=jax.device_count(),
        mesh_model=n_model,
        jax_version=jax.__version__,
        platform=platform.platform(),
        rows=[dict(name=n, us_per_call=us, derived=d) for n, us, d in rows],
    )
    with open(path, "w") as f:
        json.dump(report, f, indent=1)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--devices", type=int, default=_N_DEVICES,
                    help="emulated host device count (= model mesh axis)")
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--full", action="store_true",
                    help="also run the BENCH_fused_train lead shape")
    ap.add_argument("--out", default="BENCH_sharded.json")
    args = ap.parse_args()
    n_model = min(args.devices, jax.device_count())
    rows = run(n_model, reps=args.reps, full=args.full)
    write_report(rows, n_model, args.out)
    print("name,us_per_call,derived")
    for name, us, derived in rows:
        print(f"{name},{us:.3f},{derived}")
    print(f"wrote {args.out}")


if __name__ == "__main__":
    main()
