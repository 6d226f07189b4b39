"""Fast fused-kernel microbenchmarks -> BENCH_fused_infer.json +
BENCH_fused_train.json + BENCH_sparse_infer.json + BENCH_term_infer.json.

    PYTHONPATH=src python scripts/bench_smoke.py [--full] [--reps N]
        [--no-autotune] [--only {infer,train,sparse,term}]

A CI-sized smoke of the fused single-pass TM kernels against the jnp
oracles on identical shapes:

  * inference (src/repro/kernels/fused_infer.py) vs the oracle ->
    ``BENCH_fused_infer.json``
  * training (src/repro/kernels/fused_train.py: clause fire -> feedback ->
    TA delta in one pallas_call) vs the oracle ->
    ``BENCH_fused_train.json``
  * block-sparse compiled-schedule inference on a TRAINED artifact
    (src/repro/kernels/sparse_infer.py) vs the dense fused kernel vs the
    uncompiled bank -> ``BENCH_sparse_infer.json``
  * shared-term FACTORIZED inference on a trained thermometer artifact
    (src/repro/kernels/term_infer.py: unique AND terms evaluated once)
    vs the flat sparse schedule vs the dense kernel, plus a synthetic
    sharing sweep -> ``BENCH_term_infer.json``

Appends nothing: each run rewrites the report files with fresh numbers +
backend metadata, so the perf trajectory of the fused kernels is a per-PR
diffable artifact.

The fused rows run at the block tilings chosen by the autotuner's cached
sweeps (kernels/autotune.py); ``--no-autotune`` pins the kernel defaults.
"""

from __future__ import annotations

import argparse
import os
import sys

# runnable as `python scripts/bench_smoke.py` — put the repo root (the
# `benchmarks` package) on the path alongside PYTHONPATH=src
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--full", action="store_true",
                    help="run every benchmark shape, not just the smoke one")
    ap.add_argument("--reps", type=int, default=8)
    ap.add_argument("--train-reps", type=int, default=3,
                    help="rounds for the (heavier) training benchmark")
    ap.add_argument("--out", default="BENCH_fused_infer.json")
    ap.add_argument("--out-train", default="BENCH_fused_train.json")
    ap.add_argument("--out-sparse", default="BENCH_sparse_infer.json")
    ap.add_argument("--out-term", default="BENCH_term_infer.json")
    ap.add_argument("--no-autotune", action="store_true",
                    help="use default fused block sizes instead of the "
                         "cached autotuner sweep")
    ap.add_argument("--only", choices=("infer", "train", "sparse", "term"),
                    default=None,
                    help="run just one of the four benchmarks")
    args = ap.parse_args()

    from benchmarks import fused_infer, fused_train, sparse_infer, term_infer

    rows = []
    if args.only in (None, "infer"):
        infer_rows = fused_infer.run(fast=not args.full, reps=args.reps,
                                     autotune=not args.no_autotune)
        fused_infer.write_report(infer_rows, args.out)
        rows += infer_rows
    if args.only in (None, "train"):
        train_rows = fused_train.run(fast=not args.full, reps=args.train_reps,
                                     autotune=not args.no_autotune)
        fused_train.write_report(train_rows, args.out_train)
        rows += train_rows
    if args.only in (None, "sparse"):
        sparse_rows = sparse_infer.run(fast=not args.full, reps=args.reps,
                                       autotune=not args.no_autotune)
        sparse_infer.write_report(sparse_rows, args.out_sparse)
        rows += sparse_rows
    if args.only in (None, "term"):
        term_rows = term_infer.run(fast=not args.full, reps=args.reps,
                                   autotune=not args.no_autotune)
        term_infer.write_report(term_rows, args.out_term)
        rows += term_rows

    print("name,us_per_call,derived")
    for name, us, derived in rows:
        print(f"{name},{us:.3f},{derived}")
    if args.only in (None, "infer"):
        print(f"wrote {args.out}")
    if args.only in (None, "train"):
        print(f"wrote {args.out_train}")
    if args.only in (None, "sparse"):
        print(f"wrote {args.out_sparse}")
    if args.only in (None, "term"):
        print(f"wrote {args.out_term}")


if __name__ == "__main__":
    main()
