"""Benchmark harness — one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--fast]

Prints ``name,us_per_call,derived`` CSV (one row per measurement):
  * table1_*   — paper Table I analog (TM vs FINN-style BNN)
  * fig8_*     — paper Fig. 8 analog (logic-sharing resource savings)
  * fig7_*     — paper Fig. 7 analog (HCB chain schedule sweep)
  * tmcore_*   — TM datapath micro-benchmarks (train/infer steps)
  * fusedinfer_* — fused single-pass inference kernel vs the jnp oracle
    (also written, with metadata, to BENCH_fused_infer.json — the perf
    trajectory file)
  * fusedtrain_* — fused single-pass TRAINING kernel (clause fire ->
    feedback -> TA delta in one pallas_call) vs the jnp oracle
    (-> BENCH_fused_train.json)
  * sparseinfer_* — block-sparse compiled-schedule inference on a trained
    artifact vs the dense fused kernel vs the uncompiled bank
    (-> BENCH_sparse_infer.json; speedup scales with model sparsity)
  * terminfer_* — shared-term FACTORIZED inference (unique AND terms
    evaluated once per sample slab) vs the flat sparse schedule vs the
    dense kernel, + a synthetic sharing sweep (-> BENCH_term_infer.json;
    speedup scales with the artifact's term-sharing fraction)
  * anytime_* — margin-ordered anytime inference on a trained artifact:
    exact early-exit speedup + the budgeted quality-tier
    accuracy-vs-latency frontier (-> BENCH_anytime.json; run as its own
    CI job, recorded in BENCH_STATUS here)
  * roofline_* — per dry-run cell roofline terms (deliverable g)
"""

from __future__ import annotations

import argparse
import json
import sys
import time
import traceback


def _tm_core_micro() -> list:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import tm
    from repro.kernels import ops

    rows = []
    cfg = tm.TMConfig(n_features=784, n_classes=10, clauses_per_class=100,
                      threshold=40, s=8.0)
    st = tm.init(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    X = jnp.asarray(rng.integers(0, 2, (256, 784), dtype=np.uint8))
    y = jnp.asarray(rng.integers(0, 10, 256, dtype=np.int32))

    step = jax.jit(lambda ta, x, yy, s: ops.tm_train_step_kernel(cfg, ta, x, yy, s)[0])
    ta = step(st.ta_state, X, y, jnp.uint32(0))
    ta.block_until_ready()
    t0 = time.perf_counter()
    for i in range(3):
        ta = step(ta, X, y, jnp.uint32(i))
    ta.block_until_ready()
    dt = (time.perf_counter() - t0) / 3
    rows.append(("tmcore_train_step_b256", dt * 1e6,
                 f"samples_s={256 / dt:,.0f}"))

    pred = jax.jit(lambda ta, x: tm.predict(cfg, tm.TMState(ta, jnp.int32(0)), x))
    pred(ta, X).block_until_ready()
    t0 = time.perf_counter()
    for _ in range(5):
        out = pred(ta, X)
    out.block_until_ready()
    dt = (time.perf_counter() - t0) / 5
    rows.append(("tmcore_dense_infer_b256", dt * 1e6,
                 f"inf_s={256 / dt:,.0f}"))
    return rows


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true",
                    help="skip the slow train-from-scratch tables")
    ap.add_argument("--status-out", default=None,
                    help="also write the per-benchmark status JSON here")
    args = ap.parse_args()

    from benchmarks import (fused_infer, fused_train, logic_sharing,
                            roofline_report, sparse_infer, table1_inference,
                            term_infer)

    # Per-benchmark status (name -> ok | skipped | "fail: <exc>") so the CI
    # log shows which benchmark actually ran — wall times alone can't
    # distinguish "fast" from "crashed before timing".
    status: dict = {}
    rows = []

    def section(name: str, fn):
        try:
            r = fn()
            status[name] = "ok"
            rows.extend(r)
        except Exception as e:  # noqa: BLE001 — keep benching, report at end
            status[name] = f"fail: {type(e).__name__}: {e}"
            traceback.print_exc()

    section("tmcore", _tm_core_micro)

    def _fused_infer():
        r = fused_infer.run(fast=args.fast)
        fused_infer.write_report(r)
        return r

    def _fused_train():
        r = fused_train.run(fast=args.fast)
        fused_train.write_report(r)
        return r

    def _sparse_infer():
        r = sparse_infer.run(fast=args.fast)
        sparse_infer.write_report(r)
        return r

    def _term_infer():
        r = term_infer.run(fast=args.fast)
        term_infer.write_report(r)
        return r

    section("fused_infer", _fused_infer)
    section("fused_train", _fused_train)
    if args.fast:
        # sparse_infer / term_infer: the CI bench job already trains +
        # times these artifacts via scripts/bench_smoke.py (fresh_sparse /
        # fresh_term.json); re-running the heavy train-and-time here would
        # double their share
        status["sparse_infer"] = "skipped (covered by scripts/bench_smoke.py)"
        status["term_infer"] = "skipped (covered by scripts/bench_smoke.py)"
        status["table1_inference"] = "skipped"
        status["logic_sharing"] = "skipped"
    else:
        section("sparse_infer", _sparse_infer)
        section("term_infer", _term_infer)
        section("table1_inference", lambda: table1_inference.run("mnist"))
        section("logic_sharing", lambda: logic_sharing.run("mnist"))
    section("roofline", roofline_report.run)
    # benchmarks/sharded_step.py needs its own process (forced device
    # count); it is a separate CI step, recorded here as such.
    status["sharded_step"] = "skipped (own process: python -m benchmarks.sharded_step)"
    # benchmarks/anytime.py trains its own edge-XL artifact and is gated
    # by the dedicated `anytime` CI job; re-running it here would double
    # the train-and-time cost of the bench job.
    status["anytime"] = "skipped (own CI job: python -m benchmarks.anytime)"

    print("name,us_per_call,derived")
    for name, us, derived in rows:
        print(f"{name},{us:.3f},{derived}")
    print("BENCH_STATUS " + json.dumps(status, sort_keys=True))
    if args.status_out:
        with open(args.status_out, "w") as f:
            json.dump(status, f, indent=1, sort_keys=True)
    return 1 if any(str(v).startswith("fail") for v in status.values()) else 0


if __name__ == "__main__":
    sys.exit(main())
