"""Predict-vs-sweep autotuner regret benchmark (-> BENCH_autotune.json).

The predict-first autotuner's promise is that a COLD-START artifact —
the zoo load path — can pick its kernel tiling from the analytical cost
model alone, with ZERO timing runs, and land within a few percent of
what a full wall-clock sweep would have chosen.  This benchmark measures
that promise end-to-end and turns it into a gated number:

1. Train + compile three small TMs spanning the regimes that move the
   model's inputs (include density and term sharing differ by seed /
   prototype density).  Two are TRAINING artifacts, one is HELD OUT.
2. Sweep the training artifacts with sidecar logging — the cost model
   refits from exactly the rows a production fleet would accumulate.
3. On the held-out artifact, in this order:
     * ``predict``: rank candidates analytically, take top-1 — the
       benchmark asserts ``autotune.TIMING_RUNS`` did not move;
     * ``verify``: wall-clock only the model's top-3 shortlist;
     * ``sweep``: time EVERY candidate — the ground truth.
4. Report regret = t(chosen)/t(best_swept) - 1 per policy.

The lead row (``autotune_sparse_predict_coldstart``) carries ``regret``
and ``timing_runs`` (must be 0); ``scripts/check_bench.py`` fails the
build when the fresh predict regret exceeds 10% — the paper-level claim
this PR ships.  Everything runs against a TEMP autotune cache + sidecar
so committed state and local caches never leak into the measurement.
"""

from __future__ import annotations

import json
import os
import platform
import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import compiler, tm
from repro.data import make_boolean_classification
from repro.kernels import autotune, cost_model, ops

# (n_features, n_classes, clauses_per_class, prototype_density, seed):
# the density spread moves include sparsity AND partial-term sharing, so
# the held-out artifact is a genuine generalization test, not a replay.
_ARTIFACTS = (
    (256, 4, 64, 0.08, 0),    # training: denser includes
    (384, 6, 64, 0.03, 1),    # training: sparser includes
    (320, 5, 64, 0.05, 2),    # HELD OUT
)
_B = 128                      # serving batch the tilings are picked for
_TRAIN_SAMPLES = 512
_TRAIN_EPOCHS = 2
_TRAIN_BATCH = 64


def _train_artifact(n_features, n_classes, cpc, density, seed):
    cfg = tm.TMConfig(n_features=n_features, n_classes=n_classes,
                      clauses_per_class=cpc, threshold=30, s=8.0)
    X, y = make_boolean_classification(
        _TRAIN_SAMPLES, n_features, n_classes,
        prototype_density=density, seed=seed)
    state = tm.init(cfg, jax.random.PRNGKey(seed))
    step = jax.jit(
        lambda ta, x, yy, s: ops.tm_train_step_kernel(cfg, ta, x, yy, s)[0])
    ta, k = state.ta_state, 0
    Xj, yj = jnp.asarray(X), jnp.asarray(y)
    for _ in range(_TRAIN_EPOCHS):
        for i in range(_TRAIN_SAMPLES // _TRAIN_BATCH):
            sl = slice(i * _TRAIN_BATCH, (i + 1) * _TRAIN_BATCH)
            ta = step(ta, Xj[sl], yj[sl], jnp.uint32(k))
            k += 1
    return compiler.compile_tm(cfg, np.asarray(ta))


def _sweep_timings(new_rows, kernel):
    """measured_us per tiling from the sidecar rows one sweep just wrote."""
    out = {}
    for row in new_rows:
        if row.get("kernel") == kernel:
            out[tuple(sorted(row["blocks"].items()))] = row["measured_us"]
    return out


def run(fast: bool = False) -> list:
    _, interpret = ops.kernel_dispatch(True, None)
    tmp = tempfile.mkdtemp(prefix="bench_autotune_")
    saved = {k: os.environ.get(k)
             for k in ("REPRO_AUTOTUNE_CACHE", "REPRO_TUNE_DATA")}
    os.environ["REPRO_AUTOTUNE_CACHE"] = os.path.join(tmp, "cache.json")
    os.environ["REPRO_TUNE_DATA"] = os.path.join(tmp, "data.json")
    autotune._PROC_CACHE.clear()
    cost_model._invalidate_model_cache()
    try:
        return _run_hermetic(interpret)
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        autotune._PROC_CACHE.clear()
        cost_model._invalidate_model_cache()


def _run_hermetic(interpret: bool) -> list:
    t0 = time.time()
    arts = [_train_artifact(*spec) for spec in _ARTIFACTS]
    held = arts[-1]
    print(f"trained {len(arts)} artifacts in {time.time() - t0:.1f}s; "
          f"held-out U={held.include_words.shape[0]} "
          f"sharing={held.stats.partial_term_sharing:.2f}")

    # 2. sidecar training data: sweep the two training artifacts
    for art in arts[:-1]:
        autotune.tune(
            "sparse_infer", B=_B, K=art.n_classes,
            include_words=art.include_words, interpret=interpret,
            policy="sweep", refresh=True, features=art.extract_features())
    n_train_rows = len(cost_model.load_observations())

    # 3a. predict on the held-out artifact — MUST issue zero timing runs
    runs_before = autotune.TIMING_RUNS
    ranked = autotune.rank_candidates(
        "sparse_infer", B=_B, K=held.n_classes,
        include_words=held.include_words, interpret=interpret)
    pred_blocks, pred_us = ranked[0]
    predict_runs = autotune.TIMING_RUNS - runs_before
    assert predict_runs == 0, f"predict issued {predict_runs} timing runs"

    # 3b. verify: wall-clock only the model's top-3
    runs_before = autotune.TIMING_RUNS
    verify_blocks = autotune.tune(
        "sparse_infer", B=_B, K=held.n_classes,
        include_words=held.include_words, interpret=interpret,
        policy="verify", top_k=3, refresh=True)
    verify_runs = autotune.TIMING_RUNS - runs_before

    # 3c. ground truth: full sweep, per-candidate times via the sidecar
    obs_before = len(cost_model.load_observations())
    runs_before = autotune.TIMING_RUNS
    sweep_blocks = autotune.tune(
        "sparse_infer", B=_B, K=held.n_classes,
        include_words=held.include_words, interpret=interpret,
        policy="sweep", refresh=True)
    sweep_runs = autotune.TIMING_RUNS - runs_before
    timings = _sweep_timings(
        cost_model.load_observations()[obs_before:], "sparse_infer")
    best_us = min(timings.values())

    def regret(blocks):
        return timings[tuple(sorted(blocks.items()))] / best_us - 1.0

    rows = [
        dict(name="autotune_sparse_predict_coldstart",
             us_per_call=timings[tuple(sorted(pred_blocks.items()))],
             regret=regret(pred_blocks), timing_runs=predict_runs,
             blocks=pred_blocks, predicted_us=pred_us,
             train_rows=n_train_rows),
        dict(name="autotune_sparse_verify_top3",
             us_per_call=timings[tuple(sorted(verify_blocks.items()))],
             regret=regret(verify_blocks), timing_runs=verify_runs,
             blocks=verify_blocks),
        dict(name="autotune_sparse_sweep_full",
             us_per_call=best_us, regret=regret(sweep_blocks),
             timing_runs=sweep_runs, blocks=sweep_blocks,
             candidates=len(timings)),
    ]
    for r in rows:
        print(f"{r['name']}: {r['us_per_call']:.0f}us regret="
              f"{r['regret']:.3f} timing_runs={r['timing_runs']}")
    return rows


def write_report(rows: list, path: str = "BENCH_autotune.json") -> None:
    _, interpret = ops.kernel_dispatch(True, None)
    report = dict(
        benchmark="autotune_cost",
        backend=jax.default_backend(),
        interpret_mode=bool(interpret),
        jax_version=jax.__version__,
        platform=platform.platform(),
        rows=rows,
    )
    with open(path, "w") as f:
        json.dump(report, f, indent=1)


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--out", default="BENCH_autotune.json")
    args = ap.parse_args(argv)
    rows = run()
    write_report(rows, args.out)
    print(f"wrote {args.out}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
