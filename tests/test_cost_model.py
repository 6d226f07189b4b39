"""The analytical autotuner cost model: feature extraction + artifact
persistence, the observation sidecar (atomicity, cap, concurrency), the
ridge refit, and predictor regret on canned artifacts spanning the
sparsity/sharing range."""

from __future__ import annotations

import json
import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import compiler, tm
from repro.kernels import autotune, cost_model

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture()
def tune_env(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(tmp_path / "tune.json"))
    monkeypatch.setenv("REPRO_TUNE_DATA", str(tmp_path / "data.json"))
    cost_model._invalidate_model_cache()
    yield tmp_path
    cost_model._invalidate_model_cache()


def _random_tm(n_features, n_classes, cpc, include_density, seed):
    rng = np.random.default_rng(seed)
    C = n_classes * cpc
    ta = np.where(
        rng.random((C, 2 * n_features)) < include_density,
        rng.integers(0, 127, (C, 2 * n_features)),
        rng.integers(-128, 0, (C, 2 * n_features)),
    ).astype(np.int8)
    cfg = tm.TMConfig(n_features=n_features, n_classes=n_classes,
                      clauses_per_class=cpc)
    return cfg, ta


def _shared_tm():
    """High term-sharing bank: every clause carries the same two-word core."""
    cfg = tm.TMConfig(n_features=64, n_classes=2, clauses_per_class=8)
    C, L = 16, 128
    ta = np.full((C, L), -5, np.int8)
    ta[:, 3] = 3
    ta[:, 40] = 3
    for c in range(C):
        ta[c, 64 + ((c * 4) % 64)] = 3
    return cfg, ta


# ---------------------------------------------------------------------------
# Features
# ---------------------------------------------------------------------------

def test_artifact_features_contents():
    cfg, ta = _random_tm(48, 3, 8, 0.10, 4)
    comp = compiler.compile_tm(cfg, ta)
    feats = comp.extract_features()
    assert feats["schema"] == cost_model.FEATURE_SCHEMA_VERSION
    assert feats["n_rows"] == comp.include_words.shape[0]
    assert 0.0 < feats["include_density"] < 1.0
    assert feats["chain_max"] >= feats["chain_mean"] > 0
    assert feats["hlo_flops_per_sample"] > 0
    assert feats["hlo_bytes_per_sample"] > 0
    assert feats["roofline_t_comp"] >= 0
    # second call answers from the memo, not a re-lowering
    assert comp.extract_features() == feats


def test_features_save_load_roundtrip(tmp_path):
    cfg, ta = _random_tm(32, 2, 6, 0.12, 5)
    comp = compiler.compile_tm(cfg, ta)
    feats = comp.extract_features()
    path = str(tmp_path / "artifact.npz")
    comp.save(path)
    loaded = compiler.CompiledTM.load(path)
    assert set(loaded.features) == set(feats)
    for k, v in feats.items():
        assert loaded.features[k] == pytest.approx(v), k


def test_hlo_and_roofline_smoke():
    """launch/hlo_analysis + launch/roofline drive the feature pipeline on
    the pinned jax — an import-and-run smoke so version drift fails here,
    not deep inside a tuning run."""
    from repro.launch import hlo_analysis, roofline  # noqa: F401

    feats = cost_model.hlo_forward_features(16, 2, 3, batch=8)
    assert feats["hlo_flops_per_sample"] > 0
    assert feats["hlo_bytes_per_sample"] > 0
    assert feats["roofline_t_mem"] > 0

    def f(a, b):
        return a @ b

    compiled = jax.jit(f).lower(
        jnp.ones((4, 4), jnp.float32), jnp.ones((4, 4), jnp.float32)).compile()
    cost = hlo_analysis.analyze(compiled.as_text())
    assert cost.flops > 0
    assert isinstance(compiled.cost_analysis(), dict)


# ---------------------------------------------------------------------------
# Sidecar
# ---------------------------------------------------------------------------

def test_sidecar_roundtrip_and_cap(tune_env):
    rows = [cost_model.make_observation(
        "fused_infer", "cpu:interp", {"block_b": 8}, {"steps": float(i)},
        10.0 + i) for i in range(10)]
    cost_model.record_observations(rows)
    back = cost_model.load_observations()
    assert len(back) == 10
    assert back[0]["basis"] == {"steps": 0.0}
    # FIFO cap: a flood keeps only the newest _MAX_OBSERVATIONS
    flood = [cost_model.make_observation(
        "fused_infer", "cpu:interp", {"block_b": 8}, {"steps": 1.0}, 1.0)
        for _ in range(cost_model._MAX_OBSERVATIONS + 50)]
    cost_model.record_observations(flood)
    assert len(cost_model.load_observations()) == cost_model._MAX_OBSERVATIONS


def test_sidecar_corrupt_file_treated_as_empty(tune_env):
    (tune_env / "data.json").write_text("{torn write")
    assert cost_model.load_observations() == []
    cost_model.record_observations([cost_model.make_observation(
        "fused_infer", "cpu:interp", {}, {"steps": 1.0}, 5.0)])
    assert len(cost_model.load_observations()) == 1


_SIDECAR_PROC = """
import os, sys
os.environ["JAX_PLATFORMS"] = "cpu"
from repro.kernels import cost_model
i = int(sys.argv[1])
for j in range(20):
    cost_model.record_observations([cost_model.make_observation(
        "fused_infer", "cpu:interp", {"block_b": i},
        {"steps": float(j)}, 1.0 + j)])
print("WROTE", i)
"""


def test_sidecar_concurrent_writers(tmp_path):
    """N processes appending observations to the same $REPRO_TUNE_DATA:
    the atomic tmp+os.replace write means the file is ALWAYS valid JSON
    with the current schema — interleaved appends may drop rows
    (last-writer-wins per flush) but never tear the file."""
    data = tmp_path / "data.json"
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"),
               REPRO_TUNE_DATA=str(data), JAX_PLATFORMS="cpu")
    procs = [
        subprocess.Popen([sys.executable, "-c", _SIDECAR_PROC, str(i)],
                         env=env, stdout=subprocess.PIPE,
                         stderr=subprocess.PIPE, text=True)
        for i in range(4)
    ]
    for p in procs:
        out, err = p.communicate(timeout=600)
        assert p.returncode == 0, out + err
        assert "WROTE" in out

    raw = json.loads(data.read_text())       # parses: never torn
    assert raw["schema"] == cost_model._DATA_SCHEMA
    assert len(raw["observations"]) >= 20    # at least one writer's rows
    for row in raw["observations"]:          # every row structurally whole
        assert row["kernel"] == "fused_infer"
        assert isinstance(row["basis"], dict)
        assert isinstance(row["measured_us"], float)
    assert [f.name for f in tmp_path.iterdir()] == ["data.json"]


# ---------------------------------------------------------------------------
# Fit
# ---------------------------------------------------------------------------

def _obs(kernel, mode, steps, work, us):
    return cost_model.make_observation(
        kernel, mode, {"block_b": 8},
        {"steps": steps, "work_melem": work}, us)


def test_fit_recovers_linear_model():
    rng = np.random.default_rng(0)
    rows = []
    for _ in range(40):
        steps = float(rng.integers(1, 200))
        work = float(rng.random() * 10)
        rows.append(_obs("fused_infer", "cpu:interp", steps, work,
                         100.0 + 5.0 * steps + 30.0 * work))
    model = cost_model.CostModel().fit(rows, "cpu:interp", ridge=1e-6)
    theta = model.coeffs["fused_infer"]
    assert theta["steps"] == pytest.approx(5.0, rel=0.1)
    assert theta["work_melem"] == pytest.approx(30.0, rel=0.1)
    # prediction ranks a cheap tiling above an expensive one
    ranked = model.rank("fused_infer", [
        ((1,), {"steps": 500.0, "work_melem": 1.0}),
        ((2,), {"steps": 5.0, "work_melem": 1.0}),
    ])
    assert ranked[0][0] == (2,)


def test_fit_ignores_other_modes_and_small_samples():
    base = cost_model.CostModel()
    other = [_obs("fused_infer", "tpu:compiled", 10.0, 1.0, 1e9)
             for _ in range(50)]
    refit = base.fit(other, "cpu:interp")
    assert refit.coeffs == base.coeffs      # zero same-mode rows: unchanged
    few = [_obs("fused_infer", "cpu:interp", float(i), 0.0, float(i))
           for i in range(cost_model.MIN_FIT_ROWS - 1)]
    refit = base.fit(few, "cpu:interp")
    assert refit.coeffs == base.coeffs      # below MIN_FIT_ROWS: unchanged


def test_fit_clips_negative_weights():
    # adversarial data where OLS would go negative on `steps`
    rows = [_obs("fused_infer", "cpu:interp", s, w, 1000.0 - s)
            for s, w in [(float(i), float(i * 2)) for i in range(1, 20)]]
    model = cost_model.CostModel().fit(rows, "cpu:interp")
    assert all(v >= 0.0 for v in model.coeffs["fused_infer"].values())


def test_get_model_refits_after_new_observations(tune_env):
    m0 = cost_model.get_model("cpu:interp")
    assert m0.coeffs == cost_model.DEFAULT_COEFFS
    rows = [_obs("fused_infer", "cpu:interp", float(i), float(i % 3),
                 50.0 + 2.0 * i) for i in range(30)]
    cost_model.record_observations(rows)    # invalidates the memo
    m1 = cost_model.get_model("cpu:interp")
    assert m1.coeffs["fused_infer"] != cost_model.DEFAULT_COEFFS["fused_infer"]


# ---------------------------------------------------------------------------
# Predictor regret on canned artifacts (low/high sparsity and sharing)
# ---------------------------------------------------------------------------

_REGRET_CANDS = ((512, 32, 16), (64, 8, 2), (256, 32, 8), (128, 16, 4),
                 (512, 64, 16))


@pytest.mark.parametrize("maker,label", [
    (lambda: _random_tm(48, 3, 12, 0.04, 1), "low_density"),
    (lambda: _random_tm(64, 4, 16, 0.20, 2), "high_density"),
    (_shared_tm, "high_sharing"),
])
def test_predictor_regret_canned_artifact(tune_env, maker, label):
    """Analytical top-1 regret vs a full wall-clock sweep, per artifact.
    Interpret-mode timings on a busy CI box are noisy, so the bound is
    spread-aware: when the candidates genuinely differ (spread > 50%),
    the predicted pick must capture at least half the spread; tighter
    shapes only require staying under 75% regret."""
    cfg, ta = maker()
    comp = compiler.compile_tm(cfg, ta)

    # predict FIRST (defaults only — nothing measured on this shape yet)
    before = autotune.TIMING_RUNS
    ranked = autotune.rank_candidates(
        "sparse_infer", B=64, K=comp.n_classes,
        include_words=comp.include_words, interpret=True,
        candidates=_REGRET_CANDS)
    assert autotune.TIMING_RUNS == before
    pred = tuple(sorted(ranked[0][0].items()))

    # then ground-truth sweep, timings via the sidecar rows it logs
    autotune.tune("sparse_infer", B=64, K=comp.n_classes,
                  include_words=comp.include_words, interpret=True,
                  policy="sweep", candidates=_REGRET_CANDS, reps=3,
                  refresh=True)
    timings = {tuple(sorted(r["blocks"].items())): r["measured_us"]
               for r in cost_model.load_observations()
               if r["kernel"] == "sparse_infer"}
    assert pred in timings
    best, worst = min(timings.values()), max(timings.values())
    regret = timings[pred] / best - 1.0
    spread = worst / best - 1.0
    assert regret <= max(0.75, 0.5 * spread), (
        f"{label}: regret {regret:.2f} spread {spread:.2f} "
        f"pred {pred} timings {timings}")
