"""Analytical cost model for the kernel autotuner: predict, don't sweep.

The wall-clock autotuner (``kernels/autotune.py``) times every candidate
tiling — fine for one artifact, hopeless for a production zoo where a cold
tenant's first request must not trigger a timing sweep.  This module is the
predict-first tier behind ``autotune.tune(policy=...)``:

* **Workload features** (:func:`artifact_features`) — candidate-independent
  statistics of the compiled artifact: include-bit counts, chain-length
  distribution, ``partial_term_sharing``, term-table size (all already
  computed by ``core/compiler.CompileStats`` / the schedule builders), plus
  bytes/flops/HBM-traffic extracted from the compiled oracle HLO via
  ``launch/hlo_analysis`` and divided by the roofline peaks from
  ``launch/mesh`` (:func:`hlo_forward_features`).  ``CompiledTM.save()``
  persists this dict so a zoo cold-load never re-pays the HLO lowering.

* **Per-candidate basis** — each tuned kernel registers a featurizer in
  ``autotune``'s kernel registry that maps ``(shape, artifact, candidate)``
  to a small dict of roofline-style work terms (grid steps, gather volume,
  fold volume, HBM bytes — computed from the REAL schedule the candidate
  would execute, so ragged tile counts are exact, and exactly the terms a
  linear timing model can weight).

* **The model** (:class:`CostModel`) — predicted microseconds are a
  non-negative linear combination of the basis terms.  Shipped
  coefficients (:data:`DEFAULT_COEFFS`) were fitted on this repo's
  interpret-mode sweeps; every measured sweep ANYWHERE logs
  ``(features, basis, tiling, measured_us)`` rows into a persistent
  training-data sidecar (:func:`record_observations` — atomic
  ``os.replace``, same contract as the tune cache) and
  :func:`get_model` refits from it, so predictions keep improving as
  sweeps accumulate.

The model ranks candidates; ``autotune.tune`` decides what to do with the
ranking per policy: ``predict`` returns the top-1 with ZERO timing runs,
``verify`` times only the top-k, ``sweep`` times everything (and feeds the
sidecar).
"""

from __future__ import annotations

import functools
import json
import math
import os

import numpy as np

FEATURE_SCHEMA_VERSION = 1

# -- training-data sidecar ---------------------------------------------------

_DATA_ENV = "REPRO_TUNE_DATA"
_DATA_SCHEMA = 1
# FIFO cap: the sidecar is a rolling window, not an unbounded log — old
# observations age out as newer (same-machine, same-jax) sweeps land
_MAX_OBSERVATIONS = 4096
# below this many rows for a (kernel, mode) the fit is underdetermined and
# the shipped defaults answer instead
MIN_FIT_ROWS = 8


def data_path() -> str:
    p = os.environ.get(_DATA_ENV)
    if p:
        return p
    return os.path.join(os.path.expanduser("~"), ".cache", "repro",
                        "autotune_data.json")


def load_observations() -> list:
    """Sidecar rows from disk; [] on missing, corrupt, or stale-schema
    files (same invalidate-never-crash contract as the tune cache)."""
    try:
        with open(data_path()) as f:
            raw = json.load(f)
    except (OSError, ValueError):
        return []
    if not isinstance(raw, dict) or raw.get("schema") != _DATA_SCHEMA:
        return []
    rows = raw.get("observations")
    return rows if isinstance(rows, list) else []


def record_observations(rows: list) -> None:
    """Append sweep observations to the sidecar (read-merge-write under an
    atomic ``os.replace`` — concurrent sweeps are last-writer-wins per
    write, never a torn file; worst case a lost row is re-measured by a
    future sweep).  Rows beyond the FIFO cap age out oldest-first."""
    if not rows:
        return
    path = data_path()
    merged = load_observations() + list(rows)
    if len(merged) > _MAX_OBSERVATIONS:
        merged = merged[-_MAX_OBSERVATIONS:]
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    tmp = f"{path}.tmp.{os.getpid()}"
    with open(tmp, "w") as f:
        json.dump({"schema": _DATA_SCHEMA, "observations": merged}, f)
    os.replace(tmp, path)
    _invalidate_model_cache()


def make_observation(kernel: str, mode: str, blocks: dict, basis: dict,
                     measured_us: float, features: dict | None = None) -> dict:
    """One sidecar row.  ``mode`` is ``autotune._mode_backend`` output —
    interpret-mode timings must never train a compiled-backend model."""
    return dict(
        kernel=kernel, mode=mode, blocks=dict(blocks),
        basis={k: float(v) for k, v in basis.items()},
        measured_us=float(measured_us),
        features=dict(features) if features else None,
    )


# -- HLO-derived workload features -------------------------------------------

_HLO_REF_BATCH = 64


@functools.lru_cache(maxsize=64)
def hlo_forward_features(U: int, Wa: int, K: int,
                         batch: int = _HLO_REF_BATCH) -> dict:
    """bytes/flops/HBM-traffic of the compiled ORACLE forward at this
    artifact shape, per sample.

    The oracle (pure-XLA ``ref.clause_fire_ref`` + ``class_sum_ref``) is
    the one engine every backend can lower, so its post-optimization HLO
    is a backend-honest measure of the workload's intrinsic arithmetic and
    memory traffic — the quantity the roofline terms divide.  Extraction
    is an AOT ``jit(...).lower(...).compile()`` read by
    ``launch/hlo_analysis.analyze``.  Memoized per shape: one lowering per
    (U, Wa, K), shared by every candidate and every batch bucket.
    """
    import jax
    import jax.numpy as jnp

    from repro.kernels import ref
    from repro.launch import hlo_analysis
    from repro.launch.mesh import HBM_BW, PEAK_FLOPS_BF16

    def fwd(lit_words, inc_words, votes):
        fired = ref.clause_fire_ref(lit_words, inc_words)
        return ref.class_sum_ref(fired, votes)

    compiled = jax.jit(fwd).lower(
        jax.ShapeDtypeStruct((batch, Wa), jnp.uint32),
        jax.ShapeDtypeStruct((U, Wa), jnp.uint32),
        jax.ShapeDtypeStruct((U, K), jnp.int32),
    ).compile()
    cost = hlo_analysis.analyze(compiled.as_text())
    ca = compiled.cost_analysis() or {}
    flops = cost.flops / batch
    hbm = cost.bytes / batch
    return dict(
        hlo_flops_per_sample=flops,
        hlo_bytes_per_sample=hbm,
        xla_flops_per_sample=float(ca.get("flops", 0.0)) / batch,
        # roofline bounds (seconds/sample on the reference accelerator):
        # what the workload costs when compute- / memory-bound — the
        # analytic floor the predicted tilings are judged against
        roofline_t_comp=flops / PEAK_FLOPS_BF16,
        roofline_t_mem=hbm / HBM_BW,
    )


def artifact_features(compiled, *, with_hlo: bool = True) -> dict:
    """Candidate-independent workload features of a compiled artifact.

    ``compiled`` is duck-typed (``include_words``/``votes``/``stats``/
    ``n_classes`` — a ``core/compiler.CompiledTM`` or anything
    shape-compatible).  The dict is JSON-serializable; ``CompiledTM.save``
    persists it under ``meta["features"]`` so cold loads skip both the
    stat recomputation and the HLO lowering (``with_hlo=False`` skips the
    lowering here too, for callers that only need schedule stats).
    """
    iw = np.ascontiguousarray(np.asarray(compiled.include_words,
                                         dtype=np.uint32))
    U, Wa = iw.shape
    K = int(compiled.n_classes)
    chain = np.unpackbits(iw.view(np.uint8)).reshape(U, -1).sum(axis=1)
    n_includes = int(chain.sum())
    stats = getattr(compiled, "stats", None)
    feats = dict(
        schema=FEATURE_SCHEMA_VERSION,
        n_rows=U,
        n_words_active=Wa,
        n_classes=K,
        n_includes=n_includes,
        include_density=n_includes / max(U * Wa * 32, 1),
        chain_mean=float(chain.mean()) if U else 0.0,
        chain_p95=float(np.percentile(chain, 95)) if U else 0.0,
        chain_max=int(chain.max()) if U else 0,
        partial_term_sharing=(
            float(stats.partial_term_sharing) if stats is not None else 0.0),
        n_partial_terms_unique=(
            int(stats.n_partial_terms_unique) if stats is not None else 0),
    )
    if with_hlo:
        feats.update(hlo_forward_features(U, Wa, K))
    return feats


# -- the model ---------------------------------------------------------------

# Shipped coefficients: predicted MICROSECONDS per basis unit, fitted with
# ridge least squares (non-negative) on this container's interpret-mode
# sweeps across the four kernels' candidate grids (see
# benchmarks/autotune_cost.py for the refit-and-measure loop).  Interpret
# mode is dominated by per-grid-step dispatch overhead, which is why the
# ``steps`` terms carry most of the weight; ``*_melem`` terms are
# millions-of-elements work volumes.  A compiled backend should not trust
# these numbers — it should run sweeps (which feed the sidecar) until
# ``get_model`` has enough same-mode rows to refit.
# Shipped zero-data defaults: fit on the CI container (cpu:interp mode)
# via `scripts/fit_cost_model.py --sweep --interpret` over a grid of
# small/wide/tall problems and low/high-sharing include banks.  Units are
# µs per basis term; only the RANKING matters, so a different machine's
# absolute error is harmless until its sidecar refits these.  In
# interpret mode the per-grid-step dispatch overhead (`steps`) and the
# K-wide class-sum fold (`fold_melem`) dominate; `bytes_mb` fits to ~0
# because interpret mode never touches real HBM.
DEFAULT_COEFFS: dict = {
    "fused_infer": {
        "intercept": 8.45, "steps": 99.497,
        "work_melem": 441.127, "fold_melem": 1193.107, "bytes_mb": 0.0,
    },
    "fused_train": {
        "intercept": 22849.81, "steps": 2262.699,
        "work_melem": 74479.131, "l_work_melem": 0.0, "bytes_mb": 72658.346,
    },
    "sparse_infer": {
        "intercept": 40.774, "steps": 27.033,
        "chain_melem": 82.833, "fold_melem": 55197.206, "bytes_mb": 0.0,
    },
    "term_infer": {
        "intercept": 0.0, "steps": 179.94,
        "term_melem": 1220.827, "chain_melem": 1233.48,
        "fold_melem": 45300.49, "bytes_mb": 0.0,
    },
}


class CostModel:
    """Non-negative linear timing model over per-candidate basis terms."""

    def __init__(self, coeffs: dict | None = None):
        self.coeffs = {k: dict(v) for k, v in
                       (coeffs or DEFAULT_COEFFS).items()}

    def predict_us(self, kernel: str, basis: dict) -> float:
        theta = self.coeffs.get(kernel)
        if theta is None:
            # an unregistered kernel still gets a deterministic ranking:
            # fewer grid steps first (the structurally-better default)
            return float(basis.get("steps", 0.0))
        us = theta.get("intercept", 0.0)
        for name, value in basis.items():
            us += theta.get(name, 0.0) * float(value)
        return float(us)

    def rank(self, kernel: str, items: list) -> list:
        """``items`` is ``[(candidate, basis_dict), ...]``; returns
        ``[(candidate, predicted_us), ...]`` best-first.  Ties break
        toward the LARGER tiling, matching the sweep's noise-floor rule
        (fewer grid steps is structurally better when the model can't
        separate candidates)."""
        scored = [(cand, self.predict_us(kernel, basis))
                  for cand, basis in items]
        return sorted(scored, key=lambda cb: (cb[1], -math.prod(cb[0])))

    def fit(self, observations: list, mode: str,
            min_rows: int = MIN_FIT_ROWS, ridge: float = 1e-3) -> "CostModel":
        """Refit per-kernel coefficients from sidecar rows of the SAME
        backend/interpret mode (interpret timings must not train a
        compiled-mode model).  Kernels with fewer than ``min_rows``
        same-mode rows keep their current coefficients.  Ridge-regularized
        least squares with negative weights clipped to zero — a negative
        work coefficient would rank unboundedly-large tilings first.
        """
        new = CostModel(self.coeffs)
        by_kernel: dict = {}
        for row in observations:
            if not isinstance(row, dict) or row.get("mode") != mode:
                continue
            k = row.get("kernel")
            basis, us = row.get("basis"), row.get("measured_us")
            if k and isinstance(basis, dict) and isinstance(us, (int, float)):
                by_kernel.setdefault(k, []).append((basis, float(us)))
        for kernel, rows in by_kernel.items():
            if len(rows) < min_rows:
                continue
            names = sorted({n for basis, _ in rows for n in basis})
            if not names:
                continue
            X = np.array([[1.0] + [float(b.get(n, 0.0)) for n in names]
                          for b, _ in rows])
            y = np.array([us for _, us in rows])
            # scale-normalized ridge so the penalty is unit-agnostic
            scale = np.maximum(np.abs(X).max(axis=0), 1e-9)
            Xs = X / scale
            A = Xs.T @ Xs + ridge * np.eye(Xs.shape[1])
            try:
                theta = np.linalg.solve(A, Xs.T @ y) / scale
            except np.linalg.LinAlgError:
                continue
            theta = np.maximum(theta, 0.0)
            if not np.any(theta > 0):
                continue
            new.coeffs[kernel] = dict(
                intercept=float(theta[0]),
                **{n: float(t) for n, t in zip(names, theta[1:])})
        return new


_MODEL_CACHE: dict = {}


def _invalidate_model_cache() -> None:
    _MODEL_CACHE.clear()


def get_model(mode: str, refresh: bool = False) -> CostModel:
    """The process-wide model for a backend mode: shipped defaults refit
    against whatever same-mode observations the sidecar holds.  Memoized
    per (sidecar path, mode); new :func:`record_observations` writes
    invalidate the memo so every sweep immediately improves predictions.
    """
    key = (data_path(), mode)
    if not refresh and key in _MODEL_CACHE:
        return _MODEL_CACHE[key]
    model = CostModel().fit(load_observations(), mode)
    _MODEL_CACHE[key] = model
    return model
