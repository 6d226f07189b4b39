"""Block-size autotuner for the fused TM Pallas kernels.

A fused kernel's throughput is a function of its ``(block_b, block_c,
block_w)`` tiling, and the best tiling depends on problem shape and backend
(VMEM budget, grid overhead, interpret vs compiled).  All four tuned
kernels (``fused_infer``, ``fused_train``, ``sparse_infer``, ``term_infer``)
register here (:data:`_REGISTRY`) and are tuned through ONE facade:

    tune("sparse_infer", B=512, K=10, include_words=iw,
         interpret=True, policy="verify")

with a three-mode ``policy``:

* ``"sweep"`` — wall-clock-time every candidate (the classic behavior),
  memoize the winner in the on-disk cache, and log every ``(basis,
  tiling, measured_us)`` observation into the cost model's training-data
  sidecar (``kernels/cost_model.py``) so sweeps anywhere keep improving
  predictions.
* ``"verify"`` (default) — rank candidates with the analytical cost
  model, then time only the predicted top-``k``.
* ``"predict"`` — trust the model outright: ZERO timing runs (the
  module-level :data:`TIMING_RUNS` counter proves it), which is what a
  multi-tenant zoo cold-load needs.

The legacy ``autotune_*_blocks`` entry points are thin wrappers over
``tune(..., policy="sweep")`` with identical cache keys and results.

Cache location: ``$REPRO_AUTOTUNE_CACHE`` if set, else
``~/.cache/repro/autotune.json``.  The file is ``{"schema": N, "entries":
{...}}``; a schema mismatch (older repo version, foreign writer, corrupt
file) invalidates the whole cache instead of crashing or silently reusing
blocks tuned for a different kernel signature.  Entries are keyed by
``<kernel>:v1:<backend>:<interp|compiled>:<shape>:cands[...]`` so a TPU run
never reads CPU-interpret timings, inference timings never answer for
training shapes, and vice versa; model-assisted policies add a
``:p<policy>`` tag so a prediction never masquerades as a measurement.
"""

from __future__ import annotations

import dataclasses
import functools
import json
import math
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import (cost_model, fused_infer, fused_train, sparse_infer,
                           term_infer)

_CACHE_ENV = "REPRO_AUTOTUNE_CACHE"
_KEY_VERSION = "v1"
# Bump when the on-disk layout (or the meaning of cached blocks) changes:
# schema 1 was the bare key->entry dict; schema 2 wrapped it in
# {"schema", "entries"}; schema 3 adds policy-tagged entries (":pverify" /
# ":ppredict" keys, "policy"/"predicted_us" fields) — a schema-2 cache may
# hold winners a model-restricted sweep would not have picked, so it
# invalidates wholesale like any other stale schema.
_SCHEMA_VERSION = 3

POLICIES = ("sweep", "verify", "predict")

# Every wall-clock kernel invocation the tuner makes (warmup included)
# increments this: ``policy="predict"`` leaving it untouched is the
# zero-timing-runs guarantee, asserted by tests and the regret benchmark.
TIMING_RUNS = 0

# candidate tilings: a deliberately small grid — the sweep is paid once per
# shape and cached, but each candidate costs a kernel compile.  Every
# candidate must lower on the TPU (tests/test_tpu_lowering.py compiles
# them all): the word chain unrolls block_w steps over a (block_c,
# block_b) tile, so block_w stays <= 64 and the tile <= 512 x 512.
_DEFAULT_CANDIDATES = (
    (128, 128, 64),   # VMEM-lean
    (128, 256, 64),   # wider clause bank: fewer adder-fold steps
    (256, 128, 64),   # taller request slab: fewer batch steps
    (256, 256, 32),
    (512, 512, 16),   # few big tiles: minimal grid overhead (small models)
    (64, 512, 64),
)

# Chain-id words the schedule kernels' SMEM tiles may hold: each tile is
# double-buffered in v5e's 1 MiB SMEM next to the scalar-prefetched tile
# tables, so block_c * block_j (+ block_t * term_w) stays within this.
SMEM_TILE_WORDS = 96 * 1024

# sparse (chain-schedule) kernel candidates: (block_c, block_j, block_s) —
# clause bank x chain-tile bits x sample-word slab.  The schedule is
# rebuilt per candidate (tile tables depend on the tiling), so the sweep
# measures real tile counts, not synthetic occupancy.
_SPARSE_CANDIDATES = (
    (512, 32, 16),    # sparse_infer.py defaults
    (1024, 32, 16),
    (512, 64, 16),
    (256, 32, 16),
    (1024, 64, 8),
    (512, 16, 16),
)

# factorized (two-level term-schedule) kernel candidates: (block_c,
# block_j, block_t, block_s, term_w) — clause bank x term-chain tile x
# stage-1 term tile x sample-word slab x term bit-chain width (0 = the
# artifact's auto width).  Schedules are rebuilt per candidate: term table
# size and tile counts depend on the tiling.  With term_w <= 32, every
# candidate's two SMEM chain tiles fit SMEM_TILE_WORDS.
_TERM_CANDIDATES = (
    (1024, 64, 512, 16, 0),     # term_infer.py defaults, auto width
    (1024, 64, 512, 16, 2),     # narrowest rows: fat terms split to pieces
    (512, 128, 512, 16, 2),
    (2048, 32, 512, 16, 2),
    (1024, 32, 1024, 16, 0),
    (512, 32, 1024, 16, 0),     # small-artifact shapes clip here
)

# training kernel candidates: the delta accumulator block is (block_c, L),
# so block_c also scales VMEM; block_b scales the fire/ftype scratch.
_TRAIN_CANDIDATES = (
    (128, 256, 64),   # fused_train.py defaults
    (128, 128, 64),
    (256, 256, 64),
    (64, 512, 64),
    (256, 512, 32),
)


def cache_path() -> str:
    p = os.environ.get(_CACHE_ENV)
    if p:
        return p
    return os.path.join(os.path.expanduser("~"), ".cache", "repro", "autotune.json")


def _load_cache() -> dict:
    """Entry dict from disk; {} on missing, corrupt, or stale-schema files."""
    try:
        with open(cache_path()) as f:
            raw = json.load(f)
    except (OSError, ValueError):
        return {}
    if not isinstance(raw, dict) or raw.get("schema") != _SCHEMA_VERSION:
        return {}   # stale schema: invalidate, never reuse or crash
    entries = raw.get("entries")
    return entries if isinstance(entries, dict) else {}


def _save_cache(entries: dict) -> None:
    path = cache_path()
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    tmp = f"{path}.tmp.{os.getpid()}"
    with open(tmp, "w") as f:
        json.dump({"schema": _SCHEMA_VERSION, "entries": entries},
                  f, indent=1, sort_keys=True)
    # os.replace keeps the file whole; concurrent tuners are last-writer-wins
    # (worst case a lost entry's sweep is re-paid, never a torn file)
    os.replace(tmp, path)


def _clip_candidate(blocks, B: int, C: int, W: int):
    """Apply the same clipping the kernel wrappers do, so duplicate
    post-clip candidates are swept only once."""
    bb, bc, bw = blocks
    bb = min(bb, fused_infer._rup(B, 8))
    bc = min(bc, fused_infer._rup(C, 128))
    bw = min(bw, W)
    return bb, bc, bw


def _clipped(candidates, B, C, W):
    out = []
    for cand in candidates:
        c = _clip_candidate(cand, B, C, W)
        if c not in out:
            out.append(c)
    return out


def _sweep(runs: dict, reps: int) -> dict:
    """min seconds per candidate tiling, timed round-robin so container
    noise drifts over every candidate equally instead of biasing the sweep
    order."""
    global TIMING_RUNS
    for run in runs.values():
        TIMING_RUNS += 1
        run().block_until_ready()      # compile + warm
    best = {k: float("inf") for k in runs}
    for _ in range(reps):
        for k, run in runs.items():
            TIMING_RUNS += 1
            t0 = time.perf_counter()
            run().block_until_ready()
            best[k] = min(best[k], time.perf_counter() - t0)
    return best


# in-process memo so hot loops (e.g. launch/train.py --autotune calling the
# resolver every step) never re-read and re-parse the on-disk JSON; keyed by
# (cache file, entry) so switching $REPRO_AUTOTUNE_CACHE mid-process works.
_PROC_CACHE: dict = {}


_DENSE_KEYS = ("block_b", "block_c", "block_w")


def _memoized_best(key: str, make_runs, reps: int, refresh: bool,
                   block_names=_DENSE_KEYS, observe=None) -> dict:
    """Sweep (or recall) the best block dict for `key`; ``block_names``
    labels the candidate-tuple fields (dense kernels use block_b/c/w, the
    sparse schedule kernel block_c/j/s).  ``observe(timings)`` fires only
    when a sweep actually ran (never on cache hits) — the tune facade uses
    it to feed the cost model's training-data sidecar."""
    pkey = (cache_path(), key)
    if not refresh and pkey in _PROC_CACHE:
        return dict(_PROC_CACHE[pkey])
    cache = _load_cache()
    if not refresh and key in cache:
        _PROC_CACHE[pkey] = dict(cache[key]["blocks"])
        return dict(cache[key]["blocks"])

    timings = _sweep(make_runs(), reps)
    if observe is not None:
        observe(timings)
    # within the measurement noise floor, prefer the largest tiling: fewer
    # grid steps is the structurally better config when timings can't
    # separate the candidates
    t_min = min(timings.values())
    best_blocks = max(
        (blk for blk, t in timings.items() if t <= t_min * 1.05),
        key=lambda blk: math.prod(blk),
    )
    result = dict(zip(block_names, best_blocks))
    cache = _load_cache()   # re-read to narrow the concurrent-writer window
    cache[key] = dict(blocks=result, us_per_call=timings[best_blocks] * 1e6)
    _save_cache(cache)
    _PROC_CACHE[pkey] = dict(result)
    return result


def _mode_backend(interpret: bool) -> str:
    mode = "interp" if interpret else "compiled"
    return f"{jax.default_backend()}:{mode}"


def _cands_tag(clipped) -> str:
    # the candidate set is part of the key: a sweep over a restricted custom
    # candidate list must not answer for the default sweep (or vice versa)
    return ",".join("x".join(map(str, c)) for c in clipped)


def _artifact_tag(include_words) -> str:
    """Short content hash of an artifact's include rows: the sparse
    kernel's runtime depends on the SCHEDULE (tile counts, chain lengths),
    so two same-shape artifacts with different sparsity must not share a
    cache entry.  Same hashing rule as the schedule memo
    (``sparse_infer.artifact_tag``)."""
    return sparse_infer.artifact_tag(include_words)[:10]


def _clip_sparse_candidate(blocks, B: int, U: int):
    bc, bj, bs = blocks
    bc = min(bc, fused_infer._rup(max(U, 1), 8))
    bs = max(min(bs, fused_infer._rup(-(-B // 32), 1)), 1)
    return bc, bj, bs


def _lit_tag(lit_words) -> str:
    """Key fragment for a caller-supplied representative literal stream:
    tunings measured on different workloads must not share an entry (a
    random stream kills trained chains in one tile — its winner can lose
    on the in-distribution stream a server actually sees)."""
    if lit_words is None:
        return ""
    return ":lit" + sparse_infer.artifact_tag(np.asarray(lit_words))[:10]


def _clip_term_candidate(blocks, B: int, U: int, iw, n_pieces_bound: int
                         ) -> tuple:
    bc, bj, bt, bs, tw = blocks
    bc = min(bc, fused_infer._rup(max(U, 1), 8))
    bs = max(min(bs, fused_infer._rup(-(-B // 32), 1)), 1)
    if tw == 0:   # 0 = the artifact's auto width (resolved so duplicate
        tw = term_infer.pick_term_width(iw)   # post-clip candidates dedup)
    # the schedule builder clips block_t to its term count; apply the same
    # bound here (pieces <= total include bits) so small artifacts dedup
    # candidates that only differ in an unreachable block_t
    bt = max(min(bt, fused_infer._rup(n_pieces_bound + 1, 8)), 1)
    return bc, bj, bt, bs, tw


# ---------------------------------------------------------------------------
# Kernel registry: candidates, cache keys, timed runs, and cost-model basis
# ---------------------------------------------------------------------------

def _ceil_div(a: int, b: int) -> int:
    return -(-a // max(b, 1))


@dataclasses.dataclass(frozen=True)
class KernelTuner:
    """One tuned kernel's registration: how to clip/dedup its candidate
    tuples, key its cache entries, build timed runs, and featurize a
    candidate into the cost model's roofline-style basis terms.  All four
    callables take the normalized ``problem`` dict built by ``prepare``
    from ``tune(...)``'s shape kwargs — this registry is the cost model's
    single registration point (a fifth kernel plugs in here and every
    policy, sidecar row, and benchmark picks it up)."""
    name: str
    block_names: tuple
    default_candidates: tuple
    default_reps: int
    prepare: callable       # (**shape_kwargs) -> problem dict
    clip: callable          # (candidates, problem) -> unique clipped tuples
    cache_key: callable     # (problem, clipped, mode) -> sweep cache key
    make_runs: callable     # (problem, clipped, interpret) -> {cand: thunk}
    basis: callable         # (problem, cand) -> {basis_term: float}


_REGISTRY: dict = {}


def register(tuner: KernelTuner) -> None:
    _REGISTRY[tuner.name] = tuner


def kernels() -> tuple:
    """Registered tunable kernel names."""
    return tuple(_REGISTRY)


# -- fused dense inference ---------------------------------------------------

def _dense_prepare(*, B, C, W, K):
    return dict(B=int(B), C=int(C), W=int(W), K=int(K))


def _dense_clip(candidates, p):
    return _clipped(candidates, p["B"], p["C"], p["W"])


def _dense_key(p, clipped, mode):
    return (f"fused_infer:{_KEY_VERSION}:{mode}:"
            f"B{p['B']}:C{p['C']}:W{p['W']}:K{p['K']}:"
            f"cands[{_cands_tag(clipped)}]")


def _dense_runs(p, clipped, interpret):
    B, C, W, K = p["B"], p["C"], p["W"], p["K"]
    rng = np.random.default_rng(0)
    lit = jnp.asarray(rng.integers(0, 2**32, (B, W), dtype=np.uint32))
    inc = jnp.asarray(rng.integers(0, 2**32, (C, W), dtype=np.uint32))
    votes = jnp.asarray(rng.integers(-2, 3, (C, K), dtype=np.int32))
    nonempty = jnp.ones((C,), jnp.int32)
    return {
        (bb, bc, bw): functools.partial(
            fused_infer.fused_tm_forward, lit, inc, votes, nonempty,
            block_b=bb, block_c=bc, block_w=bw, interpret=interpret,
        )
        for bb, bc, bw in clipped
    }


def _dense_basis(p, cand):
    """Roofline terms for one (block_b, block_c, block_w): grid steps
    (per-step dispatch dominates interpret mode), padded clause-eval
    volume, class-sum fold volume, and HBM tile traffic."""
    B, C, W, K = p["B"], p["C"], p["W"], p["K"]
    bb, bc, bw = cand
    nb, nc, nw = _ceil_div(B, bb), _ceil_div(C, bc), _ceil_div(W, bw)
    steps = nb * nc * nw
    return dict(
        steps=float(steps),
        work_melem=steps * bb * bc * bw / 1e6,
        fold_melem=nb * nc * bb * bc * K / 1e6,
        bytes_mb=steps * (bb * bw + bc * bw) * 4 / 1e6,
    )


register(KernelTuner(
    name="fused_infer", block_names=_DENSE_KEYS,
    default_candidates=_DEFAULT_CANDIDATES, default_reps=5,
    prepare=_dense_prepare, clip=_dense_clip, cache_key=_dense_key,
    make_runs=_dense_runs, basis=_dense_basis,
))


# -- fused training ----------------------------------------------------------

def _train_prepare(*, B, C, W, L, K):
    return dict(B=int(B), C=int(C), W=int(W), L=int(L), K=int(K))


def _train_clip(candidates, p):
    return _clipped(candidates, p["B"], p["C"], p["W"])


def _train_key(p, clipped, mode):
    return (f"fused_train:{_KEY_VERSION}:{mode}:"
            f"B{p['B']}:C{p['C']}:W{p['W']}:L{p['L']}:K{p['K']}:"
            f"cands[{_cands_tag(clipped)}]")


def _train_runs(p, clipped, interpret):
    from repro.core import packetizer

    B, C, W, L, K = p["B"], p["C"], p["W"], p["L"], p["K"]
    rng = np.random.default_rng(0)
    bits = rng.integers(0, 2, (B, L), dtype=np.uint8)
    lits = jnp.asarray(bits)
    lit_words = jnp.asarray(packetizer.pack_bits_np(bits))
    inc_bits = (rng.random((C, L)) < 0.05).astype(np.uint8)
    inc_full = np.zeros((C, W * 32), np.uint8)
    inc_full[:, :L] = inc_bits
    inc_words = jnp.asarray(packetizer.pack_bits_np(inc_full))
    ta = jnp.asarray(rng.integers(-64, 64, (C, L), dtype=np.int8))
    y = jnp.asarray(rng.integers(0, K, B, dtype=np.int32))
    kn = jnp.asarray((y + 1) % K, jnp.int32)
    p_t = jnp.asarray(rng.random(B, dtype=np.float32))
    p_n = jnp.asarray(rng.random(B, dtype=np.float32))
    cpc = max(1, C // K)
    cls = jnp.asarray(np.clip(np.arange(C) // cpc, 0, K - 1), jnp.int32)
    pol = jnp.asarray(np.where(np.arange(C) % 2 == 0, 1, -1), jnp.int32)
    seed = jnp.uint32(0)
    return {
        (bb, bc, bw): functools.partial(
            fused_train.fused_tm_train_delta,
            ta, lits, lit_words, inc_words, y, kn, p_t, p_n, cls, pol,
            seed, p_act=1.0, p_inact=0.1,
            block_b=bb, block_c=bc, block_w=bw, interpret=interpret,
        )
        for bb, bc, bw in clipped
    }


def _train_basis(p, cand):
    """Dense-inference terms plus the (block_c, L) delta-accumulator and
    (block_b, L) literal-slab traffic the training kernel adds."""
    B, C, W, L, K = p["B"], p["C"], p["W"], p["L"], p["K"]
    bb, bc, bw = cand
    nb, nc, nw = _ceil_div(B, bb), _ceil_div(C, bc), _ceil_div(W, bw)
    steps = nb * nc * nw
    return dict(
        steps=float(steps),
        work_melem=steps * bb * bc * bw / 1e6,
        l_work_melem=nb * nc * (bc + bb) * L / 1e6,
        bytes_mb=(steps * (bb * bw + bc * bw) + nb * nc * bc * L) * 4 / 1e6,
    )


register(KernelTuner(
    name="fused_train", block_names=_DENSE_KEYS,
    default_candidates=_TRAIN_CANDIDATES, default_reps=3,
    prepare=_train_prepare, clip=_train_clip, cache_key=_train_key,
    make_runs=_train_runs, basis=_train_basis,
))


# -- sparse chain-schedule inference -----------------------------------------

def _sparse_prepare(*, B, K, include_words, lit_words=None):
    iw = np.ascontiguousarray(np.asarray(include_words, dtype=np.uint32))
    U, Wa = iw.shape
    return dict(B=int(B), K=int(K), iw=iw, U=U, Wa=Wa, lit_words=lit_words)


def _sparse_clip(candidates, p):
    clipped = []
    for cand in candidates:
        c = _clip_sparse_candidate(cand, p["B"], p["U"])
        if c not in clipped:
            clipped.append(c)
    return clipped


def _sparse_key(p, clipped, mode):
    return (f"sparse_infer:{_KEY_VERSION}:{mode}:"
            f"B{p['B']}:U{p['U']}:W{p['Wa']}:K{p['K']}:"
            f"sig{_artifact_tag(p['iw'])}{_lit_tag(p['lit_words'])}:"
            f"cands[{_cands_tag(clipped)}]")


def _sparse_runs(p, clipped, interpret):
    rng = np.random.default_rng(0)
    lw = p["lit_words"]
    lit = (jnp.asarray(np.asarray(lw)) if lw is not None
           else jnp.asarray(
               rng.integers(0, 2**32, (p["B"], p["Wa"]), dtype=np.uint32)))
    votes = jnp.asarray(
        rng.integers(-2, 3, (p["U"], p["K"]), dtype=np.int32))
    runs = {}
    for bc, bj, bs in clipped:
        sched = sparse_infer.build_schedule(p["iw"], block_c=bc, block_j=bj)
        runs[(bc, bj, bs)] = functools.partial(
            sparse_infer.sparse_tm_forward, lit, votes, sched,
            block_s=bs, interpret=interpret,
        )
    return runs


def _sparse_basis(p, cand):
    """Terms from the REAL ragged schedule this candidate would execute
    (``build_schedule_cached`` — numpy-only, memoized): actual tile count
    and clause-block count, not a dense-occupancy guess."""
    bc, bj, bs = cand
    sched = sparse_infer.build_schedule_cached(
        p["iw"], block_c=bc, block_j=bj)
    n_tiles = int(len(sched.tile_cb))
    n_cblocks = int(len(sched.counts))
    sw = _ceil_div(_ceil_div(p["B"], 32), bs)
    steps = sw * n_tiles
    return dict(
        steps=float(steps),
        chain_melem=steps * bc * bj * bs / 1e6,
        fold_melem=sw * n_cblocks * bc * p["K"] * bs / 1e6,
        bytes_mb=steps * bc * bj * 4 / 1e6,
    )


register(KernelTuner(
    name="sparse_infer", block_names=("block_c", "block_j", "block_s"),
    default_candidates=_SPARSE_CANDIDATES, default_reps=5,
    prepare=_sparse_prepare, clip=_sparse_clip, cache_key=_sparse_key,
    make_runs=_sparse_runs, basis=_sparse_basis,
))


# -- factorized two-level term-schedule inference ----------------------------

def _term_prepare(*, B, K, include_words, lit_words=None):
    iw = np.ascontiguousarray(np.asarray(include_words, dtype=np.uint32))
    U, Wa = iw.shape
    n_bits_total = int(np.unpackbits(iw.view(np.uint8)).sum())
    return dict(B=int(B), K=int(K), iw=iw, U=U, Wa=Wa,
                n_bits_total=n_bits_total, lit_words=lit_words)


def _term_clip(candidates, p):
    clipped = []
    for cand in candidates:
        c = _clip_term_candidate(cand, p["B"], p["U"], p["iw"],
                                 p["n_bits_total"])
        if c not in clipped:
            clipped.append(c)
    return clipped


def _term_key(p, clipped, mode):
    return (f"term_infer:{_KEY_VERSION}:{mode}:"
            f"B{p['B']}:U{p['U']}:W{p['Wa']}:K{p['K']}:"
            f"sig{_artifact_tag(p['iw'])}{_lit_tag(p['lit_words'])}:"
            f"cands[{_cands_tag(clipped)}]")


def _term_runs(p, clipped, interpret):
    rng = np.random.default_rng(0)
    lw = p["lit_words"]
    lit = (jnp.asarray(np.asarray(lw)) if lw is not None
           else jnp.asarray(
               rng.integers(0, 2**32, (p["B"], p["Wa"]), dtype=np.uint32)))
    votes = jnp.asarray(
        rng.integers(-2, 3, (p["U"], p["K"]), dtype=np.int32))
    runs = {}
    for bc, bj, bt, bs, tw in clipped:
        sched = term_infer.build_factorized_schedule(
            p["iw"], block_c=bc, block_j=bj, block_t=bt, term_w=tw)
        runs[(bc, bj, bt, bs, tw)] = functools.partial(
            term_infer.factorized_tm_forward, lit, votes, sched,
            block_s=bs, interpret=interpret,
        )
    return runs


def _term_basis(p, cand):
    """Terms from the real factorized schedule: the stage-1 (term eval) /
    stage-2 (clause chain) tile split and the term-table size are
    properties of the trained artifact + tiling, so both stages get their
    own work term for the model to weight."""
    bc, bj, bt, bs, tw = cand
    sched = term_infer.build_factorized_schedule_cached(
        p["iw"], block_c=bc, block_j=bj, block_t=bt, term_w=tw)
    stage = np.asarray(sched.tile_stage)
    n_tiles = int(len(stage))
    n_term_tiles = int((stage == 0).sum())
    n_clause_tiles = n_tiles - n_term_tiles
    n_cblocks = int(len(sched.counts))
    sw = _ceil_div(_ceil_div(p["B"], 32), bs)
    return dict(
        steps=float(sw * n_tiles),
        term_melem=sw * n_term_tiles * bt * tw * bs / 1e6,
        chain_melem=sw * n_clause_tiles * bc * bj * bs / 1e6,
        fold_melem=sw * n_cblocks * bc * p["K"] * bs / 1e6,
        bytes_mb=sw * (n_term_tiles * bt * tw
                       + n_clause_tiles * bc * bj) * 4 / 1e6,
    )


register(KernelTuner(
    name="term_infer",
    block_names=("block_c", "block_j", "block_t", "block_s", "term_w"),
    default_candidates=_TERM_CANDIDATES, default_reps=5,
    prepare=_term_prepare, clip=_term_clip, cache_key=_term_key,
    make_runs=_term_runs, basis=_term_basis,
))


# ---------------------------------------------------------------------------
# The facade
# ---------------------------------------------------------------------------

def tune(
    kernel: str,
    *,
    interpret: bool,
    policy: str = "verify",
    top_k: int = 3,
    candidates=None,
    reps: int | None = None,
    refresh: bool = False,
    features: dict | None = None,
    **shape,
) -> dict:
    """Best block dict for one registered kernel under a tuning policy.

    ``shape`` kwargs are per kernel: ``fused_infer`` takes ``B, C, W, K``;
    ``fused_train`` adds ``L``; ``sparse_infer``/``term_infer`` take
    ``B, K, include_words`` (+ optional ``lit_words`` representative
    stream).  ``features`` optionally attaches the artifact's
    candidate-independent feature dict (``cost_model.artifact_features``)
    to the sidecar rows a sweep logs.

    Policies: ``"sweep"`` times every candidate; ``"verify"`` times only
    the cost model's top-``top_k``; ``"predict"`` returns the model's
    top-1 with zero timing runs.  All three memoize on disk — predictions
    under a ``:ppredict``-tagged key carrying ``predicted_us`` instead of
    a measurement, so a later sweep of the same shape never reads them.
    """
    try:
        tuner = _REGISTRY[kernel]
    except KeyError:
        raise ValueError(
            f"unknown kernel {kernel!r}; registered: {sorted(_REGISTRY)}")
    if policy not in POLICIES:
        raise ValueError(f"unknown policy {policy!r}; one of {POLICIES}")

    problem = tuner.prepare(**shape)
    clipped = tuner.clip(candidates or tuner.default_candidates, problem)
    mode = _mode_backend(interpret)
    base_key = tuner.cache_key(problem, clipped, mode)
    reps = tuner.default_reps if reps is None else reps

    def observe(cands):
        def _log(timings):
            rows = [cost_model.make_observation(
                kernel, mode, dict(zip(tuner.block_names, cand)),
                tuner.basis(problem, cand), t * 1e6, features)
                for cand, t in timings.items()]
            cost_model.record_observations(rows)
        return _log

    if policy == "sweep":
        return _memoized_best(
            base_key, lambda: tuner.make_runs(problem, clipped, interpret),
            reps, refresh, block_names=tuner.block_names,
            observe=observe(clipped))

    ranked = cost_model.get_model(mode).rank(
        kernel, [(cand, tuner.basis(problem, cand)) for cand in clipped])

    if policy == "predict":
        key = f"{base_key}:ppredict"
        pkey = (cache_path(), key)
        if not refresh and pkey in _PROC_CACHE:
            return dict(_PROC_CACHE[pkey])
        cache = _load_cache()
        if not refresh and key in cache:
            _PROC_CACHE[pkey] = dict(cache[key]["blocks"])
            return dict(cache[key]["blocks"])
        best, pred_us = ranked[0]
        result = dict(zip(tuner.block_names, best))
        cache = _load_cache()
        cache[key] = dict(blocks=result, predicted_us=pred_us,
                          policy="predict")
        _save_cache(cache)
        _PROC_CACHE[pkey] = dict(result)
        return result

    # verify: wall-clock only the predicted top-k.  The shortlist is part
    # of the key — as the model refits, a new shortlist re-verifies rather
    # than trusting a stale one.
    short = [cand for cand, _ in ranked[:max(1, int(top_k))]]
    key = f"{base_key}:pverify:top[{_cands_tag(short)}]"
    return _memoized_best(
        key, lambda: tuner.make_runs(problem, short, interpret),
        reps, refresh, block_names=tuner.block_names,
        observe=observe(short))


def rank_candidates(kernel: str, *, interpret: bool, candidates=None,
                    **shape) -> list:
    """The cost model's full analytical ranking for a shape —
    ``[(blocks_dict, predicted_us), ...]`` best-first, zero timing runs.
    The introspection hook the regret benchmark and tests use."""
    tuner = _REGISTRY[kernel]
    problem = tuner.prepare(**shape)
    clipped = tuner.clip(candidates or tuner.default_candidates, problem)
    ranked = cost_model.get_model(_mode_backend(interpret)).rank(
        kernel, [(cand, tuner.basis(problem, cand)) for cand in clipped])
    return [(dict(zip(tuner.block_names, cand)), us) for cand, us in ranked]


def plan_engine(compiled, B: int, *, interpret: bool,
                policy: str = "predict", top_k: int = 3,
                refresh: bool = False) -> tuple:
    """Pick ``(engine_name, blocks)`` for serving a compiled artifact at
    batch ``B`` — the zoo cold-load path: with ``policy="predict"`` this
    makes ZERO timing runs (engine by the compiler's sharing heuristic,
    tiling by the cost model over the artifact's persisted features).
    """
    from repro.core import compiler

    stats = getattr(compiled, "stats", None)
    sharing = float(getattr(stats, "partial_term_sharing", 0.0) or 0.0)
    engine = ("factorized" if sharing >= compiler.FACTORIZE_SHARING_THRESHOLD
              else "sparse")
    kernel = "term_infer" if engine == "factorized" else "sparse_infer"
    blocks = tune(
        kernel, B=B, K=int(compiled.n_classes),
        include_words=compiled.include_words, interpret=interpret,
        policy=policy, top_k=top_k, refresh=refresh,
        features=getattr(compiled, "features", None) or None)
    return engine, blocks


# ---------------------------------------------------------------------------
# Legacy entry points (thin wrappers; same cache keys, same results)
# ---------------------------------------------------------------------------

def autotune_fused_blocks(
    B: int,
    C: int,
    W: int,
    K: int,
    *,
    interpret: bool,
    candidates=None,
    reps: int = 5,
    refresh: bool = False,
) -> dict:
    """Best ``{block_b, block_c, block_w}`` for a fused-INFERENCE shape.

    Thin wrapper over ``tune("fused_infer", ..., policy="sweep")``:
    sweeps ``candidates`` on synthetic data of the given shape, memoizing
    the winner on disk.  ``refresh=True`` ignores (and overwrites) any
    cached entry.
    """
    return tune("fused_infer", B=B, C=C, W=W, K=K, interpret=interpret,
                policy="sweep", candidates=candidates, reps=reps,
                refresh=refresh)


def autotune_sparse_infer_blocks(
    B: int,
    K: int,
    include_words,
    *,
    interpret: bool,
    candidates=None,
    reps: int = 5,
    refresh: bool = False,
    lit_words=None,
) -> dict:
    """Best ``{block_c, block_j, block_s}`` for a SPARSE-schedule artifact.

    Thin wrapper over ``tune("sparse_infer", ..., policy="sweep")``.
    Cached under ``sparse_infer:`` keys that include a content hash of the
    include rows — the ragged tile grid's cost is a property of the
    trained artifact, not just its shape.  Each candidate is timed on the
    real schedule it would execute (``build_schedule`` per tiling).
    ``lit_words`` supplies a representative packed request stream (e.g.
    an in-distribution serving bucket) — without it the sweep uses
    uniform-random literals, which let every trained chain die in its
    first tile and can crown a tiling that loses on live traffic.
    """
    return tune("sparse_infer", B=B, K=K, include_words=include_words,
                lit_words=lit_words, interpret=interpret, policy="sweep",
                candidates=candidates, reps=reps, refresh=refresh)


def autotune_term_infer_blocks(
    B: int,
    K: int,
    include_words,
    *,
    interpret: bool,
    candidates=None,
    reps: int = 5,
    refresh: bool = False,
    lit_words=None,
) -> dict:
    """Best ``{block_c, block_j, block_t, block_s, term_w}`` for a
    FACTORIZED-schedule artifact.

    Thin wrapper over ``tune("term_infer", ..., policy="sweep")``.
    Cached under ``term_infer:`` keys that include a content hash of the
    include rows — term-table size, tile counts, and the stage-1/stage-2
    work split are all properties of the trained artifact, not its shape.
    Each candidate is timed on the real factorized schedule it would
    execute (``build_factorized_schedule`` per tiling).  ``lit_words``
    supplies a representative packed request stream (see
    :func:`autotune_sparse_infer_blocks`).
    """
    return tune("term_infer", B=B, K=K, include_words=include_words,
                lit_words=lit_words, interpret=interpret, policy="sweep",
                candidates=candidates, reps=reps, refresh=refresh)


def autotune_fused_train_blocks(
    B: int,
    C: int,
    W: int,
    L: int,
    K: int,
    *,
    interpret: bool,
    candidates=None,
    reps: int = 3,
    refresh: bool = False,
) -> dict:
    """Best ``{block_b, block_c, block_w}`` for a fused-TRAINING shape.

    Thin wrapper over ``tune("fused_train", ..., policy="sweep")``.
    Cached under a distinct ``fused_train`` key — training tilings are
    never answered by inference sweeps (the training kernel's VMEM budget
    includes the (block_c, L) delta accumulator and the (block_b, L)
    literal slab, so its optimum differs).  Synthetic data uses
    class-aligned clause banks so the kernel's feedback-sparsity skip sees
    a realistic feedback density.
    """
    return tune("fused_train", B=B, C=C, W=W, L=L, K=K, interpret=interpret,
                policy="sweep", candidates=candidates, reps=reps,
                refresh=refresh)
