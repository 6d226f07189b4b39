"""Block-sparse compiled inference benchmark (perf trajectory tracker).

This is the first benchmark whose measured speedup scales with TRAINED-MODEL
SPARSITY rather than raw shape: a TM is trained on class-structured data,
compiled (``core/compiler.py``: dedup + dead words + chain-schedule
emission), and the same compiled artifact is timed through four engines on
the same request stream:

  * ``sparse``     — kernels/sparse_infer.py: the block-sparse chain
    schedule (scalar-prefetched ragged tile grid, bit-parallel over
    samples; work ~ include bits of the artifact) [the lead row]
  * ``dense``      — kernels/fused_infer.py on the compiled artifact at the
    autotuner's best dense tiling (streams every literal word per clause
    block)
  * ``uncompiled`` — kernels/fused_infer.py on the RAW trained bank
    (no dedup / dead-word elim; empty clauses masked at runtime)
  * ``oracle``     — the pure-jnp XLA path on the compiled artifact

The lead shape is the repo's edge-XL-width bank: B=512 requests x C=4096
clauses over 4096 boolean features (W=256 literal words) — wide enough that
a trained clause's ~20-bit chain leaves >90% of the dense word stream
untouched.  The bank is trained with ``ops.tm_train_step_kernel`` (the
artifact's include statistics are what matter here).

Engines are timed in ISOLATED per-engine loops, the whole sweep run twice
(see ``_time_isolated`` — a round-robin would charge whichever engine runs
after the oracle for its ~2 GB evicted working set), and written to
``BENCH_sparse_infer.json`` by ``write_report`` — the cross-PR perf
trajectory file gated by scripts/check_bench.py.  On this CPU container
both kernels run in Pallas interpret mode; the sparse-vs-dense ratio is the
tracked quantity.
"""

from __future__ import annotations

import json
import platform
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import compiler, packetizer, tm
from repro.data import make_boolean_classification
from repro.kernels import autotune as _autotune
from repro.kernels import ops

# (B, n_features, n_classes, clauses_per_class): the lead row is
# B=512 x C=4096 at edge-XL literal width (W=256 words).
SHAPES = [
    (512, 4096, 8, 512),
    (512, 784, 8, 512),    # paper MNIST width (W=49)
]
# enough steps that clauses converge to their sparse include sets (the
# young-model regime is dense and under-represents a deployed artifact)
_TRAIN_SAMPLES = 1536
_TRAIN_EPOCHS = 3
_TRAIN_BATCH = 64


def _train_artifact(n_features: int, n_classes: int, cpc: int, seed: int = 0):
    """Train a TM with the training step and compile it."""
    cfg = tm.TMConfig(n_features=n_features, n_classes=n_classes,
                      clauses_per_class=cpc, threshold=50, s=10.0)
    X, y = make_boolean_classification(
        _TRAIN_SAMPLES, n_features, n_classes,
        prototype_density=0.05, seed=seed,
    )
    Xj, yj = jnp.asarray(X), jnp.asarray(y)
    state = tm.init(cfg, jax.random.PRNGKey(seed))
    step = jax.jit(
        lambda ta, x, yy, s: ops.tm_train_step_kernel(cfg, ta, x, yy, s)[0]
    )
    ta = state.ta_state
    k = 0
    n_batches = _TRAIN_SAMPLES // _TRAIN_BATCH
    for _ in range(_TRAIN_EPOCHS):
        for i in range(n_batches):
            sl = slice(i * _TRAIN_BATCH, (i + 1) * _TRAIN_BATCH)
            ta = step(ta, Xj[sl], yj[sl], jnp.uint32(k))
            k += 1
    ta.block_until_ready()
    return cfg, ta, compiler.compile_tm(cfg, ta)


def _time_isolated(fns: dict, reps: int, sweeps: int = 2) -> dict:
    """min seconds per engine, each timed in its own consecutive loop.

    Unlike the round-robin used by the dense benches, engines here have
    very different working sets (the oracle materializes the (B, C, W)
    violation tensor, ~2 GB at the lead shape; the raw bank streams the
    full dense word grid) — in a rotation, whoever runs after the big one
    is charged its evicted caches, which on a small container flips the
    measured ratio run to run.  Isolated loops give each engine its own
    steady state; running the whole sweep twice still catches container
    drift across the bench.
    """
    for fn in fns.values():
        fn().block_until_ready()        # compile + warm
    best = {k: float("inf") for k in fns}
    for _ in range(sweeps):
        for k, fn in fns.items():
            fn().block_until_ready()    # re-warm this engine's buffers
            for _ in range(reps):
                t0 = time.perf_counter()
                fn().block_until_ready()
                best[k] = min(best[k], time.perf_counter() - t0)
    return best


def run(fast: bool = True, reps: int = 5, autotune: bool = True) -> list:
    _, interpret = ops.kernel_dispatch(True, None)
    rng = np.random.default_rng(0)
    rows = []
    for B, F, K, cpc in SHAPES[:1] if fast else SHAPES:
        cfg, ta, comp = _train_artifact(F, K, cpc)
        W = comp.stats.n_words_dense
        lit = jnp.asarray(
            packetizer.pack_literals(
                jnp.asarray(rng.integers(0, 2, (B, F), dtype=np.uint8))
            )
        )

        sblocks = (
            _autotune.autotune_sparse_infer_blocks(
                B, K, comp.include_words, interpret=interpret)
            if autotune else {}
        )
        dblocks = (
            _autotune.autotune_fused_blocks(
                B, comp.n_unique, comp.n_words_active, K,
                interpret=interpret)
            if autotune else {}
        )
        raw_iw = packetizer.pack_include_masks(jnp.asarray(ta))
        raw_votes = tm.vote_matrix(cfg)
        raw_ne = jnp.any(jnp.asarray(ta) >= 0, axis=-1).astype(jnp.uint8)
        rblocks = (
            _autotune.autotune_fused_blocks(
                B, cfg.n_clauses_total, W, K, interpret=interpret)
            if autotune else {}
        )

        def compiled_fwd(engine, **blk):
            # engine="sparse" (not "auto"): this bench tracks the PR-4
            # flat bit-chain kernel; under "auto" the factorize heuristic
            # would serve the term-schedule kernel on high-sharing trained
            # artifacts and silently corrupt the sparse trajectory row
            jitted = jax.jit(lambda l: compiler.run_compiled(
                comp, l, engine=engine, interpret=interpret, **blk,
            ))
            return lambda: jitted(lit)

        def raw_fwd(**blk):
            jitted = jax.jit(lambda l: ops.tm_forward_packed(
                l, raw_iw, raw_votes, raw_ne,
                use_kernel=True, interpret=interpret, **blk,
            ))
            return lambda: jitted(lit)

        def oracle_fwd():
            jitted = jax.jit(lambda l: compiler.run_compiled(
                comp, l, engine="oracle"))
            return lambda: jitted(lit)

        t = _time_isolated(
            dict(
                sparse=compiled_fwd("sparse", **sblocks),
                dense=compiled_fwd("dense", **dblocks),
                uncompiled=raw_fwd(**rblocks),
            ),
            reps,
        )
        # informational row; ~0.5 s/call, so a short isolated loop suffices
        t.update(_time_isolated(dict(oracle=oracle_fwd()), 2, sweeps=1))
        sched = comp.schedule(sblocks.get("block_c"), sblocks.get("block_j"))
        tag = f"b{B}_c{cfg.n_clauses_total}_w{W}_k{K}"
        sblk = ";".join(f"{k}={v}" for k, v in sorted(sblocks.items()))
        rows.append((
            f"sparseinfer_sparse_{tag}", t["sparse"] * 1e6,
            f"speedup_vs_dense={t['dense'] / t['sparse']:.2f}x;"
            f"include_sparsity={comp.stats.include_sparsity:.4f};"
            f"tile_sparsity={sched.tile_sparsity:.4f};"
            f"n_tiles={sched.n_tiles}"
            + (f";{sblk}" if sblk else ""),
        ))
        rows.append((
            f"sparseinfer_dense_{tag}", t["dense"] * 1e6,
            "compiled_dense_fused;" + ";".join(
                f"{k}={v}" for k, v in sorted(dblocks.items())),
        ))
        rows.append((
            f"sparseinfer_uncompiled_{tag}", t["uncompiled"] * 1e6,
            f"raw_bank_fused;speedup_compiled_sparse="
            f"{t['uncompiled'] / t['sparse']:.2f}x",
        ))
        rows.append((
            f"sparseinfer_oracle_{tag}", t["oracle"] * 1e6, "pure_jnp_xla",
        ))
    return rows


def write_report(rows: list, path: str = "BENCH_sparse_infer.json") -> None:
    _, interpret = ops.kernel_dispatch(True, None)
    report = dict(
        benchmark="sparse_infer",
        backend=jax.default_backend(),
        interpret_mode=bool(interpret),
        jax_version=jax.__version__,
        platform=platform.platform(),
        autotune_cache=_autotune.cache_path(),
        rows=[dict(name=n, us_per_call=us, derived=d) for n, us, d in rows],
    )
    with open(path, "w") as f:
        json.dump(report, f, indent=1)
