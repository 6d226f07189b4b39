"""Fused TM *training-step* microbenchmark (perf trajectory).

Times two execution engines on identical problem shapes, mirroring
``benchmarks/fused_infer.py`` for the training hot loop:

  * ``fused``    — kernels/fused_train.py single-pass kernel (clause fire +
    feedback plan + TA delta in one ``pallas_call``, fed by one fused-
    inference pass for class sums; the (B, C) fire/ftype matrices never
    exist in HBM), at the block tiling picked by kernels/autotune.py's
    cached training-shape sweep
  * ``oracle``   — the pure-jnp XLA path (the off-TPU default engine),
    batch-chunked so its (chunk, C, L) random field stays bounded

Both engines are bit-identical on the delta (tests/test_fused_train.py);
only speed differs.  Engines are timed interleaved (alternating calls, min
over rounds) so host noise hits all rows equally.  ``write_report``
persists the rows to ``BENCH_fused_train.json`` so the fused training
kernel's perf trajectory is tracked over time.  On a CPU the kernel runs
in Pallas interpret mode; on a TPU the same harness times the compiled
kernels.
"""

from __future__ import annotations

import json
import platform

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.fused_infer import _time_interleaved
from repro.core import tm
from repro.kernels import autotune as _autotune
from repro.kernels import ops

# (B, n_features, n_classes, clauses_per_class): the lead shape is the
# 512 x 4096-clause training cell — where (B, C) HBM intermediates and a
# dense (B, C, L) hash sweep would actually cost.
SHAPES = [
    (512, 128, 8, 512),    # C = 4096, L = 256, W = 8
    (256, 128, 8, 64),     # C = 512: small-bank regime
]

_ORACLE_CHUNK = 128   # bounds the oracle's (chunk, C, L) random field


def run(fast: bool = True, reps: int = 3, autotune: bool = True) -> list:
    _, interpret = ops.kernel_dispatch(True, None)
    rng = np.random.default_rng(0)
    rows = []
    for B, F, K, cpc in SHAPES[:1] if fast else SHAPES:
        cfg = tm.TMConfig(n_features=F, n_classes=K, clauses_per_class=cpc,
                          threshold=40, s=8.0)
        C, L = cfg.n_clauses_total, cfg.n_literals
        W = (L + 31) // 32
        ta = jnp.asarray(rng.integers(-64, 64, (C, L), dtype=np.int8))
        X = jnp.asarray(rng.integers(0, 2, (B, F), dtype=np.uint8))
        y = jnp.asarray(rng.integers(0, K, B, dtype=np.int32))
        seed = jnp.uint32(3)

        blocks = (
            _autotune.autotune_fused_train_blocks(
                B, C, W, L, K, interpret=interpret)
            if autotune else None
        )

        def step(**kwargs):
            # inputs stay jit arguments (not closure constants) so XLA
            # cannot constant-fold the timed computation away; the delta
            # output forces the whole pipeline.
            jitted = jax.jit(lambda t, x, yy, s: ops.tm_train_step_kernel(
                cfg, t, x, yy, s, **kwargs)[1])
            return lambda: jitted(ta, X, y, seed)

        t = _time_interleaved(
            dict(
                fused=step(use_kernel=True, interpret=interpret,
                           blocks=blocks),
                oracle=step(use_kernel=False, batch_chunk=_ORACLE_CHUNK),
            ),
            reps,
        )
        tag = f"b{B}_c{C}_l{L}_k{K}"
        blk_str = ";".join(f"{k}={v}" for k, v in sorted((blocks or {}).items()))
        rows.append((f"fusedtrain_fused_{tag}", t["fused"] * 1e6,
                     f"speedup_vs_oracle={t['oracle'] / t['fused']:.2f}x"
                     + (f";{blk_str}" if blk_str else "")))
        rows.append((f"fusedtrain_oracle_{tag}", t["oracle"] * 1e6,
                     f"pure_jnp_xla;batch_chunk={_ORACLE_CHUNK}"))
    return rows


def write_report(rows: list, path: str = "BENCH_fused_train.json") -> None:
    _, interpret = ops.kernel_dispatch(True, None)
    report = dict(
        benchmark="fused_train",
        backend=jax.default_backend(),
        interpret_mode=bool(interpret),
        jax_version=jax.__version__,
        platform=platform.platform(),
        autotune_cache=_autotune.cache_path(),
        rows=[dict(name=n, us_per_call=us, derived=d) for n, us, d in rows],
    )
    with open(path, "w") as f:
        json.dump(report, f, indent=1)
