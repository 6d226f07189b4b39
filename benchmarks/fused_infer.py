"""Fused TM inference microbenchmark (perf trajectory tracker).

Times two execution engines on identical problem shapes:

  * ``fused``    — kernels/fused_infer.py single-pass kernel (clause eval +
    class sum in one ``pallas_call``, no (B, C) fired matrix in HBM), at
    the block tiling picked by kernels/autotune.py's cached sweep
  * ``oracle``   — the pure-jnp XLA path (the off-TPU default engine)

Engines are timed interleaved (alternating calls, min over rounds) so
host noise hits all rows equally.  ``write_report`` persists the rows to
``BENCH_fused_infer.json`` so the fused-kernel perf trajectory is tracked
over time.  On a CPU the kernel runs in Pallas interpret mode; on a TPU
the same harness times the compiled kernel.
"""

from __future__ import annotations

import json
import platform
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import packetizer
from repro.kernels import autotune as _autotune
from repro.kernels import ops

# (B, C, W, K): serving bucket x clause bank x literal words x classes.
# The lead shape is a big clause bank — where a (B, C) fired matrix in HBM
# would actually cost something.
SHAPES = [
    (512, 4096, 8, 10),
    (256, 512, 16, 10),
]


def _time_interleaved(fns: dict, reps: int) -> dict:
    """min seconds per engine over `reps` alternating rounds."""
    for fn in fns.values():
        fn().block_until_ready()        # compile + warm
    best = {k: float("inf") for k in fns}
    for _ in range(reps):
        for k, fn in fns.items():
            t0 = time.perf_counter()
            fn().block_until_ready()
            best[k] = min(best[k], time.perf_counter() - t0)
    return best


def run(fast: bool = True, reps: int = 8, autotune: bool = True) -> list:
    _, interpret = ops.kernel_dispatch(True, None)
    rng = np.random.default_rng(0)
    rows = []
    for B, C, W, K in SHAPES[:1] if fast else SHAPES:
        lit = jnp.asarray(rng.integers(0, 2**32, (B, W), dtype=np.uint32))
        inc_bits = (rng.random((C, W * 32)) < 0.03).astype(np.uint8)
        inc = jnp.asarray(packetizer.pack_bits_np(inc_bits))
        votes = jnp.asarray(rng.integers(-2, 3, (C, K), dtype=np.int32))
        ne = jnp.asarray(rng.integers(0, 2, (C,), dtype=np.uint8))

        blocks = (
            _autotune.autotune_fused_blocks(B, C, W, K, interpret=interpret)
            if autotune else {}
        )

        def fwd(use_kernel, **blk):
            # inputs stay jit arguments (not closure constants) so XLA
            # cannot constant-fold the timed computation away
            jitted = jax.jit(lambda l, i, v, n: ops.tm_forward_packed(
                l, i, v, n,
                use_kernel=use_kernel, interpret=interpret, **blk,
            ))
            return lambda: jitted(lit, inc, votes, ne)

        t = _time_interleaved(
            dict(fused=fwd(True, **blocks), oracle=fwd(False)), reps)
        tag = f"b{B}_c{C}_w{W}_k{K}"
        blk_str = ";".join(f"{k}={v}" for k, v in sorted(blocks.items()))
        rows.append((f"fusedinfer_fused_{tag}", t["fused"] * 1e6,
                     f"speedup_vs_oracle={t['oracle'] / t['fused']:.2f}x"
                     + (f";{blk_str}" if blk_str else "")))
        rows.append((f"fusedinfer_oracle_{tag}", t["oracle"] * 1e6,
                     "pure_jnp_xla"))
    return rows


def write_report(rows: list, path: str = "BENCH_fused_infer.json") -> None:
    _, interpret = ops.kernel_dispatch(True, None)
    report = dict(
        benchmark="fused_infer",
        backend=jax.default_backend(),
        interpret_mode=bool(interpret),
        jax_version=jax.__version__,
        platform=platform.platform(),
        autotune_cache=_autotune.cache_path(),
        rows=[dict(name=n, us_per_call=us, derived=d) for n, us, d in rows],
    )
    with open(path, "w") as f:
        json.dump(report, f, indent=1)
