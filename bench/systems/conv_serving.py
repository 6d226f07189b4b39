"""The program's serving path for a convolutional TM (ConvCoTM), built as
``launch/serve.py:serve_tm`` builds it for ``--arch convcotm-mnist``: a
bank made from the seed, ``compiler.compile_tm``, an ``ops.EngineLadder``
over the engines ``ops.engine_levels`` picks from the artifact, a warm
probe, and ``runtime.gateway.Gateway`` over the ``run_rows`` runner
(``serving.Stack``'s).  After the window the answers, and the class sums
of one bucket, are compared with the plain reference
(``bench/reference/conv_tm.py``).

The bank, from the seed by the recipe under ``bank`` in the configuration:
clause ``j`` includes ``k`` in ``[k_min, k_max]`` true literals of one
seeded pool image at one seeded position, the share ``lit_pixel_share``
of them (rounded up) from the patch's lit pixels and the rest from its
other true literals; the weights are uniform over
``[-weight_max, weight_max]``, drawn again from the seed's stream until
no class is the reference's answer for fewer than ``class_floor`` of the
pool.  That needs the reference's clause outputs over the pool: the
reference's work, timed apart (``pool_reference_s``) and kept out of
``setup_s``.
"""

from __future__ import annotations

import gc
import time

import numpy as np

from bench import core, data
from bench.reference import conv_tm as reference
from bench.systems import serving
from repro.core.tm import ConvTMConfig


def make_bank(ctx: core.Context, x_pool):
    """``(include (C, Lp) uint8, weights (C, K) int32, info)``."""
    cfg, b = ctx.cfg, ctx.cfg["bank"]
    geom = reference.geometry(cfg)
    H, W, win = geom
    C, K = int(cfg["n_clauses"]), int(cfg["n_classes"])
    rng = np.random.default_rng([ctx.seed & 0xFFFFFFFF, ctx.seed >> 32, 21])
    n, P = x_pool.shape[0], (H - win + 1) * (W - win + 1)
    img, pos = rng.integers(0, n, C), rng.integers(0, P, C)
    lits = reference.patch_literals(
        np.asarray(x_pool[np.asarray(img)]), geom)[np.arange(C), pos]
    include = np.zeros_like(lits)
    for j in range(C):
        k = int(rng.integers(b["k_min"], b["k_max"] + 1))
        lit = np.flatnonzero(lits[j, :win * win])
        rest = np.setdiff1d(np.flatnonzero(lits[j]), lit)
        # rounded to 1e-9 first, so that 10 * 0.3 takes 3, not 4
        n_lit = min(len(lit),
                    int(np.ceil(round(k * b["lit_pixel_share"], 9))))
        include[j, rng.choice(lit, n_lit, replace=False)] = 1
        include[j, rng.choice(rest, k - n_lit, replace=False)] = 1
    t = time.perf_counter()
    fire, _ = reference.run(x_pool, include, np.zeros((C, K), np.int32), geom)
    pool_s = time.perf_counter() - t
    f = fire.astype(np.float64)          # sums stay exact in float64
    wmax = int(b["weight_max"])
    for draw in range(1, int(b["max_draws"]) + 1):
        weights = rng.integers(-wmax, wmax + 1, (C, K)).astype(np.int32)
        share = np.bincount(np.argmax(f @ weights, axis=1),
                            minlength=K) / n
        if share.min() >= b["class_floor"]:
            break
    else:
        raise SystemExit(f"bench: no weight draw in {b['max_draws']} gave "
                         f"every class {b['class_floor']} of the pool")
    per_clause = fire.mean(axis=0)
    per_image = fire.sum(axis=1)
    info = dict(
        pool_reference_s=pool_s, weight_draws=draw,
        class_share=share.round(4).tolist(),
        clause_fire_share=dict(
            mean=float(per_clause.mean()),
            **{f"q{q}": float(np.quantile(per_clause, q / 100))
               for q in (0, 10, 50, 90, 100)}),
        clauses_fired_per_image=dict(
            mean=float(per_image.mean()),
            **{f"q{q}": float(np.quantile(per_image, q / 100))
               for q in (10, 50, 90)}),
        # rows whose class sums are all 0 answer class 0 by the tie rule
        # whatever a clause does: the fewer, the more the check can see
        zero_sum_share=float(((f @ weights) == 0).all(axis=1).mean()))
    return include, weights, info


class Stack(serving.Stack):
    """Bank, compiled artifact, engine ladder, request pool and runner of a
    ConvCoTM; the runner, gateway, warm-up and counters are
    ``serving.Stack``'s."""

    def __init__(self, ctx: core.Context):
        import jax
        import jax.numpy as jnp
        from repro.core import compiler, packetizer
        from repro.kernels import ops

        self.ctx = ctx
        cfg, mix = ctx.cfg, ctx.mix
        self.bucket = int(mix["bucket"])
        self.max_wait = float(mix["max_wait_ms"]) / 1e3
        self.geom = reference.geometry(cfg)
        self.x_pool, _ = data.samples(cfg, ctx.seed, 20, int(mix["pool"]))

        t = time.perf_counter()
        self.include, self.weights, bank = make_bank(ctx, self.x_pool)
        bank["bank_s"] = time.perf_counter() - t
        self.reference_s = bank["pool_reference_s"]
        config = ConvTMConfig(
            image_h=cfg["image_h"], image_w=cfg["image_w"],
            window=cfg["window"], n_clauses=cfg["n_clauses"],
            n_classes=cfg["n_classes"])
        ta = np.where(self.include == 1, 0, -1).astype(np.int8)
        t = time.perf_counter()
        art = ctx.wrap("compile", compiler.compile_tm)(
            config, ta, weights=self.weights)
        st = art.stats
        levels = ops.engine_levels(art)
        ctx.info["bank"] = dict(
            bank, U=art.n_unique, include_sparsity=st.include_sparsity,
            positions=st.n_positions, patch_literals=st.n_patch_literals,
            weight_min=st.weight_min, weight_max=st.weight_max,
            ladder=levels, compile_tm_s=time.perf_counter() - t)

        donate = (0,) if jax.default_backend() != "cpu" else ()

        def build(name):
            # launch/serve.py:build_engine: the oracle takes no donation
            fn = lambda xw: compiler.run_compiled(art, xw,  # noqa: E731
                                                  engine=name).argmax(-1)
            return jax.jit(fn, donate_argnums=() if name == "oracle"
                           else donate)

        self.art = art
        self.ladder = ops.EngineLadder(
            [(n, (lambda n=n: build(n))) for n in levels])
        self.xp = np.asarray(jax.jit(packetizer.pack_bits)(self.x_pool))
        t = time.perf_counter()
        self.ladder.run(lambda: jnp.asarray(self.xp[:self.bucket]),
                        bucket="warm", count=False)
        ctx.info["bank"]["warm_probe_s"] = time.perf_counter() - t
        self.spans = []
        self.runner = ctx.wrap("runner", self._runner())

    def check(self, pred: np.ndarray, ok: np.ndarray, idx: np.ndarray):
        """Run one bucket of pool rows through the engine that served and
        keep its class sums; free the program's state; then compare every
        answer, and those sums, with the plain reference over the pool."""
        import jax.numpy as jnp
        from repro.core import compiler

        self.ctx.info["serve"] = self.health()
        served = np.asarray(compiler.run_compiled(
            self.art, jnp.asarray(self.xp[:self.bucket]),
            engine=self.ladder.engine))
        self.ladder = self.runner = self.art = None
        gc.collect()
        t = time.perf_counter()
        _, sums = reference.run(self.x_pool, self.include, self.weights,
                                self.geom)
        ref_pred = reference.predict(sums)
        wrong = int((pred[ok] != ref_pred[idx[ok]]).sum())
        self.ctx.info["reference"] = dict(
            seconds=time.perf_counter() - t, answers_compared=int(ok.sum()),
            sums_compared=int(served.size))
        return {"answers_wrong": (wrong, 0),
                "class_sums_differ": (
                    int((served != sums[:self.bucket]).sum()), 0)}
