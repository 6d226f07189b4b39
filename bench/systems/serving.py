"""The program's serving path, built as ``launch/serve.py:serve_tm`` builds
it at its defaults: a bank trained from the seed, ``compiler.compile_tm``,
an ``ops.EngineLadder`` over ``compiler.run_compiled`` engines (default
tilings, no autotune), a warm probe, and ``runtime.gateway.Gateway`` over
the ``run_rows`` runner.  After the window the answers are compared with
the plain reference (``bench/reference/tm.py``)."""

from __future__ import annotations

import asyncio
import gc
import time

import numpy as np

from bench import core, data
from bench.reference import tm as reference
from bench.systems import training


class Stack:
    """Bank, compiled artifact, engine ladder, request pool and runner."""

    def __init__(self, ctx: core.Context):
        import jax
        import jax.numpy as jnp
        from repro.core import compiler, packetizer
        from repro.kernels import ops

        self.ctx = ctx
        mix = ctx.mix
        self.bucket = int(mix["bucket"])
        self.max_wait = float(mix["max_wait_ms"]) / 1e3
        self.sz = data.sizes(ctx.cfg)

        tr = training.train_bank(ctx, ctx.cfg["bank"])
        self.ta0, self.record = tr.ta0, tr.record
        self.bank = np.asarray(tr.ta)
        t = time.perf_counter()
        art = compiler.compile_tm(tr.tmc, self.bank)
        st = art.stats
        use_kernel, _ = ops.kernel_dispatch()
        factorize = (use_kernel and st.partial_term_sharing
                     >= compiler.FACTORIZE_SHARING_THRESHOLD)
        levels = (["factorized"] if factorize else []) + (
            ["sparse", "dense"] if use_kernel else []) + ["oracle"]
        ctx.info["bank"] = dict(
            U=art.n_unique, include_sparsity=st.include_sparsity,
            partial_term_sharing=st.partial_term_sharing,
            clause_sharing=st.clause_sharing,
            words_active=st.n_words_active,
            factorize_threshold=compiler.FACTORIZE_SHARING_THRESHOLD,
            ladder=levels, compile_tm_s=time.perf_counter() - t)

        donate = (0,) if jax.default_backend() != "cpu" else ()

        def build(name):
            # launch/serve.py:build_engine with no autotune and no early
            # exit: the schedule engines build one jit per anytime quality
            # level on first use (the cells serve level 0, exact); the
            # oracle takes no donation, as there
            if name == "oracle":
                return jax.jit(lambda xw: compiler.run_compiled(
                    art, xw, engine="oracle").argmax(-1))
            if name == "dense":
                return jax.jit(lambda xw: compiler.run_compiled(
                    art, xw, engine="dense").argmax(-1), donate_argnums=donate)
            fns = {}

            def run(xw, quality=0):
                fn = fns.get(quality)
                if fn is None:
                    fn = fns[quality] = jax.jit(
                        lambda xw, q=quality: compiler.run_compiled(
                            art, xw, engine=name, quality=q).argmax(-1),
                        donate_argnums=donate)
                return fn(xw)

            run.supports_quality = True
            return run

        self.ladder = ops.EngineLadder(
            [(n, (lambda n=n: build(n))) for n in levels])

        n_pool = int(mix["pool"])
        self.x_pool, _ = data.samples(ctx.cfg, ctx.seed, 20, n_pool)
        self.xp = np.asarray(jax.jit(packetizer.pack_literals)(self.x_pool))
        t = time.perf_counter()
        # the guarded warm probe of serve_tm: compiles the serving engine
        self.ladder.run(lambda: jnp.asarray(self.xp[:self.bucket]),
                        bucket="warm", count=False)
        ctx.info["bank"]["warm_probe_s"] = time.perf_counter() - t
        self.spans = []            # (start, end, rows) of every runner call
        self.runner = ctx.wrap("runner", self._runner())

    def _runner(self):
        import jax
        import jax.numpy as jnp

        ladder, bucket, xp, spans = self.ladder, self.bucket, self.xp, self.spans
        W = xp.shape[1]
        count = iter(range(1 << 62))

        def run_rows(rows, quality=0):
            # launch/serve.py:run_rows: pad to the one jit shape, run the
            # ladder, copy out; timed to the end of the blocking copy
            i = next(count)
            t_b = time.perf_counter()
            with jax.profiler.TraceAnnotation("bench.bucket"):
                padded = np.zeros((bucket, W), xp.dtype)
                padded[:len(rows)] = rows
                out = ladder.run(lambda: jnp.asarray(padded), bucket=i,
                                 quality=quality)
                preds = np.asarray(out)[:len(rows)]
            spans.append((t_b, time.perf_counter(), len(rows)))
            return preds, dict(quality=ladder.last_quality, err_bound=None)

        return lambda tenant, rows, quality=0: run_rows(rows, quality)

    async def gateway(self):
        from repro.runtime.gateway import Gateway

        return await Gateway(self.runner, bucket=self.bucket, max_queue=None,
                             max_wait=self.max_wait, drain_timeout=5.0).start()

    async def warm(self, gw, n_buckets: int = 4):
        """A few buckets through the gateway, so that the window starts
        with every path of the process exercised."""
        n = n_buckets * self.bucket
        futs = [gw.offer("t0", self.xp[j % len(self.xp)]) for j in range(n)]
        await asyncio.gather(*futs)
        del self.spans[:]

    @staticmethod
    def counters(gw) -> dict:
        return dict(offered=gw.offered, answered=gw.answered,
                    shed=sum(gw.shed.values()), buckets=gw.buckets,
                    flushes=dict(gw.flushes))

    def health(self) -> dict:
        lad = self.ladder
        return dict(engine=lad.engine, engine_buckets=dict(lad.counts),
                    demotions=lad.demotions,
                    probe_failures=lad.probe_failures)

    def check(self, pred: np.ndarray, ok: np.ndarray, idx: np.ndarray):
        """Free the program's state, then compare every answer with the
        plain reference.  The reference trains its own bank from the same
        start, batches and seeds, so it takes nothing the program made."""
        self.ctx.info["serve"] = self.health()
        self.ladder = self.runner = None
        gc.collect()
        t = time.perf_counter()
        ta = self.ta0
        for xb, yb, seed in self.record:
            ta = reference.train_step(ta, xb, yb, seed, self.sz)
        ref_bank = np.asarray(ta)
        ref_pred = reference.predict(ta, self.x_pool, self.sz)
        wrong = int((pred[ok] != ref_pred[idx[ok]]).sum())
        self.ctx.info["reference"] = dict(
            seconds=time.perf_counter() - t, answers_compared=int(ok.sum()),
            bank_steps=len(self.record))
        return {"bank_cells_differ": (int((ref_bank != self.bank).sum()), 0),
                "answers_wrong": (wrong, 0)}
