"""Serving driver: batched TM inference (the paper's accelerator loop) and
LM prefill+decode.

    PYTHONPATH=src python -m repro.launch.serve --arch tm-mnist --requests 4096
    PYTHONPATH=src python -m repro.launch.serve --arch convcotm-mnist
    PYTHONPATH=src python -m repro.launch.serve --arch tinyllama-1.1b --smoke

The TM path mirrors the MATADOR runtime: train -> compile (compiler.py) ->
packetize requests -> stream through the clause-eval datapath -> argmax,
reporting throughput the way the paper's jupyter flow does.
"""

from __future__ import annotations

import argparse
import asyncio
import itertools
import signal
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation


def make_runner(ladder, bucket: int, width: int, dtype, *, on_bucket=None):
    """The runner of one gateway bucket: ``run_rows(rows, quality=0)``.

    It zero-pads ``rows`` to the one jit shape ``(bucket, width)`` (a
    partial age or drain flush never retraces), runs ``ladder`` (an
    ``ops.EngineLadder``) on it, copies the answers out, and returns
    ``(preds, info)``, ``info`` holding the quality the ladder served.
    Spans ``repro.runner.pad`` and ``repro.runner.copy_out`` time the host
    work here; the ladder adds ``copy_in``, ``dispatch`` and ``wait``.

    ``on_bucket(i)``, when given, is called before bucket ``i`` runs and
    returns a callable that takes the bucket's ``info`` once its answers
    are out and returns the ``info`` to answer with.  The serve loop's
    straggler deadline, fault drill and latency watch live there, so a
    runner built without it runs none of them.
    """
    count = itertools.count()

    def run_rows(rows, quality=0):
        i = next(count)
        after = on_bucket(i) if on_bucket is not None else None
        with TraceAnnotation("repro.runner.pad"):
            padded = np.zeros((bucket, width), dtype)
            padded[:len(rows)] = rows
        out = ladder.run(lambda: jnp.asarray(padded), bucket=i,
                         quality=quality)
        with TraceAnnotation("repro.runner.copy_out"):
            preds = np.asarray(out)[:len(rows)]
        info = dict(quality=ladder.last_quality, err_bound=None)
        return preds, (info if after is None else after(info))

    return run_rows


def serve_tm(args) -> dict:
    """Chunked streaming TM serve loop with an engine degradation ladder.

    Requests stream through fixed-size buckets of ``--bucket`` datapoints:
    one jit trace (bucket-shaped input, donated on accelerators) serves any
    request count — the last bucket is zero-padded, never retraced.  With
    the kernel path active (the TPU default; ``REPRO_USE_PALLAS=1`` on
    CPU) each bucket runs
    the schedule/fused kernels; ``--autotune`` picks block sizes via
    ``kernels/autotune.tune`` under ``--tune-policy``: ``predict`` trusts
    the analytical cost model (zero timing runs — the zoo cold-start
    mode), ``verify`` (default) wall-clocks only the model's top-3
    shortlist, ``sweep`` times the full candidate grid (and feeds the
    model's training-data sidecar).

    **Fault tolerance** — each bucket runs through an
    ``ops.EngineLadder`` (factorized -> sparse -> dense-fused -> XLA
    oracle; a ``--mesh`` engine sits on top and degrades to the unsharded
    ladder): a guarded warm probe catches kernel/lowering failures before
    the request stream starts, any per-bucket failure demotes one engine
    and retries that bucket, and ``--bucket-deadline N`` additionally
    demotes when a bucket runs longer than ``N x`` the ``StragglerMonitor``
    EWMA of bucket wall-times.  ``--promote-after N`` adds the
    re-promotion path: after N healthy buckets the ladder probes one level
    up.  The run ends with a machine-readable ``SERVE_HEALTH`` JSON line
    reporting which engine served each bucket, every demotion/promotion,
    and straggler flags.

    **Gateway** — requests flow through the resilient async gateway
    (``runtime/gateway.py``): continuous per-tenant batching with
    age-based partial flushes (``--max-wait-ms``), bounded-queue admission
    control (``--max-queue``), per-request deadlines (``--deadline-ms``),
    and graceful drain on SIGTERM (``--drain-timeout``) — every offered
    request is answered or shed with a typed reason, and the final
    ``GATEWAY_HEALTH`` JSON line proves it (``unaccounted == 0`` or the
    process exits non-zero).  ``--zoo N`` serves N round-robin tenants
    through the artifact zoo (``runtime/zoo.py``): per-tenant circuit
    breakers and an LRU-capped artifact cache.  Buckets still execute one
    at a time (a single executor thread) so failures and deadlines
    attribute to the bucket that caused them.

    **Anytime / brownout** — ``--early-exit`` serves exact buckets
    through the in-kernel certified early-exit path (bit-identical
    argmax, tiles skipped once the artifact's margin metadata proves the
    leader unassailable).  ``--brownout`` arms the gateway's
    :class:`~repro.runtime.gateway.BrownoutController`: under overload,
    buckets on the schedule engines run budgeted prefix inference at the
    controller's quality level and each degraded answer carries its
    concrete vote-margin error bound.  The dense/oracle engines (and the
    zoo/online tenant paths, whose runner protocol is exact-only) keep
    serving exact — serving better than requested is always allowed.
    ``SERVE_HEALTH``/``GATEWAY_HEALTH`` report the quality-tier
    distribution.

    Returns ``{"serve": SERVE_HEALTH, "gateway": GATEWAY_HEALTH, "preds":
    per-request predicted class in request order (-1 = shed)}``.
    """
    import json
    import os

    from repro.configs.matador_tm import TM_CONFIGS
    from repro.core import compiler, packetizer, tm, train
    from repro.data import make_boolean_classification
    from repro.kernels import ops
    from repro.runtime import StragglerMonitor, faults

    config = TM_CONFIGS[args.arch]
    conv = isinstance(config, tm.ConvTMConfig)
    if conv and (args.online or args.mesh):
        raise SystemExit(f"--arch {args.arch} is a convolutional TM: it has "
                         "no training step for --online and no sharded "
                         "engine for --mesh")
    if args.artifact and not args.artifact.endswith(".npz"):
        # np.savez_compressed appends .npz — normalize up front so the
        # load check looks for the file save() actually wrote
        args.artifact += ".npz"
    trained_this_run = False
    state = None
    if args.online and args.artifact and os.path.exists(args.artifact):
        # the online updater trains a LIVE bank next to serving; a loaded
        # artifact has no automata to train, so --online always takes the
        # train path (the artifact is rewritten at exit as usual)
        print(f"--online: training a live bank (artifact {args.artifact} "
              "will be refreshed at exit)")
    if args.artifact and os.path.exists(args.artifact) and not args.online:
        # cold-start fast path: the artifact ships its execution schedules
        # AND the tilings recorded by a previous --autotune run, so neither
        # the training loop nor the sweep is re-paid.  load() verifies
        # schema, checksum, and schedule invariants — a corrupt or stale
        # artifact is rejected here instead of serving wrong predictions.
        try:
            compiled = compiler.CompiledTM.load(args.artifact)
        except compiler.ArtifactError as e:
            raise SystemExit(f"refusing to serve: {e}")
        # a mismatched artifact would serve silently wrong predictions
        # (out-of-range word gathers clamp instead of failing).  tm-mnist
        # and convcotm-mnist share F and K, so the kind (vanilla or
        # convolutional) and a convolutional geometry must match too: the
        # ladder follows the artifact and the request rows the --arch
        want = config.geometry if conv else None
        if (compiled.n_features != config.n_features
                or compiled.n_classes != config.n_classes
                or compiled.geometry != want):
            raise SystemExit(
                f"artifact {args.artifact} was compiled for "
                f"F={compiled.n_features}/K={compiled.n_classes}/"
                f"geometry={compiled.geometry}, but --arch {args.arch} is "
                f"F={config.n_features}/K={config.n_classes}/"
                f"geometry={want}")
        print(f"loaded artifact {args.artifact} "
              f"(U={compiled.n_unique}, tuned={sorted(compiled.tuned)})")
    elif conv:
        # no ConvCoTM training step yet: a bank drawn from seeded images
        # (tm.conv_bank), every clause firing on some image
        X, _ = make_boolean_classification(
            args.n_train, config.n_features, config.n_classes, seed=0)
        ta, weights = tm.conv_bank(
            config, packetizer.pack_bits_np(X), seed=0)
        compiled = compiler.compile_tm(config, ta, weights=weights)
        trained_this_run = True
    else:
        X, y = make_boolean_classification(
            args.n_train, config.n_features, config.n_classes, seed=0
        )
        state = tm.init(config, jax.random.PRNGKey(0))
        state = train.fit(
            config, state, jnp.asarray(X), jnp.asarray(y),
            epochs=args.epochs, batch_size=64, rng=jax.random.PRNGKey(1),
        )
        compiled = compiler.compile_tm(config, state.ta_state)
        trained_this_run = True
    tuned_at_start = dict(compiled.tuned)
    print("compile stats:", compiled.stats.as_dict())
    if args.online and args.mesh:
        raise SystemExit("--online hot-swaps the unsharded engine ladder; "
                         "combine it with --mesh once the sharded builders "
                         "read the swapped artifact")
    # the serving artifact, as a mutable cell: the online updater promotes
    # a successor by updating this and rebinding the ladder (built engines
    # closed over the old artifact's schedules are discarded lazily)
    current = {"compiled": compiled}

    bucket = args.bucket
    use_kernel, interpret = ops.kernel_dispatch()
    # the ladder comes from the platform and the artifact alone
    # (ops.engine_levels): on the kernel path the chain-schedule kernels,
    # the FACTORIZED one first when the artifact's measured term sharing
    # clears the compile-time threshold (shared AND terms evaluated once
    # per bucket); a convolutional artifact has one kernel
    levels = ops.engine_levels(compiled)
    sparse, factorize = "sparse" in levels, "factorized" in levels

    def tuned_blocks(n_clauses):
        # autotune the shape the kernel ACTUALLY runs: per-shard C_loc on a
        # mesh, the whole unique bank otherwise
        if not (use_kernel and args.autotune):
            return {}
        from repro.kernels import autotune

        blocks = autotune.tune(
            "fused_infer", B=bucket, C=n_clauses,
            W=compiled.n_words_active, K=compiled.n_classes,
            interpret=interpret, policy=args.tune_policy,
        )
        print(f"autotuned dense blocks (C={n_clauses}, "
              f"policy={args.tune_policy}):", blocks)
        return blocks

    def _tuned_ctx(inc_rows):
        # recorded tunings are keyed by (bucket, swept rows, backend/mode):
        # a mesh run tunes a per-shard SLICE and an interpret-mode tiling
        # must not answer for a compiled server
        from repro.kernels import autotune

        return dict(rows=inc_rows.shape[0],
                    mode=autotune._mode_backend(interpret))

    def tuned_sparse_blocks(inc_rows):
        # the schedule tiling is swept on the rows the shard actually
        # serves, under sparse_infer: cache keys (artifact-hashed); an
        # artifact-recorded tiling (save()d by a previous run) short-
        # circuits the sweep on cold starts
        if not (use_kernel and args.autotune):
            return {}
        ctx = _tuned_ctx(inc_rows)
        recorded = compiled.tuned_blocks("sparse_infer", bucket, **ctx)
        if recorded is not None:
            print("artifact-recorded sparse blocks:", recorded)
            return recorded
        from repro.kernels import autotune

        blocks = autotune.tune(
            "sparse_infer", B=bucket, K=compiled.n_classes,
            include_words=inc_rows, interpret=interpret,
            policy=args.tune_policy, features=compiled.features or None,
        )
        if args.tune_policy != "predict":
            # measured tilings persist with the artifact; predictions are
            # re-derived in microseconds and must not masquerade as sweeps
            compiled.record_tuned("sparse_infer", bucket, blocks, **ctx)
        print(f"autotuned sparse blocks (U={inc_rows.shape[0]}, "
              f"policy={args.tune_policy}):", blocks)
        return blocks

    def tuned_factorized_blocks(inc_rows):
        # term_infer: cache keys are artifact-hashed too (the stage-1/2
        # work split is a property of the trained include structure)
        if not (use_kernel and args.autotune):
            return {}
        ctx = _tuned_ctx(inc_rows)
        recorded = compiled.tuned_blocks("term_infer", bucket, **ctx)
        if recorded is not None:
            print("artifact-recorded factorized blocks:", recorded)
            return recorded
        from repro.kernels import autotune

        blocks = autotune.tune(
            "term_infer", B=bucket, K=compiled.n_classes,
            include_words=inc_rows, interpret=interpret,
            policy=args.tune_policy, features=compiled.features or None,
        )
        if args.tune_policy != "predict":
            compiled.record_tuned("term_infer", bucket, blocks, **ctx)
        print(f"autotuned factorized blocks (U={inc_rows.shape[0]}, "
              f"policy={args.tune_policy}):", blocks)
        return blocks

    # donation recycles each bucket's literal buffer on accelerators
    donate = (0,) if jax.default_backend() != "cpu" else ()
    word_ids = jnp.asarray(compiled.word_ids)

    def build_mesh():
        # clause-sharded serve: the compiled artifact's unique-clause bank
        # splits over `model` (banks bigger than one core's VMEM), each
        # shard runs the fused kernel on its local bank — carrying its own
        # block-sparse tile table on the sparse path — and one (B, K)
        # class-sum psum completes the adder bank; requests shard over the
        # data axes.
        from jax.sharding import NamedSharding, PartitionSpec as P

        from repro.core import sharding as tm_sharding
        from repro.launch.mesh import parse_mesh_spec

        mesh = parse_mesh_spec(args.mesh)
        n_model = mesh.shape["model"]

        def place(stack):
            # each shard's stack slice lands on its own device, once
            return jax.device_put(stack, NamedSharding(
                mesh, P("model", *[None] * (np.ndim(stack) - 1))))
        U = compiled.n_unique
        if args.autotune:
            # ROADMAP "Next": seed the per-shard C_loc cache entries for
            # ALL kernels so later mesh runs skip the sweeps
            tuned_blocks(-(-U // n_model))
        if factorize:
            from repro.kernels import sparse_infer, term_infer

            C_loc_est = sparse_infer._rup(-(-max(U, 1) // n_model), 8)
            fblocks = tuned_factorized_blocks(
                np.ascontiguousarray(compiled.include_words[:C_loc_est]))
            schedules, term_stack, chain_stack, votes_stack, tile_stack, \
                C_loc = term_infer.stack_shard_factorized(
                    compiled.include_words, compiled.votes, n_model,
                    block_c=fblocks.get(
                        "block_c", term_infer.DEFAULT_BLOCK_C),
                    block_j=fblocks.get(
                        "block_j", term_infer.DEFAULT_BLOCK_J),
                    block_t=fblocks.get(
                        "block_t", term_infer.DEFAULT_BLOCK_T),
                    term_w=fblocks.get("term_w"),
                )
            fwd = tm_sharding.sharded_factorized_forward_fn(
                mesh,
                block_t=schedules[0].block_t,
                block_c=schedules[0].block_c, block_j=schedules[0].block_j,
                block_s=fblocks.get("block_s"),
            )
            terms_sh, chains, votes_sh, tiles = map(
                place, (term_stack, chain_stack, votes_stack, tile_stack))
            print(f"mesh {dict(mesh.shape)}: {C_loc * n_model} unique "
                  f"clauses sharded over model={n_model} ({C_loc}/shard, "
                  f"{tile_stack.shape[-1]} tiles/shard, "
                  f"{term_stack.shape[1]} term rows/shard)")
            run_bucket = jax.jit(
                lambda xw: fwd(terms_sh, chains, votes_sh, tiles,
                               xw[:, word_ids]).argmax(-1),
                donate_argnums=donate,
            )
        elif sparse:
            from repro.kernels import sparse_infer

            C_loc_est = sparse_infer._rup(-(-max(U, 1) // n_model), 8)
            sblocks = tuned_sparse_blocks(
                np.ascontiguousarray(compiled.include_words[:C_loc_est]))
            schedules, chain_stack, votes_stack, tile_stack, C_loc = (
                sparse_infer.stack_shard_schedules(
                    compiled.include_words, compiled.votes, n_model,
                    block_c=sblocks.get(
                        "block_c", sparse_infer.DEFAULT_BLOCK_C),
                    block_j=sblocks.get(
                        "block_j", sparse_infer.DEFAULT_BLOCK_J),
                ))
            fwd = tm_sharding.sharded_schedule_forward_fn(
                mesh,
                block_c=schedules[0].block_c, block_j=schedules[0].block_j,
                block_s=sblocks.get("block_s"),
            )
            chains, votes_sh, tiles = map(
                place, (chain_stack, votes_stack, tile_stack))
            print(f"mesh {dict(mesh.shape)}: {C_loc * n_model} unique "
                  f"clauses sharded over model={n_model} ({C_loc}/shard, "
                  f"{tile_stack.shape[-1]} chain tiles/shard)")
            run_bucket = jax.jit(
                lambda xw: fwd(chains, votes_sh, tiles,
                               xw[:, word_ids]).argmax(-1),
                donate_argnums=donate,
            )
        else:
            Up = -(-U // n_model) * n_model
            blocks = tuned_blocks(Up // n_model)
            # zero include words never violate -> padded clauses fire but
            # carry zero votes, so the class sums are unchanged.
            inc_sh = place(np.pad(compiled.include_words,
                                  ((0, Up - U), (0, 0))))
            votes_sh = place(np.pad(compiled.votes, ((0, Up - U), (0, 0))))
            ne_sh = place(np.ones((Up,), np.uint8))
            fwd = tm_sharding.sharded_forward_fn(mesh, blocks=blocks or None)
            print(f"mesh {dict(mesh.shape)}: {Up} unique clauses sharded "
                  f"over model={n_model} ({Up // n_model}/shard)")

            # same jit + donation shape as the unsharded path: the
            # dead-word slice and argmax fuse into one dispatch per bucket
            run_bucket = jax.jit(
                lambda xw: fwd(inc_sh, votes_sh, ne_sh,
                               xw[:, word_ids]).argmax(-1),
                donate_argnums=donate,
            )
        return run_bucket

    # anytime serving state: per-engine {level: err_bound} tables (filled
    # when a schedule engine is built) and the served-tier histogram
    ee0 = bool(args.early_exit or args.brownout)
    quality_bounds = {}
    quality_served = {}

    def _quality_engine(art, engine, blocks, tiling_keys):
        # one jit trace per (engine, quality): level 0 is the full
        # schedule (early-exit kernel when armed), level q > 0 slices the
        # tile table to the artifact's margin-certified prefix.  Traces
        # build lazily — a server that never browns out pays only q=0.
        tiling = {k: v for k, v in blocks.items() if k in tiling_keys}
        quality_bounds[engine] = {
            q["level"]: q["bound"]
            for q in art.quality_levels(engine=engine, **tiling)}
        fns = {}

        def make(q):
            return jax.jit(
                lambda xw: compiler.run_compiled(
                    art, xw, engine=engine, quality=q,
                    early_exit=ee0 and q == 0, **blocks).argmax(-1),
                donate_argnums=donate)

        def run(xw, quality=0):
            q = min(int(quality), max(quality_bounds[engine], default=0))
            fn = fns.get(q)
            if fn is None:
                fn = fns[q] = make(q)
            return fn(xw)

        run.supports_quality = True
        return run

    def build_engine(name):
        # lazy per-level builders: engines the ladder never reaches pay
        # neither their jit trace nor their autotune sweep.  The serving
        # artifact is read from the `current` cell at BUILD time, so a
        # ladder.rebind() after an online hot-swap rebuilds against the
        # promoted artifact.
        art = current["compiled"]
        if name.startswith("mesh"):
            return build_mesh()
        if name == "factorized":
            blocks = tuned_factorized_blocks(art.include_words)
            return _quality_engine(
                art, "factorized", blocks,
                ("block_c", "block_j", "block_t", "term_w"))
        if name == "sparse":
            blocks = tuned_sparse_blocks(art.include_words)
            return _quality_engine(
                art, "sparse", blocks, ("block_c", "block_j"))
        if name == "conv":
            return jax.jit(
                lambda xw: compiler.run_compiled(
                    art, xw, engine="conv").argmax(-1),
                donate_argnums=donate)
        if name == "dense":
            blocks = tuned_blocks(art.n_unique)
            return jax.jit(
                lambda xw: compiler.run_compiled(
                    art, xw, engine="dense", **blocks).argmax(-1),
                donate_argnums=donate)
        # bottom of the ladder: pure-XLA oracle — no Pallas lowering, no
        # donation, so it survives whatever failure killed the kernels
        assert name == "oracle", name
        return jax.jit(
            lambda xw: compiler.run_compiled(
                art, xw, engine="oracle").argmax(-1))

    if args.mesh:
        # the sharded engine degrades to the unsharded ladder: a mesh-only
        # failure (bad spec, per-shard lowering) still serves every bucket
        levels.insert(0, f"mesh-{levels[0]}")
    ladder = ops.EngineLadder(
        [(name, (lambda n=name: build_engine(n))) for name in levels],
        promote_after=args.promote_after)

    Xr, yr = make_boolean_classification(
        args.requests, config.n_features, config.n_classes, seed=2
    )
    # --online: the request stream's labels double as the labeled feedback
    # stream (serve.py's stand-in for a production label joiner)
    # a request row: packed literals, or a convolutional TM's packed image
    xp = (packetizer.pack_bits_np(Xr) if conv
          else np.asarray(packetizer.pack_literals(jnp.asarray(Xr))))
    n, W = xp.shape

    mon = StragglerMonitor(threshold=args.bucket_deadline or 2.0, warmup=2)
    # guarded warm probe: kernel/lowering failures surface here (one trace
    # per attempted engine, demoting through the ladder), so the request
    # stream starts on an engine that actually runs
    ladder.run(lambda: jnp.asarray(xp[:bucket]), bucket="warm", count=False)

    online_hooks = {"latency": None}   # filled when --online wires the updater

    def on_bucket(i):
        # the straggler/deadline accounting of the old sync loop around
        # one gateway bucket.  ``quality`` is the brownout controller's
        # level; only engines that opt in (supports_quality) ever degrade,
        # and the info records what was ACTUALLY served plus its bound.
        t_b = time.perf_counter()
        mon.start_step()
        faults.sleep_if("serve.slow_bucket", step=i)    # deadline drill site

        def after(info):
            q = info["quality"]
            quality_served[q] = quality_served.get(q, 0) + 1
            info["err_bound"] = (quality_bounds.get(ladder.engine, {}).get(q)
                                 if q else None)
            flag = mon.end_step(i)
            # an engine's FIRST bucket pays its jit trace — exempting it
            # from the deadline stops one slow bucket cascading down
            if (flag and args.bucket_deadline
                    and ladder.counts[ladder.engine] > 1):
                ladder.demote(
                    f"bucket deadline: {flag['seconds'] * 1e3:.1f} ms > "
                    f"{args.bucket_deadline:g}x EWMA "
                    f"{flag['ewma'] * 1e3:.1f} ms", bucket=i)
            if online_hooks["latency"] is not None:
                # post-swap latency watch: a promoted artifact that blows
                # up bucket wall-time gets rolled back by the updater
                online_hooks["latency"](time.perf_counter() - t_b)
            return info

        return after

    run_rows = make_runner(ladder, bucket, W, xp.dtype, on_bucket=on_bucket)

    zoo = None
    updater = None
    if args.online:
        # online mode always routes through the zoo (one tenant unless
        # --zoo): the updater's atomic hot-swap IS a zoo operation, and
        # every bucket leases the entry it answers with, so in-flight
        # buckets finish on the version they started on
        from repro.runtime import online as online_mod
        from repro.runtime.zoo import ArtifactZoo

        def _nbytes(c):
            return int(c.include_words.nbytes + c.word_ids.nbytes
                       + c.votes.nbytes)

        def make_obj(c):
            # the zoo entry pairs the artifact with the shared ladder
            # runner: leases pin the object (and thus its version); the
            # ladder itself is rebound on promote via on_promote below
            return {"compiled": c, "run": run_rows}, _nbytes(c)

        zoo = ArtifactZoo(lambda tenant: make_obj(current["compiled"]),
                          max_entries=max(args.zoo or 1, 1))
        runner = zoo.runner(lambda obj, rows: obj["run"](rows))

        def canary_serve(obj, rows):
            # candidate side of the shadow canary: a standalone XLA-oracle
            # runner per artifact (bit-identical predictions to every
            # ladder engine), padded to the live trace shape so the
            # candidate's jit warm-up happens HERE, not on its first
            # post-swap bucket
            fn = obj.get("_canary_fn")
            if fn is None:
                c = obj["compiled"]
                fn = obj["_canary_fn"] = jax.jit(
                    lambda xw: compiler.run_compiled(
                        c, xw, engine="oracle").argmax(-1))
            padded = np.zeros((bucket, W), xp.dtype)
            padded[:len(rows)] = rows
            return np.asarray(fn(jnp.asarray(padded)))[:len(rows)]

        def on_promote(cand):
            current["compiled"] = cand
            ladder.rebind(
                [(nm, (lambda n2=nm: build_engine(n2))) for nm in levels])
            print(f"online: promoted artifact live (U={cand.n_unique}); "
                  "engine ladder rebound")

        ckpt_manager = None
        if args.online_ckpt_dir:
            from repro.checkpoint.store import CheckpointManager

            ckpt_manager = CheckpointManager(args.online_ckpt_dir)
        updater = online_mod.OnlineUpdater(
            config, state.ta_state, compiled,
            cfg=online_mod.OnlineConfig(
                drift_threshold=args.drift_threshold,
                canary_frac=args.canary_frac,
                swap_policy=args.swap_policy),
            zoo=zoo, tenant="t0", make_obj=make_obj, serve_fn=canary_serve,
            deployed_obj={"compiled": compiled, "run": run_rows},
            deployed_nbytes=_nbytes(compiled),
            ckpt_manager=ckpt_manager, on_promote=on_promote)
        online_hooks["latency"] = updater.record_bucket_latency
    elif args.zoo:
        # multi-tenant mode: requests round-robin over --zoo tenants that
        # share the compiled engines but carry per-tenant circuit breakers;
        # max_entries < tenants keeps the LRU churning under real pressure
        from repro.runtime.zoo import ArtifactZoo

        nbytes = int(compiled.include_words.nbytes + compiled.votes.nbytes)
        zoo = ArtifactZoo(lambda tenant: (tenant, nbytes),
                          max_entries=max(args.zoo - 1, 1))
        runner = zoo.runner(lambda obj, rows: run_rows(rows))
    else:
        # the single-tenant runner is quality-aware (the zoo runner
        # protocol is exact-only: leases/breakers wrap a plain
        # run(tenant, rows), so multi-tenant brownout would need a
        # protocol bump — those paths serve exact under pressure)
        runner = lambda tenant, rows, quality=0: run_rows(rows, quality)

    def tenant_of(j):
        return f"t{j % args.zoo}" if args.zoo else "t0"

    async def stream():
        from repro.runtime.gateway import BrownoutController, Gateway

        gw = await Gateway(
            runner, bucket=bucket, max_queue=args.max_queue or None,
            max_wait=args.max_wait_ms / 1e3,
            drain_timeout=args.drain_timeout,
            mirror=updater.mirror if updater is not None else None,
            brownout=BrownoutController() if args.brownout else None,
        ).start()
        loop = asyncio.get_running_loop()
        stop = asyncio.Event()
        try:
            # graceful drain: SIGTERM stops admission, flushes what fits
            # in the drain window, typed-sheds the rest, exits 0
            loop.add_signal_handler(signal.SIGTERM, stop.set)
        except (NotImplementedError, RuntimeError):
            pass
        stop_online = threading.Event()
        online_thread = None
        if updater is not None:
            # the updater's own thread: ingest labeled feedback in batch-
            # sized slices and train/drift-check between gateway buckets
            feed = iter(range(n))

            def online_loop():
                while not stop_online.is_set():
                    progressed = False
                    for _ in range(updater.cfg.batch_size):
                        j = next(feed, None)
                        if j is None:
                            break
                        updater.ingest(Xr[j], int(yr[j]))
                        progressed = True
                    progressed = updater.step() or progressed
                    if not progressed:
                        time.sleep(0.002)

            online_thread = threading.Thread(
                target=online_loop, name="online-updater", daemon=True)
            online_thread.start()
        deadline = args.deadline_ms / 1e3 if args.deadline_ms else None
        futs = [gw.offer(tenant_of(j), xp[j], deadline=deadline)
                for j in range(n)]
        answered = asyncio.ensure_future(asyncio.gather(*futs))
        sigterm = asyncio.ensure_future(stop.wait())
        await asyncio.wait({answered, sigterm},
                           return_when=asyncio.FIRST_COMPLETED)
        health = await gw.drain()
        if online_thread is not None:
            stop_online.set()
            online_thread.join(timeout=10)
        if updater is not None and stop.is_set():
            # SIGTERM: after the gateway drains, flush the pending feedback
            # queue through the PR-6 checkpoint path — a restarted updater
            # resumes the bank and re-ingests every drained record
            ck_step = updater.drain()
            if ck_step is not None:
                print(f"online: feedback queue drained to checkpoint "
                      f"step {ck_step}")
        sigterm.cancel()
        return await answered, health, stop.is_set()

    t0 = time.perf_counter()
    responses, gw_health, sigtermed = asyncio.run(stream())
    dt = time.perf_counter() - t0
    if sigtermed:
        print("SIGTERM: gateway drained "
              f"({gw_health['answered']}/{gw_health['offered']} answered, "
              f"{gw_health['shed_total']} typed-shed)")
    if args.artifact and (trained_this_run
                          or compiled.tuned != tuned_at_start):
        # persist schedules + newly recorded tunings for cold starts; a
        # pure load with nothing new recorded skips the multi-MB rewrite.
        # Saved AFTER the stream so tilings recorded lazily by ladder
        # builders (when an engine first actually runs) persist too.
        # Under --online this is the PROMOTED artifact, not the boot one.
        current["compiled"].save(args.artifact)
        print(f"saved artifact (schedules + tuned tilings) to {args.artifact}")
    engine_labels = {"factorized": "factorized-schedule",
                     "sparse": "sparse-schedule",
                     "dense": "fused-kernel", "conv": "conv-kernel",
                     "oracle": "oracle"}
    eng = ladder.engine
    label = (f"clause-sharded {engine_labels[eng[len('mesh-'):]]} "
             f"({args.mesh})" if eng.startswith("mesh-")
             else engine_labels[eng])
    n_answered = gw_health["answered"]
    n_buckets = gw_health["buckets"]
    print(f"{n_answered} inferences in {n_buckets} buckets of {bucket} "
          f"[{label}] in {dt * 1e3:.2f} ms ({max(n_answered, 1) / dt:,.0f} "
          f"inf/s, {dt / max(n_answered, 1) * 1e6:.2f} us/inf)")
    health = dict(
        requests=n, buckets=n_buckets, bucket_size=bucket,
        ladder=levels, final_engine=ladder.engine,
        engine_buckets=ladder.counts, demotions=ladder.demotions,
        promotions=ladder.promotions, probe_failures=ladder.probe_failures,
        stragglers=mon.events,
        early_exit=ee0, brownout=bool(args.brownout),
        quality_tiers={str(k): v
                       for k, v in sorted(quality_served.items())},
    )
    print("SERVE_HEALTH " + json.dumps(health))
    if zoo is not None:
        gw_health["zoo"] = zoo.health()
    print("GATEWAY_HEALTH " + json.dumps(gw_health))
    if updater is not None:
        print("ONLINE_HEALTH " + json.dumps(updater.health()))
    if gw_health["unaccounted"]:
        raise SystemExit(
            f"gateway accounting violated: {gw_health['unaccounted']} "
            f"of {gw_health['offered']} requests unaccounted for")
    preds = np.asarray([r.pred if r.ok else -1 for r in responses], np.int64)
    hist = np.bincount(preds[preds >= 0], minlength=config.n_classes)
    print("pred class histogram:", hist.tolist())
    return dict(serve=health, gateway=gw_health, preds=preds)


def serve_lm(args) -> None:
    from repro.configs import get_config, get_smoke_config
    from repro.models import steps, transformer

    cfg = (get_smoke_config if args.smoke else get_config)(args.arch)
    params = transformer.init_params(cfg, jax.random.PRNGKey(0))
    B, S_max = args.batch_size, args.seq_len
    caches = transformer.init_caches(cfg, B, S_max)
    prefill = jax.jit(steps.make_prefill_step(cfg))
    decode = jax.jit(steps.make_decode_step(cfg))

    nprng = np.random.default_rng(0)
    prompt_len = S_max // 2
    if cfg.frontend == "audio_stub":
        batch = {"embeds": jnp.asarray(
            nprng.normal(size=(B, prompt_len, cfg.d_model)), jnp.float32)}
        mk_inp = lambda tok: {"embeds": jnp.zeros((B, 1, cfg.d_model), jnp.float32)}
    else:
        batch = {"tokens": jnp.asarray(
            nprng.integers(0, cfg.vocab_size, (B, prompt_len)), jnp.int32)}
        mk_inp = lambda tok: {"tokens": tok}

    t0 = time.perf_counter()
    logits, caches = prefill(params, batch, caches)
    logits.block_until_ready()
    t_prefill = time.perf_counter() - t0
    tok = jnp.argmax(logits[:, : cfg.vocab_size], -1).astype(jnp.int32)[:, None]

    n_new = args.new_tokens
    t0 = time.perf_counter()
    for i in range(n_new):
        logits, caches = decode(params, caches, mk_inp(tok), jnp.int32(prompt_len + i))
        tok = jnp.argmax(logits[:, : cfg.vocab_size], -1).astype(jnp.int32)[:, None]
    tok.block_until_ready()
    t_decode = time.perf_counter() - t0
    print(f"prefill {prompt_len} tok x {B}: {t_prefill * 1e3:.1f} ms; "
          f"decode {n_new} steps: {t_decode / n_new * 1e3:.2f} ms/step "
          f"({B * n_new / t_decode:,.0f} tok/s)")


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", required=True)
    ap.add_argument("--requests", type=int, default=4096)
    ap.add_argument("--bucket", type=int, default=512,
                    help="TM streaming bucket size (one jit trace per run)")
    ap.add_argument("--autotune", action="store_true",
                    help="autotune fused-kernel block sizes for the bucket shape")
    ap.add_argument("--tune-policy", default="verify",
                    choices=("predict", "verify", "sweep"),
                    help="TM --autotune mode: 'predict' trusts the "
                         "analytical cost model (zero timing runs), "
                         "'verify' (default) wall-clocks only the model's "
                         "top-3 shortlist, 'sweep' times every candidate "
                         "and feeds the model's training-data sidecar")
    ap.add_argument("--bucket-deadline", type=float, default=None,
                    help="TM: demote the serving engine when a bucket runs "
                         "longer than this multiple of the EWMA of bucket "
                         "wall-times (soft per-bucket deadline)")
    ap.add_argument("--promote-after", type=int, default=None,
                    help="TM: probe the engine one ladder level up after "
                         "this many consecutive healthy buckets (failed "
                         "probes double the cooldown); default: demote-only")
    ap.add_argument("--max-queue", type=int, default=None,
                    help="TM gateway: bound the pending-request queue — a "
                         "full queue sheds new requests with the typed "
                         "reason queue_full (default: unbounded)")
    ap.add_argument("--max-wait-ms", type=float, default=20.0,
                    help="TM gateway: flush a partial bucket once its "
                         "oldest request has waited this long (age-based "
                         "continuous batching)")
    ap.add_argument("--deadline-ms", type=float, default=None,
                    help="TM gateway: per-request deadline — a request "
                         "still queued past it is shed deadline_expired, "
                         "never executed (default: none)")
    ap.add_argument("--drain-timeout", type=float, default=5.0,
                    help="TM gateway: seconds the SIGTERM/end-of-stream "
                         "drain may spend flushing before shedding the "
                         "remainder drain_timeout")
    ap.add_argument("--early-exit", action="store_true",
                    help="TM: serve exact buckets through the in-kernel "
                         "certified early-exit path (bit-identical argmax; "
                         "tiles skipped once the artifact's anytime margin "
                         "metadata proves the leader unassailable)")
    ap.add_argument("--brownout", action="store_true",
                    help="TM gateway: degrade answer QUALITY instead of "
                         "shedding under overload — a hysteresis "
                         "controller maps queue depth / bucket age / "
                         "deadline pressure to an anytime quality level; "
                         "degraded answers carry a concrete vote-margin "
                         "error bound (implies --early-exit for exact "
                         "buckets)")
    ap.add_argument("--zoo", type=int, default=None,
                    help="TM gateway: serve this many round-robin tenants "
                         "through the artifact zoo (per-tenant circuit "
                         "breakers, LRU-capped cache) instead of one")
    ap.add_argument("--online", action="store_true",
                    help="TM: run the online-learning updater beside "
                         "serving — stream labeled feedback into a live "
                         "automata bank, rebuild on include-bit drift, "
                         "shadow-canary the candidate on mirrored buckets, "
                         "and hot-swap it atomically through the artifact "
                         "zoo (zero dropped requests)")
    ap.add_argument("--drift-threshold", type=float, default=0.05,
                    help="TM --online: include-bit drift fraction (live "
                         "bank vs the deployed artifact's bank) that arms "
                         "an incremental recompile")
    ap.add_argument("--canary-frac", type=float, default=0.25,
                    help="TM --online: fraction of live buckets the "
                         "gateway mirrors to the candidate during the "
                         "shadow canary")
    ap.add_argument("--swap-policy", default="canary",
                    choices=("canary", "immediate"),
                    help="TM --online: 'canary' (default) shadow-validates "
                         "the candidate on mirrored traffic before the "
                         "atomic swap; 'immediate' promotes as soon as the "
                         "integrity envelope passes")
    ap.add_argument("--online-ckpt-dir", default=None,
                    help="TM --online: checkpoint directory the SIGTERM "
                         "drain writes the live bank + pending feedback "
                         "through (a restart resumes from it)")
    ap.add_argument("--artifact", default=None,
                    help="TM: compiled-artifact .npz path — loaded instead "
                         "of train+compile when it exists, (re)saved with "
                         "schedules + autotuned tilings after serving")
    ap.add_argument("--mesh", default=None,
                    help="TM: mesh spec, e.g. 'model=4' — shard the compiled "
                         "clause bank over the mesh (fused kernel per shard, "
                         "one class-sum psum); on CPU export XLA_FLAGS="
                         "--xla_force_host_platform_device_count=N first")
    ap.add_argument("--epochs", type=int, default=3)
    ap.add_argument("--n-train", type=int, default=2000)
    ap.add_argument("--batch-size", type=int, default=4)
    ap.add_argument("--seq-len", type=int, default=64)
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--smoke", action="store_true")
    return ap


def main() -> None:
    from repro.launch.compile_cache import enable_compile_cache

    args = build_parser().parse_args()
    enable_compile_cache()
    from repro.configs.matador_tm import TM_CONFIGS

    if args.arch in TM_CONFIGS:
        serve_tm(args)
    else:
        serve_lm(args)


if __name__ == "__main__":
    main()
