"""Training launcher: TM (the paper's flow) and LM archs, fault-tolerant.

    PYTHONPATH=src python -m repro.launch.train --arch tm-mnist \
        --steps 200 --batch-size 64 --ckpt-dir /tmp/ckpt
    PYTHONPATH=src python -m repro.launch.train --arch tinyllama-1.1b \
        --smoke --steps 10

The loop wires together every production substrate in this repo: sharded
step functions, the prefetching loader, async atomic checkpoints with
restart-resume, preemption handling, and the straggler monitor.  ``--smoke``
swaps in the reduced config so the same driver runs on one CPU.
"""

from __future__ import annotations

import argparse
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import CheckpointManager
from repro.data import ShardedBatcher, make_boolean_classification, paper_dataset
from repro.runtime import (RESUME_EXIT_CODE, PreemptionHandler,
                           StragglerMonitor, faults)


def train_tm(args) -> dict:
    """TM training loop; returns ``{"health": TRAIN_HEALTH, "ta": the
    final automata}``."""
    from repro.configs.matador_tm import TM_CONFIGS
    from repro.core import tm
    from repro.kernels import ops

    config = TM_CONFIGS[args.arch]
    name = args.arch.replace("tm-", "")
    if name in ("mnist", "kmnist", "fmnist", "cifar2", "kws6"):
        X, y, Xte, yte = paper_dataset(name, n_train=args.n_train)
    else:
        X, y = make_boolean_classification(
            args.n_train, config.n_features, config.n_classes, seed=0
        )
        Xte, yte = make_boolean_classification(
            1000, config.n_features, config.n_classes, seed=1
        )

    mgr = CheckpointManager(args.ckpt_dir) if args.ckpt_dir else None
    state = tm.init(config, jax.random.PRNGKey(args.seed))
    start_step = 0
    loader = ShardedBatcher((X, y), args.batch_size, seed=args.seed)
    if mgr and mgr.latest_step() is not None:
        restored, extra = mgr.restore({"ta": state.ta_state})
        state = tm.TMState(ta_state=restored["ta"], steps=jnp.int32(extra["step"]))
        loader.load_state_dict(extra["loader"])
        start_step = extra["step"]
        print(f"resumed from step {start_step}")

    # chains to any handler the host process already registered and is
    # uninstalled in the finally below, so embedding this loop in a
    # serving process never clobbers the gateway's SIGTERM drain
    pre = PreemptionHandler().install()
    mon = StragglerMonitor()
    ta = state.ta_state
    it = iter(loader)
    # on the kernel path the step is the fused training pipeline: two
    # pallas launches per step, no (B, C) fire/ftype HBM round-trips.
    # --autotune resolves (and caches) the fused block tilings on first use.
    step_kw = dict(batch_chunk=args.batch_chunk, autotune=args.autotune)
    sharded_step = None
    if args.mesh:
        # clause-sharded shard_map schedule: automata over `model`, batch
        # over the data axes, fused kernels per shard — bit-identical to
        # the single-device step (sharding.py engine="kernel").
        from repro.core import sharding as tm_sharding
        from repro.launch.mesh import parse_mesh_spec

        mesh = parse_mesh_spec(args.mesh)
        if config.n_clauses_total % mesh.shape["model"]:
            raise SystemExit(
                f"clause axis ({config.n_clauses_total}) not divisible by "
                f"mesh model={mesh.shape['model']}; pick a divisor (configs "
                "pad via clause_pad_multiple)")
        blocks = None
        if args.autotune:
            # autotune the PER-SHARD shapes (C_loc clauses, B_loc samples)
            # outside the shard_map trace and pin them via `blocks`
            uk, interp = ops.kernel_dispatch()
            if uk:
                from repro.core import packetizer
                from repro.kernels import autotune as _autotune

                d_size = 1
                for ax in ("pod", "data"):
                    d_size *= mesh.shape.get(ax, 1)
                C_loc = config.n_clauses_total // mesh.shape["model"]
                B_loc = max(1, args.batch_size // d_size)
                if args.batch_chunk and B_loc > args.batch_chunk:
                    B_loc = args.batch_chunk
                blocks = _autotune.autotune_fused_train_blocks(
                    B_loc, C_loc, packetizer.n_words(config.n_literals),
                    config.n_literals, config.n_classes, interpret=interp)
                print("autotuned sharded blocks:", blocks)
            else:
                print("--autotune ignored: the kernel path is inactive "
                      "(a TPU, or REPRO_USE_PALLAS=1)")
        sharded_step = tm_sharding.sharded_train_step_fn(
            config, mesh, batch_chunk=args.batch_chunk, engine="kernel",
            blocks=blocks,
        )
        print(f"mesh {dict(mesh.shape)}: clause axis sharded over "
              f"model={mesh.shape['model']}")
    try:
        for step in range(start_step, args.steps):
            mon.start_step()
            xb, yb = next(it)
            if sharded_step is not None:
                ta = sharded_step(ta, jnp.asarray(xb), jnp.asarray(yb),
                                  jnp.uint32(step))
            else:
                ta, _ = ops.tm_train_step_kernel(
                    config, ta, jnp.asarray(xb), jnp.asarray(yb),
                    jnp.uint32(step), **step_kw,
                )
            faults.sleep_if("train.slow_step", step=step)  # straggler drill
            flag = mon.end_step(step)
            if flag:
                print(f"straggler flagged: {flag}")
            if mgr and (step + 1) % args.ckpt_every == 0:
                mgr.save(step + 1, {"ta": ta},
                         extra={"step": step + 1,
                                "loader": loader.state_dict()},
                         blocking=False)
            faults.sigterm_if("train.sigterm", step=step)  # preemption drill
            if pre.preempted:
                # checkpoint (when durable storage is configured) and exit
                # with the dedicated code the launcher restarts on — even
                # without a --ckpt-dir the exit code must still say
                # "resume me", not crash
                print("preempted: checkpointing and exiting for restart "
                      f"(exit code {RESUME_EXIT_CODE})")
                pre.checkpoint_and_exit(
                    (lambda: mgr.save(
                        step + 1, {"ta": ta},
                        extra={"step": step + 1,
                               "loader": loader.state_dict()}))
                    if mgr else (lambda: None))
            if (step + 1) % args.log_every == 0:
                st = tm.TMState(ta_state=ta, steps=jnp.int32(step))
                acc = float(tm.accuracy(
                    config, st, jnp.asarray(Xte), jnp.asarray(yte)))
                inc = float((np.asarray(ta) >= 0).mean())
                print(f"step {step + 1}: test_acc={acc:.4f} "
                      f"include_frac={inc:.4f}")
    finally:
        pre.uninstall()
    if mgr:
        mgr.save(args.steps, {"ta": ta},
                 extra={"step": args.steps, "loader": loader.state_dict()})
        mgr.wait()
    import json as _json

    health = dict(steps=args.steps, resumed_from=start_step,
                  stragglers=mon.events)
    print("TRAIN_HEALTH " + _json.dumps(health))
    return dict(health=health, ta=ta)


def train_lm(args) -> None:
    from repro.configs import get_config, get_smoke_config
    from repro.models import steps as lm_steps, transformer
    from repro.optim import adamw

    cfg = (get_smoke_config if args.smoke else get_config)(args.arch)
    rng = jax.random.PRNGKey(args.seed)
    params = transformer.init_params(cfg, rng)
    opt = adamw.adamw_init(params)
    step_fn = jax.jit(lm_steps.make_train_step(cfg))

    B, S = args.batch_size, args.seq_len
    nprng = np.random.default_rng(args.seed)
    mgr = CheckpointManager(args.ckpt_dir) if args.ckpt_dir else None
    mon = StragglerMonitor()
    for step in range(args.steps):
        mon.start_step()
        tokens = nprng.integers(0, cfg.vocab_size, (B, S + 1))
        batch = {
            "tokens": jnp.asarray(tokens[:, :-1], jnp.int32),
            "labels": jnp.asarray(tokens[:, 1:], jnp.int32),
        }
        if cfg.frontend == "audio_stub":
            batch = {
                "embeds": jnp.asarray(
                    nprng.normal(size=(B, S, cfg.d_model)), jnp.float32
                ),
                "labels": jnp.asarray(
                    nprng.integers(0, cfg.vocab_size, (B, S, cfg.n_codebooks)),
                    jnp.int32,
                ),
            }
        elif cfg.frontend == "vision_stub":
            si = S // 4
            batch = {
                "embeds": jnp.asarray(
                    nprng.normal(size=(B, si, cfg.d_model)), jnp.float32
                ),
                "tokens": jnp.asarray(tokens[:, : S - si], jnp.int32),
                "labels": jnp.asarray(tokens[:, 1 : S - si + 1], jnp.int32),
            }
        params, opt, info = step_fn(params, opt, batch)
        mon.end_step(step)
        print(f"step {step + 1}: loss={float(info['loss']):.4f} "
              f"gnorm={float(info['grad_norm']):.3f}")
        if mgr and (step + 1) % args.ckpt_every == 0:
            mgr.save(step + 1, {"params": params}, extra={"step": step + 1},
                     blocking=False)
    if mgr:
        mgr.wait()


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch-size", type=int, default=64)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--n-train", type=int, default=4000)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch-chunk", type=int, default=None,
                    help="TM: scan the batch in slices of this size "
                         "(O(chunk) working set; ragged tails are padded "
                         "and masked, results stay bit-identical)")
    ap.add_argument("--autotune", action="store_true",
                    help="TM: pick fused-kernel block tilings from the "
                         "cached autotuner sweep")
    ap.add_argument("--mesh", default=None,
                    help="TM: mesh spec, e.g. 'model=4' or 'data=2,model=4' "
                         "— clause-sharded shard_map training step (on CPU "
                         "export XLA_FLAGS=--xla_force_host_platform_"
                         "device_count=N first)")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--log-every", type=int, default=20)
    return ap


def main() -> None:
    from repro.launch.compile_cache import enable_compile_cache

    args = build_parser().parse_args()
    enable_compile_cache()
    if args.arch.startswith("tm-"):
        train_tm(args)
    else:
        train_lm(args)


if __name__ == "__main__":
    main()
