"""TM training / eval steps (single-host and mesh-sharded).

The sharded step is the distribution story of DESIGN.md §5: automata are
sharded over the ``model`` axis on the clause dimension, the batch over
``data`` (× ``pod``); the only cross-device traffic is
  * an int32 ``psum`` of feedback deltas over ``data`` — the TM's native
    "compressed gradient" (bounded small ints), and
  * nothing at all over ``model`` for feedback (each clause's feedback is
    local to its shard; class sums inside feedback are computed per-class
    from the local slice — clause shards are class-aligned by construction).
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.core import feedback, tm


@functools.partial(jax.jit, static_argnums=0, donate_argnums=1)
def train_step(
    config: tm.TMConfig, state: tm.TMState, x: jax.Array, y: jax.Array, rng: jax.Array
) -> Tuple[tm.TMState, dict]:
    delta = feedback.batch_feedback_delta(config, state.ta_state, x, y, rng)
    new_ta = feedback.apply_delta(config, state.ta_state, delta)
    new_state = tm.TMState(ta_state=new_ta, steps=state.steps + 1)
    metrics = {
        "delta_abs_sum": jnp.sum(jnp.abs(delta)),
        "include_frac": jnp.mean((new_ta >= 0).astype(jnp.float32)),
    }
    return new_state, metrics


@functools.partial(jax.jit, static_argnums=(0, 5), donate_argnums=1)
def train_step_kernel(
    config: tm.TMConfig, state: tm.TMState, x: jax.Array, y: jax.Array,
    seed: jax.Array, batch_chunk: int | None = None,
) -> Tuple[tm.TMState, dict]:
    """Kernel-path batch step (hash RNG; fused Pallas pipeline).

    Same contract as :func:`train_step` but driven by ``ops.
    tm_train_step_kernel`` — on the kernel path the whole step is two
    fused ``pallas_call`` launches (class sums, then clause-fire ->
    feedback -> TA delta with nothing spilled to HBM).  ``state`` is
    donated so the int8 automata bank is updated in place across long
    ``fit`` runs instead of double-buffering.
    """
    from repro.kernels import ops

    new_ta, delta = ops.tm_train_step_kernel(
        config, state.ta_state, x, y, seed,
        batch_chunk=batch_chunk,
    )
    new_state = tm.TMState(ta_state=new_ta, steps=state.steps + 1)
    metrics = {
        "delta_abs_sum": jnp.sum(jnp.abs(delta)),
        "include_frac": jnp.mean((new_ta >= 0).astype(jnp.float32)),
    }
    return new_state, metrics


@functools.partial(jax.jit, static_argnums=0)
def online_step(
    config: tm.TMConfig, ta_state: jax.Array, x: jax.Array, y: jax.Array,
    seed: jax.Array,
) -> Tuple[jax.Array, jax.Array]:
    """One streaming-feedback step on a RAW automata bank (hash RNG).

    The online updater (``runtime/online.py``) steps a live bank beside a
    serving loop: it feeds fixed-size feedback batches (one jit trace),
    seeds by its own global step counter for reproducibility, and keeps
    the previous bank un-donated — rollback and SIGTERM-drain
    checkpointing both need the pre-step buffer intact.  Returns
    ``(new_ta, delta_abs_sum)``.
    """
    from repro.kernels import ops

    new_ta, delta = ops.tm_train_step_kernel(config, ta_state, x, y, seed)
    return new_ta, jnp.sum(jnp.abs(delta))


@functools.partial(jax.jit, static_argnums=0)
def eval_step(
    config: tm.TMConfig, state: tm.TMState, x: jax.Array, y: jax.Array
) -> jax.Array:
    return tm.accuracy(config, state, x, y)


def fit(
    config: tm.TMConfig,
    state: tm.TMState,
    x: jax.Array,
    y: jax.Array,
    *,
    epochs: int,
    batch_size: int,
    rng: jax.Array,
    x_val=None,
    y_val=None,
    log_every: int = 0,
    engine: str = "jnp",
    batch_chunk: int | None = None,
    mesh=None,
    ckpt_manager=None,
    ckpt_every: int = 0,
    preemption=None,
    monitor=None,
) -> tm.TMState:
    """Simple host loop used by examples/tests (the GUI "Train" button).

    The batch stream is pre-shuffled ONCE per epoch on device (one gather
    of ``x``/``y``), so the inner loop slices contiguous device buffers
    instead of re-gathering ``x[idx]`` every step; the TA state is donated
    through both step functions, so long runs keep a single automata
    buffer alive instead of double-buffering.

    ``engine="jnp"`` runs the per-sample jax.random step (paper-faithful
    sequential semantics, batch-accumulated); ``engine="kernel"`` runs the
    hash-RNG kernel-path step (fused Pallas pipeline on the kernel path),
    seeded by the global step index so runs are reproducible.

    ``mesh`` (with ``engine="kernel"``) runs every step through the
    clause-sharded ``shard_map`` schedule of
    ``core/sharding.py:sharded_train_step_fn(engine="kernel")`` — automata
    sharded over ``model``, batch over the data axes.  The shuffle stream
    and per-step seeds are unchanged, and the sharded step is bit-identical
    to the single-device one, so ``fit`` results do not depend on the mesh.

    **Fault tolerance** — ``ckpt_manager`` (a ``CheckpointManager``) with
    ``ckpt_every > 0`` checkpoints the TA state, the (epoch, step-in-epoch)
    cursor, and the EPOCH-START rng key at step boundaries, and auto-resumes
    from the newest checkpoint when the directory already holds one.
    Resume is *bit-exact*: the epoch's shuffle permutation is re-derived
    from the saved epoch key and the per-step rng splits already consumed
    are replayed, so an interrupted-then-resumed run produces exactly the
    TA state of an uninterrupted one (drilled in
    tests/test_fault_tolerance.py).  ``preemption`` (a ``PreemptionHandler``)
    turns SIGTERM into checkpoint + ``sys.exit(RESUME_EXIT_CODE)`` at the
    next step boundary; ``monitor`` (a ``StragglerMonitor``) flags slow
    steps.  Fault-injection sites (``runtime/faults.py``): ``train.sigterm``
    and ``train.slow_step``, keyed by the global step index.
    """
    from repro.runtime import faults

    sharded_step = None
    if mesh is not None:
        if engine != "kernel":
            raise ValueError("fit(mesh=...) requires engine='kernel' "
                             "(the hash-RNG step; no cross-shard RNG state)")
        from repro.core import sharding as tm_sharding

        sharded_step = tm_sharding.sharded_train_step_fn(
            config, mesh, batch_chunk=batch_chunk, engine="kernel"
        )
    n = x.shape[0]
    steps_per_epoch = max(1, n // batch_size)
    gstep = 0
    start_epoch = start_step = 0
    if ckpt_manager is not None and ckpt_manager.latest_step() is not None:
        restored, extra = ckpt_manager.restore(
            {"ta": state.ta_state, "rng": rng})
        rng = jnp.asarray(restored["rng"], jnp.uint32)   # epoch-start key
        start_epoch = int(extra["epoch"])
        start_step = int(extra["step_in_epoch"])
        gstep = int(extra["gstep"])
        state = tm.TMState(ta_state=restored["ta"], steps=jnp.int32(gstep))
        print(f"fit: resumed at epoch {start_epoch} step {start_step} "
              f"(global step {gstep})")

    def save_ckpt(ep, next_step, rng_epoch, blocking=True):
        ckpt_manager.save(
            gstep, {"ta": state.ta_state, "rng": rng_epoch},
            extra={"epoch": ep, "step_in_epoch": next_step, "gstep": gstep},
            blocking=blocking)

    for ep in range(start_epoch, epochs):
        rng_epoch = rng                  # resume anchor: key at epoch start
        rng, rp = jax.random.split(rng)
        perm = jax.random.permutation(rp, n)
        xs, ys = x[perm], y[perm]        # one device-side shuffle per epoch
        i0 = start_step if ep == start_epoch else 0
        for _ in range(i0):              # replay consumed per-step splits
            rng, _ = jax.random.split(rng)
        for i in range(i0, steps_per_epoch):
            if monitor is not None:
                monitor.start_step()
            xb = xs[i * batch_size : (i + 1) * batch_size]
            yb = ys[i * batch_size : (i + 1) * batch_size]
            rng, rs = jax.random.split(rng)
            if sharded_step is not None:
                new_ta = sharded_step(state.ta_state, xb, yb,
                                      jnp.uint32(gstep))
                state = tm.TMState(ta_state=new_ta, steps=state.steps + 1)
            elif engine == "kernel":
                state, _ = train_step_kernel(
                    config, state, xb, yb, jnp.uint32(gstep), batch_chunk
                )
            else:
                state, _ = train_step(config, state, xb, yb, rs)
            faults.sleep_if("train.slow_step", step=gstep)
            gstep += 1
            if monitor is not None:
                flag = monitor.end_step(gstep - 1)
                if flag:
                    print(f"fit: straggler flagged: {flag}")
            if (ckpt_manager is not None and ckpt_every
                    and gstep % ckpt_every == 0):
                save_ckpt(ep, i + 1, rng_epoch, blocking=False)
            faults.sigterm_if("train.sigterm", step=gstep - 1)
            if preemption is not None and preemption.preempted:
                print("fit: preempted — checkpointing and exiting for resume")
                preemption.checkpoint_and_exit(
                    (lambda: save_ckpt(ep, i + 1, rng_epoch))
                    if ckpt_manager is not None else (lambda: None))
        if log_every and (ep + 1) % log_every == 0 and x_val is not None:
            acc = eval_step(config, state, x_val, y_val)
            print(f"epoch {ep + 1}: val_acc={float(acc):.4f}")
    if ckpt_manager is not None:
        ckpt_manager.wait()              # surface any pending async failure
    return state
