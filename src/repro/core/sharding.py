"""Mesh sharding for TM training/inference (DESIGN.md §5).

Layout:
  * automata / include words: clause axis over ``model``, replicated over
    ``data`` (and ``pod``);
  * batch: over (``pod`` x) ``data``;
  * vote matrix: clause axis over ``model``;
  * class sums: partial per model-shard -> one tiny ``psum`` over ``model``
    (the only inference collective);
  * training feedback deltas: computed locally per (data, model) shard, then
    ``psum`` over ``data`` only — int32 bounded-magnitude "compressed
    gradients".

Two execution engines share this one dispatch layer (PR 3):

  * ``engine="gspmd"`` — jit + NamedSharding constraints; GSPMD inserts the
    collectives above.  The original path; kernel-free, XLA everywhere.
  * ``engine="kernel"`` — an explicit ``shard_map`` schedule whose per-shard
    body IS the fused Pallas pipeline (``ops.tm_train_step_kernel`` /
    ``ops.tm_forward_packed``): each ``model`` shard runs the fused kernels
    on its local clause bank with runtime ``b_offset``/``c_offset`` global
    RNG ids, one int32 class-sum ``psum`` over ``model`` completes the
    partial adder-bank outputs, and training deltas ``psum`` over ``data``.
    Bit-identical to the single-device ``ref.py`` oracle (the hash RNG is
    indexed by global (sample, clause, literal) ids on every shard) —
    verified in tests/test_sharded_fused.py on an emulated mesh.

The clause axis is the natural partition unit (the eFPGA runtime-tunable TM
work partitions by clause bank for exactly this reason): clause banks larger
than one core's VMEM split across ``model`` with only the tiny (B, K) psum
on the wire.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import tm


def data_axes(mesh: Mesh) -> tuple:
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def _engine_dispatch(engine, use_kernel, interpret, *, allowed,
                     fuse: bool = True) -> tuple:
    """Resolve a forward builder's ``(use_kernel, interpret, fuse)`` from
    either an ``ops.EngineSpec``/name (the high-level vocabulary) or the
    low-level ``use_kernel``/``interpret`` overrides — not both.  Each
    builder already names its kernel, so only the engines it can actually
    build (``allowed``) plus ``"auto"``/``"oracle"`` are accepted: asking
    the dense-fused builder for ``"sparse"`` would silently build the
    wrong schedule."""
    from repro.kernels import ops

    if engine is None:
        uk, it = ops.kernel_dispatch(use_kernel, interpret)
        return uk, it, fuse
    if use_kernel is not None:
        raise TypeError("pass engine= or use_kernel=, not both")
    spec = ops.EngineSpec.coerce(engine)
    if spec.name not in allowed:
        raise ValueError(
            f"engine {spec.name!r} does not apply to this sharded builder; "
            f"one of {allowed}")
    uk_s, it_s, fuse_s, _, _ = spec.resolve(interpret)
    uk, it = ops.kernel_dispatch(uk_s, it_s)
    return uk, it, fuse_s


def tm_shardings(config: tm.TMConfig, mesh: Mesh):
    """(state_sharding, batch_sharding) for the TM train/serve steps."""
    d = data_axes(mesh)
    state = tm.TMState(
        ta_state=NamedSharding(mesh, P("model", None)),
        steps=NamedSharding(mesh, P()),
    )
    batch = NamedSharding(mesh, P(d, None))
    return state, batch


def sharded_forward_fn(mesh: Mesh, *, engine=None,
                       use_kernel: bool | None = None,
                       interpret: bool | None = None, fuse: bool = True,
                       blocks: dict | None = None):
    """Clause-sharded fused forward: (inc_words, votes, nonempty,
    lit_words) -> (B, K) int32 GLOBAL class sums.

    An explicit ``shard_map`` schedule: each ``model`` shard evaluates its
    local clause bank with the fused single-pass inference kernel (or the
    oracle, per ``engine`` — ``"auto"``/``"dense"``/``"oracle"``, or the
    low-level ``use_kernel`` override) — the full bank never needs to fit
    one core's VMEM — and one int32 ``psum`` over ``model`` completes the
    adder bank.  Exact: integer partial sums compose bit-identically to
    the unsharded kernel.  Shape-agnostic (works for dense banks and
    compiled artifacts); the clause axis size must be divisible by the
    ``model`` axis size.
    """
    from repro.kernels import ops

    uk, it, fuse = _engine_dispatch(engine, use_kernel, interpret,
                                    allowed=("auto", "dense", "oracle"),
                                    fuse=fuse)
    d = data_axes(mesh)

    def body(inc_loc, votes_loc, ne_loc, lw_loc):
        sums = ops.tm_forward_packed(
            lw_loc, inc_loc, votes_loc, ne_loc,
            use_kernel=uk, interpret=it, fuse=fuse, **(blocks or {}),
        )
        return jax.lax.psum(sums, "model")

    fwd = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P("model", None), P("model", None), P("model"), P(d, None)),
        out_specs=P(d, None),
        check_vma=False,
    )
    return jax.jit(fwd)


def sharded_schedule_forward_fn(mesh: Mesh, *,
                                block_c: int, block_j: int,
                                block_s: int | None = None,
                                engine=None,
                                use_kernel: bool | None = None,
                                interpret: bool | None = None):
    """Clause-sharded COMPILED-SCHEDULE forward: each ``model`` shard owns
    its own block-sparse tile table (built by
    ``kernels/sparse_infer.stack_shard_schedules``) and runs the
    scalar-prefetched chain kernel on its local clause bank; one int32
    ``psum`` over ``model`` completes the adder bank.  The batch shards
    over the data axes.

    Signature of the returned jit'd fn:
    ``(chain_stack (n, Cp, Jp), votes_stack (n, Cp, K),
    tile_stack (n, 4, T), lit_words (B, Wa)) -> (B, K) int32``.

    Exact: per-shard partial sums are integers, and no-op padding tiles
    (all-sentinel chains, never first/last) equalize tile counts across
    shards without touching any shard's class sums.
    """
    from repro.kernels import ops, sparse_infer

    uk, it, _ = _engine_dispatch(engine, use_kernel, interpret,
                                 allowed=("auto", "sparse", "oracle"))
    d = data_axes(mesh)
    bs = block_s or sparse_infer.DEFAULT_BLOCK_S

    def body(chain_loc, votes_loc, tiles_loc, lw_loc):
        chain, vt, tiles = chain_loc[0], votes_loc[0], tiles_loc[0]
        if uk:
            sums = sparse_infer.sparse_tm_forward_tables(
                lw_loc, chain, vt, tiles,
                block_c=block_c, block_j=block_j, block_s=bs, interpret=it,
            )
        else:
            sums = sparse_infer.schedule_class_sums_ref(lw_loc, chain, vt)
        return jax.lax.psum(sums, "model")

    fwd = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P("model", None, None), P("model", None, None),
                  P("model", None, None), P(d, None)),
        out_specs=P(d, None),
        check_vma=False,
    )
    return jax.jit(fwd)


def sharded_factorized_forward_fn(mesh: Mesh, *,
                                  block_t: int, block_c: int, block_j: int,
                                  block_s: int | None = None,
                                  engine=None,
                                  use_kernel: bool | None = None,
                                  interpret: bool | None = None):
    """Clause-sharded FACTORIZED-schedule forward: each ``model`` shard
    owns its own term table + tile table (built by
    ``kernels/term_infer.stack_shard_factorized`` — terms are extracted
    per shard, so stage 1 evaluates only the terms the shard's clauses
    reference) and runs the two-stage kernel on its local bank; one int32
    ``psum`` over ``model`` completes the adder bank.  The batch shards
    over the data axes.

    Signature of the returned jit'd fn:
    ``(term_stack (n, Tp, term_w), chain_stack (n, Cp, Jp),
    votes_stack (n, Cp, K), tile_stack (n, 6, T), lit_words (B, Wa))
    -> (B, K) int32``.

    Exact: per-shard partial sums are integers; no-op padding tiles and
    all-sentinel padding term rows change no shard's class sums.
    """
    from repro.kernels import ops, term_infer

    uk, it, _ = _engine_dispatch(engine, use_kernel, interpret,
                                 allowed=("auto", "factorized", "oracle"))
    d = data_axes(mesh)
    bs = block_s or term_infer.DEFAULT_BLOCK_S

    def body(term_loc, chain_loc, votes_loc, tiles_loc, lw_loc):
        term, chain, vt, tiles = (term_loc[0], chain_loc[0],
                                  votes_loc[0], tiles_loc[0])
        if uk:
            sums = term_infer.factorized_tm_forward_tables(
                lw_loc, term, chain, vt, tiles,
                block_t=block_t, block_c=block_c, block_j=block_j,
                block_s=bs, interpret=it,
            )
        else:
            sums = term_infer.factorized_class_sums_ref(lw_loc, term, chain, vt)
        return jax.lax.psum(sums, "model")

    fwd = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P("model", None, None), P("model", None, None),
                  P("model", None, None), P("model", None, None),
                  P(d, None)),
        out_specs=P(d, None),
        check_vma=False,
    )
    return jax.jit(fwd)


def sharded_predict_fn(config: tm.TMConfig, mesh: Mesh, *,
                       engine=None,
                       use_kernel: bool | None = None,
                       interpret: bool | None = None, fuse: bool = True,
                       blocks: dict | None = None):
    """Build a jit'd sharded inference fn: packed literals -> class ids.

    Clause axis sharded over ``model``.  On the kernel path (``use_kernel``
    / ``REPRO_USE_PALLAS``) the per-shard body is the fused single-pass
    Pallas kernel inside an explicit ``shard_map`` (clause banks bigger
    than one core's VMEM split across the mesh; one (B, K) class-sum psum
    on the wire).  Otherwise GSPMD turns the vote matmul into a local
    matmul + all-reduce over ``model`` of the (B, K) partial sums.
    """
    from repro.kernels import ops

    uk, it, fuse = _engine_dispatch(engine, use_kernel, interpret,
                                    allowed=("auto", "dense", "oracle"),
                                    fuse=fuse)
    d = data_axes(mesh)
    votes_s = NamedSharding(mesh, P("model", None))
    inc_s = NamedSharding(mesh, P("model", None))
    x_s = NamedSharding(mesh, P(d, None))
    out_s = NamedSharding(mesh, P(d))

    if uk:
        fwd = sharded_forward_fn(mesh, use_kernel=uk, interpret=it,
                                 fuse=fuse, blocks=blocks)

        def predict(inc_words, votes, nonempty, lit_words):
            return jnp.argmax(fwd(inc_words, votes, nonempty, lit_words),
                              axis=-1)
    else:
        def predict(inc_words, votes, nonempty, lit_words):
            fired = ops.clause_fire(lit_words, inc_words, use_kernel=False)
            fired = fired * nonempty[None, :].astype(fired.dtype)
            sums = fired.astype(jnp.int32) @ votes
            return jnp.argmax(sums, axis=-1)

    return jax.jit(
        predict,
        in_shardings=(inc_s, votes_s, NamedSharding(mesh, P("model")), x_s),
        out_shardings=out_s,
    )


def sharded_train_step_fn(config: tm.TMConfig, mesh: Mesh,
                          batch_chunk: int | None = 2048,
                          algorithm: str = "bitwise",
                          *,
                          engine: str = "gspmd",
                          use_kernel: bool | None = None,
                          interpret: bool | None = None,
                          fuse: bool = True,
                          blocks: dict | None = None):
    """Build a jit'd sharded batch training step.

    The kernel-path step (hash RNG) is used because its feedback plan is a
    pure function of (fire, y, seed) — no cross-shard RNG state. Automata are
    replicated over ``data`` and sharded over ``model`` on the clause axis;
    the per-data-shard deltas are combined by GSPMD's all-reduce when the
    (replicated-output) update is applied.

    ``engine`` selects the execution engine of the clause shards:

      * ``"gspmd"`` (default) — jit + NamedSharding; XLA partitions the
        oracle step.  Semantically the whole-bank function; sharding is
        pure layout.
      * ``"kernel"`` — explicit ``shard_map`` schedule running
        ``ops.tm_train_step_kernel`` per shard (the fused two-launch Pallas
        pipeline when the kernel path is active; ``fuse``/``use_kernel``/
        ``interpret``/``blocks`` pass through).  Collectives: one int32
        (B, K) class-sum ``psum`` over ``model`` + one int32 (C_loc, L)
        delta ``psum`` over ``data``.  Bit-identical to the single-device
        oracle — every hash is indexed by global (sample, clause) ids via
        runtime ``b_offset``/``c_offset`` scalars.  Requires the clause
        axis divisible by the ``model`` axis size (``clause_pad_multiple``)
        and the batch by the data axes.

    ``algorithm="matmul"`` selects the beyond-paper binomial-aggregation
    step (its own shard_map schedule; statistically, not bitwise, exact).
    """
    if engine not in ("gspmd", "kernel"):
        # all engines are bit-identical, so a silent fallthrough on a typo
        # would "work" while measuring the wrong schedule — fail loudly
        raise ValueError(f"unknown engine {engine!r}: expected 'gspmd' or "
                         "'kernel'")
    if engine == "kernel" and config.n_clauses_total % mesh.shape["model"]:
        raise ValueError(
            f"clause axis ({config.n_clauses_total}) not divisible by the "
            f"model axis ({mesh.shape['model']}); align via "
            "clause_pad_multiple")
    d = data_axes(mesh)
    # matmul path: automata sharded over BOTH axes (clauses x literals): the
    # step all-gathers the int8 states over `data` (34 MB at pod scale) and
    # GSPMD reduce-scatters the f32 delta — far less wire than all-reducing
    # the dense delta against data-replicated states.
    lit_shard = d if algorithm == "matmul" else None
    state_s = NamedSharding(mesh, P("model", lit_shard))
    x_s = NamedSharding(mesh, P(d, None))
    y_s = NamedSharding(mesh, P(d))

    def step(ta_state, x, y, seed):
        from repro.kernels import ops

        if algorithm == "matmul":   # beyond-paper binomial-aggregation path
            # explicit shard_map schedule: GSPMD falls back to a dense f32
            # delta all-reduce here; the hand schedule is AG(int8) + two tiny
            # psums + psum_scatter (see EXPERIMENTS.md §Perf, TM cell)
            data_ax = d[-1] if d else "data"

            return jax.shard_map(
                lambda ta, xx, yy: ops.tm_train_step_matmul_local(
                    config, ta, xx, yy, seed
                ),
                mesh=mesh,
                in_specs=(P("model", data_ax), P(d, None), P(d)),
                out_specs=P("model", data_ax),
                check_vma=False,
            )(ta_state, x, y)

        if engine == "kernel":
            # explicit clause-sharded shard_map schedule around the fused
            # kernel pipeline: each model shard owns (C_loc, L) automata and
            # evaluates/updates them locally; one class-sum psum over
            # `model`, one delta psum over the data axes.
            def body(ta_loc, xx, yy):
                C_loc, B_loc = ta_loc.shape[0], xx.shape[0]
                c_off = (jax.lax.axis_index("model").astype(jnp.uint32)
                         * jnp.uint32(C_loc))
                b_off = jnp.uint32(0)
                for ax in d:   # row-major global id of this data shard
                    b_off = (b_off * jnp.uint32(jax.lax.axis_size(ax))
                             + jax.lax.axis_index(ax).astype(jnp.uint32))
                b_off = b_off * jnp.uint32(B_loc)
                _, delta = ops.tm_train_step_kernel(
                    config, ta_loc, xx, yy, seed,
                    batch_chunk=batch_chunk, fuse=fuse, blocks=blocks,
                    b_offset=b_off, c_offset=c_off,
                    c_total=config.n_clauses_total,
                    sums_reduce=lambda s: jax.lax.psum(s, "model"),
                    use_kernel=use_kernel, interpret=interpret,
                )
                if d:   # combine the per-data-shard int32 partial deltas
                    delta = jax.lax.psum(delta, d)
                return jnp.clip(
                    ta_loc.astype(jnp.int32) + delta,
                    -config.n_states, config.n_states - 1,
                ).astype(jnp.int8)

            return jax.shard_map(
                body, mesh=mesh,
                in_specs=(P("model", None), P(d, None), P(d)),
                out_specs=P("model", None),
                check_vma=False,
            )(ta_state, x, y)

        new_ta, _ = ops.tm_train_step_kernel(
            config, ta_state, x, y, seed, use_kernel=False,
            batch_chunk=batch_chunk,
        )
        return new_ta

    return jax.jit(
        step,
        in_shardings=(state_s, x_s, y_s, None),
        out_shardings=state_s,
        donate_argnums=0,
    )
