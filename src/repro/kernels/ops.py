"""Jit'd dispatch wrappers over the Pallas kernels (with oracle fallback).

Every op takes ``use_kernel``/``interpret`` switches whose defaults follow
the platform: on a TPU the kernels are the default engine and run compiled
(``interpret=False``); on CPU the ``ref.py`` oracle path is the default
(XLA-compiled, fast), and the kernels run in Pallas interpret mode, where
the tests validate them bit-exactly against those oracles.

Which inference engine serves a compiled artifact is decided in one place,
:func:`engine_levels`, from the platform and the artifact.
``REPRO_USE_PALLAS=1``, read once at import, makes the kernels the default
on CPU too, the way a TPU does; it never picks among the kernels.  The CPU
drills use it to put the kernel ladder under the launchers.
"""

from __future__ import annotations

import contextlib
import functools
import os
import threading

import jax
import jax.numpy as jnp
from jax.profiler import TraceAnnotation

from repro.runtime import faults

from repro.kernels import conv_infer as _conv_infer_kernel
from repro.kernels import fused_infer as _fused_infer_kernel
from repro.kernels import fused_train as _fused_train_kernel
from repro.kernels import ref
from repro.kernels import sparse_infer as _sparse_infer_kernel
from repro.kernels import term_infer as _term_infer_kernel
from repro.kernels import xnor_popcount as _xnor_kernel

_ON_TPU = jax.default_backend() == "tpu"
_DEFAULT_USE_KERNEL = (_ON_TPU
                       or os.environ.get("REPRO_USE_PALLAS", "0") == "1")


def _resolve(use_kernel, interpret):
    if use_kernel is None:
        use_kernel = _DEFAULT_USE_KERNEL
    if interpret is None:
        interpret = not _ON_TPU
    return use_kernel, interpret


def kernel_dispatch(use_kernel=None, interpret=None):
    """Public resolver for callers that branch on the dispatch decision
    (serve loop, compiled-artifact runner): (use_kernel, interpret)."""
    return _resolve(use_kernel, interpret)


ENGINE_NAMES = ("auto", "factorized", "sparse", "dense", "conv", "oracle")


def engine_levels(compiled, use_kernel: bool | None = None) -> list:
    """The engine ladder a compiled artifact is served on, preferred
    engine first: the one decision of which inference engine runs, made
    from the platform (:func:`kernel_dispatch`) and the artifact alone.

    A convolutional artifact runs its kernel, then the oracle; a vanilla
    one the factorized schedule kernel (when its measured term sharing
    clears ``compiler.FACTORIZE_SHARING_THRESHOLD``), the sparse and
    dense kernels, then the oracle.  Off the kernel path only the oracle
    is left.  ``run_compiled(engine="auto")`` serves ``levels[0]``."""
    from repro.core import compiler

    uk, _ = _resolve(use_kernel, None)
    if compiled.geometry is not None:
        return (["conv"] if uk else []) + ["oracle"]
    factorize = (compiled.stats.partial_term_sharing
                 >= compiler.FACTORIZE_SHARING_THRESHOLD)
    return ((["factorized"] if uk and factorize else [])
            + (["sparse", "dense"] if uk else []) + ["oracle"])


class EngineLadder:
    """Degradation ladder over inference engines (serve fault tolerance).

    ``engines`` is an ordered ``[(name, builder)]`` list, preferred engine
    first; ``builder()`` returns the engine's callable and is invoked
    lazily, so engines the ladder never reaches pay neither their jit
    trace nor their autotune sweep.  :meth:`run` executes the current
    engine on a *fresh* input from ``make_input`` (re-invoked per attempt
    so a retry never reuses a buffer a failed call may already have
    donated), blocks until the result is ready so asynchronous failures
    surface here, and on ANY exception — a Mosaic lowering error on a real
    backend, an injected fault in a drill — demotes one level and retries
    the same input.  Only the LAST engine's failure propagates: the run
    degrades instead of crashing.  ``counts``/``demotions`` feed the serve
    health summary (which engine actually served each bucket).

    **Re-promotion** (``promote_after=N``): a demotion is not a life
    sentence — after ``N`` consecutive healthy buckets at the current
    level, the next :meth:`run` serves its bucket as a PROBE on the engine
    one level up.  A successful probe promotes (the probe bucket IS served
    by the higher engine, so probing costs nothing extra); a failed probe
    falls back to the current engine for the same input, resets the
    healthy streak, and DOUBLES the cooldown (the streak required before
    the next probe) — a permanent fault converges to exponentially-rare
    probes while a transient one no longer pins the tenant on the slow
    oracle forever.  A demotion resets both streak and cooldown to base.
    ``promote_after=None`` (default) keeps the demote-only behavior.
    ``promotions``/``probe_failures`` feed the health summary alongside
    ``demotions``.

    **Anytime quality** (brownout serving): :meth:`run` takes a
    ``quality`` level.  Engines whose built callable is marked
    ``supports_quality = True`` (an attribute the builder sets on the
    closure) are invoked ``fn(x, quality)`` and serve the budgeted tile
    prefix; every other engine serves exact.  ``last_quality`` reports
    what the serving engine actually delivered (0 = exact) so the caller
    can attribute the answer — a ladder demoted to the dense or oracle
    engine keeps serving exact answers under brownout, which is safe
    (stronger than requested).
    """

    def __init__(self, engines, promote_after: int | None = None):
        self._names = [name for name, _ in engines]
        self._builders = dict(engines)
        self._built: dict = {}
        self._level = 0
        self.counts = {name: 0 for name in self._names}
        self.demotions: list = []
        self.promote_after = promote_after
        self.promotions: list = []
        self.probe_failures: list = []
        self._healthy = 0                    # success streak at this level
        self._cooldown = promote_after or 0  # streak required to probe up
        self.last_quality = 0                # quality the last run served

    @property
    def engine(self) -> str:
        """Name of the engine currently serving."""
        return self._names[self._level]

    @property
    def exhausted(self) -> bool:
        return self._level + 1 >= len(self._names)

    def demote(self, reason: str, bucket=None) -> bool:
        """Drop one level (False when already on the last engine)."""
        if self.exhausted:
            print(f"engine ladder exhausted at {self.engine!r}; cannot "
                  f"demote further ({reason})")
            return False
        frm, to = self._names[self._level], self._names[self._level + 1]
        self.demotions.append(
            dict(frm=frm, to=to, bucket=bucket, reason=reason))
        print(f"engine demoted: {frm} -> {to} (bucket {bucket}): {reason}")
        self._level += 1
        self._healthy = 0
        self._cooldown = self.promote_after or 0
        return True

    def rebind(self, engines) -> None:
        """Swap in a new ``[(name, builder)]`` list (artifact hot-swap).

        Built callables are discarded — they closed over the OLD
        artifact's schedules — and rebuild lazily on next use, while the
        ladder's health state (current level, streaks, telemetry) carries
        over: a tenant demoted to a safe engine stays demoted across a
        swap instead of re-crashing its way down the ladder.  The engine
        names must match the existing ladder (the level index keeps its
        meaning).
        """
        names = [name for name, _ in engines]
        if names != self._names:
            raise ValueError(
                f"rebind: engine names {names} != ladder levels "
                f"{self._names} — a swap must not reorder the ladder")
        self._builders = dict(engines)
        self._built = {}

    def _run_at(self, level, make_input, quality=0):
        name = self._names[level]
        fn = self._built.get(name)
        if fn is None:
            fn = self._built[name] = self._builders[name]()
        degrade = bool(quality and getattr(fn, "supports_quality", False))
        # one span per phase of every attempt (probes included): the host
        # copy in, the engine's dispatch, and the wait for the device
        with TraceAnnotation("repro.runner.copy_in"):
            x = make_input()
        with TraceAnnotation("repro.runner.dispatch"):
            out = fn(x, quality) if degrade else fn(x)
        with TraceAnnotation("repro.runner.wait"):
            out = jax.block_until_ready(out)
        self.last_quality = int(quality) if degrade else 0
        return out

    def _maybe_probe(self, make_input, bucket, count, quality=0):
        """Serve this bucket on the engine one level up when the healthy
        streak has cleared the cooldown; returns the output or None."""
        if (not self.promote_after or self._level == 0
                or self._healthy < self._cooldown):
            return None
        target = self._names[self._level - 1]
        try:
            out = self._run_at(self._level - 1, make_input, quality)
        except Exception as e:  # noqa: BLE001 — a failed probe never escapes
            self.probe_failures.append(dict(
                engine=target, bucket=bucket,
                reason=f"{type(e).__name__}: {e}"))
            self._healthy = 0
            self._cooldown *= 2
            print(f"engine probe failed: {target} (bucket {bucket}); "
                  f"cooldown now {self._cooldown} healthy buckets")
            return None
        self.promotions.append(
            dict(to=target, frm=self.engine, bucket=bucket,
                 after_healthy=self._healthy))
        print(f"engine promoted: {self.engine} -> {target} (bucket {bucket}) "
              f"after {self._healthy} healthy buckets")
        self._level -= 1
        self._healthy = 0
        self._cooldown = self.promote_after
        if count:
            self.counts[target] += 1
        return out

    def run(self, make_input, bucket=None, count=True, quality=0):
        """Run the current engine on ``make_input()``, demoting on failure.

        ``quality > 0`` requests a budgeted (anytime) answer; engines
        without quality support serve exact.  ``self.last_quality`` holds
        the level actually served after the call returns.
        """
        probed = self._maybe_probe(make_input, bucket, count, quality)
        if probed is not None:
            return probed
        while True:
            name = self.engine
            try:
                out = self._run_at(self._level, make_input, quality)
            except Exception as e:  # noqa: BLE001 — any engine failure demotes
                if not self.demote(f"{type(e).__name__}: {e}", bucket=bucket):
                    raise
                continue
            if count:
                self.counts[name] += 1
            self._healthy += 1
            return out


def xnor_dot(
    a_words, w_words, n_bits: int,
    *,
    use_kernel: bool | None = None,
    interpret: bool | None = None,
    **blocks,
) -> jax.Array:
    use_kernel, interpret = _resolve(use_kernel, interpret)
    if use_kernel:
        return _xnor_kernel.xnor_popcount(
            a_words, w_words, n_bits, interpret=interpret, **blocks
        )
    return ref.xnor_popcount_ref(a_words, w_words, n_bits)


# ---------------------------------------------------------------------------
# Fused TM pipelines (the full accelerator datapath)
# ---------------------------------------------------------------------------

def tm_forward_packed(
    lit_words: jax.Array,    # (B, W)
    inc_words: jax.Array,    # (C, W)
    votes: jax.Array,        # (C, K)
    nonempty: jax.Array | None = None,  # (C,) uint8; None = training semantics
    *,
    use_kernel: bool | None = None,
    interpret: bool | None = None,
    autotune: bool = False,
    **blocks,
) -> jax.Array:
    """Packed literals -> (B, K) class sums (HCB chain + adder bank + mask).

    Kernel path (``use_kernel=True`` or ``REPRO_USE_PALLAS=1``) runs the
    fused single-pass kernel (``fused_infer.py``) — clause eval and vote
    accumulation in one ``pallas_call``, no (B, C) fired matrix in HBM;
    the ``ref.py`` oracle is the default execution engine off-TPU.
    ``autotune=True`` (kernel path, no explicit blocks) picks block sizes
    via ``autotune.py``'s cached sweep.
    """
    use_kernel, interpret = _resolve(use_kernel, interpret)
    if use_kernel:
        faults.raise_if("kernel.dense")   # drill: dense-kernel lowering failure
        if autotune and not blocks:
            from repro.kernels import autotune as _autotune

            B, W = lit_words.shape
            C, K = votes.shape
            blocks = _autotune.autotune_fused_blocks(
                B, C, W, K, interpret=interpret
            )
        return _fused_infer_kernel.fused_tm_forward(
            lit_words, inc_words, votes, nonempty, interpret=interpret, **blocks
        )
    fired = ref.clause_fire_ref(lit_words, inc_words)
    if nonempty is not None:
        fired = fired * nonempty[None, :].astype(fired.dtype)
    return ref.class_sum_ref(fired, votes)


def conv_tm_forward_packed(
    img_words: jax.Array,    # (B, Wr) packed images
    include_words,           # (C, Wl) packed patch-literal include bits
    votes,                   # (C, K) int32 weights
    *,
    geom,                    # kernels/conv_infer.Geometry
    operands=None,           # conv_infer.conv_operands(...) of this bank
    use_kernel: bool | None = None,
    interpret: bool | None = None,
    **blocks,
) -> jax.Array:
    """Packed images -> (B, K) class sums of a convolutional TM.

    Kernel path: ``conv_infer.conv_tm_forward`` over the bank's banded
    ``operands`` (which it needs).  Oracle path: the patch literals made
    on the device (``packetizer.patch_literals``) and
    ``ref.conv_class_sums_ref`` over the include bits and votes."""
    from repro.core import packetizer

    use_kernel, interpret = _resolve(use_kernel, interpret)
    if use_kernel:
        faults.raise_if("kernel.conv")   # drill: conv-kernel failure
        band, v = operands
        return _conv_infer_kernel.conv_tm_forward(
            img_words, jnp.asarray(band), jnp.asarray(v),
            geom=geom, interpret=interpret, **blocks)
    return ref.conv_class_sums_ref(
        packetizer.patch_literals(img_words, geom),
        packetizer.unpack_bits(jnp.asarray(include_words), geom.literals),
        jnp.asarray(votes))


def tm_forward_schedule(
    lit_words: jax.Array,       # (B, Wa) packed literals (word-compacted)
    include_words,              # (U, Wa) uint32 — np or jax; oracle operand
    votes: jax.Array,           # (U, K) int32 multiplicity x polarity
    schedule=None,              # kernels/sparse_infer.SparseSchedule
    *,
    use_kernel: bool | None = None,
    interpret: bool | None = None,
    autotune: bool = False,
    block_s: int | None = None,
    tile_margin=None,           # (T,) anytime margins -> exact early-exit
    **blocks,
) -> jax.Array:
    """Compiled-artifact class sums via the block-sparse chain schedule.

    Kernel path: ``sparse_infer.sparse_tm_forward`` — the scalar-prefetched
    ragged tile grid, work proportional to the artifact's include bits.
    Otherwise the jnp oracle (vacuous-AND semantics: no nonempty mask —
    valid because ``compile_tm`` artifacts give all-zero rows zero votes;
    do NOT call this with a raw model whose empty clauses carry votes).
    ``schedule=None`` builds (or, with ``autotune=True``, sweeps) the
    tiling from ``include_words``.
    """
    use_kernel, interpret = _resolve(use_kernel, interpret)
    if use_kernel:
        faults.raise_if("kernel.sparse")  # drill: chain-kernel lowering failure
        if schedule is None:
            import numpy as np

            inc_np = np.asarray(include_words)
            if autotune and not blocks and block_s is None:
                from repro.kernels import autotune as _autotune

                B = lit_words.shape[0]
                tuned = _autotune.autotune_sparse_infer_blocks(
                    B, votes.shape[1], inc_np, interpret=interpret
                )
                blocks = {k: tuned[k] for k in ("block_c", "block_j")}
                block_s = tuned["block_s"]
            # content-memoized: the schedule is an identity-hashed jit
            # static arg, so per-call rebuilds would re-lower the kernel
            schedule = _sparse_infer_kernel.build_schedule_cached(
                inc_np,
                block_c=blocks.get(
                    "block_c", _sparse_infer_kernel.DEFAULT_BLOCK_C),
                block_j=blocks.get(
                    "block_j", _sparse_infer_kernel.DEFAULT_BLOCK_J),
            )
        return _sparse_infer_kernel.sparse_tm_forward(
            lit_words, votes, schedule,
            block_s=block_s or _sparse_infer_kernel.DEFAULT_BLOCK_S,
            interpret=interpret, tile_margin=tile_margin,
        )
    # oracle path ignores tile_margin: full sums are exact, which is a
    # strictly stronger answer than early-exit promises
    fired = ref.clause_fire_ref(lit_words, jnp.asarray(include_words))
    return ref.class_sum_ref(fired, votes)


def tm_forward_factorized(
    lit_words: jax.Array,       # (B, Wa) packed literals (word-compacted)
    include_words,              # (U, Wa) uint32 — np or jax; schedule source
    votes: jax.Array,           # (U, K) int32 multiplicity x polarity
    schedule=None,              # kernels/term_infer.FactorizedSchedule
    *,
    use_kernel: bool | None = None,
    interpret: bool | None = None,
    autotune: bool = False,
    block_s: int | None = None,
    tile_margin=None,           # (T,) anytime margins -> exact early-exit
    **blocks,
) -> jax.Array:
    """Compiled-artifact class sums via the two-level FACTORIZED schedule.

    Kernel path: ``term_infer.factorized_tm_forward`` — stage 1 evaluates
    each unique (word, include-pattern) AND term once per sample slab into
    a VMEM term bitvector, stage 2 chains TERM ids per clause, so shared
    terms are computed once instead of once per clause.  Off the kernel
    path the jnp table oracle (``factorized_class_sums_ref``) runs the
    same two-level gather — both are bit-identical to dense ``ref``
    semantics for ``compile_tm`` artifacts (vacuous-AND contract as in
    ``tm_forward_schedule``).  ``schedule=None`` builds (or, with
    ``autotune=True``, sweeps) the tiling from ``include_words``.
    """
    import numpy as np

    use_kernel, interpret = _resolve(use_kernel, interpret)
    if use_kernel:
        # drill: factorized-kernel lowering failure (fires before the
        # schedule build so a demoted serve never pays it either)
        faults.raise_if("kernel.factorized")
    if schedule is None:
        inc_np = np.asarray(include_words)
        if (use_kernel and autotune and not blocks and block_s is None):
            from repro.kernels import autotune as _autotune

            B = lit_words.shape[0]
            tuned = _autotune.autotune_term_infer_blocks(
                B, votes.shape[1], inc_np, interpret=interpret
            )
            blocks = {k: tuned[k]
                      for k in ("block_c", "block_j", "block_t", "term_w")}
            block_s = tuned["block_s"]
        # content-memoized: the schedule is an identity-hashed jit static
        # arg, so per-call rebuilds would re-lower the kernel
        schedule = _term_infer_kernel.build_factorized_schedule_cached(
            inc_np,
            block_c=blocks.get(
                "block_c", _term_infer_kernel.DEFAULT_BLOCK_C),
            block_j=blocks.get(
                "block_j", _term_infer_kernel.DEFAULT_BLOCK_J),
            block_t=blocks.get(
                "block_t", _term_infer_kernel.DEFAULT_BLOCK_T),
            term_w=blocks.get("term_w"),
        )
    if use_kernel:
        return _term_infer_kernel.factorized_tm_forward(
            lit_words, votes, schedule,
            block_s=block_s or _term_infer_kernel.DEFAULT_BLOCK_S,
            interpret=interpret, tile_margin=tile_margin,
        )
    Cp = schedule.clause_chain.shape[0]
    vts = jnp.pad(votes.astype(jnp.int32), ((0, Cp - votes.shape[0]), (0, 0)))
    return _term_infer_kernel.factorized_class_sums_ref(
        lit_words, jnp.asarray(schedule.term_chain),
        jnp.asarray(schedule.clause_chain), vts,
    )


# ---------------------------------------------------------------------------
# Kernel-path TM training step (hash-RNG; matches ref.py bit-for-bit)
# ---------------------------------------------------------------------------

def feedback_probs(
    sums: jax.Array,       # (B, K) int32 CLAMPED class sums
    y: jax.Array,          # (B,) int32 targets (-1 = padded/invalid sample)
    n_classes: int,
    threshold: int,
    seed: jax.Array,       # uint32 scalar
    b_offset=0,            # global index of sample 0 (chunked training)
):
    """Per-sample feedback scalars: (kn, p_t, p_n).

    ``kn`` is the hash-sampled negative class (uniform over the K-1 others);
    ``p_t``/``p_n`` are the Type-I-side / Type-II-side clause selection
    probabilities ``(T -/+ clamp(sum))/2T``.  These are the only O(B)
    quantities the per-(sample, clause) feedback plan needs — the fused
    training kernel consumes them directly.
    """
    B = y.shape[0]
    T = threshold
    b_idx = jnp.arange(B, dtype=jnp.uint32) + jnp.uint32(b_offset)
    # negative class: hash-sampled uniformly from the K-1 others
    r_neg = ref.hash_u32(b_idx, seed ^ jnp.uint32(0x9E3779B9))
    kn = (r_neg % jnp.uint32(n_classes - 1)).astype(jnp.int32)
    kn = kn + (kn >= y)

    sum_t = jnp.take_along_axis(sums, y[:, None], axis=1)[:, 0]
    sum_n = jnp.take_along_axis(sums, kn[:, None], axis=1)[:, 0]
    p_t = (T - sum_t).astype(jnp.float32) / (2.0 * T)
    p_n = (T + sum_n).astype(jnp.float32) / (2.0 * T)
    return kn, p_t, p_n


def feedback_select(
    y: jax.Array,          # (B,) int32 targets
    kn: jax.Array,         # (B,) int32 sampled negative classes
    p_t: jax.Array,        # (B,) float32
    p_n: jax.Array,        # (B,) float32
    clause_class: jax.Array,   # (C,) int32 class id per clause
    clause_pol: jax.Array,     # (C,) int32 +1/-1 (0 = padded)
    seed: jax.Array,       # uint32 scalar
    b_offset=0,            # global index of sample 0
    c_offset=0,            # global index of clause 0 (clause-sharded step)
) -> jax.Array:
    """(B, C) uint8 feedback types: 0 none, 1 Type I, 2 Type II.

    This is the oracle the fused training kernel reproduces bit-for-bit;
    randomness is the same counter hash as ``ref.ta_delta_ref``, indexed by
    GLOBAL (sample, clause) id so sharded/chunked callers match unsharded.
    """
    B = y.shape[0]
    C = clause_class.shape[0]
    b_idx = jnp.arange(B, dtype=jnp.uint32) + jnp.uint32(b_offset)
    c_idx = (jnp.arange(C, dtype=jnp.uint32) + jnp.uint32(c_offset))[None, :]
    # hash indexed by global (b, c) via an offset-consistent mixing
    # (identical for sharded and unsharded callers)
    r_sel = ref.hash_unit(ref.hash_u32(
        b_idx[:, None] * jnp.uint32(0x9E3779B1) + c_idx,
        seed ^ jnp.uint32(0x85EBCA6B),
    ))

    is_t = clause_class[None, :] == y[:, None]                 # (B, C)
    is_n = clause_class[None, :] == kn[:, None]
    p = jnp.where(is_t, p_t[:, None], jnp.where(is_n, p_n[:, None], 0.0))
    sel = r_sel < p

    pos = clause_pol[None, :] > 0
    neg = clause_pol[None, :] < 0
    ftype = jnp.where(
        is_t & pos, 1, jnp.where(is_t & neg, 2,
        jnp.where(is_n & pos, 2, jnp.where(is_n & neg, 1, 0))),
    )
    return jnp.where(sel, ftype, 0).astype(jnp.uint8)


def feedback_plan(
    fire: jax.Array,       # (B, C) uint8 training-mode clause outputs
    y: jax.Array,          # (B,) int32 targets
    votes: jax.Array,      # (C, K) int32
    clause_class: jax.Array,   # (C,) int32 class id per clause
    clause_pol: jax.Array,     # (C,) int32 +1/-1 (0 = padded)
    threshold: int,
    seed: jax.Array,       # uint32 scalar
    b_offset=0,            # global index of fire[0] (chunked training)
    c_offset=0,            # global index of fire[:, 0] (clause-sharded step)
    sums: jax.Array | None = None,  # precomputed clamped class sums (B, K)
):
    """Compute per-(sample, clause) feedback types: 0 none, 1 Type I, 2 Type II.

    Clause-level randomness uses the same hash RNG as ``ref.ta_delta_ref``
    so the whole training step is reproducible and oracle-testable.
    """
    K = votes.shape[1]
    T = threshold
    if sums is None:
        sums = jnp.clip(fire.astype(jnp.int32) @ votes, -T, T)  # (B, K)
    kn, p_t, p_n = feedback_probs(sums, y, K, T, seed, b_offset=b_offset)
    ftype = feedback_select(
        y, kn, p_t, p_n, clause_class, clause_pol, seed,
        b_offset=b_offset, c_offset=c_offset,
    )
    return ftype, sums


_step_calls_lock = threading.Lock()
_step_calls = {"compiled": 0, "inlined": 0}


def train_step_counts() -> dict:
    """Process-wide count of training steps since import: ``compiled``,
    eager calls run as the one cached program :func:`tm_train_step`;
    ``inlined``, step bodies traced into a caller's own ``jit`` or
    ``shard_map`` (once per trace, not per call of the caller's program)."""
    with _step_calls_lock:
        return dict(_step_calls)


def _frozen(d: dict | None) -> tuple:
    """A dict of static arguments as a hashable jit cache key."""
    return tuple(sorted((d or {}).items()))


def tm_train_step_kernel(
    config,
    ta_state: jax.Array,     # (C, L) int8 — the full bank OR a clause shard
    x: jax.Array,            # (B, F) {0,1}
    y: jax.Array,            # (B,)
    seed: jax.Array,         # uint32 scalar
    batch_chunk: int | None = None,
    *,
    # bench/systems/training.py spells fuse=True; the fused step is the
    # only kernel step, so any other value is refused
    fuse: bool = True,
    autotune: bool = False,
    blocks: dict | None = None,
    b_offset=0,              # global index of sample 0 (data-sharded caller)
    c_offset=0,              # global index of clause 0 (clause-sharded caller)
    c_total: int | None = None,  # set when ta_state is a clause shard
    sums_reduce=None,        # e.g. lambda s: lax.psum(s, "model")
    use_kernel: bool | None = None,
    interpret: bool | None = None,
):
    """Full batch training step (class sums -> feedback plan -> TA delta).

    On the kernel path (``use_kernel=True`` / ``REPRO_USE_PALLAS=1``) the
    whole step is TWO kernel launches: a fused-inference pass for the
    class sums the feedback plan needs, then the fused training kernel
    (``fused_train.py``) — clause fire, feedback type, and TA delta in one
    ``pallas_call``, with the ``(B, C)`` fire and ftype matrices never
    touching HBM.  Off the kernel path the ``ref.py`` oracles run.  Both
    are bit-identical.

    An eager call (concrete arrays) runs the step as ONE compiled program,
    :func:`tm_train_step` under a cached ``jax.jit``: the configuration,
    tilings and engine switches are static, the bank, batch, seed and
    offsets traced, so a new seed or batch of the same shape never
    retraces.  ``ta_state`` is not donated: the caller keeps the pre-step
    bank.  Under a caller's trace (``jit``, ``shard_map``) the body is
    inlined into the caller's program instead.

    ``batch_chunk`` scans the batch in slices, accumulating the int32 delta —
    bit-identical to unchunked (the hash RNG is indexed by global sample id)
    but with O(chunk) working set instead of O(batch).  A ragged tail
    (``B % batch_chunk != 0``) is zero-padded to a full chunk and masked out
    of the feedback plan, so every batch size chunks bit-identically.

    ``autotune=True`` picks the fused kernels' block tilings from
    ``kernels/autotune.py``'s cached sweep (training shapes cache under
    their own key); ``blocks`` pins the fused training kernel tiling
    explicitly.

    **Clause-sharded mode** (the ``shard_map`` body of
    ``core/sharding.py:sharded_train_step_fn(engine="kernel")``): pass
    ``ta_state`` as the local ``(C_loc, L)`` shard, ``c_offset`` as its
    global clause offset (a traced ``axis_index``-derived scalar is fine),
    ``c_total=config.n_clauses_total``, and ``sums_reduce`` as the
    class-sum ``psum`` over the clause-shard axis.  ``b_offset`` is the
    global id of ``x[0]`` for data-sharded batches.  Every hash draw is
    then indexed by GLOBAL (sample, clause, literal) ids, so the returned
    shard delta equals the corresponding rows of the unsharded full-bank
    delta bit-for-bit.  NOTE: the returned ``new_ta`` applies only the
    LOCAL batch's delta — a data-sharded caller must ``psum`` the returned
    delta over its data axes and apply it to the shard itself.
    """
    if fuse is not True:
        raise ValueError(f"fuse={fuse!r}: the fused step is the only "
                         "kernel training step")
    args = (ta_state, x, y, seed, b_offset, c_offset)
    traced = any(isinstance(a, jax.core.Tracer) for a in args)
    # Spans time the eager call only: under a caller's trace the body runs
    # once, while tracing, and a span there would time the trace.
    with (contextlib.nullcontext() if traced
          else TraceAnnotation("repro.train.step")):
        fused, interpret = _resolve(use_kernel, interpret)
        infer_blocks = {}
        if fused and autotune:   # timed eagerly, outside the step's own jit
            from repro.core import packetizer
            from repro.kernels import autotune as _autotune

            B = x.shape[0]
            chunk_b = batch_chunk if (batch_chunk and B > batch_chunk) else B
            C_tot, L = ta_state.shape
            W = packetizer.n_words(config.n_literals)
            if blocks is None:
                blocks = _autotune.autotune_fused_train_blocks(
                    chunk_b, C_tot, W, L, config.n_classes,
                    interpret=interpret)
            infer_blocks = _autotune.autotune_fused_blocks(
                chunk_b, C_tot, W, config.n_classes, interpret=interpret)
        static = dict(
            config=config, batch_chunk=batch_chunk, fused=bool(fused),
            interpret=interpret, blocks=_frozen(blocks),
            infer_blocks=_frozen(infer_blocks), c_total=c_total,
            sums_reduce=sums_reduce)
        with _step_calls_lock:
            _step_calls["inlined" if traced else "compiled"] += 1
        if traced:
            return tm_train_step(*args, **static)
        with TraceAnnotation("repro.train.dispatch"):
            return _compiled_train_step(*args, **static)


def tm_train_step(ta_state, x, y, seed, b_offset, c_offset, *, config,
                  batch_chunk, fused, interpret, blocks, infer_blocks,
                  c_total, sums_reduce):
    """The body of :func:`tm_train_step_kernel`, always traced: inlined
    into a caller's program, or compiled alone as the eager step.
    ``fused`` picks the fused kernels over the ``ref.py`` oracles;
    ``blocks``/``infer_blocks`` are the fused kernels' tilings, each as
    sorted items."""
    from repro.core import packetizer, tm

    inc_words = packetizer.pack_include_masks(ta_state)
    C_loc = ta_state.shape[0]
    votes = tm.vote_matrix(config)
    c = jnp.arange(config.n_clauses_total)
    clause_class = jnp.clip(c // config.clauses_per_class, 0,
                            config.n_classes - 1)
    pol = tm.polarity(config)
    if c_total is not None:   # clause shard: local slices of the metadata
        assert c_total == config.n_clauses_total, (c_total, config)
        votes = jax.lax.dynamic_slice_in_dim(votes, c_offset, C_loc, 0)
        clause_class = jax.lax.dynamic_slice_in_dim(
            clause_class, c_offset, C_loc, 0)
        pol = jax.lax.dynamic_slice_in_dim(pol, c_offset, C_loc, 0)
    p_act = (1.0 if config.boost_true_positive
             else (config.s - 1.0) / config.s)
    T = config.threshold
    B = x.shape[0]
    b_base = jnp.asarray(b_offset).astype(jnp.uint32)

    def chunk_delta(xc, yc, b_off, valid):
        lits = tm.literals(xc)
        lit_words = packetizer.pack_bits(lits)
        if fused:
            # launch 1: class sums via the fused-inference accumulator
            # (training semantics: no nonempty mask) — bit-identical ints
            # to fire @ votes.  On a clause shard these are PARTIAL sums
            # over the local bank; ``sums_reduce`` (a psum over the
            # clause-shard axis) completes them exactly (int32 addition).
            sums = _fused_infer_kernel.fused_tm_forward(
                lit_words, inc_words, votes, None,
                interpret=interpret, **dict(infer_blocks),
            )
            if sums_reduce is not None:
                sums = sums_reduce(sums)
            kn, p_t, p_n = feedback_probs(
                jnp.clip(sums, -T, T), yc, config.n_classes, T, seed,
                b_offset=b_off,
            )
            if valid is not None:   # padded tail samples select nothing
                p_t = jnp.where(valid, p_t, 0.0)
                p_n = jnp.where(valid, p_n, 0.0)
            # launch 2: fire -> ftype -> delta, all in VMEM
            return _fused_train_kernel.fused_tm_train_delta(
                ta_state, lits, lit_words, inc_words, yc, kn, p_t,
                p_n, clause_class, pol, seed,
                p_act=p_act, p_inact=1.0 / config.s, b_offset=b_off,
                c_offset=c_offset, c_total=c_total,
                interpret=interpret, **dict(blocks),
            )
        fire = ref.clause_fire_ref(lit_words, inc_words).astype(jnp.uint8)
        sums = None
        if sums_reduce is not None:   # clause shard: complete partials
            sums = jnp.clip(
                sums_reduce(fire.astype(jnp.int32) @ votes), -T, T
            )
        ftype, _ = feedback_plan(
            fire, yc, votes, clause_class, pol, T, seed, b_offset=b_off,
            c_offset=c_offset, sums=sums,
        )
        if valid is not None:
            ftype = jnp.where(valid[:, None], ftype, jnp.uint8(0))
        return ref.ta_delta_ref(
            ta_state, lits, fire, ftype, seed,
            p_act=p_act, p_inact=1.0 / config.s, b_offset=b_off,
            c_offset=c_offset, c_total=c_total,
        )

    if batch_chunk and B > batch_chunk:
        n = -(-B // batch_chunk)
        Bp = n * batch_chunk
        xs, ys = x, y
        if Bp != B:   # ragged tail: zero-pad samples, mask their feedback
            xs = jnp.pad(x, ((0, Bp - B), (0, 0)))
            ys = jnp.pad(y, (0, Bp - B), constant_values=-1)
        xs = xs.reshape(n, batch_chunk, *x.shape[1:])
        ys = ys.reshape(n, batch_chunk)
        need_mask = Bp != B

        def body(acc, inp):
            i, xc, yc = inp
            local_off = i * jnp.uint32(batch_chunk)
            valid = (
                (jnp.arange(batch_chunk, dtype=jnp.uint32) + local_off)
                < jnp.uint32(B)
            ) if need_mask else None
            return acc + chunk_delta(xc, yc, b_base + local_off, valid), None

        delta, _ = jax.lax.scan(
            body,
            jnp.zeros(ta_state.shape, jnp.int32),
            (jnp.arange(n, dtype=jnp.uint32), xs, ys),
        )
    else:
        delta = chunk_delta(x, y, b_base, None)
    new_ta = jnp.clip(
        ta_state.astype(jnp.int32) + delta,
        -config.n_states, config.n_states - 1,
    ).astype(jnp.int8)
    return new_ta, delta


_compiled_train_step = jax.jit(
    tm_train_step,
    static_argnames=("config", "batch_chunk", "fused", "interpret", "blocks",
                     "infer_blocks", "c_total", "sums_reduce"))
