"""Shared-term factorized inference benchmark (perf trajectory tracker).

MATADOR's Fig. 5 logic absorption collapses the AND terms that overlapping
clauses share to a single gate; ``CompileStats.partial_term_sharing``
measures that opportunity and the PR-5 factorized schedule
(``kernels/term_infer.py``) exploits it: each unique (word,
include-pattern) term is evaluated ONCE per sample slab, clauses chain
term ids.  This benchmark times the same compiled artifact through three
engines on the same request stream:

  * ``factorized`` — the two-stage term-table kernel [the lead row]
  * ``sparse``     — kernels/sparse_infer.py: the flat bit-chain schedule
    (PR 4; the kernel the factorized path must beat)
  * ``dense``      — kernels/fused_infer.py at the autotuner's best dense
    tiling (streams every literal word per clause block)

The lead artifact is TRAINED at the repo's edge-XL lead shape — B=512
requests x C=4096 clauses over 4096 boolean features (W=256 literal
words) — on word-aligned 32-level THERMOMETER features (the paper's
booleanization: 128 continuous features x 32 unary levels = one packed
word per feature), so converged clauses hold multi-bit threshold runs and
the deduped bank's term sharing clears the factorized-serving threshold.
Requests are IN-DISTRIBUTION (drawn from the training generator, fresh
seed): a serving bucket fires real clauses, so neither kernel rides its
dead-slab early-exit the way an all-random stream would let it.

A synthetic sharing SWEEP rides along: fixed-shape clause banks whose
(word, value) terms are drawn from pools of decreasing size, so the
sharing fraction rises while total chain work stays constant — the
factorized speedup must GROW along these rows (the sparse kernel's time
is flat by construction).

Engines are timed in isolated per-engine loops (``_time_isolated`` —
see benchmarks/sparse_infer.py for why rotation misleads here) and
written to ``BENCH_term_infer.json`` by ``write_report`` — the cross-PR
perf trajectory file gated by scripts/check_bench.py.  On this CPU
container the kernels run in Pallas interpret mode; the factorized-vs-
sparse ratio is the tracked quantity.
"""

from __future__ import annotations

import json
import platform
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.sparse_infer import _time_isolated
from repro.core import compiler, packetizer, tm
from repro.data.booleanize import thermometer_encode
from repro.kernels import autotune as _autotune
from repro.kernels import ops, sparse_infer, term_infer

# lead shape: B x (n_cont x therm_bits) features, K classes, cpc
# clauses/class -> C=4096 clauses over F=4096 booleans (W=256 words)
LEAD = dict(B=512, n_cont=128, therm_bits=32, K=8, cpc=512)
# converged-model regime: enough steps at a high threshold that clauses
# fill in their thermometer runs (young models are 1-bit-per-word and
# under-represent a deployed artifact's sharing)
_TRAIN_SAMPLES = 2048
_TRAIN_EPOCHS = 7
_TRAIN_BATCH = 64
_NOISE = 0.15

# sharing sweep: same bank shape, term pool shrinks -> sharing rises
_SWEEP_U = 2048
_SWEEP_WORDS = 128           # active words per clause
_SWEEP_PC = 3                # bits per synthetic term
_SWEEP_SHARES = (0.0, 0.5, 0.9)


def _thermo_batch(n, *, seed, protos):
    rng = np.random.default_rng(seed)
    K, n_cont = protos.shape
    y = rng.integers(0, K, n).astype(np.int32)
    Xc = protos[y] * 1.0 + rng.normal(size=(n, n_cont)) * _NOISE
    return thermometer_encode(Xc, LEAD["therm_bits"]), y


def _train_artifact(seed: int = 0):
    """Train a TM on word-aligned thermometer features and compile it;
    returns (cfg, protos, compiled)."""
    rng = np.random.default_rng(seed)
    protos = rng.normal(size=(LEAD["K"], LEAD["n_cont"]))
    X, y = _thermo_batch(_TRAIN_SAMPLES, seed=seed + 1, protos=protos)
    cfg = tm.TMConfig(n_features=X.shape[1], n_classes=LEAD["K"],
                      clauses_per_class=LEAD["cpc"], threshold=200, s=30.0)
    Xj, yj = jnp.asarray(X), jnp.asarray(y)
    ta = tm.init(cfg, jax.random.PRNGKey(seed)).ta_state
    step = jax.jit(
        lambda t_, x, yy, s: ops.tm_train_step_kernel(cfg, t_, x, yy, s)[0]
    )
    k = 0
    n_batches = _TRAIN_SAMPLES // _TRAIN_BATCH
    for _ in range(_TRAIN_EPOCHS):
        for i in range(n_batches):
            sl = slice(i * _TRAIN_BATCH, (i + 1) * _TRAIN_BATCH)
            ta = step(ta, Xj[sl], yj[sl], jnp.uint32(k))
            k += 1
    ta.block_until_ready()
    return cfg, protos, compiler.compile_tm(cfg, ta)


def _synthetic_bank(share: float, *, Wa: int = 128, seed: int = 0):
    """(include_words, votes) with a CONTROLLED term-sharing fraction.

    Every clause activates ``_SWEEP_WORDS`` distinct words; each active
    word's include pattern is drawn from a per-word pool of
    ``_SWEEP_PC``-bit values sized so that
    ``1 - n_unique_terms / n_refs ~= share``.  Chain length, word count,
    and bit count per clause are identical across the sweep — only the
    sharing changes, so the sparse kernel's work is flat and any
    factorized trend is attributable to sharing alone.
    """
    rng = np.random.default_rng(seed)
    U = _SWEEP_U
    iw = np.zeros((U, Wa), np.uint32)
    # column-major assignment so every word serves refs_w = U*W/Wa refs:
    # the first u_w refs get DISTINCT values (u_w = refs_w * (1-share)),
    # the rest reuse them — realized sharing hits the target exactly
    # instead of depending on pool-collision luck
    refs_of_word = [[] for _ in range(Wa)]
    for c in range(U):
        for w in rng.choice(Wa, _SWEEP_WORDS, replace=False):
            refs_of_word[w].append(c)
    for w in range(Wa):
        refs = refs_of_word[w]
        u_w = max(1, round(len(refs) * (1.0 - share)))
        vals = set()
        while len(vals) < u_w:
            bits = rng.choice(32, _SWEEP_PC, replace=False)
            vals.add(int(sum(1 << b for b in bits)))
        vals = np.array(sorted(vals), np.uint32)
        for i, c in enumerate(refs):
            iw[c, w] = vals[i] if i < u_w else vals[rng.integers(u_w)]
    votes = rng.integers(-2, 3, (U, 8), dtype=np.int32)
    return iw, votes


def _biased_literals(B: int, Wa: int, *, p: float = 0.95, seed: int = 1):
    """Packed literal words with high bit density, so synthetic chains
    survive several tiles (an all-random stream kills every clause in the
    first tile and both kernels just ride their early-exits)."""
    rng = np.random.default_rng(seed)
    bits = (rng.random((B, Wa * 32)) < p).astype(np.uint8)
    return jnp.asarray(packetizer.pack_bits_np(bits))


def run(fast: bool = True, reps: int = 5, autotune: bool = True) -> list:
    _, interpret = ops.kernel_dispatch(True, None)
    rows = []

    # -- lead row: the trained thermometer artifact --------------------
    cfg, protos, comp = _train_artifact()
    Xr, _ = _thermo_batch(LEAD["B"], seed=777, protos=protos)
    lit = jnp.asarray(packetizer.pack_literals(jnp.asarray(Xr)))
    # both schedule kernels are tuned ON THE MEASURED STREAM (word-
    # compacted, as run_compiled serves it): a uniform-random sweep lets
    # trained chains die in their first tile and crowns tilings that lose
    # on live traffic — best-vs-best on the same bucket keeps the
    # comparison honest
    lit_rep = np.asarray(lit[:, comp.word_ids])

    fblocks = (
        _autotune.autotune_term_infer_blocks(
            LEAD["B"], comp.n_classes, comp.include_words,
            interpret=interpret, lit_words=lit_rep)
        if autotune else {}
    )
    sblocks = (
        _autotune.autotune_sparse_infer_blocks(
            LEAD["B"], comp.n_classes, comp.include_words,
            interpret=interpret, lit_words=lit_rep)
        if autotune else {}
    )
    dblocks = (
        _autotune.autotune_fused_blocks(
            LEAD["B"], comp.n_unique, comp.n_words_active, comp.n_classes,
            interpret=interpret)
        if autotune else {}
    )

    def compiled_fwd(engine, **blk):
        jitted = jax.jit(lambda l: compiler.run_compiled(
            comp, l, engine=engine, interpret=interpret, **blk,
        ))
        return lambda: jitted(lit)

    t = _time_isolated(
        dict(
            factorized=compiled_fwd("factorized", **fblocks),
            sparse=compiled_fwd("sparse", **sblocks),
            dense=compiled_fwd("dense", **dblocks),
        ),
        reps,
    )
    fsched = comp.factorized_schedule(
        fblocks.get("block_c"), fblocks.get("block_j"),
        fblocks.get("block_t"), fblocks.get("term_w"))
    W = comp.stats.n_words_dense
    tag = f"b{LEAD['B']}_c{cfg.n_clauses_total}_w{W}_k{comp.n_classes}"
    fblk = ";".join(f"{k}={v}" for k, v in sorted(fblocks.items()))
    rows.append((
        f"terminfer_factorized_{tag}", t["factorized"] * 1e6,
        f"speedup_vs_sparse={t['sparse'] / t['factorized']:.2f}x;"
        f"partial_term_sharing={comp.stats.partial_term_sharing:.4f};"
        f"realized_term_sharing={fsched.realized_term_sharing:.4f};"
        f"n_terms={fsched.n_terms};n_term_refs={fsched.n_term_refs}"
        + (f";{fblk}" if fblk else ""),
    ))
    rows.append((
        f"terminfer_sparse_{tag}", t["sparse"] * 1e6,
        "flat_bit_chain_schedule;" + ";".join(
            f"{k}={v}" for k, v in sorted(sblocks.items())),
    ))
    rows.append((
        f"terminfer_dense_{tag}", t["dense"] * 1e6,
        f"compiled_dense_fused;speedup_factorized="
        f"{t['dense'] / t['factorized']:.2f}x",
    ))

    # -- sharing sweep: speedup must GROW with the sharing fraction ----
    # tilings are PINNED across the sweep (and term_w pinned above the
    # synthetic popcount so no term splits): every row runs identical
    # chain work through identical grids, so the trend is attributable to
    # the sharing fraction alone — sparse time is flat by construction.
    # fast (CI) mode keeps only the gated lead rows above; the committed
    # BENCH file's sweep rows come from a full run.
    for share in () if fast else _SWEEP_SHARES:
        iw, votes = _synthetic_bank(share)
        slit = _biased_literals(LEAD["B"], iw.shape[1])
        vts = jnp.asarray(votes)
        fs = term_infer.build_factorized_schedule_cached(
            iw, block_c=1024, block_j=128, block_t=32768, term_w=4)
        ss = sparse_infer.build_schedule_cached(
            iw, block_c=2048, block_j=128)

        def fact_fwd():
            jitted = jax.jit(lambda l: term_infer.factorized_tm_forward(
                l, vts, fs, block_s=16, interpret=interpret))
            return lambda: jitted(slit)

        def sparse_fwd():
            jitted = jax.jit(lambda l: sparse_infer.sparse_tm_forward(
                l, vts, ss, block_s=16, interpret=interpret))
            return lambda: jitted(slit)

        ts = _time_isolated(dict(factorized=fact_fwd(),
                                 sparse=sparse_fwd()), reps)
        rows.append((
            f"terminfer_sweep_share{int(share * 100):02d}",
            ts["factorized"] * 1e6,
            f"speedup_vs_sparse={ts['sparse'] / ts['factorized']:.2f}x;"
            f"realized_term_sharing={fs.realized_term_sharing:.4f};"
            f"n_terms={fs.n_terms};sparse_us={ts['sparse'] * 1e6:.0f}",
        ))
    return rows


def write_report(rows: list, path: str = "BENCH_term_infer.json") -> None:
    _, interpret = ops.kernel_dispatch(True, None)
    report = dict(
        benchmark="term_infer",
        backend=jax.default_backend(),
        interpret_mode=bool(interpret),
        jax_version=jax.__version__,
        platform=platform.platform(),
        autotune_cache=_autotune.cache_path(),
        rows=[dict(name=n, us_per_call=us, derived=d) for n, us, d in rows],
    )
    with open(path, "w") as f:
        json.dump(report, f, indent=1)
