"""Shared-term factorized compiled inference: exactness + schedule shape.

The central property: for ANY automata state, inference through the
two-level factorized schedule (``kernels/term_infer.py`` — unique
(word, include-pattern) AND terms evaluated once per sample slab, clauses
rewritten as term-id chains) produces BIT-identical class sums to dense
``ref``-semantics inference AND to the flat block-sparse chain schedule —
across dedup on/off, zero-sharing artifacts (every term unique),
fully-shared artifacts (one term everywhere), fat-term splitting, ragged
batch tails, save/load round-trips, and a clause-sharded emulated
4-device mesh.

``hypothesis`` is optional (fixed-seed fallbacks keep the checks in
tier-1), matching the repo-wide ``hypothesis_optional`` pattern.
"""

import os
import subprocess
import sys
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False

from repro.core import compiler, packetizer, tm
from repro.kernels import ops, term_infer

pytestmark = pytest.mark.schedule

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _random_tm(n_features, n_classes, cpc, include_density, seed):
    rng = np.random.default_rng(seed)
    C = n_classes * cpc
    ta = np.where(
        rng.random((C, 2 * n_features)) < include_density,
        rng.integers(0, 127, (C, 2 * n_features)),
        rng.integers(-128, 0, (C, 2 * n_features)),
    ).astype(np.int8)
    cfg = tm.TMConfig(n_features=n_features, n_classes=n_classes,
                      clauses_per_class=cpc)
    return cfg, ta


def _check_factorized_equals_dense(n_features, n_classes, cpc, density,
                                   seed, batch=16, dedup=True, term_w=None):
    """Factorized-kernel class sums == dense inference == the flat sparse
    schedule, bit for bit."""
    cfg, ta = _random_tm(n_features, n_classes, cpc, density, seed)
    comp = compiler.compile_tm(cfg, ta, dedup=dedup)
    x = jnp.asarray(np.random.default_rng(seed + 1).integers(
        0, 2, (batch, n_features), dtype=np.uint8))
    dense = tm.class_sums(cfg, jnp.asarray(ta), tm.literals(x),
                          training=False)
    xp = packetizer.pack_literals(x)
    fact = compiler.run_compiled(comp, xp, engine="factorized",
                                 interpret=True, term_w=term_w)
    flat = compiler.run_compiled(comp, xp, engine="sparse", interpret=True)
    np.testing.assert_array_equal(np.asarray(dense), np.asarray(fact))
    np.testing.assert_array_equal(np.asarray(flat), np.asarray(fact))


if HAVE_HYPOTHESIS:
    @pytest.mark.hypothesis_optional
    @settings(max_examples=20, deadline=None)
    @given(
        n_features=st.integers(3, 80),
        n_classes=st.integers(2, 5),
        cpc=st.integers(2, 12),
        density=st.floats(0.0, 0.3),
        seed=st.integers(0, 10_000),
        batch=st.integers(1, 70),
        dedup=st.booleans(),
    )
    def test_factorized_equals_dense(n_features, n_classes, cpc, density,
                                     seed, batch, dedup):
        _check_factorized_equals_dense(n_features, n_classes, cpc, density,
                                       seed, batch=batch, dedup=dedup)


@pytest.mark.parametrize(
    "n_features,n_classes,cpc,density,seed,batch,dedup,term_w",
    [
        (3, 2, 2, 0.0, 0, 5, True, None),     # empty-clause-only model
        (3, 2, 2, 0.0, 0, 5, False, None),    # ... with dedup off
        (17, 3, 5, 0.05, 11, 7, True, None),  # sparse ragged batch tail
        (80, 5, 12, 0.3, 4242, 33, True, 2),  # dense + forced fat-term split
        (33, 2, 7, 0.15, 977, 64, False, 4),  # no dedup: duplicate rows kept
        (64, 4, 10, 0.02, 5, 40, True, None),  # wide + very sparse chains
    ],
)
def test_factorized_equals_dense_fixed(n_features, n_classes, cpc, density,
                                       seed, batch, dedup, term_w):
    """Fixed-seed fallback for the central property (always runs)."""
    _check_factorized_equals_dense(n_features, n_classes, cpc, density, seed,
                                   batch=batch, dedup=dedup, term_w=term_w)


def test_zero_sharing_artifact():
    """Every clause includes a distinct single word pattern: every term is
    unique (realized sharing 0), the term table is as large as the chain
    reference count, and execution is still exact."""
    cfg = tm.TMConfig(n_features=64, n_classes=2, clauses_per_class=8)
    C, L = 16, 128
    ta = np.full((C, L), -5, np.int8)
    for c in range(C):
        ta[c, (c * 8) % L] = 3              # distinct single-bit words
        ta[c, (c * 8 + 1) % L] = 3
    comp = compiler.compile_tm(cfg, ta)
    sched = comp.default_factorized_schedule
    assert sched.realized_term_sharing == 0.0
    assert sched.n_terms == sched.n_term_refs
    _check_state(cfg, ta, batch=9, seed=0)


def test_fully_shared_artifact():
    """One term everywhere: every clause includes the SAME word pattern
    (plus a per-clause discriminator so dedup keeps them apart) — the
    shared term collapses to one table row referenced by all clauses."""
    cfg = tm.TMConfig(n_features=64, n_classes=2, clauses_per_class=8)
    C, L = 16, 128
    ta = np.full((C, L), -5, np.int8)
    ta[:, 3] = 3                            # the shared term (word 0, bit 3)
    ta[:, 5] = 3                            # ... two bits wide
    comp = compiler.compile_tm(cfg, ta, dedup=False)
    sched = comp.default_factorized_schedule
    assert sched.n_terms == 1
    assert sched.n_term_refs == comp.n_unique
    assert sched.realized_term_sharing == pytest.approx(
        1.0 - 1.0 / comp.n_unique)
    _check_state(cfg, ta, batch=11, seed=1, dedup=False)
    # with a distinct second word per clause the shared term still
    # amortizes: n_terms = 1 shared + C distinct
    ta2 = ta.copy()
    for c in range(C):
        ta2[c, 64 + ((c * 4) % 64)] = 3
    comp2 = compiler.compile_tm(cfg, ta2)
    sched2 = comp2.default_factorized_schedule
    assert sched2.n_terms == 1 + comp2.n_unique
    _check_state(cfg, ta2, batch=11, seed=2)


def _check_state(cfg, ta, batch, seed, dedup=True):
    comp = compiler.compile_tm(cfg, ta, dedup=dedup)
    x = jnp.asarray(np.random.default_rng(seed).integers(
        0, 2, (batch, cfg.n_features), dtype=np.uint8))
    dense = tm.class_sums(cfg, jnp.asarray(ta), tm.literals(x),
                          training=False)
    sp = compiler.run_compiled(comp, packetizer.pack_literals(x),
                               engine="factorized", interpret=True)
    np.testing.assert_array_equal(np.asarray(dense), np.asarray(sp))


@pytest.mark.parametrize("batch", [1, 31, 32, 33, 64, 97])
def test_ragged_batch_tails(batch):
    """Sample-word packing (32 samples/word) handles every tail exactly
    through the two-stage kernel."""
    cfg, ta = _random_tm(24, 3, 6, 0.12, 9)
    _check_state(cfg, ta, batch=batch, seed=1)


def test_factorized_schedule_invariants():
    cfg, ta = _random_tm(60, 4, 10, 0.08, 3)
    comp = compiler.compile_tm(cfg, ta)
    for bc, bj, bt, tw in [(8, 2, 8, 2), (32, 4, 16, 4), (512, 8, 64, None)]:
        s = comp.factorized_schedule(bc, bj, bt, tw)
        # CSR over clause tiles; stage-1 tiles precede every clause tile
        assert s.n_tiles >= s.n_term_tiles + int(s.counts.sum())
        np.testing.assert_array_equal(np.diff(s.indptr), s.counts)
        stages = s.tile_stage
        assert (stages[: s.n_term_tiles] == 0).all()
        assert (stages[s.n_term_tiles:] == 1).all()
        # every term row's chain: real ids then sentinels; padding rows all
        # sentinel; every chain id < n_lit_bits + 1
        assert s.term_chain.shape[1] == s.term_w
        assert (s.term_chain[s.n_terms:] == s.n_lit_bits).all()
        assert s.term_chain.max() <= s.n_lit_bits
        # clause chains reference real terms or the sentinel term
        assert s.clause_chain.max() <= s.n_terms
        # reconstruct every clause's include bits from its term chain:
        # the factorization is exact by construction
        bits = packetizer.unpack_bits_np(
            np.ascontiguousarray(comp.include_words), s.n_lit_bits)
        for c in range(comp.n_unique):
            ids = s.clause_chain[c]
            ids = ids[ids < s.n_terms]
            got = np.zeros(s.n_lit_bits, np.uint8)
            for t in ids:
                lids = s.term_chain[t]
                got[lids[lids < s.n_lit_bits]] = 1
            np.testing.assert_array_equal(got, bits[c])


def test_fat_terms_split_into_shared_pieces():
    """A term wider than term_w splits into <= term_w-bit pieces, and two
    fat terms sharing a sub-pattern share its piece."""
    iw = np.zeros((2, 1), np.uint32)
    iw[0, 0] = 0b111101          # bits 0,2,3,4,5
    iw[1, 0] = 0b1101            # bits 0,2,3 — the first piece of row 0
    s = term_infer.build_factorized_schedule(iw, block_c=8, block_j=2,
                                             block_t=8, term_w=3)
    # row 0 -> pieces {0,2,3} + {4,5}; row 1 -> piece {0,2,3} (shared)
    assert s.n_terms == 2
    assert s.n_term_refs == 3
    lit = jnp.asarray(np.array([[0b111101], [0b1101], [0b101]], np.uint32))
    votes = jnp.asarray(np.array([[1, 0], [0, 1]], np.int32))
    out = term_infer.factorized_tm_forward(lit, votes, s, interpret=True)
    np.testing.assert_array_equal(
        np.asarray(out), [[1, 1], [0, 1], [0, 0]])


def test_ops_dispatch_kernel_equals_oracle():
    """ops.tm_forward_factorized: kernel path == jnp table oracle == the
    flat schedule op, bit-for-bit."""
    cfg, ta = _random_tm(50, 4, 9, 0.07, 21)
    comp = compiler.compile_tm(cfg, ta)
    x = jnp.asarray(np.random.default_rng(2).integers(0, 2, (19, 50),
                                                      dtype=np.uint8))
    xw = packetizer.pack_literals(x)[:, jnp.asarray(comp.word_ids)]
    votes = jnp.asarray(comp.votes)
    kern = ops.tm_forward_factorized(xw, comp.include_words, votes,
                                     use_kernel=True, interpret=True)
    oracle = ops.tm_forward_factorized(xw, comp.include_words, votes,
                                       use_kernel=False)
    flat = ops.tm_forward_schedule(xw, comp.include_words, votes,
                                   use_kernel=True, interpret=True)
    np.testing.assert_array_equal(np.asarray(kern), np.asarray(oracle))
    np.testing.assert_array_equal(np.asarray(kern), np.asarray(flat))


def test_run_compiled_heuristic_default():
    """The kernel ladder's head serves the factorized schedule exactly when
    the artifact's partial_term_sharing clears the threshold (both routes
    stay bit-identical, so the check is on the memoized schedule tables)."""
    # high-sharing artifact: every clause carries the same two-word core
    cfg = tm.TMConfig(n_features=64, n_classes=2, clauses_per_class=8)
    C, L = 16, 128
    ta = np.full((C, L), -5, np.int8)
    ta[:, 3] = 3
    ta[:, 40] = 3
    for c in range(C):
        ta[c, 64 + ((c * 4) % 64)] = 3
    comp = compiler.compile_tm(cfg, ta)
    assert comp.stats.partial_term_sharing \
        >= compiler.FACTORIZE_SHARING_THRESHOLD
    x = jnp.asarray(np.random.default_rng(0).integers(0, 2, (9, 64),
                                                      dtype=np.uint8))
    xp = packetizer.pack_literals(x)
    compiler.run_compiled(comp, xp,
                          engine=ops.engine_levels(comp, use_kernel=True)[0],
                          interpret=True)
    assert comp._fschedules, "heuristic should have built the factorized " \
        "schedule"
    # a low-sharing artifact stays on the flat schedule
    cfg2, ta2 = _random_tm(24, 2, 4, 0.08, 0)
    comp2 = compiler.compile_tm(cfg2, ta2)
    assert comp2.stats.partial_term_sharing \
        < compiler.FACTORIZE_SHARING_THRESHOLD
    x2 = jnp.asarray(np.random.default_rng(1).integers(0, 2, (9, 24),
                                                       dtype=np.uint8))
    xp2 = packetizer.pack_literals(x2)
    compiler.run_compiled(comp2, xp2,
                          engine=ops.engine_levels(comp2, use_kernel=True)[0],
                          interpret=True)
    assert not comp2._fschedules
    assert comp2._schedules
    # the factorized engine named below the sharing threshold takes its
    # own tiling keys...
    compiler.run_compiled(comp2, xp2, engine="factorized", interpret=True,
                          term_w=2)
    assert comp2._fschedules
    # ... and another engine with such a key fails loudly (a tuned config
    # is never silently dropped)
    with pytest.raises(TypeError, match="takes no tiling"):
        compiler.run_compiled(comp2, xp2, engine="sparse", interpret=True,
                              block_t=16)


def test_stacked_shard_factorized_composes_exactly():
    """Per-shard term + tile tables (common-shape padded) sum to the
    unsharded class sums — the single-process version of the mesh
    invariant."""
    cfg, ta = _random_tm(45, 3, 12, 0.09, 13)
    comp = compiler.compile_tm(cfg, ta)
    x = jnp.asarray(np.random.default_rng(3).integers(0, 2, (21, 45),
                                                      dtype=np.uint8))
    xw = packetizer.pack_literals(x)[:, jnp.asarray(comp.word_ids)]
    dense = tm.class_sums(cfg, jnp.asarray(ta), tm.literals(x),
                          training=False)
    for n_shards in (2, 4):
        scheds, terms, chains, votes_st, tiles, C_loc = (
            term_infer.stack_shard_factorized(
                comp.include_words, comp.votes, n_shards,
                block_c=16, block_j=4, block_t=32))
        assert len({s.block_t for s in scheds}) == 1, \
            "shards must share one static block_t"
        total = np.zeros_like(np.asarray(dense))
        for s in range(n_shards):
            part = term_infer.factorized_tm_forward_tables(
                xw, jnp.asarray(terms[s]), jnp.asarray(chains[s]),
                jnp.asarray(votes_st[s]), jnp.asarray(tiles[s]),
                block_t=scheds[s].block_t, block_c=scheds[s].block_c,
                block_j=scheds[s].block_j, interpret=True)
            total += np.asarray(part)
        np.testing.assert_array_equal(np.asarray(dense), total)


def test_save_load_keeps_factorized_schedule_and_tuned():
    cfg, ta = _random_tm(30, 3, 6, 0.1, 7)
    comp = compiler.compile_tm(cfg, ta)
    comp.record_tuned("term_infer", 512,
                      dict(block_c=64, block_j=8, block_t=32, block_s=4,
                           term_w=2))
    with tempfile.TemporaryDirectory() as d:
        path = os.path.join(d, "model.npz")
        comp.save(path)
        back = compiler.CompiledTM.load(path)
    assert back._fschedules, "artifact should ship its factorized schedule"
    sched = next(iter(back._fschedules.values()))
    ref_sched = comp.default_factorized_schedule
    np.testing.assert_array_equal(ref_sched.term_chain, sched.term_chain)
    np.testing.assert_array_equal(ref_sched.clause_chain, sched.clause_chain)
    np.testing.assert_array_equal(ref_sched.tile_stage, sched.tile_stage)
    np.testing.assert_array_equal(ref_sched.counts, sched.counts)
    assert sched.term_w == ref_sched.term_w
    # the loaded schedule answers the default lookup without a rebuild
    assert back.default_factorized_schedule is sched
    # recorded tilings round-trip for cold-start serving
    assert back.tuned_blocks("term_infer", 512) == dict(
        block_c=64, block_j=8, block_t=32, block_s=4, term_w=2)
    assert back.tuned_blocks("term_infer", 256) is None
    assert back.tuned_blocks("sparse_infer", 512) is None
    # context-keyed recall: a shard-slice sweep or another backend/mode
    # must not answer for the full bank (and vice versa)
    comp.record_tuned("term_infer", 512, dict(block_c=8), rows=10,
                      mode="cpu:interp")
    assert comp.tuned_blocks("term_infer", 512, rows=10,
                             mode="cpu:interp") == dict(block_c=8)
    assert comp.tuned_blocks("term_infer", 512, rows=40,
                             mode="cpu:interp") is None
    assert comp.tuned_blocks("term_infer", 512, rows=10,
                             mode="tpu:compiled") is None


def test_autotune_term_keys(tmp_path, monkeypatch):
    """The factorized sweep caches under artifact-hashed term_infer: keys
    and returns the five-knob tiling dict."""
    import json

    from repro.kernels import autotune

    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(tmp_path / "t.json"))
    cfg, ta = _random_tm(20, 2, 4, 0.1, 0)
    comp = compiler.compile_tm(cfg, ta)
    blocks = autotune.autotune_term_infer_blocks(
        9, 2, comp.include_words, interpret=True,
        candidates=((8, 2, 8, 1, 0), (16, 2, 8, 1, 2)), reps=1)
    assert set(blocks) == {"block_c", "block_j", "block_t", "block_s",
                           "term_w"}
    cache = json.loads((tmp_path / "t.json").read_text())
    keys = [k for k in cache["entries"] if k.startswith("term_infer:")]
    assert len(keys) == 1 and ":sig" in keys[0]
    # a different artifact of the SAME shape must not share the entry
    cfg2, ta2 = _random_tm(20, 2, 4, 0.1, 99)
    comp2 = compiler.compile_tm(cfg2, ta2)
    autotune.autotune_term_infer_blocks(
        9, 2, comp2.include_words, interpret=True,
        candidates=((8, 2, 8, 1, 0), (16, 2, 8, 1, 2)), reps=1)
    cache = json.loads((tmp_path / "t.json").read_text())
    assert len([k for k in cache["entries"]
                if k.startswith("term_infer:")]) == 2


def test_realized_sharing_matches_compile_stat():
    """With no fat-term splits the schedule's realized sharing equals the
    compiler's measured partial_term_sharing opportunity exactly."""
    cfg, ta = _random_tm(40, 3, 10, 0.1, 17)
    comp = compiler.compile_tm(cfg, ta)
    sched = comp.factorized_schedule(term_w=32)   # no splits at full width
    assert sched.realized_term_sharing == pytest.approx(
        comp.stats.partial_term_sharing)
    assert sched.n_terms == comp.stats.n_partial_terms_unique
    assert sched.n_term_refs == comp.stats.n_partial_terms_dense


# ---------------------------------------------------------------------------
# Emulated multi-device: the clause-sharded factorized schedule
# ---------------------------------------------------------------------------

_MESH_CODE = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, jax.numpy as jnp, numpy as np
from repro.core import tm, compiler, packetizer, sharding
from repro.kernels import term_infer

rng = np.random.default_rng(0)
cfg = tm.TMConfig(n_features=48, n_classes=4, clauses_per_class=20)
ta = np.where(rng.random((80, 96)) < 0.08,
              rng.integers(0, 127, (80, 96)),
              rng.integers(-128, 0, (80, 96))).astype(np.int8)
comp = compiler.compile_tm(cfg, ta)
X = jnp.asarray(rng.integers(0, 2, (24, 48), dtype=np.uint8))
xw = packetizer.pack_literals(X)[:, jnp.asarray(comp.word_ids)]
dense = tm.class_sums(cfg, jnp.asarray(ta), tm.literals(X), training=False)
for shape, axes in (((4,), ("model",)), ((2, 2), ("data", "model"))):
    mesh = jax.make_mesh(shape, axes)
    n_model = mesh.shape["model"]
    scheds, terms, chains, votes, tiles, C_loc = (
        term_infer.stack_shard_factorized(
            comp.include_words, comp.votes, n_model,
            block_c=32, block_j=4, block_t=32))
    for uk in (True, False):   # Pallas factorized kernel and jnp oracle
        fwd = sharding.sharded_factorized_forward_fn(
            mesh, block_t=scheds[0].block_t, block_c=scheds[0].block_c,
            block_j=scheds[0].block_j, use_kernel=uk, interpret=True)
        out = fwd(jnp.asarray(terms), jnp.asarray(chains),
                  jnp.asarray(votes), jnp.asarray(tiles), xw)
        np.testing.assert_array_equal(np.asarray(dense), np.asarray(out))
print("SHARDED_FACTORIZED_BITEXACT_OK")
"""


@pytest.mark.multidevice
def test_clause_sharded_factorized_bit_identical():
    """The factorized schedule, clause-sharded over an emulated 4-device
    mesh (each shard carrying its own term + tile tables + one int32
    psum), equals dense single-device inference EXACTLY — kernel and
    oracle engines, on a pure-model mesh and a (data x model) mesh."""
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"),
               JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, "-c", _MESH_CODE], env=env,
                       capture_output=True, text=True, timeout=600,
                       cwd=REPO)
    assert "SHARDED_FACTORIZED_BITEXACT_OK" in r.stdout, r.stdout + r.stderr


@pytest.mark.multidevice
def test_serve_mesh_factorized_wiring(tmp_path):
    """`serve --artifact ... --mesh model=2` end-to-end on the FACTORIZED
    path: a saved high-sharing artifact (every clause carries a shared
    two-word core) clears the factorize threshold, so the mesh branch
    must build per-shard term tables and report the factorized path —
    the sparse-schedule fallback would fail the path assert."""
    from repro.configs.matador_tm import TM_CONFIGS

    cfg = TM_CONFIGS["tm-mnist"]
    C, L = cfg.n_clauses_raw, cfg.n_literals
    ta = np.full((C, L), -5, np.int8)
    ta[:, 3] = 3
    ta[:, 40] = 3                     # the shared two-word core
    for c in range(C):
        ta[c, 200 + (c % 600)] = 3    # per-clause discriminator word
    comp = compiler.compile_tm(cfg, ta)
    assert comp.stats.partial_term_sharing \
        >= compiler.FACTORIZE_SHARING_THRESHOLD
    path = os.path.join(str(tmp_path), "artifact.npz")
    comp.save(path)

    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"),
               JAX_PLATFORMS="cpu", REPRO_USE_PALLAS="1",
               XLA_FLAGS="--xla_force_host_platform_device_count=2")
    r = subprocess.run(
        [sys.executable, "-m", "repro.launch.serve", "--arch", "tm-mnist",
         "--requests", "64", "--bucket", "32", "--mesh", "model=2",
         "--artifact", path],
        env=env, capture_output=True, text=True, timeout=600, cwd=REPO,
    )
    assert r.returncode == 0, r.stdout + r.stderr
    assert f"loaded artifact {path}" in r.stdout, r.stdout + r.stderr
    assert "clause-sharded factorized-schedule" in r.stdout, \
        r.stdout + r.stderr
    assert "inf/s" in r.stdout, r.stdout + r.stderr
