"""Block-sparse compiled-schedule inference: exactness + schedule shape.

The central property: for ANY automata state, inference through the
compiled chain schedule (``kernels/sparse_infer.py`` — clause clustering,
bit-level chains, scalar-prefetched ragged tile grid, early-exit) produces
BIT-identical class sums to dense ``ref``-semantics inference — across
dedup on/off, empty-clause-only models, single-active-word models, ragged
batch tails, and a clause-sharded emulated 4-device mesh.

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
from repro.kernels import ops, sparse_infer

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


def _check_schedule_equals_dense(n_features, n_classes, cpc, density, seed,
                                 batch=16, dedup=True):
    """Schedule-kernel class sums == dense inference, bit for bit."""
    cfg, ta = _random_tm(n_features, n_classes, cpc, density, seed)
    comp = compiler.compile_tm(cfg, ta, dedup=dedup)
    x = jnp.asarray(np.random.default_rng(seed + 1).integers(
        0, 2, (batch, n_features), dtype=np.uint8))
    dense = tm.class_sums(cfg, jnp.asarray(ta), tm.literals(x),
                          training=False)
    xp = packetizer.pack_literals(x)
    # engine="sparse" (not "auto"): these tests exist to cover the flat
    # bit-chain kernel; the PR-5 heuristic would route high-sharing random
    # banks to the factorized kernel and quietly drop that coverage
    sp = compiler.run_compiled(comp, xp, engine="sparse", interpret=True)
    np.testing.assert_array_equal(np.asarray(dense), np.asarray(sp))


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
    def test_schedule_equals_dense(n_features, n_classes, cpc, density,
                                   seed, batch, dedup):
        _check_schedule_equals_dense(n_features, n_classes, cpc, density,
                                     seed, batch=batch, dedup=dedup)


@pytest.mark.parametrize(
    "n_features,n_classes,cpc,density,seed,batch,dedup",
    [
        (3, 2, 2, 0.0, 0, 5, True),       # empty-clause-only model
        (3, 2, 2, 0.0, 0, 5, False),      # ... with dedup off
        (17, 3, 5, 0.05, 11, 7, True),    # sparse ragged batch tail
        (80, 5, 12, 0.3, 4242, 33, True),  # dense upper corner
        (33, 2, 7, 0.15, 977, 64, False),  # no dedup: duplicate rows kept
        (64, 4, 10, 0.02, 5, 40, True),   # wide + very sparse chains
    ],
)
def test_schedule_equals_dense_fixed(n_features, n_classes, cpc, density,
                                     seed, batch, dedup):
    """Fixed-seed fallback for the central property (always runs)."""
    _check_schedule_equals_dense(n_features, n_classes, cpc, density, seed,
                                 batch=batch, dedup=dedup)


def test_single_active_word_model():
    """Every clause includes exactly one literal: one-step chains, and the
    schedule's tile table collapses to one tile per clause block."""
    cfg = tm.TMConfig(n_features=40, n_classes=2, clauses_per_class=6)
    C, L = 12, 80
    ta = np.full((C, L), -5, np.int8)
    for c in range(C):
        ta[c, (c * 7) % L] = 3              # one include each
    comp = compiler.compile_tm(cfg, ta)
    sched = comp.default_schedule
    assert sched.n_tiles == sched.n_cblocks
    np.testing.assert_array_equal(sched.counts,
                                  np.ones(sched.n_cblocks, np.int32))
    _check_schedule_equals_dense_state(cfg, ta, batch=9, seed=0)


def test_empty_clause_only_model():
    """All-exclude bank: the degenerate artifact has zero chain tiles and
    the schedule path returns all-zero sums without launching a kernel."""
    cfg = tm.TMConfig(n_features=8, n_classes=2, clauses_per_class=2)
    ta = np.full((4, 16), -5, np.int8)
    comp = compiler.compile_tm(cfg, ta)
    assert comp.default_schedule.n_tiles == 0
    x = jnp.asarray(np.random.default_rng(0).integers(0, 2, (3, 8),
                                                      dtype=np.uint8))
    sums = compiler.run_compiled(
        comp, packetizer.pack_literals(x),
        engine="sparse", interpret=True)
    np.testing.assert_array_equal(np.asarray(sums), 0)


def _check_schedule_equals_dense_state(cfg, ta, batch, seed):
    comp = compiler.compile_tm(cfg, ta)
    x = jnp.asarray(np.random.default_rng(seed).integers(
        0, 2, (batch, cfg.n_features), dtype=np.uint8))
    dense = tm.class_sums(cfg, jnp.asarray(ta), tm.literals(x),
                          training=False)
    sp = compiler.run_compiled(comp, packetizer.pack_literals(x),
                               engine="sparse", interpret=True)
    np.testing.assert_array_equal(np.asarray(dense), np.asarray(sp))


@pytest.mark.parametrize("batch", [1, 31, 32, 33, 64, 97])
def test_ragged_batch_tails(batch):
    """Sample-word packing (32 samples/word) handles every tail exactly:
    padded sample bits read literal 0, so non-empty clauses report 0 and
    the padded rows are sliced away."""
    cfg, ta = _random_tm(24, 3, 6, 0.12, 9)
    _check_schedule_equals_dense_state(cfg, ta, batch=batch, seed=1)


def test_schedule_csr_invariants():
    cfg, ta = _random_tm(60, 4, 10, 0.08, 3)
    comp = compiler.compile_tm(cfg, ta)
    for bc, bj in [(8, 8), (32, 16), (512, 32)]:
        s = comp.schedule(bc, bj)
        assert s.n_tiles == int(s.counts.sum())
        np.testing.assert_array_equal(np.diff(s.indptr), s.counts)
        # per block: tiles are contiguous, first/last flags bracket them
        for b in range(s.n_cblocks):
            lo, hi = int(s.indptr[b]), int(s.indptr[b + 1])
            if lo == hi:
                continue
            np.testing.assert_array_equal(s.tile_cb[lo:hi], b)
            np.testing.assert_array_equal(s.tile_jb[lo:hi],
                                          np.arange(hi - lo))
            assert s.tile_first[lo] == 1 and s.tile_last[hi - 1] == 1
            assert s.tile_first[lo + 1:hi].sum() == 0
            assert s.tile_last[lo:hi - 1].sum() == 0
        # chain entries beyond each clause's include count are sentinels
        bits = packetizer.unpack_bits_np(
            np.ascontiguousarray(comp.include_words), s.n_lit_bits)
        for c in range(comp.n_unique):
            n = int(bits[c].sum())
            np.testing.assert_array_equal(
                s.chain_ids[c, :n], np.nonzero(bits[c])[0])
            assert (s.chain_ids[c, n:] == s.n_lit_bits).all()
        assert 0.0 <= s.tile_sparsity <= 1.0


def test_pad_tiles_are_noops():
    """pad_tiles_to appends all-sentinel never-first/last tiles that leave
    class sums untouched (the cross-shard tile-count equalizer)."""
    cfg, ta = _random_tm(30, 2, 8, 0.1, 4)
    comp = compiler.compile_tm(cfg, ta)
    base = sparse_infer.build_schedule(comp.include_words,
                                      block_c=8, block_j=8)
    padded = sparse_infer.build_schedule(comp.include_words, block_c=8,
                                        block_j=8,
                                        pad_tiles_to=base.n_tiles + 5)
    assert padded.n_tiles == base.n_tiles + 5
    assert (padded.tile_first[base.n_tiles:] == 0).all()
    assert (padded.tile_last[base.n_tiles:] == 0).all()
    x = jnp.asarray(np.random.default_rng(0).integers(0, 2, (11, 30),
                                                      dtype=np.uint8))
    xp = packetizer.pack_literals(x)[:, jnp.asarray(comp.word_ids)]
    votes = jnp.asarray(comp.votes)
    a = sparse_infer.sparse_tm_forward(xp, votes, base, interpret=True)
    b = sparse_infer.sparse_tm_forward(xp, votes, padded, interpret=True)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_cluster_order_preserves_sums():
    """Clustering is a pure permutation: votes travel with their rows.

    compile_tm(cluster=True) lays rows out in anytime.margin_order —
    vote-mass bands descending, density-clustered within each band — so
    the check is against that permutation, and class sums must be
    bit-identical between the plain and reordered banks.
    """
    from repro.kernels import anytime

    cfg, ta = _random_tm(40, 3, 8, 0.1, 7)
    plain = compiler.compile_tm(cfg, ta, cluster=False)
    clustered = compiler.compile_tm(cfg, ta, cluster=True)
    order = anytime.margin_order(plain.include_words, plain.votes,
                                 cluster_fn=sparse_infer.cluster_order)
    np.testing.assert_array_equal(plain.include_words[order],
                                  clustered.include_words)
    np.testing.assert_array_equal(plain.votes[order], clustered.votes)
    # vote mass (the banding key) never climbs back above a prior band
    mass = np.abs(clustered.votes.astype(np.int64)).sum(axis=1)
    top = int(mass.max())
    with np.errstate(divide="ignore"):
        band = np.floor(np.log2(top / np.maximum(mass, 1)))
    band = np.clip(band, 0, 7)
    band[mass == 0] = 8
    assert (np.diff(band) >= 0).all()
    # reordering is sum-preserving: both banks score identically
    x = jnp.asarray(np.random.default_rng(3).integers(0, 2, (9, 40),
                                                      dtype=np.uint8))
    xw = packetizer.pack_literals(x)
    a = ops.tm_forward_schedule(xw[:, jnp.asarray(plain.word_ids)],
                                plain.include_words,
                                jnp.asarray(plain.votes), use_kernel=False)
    b = ops.tm_forward_schedule(xw[:, jnp.asarray(clustered.word_ids)],
                                clustered.include_words,
                                jnp.asarray(clustered.votes),
                                use_kernel=False)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_ops_dispatch_kernel_equals_oracle():
    """ops.tm_forward_schedule: kernel path == jnp oracle (and the traced
    table oracle) bit-for-bit."""
    cfg, ta = _random_tm(50, 4, 9, 0.07, 21)
    comp = compiler.compile_tm(cfg, ta)
    x = jnp.asarray(np.random.default_rng(2).integers(0, 2, (19, 50),
                                                      dtype=np.uint8))
    xw = packetizer.pack_literals(x)[:, jnp.asarray(comp.word_ids)]
    votes = jnp.asarray(comp.votes)
    kern = ops.tm_forward_schedule(xw, comp.include_words, votes,
                                   use_kernel=True, interpret=True)
    oracle = ops.tm_forward_schedule(xw, comp.include_words, votes,
                                     use_kernel=False)
    sched = comp.default_schedule
    table_oracle = sparse_infer.schedule_class_sums_ref(
        xw, jnp.asarray(sched.chain_ids),
        jnp.pad(votes, ((0, sched.chain_ids.shape[0] - comp.n_unique),
                        (0, 0))))
    np.testing.assert_array_equal(np.asarray(kern), np.asarray(oracle))
    np.testing.assert_array_equal(np.asarray(kern),
                                  np.asarray(table_oracle))


def test_stacked_shard_schedules_compose_exactly():
    """Per-shard tile tables (common-shape padded) sum to the unsharded
    class sums — the single-process version of the mesh invariant."""
    cfg, ta = _random_tm(45, 3, 12, 0.09, 13)
    comp = compiler.compile_tm(cfg, ta)
    x = jnp.asarray(np.random.default_rng(3).integers(0, 2, (21, 45),
                                                      dtype=np.uint8))
    xw = packetizer.pack_literals(x)[:, jnp.asarray(comp.word_ids)]
    dense = tm.class_sums(cfg, jnp.asarray(ta), tm.literals(x),
                          training=False)
    for n_shards in (2, 4):
        schedules, chains, votes_st, tiles, C_loc = (
            sparse_infer.stack_shard_schedules(
                comp.include_words, comp.votes, n_shards,
                block_c=16, block_j=8))
        total = np.zeros_like(np.asarray(dense))
        for s in range(n_shards):
            part = sparse_infer.sparse_tm_forward_tables(
                xw, jnp.asarray(chains[s]), jnp.asarray(votes_st[s]),
                jnp.asarray(tiles[s]),
                block_c=schedules[s].block_c,
                block_j=schedules[s].block_j, interpret=True)
            total += np.asarray(part)
        np.testing.assert_array_equal(np.asarray(dense), total)


def test_save_load_keeps_schedule():
    cfg, ta = _random_tm(30, 3, 6, 0.1, 7)
    comp = compiler.compile_tm(cfg, ta)
    with tempfile.TemporaryDirectory() as d:
        path = os.path.join(d, "model.npz")
        comp.save(path)
        back = compiler.CompiledTM.load(path)
    assert back._schedules, "artifact should ship its default schedule"
    sched = next(iter(back._schedules.values()))
    ref_sched = comp.default_schedule
    np.testing.assert_array_equal(ref_sched.chain_ids, sched.chain_ids)
    np.testing.assert_array_equal(ref_sched.tile_cb, sched.tile_cb)
    np.testing.assert_array_equal(ref_sched.counts, sched.counts)


def test_bit_transpose_roundtrip():
    rng = np.random.default_rng(0)
    for B, W in [(7, 3), (32, 1), (65, 4)]:
        words = jnp.asarray(rng.integers(0, 2**32, (B, W), dtype=np.uint32))
        litT = sparse_infer.bit_transpose_literals(words, W * 32)
        assert litT.shape == (W * 32 + 1, packetizer.n_words(B))
        np.testing.assert_array_equal(np.asarray(litT[-1]), 0xFFFFFFFF)
        bits = packetizer.unpack_bits_np(np.asarray(words), W * 32)
        back = packetizer.unpack_bits_np(np.asarray(litT[:-1]),
                                         packetizer.n_words(B) * 32)
        np.testing.assert_array_equal(bits, back[:, :B].T)


def test_autotune_sparse_keys(tmp_path, monkeypatch):
    """The sparse sweep caches under artifact-hashed sparse_infer: keys and
    returns the schedule-tiling block names."""
    import json

    from repro.kernels import autotune

    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(tmp_path / "t.json"))
    cfg, ta = _random_tm(20, 2, 4, 0.1, 0)
    comp = compiler.compile_tm(cfg, ta)
    blocks = autotune.autotune_sparse_infer_blocks(
        9, 2, comp.include_words, interpret=True,
        candidates=((8, 8, 1), (16, 8, 1)), reps=1)
    assert set(blocks) == {"block_c", "block_j", "block_s"}
    cache = json.loads((tmp_path / "t.json").read_text())
    keys = [k for k in cache["entries"] if k.startswith("sparse_infer:")]
    assert len(keys) == 1 and ":sig" in keys[0]
    # a different artifact of the SAME shape must not share the entry
    cfg2, ta2 = _random_tm(20, 2, 4, 0.1, 99)
    comp2 = compiler.compile_tm(cfg2, ta2)
    autotune.autotune_sparse_infer_blocks(
        9, 2, comp2.include_words, interpret=True,
        candidates=((8, 8, 1), (16, 8, 1)), reps=1)
    cache = json.loads((tmp_path / "t.json").read_text())
    assert len([k for k in cache["entries"]
                if k.startswith("sparse_infer:")]) == 2


# ---------------------------------------------------------------------------
# Emulated multi-device: the clause-sharded compiled schedule
# ---------------------------------------------------------------------------

_MESH_CODE = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, jax.numpy as jnp, numpy as np
from repro.core import tm, compiler, packetizer, sharding
from repro.kernels import sparse_infer

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
    schedules, chains, votes, tiles, C_loc = (
        sparse_infer.stack_shard_schedules(
            comp.include_words, comp.votes, n_model, block_c=32, block_j=8))
    for uk in (True, False):   # Pallas schedule kernel and jnp table oracle
        fwd = sharding.sharded_schedule_forward_fn(
            mesh, block_c=schedules[0].block_c,
            block_j=schedules[0].block_j, use_kernel=uk, interpret=True)
        out = fwd(jnp.asarray(chains), jnp.asarray(votes),
                  jnp.asarray(tiles), xw)
        np.testing.assert_array_equal(np.asarray(dense), np.asarray(out))
print("SHARDED_SCHEDULE_BITEXACT_OK")
"""


@pytest.mark.multidevice
def test_clause_sharded_schedule_bit_identical():
    """The compiled schedule, clause-sharded over an emulated 4-device
    mesh (each shard carrying its own tile table + one int32 psum), equals
    dense single-device inference EXACTLY — kernel and oracle engines, on
    a pure-model mesh and a (data x model) mesh."""
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"),
               JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, "-c", _MESH_CODE], env=env,
                       capture_output=True, text=True, timeout=600,
                       cwd=REPO)
    assert "SHARDED_SCHEDULE_BITEXACT_OK" in r.stdout, r.stdout + r.stderr


@pytest.mark.multidevice
def test_serve_mesh_sparse_schedule_wiring():
    """`serve --mesh model=2` end-to-end on the sparse-schedule path."""
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"),
               JAX_PLATFORMS="cpu", REPRO_USE_PALLAS="1",
               XLA_FLAGS="--xla_force_host_platform_device_count=2")
    r = subprocess.run(
        [sys.executable, "-m", "repro.launch.serve", "--arch", "tm-mnist",
         "--requests", "64", "--bucket", "32", "--epochs", "1",
         "--n-train", "128", "--mesh", "model=2"],
        env=env, capture_output=True, text=True, timeout=600, cwd=REPO,
    )
    assert r.returncode == 0, r.stdout + r.stderr
    assert "clause-sharded sparse-schedule" in r.stdout, r.stdout + r.stderr
    assert "inf/s" in r.stdout, r.stdout + r.stderr
