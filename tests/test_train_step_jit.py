"""The eager training step runs as one compiled program.

``ops.tm_train_step_kernel`` called with concrete arrays runs its body,
``ops.tm_train_step``, under one cached ``jax.jit``; called under a
caller's trace it inlines the same body.  Both must give the same bits,
a new seed or batch of one shape must not build the step again, and the
caller's bank must survive the step (it is not donated).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import tm
from repro.kernels import ops
from repro.launch.compile_cache import compile_counts

KERNEL = dict(use_kernel=True, interpret=True)
ORACLE = dict(use_kernel=False)


def _problem(B, seed, F=17, K=3, cpc=7, threshold=9):
    rng = np.random.default_rng(seed)
    cfg = tm.TMConfig(n_features=F, n_classes=K, clauses_per_class=cpc,
                      threshold=threshold, s=4.0)
    ta = jnp.asarray(rng.integers(
        -30, 30, (cfg.n_clauses_total, cfg.n_literals), dtype=np.int8))
    x = jnp.asarray(rng.integers(0, 2, (B, F), dtype=np.uint8))
    y = jnp.asarray(rng.integers(0, K, B, dtype=np.int32))
    return cfg, ta, x, y


@pytest.mark.parametrize("engine,B,chunk,b_offset,shard", [
    (KERNEL, 13, None, 0, False),
    (KERNEL, 21, 8, 37, False),      # chunked, ragged tail, offset batch
    (KERNEL, 13, None, 5, True),     # a clause shard at a nonzero offset
    (ORACLE, 13, None, 0, False),
    (ORACLE, 21, 8, 37, False),
    (ORACLE, 13, None, 5, True),
], ids=["fused", "fused-chunked", "fused-shard", "oracle", "oracle-chunked",
        "oracle-shard"])
def test_compiled_step_equals_the_inlined_body(engine, B, chunk, b_offset,
                                               shard):
    cfg, ta, x, y = _problem(B, seed=B + b_offset)
    kw = dict(engine)
    c_offset = 0
    if shard:   # the second of two clause shards, as core/sharding.py's body
        C = cfg.n_clauses_total
        c_offset = C // 2
        ta = ta[c_offset:]
        kw.update(c_total=C)

    @jax.jit
    def inlined(ta, x, y, seed, b_off, c_off):
        return ops.tm_train_step_kernel(cfg, ta, x, y, seed, chunk,
                                        b_offset=b_off, c_offset=c_off, **kw)

    for seed in (3, 77, 2**31 + 11):
        calls = ops.train_step_counts()
        got = ops.tm_train_step_kernel(cfg, ta, x, y, jnp.uint32(seed), chunk,
                                       b_offset=b_offset, c_offset=c_offset,
                                       **kw)
        want = inlined(ta, x, y, jnp.uint32(seed), b_offset, c_offset)
        now = ops.train_step_counts()
        assert now["compiled"] == calls["compiled"] + 1
        # the caller's jit traces the body on its first call only
        assert now["inlined"] - calls["inlined"] == (seed == 3)
        for g, w in zip(got, want):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
        assert np.abs(np.asarray(got[1])).sum() > 0   # the step trained


@pytest.mark.parametrize("engine", [KERNEL, ORACLE], ids=["fused", "oracle"])
def test_new_seeds_and_batches_build_the_step_once(engine):
    # a threshold no other test uses, so the step's cache starts empty
    cfg, ta, _, _ = _problem(8, seed=1, threshold=23)
    rng = np.random.default_rng(2)
    feed = [(jnp.asarray(rng.integers(0, 2, (16, 17), dtype=np.uint8)),
             jnp.asarray(rng.integers(0, 3, 16, dtype=np.int32)),
             jnp.uint32(s)) for s in range(5)]
    compile_counts()                        # listeners from here on
    k0 = compile_counts()
    ta, _ = ops.tm_train_step_kernel(cfg, ta, *feed[0], **engine)
    jax.block_until_ready(ta)
    k1 = compile_counts()
    for x, y, seed in feed[1:]:
        ta, _ = ops.tm_train_step_kernel(cfg, ta, x, y, seed, **engine)
    jax.block_until_ready(ta)
    k2 = compile_counts()
    name = "jit(tm_train_step)"
    assert k1["names"].get(name, 0) - k0["names"].get(name, 0) == 1
    assert k2["names"] == k1["names"]
    assert k2["traces"] == k1["traces"]
    assert k2["executables"] == k1["executables"]


def test_the_callers_bank_is_not_donated():
    cfg, ta, x, y = _problem(13, seed=4)
    before = np.asarray(ta)
    new, delta = ops.tm_train_step_kernel(cfg, ta, x, y, jnp.uint32(9),
                                          **KERNEL)
    jax.block_until_ready(new)
    assert not ta.is_deleted()
    np.testing.assert_array_equal(np.asarray(ta), before)
    np.testing.assert_array_equal(
        np.asarray(new),
        np.clip(before.astype(np.int32) + np.asarray(delta),
                -cfg.n_states, cfg.n_states - 1).astype(np.int8))


def test_autotuned_tilings_resolve_before_the_step_compiles(monkeypatch):
    from repro.kernels import autotune

    asked = []

    def tuner(kind):
        def tune(*shape, interpret):
            asked.append((kind, shape))
            return dict(block_b=8, block_c=128, block_w=1)
        return tune

    monkeypatch.setattr(autotune, "autotune_fused_train_blocks",
                        tuner("train"))
    monkeypatch.setattr(autotune, "autotune_fused_blocks", tuner("infer"))
    cfg, ta, x, y = _problem(21, seed=6)
    C, L = ta.shape
    W = -(-L // 32)
    want = ops.tm_train_step_kernel(cfg, ta, x, y, jnp.uint32(5), **ORACLE)
    got = ops.tm_train_step_kernel(cfg, ta, x, y, jnp.uint32(5), 8,
                                   autotune=True, **KERNEL)
    # shapes of one chunk of 8 samples, as plain ints: no tracer reached them
    assert asked == [("train", (8, C, W, L, 3)), ("infer", (8, C, W, 3))]
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
