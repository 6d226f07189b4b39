"""Substrate tests: optimizer, checkpointing, data pipeline, runtime."""

import os
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

from repro.checkpoint import CheckpointManager, load_checkpoint, save_checkpoint
from repro.data import ShardedBatcher, make_boolean_classification, thermometer_encode
from repro.data.booleanize import quantile_binarize
from repro.optim import adamw
from repro.runtime import PreemptionHandler, StragglerMonitor


# -- optimizer ---------------------------------------------------------------

def test_adamw_reduces_quadratic():
    cfg = adamw.AdamWConfig(lr=0.1, warmup_steps=1, decay_steps=100, weight_decay=0.0)
    params = {"w": jnp.asarray([3.0, -2.0])}
    opt = adamw.adamw_init(params)
    loss = lambda p: jnp.sum(p["w"] ** 2)
    for _ in range(60):
        g = jax.grad(loss)(params)
        params, opt, info = adamw.adamw_update(cfg, g, params, opt)
    assert float(loss(params)) < 0.05
    assert int(opt.step) == 60


def test_grad_clipping():
    cfg = adamw.AdamWConfig(clip_norm=1.0)
    g = {"w": jnp.asarray([1e6, 1e6])}
    params = {"w": jnp.zeros(2)}
    opt = adamw.adamw_init(params)
    _, _, info = adamw.adamw_update(cfg, g, params, opt)
    assert float(info["grad_norm"]) > 1e5  # reported pre-clip


def test_lr_warmup():
    cfg = adamw.AdamWConfig(lr=1.0, warmup_steps=10, decay_steps=100)
    assert float(adamw._schedule(cfg, jnp.int32(1))) < 0.2
    assert float(adamw._schedule(cfg, jnp.int32(10))) >= 0.99


# -- gradient compression (single-shard semantics) ---------------------------

def test_compression_error_feedback_roundtrip():
    from repro.optim import compress

    # on one device use shard_map over a 1-device mesh axis
    mesh = jax.make_mesh((1,), ("data",))
    g = {"w": jnp.asarray(np.random.default_rng(0).normal(size=(64,)), jnp.float32)}
    err = compress.init_error(g)

    def f(g, e):
        return compress.compressed_allreduce(g, e, "data")

    from jax.sharding import PartitionSpec as P

    out, new_err = jax.jit(
        jax.shard_map(f, mesh=mesh, in_specs=(P(), P()),
                      out_specs=(P(), P()), check_vma=False)
    )(g, err)
    # quantized value + residual reconstructs the original exactly
    np.testing.assert_allclose(
        np.asarray(out["w"] + new_err["w"]), np.asarray(g["w"]), atol=1e-6
    )
    # 8-bit quantization error bounded by scale
    scale = float(jnp.abs(g["w"]).max()) / 127.0
    assert float(jnp.abs(new_err["w"]).max()) <= scale * 0.5 + 1e-6


# -- checkpointing ------------------------------------------------------------

def test_checkpoint_roundtrip_and_retention():
    tree = {"a": jnp.arange(5, dtype=jnp.float32), "b": {"c": jnp.ones((2, 3))}}
    with tempfile.TemporaryDirectory() as d:
        mgr = CheckpointManager(d, max_to_keep=2)
        for step in (1, 2, 3):
            mgr.save(step, tree, extra={"step": step})
        mgr.wait()
        assert mgr.latest_step() == 3
        assert sorted(os.listdir(d)) == ["step_0000000002", "step_0000000003"]
        restored, extra = mgr.restore(tree)
        np.testing.assert_array_equal(np.asarray(restored["a"]), np.arange(5))
        assert extra["step"] == 3


def test_checkpoint_async_and_atomic():
    tree = {"w": jnp.zeros((100, 100))}
    with tempfile.TemporaryDirectory() as d:
        mgr = CheckpointManager(d)
        mgr.save(7, tree, blocking=False)
        mgr.wait()
        assert mgr.latest_step() == 7
        assert not any(p.endswith(".tmp") for p in os.listdir(d))


def test_checkpoint_elastic_restore_with_sharding():
    from jax.sharding import NamedSharding, PartitionSpec as P

    mesh = jax.make_mesh((1,), ("data",))
    tree = {"w": jnp.arange(8, dtype=jnp.float32)}
    shardings = {"w": NamedSharding(mesh, P("data"))}
    with tempfile.TemporaryDirectory() as d:
        save_checkpoint(d, 1, tree)
        restored, _ = load_checkpoint(d, tree, shardings=shardings)
        np.testing.assert_array_equal(np.asarray(restored["w"]), np.arange(8))
        assert restored["w"].sharding == shardings["w"]


# -- data pipeline ------------------------------------------------------------

def test_loader_deterministic_and_resumable():
    X = np.arange(100)[:, None]
    y = np.arange(100)
    a = ShardedBatcher((X, y), 10, seed=3, prefetch=0)
    it = iter(a)
    seen = [next(it)[1] for _ in range(7)]
    state = a.state_dict()

    b = ShardedBatcher((X, y), 10, seed=3, prefetch=0)
    b.load_state_dict(state)
    nxt_a = next(it)[1]
    nxt_b = next(iter(b))[1]
    np.testing.assert_array_equal(nxt_a, nxt_b)


def test_loader_process_sharding_partitions():
    X = np.arange(64)[:, None]
    y = np.arange(64)
    seen = set()
    for pi in range(4):
        l = ShardedBatcher((X, y), 4, shuffle=False, process_index=pi,
                           process_count=4, prefetch=0)
        it = iter(l)
        for _ in range(4):
            seen.update(next(it)[1].tolist())
    assert seen == set(range(64))


def test_loader_prefetch_thread():
    X = np.arange(32)[:, None]
    y = np.arange(32)
    l = ShardedBatcher((X, y), 8, prefetch=2)
    it = iter(l)
    batches = [next(it) for _ in range(6)]  # crosses an epoch boundary
    assert all(b[0].shape == (8, 1) for b in batches)


def _check_thermometer_monotone(n_bits):
    x = np.random.default_rng(0).normal(size=(20, 3))
    th = thermometer_encode(x, n_bits=n_bits).reshape(20, 3, n_bits)
    # thermometer property: once a bit is 0, all higher bits are 0
    diffs = np.diff(th.astype(int), axis=-1)
    assert (diffs <= 0).all()


if HAVE_HYPOTHESIS:
    @pytest.mark.hypothesis_optional
    @settings(max_examples=15, deadline=None)
    @given(st.integers(2, 6))
    def test_thermometer_monotone(n_bits):
        _check_thermometer_monotone(n_bits)


@pytest.mark.parametrize("n_bits", [2, 3, 6])
def test_thermometer_monotone_fixed(n_bits):
    _check_thermometer_monotone(n_bits)


def test_quantile_binarize_shape():
    x = np.random.default_rng(0).normal(size=(50, 4))
    q = quantile_binarize(x, n_bits=3)
    assert q.shape == (50, 12)
    assert set(np.unique(q)) <= {0, 1}


def test_synthetic_is_learnable_by_construction():
    X, y = make_boolean_classification(500, 64, 4, seed=0)
    # class prototypes make same-class samples more similar
    same = ((X[y == 0][:10, None] == X[y == 0][None, :10]).mean())
    diff = ((X[y == 0][:10, None] == X[y == 1][None, :10]).mean())
    assert same > diff


# -- runtime -------------------------------------------------------------------

def test_straggler_monitor_flags_slow_step():
    import time

    mon = StragglerMonitor(threshold=3.0, warmup=2)
    for s in range(6):
        mon.start_step()
        time.sleep(0.002)
        mon.end_step(s)
    mon.start_step()
    time.sleep(0.05)
    flagged = mon.end_step(6)
    assert flagged is not None and flagged["step"] == 6
    assert mon.events


def test_straggler_monitor_back_to_back_stragglers_both_flag():
    """Flagged outliers must not fold into the EWMA: the second of two
    consecutive stragglers used to compare against a baseline poisoned by
    the first and slip under the threshold."""
    import time

    mon = StragglerMonitor(alpha=0.5, threshold=3.0, warmup=1)

    def step(idx, dt):
        mon.start_step()
        mon._t0 = time.monotonic() - dt      # simulate a dt-second step
        return mon.end_step(idx)

    for s in range(4):
        assert step(s, 0.01) is None         # healthy baseline ~10ms
    ewma_before = mon.ewma
    first = step(4, 0.5)
    second = step(5, 0.5)                    # back-to-back straggler
    assert first is not None and second is not None
    assert [e["step"] for e in mon.events] == [4, 5]
    # the baseline still tracks the healthy distribution
    assert mon.ewma == ewma_before


def test_preemption_handler_flag():
    h = PreemptionHandler()
    assert not h.preempted
    h.trigger()
    assert h.preempted


def test_preemption_nested_install_chains_and_unwinds():
    """install() chains to the previous handler (both flags flip) and
    uninstall() unwinds like a stack, restoring what was there before."""
    import os
    import signal

    before = signal.getsignal(signal.SIGTERM)
    outer = PreemptionHandler(signals=(signal.SIGTERM,)).install()
    inner = PreemptionHandler(signals=(signal.SIGTERM,)).install()
    try:
        os.kill(os.getpid(), signal.SIGTERM)
        # chained delivery: the inner handler ran AND forwarded to outer
        assert inner.preempted and outer.preempted
        # idempotent: re-install without uninstall is a no-op
        handler_now = signal.getsignal(signal.SIGTERM)
        inner.install()
        assert signal.getsignal(signal.SIGTERM) is handler_now
    finally:
        inner.uninstall()
        outer_handler = signal.getsignal(signal.SIGTERM)
        outer.uninstall()
    # after the inner unwind, only the outer flag flips on a new signal
    assert callable(outer_handler)
    # fully unwound: the pre-test handler is back, and a never-installed
    # handler uninstalls as a no-op
    assert signal.getsignal(signal.SIGTERM) is before
    PreemptionHandler().uninstall()
