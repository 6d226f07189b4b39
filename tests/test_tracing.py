"""The program's own spans and counters: what a profiler trace of the
serving path and the training step holds, the gateway's flush-delay and
handoff counters, the compile counter, and the serve loop's hooks through
the runner builder (``launch/serve.py:make_runner``).

Traces are recorded on the CPU with ``jax.profiler`` and read back with
``jax.profiler.ProfileData``, as the benchmark reads the chip's.
"""

import asyncio
import glob

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import compiler, packetizer, tm
from repro.data import ShardedBatcher, make_boolean_classification
from repro.kernels import ops
from repro.launch.compile_cache import compile_counts
from repro.launch.serve import build_parser, make_runner, serve_tm
from repro.runtime import faults
from repro.runtime.gateway import Gateway

RUNNER = ("repro.runner.pad", "repro.runner.copy_in",
          "repro.runner.dispatch", "repro.runner.wait",
          "repro.runner.copy_out")
# the children of ``repro.train.step``: an eager step, on the fused kernel
# path and the oracle path alike, is one compiled program dispatched whole
TRAIN = {True: ("repro.train.dispatch",), False: ("repro.train.dispatch",)}
CONFIG = tm.TMConfig(n_features=32, n_classes=3, clauses_per_class=8)
BUCKET = 32


def _spans(tmp_path, prefixes=("repro.", "test.")):
    """[(name, start_ns, end_ns)] of the host spans of the one trace
    recorded under ``tmp_path``."""
    path = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    assert len(path) == 1, path
    pd = jax.profiler.ProfileData.from_file(path[0])
    return sorted(
        ((ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
         for plane in pd.planes if plane.name.startswith("/host:")
         for line in plane.lines for ev in line.events
         if ev.name.startswith(prefixes)), key=lambda s: s[1])


def _inside(spans, outer):
    _, a, b = outer
    return [s for s in spans if a <= s[1] and s[2] <= b]


def _ladder():
    state = tm.init(CONFIG, jax.random.PRNGKey(0))
    art = compiler.compile_tm(CONFIG, state.ta_state)
    engine = jax.jit(lambda xw: compiler.run_compiled(
        art, xw, engine="oracle").argmax(-1))
    return ops.EngineLadder([("oracle", lambda: engine)])


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """Four full buckets and one age flush through a gateway over the
    builder's runner, traced; each runner call inside a ``test.call``."""
    tmp = tmp_path_factory.mktemp("served")
    X, _ = make_boolean_classification(4 * BUCKET + 5, 32, 3, seed=0)
    xp = np.asarray(packetizer.pack_literals(jnp.asarray(X)))
    ladder = _ladder()
    ladder.run(lambda: jnp.asarray(xp[:BUCKET]), bucket="warm", count=False)
    run_rows = make_runner(ladder, BUCKET, xp.shape[1], xp.dtype)

    def runner(tenant, rows, quality=0):
        with jax.profiler.TraceAnnotation("test.call"):
            return run_rows(rows, quality)

    async def go():
        gw = await Gateway(runner, bucket=BUCKET, max_wait=0.05).start()
        futs = [gw.offer("t0", row) for row in xp]
        res = await asyncio.gather(*futs)
        return res, await gw.drain()

    jax.profiler.start_trace(str(tmp))
    res, health = asyncio.run(go())
    jax.profiler.stop_trace()
    return _spans(tmp), res, health, ladder


def test_runner_spans_one_of_each_per_bucket_inside_the_call(served):
    spans, res, health, _ = served
    calls = [s for s in spans if s[0] == "test.call"]
    assert all(r.ok for r in res)
    assert len(calls) == health["buckets"] == 5
    assert health["flushes"] == {"full": 4, "age": 1, "drain": 0}
    for call in calls:
        names = [s[0] for s in _inside(spans, call)]
        for phase in RUNNER:
            assert names.count(phase) == 1, (phase, names)
    assert sum(s[0].startswith("repro.runner.") for s in spans) == 5 * 5


def test_gateway_spans_one_resolve_and_flush_per_bucket(served):
    spans, _, health, _ = served
    names = [s[0] for s in spans]
    assert names.count("repro.gateway.resolve") == health["buckets"]
    assert names.count("repro.gateway.flush") == health["buckets"]
    assert names.count("repro.gateway.wait") >= 1
    # no runner call overlaps a resolve: the dispatcher answers a bucket
    # once its runner has returned
    calls = [s for s in spans if s[0] == "test.call"]
    for r in (s for s in spans if s[0] == "repro.gateway.resolve"):
        assert not any(c[1] < r[2] and r[1] < c[2] for c in calls)


def test_runner_builder_pads_answers_and_wraps_hooks(served):
    *_, ladder = served
    X, _ = make_boolean_classification(5, 32, 3, seed=4)
    rows = np.asarray(packetizer.pack_literals(jnp.asarray(X)))
    padded = np.zeros((BUCKET, rows.shape[1]), rows.dtype)
    padded[:5] = rows
    want = np.asarray(ladder.run(lambda: jnp.asarray(padded), count=False))
    seen = []

    def on_bucket(i):
        seen.append(("before", i))

        def after(info):
            seen.append(("after", i, info["quality"]))
            return dict(info, tag=i)

        return after

    plain = make_runner(ladder, BUCKET, rows.shape[1], rows.dtype)
    preds, info = plain(rows[:3])
    np.testing.assert_array_equal(preds, want[:3])
    assert info == dict(quality=0, err_bound=None)
    hooked = make_runner(ladder, BUCKET, rows.shape[1], rows.dtype,
                         on_bucket=on_bucket)
    for _ in range(2):
        preds, info = hooked(list(rows))
        np.testing.assert_array_equal(preds, want[:5])
    assert info == dict(quality=0, err_bound=None, tag=1)
    assert seen == [("before", 0), ("after", 0, 0),
                    ("before", 1), ("after", 1, 0)]


def _train_feed(n_steps):
    X, y = make_boolean_classification(64 * n_steps, 32, 3, seed=1)
    return iter(ShardedBatcher((X, y), 64, seed=3))


@pytest.mark.parametrize("kernel", [True, False], ids=["fused", "oracle"])
def test_train_spans_nest_inside_the_eager_step(tmp_path, kernel):
    ta = tm.init(CONFIG, jax.random.PRNGKey(0)).ta_state
    feed = _train_feed(2)
    xb, yb = next(feed)
    ops.tm_train_step_kernel(CONFIG, ta, jnp.asarray(xb), jnp.asarray(yb),
                             jnp.uint32(1), use_kernel=kernel)   # compile
    calls = ops.train_step_counts()
    jax.profiler.start_trace(str(tmp_path))
    for s in range(2):
        xb, yb = next(feed)
        ta, _ = ops.tm_train_step_kernel(CONFIG, ta, jnp.asarray(xb),
                                         jnp.asarray(yb), jnp.uint32(s),
                                         use_kernel=kernel)
    jax.block_until_ready(ta)
    jax.profiler.stop_trace()
    assert ops.train_step_counts() == dict(
        compiled=calls["compiled"] + 2, inlined=calls["inlined"])
    spans = _spans(tmp_path)
    steps = [s for s in spans if s[0] == "repro.train.step"]
    assert len(steps) == 2
    assert [s[0] for s in spans].count("repro.data.wait") == 2
    for step in steps:
        names = [s[0] for s in _inside(spans, step)]
        assert sorted(names) == sorted(("repro.train.step",) + TRAIN[kernel])


def test_no_train_spans_when_the_step_runs_under_jit(tmp_path):
    ta = tm.init(CONFIG, jax.random.PRNGKey(0)).ta_state
    xb, yb = next(_train_feed(1))
    step = jax.jit(lambda ta, x, y, s: ops.tm_train_step_kernel(
        CONFIG, ta, x, y, s)[0])
    x, y = jnp.asarray(xb), jnp.asarray(yb)
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("test.jit"):
        new = step(ta, x, y, jnp.uint32(1))        # traces the body here
        jax.block_until_ready(new)
    jax.profiler.stop_trace()
    eager, _ = ops.tm_train_step_kernel(CONFIG, ta, x, y, jnp.uint32(1))
    np.testing.assert_array_equal(np.asarray(new), np.asarray(eager))
    names = [s[0] for s in _spans(tmp_path)]
    assert "test.jit" in names
    assert not [n for n in names if n.startswith("repro.train.")], names


class _Clock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


def test_flush_delay_counts_from_ready_to_taken():
    clock = _Clock()

    async def go():
        gw = await Gateway(lambda t, rows: np.zeros(len(rows)), bucket=4,
                           max_wait=0.5, clock=clock).start()
        futs = []
        for i in range(4):                  # the 4th offer fills the bucket
            clock.t = float(i)
            futs.append(gw.offer("t0", np.zeros(1)))
        clock.t = 5.0                       # the dispatcher takes it at 5
        await asyncio.gather(*futs)
        full = gw.flush_delay_s
        clock.t = 10.0
        fut = gw.offer("t0", np.zeros(1))   # age deadline at 10.5
        clock.t = 12.0                      # taken at 12
        await fut
        return full, gw.flush_delay_s, await gw.drain()

    full, both, h = asyncio.run(go())
    assert full == pytest.approx(2.0)
    assert both == pytest.approx(2.0 + 1.5)
    assert h["flush_delay_s"] == both and h["flushes"]["age"] == 1
    assert h["handoff_s"] == 0.0            # the clock stood still


def test_counters_nonnegative_and_still_while_no_bucket_runs():
    async def go():
        gw = await Gateway(lambda t, rows: np.zeros(len(rows)), bucket=8,
                           max_wait=0.01).start()
        await asyncio.gather(*[gw.offer("t0", np.zeros(1))
                               for _ in range(20)])
        h0 = gw.health()
        await asyncio.sleep(0.05)           # no traffic, no bucket
        h1 = gw.health()
        return h0, h1, await gw.drain()

    h0, h1, h2 = asyncio.run(go())
    assert h0["buckets"] == 3
    for k in ("flush_delay_s", "handoff_s"):
        assert h0[k] >= 0.0
        assert h1[k] == h0[k] == h2[k]


def test_compile_counter_counts_a_fresh_jit_once():
    compile_counts()                        # listeners from here on
    x = jnp.arange(7.0)
    f = jax.jit(lambda v: v * 3.0 + 1.0)
    k0 = compile_counts()
    f(x).block_until_ready()
    k1 = compile_counts()
    f(x).block_until_ready()
    k2 = compile_counts()
    assert k1["executables"] - k0["executables"] == 1
    assert k1["traces"] - k0["traces"] >= 1
    assert (k1["names"].get("jit(<lambda>)", 0)
            == k0["names"].get("jit(<lambda>)", 0) + 1)
    assert {k: k2[k] - k1[k] for k in ("executables", "cache_hits",
                                       "traces")} == dict(
        executables=0, cache_hits=0, traces=0)


def test_serve_tm_straggler_deadline_fires_through_the_builder_hook(
        monkeypatch):
    monkeypatch.setattr(ops, "_DEFAULT_USE_KERNEL", True)
    args = build_parser().parse_args(
        ["--arch", "tm-tiny", "--requests", "640", "--bucket", "128",
         "--epochs", "1", "--n-train", "256", "--factorize",
         "--bucket-deadline", "3"])
    with faults.injected("serve.slow_bucket@3:0.3"):
        out = serve_tm(args)
    h = out["serve"]
    assert any(s["step"] == 3 for s in h["stragglers"]), h["stragglers"]
    assert h["demotions"] and "deadline" in h["demotions"][0]["reason"]
    assert h["demotions"][0]["frm"] == "factorized"
    assert out["gateway"]["unaccounted"] == 0
