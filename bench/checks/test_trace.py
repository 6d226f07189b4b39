"""The trace reduction (``bench/trace.py``): on device events made by hand,
on the host spans of a trace recorded here on the CPU, and on a small
trace recorded on the chip (``bench/testdata/train3.xplane.pb``: three
tm-mnist training steps, by ``record_trace.py``) where one is kept.

    JAX_PLATFORMS=cpu python -m pytest -q bench/checks
"""

import glob
from pathlib import Path

import pytest

from bench import core, trace

RECORDED = Path(__file__).resolve().parents[1] / "testdata" / "train3.xplane.pb"
PEAKS = core.load_json(core.BENCH / "peaks.json")["devices"]["TPU v5 lite"]

# one device: a kernel at 1-3 s (two overlapping events), a copy at 5-6 s,
# and one op cut by the window's end; the host inside bench.step at 0-4 s
# and 4-8 s, the kernel's name as the trace gives it
SPANS = [("bench.window", 0.0, 10.0), ("bench.step", 0.0, 4.0),
         ("bench.step", 4.0, 8.0), ("bench.bucket", 20.0, 21.0)]
DEVICE = [("fused_tm_train_delta.1", 1.0, 2.0),
          ("fused_tm_train_delta.1", 1.5, 3.0),
          ("copy.2", 5.0, 6.0), ("copy.2", 9.5, 12.0)]


def test_busy_idle_and_ops_from_events():
    tr = trace.reduce_events(SPANS, [DEVICE])
    assert tr["window_s"] == 10.0 and tr["n_devices"] == 1
    assert tr["busy_s"] == pytest.approx(2.0 + 1.0 + 0.5)
    assert tr["op_s"]["fused_tm_train_delta.1"] == pytest.approx(2.5)
    assert tr["op_n"] == {"fused_tm_train_delta.1": 2, "copy.2": 2}
    # gaps 0-1, 3-5 and 6-9.5, each named by the span open at its middle
    assert tr["gaps"] == pytest.approx({"host in bench.step": 1 + 2 + 3.5})
    assert tr["gap_n"] == {"host in bench.step": 3}
    assert tr["busy_s"] + sum(tr["gaps"].values()) == pytest.approx(10.0)
    assert tr["span_n"] == {"bench.step": 2}


def test_roofline_and_idle_reducers():
    from bench.metrics import device_idle, train_kernel_roofline

    tr = trace.reduce_events(SPANS, [DEVICE])
    rec = dict(kind="train_loop", trace=tr, batch=256,
               cfg=core.config("tm-mnist"), peaks=PEAKS)
    # 2 calls x 256 x 4CL ops at 393 TOP/s over 2.5 s of kernel time
    ops = 2 * 256 * 4 * 2000 * 1568
    assert train_kernel_roofline.value(rec) == pytest.approx(
        100 * ops / 393e12 / 2.5)
    assert device_idle.value(rec) == pytest.approx(65.0)
    rec["trace"] = trace.reduce_events(SPANS, [[("copy.2", 5.0, 6.0)]])
    assert train_kernel_roofline.value(rec) is None   # nothing to read


def test_host_spans_of_a_recorded_cpu_trace(tmp_path):
    import jax

    f = jax.jit(lambda x: (x @ x).sum())
    x = jax.numpy.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path), profiler_options=trace.options())
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench.step"):
                f(x).block_until_ready()
    jax.profiler.stop_trace()
    path = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)[0]
    spans, devices = trace.events(path)
    assert [s[0] for s in spans].count("bench.step") == 3
    tr = trace.reduce_events(spans, devices)
    assert tr["span_n"] == {"bench.step": 3} and tr["window_s"] > 0


def test_recorded_chip_trace():
    if not RECORDED.exists():
        pytest.skip("no chip trace recorded in bench/testdata yet")
    tr = trace.reduce(str(RECORDED))
    assert tr["n_devices"] == 1
    assert 0 < tr["busy_s"] < tr["window_s"]
    assert sum(n for k, n in tr["op_n"].items()
               if "fused_tm_train_delta" in k) == 3
    assert tr["span_n"]["bench.step"] == 3


def test_breakdown_is_capped():
    tr = dict(op_s={f"op{i}": float(i) for i in range(20)},
              gaps={f"g{i}": float(i) for i in range(12)})
    bd = trace.breakdown(tr)
    assert len(bd["device_ops"]) == 10 and bd["device_ops"][0] == ["op19", 19.0]
    assert len(bd["idle_gaps"]) == 10


def test_union_and_names():
    assert trace._union([(0, 2), (1, 3), (5, 6), (6, 7)]) == [(0, 3), (5, 7)]
    spans = [("bench.bucket", 0.0, 1.0), ("bench.offer", 0.5, 0.6)]
    assert trace._span_at(spans, 0.55) == "host in bench.offer"
    assert trace._span_at(spans, 0.9) == "host in bench.bucket"
    assert trace._span_at(spans, 2.0) == "host outside harness spans"
    assert trace.short("%fusion.3 = u32[8] fusion(%a), kind=kLoop") == "fusion.3"
