"""What the trace reduction (``bench/trace.py``) makes of the program's own
``repro.*`` spans once they are handed to it besides the harness's
``bench.*`` ones (``trace.events`` keeps only the latter): on events made
by hand, and on a small trace recorded on the chip with the program's
spans (``bench/testdata/train3_spans.xplane.pb``: three tm-mnist training
steps, by ``record_trace.py``).

    JAX_PLATFORMS=cpu python -m pytest -q bench/checks
"""

from pathlib import Path

import pytest

from bench import trace

RECORDED = (Path(__file__).resolve().parents[1] / "testdata"
            / "train3_spans.xplane.pb")

# one device busy at 1-2 s and 5-6 s; the host in bench.step at 0-4 s and
# 4-7.5 s, and inside them in program phases
BENCH = [("bench.window", 0.0, 10.0), ("bench.step", 0.0, 4.0),
         ("bench.step", 4.0, 7.5)]
PROGRAM = [("repro.train.step", 0.1, 3.9), ("repro.train.prep", 0.2, 0.8),
           ("repro.train.plan", 2.0, 3.8), ("repro.train.step", 4.1, 7.4),
           ("repro.train.prep", 6.0, 7.3)]
DEVICE = [("fused_tm_train_delta.1", 1.0, 2.0), ("copy.2", 5.0, 6.0)]


def _program_spans(path):
    import jax

    pd = jax.profiler.ProfileData.from_file(str(path))
    return [(ev.name, ev.start_ns * 1e-9,
             (ev.start_ns + ev.duration_ns) * 1e-9)
            for plane in pd.planes if plane.name.startswith("/host:")
            for line in plane.lines for ev in line.events
            if ev.name.startswith("repro.")]


def test_gap_goes_to_the_innermost_span_of_either_prefix():
    base = trace.reduce_events(BENCH, [DEVICE])
    wide = trace.reduce_events(BENCH + PROGRAM, [DEVICE])
    for k in ("window_s", "busy_s", "op_s", "op_n"):
        assert wide[k] == base[k], k
    # gaps 0-1 (middle 0.5), 2-5 (3.5), 6-10 (8.0)
    assert wide["gaps"] == pytest.approx({
        "host in repro.train.prep": 1.0, "host in repro.train.plan": 3.0,
        "host outside harness spans": 4.0})
    assert base["gaps"] == pytest.approx({
        "host in bench.step": 4.0, "host outside harness spans": 4.0})
    assert wide["span_n"]["repro.train.step"] == 2


@pytest.mark.skipif(not RECORDED.exists(), reason="no chip trace kept")
def test_recorded_chip_trace_with_program_spans():
    spans, devices = trace.events(str(RECORDED))
    program = _program_spans(RECORDED)
    base = trace.reduce_events(spans, devices)
    wide = trace.reduce_events(spans + program, devices)
    for k in ("window_s", "busy_s", "op_s", "op_n"):
        assert wide[k] == base[k], k
    assert sum(wide["gaps"].values()) == pytest.approx(
        sum(base["gaps"].values()))
    # every phase of every step, nested in its step
    steps = [s for s in program if s[0] == "repro.train.step"]
    assert len(steps) == wide["span_n"]["bench.step"] == 3
    for name in ("prep", "class_sums", "plan", "delta", "apply"):
        inner = [s for s in program if s[0] == f"repro.train.{name}"]
        assert len(inner) == 3, name
        assert all(any(a <= s[1] and s[2] <= b for _, a, b in steps)
                   for s in inner), name
    # the idle time the harness could only put in bench.step now lies,
    # nearly all of it, in the phases of the program's step
    in_step = base["gaps"]["host in bench.step"]
    in_train = sum(v for k, v in wide["gaps"].items()
                   if k.startswith("host in repro.train."))
    assert in_train > 0.95 * in_step
    assert wide["gaps"].get("host in bench.step", 0.0) < 0.05 * in_step
