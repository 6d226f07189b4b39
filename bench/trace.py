"""Device trace of the window, and its reduction to numbers.

A traced run records the measured window with the JAX profiler.  The
harness marks its own host spans (``bench.window`` around the window,
``bench.bucket`` around each runner call, ``bench.step`` around each
training step) with ``jax.profiler.TraceAnnotation``, so the host and the
device share one clock in the trace.  :func:`reduce` reads the
``.xplane.pb`` file with ``jax.profiler.ProfileData`` and gives:

* ``busy_s``: the union of the intervals in which an operation ran on the
  device, inside the window, averaged over the device planes;
* ``op_s`` / ``op_n``: time and count of each device operation by name;
* ``gaps``: idle time on the device, attributed to the innermost harness
  span that was open on the host at the middle of each gap.

``bench/checks/test_trace.py`` checks the reduction on events made by
hand, on a trace recorded on the CPU, and on a small trace recorded on the
chip (``bench/testdata/train3.xplane.pb``, by ``record_trace.py``) once
one is kept there.
"""

from __future__ import annotations

import glob
import shutil
from pathlib import Path
from typing import Dict, List, Optional, Tuple

# device-plane lines that hold one event per operation executed
OP_LINES = ("XLA Ops",)
WINDOW = "bench.window"


class Tracer:
    def __init__(self, on: bool, out_dir: Path):
        self.on, self.dir = on, Path(out_dir)

    def start(self):
        if self.on:
            import jax

            shutil.rmtree(self.dir, ignore_errors=True)
            jax.profiler.start_trace(str(self.dir),
                                     profiler_options=options())

    def stop(self) -> Optional[dict]:
        if not self.on:
            return None
        import jax

        jax.profiler.stop_trace()
        files = glob.glob(str(self.dir / "**" / "*.xplane.pb"), recursive=True)
        if not files:
            raise RuntimeError(f"no trace written under {self.dir}")
        out = reduce(max(files))
        shutil.rmtree(self.dir, ignore_errors=True)
        return out


def options():
    """Device trace, and on the host only annotations (``TraceAnnotation``
    is level 1): the Python tracer, on by default, records every Python
    call and slows the host enough to overload a serving cell."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    opts.enable_hlo_proto = False
    return opts


def short(name: str) -> str:
    """``%fusion.3 = u32[...] fusion(...)`` -> ``fusion.3``: the HLO
    instruction name of a device op event (kernels are named after the
    jitted function that wraps their ``pallas_call``)."""
    return name.split(" = ", 1)[0].lstrip("%")


def _union(iv: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for a, b in sorted(iv):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def events(path: str):
    """(harness spans, op events per device plane) of one ``.xplane.pb``:
    ``[(name, start_s, end_s)]`` and ``[[(op name, start_s, end_s)]]``."""
    import jax

    pd = jax.profiler.ProfileData.from_file(path)
    spans: List[Tuple[str, float, float]] = []
    devices = []
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("bench."):
                        spans.append((ev.name, ev.start_ns * 1e-9,
                                      (ev.start_ns + ev.duration_ns) * 1e-9))
        elif plane.name.startswith("/device:"):
            ops = [(short(ev.name), ev.start_ns * 1e-9,
                    (ev.start_ns + ev.duration_ns) * 1e-9)
                   for line in plane.lines if line.name in OP_LINES
                   for ev in line.events]
            if ops:
                devices.append(ops)
    return spans, devices


def reduce(path: str) -> dict:
    """Numbers of one traced window (times in seconds)."""
    return reduce_events(*events(path))


def reduce_events(spans, devices) -> dict:
    win = [s for s in spans if s[0] == WINDOW]
    if not win:
        raise RuntimeError(f"trace has no {WINDOW} span")
    _, w0, w1 = win[0]
    inner = sorted((s for s in spans if s[0] != WINDOW and s[2] > w0
                    and s[1] < w1), key=lambda s: s[1])

    busy_total = 0.0
    op_s: Dict[str, float] = {}
    op_n: Dict[str, int] = {}
    gaps: Dict[str, float] = {}
    gap_n: Dict[str, int] = {}
    for ops in devices:
        clipped = []
        for name, a, b in ops:
            a, b = max(a, w0), min(b, w1)
            if b <= a:
                continue
            clipped.append((a, b))
            op_s[name] = op_s.get(name, 0.0) + (b - a)
            op_n[name] = op_n.get(name, 0) + 1
        busy = _union(clipped)
        busy_total += sum(b - a for a, b in busy)
        edges = [w0] + [t for iv in busy for t in iv] + [w1]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b > a:
                label = _span_at(inner, 0.5 * (a + b))
                gaps[label] = gaps.get(label, 0.0) + (b - a)
                gap_n[label] = gap_n.get(label, 0) + 1
    n_dev = max(len(devices), 1)
    return dict(
        window_s=w1 - w0, busy_s=busy_total / n_dev, n_devices=len(devices),
        op_s={k: v / n_dev for k, v in op_s.items()}, op_n=op_n,
        gaps={k: v / n_dev for k, v in gaps.items()}, gap_n=gap_n,
        span_n={n: sum(1 for s in inner if s[0] == n)
                for n in {s[0] for s in inner}},
        span_s={n: sum(min(s[2], w1) - max(s[1], w0) for s in inner
                       if s[0] == n) for n in {s[0] for s in inner}})


def _span_at(spans, t: float) -> str:
    """Innermost (latest-starting) harness span open at time ``t``."""
    best = None
    for name, a, b in spans:
        if a > t:
            break
        if b >= t:
            best = name
    return f"host in {best}" if best else "host outside harness spans"


def breakdown(tr: dict) -> dict:
    """The ten device operations that took most time, and idle time by
    what the host was doing."""
    top = sorted(tr["op_s"].items(), key=lambda kv: -kv[1])[:10]
    gaps = sorted(tr["gaps"].items(), key=lambda kv: -kv[1])[:10]
    return {"device_ops": [[k, v] for k, v in top],
            "idle_gaps": [[k, v] for k, v in gaps]}
