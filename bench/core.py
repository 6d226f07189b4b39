"""What every cell shares: files found by name, seeds, the run context.

The harness is driven by data.  A cell names a configuration and a traffic
mix; each is a file of its own, and each metric is a reducer of its own:

    bench/configs/<config>.json     sizes, bank recipe, assumptions
    bench/traffic/<traffic>.json    a mix: {"kind": <generator>, params...}
    bench/traffic/<kind>.py         the generator that drives a kind of mix
    bench/metrics/<metric>.py       reducer; a suffixed name such as
                                    ``bucket_ms.closed`` falls back to
                                    ``bucket_ms.py``

Adding a cell, a configuration, a mix or a metric adds files and entries
and edits none.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import sys
from pathlib import Path
from typing import Any, Callable, Dict, Optional

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
CACHE_DIR = ROOT / ".jax_cache"
TRACE_DIR = ROOT / ".bench_trace"


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path):
    """Import one file by path (metric and traffic names may hold dots)."""
    name = "bench_file_" + "".join(c if c.isalnum() else "_"
                                   for c in str(path.relative_to(BENCH)))
    mod = sys.modules.get(name)
    if mod is None:
        spec = importlib.util.spec_from_file_location(name, path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[name] = mod
        spec.loader.exec_module(mod)
    return mod


def benchmark() -> dict:
    return load_json(ROOT / "BENCHMARK.json")


def workload(spec: dict, name: str) -> dict:
    for w in spec["workloads"]:
        if w["name"] == name:
            return w
    raise SystemExit(f"bench: no workload {name!r} in BENCHMARK.json")


def config(name: str) -> dict:
    return load_json(BENCH / "configs" / f"{name}.json")


def traffic(name: str) -> dict:
    return load_json(BENCH / "traffic" / f"{name}.json")


def traffic_kind(kind: str):
    return load_module(BENCH / "traffic" / f"{kind}.py")


def reducer(metric: str):
    """The reducer file of a metric: its full name, else the part before
    the first dot (one reducer serves ``bucket_ms.poisson`` and
    ``bucket_ms.closed``)."""
    for stem in (metric, metric.split(".")[0]):
        path = BENCH / "metrics" / f"{stem}.py"
        if path.is_file():
            return load_module(path)
    raise SystemExit(f"bench: no reducer for metric {metric!r} "
                     f"in bench/metrics/")


def metrics_of(spec: dict, cell: str, per_layer: bool) -> list:
    """The metrics a cell reports: end-to-end ones with ``--trace 0``,
    per-layer ones with ``--trace 1``.  A metric without ``workloads``
    belongs to every cell that reports the metric it moves."""
    e2e = [m for m in spec["end_to_end"]
           if cell in m.get("workloads", [cell])]
    if not per_layer:
        return e2e
    e2e_names = {m["name"] for m in e2e}
    return [m for m in spec["per_layer"]
            if cell in m.get("workloads", [cell])
            and ("workloads" in m or m["moves"] in e2e_names)]


def seed_u32(seed: int, salt: int = 0) -> int:
    """A 32-bit draw from a seed of any size (seeds above 2**32 stay
    distinct: both halves are mixed)."""
    import numpy as np

    ss = np.random.SeedSequence([seed & 0xFFFFFFFF, seed >> 32, salt])
    return int(ss.generate_state(1, np.uint32)[0])


def seed_key(seed: int, salt: int = 0):
    import jax

    return jax.random.PRNGKey(seed_u32(seed, salt))


def settle():
    """The end of set-up: collect what set-up left, then move every object
    still alive into the collector's permanent generation, as a server
    does once it is warm.  A full pass of the collector then walks only
    what the window itself keeps alive, not the imports, the compiled
    programs and the set-up's data, so its pauses stay short."""
    import gc

    gc.collect()
    gc.freeze()


def unsettle():
    """After the window: set-up's objects can be collected again."""
    import gc

    gc.unfreeze()


class GcWatch:
    """Counts the garbage collector's passes and their pauses while it is
    on: a pass over every live object stalls the event loop that offers
    and answers requests, so its pauses read into the serving tail."""

    def __init__(self):
        self.n = [0, 0, 0]
        self.pause_s = [0.0, 0.0, 0.0]
        self.longest_s = 0.0
        self._t = 0.0

    def _cb(self, phase, info):
        import time

        if phase == "start":
            self._t = time.perf_counter()
            return
        d = time.perf_counter() - self._t
        g = info["generation"]
        self.n[g] += 1
        self.pause_s[g] += d
        self.longest_s = max(self.longest_s, d)

    def __enter__(self):
        import gc

        gc.callbacks.append(self._cb)
        return self

    def __exit__(self, *exc):
        import gc

        gc.callbacks.remove(self._cb)

    def summary(self) -> dict:
        return dict(passes=self.n, pause_s=self.pause_s,
                    longest_s=self.longest_s)


@dataclasses.dataclass
class Context:
    """One run of one cell, as the traffic generator sees it."""
    cell: str
    seed: int
    seconds: float
    trace: bool
    cfg: dict
    mix: dict
    t_start: float                      # perf_counter at process start
    peaks: dict
    log: Callable[[str], None] = print
    # test hook: wraps a stage of the timed path (see bench/checks)
    patch: Optional[Callable[[str, Any], Any]] = None
    info: Dict[str, Any] = dataclasses.field(default_factory=dict)

    def wrap(self, stage: str, fn):
        return fn if self.patch is None else self.patch(stage, fn)
