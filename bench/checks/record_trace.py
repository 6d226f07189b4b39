"""Record the small trace that ``test_trace.py`` reduces (run on the chip).

    python3 bench/checks/record_trace.py bench/testdata/train3.xplane.pb

Three tm-mnist training steps through the program's kernel path, traced
with the harness's own profiler options and spans, as a traced run of the
training cell records them.
"""

import glob
import shutil
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main():
    out = Path(sys.argv[1])
    from bench import core, run, trace
    from bench.systems import training

    jax = run.setup_jax()
    ctx = core.Context(cell="record", seed=7, seconds=1, trace=True,
                       cfg=core.config("tm-mnist"), mix={},
                       t_start=time.perf_counter(), peaks={})
    tr = training.Trainer(ctx, 2048, 256)
    for _ in range(3):
        tr.step()
    jax.block_until_ready(tr.ta)
    d = core.TRACE_DIR / "record"
    shutil.rmtree(d, ignore_errors=True)
    jax.profiler.start_trace(str(d), profiler_options=trace.options())
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench.step"):
                tr.step()
        jax.block_until_ready(tr.ta)
    jax.profiler.stop_trace()
    tr.close()
    out.parent.mkdir(parents=True, exist_ok=True)
    shutil.copy(glob.glob(str(d / "**" / "*.xplane.pb"), recursive=True)[0], out)
    shutil.rmtree(d, ignore_errors=True)
    print(out, out.stat().st_size, trace.reduce(str(out)))


if __name__ == "__main__":
    main()
