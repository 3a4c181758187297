#!/usr/bin/env python3
"""Record the small trace that ``test_bench_trace.py`` reduces.

    python3 tests/bench/record_trace.py OUT.json

On one TPU: builds the ``atis6-tt.b1s32`` cell's program, warms it up, and
traces two steps with the harness's host spans.  Writes the device
operations and host spans (``bench.trace_reduce.load_events``), the
compiled step's kernel calls and the parameter layout as JSON.
"""
import json
import os
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]

import jax  # noqa: E402
from jax.profiler import TraceAnnotation  # noqa: E402

from bench import run, spec  # noqa: E402
from bench.program import Program  # noqa: E402
from bench.trace_reduce import load_events  # noqa: E402


def main(out: str) -> None:
    cell = spec.cell("atis6-tt.b1s32")
    run.find_device(1)
    run.enable_cache()
    prog = Program(cell["config"], cell["traffic"], 7)
    for i in range(3):
        prog.step(i)
    log_dir = tempfile.mkdtemp(prefix="bench-trace-")
    jax.profiler.start_trace(log_dir)
    for i in range(3, 5):
        with TraceAnnotation("bench.input"):
            batch = prog.place(prog.host_batch(i))
        with TraceAnnotation("bench.dispatch"):
            metrics = prog.call(batch)
        with TraceAnnotation("bench.sync"):
            jax.device_get(metrics)
    jax.profiler.stop_trace()
    ev = load_events(log_dir)
    with open(out, "w") as f:
        json.dump({"device": ev["device"], "host": ev["host"],
                   "calls": prog.calls, "layout": prog.layout}, f)
    print("events", [len(x) for x in ev["device"]], len(ev["host"]))


if __name__ == "__main__":
    main(sys.argv[1])
