#!/usr/bin/env python3
"""Record the small trace that ``test_bench_scopes.py`` reads.

    python3 tests/bench/record_scopes.py OUT.json

On one TPU: builds the ``atis6-tt.b1s32`` cell's program, warms it up, and
traces two steps with the harness's host spans, as ``record_trace.py``
does.  Writes the device operations and harness spans
(``bench.trace_reduce.load_events``), the program's own host spans
(``bench.scopes.load_program_spans``), the compiled step's kernel calls,
the parameter layout, and the ``op_name`` of every traced instruction as
the readers take it from the trace (``bench.scopes.step_names``) as JSON.
It prints the traced instructions that the compiled step names otherwise
(none, where the trace holds the step the program compiled).
"""
import glob
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
from bench.scopes import attributed, load_program_spans, step_names  # noqa: E402
from bench.trace_reduce import load_events, reduce  # noqa: E402


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
    path = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True)[0]
    names = step_names(path, reduce(ev)["op_s"])
    traced = {n for chip in ev["device"] for n, _, _ in chip}
    compiled = attributed(prog.compiled.as_text())
    differ = sorted(n for n in traced if compiled.get(n) != names.get(n))
    with open(out, "w") as f:
        json.dump({"device": ev["device"], "host": ev["host"],
                   "program": load_program_spans(path),
                   "calls": prog.calls, "layout": prog.layout,
                   "scopes": {n: names[n] for n in sorted(traced & set(names))}},
                  f)
    print("events", [len(x) for x in ev["device"]], len(ev["host"]),
          "unnamed", sorted(traced - set(names))[:20],
          "named otherwise than in the compiled step", differ[:20])


if __name__ == "__main__":
    main(sys.argv[1])
