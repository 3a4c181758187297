#!/usr/bin/env python3
"""One run of one benchmark cell on the chip.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up builds the cell's compiled train step with its state, from weights
made from the seed (``bench.program``), and drives it through its first
steps: that compiles it, warms it up, and gives the readings the check
compares.  The window then calls the same step for ``--seconds`` seconds,
each step as ``repro.launch.train.main``'s loop makes it.  After the window
the peak device memory is read, the program's state is freed, and the plain
reference (``bench.reference``) follows the same first steps; ``correct``
compares the two (``bench.check``).

``--trace 0`` reports the cell's end-to-end metrics; ``--trace 1`` traces a
stretch of the window with the profiler and reports the per-layer metrics,
each read by ``bench/metrics/<name>.py``.  The last line of standard output
is one JSON object; the numbers compared, each with its limit, are the last
lines of standard error.  With no TPU, or fewer chips than the cell asks
for, or a device with no entry in ``bench.peaks``, the run prints no result
and exits non-zero.
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import traceback  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
# Run as a script, this directory heads sys.path; its modules are imported
# as ``bench.*`` only.
sys.path[:] = [p for p in sys.path if os.path.abspath(p or ".") != BENCH]
CACHE = os.path.join(ROOT, ".jax_cache")
TRACE_WARM_S = 1.0


class NoChip(Exception):
    pass


def _paths() -> None:
    for p in (os.path.join(ROOT, "src"), ROOT):
        if p not in sys.path:
            sys.path.insert(0, p)


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def find_device(chips: int) -> dict:
    """The chip this run measures, with its peaks; raises when there is no
    TPU, too few of them, or no peaks for its kind."""
    import jax

    from bench.peaks import peaks_for

    try:
        devs = jax.devices()
    except RuntimeError as e:
        raise NoChip(f"JAX found no devices: {e}") from e
    d = devs[0]
    if d.platform != "tpu":
        raise NoChip(f"no TPU: JAX's first device is {d.platform} ({d.device_kind})")
    if len(devs) < chips:
        raise NoChip(f"{len(devs)} TPU device(s); this cell needs {chips}")
    return {"platform": d.platform, "kind": d.device_kind, "count": chips,
            "peaks": peaks_for(d.device_kind)}


def enable_cache() -> None:
    """JAX's persistent compilation cache, at a fixed path in the checkout."""
    import jax

    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE
    jax.config.update("jax_compilation_cache_dir", CACHE)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)


def peak_bytes() -> int | None:
    import jax

    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


class Compiles:
    """Counts JAX's tracing, lowering and compile events while ``on``:
    none should fall inside the window."""

    def __init__(self):
        import jax

        self.on, self.n = False, 0
        jax.monitoring.register_event_duration_secs_listener(self)

    def __call__(self, event: str, duration: float, **kwargs) -> None:
        if self.on and event.startswith("/jax/core/compile/"):
            self.n += 1


class Window:
    """Steps for a fixed time; with ``trace`` the profiler records a
    stretch of it, from ``TRACE_WARM_S`` in until ``trace_s`` seconds and
    three steps have passed."""

    def __init__(self, prog, first_step: int, seconds: float, trace: bool,
                 trace_s: float):
        self.prog, self.step0 = prog, first_step
        self.seconds, self.trace, self.trace_s = seconds, trace, trace_s
        self.log_dir = None

    def run(self) -> dict:
        import jax
        from jax.profiler import TraceAnnotation

        prog, step = self.prog, self.step0
        times, losses, phases = [], [], []
        tracing, traced_from, trace_t0 = False, None, None
        if self.trace:
            self.log_dir = tempfile.mkdtemp(prefix="bench-trace-")
        start = time.perf_counter()
        while True:
            t0 = time.perf_counter()
            if self.trace and not tracing and traced_from is None \
                    and t0 - start >= min(TRACE_WARM_S, self.seconds / 4):
                jax.profiler.start_trace(self.log_dir)
                tracing, traced_from, trace_t0 = True, step, time.perf_counter()
                t0 = trace_t0
            with TraceAnnotation("bench.input"):
                batch = prog.place(prog.host_batch(step))
            ta = time.perf_counter()
            with TraceAnnotation("bench.dispatch"):
                metrics = prog.call(batch)
            tb = time.perf_counter()
            with TraceAnnotation("bench.sync"):
                host = jax.device_get(metrics)
            t1 = time.perf_counter()
            times.append(t1 - t0)
            phases.append((t0 - start, ta - t0, tb - ta, t1 - tb))
            losses.append(float(host["loss"]))
            step += 1
            if tracing and t1 - trace_t0 >= self.trace_s \
                    and step - traced_from >= 3:
                jax.profiler.stop_trace()
                tracing = False
            if t1 - start >= self.seconds:
                break
        if tracing:
            jax.profiler.stop_trace()
        return {"seconds": t1 - start, "times": times, "losses": losses,
                "phases": phases}


def slow_steps(w: dict, factor: float = 5.0, top: int = 5) -> list:
    """The window's slowest steps beyond ``factor`` times the median, each
    as ``[index, start s, input ms, dispatch ms, sync ms]``."""
    med = statistics.median(w["times"])
    slow = sorted((i for i, t in enumerate(w["times"]) if t > factor * med),
                  key=lambda i: -w["times"][i])[:top]
    return [[i, w["phases"][i][0]] + [1e3 * x for x in w["phases"][i][1:]]
            for i in slow]


def per_layer(cell: dict, log_dir: str, work: dict, calls: dict,
              peaks: dict) -> tuple:
    from bench import spec
    from bench.trace_reduce import load_events, reduce

    events = load_events(log_dir)
    red = reduce(events)
    record = {"trace": red, "work": work, "calls": calls, "peaks": peaks,
              "config": cell["config"], "traffic": cell["traffic"]}
    metrics = {}
    for m in cell["per_layer"]:
        value = spec.metric_reader(m["name"])(record)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    return metrics, red


def main(argv=None, device: dict | None = None) -> dict:
    """One run; returns the result object.  ``device`` stands in for the
    chip's look (tests run the rest of a run on the CPU with it)."""
    args = parse(argv)
    _paths()
    from bench import spec

    cell = spec.cell(args.workload)
    if device is None:
        device = find_device(cell["workload"]["chips"])
    enable_cache()

    import jax
    import numpy as np

    from bench import check, tokens
    from bench.program import Program, reference_weights
    from bench.reference import Reference
    from bench.workcount import context, on_chip_share, step_work

    config, traffic = cell["config"], cell["traffic"]
    seed = args.seed
    prog = Program(config, traffic, seed)
    n_check = traffic["check_steps"]
    first = prog.first_steps(n_check)
    ours = [tokens.batch(seed, i, prog.B, prog.S, prog.V) for i in range(n_check)]
    differ = sum(int(np.sum(np.asarray(prog.host_batch(i)[k]) != ours[i][k]))
                 for i in range(n_check) for k in ("tokens", "labels"))
    work = step_work(config, traffic, prog.layout)
    # Set-up's objects stay alive; a collection of them inside the window
    # would pause the host loop.
    gc.collect()
    gc.freeze()
    compiles = Compiles()
    setup_s = time.perf_counter() - T0

    window = Window(prog, n_check, args.seconds, bool(args.trace),
                    traffic["trace_seconds"])
    compiles.on = True
    w = window.run()
    compiles.on = False
    peak = peak_bytes()
    kernels, calls, layout = prog.kernels, prog.calls, prog.layout
    prog.free()
    del prog

    t_ref = time.perf_counter()
    ref = Reference(config, traffic).run(
        reference_weights(layout, seed, config["family"]), ours)
    reference_s = time.perf_counter() - t_ref
    numbers = check.gaps(first, ref)
    numbers["inputs_differ"] = differ
    ok, table = check.verdict(numbers, check.load_limits(args.workload))
    failed = sum(1 for v in w["losses"] if not math.isfinite(v))
    ok = ok and failed == 0

    tokens_per_step = traffic["batch"] * traffic["seq"]
    dev = {"platform": device["platform"], "kind": device["kind"],
           "count": device["count"], "memory_peak_bytes": peak}
    result = {"correct": ok, "attempted": len(w["times"]), "failed": failed}
    info = {"steps": len(w["times"]), "window_s": w["seconds"],
            "median_step_ms": 1e3 * statistics.median(w["times"]),
            "max_step_ms": 1e3 * max(w["times"]),
            "slow_steps": slow_steps(w),
            "compiles_in_window": compiles.n,
            "check_losses": first["losses"], "reference_losses": ref["losses"],
            "window_first_loss": w["losses"][0], "window_last_loss": w["losses"][-1],
            "kernels": kernels,
            "kernel_bytes_on_chip_pct": on_chip_share(calls, context(
                {"config": config, "traffic": traffic, "work": work,
                 "calls": calls})),
            "numbers": {k: numbers[k] for k in check.NUMBERS},
            "worst_leaf": numbers["worst_leaf"],
            "leaves_kept": numbers["leaves_kept"], "leaves": numbers["leaves"],
            "setup_s": setup_s, "reference_s": reference_s,
            "platform": device["platform"],
            "device_kind": device["kind"], "device_count": len(jax.devices())}
    if args.trace:
        metrics, red = per_layer(cell, window.log_dir, work, calls,
                                        device["peaks"])
        shutil.rmtree(window.log_dir, ignore_errors=True)
        dev.update(busy_s=red["busy_s"], window_s=red["window_s"])
        result.update(metrics=metrics, device=dev,
                      breakdown={"device_ops": red["device_ops"],
                                 "idle_gaps": red["idle_gaps"]})
        info.update(traced_steps=red["steps"])
    else:
        values = {
            "tokens_per_s": len(w["times"]) * tokens_per_step / w["seconds"],
            "peak_hbm_mb": (peak or 0) / 1e6,
            "step_ms_p95": 1e3 * statistics.quantiles(w["times"], n=20)[18],
            "setup_s": setup_s,
        }
        # ``tokens_per_s.host_bound`` is ``tokens_per_s`` under a bound of
        # its own: the name's head says what is measured.
        metrics = {m["name"]: {"value": values[m["name"].split(".")[0]],
                               "unit": m["unit"]}
                   for m in cell["end_to_end"]}
        result.update(metrics=metrics, device=dev)
    result["checks"] = table
    print(json.dumps({"info": info}), flush=True)
    for name, v in table.items():
        print(f"check {name} {v['value']!r} limit {v['limit']!r}",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return result


if __name__ == "__main__":
    try:
        main()
    except Exception:  # noqa: BLE001 - any failure: no result, non-zero exit
        traceback.print_exc()
        sys.exit(1)
