"""The stage and batch-build readers on a trace recorded on one TPU v5e
(``record_scopes.py``: two steps of the ``atis6-tt.b1s32`` cell with the
harness's spans, the program's own spans and every traced instruction's
``op_name``), and the ways a reader finds those when its record lacks
them.  The harness's reduction and its readers read what they did."""
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
for p in (os.path.dirname(os.path.dirname(HERE)), HERE):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import scopes, spec  # noqa: E402
from bench.trace_reduce import reduce  # noqa: E402
from bench.workcount import step_work  # noqa: E402
from repro.tracing import STAGES, stage_of  # noqa: E402

CELL = "atis6-tt.b1s32"
KEYS = {"window_s", "busy_s", "steps", "chips", "op_s", "op_n", "spans_s",
        "device_ops", "idle_gaps"}
OLD = ("input.ms_per_step", "step.mfu", "btt_roofline", "flash_roofline",
       "update_roofline", "device.idle_share")


def _load(name):
    with open(os.path.join(HERE, "data", name)) as f:
        data = json.load(f)
    data["device"] = [[tuple(e) for e in chip] for chip in data["device"]]
    data["host"] = [tuple(e) for e in data["host"]]
    return data


@pytest.fixture(scope="module")
def recorded():
    return _load("trace_scopes.json")


def _record(data, **extra):
    cell = spec.cell(CELL)
    layout = [(p, tuple(s), d) for p, s, d in data["layout"]]
    return {"trace": reduce(data), "calls": data["calls"],
            "work": step_work(cell["config"], cell["traffic"], layout),
            "peaks": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
            "config": cell["config"], "traffic": cell["traffic"], **extra}


@pytest.fixture(scope="module")
def record(recorded):
    spans = scopes.window_spans([tuple(s) for s in recorded["program"]],
                                recorded["host"])
    return _record(recorded, scopes=recorded["scopes"], program_spans_s=spans)


@pytest.mark.parametrize("fixture", ["trace_small.json", "trace_scopes.json"])
def test_reduce_keeps_its_keys(fixture):
    red = reduce(_load(fixture))
    assert set(red) == KEYS
    assert set(red["spans_s"]) == {"bench.input", "bench.dispatch", "bench.sync"}


@pytest.mark.parametrize("name", OLD)
def test_old_readers_read_the_same_beside_the_new_keys(recorded, record, name):
    read = spec.metric_reader(name)
    assert read(record) == read(_record(recorded))


def _stages(record):
    return {s: spec.metric_reader(f"step.{s}_ms")(record) for s in STAGES}


def test_stages_split_the_device_time(record):
    got = _stages(record)
    t = record["trace"]
    total = 1e3 * sum(t["op_s"].values()) / t["steps"]
    assert all(v > 0 for v in got.values())
    assert 0.9 * total <= sum(got.values()) <= total * (1 + 1e-9)


def test_every_top_operation_has_a_stage(recorded, record):
    for name, _ in record["trace"]["device_ops"]:
        assert stage_of(recorded["scopes"][name]) in STAGES, name


def test_recompute_is_the_second_flash_forward(recorded):
    flash = sorted(n for n in recorded["scopes"] if n.startswith("flash_fwd"))
    assert [stage_of(recorded["scopes"][n]) for n in flash] == [
        "forward", "recompute"]


def test_build_is_part_of_the_input(record):
    build = spec.metric_reader("input.build_ms")(record)
    assert 0 < build <= spec.metric_reader("input.ms_per_step")(record)
    assert spec.metric_reader("input.build_ms.host_bound")(record) == build


def test_nothing_to_read_without_the_programs_names(recorded, record,
                                                    monkeypatch):
    assert spec.metric_reader("input.build_ms")(
        dict(record, program_spans_s={})) is None
    monkeypatch.setattr(scopes, "_stage_of", lambda: None)
    assert set(_stages(record).values()) == {None}


def test_window_spans_keep_what_lies_inside():
    host = [("bench.input", 100, 150), ("bench.sync", 180, 200),
            ("bench.input", 200, 260), ("bench.sync", 280, 300)]
    spans = [("data.lm_batch", 90, 120), ("data.lm_batch", 210, 250),
             ("train.sync", 290, 310)]
    assert scopes.window_spans(spans, host) == {"data.lm_batch": [40e-9]}


def _traced_runs(tmp_path, monkeypatch, step, x, counts):
    """One ``bench-trace-*`` trace a count of steps, each a call of
    ``step`` inside the harness's spans, with the batch build's span."""
    import tempfile

    import jax
    from jax.profiler import TraceAnnotation

    from repro.tracing import span

    monkeypatch.setattr(tempfile, "tempdir", str(tmp_path))
    dirs = []
    for n in counts:
        log_dir = tempfile.mkdtemp(prefix="bench-trace-")
        jax.profiler.start_trace(log_dir)
        for _ in range(n):
            with TraceAnnotation("bench.input"), span("data.lm_batch"):
                pass
            with TraceAnnotation("bench.sync"):
                jax.block_until_ready(step(x))
        jax.profiler.stop_trace()
        dirs.append(log_dir)
    return dirs


def test_names_and_spans_from_the_runs_own_trace_file(tmp_path, monkeypatch):
    """Without ``scopes`` and ``program_spans_s`` the first reader reads the
    ``bench-trace-*`` trace the record reduces, and no other: its spans,
    and the compiled step the profiler keeps in it."""
    import glob

    import jax
    import jax.numpy as jnp

    from bench.trace_reduce import load_events

    def loss(w, x):
        with jax.named_scope("attn"):
            return jnp.sum(jnp.tanh(x @ w))

    @jax.jit
    def step(x):
        g = jax.grad(loss)(x, x)
        with jax.named_scope("update"):
            return x - 0.1 * g

    x = jnp.ones((8, 8))
    dirs = _traced_runs(tmp_path, monkeypatch, step, x, (2, 3))
    for log_dir, steps in zip(dirs, (2, 3)):
        record = {"trace": reduce(load_events(log_dir))}
        assert len(scopes.program_spans(record)["data.lm_batch"]) == steps
        # The CPU trace holds no device operations to name.
        assert scopes.scopes(record) is None
    assert scopes.program_spans({"trace": dict(record["trace"], steps=9)}) == {}

    want = scopes.attributed(step.lower(x).compile().as_text())
    ran = {n: 1.0 for n, v in want.items() if stage_of(v) == "update"}
    path = glob.glob(f"{dirs[0]}/**/*.xplane.pb", recursive=True)[0]
    got = scopes.step_names(path, ran)
    assert ran and {n: got[n] for n in ran} == {n: want[n] for n in ran}
    assert {stage_of(v) for v in got.values()} >= {"forward", "backward",
                                                    "update"}
