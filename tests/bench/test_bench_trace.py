"""The trace reduction on a small trace recorded on one TPU v5e: two
steps of the ``atis6-tt.b1s32`` cell, with the harness's host spans and
the compiled step's kernel calls.  Busy time, idle share and kernel time
by name are checked against a plain count over the same events."""
import json
import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

from bench import spec  # noqa: E402
from bench.trace_reduce import CONTAINERS, base_name, reduce, short_name  # noqa: E402
from bench.workcount import context, family_share, on_chip_share, step_work  # noqa: E402

KERNELS = {f: spec.load_module("metrics", f"{f}_roofline").KERNELS
           for f in ("btt", "flash", "update")}
CELL = "atis6-tt.b1s32"


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(HERE, "data", "trace_small.json")) as f:
        data = json.load(f)
    data["device"] = [[tuple(e) for e in chip] for chip in data["device"]]
    data["host"] = [tuple(e) for e in data["host"]]
    return data


@pytest.fixture(scope="module")
def reduced(recorded):
    return reduce(recorded)


def _window(recorded):
    inputs = [h for h in recorded["host"] if h[0] == "bench.input"]
    syncs = [h for h in recorded["host"] if h[0] == "bench.sync"]
    return inputs[0][1], syncs[-1][2]


def test_window_and_steps(recorded, reduced):
    w0, w1 = _window(recorded)
    assert reduced["window_s"] == pytest.approx((w1 - w0) / 1e9)
    assert reduced["steps"] == 2


def test_busy_is_the_union_of_device_intervals(recorded, reduced):
    w0, w1 = _window(recorded)
    t0 = int(np.floor(w0))
    busy = np.zeros(int(np.ceil(w1)) - t0 + 1, bool)
    for _, s, e in recorded["device"][0]:
        s, e = max(s, w0), min(e, w1)
        if e > s:
            busy[int(round(s)) - t0:int(round(e)) - t0] = True
    assert reduced["busy_s"] == pytest.approx(busy.sum() / 1e9, rel=1e-3)
    idle = 1 - reduced["busy_s"] / reduced["window_s"]
    assert 0.0 < idle < 1.0


@pytest.mark.parametrize("family", sorted(KERNELS))
def test_kernel_time_by_name(recorded, reduced, family):
    w0, w1 = _window(recorded)
    want = sum(min(e, w1) - max(s, w0) for n, s, e in recorded["device"][0]
               if base_name(n) in KERNELS[family] and e > w0 and s < w1)
    got = sum(v for n, v in reduced["op_s"].items()
              if base_name(n) in KERNELS[family])
    assert want > 0
    assert got == pytest.approx(want / 1e9)


def test_containers_count_as_busy_not_as_operations(reduced):
    assert not any(base_name(n) in CONTAINERS for n in reduced["op_s"])
    assert len(reduced["device_ops"]) <= 10
    assert len(reduced["idle_gaps"]) <= 10
    assert {g[0] for g in reduced["idle_gaps"]} <= {"input", "dispatch", "sync", "other"}


def test_short_name_reads_the_hlo_text():
    assert short_name("%btt_linear.64 = f32[32,768]{1,0} custom-call(%pad.1)") == "btt_linear.64"
    assert short_name("fusion.3") == "fusion.3"


def _record(recorded, reduced):
    cell = spec.cell(CELL)
    layout = [(p, tuple(s), d) for p, s, d in recorded["layout"]]
    return {"trace": reduced, "calls": recorded["calls"],
            "work": step_work(cell["config"], cell["traffic"], layout),
            "peaks": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
            "config": cell["config"], "traffic": cell["traffic"]}


@pytest.mark.parametrize("family", ["btt", "flash"])
def test_roofline_shares_stay_under_100(recorded, reduced, family):
    share = family_share(_record(recorded, reduced), KERNELS[family])
    assert share is not None and 0.0 < share <= 100.0


def test_no_roofline_where_every_operand_sits_on_chip(recorded, reduced):
    """The cell keeps every kernel's operands on chip, so HBM bounds none
    of them: the update's count from shapes outruns HBM, and the cell
    reports no kernel roofline."""
    record = _record(recorded, reduced)
    assert set(on_chip_share(recorded["calls"], context(record)).values()) == {100.0}
    assert family_share(record, KERNELS["update"]) > 100.0
    assert not [m for m in spec.cell(CELL)["per_layer"] if "_roofline" in m["name"]]
