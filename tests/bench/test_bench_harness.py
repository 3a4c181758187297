"""The harness finds every cell's files by name, and refuses to measure
without the chip it needs: no TPU, too few chips, or a device kind with no
published peaks, each with no result line."""
import os
import re
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
for p in (os.path.join(ROOT, "src"), ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import check, peaks, run, spec  # noqa: E402

BENCH = spec.load_benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


@pytest.mark.parametrize("name", [w["name"] for w in BENCH["workloads"]])
def test_cell_resolves_by_name(name):
    cell = spec.cell(name)
    w = cell["workload"]
    conf = next(c for c in BENCH["configs"] if c["name"] == w["config"])
    assert conf["file"] == f"bench/configs/{w['config']}.json"
    assert cell["config"]["name"] == w["config"]
    for key in ("batch", "seq", "optimizer", "lr", "warmup_steps",
                "schedule_steps", "check_steps", "trace_seconds"):
        assert key in cell["traffic"]
    assert set(check.load_limits(name)) == set(check.NUMBERS)
    assert cell["per_layer"] and cell["end_to_end"]
    for m in cell["per_layer"]:
        assert callable(spec.metric_reader(m["name"]))


def test_benchmark_names_and_units():
    names = [x["name"] for k in ("configs", "workloads", "end_to_end", "per_layer")
             for x in BENCH[k]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert re.match(r"^[A-Za-z0-9_/%.-]{1,16}$", m["unit"])
        assert m["better"] in ("lower", "higher")
    moves = {m["name"] for m in BENCH["end_to_end"]}
    assert all(m["moves"] in moves for m in BENCH["per_layer"])
    assert {"setup_s"} <= moves


def test_no_tpu_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "bench", "run.py"), "--workload",
         BENCH["workloads"][0]["name"], "--seed", "1", "--seconds", "1"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "no TPU" in out.stderr


class _Dev:
    def __init__(self, kind):
        self.platform, self.device_kind = "tpu", kind


def test_unknown_device_kind_is_refused(monkeypatch, capsys):
    import jax

    monkeypatch.setattr(jax, "devices", lambda *a: [_Dev("TPU v99 imaginary")])
    argv = ["--workload", BENCH["workloads"][0]["name"], "--seed", "1",
            "--seconds", "1"]
    with pytest.raises(peaks.UnknownDevice):
        run.main(argv)
    assert capsys.readouterr().out == ""


def test_too_few_chips_is_refused(monkeypatch):
    import jax

    monkeypatch.setattr(jax, "devices", lambda *a: [_Dev("TPU v5 lite")])
    with pytest.raises(run.NoChip):
        run.find_device(4)
    assert run.find_device(1)["peaks"]["bf16_flops"] == 197e12


def test_seed_words_split_large_seeds():
    from bench.weights import seed_words

    assert list(seed_words(2**31 + 5)) == [2**31 + 5, 0]
    assert list(seed_words(2**33 + 7)) == [7, 2]


@pytest.mark.parametrize("name", [w["name"] for w in BENCH["workloads"]])
def test_cell_reports_what_its_per_layer_metrics_move(name):
    cell = spec.cell(name)
    e2e = {m["name"] for m in cell["end_to_end"]}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert all(m["moves"] in e2e for m in cell["per_layer"])


def test_every_metric_has_a_cell():
    reported = {m["name"] for w in BENCH["workloads"]
                for k in ("end_to_end", "per_layer") for m in spec.cell(w["name"])[k]}
    assert reported == {m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]}


def test_a_suffixed_metric_reads_with_its_head():
    assert spec.metric_reader("step.mfu.host_bound") is spec.metric_reader("step.mfu")
    with pytest.raises(KeyError):
        spec.metric_reader("no_such_metric.host_bound")
