"""``correct`` separates a sound program from a broken one.

The control (the reference one precision below the configuration's, in the
program's place) fails the cell's limits while the program passes them; a
run whose timed step is broken underneath reads ``correct`` false.  Both at
scaled-down sizes on the CPU; the chip's readings at the cells' own sizes
are in PERF.md."""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from bench_tiny import CPU, tiny_cell  # noqa: E402

from bench import check, run, spec  # noqa: E402
from bench.program import Program  # noqa: E402
from bench.readings import readings  # noqa: E402

# Each configuration at a scaled-down size against its cell's limits:
# ATIS in its own float32; Granite in float32 too, since bfloat16 rounding
# at these widths is far coarser than at the cell's own.
CELLS = [("atis6-tt.b1s32", False), ("granite8b-tt.b1s4096", True)]


@pytest.mark.parametrize("name,f32", CELLS)
def test_control_fails_where_the_program_passes(name, f32):
    limits = check.load_limits(name)
    got = {r["kind"]: r for r in readings(tiny_cell(name, f32), [2**31 + 11],
                                          kinds=("program", "control"))}
    nums = [k for k in check.NUMBERS[:-1] if limits[k] is not None]
    assert all(got["program"][k] <= limits[k] for k in nums), got["program"]
    assert any(got["control"][k] > limits[k] for k in nums), got["control"]


def _state_unchanged(monkeypatch):
    call = Program.call

    def broken(self, batch):
        kept = jax.tree.map(jnp.copy, (self.params, self.opt_state))
        metrics = call(self, batch)
        self.params, self.opt_state = kept
        return metrics

    monkeypatch.setattr(Program, "call", broken)


def _half_batch(monkeypatch):
    place = Program.place

    def broken(self, batch):
        mask = np.array(batch["mask"])
        if mask.shape[0] > 1:
            mask[mask.shape[0] // 2:] = 0
        else:
            mask[:, mask.shape[1] // 2:] = 0
        return place(self, dict(batch, mask=mask))

    monkeypatch.setattr(Program, "place", broken)


def _token_altered(monkeypatch):
    host_batch = Program.host_batch

    def broken(self, step):
        b = dict(host_batch(self, step))
        b["tokens"] = b["tokens"].copy()
        b["tokens"][0, 0] = (b["tokens"][0, 0] + 1) % self.V
        return b

    monkeypatch.setattr(Program, "host_batch", broken)


def test_null_limit_is_not_compared():
    numbers = dict.fromkeys(check.NUMBERS, 0.0)
    numbers["loss_gap"] = 0.5
    limits = dict.fromkeys(check.NUMBERS, 0.1)
    limits["loss_gap"] = None
    ok, table = check.verdict(numbers, limits)
    assert ok and "loss_gap" not in table
    ok, _ = check.verdict(dict(numbers, grad_gap=float("nan")), limits)
    assert not ok


def _tiny(monkeypatch, f32):
    monkeypatch.setattr(spec, "cell", lambda name, bench=None: tiny_cell(name, f32))
    monkeypatch.setattr(run, "enable_cache", lambda: None)


@pytest.mark.parametrize("name,f32", CELLS)
@pytest.mark.parametrize("fault", [_state_unchanged, _half_batch, _token_altered])
def test_broken_step_reads_incorrect(fault, name, f32, monkeypatch, capsys):
    _tiny(monkeypatch, f32)
    fault(monkeypatch)
    result = run.main(["--workload", name, "--seed", str(2**31 + 23),
                       "--seconds", "0.3"], device=CPU)
    assert result["correct"] is False
    assert capsys.readouterr().out.strip().splitlines()[-1].startswith('{"correct": false')


@pytest.mark.parametrize("name,f32", CELLS)
def test_sound_run_reads_correct(name, f32, monkeypatch):
    _tiny(monkeypatch, f32)
    result = run.main(["--workload", name, "--seed", str(2**31 + 29),
                       "--seconds", "0.3"], device=CPU)
    assert result["correct"] is True
    assert list(result)[-1] == "checks"
