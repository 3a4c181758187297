"""The comparison that decides ``correct`` for a training cell.

The program's first steps (``bench.program.Program.first_steps``) and the
plain reference's (``bench.reference.Reference.run``) each give every
step's loss and global gradient norm before clipping, each leaf's norm of
the first gradient as the optimizer got it, and each leaf's norm of the
parameters' change after the steps.  These numbers come of them:

- ``loss_gap``: the largest relative gap between the two losses of a step;
- ``grad_norm_gap``: the relative gap between the two global gradient
  norms of the first step, taken at the same parameters on both sides;
- ``grad_gap``: over the leaves, the largest gap between the two norms of
  the first gradient, over the reference's norm of that leaf or of the
  median leaf, whichever is larger;
- ``delta_gap``: the same for the parameters' change;
- ``grad_gap_median`` and ``delta_gap_median``: the median leaf's gap of
  the two, steady from seed to seed where the worst leaf swings.

Leaves whose reference gradient is nought to rounding (under a thousandth
of the median leaf's, as a key's bias is under softmax) move by round-off
alone and are left out of both leaf numbers.  ``inputs_differ`` counts the
tokens in which the program's batches differ from the benchmark's own
stream; its limit is 0.

Each cell's limits sit in ``bench/limits/<cell>.json``, with the readings
they were set from.  A number whose limit is ``null`` is not compared
there (PERF.md gives the readings that left it without a limit).
"""
from __future__ import annotations

import json
import math
import os
import statistics

NOUGHT = 1e-3
NUMBERS = ("loss_gap", "grad_norm_gap", "grad_gap", "grad_gap_median",
           "delta_gap", "delta_gap_median", "inputs_differ")


def kept_leaves(ref: dict) -> list[str]:
    med = statistics.median(ref["first_grad"].values())
    return [p for p, v in ref["first_grad"].items() if v >= NOUGHT * med]


def _leaf_gaps(prog: dict, ref: dict, keep) -> dict[str, float]:
    med = statistics.median(ref[p] for p in keep)
    return {p: abs(prog[p] - ref[p]) / max(ref[p], med)
            if math.isfinite(prog[p]) else math.inf for p in keep}


def _worst(gaps: dict[str, float]) -> tuple[float, str]:
    leaf = max(gaps, key=gaps.get)
    return gaps[leaf], leaf


def gaps(prog: dict, ref: dict) -> dict:
    """The numbers, with the worst leaf of each leaf number."""
    loss = max((abs(a - b) / abs(b) if math.isfinite(a) else math.inf)
               for a, b in zip(prog["losses"], ref["losses"]))
    a, b = prog["grad_norms"][0], ref["grad_norms"][0]
    norm = abs(a - b) / b if math.isfinite(a) else math.inf
    keep = kept_leaves(ref)
    grads = _leaf_gaps(prog["first_grad"], ref["first_grad"], keep)
    deltas = _leaf_gaps(prog["delta"], ref["delta"], keep)
    grad, grad_leaf = _worst(grads)
    delta, delta_leaf = _worst(deltas)
    return {"loss_gap": loss, "grad_norm_gap": norm, "grad_gap": grad,
            "grad_gap_median": statistics.median(grads.values()),
            "delta_gap": delta,
            "delta_gap_median": statistics.median(deltas.values()),
            "worst_leaf": {"grad_gap": grad_leaf, "delta_gap": delta_leaf},
            "leaves_kept": len(keep), "leaves": len(ref["first_grad"])}


def load_limits(workload: str) -> dict:
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)), "limits",
                        f"{workload}.json")
    with open(path) as f:
        return json.load(f)["limits"]


def verdict(numbers: dict, limits: dict) -> tuple[bool, dict]:
    """``(correct, {name: {"value", "limit"}})`` over the numbers that have
    a limit; a number that is not finite fails."""
    table = {k: {"value": numbers[k], "limit": limits[k]} for k in NUMBERS
             if limits[k] is not None}
    ok = all(math.isfinite(v["value"]) and v["value"] <= v["limit"]
             for v in table.values())
    return ok, table
