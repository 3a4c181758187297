#!/usr/bin/env python3
"""Readings that the limits of ``bench/limits/<cell>.json`` are set from.

    python3 bench/readings.py --workload <cell> --seeds 1,2,3 [--kinds ...] [--out FILE]

For each seed, in one process and at the cell's own size, against the plain
reference (``bench.reference``):

- ``program``: the program's first steps (the lower readings);
- ``control``: the reference computed one precision below the precision
  of the configuration's matrix products (``product_dtype``) in the
  program's place: float8 e4m3 operands for bfloat16 products;
- ``half_batch``: the fault of half of each batch left out, planted in the
  reference put in the program's place;
- ``bf16_products``: the reference with bfloat16 operands and the
  configuration's storage, the precision the program states;
- ``bf16_storage``: bfloat16 operands and parameters stored in bfloat16;
- ``highest``: the program built and run under
  ``jax.default_matmul_precision("highest")``, to see how much of the
  program's gap to the reference its products' precision makes.

A step that returns its state unchanged reads 1 on both leaf numbers by
their definition and needs no run.  One JSON line per seed and kind.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path[:] = [p for p in sys.path if os.path.abspath(p or ".") != BENCH]

CONTROL = {"float32": "bfloat16", "bfloat16": "float8_e4m3fn"}
PROGRAM_KINDS = ("program", "highest")


def readings(cell: dict, seeds, kinds=("program", "control", "half_batch")):
    """Yield ``{"seed", "kind", numbers...}`` for every seed and kind."""
    import jax

    from bench import check, tokens
    from bench.program import Program, layout, reference_weights
    from bench.reference import Reference

    config, traffic = cell["config"], cell["traffic"]
    n = traffic["check_steps"]
    progs = {}
    if "program" in kinds:
        progs["program"] = Program(config, traffic, seeds[0])
    if "highest" in kinds:
        with jax.default_matmul_precision("highest"):
            progs["highest"] = Program(config, traffic, seeds[0])
    names = layout(config)
    ref = Reference(config, traffic)
    others = {
        "control": Reference(config, traffic,
                             lowp=CONTROL[config["product_dtype"]]),
        "half_batch": Reference(config, traffic, drop_half=True),
        "bf16_products": Reference(config, traffic, lowp="bfloat16"),
        "bf16_storage": Reference(config, traffic, lowp="bfloat16",
                                  store="bfloat16"),
    }
    for seed in seeds:
        batches = [tokens.batch(seed, i, traffic["batch"], traffic["seq"],
                                config["model"]["vocab_size"]) for i in range(n)]
        w = reference_weights(names, seed, config["family"])
        base = ref.run(w, batches)
        for kind in kinds:
            if kind in PROGRAM_KINDS:
                progs[kind].reseed(seed)
                got = progs[kind].first_steps(n)
            else:
                got = others[kind].run(w, batches)
            nums = check.gaps(got, base)
            yield {"seed": seed, "kind": kind, "t": time.perf_counter(),
                   **{k: nums[k] for k in check.NUMBERS[:-1]},
                   "worst_leaf": nums["worst_leaf"], "losses": got["losses"],
                   "reference_losses": base["losses"],
                   "grad_norms": got["grad_norms"],
                   "reference_grad_norms": base["grad_norms"]}


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--out", default=None)
    ap.add_argument("--kinds", default="program,control,half_batch")
    args = ap.parse_args(argv)
    for p in (os.path.join(ROOT, "src"), ROOT):
        if p not in sys.path:
            sys.path.insert(0, p)
    from bench import run, spec

    cell = spec.cell(args.workload)
    run.find_device(cell["workload"]["chips"])
    run.enable_cache()
    seeds = [int(s) for s in args.seeds.split(",")]
    out = open(args.out, "a") if args.out else None
    for rec in readings(cell, seeds, tuple(args.kinds.split(","))):
        line = json.dumps({"workload": args.workload, **rec})
        print(line, flush=True)
        if out:
            out.write(line + "\n")
            out.flush()


if __name__ == "__main__":
    main()
