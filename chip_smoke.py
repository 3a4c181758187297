"""Smoke test of the training main path on a TPU.

Trains the paper's ATIS model (``--arch atis-transformer``: 2 encoders,
d_model 768, 12 heads, TT rank 12, TTM embedding rank 30, vocab 1000, f32)
at the paper's shape (batch 1, seq 32) through ``repro.launch.train.main``,
with random weights from a seed, in one process:

1. device  -- JAX's first device must be a TPU; there is no CPU fallback.
2. default -- 5 steps with SGD (the paper's optimizer), then with AdamW.
   Every loss is finite, and the last update lowers the loss of the batch
   it was taken on.  (Each step draws a fresh batch of 32 tokens from a
   near-uniform stream over 1000 tokens, so the step-to-step losses of 5
   steps do not fall: the first updates fit their own batch and hurt the
   next.)
3. kernels -- the same two runs with ``--kernel-flow --fused-attn
   --fused-ffn --fused``.  The compiled step must hold a Mosaic kernel
   (``tpu_custom_call``) for each of the BTT linear, BTT backward, FFN
   megakernel forward and backward, flash attention forward and backward
   and the fused update; its losses, and its step-0 gradient norm, must
   agree with the default path.

``--four-chips`` runs instead, and only, the multi-device training paths
(``--data-axis 4``; ``--pipeline-stages 2 --tp 2``), each against the
one-device step at the same global batch and seed: first at the TPU's
default matmul precision, the one users run, then at "highest", where
rounding cannot hide a partitioning error.

Each phase prints one JSON line; the last line of standard output is
``{"ok": true, "device": {...}}``.  Any failure prints the error to
standard error and exits non-zero with no result line.

    python chip_smoke.py [--four-chips]
"""
from __future__ import annotations

import argparse
import contextlib
import json
import math
import os
import re
import sys
import traceback

ROOT = os.path.dirname(os.path.abspath(__file__))

STEPS = 5
ATIS = ["--arch", "atis-transformer", "--seq", "32", "--steps", str(STEPS),
        "--seed", "0", "--log-every", "1"]
KERNELS = ["--kernel-flow", "--fused-attn", "--fused-ffn", "--fused"]
LR = {"sgd": "4e-2", "adamw": "3e-3"}
# Kernel path vs default path on the chip, relative.  On a v5e the step-0
# losses are equal, the step-4 losses differ by at most 3.1e-4 and the
# step-0 gradient norms (taken before any update) by 3.5e-4.
KERNEL_RTOL = {"loss_step0": 1e-4, "loss_last": 3e-3,
               "grad_norm_step0": 2e-3}
# Multi-device step vs one-device step, relative, per matmul precision
# (None: the TPU's default).  Steps 0 and 1 start from the same parameters
# (the warm-up gives step 0 a learning rate of 0), so they compare one
# gradient evaluation; later steps compare two trajectories.  Measured on
# four v5e at the default precision, worst of both paths: steps 0-1 loss
# 4.3e-6, gradient norm 2.8e-4; all steps loss 7.7e-5, gradient norm
# 2.3e-2 (at step 4: each program's bf16 gradients are about 3% off the
# exact ones, and updates in slightly different directions move the
# later gradient norms apart).  At "highest" every step agreed within
# 1.4e-7 (loss) and 8.8e-7 (gradient norm).
MESH_RTOL = {
    None: {"steps_0_1": {"loss": 1e-4, "grad_norm": 2e-3},
           "all_steps": {"loss": 5e-4, "grad_norm": 5e-2}},
    "highest": {"all_steps": {"loss": 1e-4, "grad_norm": 1e-3}},
}
# Mosaic kernels the kernel-path step must hold (pallas_call names).
REQUIRED_KERNELS = {
    "sgd": ("btt_linear", "btt_backward", "btt_ffn_fwd", "btt_ffn_bwd",
            "flash_fwd", "flash_bwd", "fused_sgd"),
    "adamw": ("btt_linear", "btt_backward", "btt_ffn_fwd", "btt_ffn_bwd",
              "flash_fwd", "flash_bwd", "fused_adamw"),
}


class SmokeError(Exception):
    pass


def check(ok: bool, msg: str) -> None:
    if not ok:
        raise SmokeError(msg)


def emit(record: dict) -> None:
    print(json.dumps(record), flush=True)


def rel(a: float, b: float) -> float:
    return abs(a - b) / max(abs(b), 1e-12)


def train(argv: list[str]) -> dict:
    """``launch.train.main`` with its log lines sent to standard error."""
    from repro.launch import train as launch_train

    with contextlib.redirect_stdout(sys.stderr):
        return launch_train.main(argv)


def atis_argv(optimizer: str, extra: list[str] = (),
              batch: int = 1) -> list[str]:
    return ATIS + ["--batch", str(batch), "--optimizer", optimizer,
                   "--lr", LR[optimizer], *extra]


def atis_run(optimizer: str, extra: list[str] = (), batch: int = 1) -> dict:
    argv = atis_argv(optimizer, extra, batch)
    out = train(argv)
    losses = out["losses"]
    check(len(losses) == STEPS, f"{argv}: {len(losses)} steps, not {STEPS}")
    check(all(math.isfinite(v) for v in losses + out["grad_norms"]),
          f"{argv}: non-finite loss or grad norm: {losses}")
    return out


def trained_run(name: str, optimizer: str, extra: list[str] = ()) -> dict:
    """A one-chip ATIS run whose last update must lower the loss of the
    batch it was taken on: the loss of the returned parameters on the last
    batch is below the loss ``main`` logged for that batch."""
    import jax

    from repro.models.transformer import loss_fn

    out = atis_run(optimizer, extra)
    cfg = out["cfg"]
    after = float(jax.jit(lambda p, b: loss_fn(p, cfg, b))(out["params"],
                                                          out["batch"]))
    before = out["losses"][-1]
    check(math.isfinite(after) and after < before,
          f"{name} {optimizer}: the last update did not lower its batch's "
          f"loss ({before} -> {after})")
    out["last_batch_loss_after"] = after
    return out


def count_kernels(hlo_text: str) -> dict[str, int]:
    """``tpu_custom_call`` count per kernel (``pallas_call`` name) in a
    compiled program's HLO text."""
    counts: dict[str, int] = {}
    for line in hlo_text.splitlines():
        if 'custom_call_target="tpu_custom_call"' in line:
            m = re.match(r"\s*(?:ROOT\s+)?%([A-Za-z_]\w*?)(?:\.\d+)*\s*=",
                         line)
            name = m.group(1) if m else "?"
            counts[name] = counts.get(name, 0) + 1
    return counts


def kernel_counts(out: dict) -> dict[str, int]:
    """Mosaic kernels in the compiled train step that ``launch.train.main``
    ran (its jitted ``step_fn``, lowered at its final state and batch)."""
    lowered = out["step_fn"].lower(out["params"], out["opt_state"],
                                   out["batch"])
    return count_kernels(lowered.compile().as_text())


def phase_device(four_chips: bool):
    import jax

    try:
        devices = jax.devices()
    except RuntimeError as e:
        raise SmokeError(f"JAX found no devices: {e}") from e
    d = devices[0]
    check(d.platform == "tpu",
          f"no TPU found: JAX's first device is {d.platform} "
          f"({d.device_kind})")
    need = 4 if four_chips else 1
    check(len(devices) >= need,
          f"{len(devices)} TPU device(s); this run needs {need}")
    device = {"platform": d.platform, "kind": d.device_kind,
              "count": len(devices)}
    emit({"phase": "device", **device})
    return device


def phase_default() -> dict[str, dict]:
    runs = {opt: trained_run("default", opt) for opt in ("sgd", "adamw")}
    emit({"phase": "default",
          **{opt: {k: out[k] for k in ("losses", "grad_norms",
                                       "last_batch_loss_after")}
             for opt, out in runs.items()}})
    return runs


def phase_kernels(default: dict[str, dict]) -> None:
    record = {"phase": "kernels"}
    for opt in ("sgd", "adamw"):
        out = trained_run("kernel", opt, KERNELS)
        counts = kernel_counts(out)
        missing = [k for k in REQUIRED_KERNELS[opt] if not counts.get(k)]
        check(not missing,
              f"kernel path ({opt}): no Mosaic kernel for {missing}; "
              f"tpu_custom_call counts {counts}")
        ref = default[opt]
        diffs = {"loss_step0": rel(out["losses"][0], ref["losses"][0]),
                 "loss_last": rel(out["losses"][-1], ref["losses"][-1]),
                 "grad_norm_step0": rel(out["grad_norms"][0],
                                        ref["grad_norms"][0])}
        over = {k: v for k, v in diffs.items() if not v <= KERNEL_RTOL[k]}
        check(not over,
              f"kernel {opt} vs default: rel diffs {over} over the limits "
              f"{KERNEL_RTOL}; kernel losses {out['losses']}, grad norms "
              f"{out['grad_norms']}; default losses {ref['losses']}, grad "
              f"norms {ref['grad_norms']}")
        record[opt] = {"tpu_custom_call": counts, "losses": out["losses"],
                       "grad_norms": out["grad_norms"],
                       "last_batch_loss_after": out["last_batch_loss_after"],
                       **{f"rel_diff_{k}": v for k, v in diffs.items()}}
    emit(record)


def _spans_four(tree, what: str) -> None:
    import jax

    for leaf in jax.tree.leaves(tree):
        n = len(leaf.sharding.device_set)
        check(n == 4, f"{what}: a {leaf.shape} leaf lives on {n} "
                      f"device(s), not 4")


def phase_four_chips() -> None:
    import jax

    # Global batch 4 so every row split is whole; SGD, the paper's optimizer.
    paths = {
        "data_axis_4": ([], ["--data-axis", "4"]),
        "pipeline_2_tp_2": (KERNELS, ["--pipeline-stages", "2", "--tp", "2",
                                      "--microbatches", "2"]),
    }
    steps = {"steps_0_1": slice(0, 2), "all_steps": slice(None)}
    record = {"phase": "four_chips"}
    failures = []
    for precision, limits in MESH_RTOL.items():
        label = f"{precision or 'default'}_precision"
        record[label] = {}
        ctx = (jax.default_matmul_precision(precision) if precision
               else contextlib.nullcontext())
        with ctx:
            for name, (base, mesh_args) in paths.items():
                ref = atis_run("sgd", base, batch=4)
                out = atis_run("sgd", base + mesh_args, batch=4)
                _spans_four(out["params"], f"{name} params")
                _spans_four(out["batch"], f"{name} batch")
                diffs = {}
                for span, limit in limits.items():
                    for key in ("losses", "grad_norms"):
                        metric = "loss" if key == "losses" else "grad_norm"
                        worst = max(rel(a, b) for a, b in
                                    zip(out[key][steps[span]],
                                        ref[key][steps[span]]))
                        diffs[f"max_rel_diff_{metric}_{span}"] = worst
                        if not worst <= limit[metric]:
                            failures.append(
                                f"{name} at {label}: {metric} rel diff "
                                f"{worst:.3e} over {span} vs one device "
                                f"(limit {limit[metric]})")
                record[label][name] = {
                    "losses": out["losses"],
                    "one_device_losses": ref["losses"],
                    "grad_norms": out["grad_norms"],
                    "one_device_grad_norms": ref["grad_norms"], **diffs}
    # The record goes out before the verdict, so a failure shows every path
    # at every precision.
    emit(record)
    check(not failures, "; ".join(failures))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the multi-device paths on four chips")
    args = ap.parse_args(argv)
    try:
        sys.path.insert(0, os.path.join(ROOT, "src"))
        try:
            from repro.launch.compile_cache import enable_compile_cache
        except ImportError as e:
            raise SmokeError(
                f"the repro package is not next to chip_smoke.py: {e}") from e
        enable_compile_cache()
        device = phase_device(args.four_chips)
        if args.four_chips:
            phase_four_chips()
        else:
            phase_kernels(phase_default())
    except Exception as e:  # noqa: BLE001 — report any failure, exit non-zero
        traceback.print_exc()
        print(f"[chip_smoke] FAILED: {e}", file=sys.stderr)
        return 1
    emit({"ok": True, "device": device})
    return 0


if __name__ == "__main__":
    sys.exit(main())
