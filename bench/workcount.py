"""A training step's least work, from the model's shapes (the
configuration's file and the layout of the program's parameters).

``step_work`` gives the whole step's least FLOPs, for MFU, from the file of
the configuration's model family, ``bench/models/<family>.py``.
``call_work`` gives one kernel call's least FLOPs and bytes, for the kernel
families' roofline shares, from the file of its kernel,
``bench/kernels/<kernel>.py``: a new family or kernel adds a file.

Both counts follow from shapes alone: each operand read once and each
result written once, at its dtype, wherever the compiled step keeps it.
``on_chip_bytes`` says beside them how much of a call's counted bytes the
compiled step keeps in the chip's own memory, which the count does not
take into account.
"""
from __future__ import annotations

import math
import re

from bench import spec, weights


# --- shapes shared by the kernel files ---------------------------------------

def itemsize(config: dict) -> int:
    return 4 if config["model"]["dtype"] == "float32" else 2


def mid_rank(config: dict, M: int, N: int) -> int:
    tt = config["tt"]
    return min(tt["rank"], M, N) if tt["clamp_ranks"] else tt["rank"]


def vocab_padded(v: int) -> int:
    return (v + 255) // 256 * 256


TILE = 512  # the widest padding a BTT kernel gives a side (btt_linear's lanes)


def fit_matrix(ctx: dict, rows: int, cols: int) -> tuple[int, int]:
    """The model's TT matrix ``(out, in)`` that the kernels padded to
    ``(rows, cols)``, or its transpose.

    The matrices are the layout's (``ctx["sides"]``, by owner), matched
    whole, with less than a ``TILE`` of padding on each side: one side
    matched alone can reach another matrix's side (an in-width of 2,816
    padded to 3,072, a query width).  A transpose (the head's backward runs
    ``btt_linear`` on its transposed factors) is matched only where no
    matrix fits as it stands; of those that fit, the largest."""
    pairs = {tuple(p) for p in ctx["sides"].values()}

    def fitting(cands):
        return [p for p in cands
                if 0 <= rows - p[0] < TILE and 0 <= cols - p[1] < TILE]

    fits = fitting(pairs) or fitting({p[::-1] for p in pairs})
    if not fits:
        raise ValueError(f"no TT matrix of the model fits in ({rows}, {cols}):"
                         f" {sorted(pairs)}")
    return max(fits, key=lambda p: (p[0] * p[1], p))


def tokens(ctx: dict, padded_rows: int) -> int:
    """The rows a kernel call works on: the traffic's tokens a step, or
    fewer where the call takes fewer (padding never counts)."""
    t = ctx["traffic"]
    return min(padded_rows, t["batch"] * t["seq"])


def groups(call: dict) -> tuple[int, list]:
    """``(G, operands)`` of a call whose count expects 2-D operands: a call
    whose operands carry leading axes besides (a kernel under ``vmap`` over
    experts) runs ``G`` groups, each on its operands without those axes."""
    ops = [tuple(o) for o in call["operands"]]
    lead = ops[0][:-2]
    if any(o[:-2] != lead for o in ops):
        raise ValueError(f"{call['kernel']}: operands with unlike group "
                         f"axes {ops}; such a call needs a count of its own")
    return math.prod(lead), [o[-2:] for o in ops]


def grouped(G: int, counted) -> tuple:
    """``(FLOPs, bytes read, bytes written)`` of one group, times ``G``."""
    fl, ins, outs = counted
    return G * fl, [G * b for b in ins], [G * b for b in outs]


# --- the whole step ------------------------------------------------------------

def step_work(config: dict, traffic: dict, layout) -> dict:
    """``{"step_flops", "params", "sides"}``: the step's least FLOPs, from
    the model family's file, the parameter count, and the ``(out, in)`` of
    every TT matrix of the layout, by owner."""
    family = spec.load_module("models", config["family"])
    return {"step_flops": family.step_flops(config, traffic, layout),
            "params": sum(math.prod(s) for _, s, _ in layout),
            "sides": weights.matrix_sides(layout)}


def least_seconds(ops, peaks: dict) -> float:
    """Sum over operations of the larger of FLOPs over peak and bytes over
    HBM bandwidth."""
    return sum(max(fl / peaks["bf16_flops"], by / peaks["hbm_bytes_per_s"])
               for fl, by in ops)


# --- per kernel call, from the compiled step's HLO -------------------------

_INSTR = re.compile(r"\s*(?:ROOT\s+)?%(?P<name>[\w.-]+)\s*=\s*(?P<type>.*?)\s[\w-]+\(")
_ARRAY = re.compile(r"[a-z0-9]+\[(?P<dims>[0-9,]*)\](?P<layout>\{[^}]*\})?")
_OPERANDS = re.compile(r"custom-call\((?P<args>[^)]*)\)")


def _in_hbm(type_text: str) -> list[bool]:
    """For each array of an HLO result type, whether it lives in HBM: the
    TPU compiler marks arrays it keeps in on-chip memory with ``S(n)``."""
    return [not (m.group("layout") or "").count("S(")
            for m in _ARRAY.finditer(type_text)]


def kernel_calls(hlo_text: str) -> dict:
    """Each Mosaic kernel instruction of a compiled program: its kernel
    (``pallas_call`` name), operand shapes, whether each operand and each
    result lives in HBM, and whether it sits in a rematerialized
    (recomputed) computation."""
    types, lines = {}, []
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if m is None:
            continue
        types[m.group("name")] = m.group("type")
        if 'custom_call_target="tpu_custom_call"' in line:
            lines.append((m, line))
    out = {}
    for m, line in lines:
        name = m.group("name")
        cons = line.split("operand_layout_constraints={", 1)
        operands = []
        if len(cons) == 2:
            body = cons[1].split("}}", 1)[0]
            operands = [tuple(int(x) for x in a.group("dims").split(",") if x)
                        for a in _ARRAY.finditer(body)]
        args = _OPERANDS.search(line)
        names = re.findall(r"%([\w.-]+)", args.group("args")) if args else []
        op_name = line.split('op_name="', 1)[1].split('"', 1)[0] if 'op_name="' in line else ""
        out[name] = {"kernel": re.match(r"[A-Za-z_]+", name).group(0),
                     "operands": operands,
                     "hbm_in": [all(_in_hbm(types.get(a, ""))) for a in names],
                     "hbm_out": _in_hbm(m.group("type")),
                     "remat": "rematted_computation" in op_name}
    return out


def _counted(call: dict, ctx: dict):
    """``(FLOPs, bytes read, bytes written)`` of one execution, from the
    kernel's file; a call in a recomputed (remat) computation needs no work
    of its own."""
    if call["remat"]:
        return 0, [], []
    return spec.load_module("kernels", call["kernel"]).work(call, ctx)


def call_work(call: dict, ctx: dict) -> tuple[int, int]:
    """The least ``(FLOPs, bytes)`` of one execution of a kernel call.
    ``ctx`` is ``context``'s."""
    fl, ins, outs = _counted(call, ctx)
    return fl, sum(ins) + sum(outs)


def on_chip_bytes(call: dict, ctx: dict) -> int:
    """Of ``call_work``'s bytes, those of the operands and results that the
    compiled step keeps in on-chip memory.  Where the compiled call's
    operands do not line up with the count's, all of them count as on chip
    if any is."""
    _, ins, outs = _counted(call, ctx)
    hin, hout = call.get("hbm_in"), call.get("hbm_out")
    if hin is None or hout is None:
        return 0
    if len(hin) != len(ins) or len(hout) != len(outs):
        return 0 if all(hin) and all(hout) else sum(ins) + sum(outs)
    return (sum(b for b, h in zip(ins, hin) if not h)
            + sum(b for b, h in zip(outs, hout) if not h))


def context(record: dict) -> dict:
    """What a kernel's count sees: the run's ``config``, ``traffic``,
    parameter count ``params``, TT matrix ``sides`` and compiled ``calls``."""
    work = record["work"]
    return {"config": record["config"], "traffic": record["traffic"],
            "params": work["params"], "sides": work["sides"],
            "calls": record["calls"]}


def family_share(record: dict, kernels) -> float | None:
    """Share of its roofline that a family of kernels (their ``pallas_call``
    names) reaches over the traced window, in %: the least time of every
    execution of them (``call_work``) over their device time.  None when
    the trace holds no execution of them that needs work of its own."""
    from bench.trace_reduce import base_name

    trace, calls = record["trace"], record["calls"]
    names = [n for n in trace["op_s"] if base_name(n) in kernels]
    spent = sum(trace["op_s"][n] for n in names)
    if spent <= 0:
        return None
    ctx = context(record)
    need = 0.0
    for n in names:
        if n not in calls:
            raise KeyError(f"traced kernel {n!r} is not in the compiled step")
        need += trace["op_n"][n] / trace["chips"] * least_seconds(
            [call_work(calls[n], ctx)], record["peaks"])
    return 100.0 * need / spent if need > 0 else None


def on_chip_share(calls: dict, ctx: dict) -> dict:
    """For each kernel of the compiled step, the share of its counted bytes
    that the step keeps on chip, in %: what the roofline's HBM term does
    not see."""
    total, chip = {}, {}
    for c in calls.values():
        k = c["kernel"]
        if c["remat"] or not spec.has_module("kernels", k):
            continue
        total[k] = total.get(k, 0) + call_work(c, ctx)[1]
        chip[k] = chip.get(k, 0) + on_chip_bytes(c, ctx)
    return {k: 100.0 * chip[k] / total[k] for k in total if total[k] > 0}
