"""From a profiler trace to the numbers the per-layer metrics read.

Two stages.  ``load_events`` reads the ``.xplane.pb`` that
``jax.profiler`` wrote, with ``jax.profiler.ProfileData``, into plain
tuples: the device's operations (the ``XLA Ops`` line of each TPU plane)
and the harness's own host spans (``bench.input``, ``bench.dispatch``,
``bench.sync``).  ``reduce`` works on those tuples alone, so a small
recorded event list checks it without a chip.

Control-flow operations (``while`` and the like) enclose the operations
they run; they count towards busy time but not as operations of their own.
The traced window runs from the start of the first traced step's input span
to the end of its last sync span.  Busy time is the union of the device's
operation intervals inside it; idle share is one minus busy over window.
Each idle gap is labelled with the host span the host was in at the gap's
middle (``input``, ``dispatch``, ``sync``, or ``other``).
"""
from __future__ import annotations

import glob
import os
import re

SPANS = ("bench.input", "bench.dispatch", "bench.sync")
# Control flow that encloses other operations on the same trace line.
CONTAINERS = ("while", "conditional", "call")
_BASE = re.compile(r"[A-Za-z_]+")


def short_name(name: str) -> str:
    """The HLO instruction's name: the TPU trace names an operation by its
    HLO text, ``%btt_linear.3 = f32[...] custom-call(...)``."""
    if name.startswith("%"):
        return name[1:].split(" ", 1)[0]
    return name


def base_name(name: str) -> str:
    """``btt_linear.3`` -> ``btt_linear``: an HLO instruction's stem."""
    m = _BASE.match(name)
    return m.group(0) if m else name


def _device_planes(planes):
    return [p for p in planes if p.name.startswith("/device:TPU:")]


def load_events(log_dir: str) -> dict:
    """``{"device": [[(name, start_ns, end_ns), ...] per chip],
    "host": [(span, start_ns, end_ns), ...]}``"""
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    data = ProfileData.from_file(sorted(paths)[-1])
    planes = list(data.planes)
    device = []
    for plane in _device_planes(planes):
        evs = [(short_name(e.name), e.start_ns, e.end_ns)
               for ln in plane.lines if ln.name == "XLA Ops" for e in ln.events]
        device.append(sorted(evs, key=lambda e: e[1]))
    host = []
    for plane in planes:
        if not plane.name.startswith("/host:"):
            continue
        for ln in plane.lines:
            for e in ln.events:
                if e.name in SPANS:
                    host.append((e.name, e.start_ns, e.end_ns))
    return {"device": device, "host": sorted(host, key=lambda e: e[1])}


def _union(intervals):
    """Merged, sorted intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def reduce(events: dict, top: int = 10) -> dict:
    """Window, busy time, device time by operation, and labelled idle gaps."""
    host = events["host"]
    inputs = [h for h in host if h[0] == "bench.input"]
    syncs = [h for h in host if h[0] == "bench.sync"]
    if not inputs or not syncs:
        raise ValueError("the trace holds no harness spans")
    w0, w1 = inputs[0][1], syncs[-1][2]
    window_ns = w1 - w0
    steps = sum(1 for s in syncs if s[1] >= w0)
    busy, op_ns, op_n, gaps = [], {}, {}, []
    for chip in events["device"]:
        inside = [(n, max(s, w0), min(e, w1)) for n, s, e in chip
                  if e > w0 and s < w1]
        for n, s, e in inside:
            if base_name(n) in CONTAINERS:
                continue
            op_ns[n] = op_ns.get(n, 0) + (e - s)
            op_n[n] = op_n.get(n, 0) + 1
        merged = _union([(s, e) for _, s, e in inside])
        busy.append(sum(e - s for s, e in merged))
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        for s, e in zip(edges[::2], edges[1::2]):
            if e > s:
                gaps.append((s, e))
    n_chips = max(len(events["device"]), 1)
    busy_ns = sum(busy) / n_chips
    spans = {}
    for name, s, e in host:
        if s >= w0 and e <= w1:
            spans.setdefault(name, []).append((e - s) / 1e9)
    ops = sorted(op_ns.items(), key=lambda kv: -kv[1])
    gaps.sort(key=lambda g: g[0] - g[1])
    return {
        "window_s": window_ns / 1e9,
        "busy_s": busy_ns / 1e9,
        "steps": steps,
        "chips": n_chips,
        "op_s": {n: v / 1e9 / n_chips for n, v in op_ns.items()},
        "op_n": op_n,
        "spans_s": spans,
        "device_ops": [[n, v / 1e9 / n_chips] for n, v in ops[:top]],
        "idle_gaps": [[_label(host, (s + e) // 2), (e - s) / 1e9]
                      for s, e in gaps[:top]],
    }


def _label(host, t):
    for name, s, e in host:
        if s <= t < e:
            return name.split(".", 1)[1]
    return "other"

