"""The program's own names in a traced window: the stage of each device
operation, and the host spans of the batch build.

The program names its work (``repro.tracing``): JAX writes forward,
backward and recompute into every compiled instruction's ``op_name``, and
the program adds the ``update`` scope and the model's parts; its host work
runs under ``data.*`` and ``train.*`` spans.  The trace names a device
operation by its HLO instruction alone, so ``attributed`` reads each
instruction's ``op_name`` from the compiled step's text, as
``bench.workcount.kernel_calls`` reads ``remat``.

A reader takes them from its record: ``record["scopes"]`` (instruction ->
``op_name``) and ``record["program_spans_s"]`` (span -> durations in s,
inside the traced window).  Where the record lacks them, the first reader
fills them in from the run's own ``.xplane.pb``, which the harness keeps
in a ``bench-trace-*`` temporary directory until its readers have run:
the trace whose window, busy time and steps are the record's.  That file
holds the program's host spans and, as the profiler keeps for every
program it saw, the compiled step itself (``hlo_modules``).  A program
without ``repro.tracing`` (one that names nothing) gives None.
"""
from __future__ import annotations

import glob
import os
import re
import tempfile

_OP = re.compile(r'^\s*(?:ROOT\s+)?%(?P<name>[\w.-]+)\s*=.*?'
                 r'\s(?P<opcode>[\w-]+)\((?P<rest>.*)$')
_COMP = re.compile(r'^(?P<entry>ENTRY\s+)?%(?P<name>[\w.-]+)\s.*\{\s*$')
_CALLED = re.compile(r'(?:body|condition|true_computation|false_computation|'
                     r'to_apply)=%([\w.-]+)|branch_computations=\{([^}]*)\}')
PROGRAM_PREFIXES = ("data.", "train.")
# Instructions that run no work of their own.
NO_WORK = ("parameter", "constant", "get-tuple-element", "tuple", "bitcast",
           "while", "conditional", "call", "after-all", "partition-id",
           "replica-id", "opt-barrier")


def _computations(hlo_text: str) -> tuple[dict, str | None]:
    comps, entry, cur = {}, None, None
    for line in hlo_text.splitlines():
        m = _COMP.match(line)
        if m:
            cur = m.group("name")
            comps[cur] = []
            if m.group("entry"):
                entry = cur
        elif line.strip() == "}":
            cur = None
        elif cur is not None:
            comps[cur].append(line)
    return comps, entry


def _op_name(rest: str) -> str:
    m = re.search(r'op_name="([^"]*)"', rest)
    return m.group(1) if m else ""


def op_names(hlo_text: str) -> dict:
    """Every instruction of a compiled program -> its ``op_name`` (empty
    where it has none)."""
    out = {}
    for line in hlo_text.splitlines():
        m = _OP.match(line)
        if m:
            out[m.group("name")] = _op_name(m.group("rest"))
    return out


def _operands(rest: str) -> list[str]:
    depth = 0
    for i, ch in enumerate(rest):
        if ch in "({":
            depth += 1
        elif ch in ")}":
            if depth == 0:
                return re.findall(r"%([\w.-]+)", rest[:i])
            depth -= 1
    return []


def top_level(hlo_text: str) -> dict:
    """The instructions the device runs as operations of their own: those
    of the entry computation and of the loops, branches and calls it runs,
    not those fused inside them.  ``{name: (opcode, op_name, operands,
    caller)}``, where ``caller`` is the loop, branch or call instruction
    that runs the instruction's computation (None in the entry)."""
    comps, entry = _computations(hlo_text)
    out, todo, seen = {}, [(entry, None)], set()
    while todo:
        comp, caller = todo.pop()
        if comp is None or comp in seen or comp not in comps:
            continue
        seen.add(comp)
        for line in comps[comp]:
            m = _OP.match(line)
            if m is None:
                continue
            name, opcode, rest = m.group("name"), m.group("opcode"), m.group("rest")
            out[name] = (opcode, _op_name(rest), _operands(rest), caller)
            if opcode in ("while", "conditional", "call"):
                for a, b in _CALLED.findall(rest):
                    todo += [(c, name) for c in
                             ([a] if a else re.findall(r"%([\w.-]+)", b))]
    return out


def _traced(op_name: str) -> bool:
    """Whether an ``op_name`` comes from the traced program (JAX names
    those ``jit(<fn>)/...``; an argument's copy carries its path)."""
    return op_name.startswith("jit(")


def attributed(hlo_text: str) -> dict:
    """``top_level``'s instructions -> ``op_name``.  One that the compiler
    inserted (a layout or memory-space copy, a convert) carries no
    ``op_name`` of the program; it takes that of the first instruction
    that reads its result, through others like it, else that of the first
    it reads, else that of the loop or call that runs it."""
    ops = top_level(hlo_text)
    users: dict = {}
    for name, (_, _, operands, _) in ops.items():
        for a in operands:
            users.setdefault(a, []).append(name)

    def search(start, step):
        seen, frontier = {start}, list(step(start))
        while frontier:
            nxt = []
            for n in frontier:
                if n in seen or n not in ops:
                    continue
                seen.add(n)
                if _traced(ops[n][1]):
                    return ops[n][1]
                nxt += step(n)
            frontier = nxt
        return None

    def own(name):
        op_name = ops[name][1]
        if _traced(op_name):
            return op_name
        caller = ops[name][3]
        return (search(name, lambda n: users.get(n, []))
                or search(name, lambda n: ops[n][2])
                or (own(caller) if caller else op_name))

    return {name: own(name) for name in ops}


def has_work(opcode: str) -> bool:
    return opcode not in NO_WORK


# --- a run's scopes and spans ---------------------------------------------

def _stage_of():
    try:
        from repro.tracing import stage_of
    except ImportError:
        return None
    return stage_of


def _varint(buf, i: int) -> tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf):
    """``(field number, value)`` of one protobuf message: an int, or a
    ``memoryview`` for the length-delimited and fixed-width fields."""
    i = 0
    while i < len(buf):
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            v, i = buf[i:i + n], i + n
        elif wire in (1, 5):
            n = 8 if wire == 1 else 4
            v, i = buf[i:i + n], i + n
        else:
            raise ValueError(f"protobuf wire type {wire}")
        yield key >> 3, v


def hlo_modules(path: str) -> list:
    """The serialized ``HloModuleProto`` of every program in a trace: the
    profiler keeps each as the ``Hlo Proto`` stat of an event metadata of
    its ``/host:metadata`` plane (XSpace: planes 1; XPlane: name 2, event
    metadata 4, stat metadata 5; XEventMetadata: stats 5; XStat: metadata
    id 1, bytes 6; HloProto: module 1)."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    for num, plane in _fields(space):
        fields = list(_fields(plane)) if num == 1 else []
        if not any(n == 2 and bytes(v) == b"/host:metadata" for n, v in fields):
            continue
        stat_names, events = {}, []
        for n, v in fields:
            entry = dict(_fields(v)) if n in (4, 5) else {}
            if n == 5:
                meta = dict(_fields(entry[2]))
                stat_names[meta.get(1, 0)] = bytes(meta.get(2, b""))
            elif n == 4:
                events.append(entry[2])
        out = []
        for em in events:
            for n, v in _fields(em):
                stat = dict(_fields(v)) if n == 5 else {}
                if stat_names.get(stat.get(1)) == b"Hlo Proto" and 6 in stat:
                    out.append(bytes(dict(_fields(stat[6])).get(1, b"")))
        return out
    return []


def hlo_text(module: bytes) -> str:
    """A serialized ``HloModuleProto`` as HLO text with its metadata, as
    ``compiled.as_text()`` prints it."""
    from jax._src.lib import xla_client

    options = xla_client._xla.HloPrintOptions()
    options.print_metadata = True
    return xla_client.XlaComputation(module).get_hlo_module().to_string(options)


def step_names(path: str, op_s: dict) -> dict | None:
    """``attributed`` names of the program in the trace at ``path`` whose
    instructions ran the most of the device time ``op_s`` (instruction ->
    s); None where none of them ran."""
    best, most = None, 0.0
    for module in hlo_modules(path):
        text = hlo_text(module)
        names = op_names(text)
        spent = sum(v for n, v in op_s.items() if n in names)
        if spent > most:
            best, most = text, spent
    return attributed(best) if best is not None else None


def load_program_spans(path: str) -> list:
    """The program's host spans (``data.*``, ``train.*``) of one
    ``.xplane.pb``: ``[(name, start_ns, end_ns), ...]``."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    out = []
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for ln in plane.lines:
            for e in ln.events:
                if e.name.startswith(PROGRAM_PREFIXES):
                    out.append((e.name, e.start_ns, e.end_ns))
    return sorted(out, key=lambda e: e[1])


def window_spans(spans, host) -> dict:
    """Program spans inside the window the harness's spans ``host`` make
    (``bench.trace_reduce.reduce``'s window): ``{name: [s, ...]}``."""
    inputs = [h for h in host if h[0] == "bench.input"]
    syncs = [h for h in host if h[0] == "bench.sync"]
    if not inputs or not syncs:
        return {}
    w0, w1 = inputs[0][1], syncs[-1][2]
    out = {}
    for name, s, e in spans:
        if s >= w0 and e <= w1:
            out.setdefault(name, []).append((e - s) / 1e9)
    return out


def _window_key(trace: dict) -> tuple:
    return (trace["window_s"], trace["busy_s"], trace["steps"])


def _trace_files():
    pattern = os.path.join(tempfile.gettempdir(), "bench-trace-*", "**",
                           "*.xplane.pb")
    return sorted(glob.glob(pattern, recursive=True), key=os.path.getmtime,
                  reverse=True)


def _read_own_trace(record: dict) -> None:
    """Fill ``record["scopes"]`` and ``record["program_spans_s"]``, where
    it lacks them, from the run's own ``.xplane.pb``: the newest
    ``bench-trace-*`` trace whose window, busy time and steps are the
    record's."""
    from bench.trace_reduce import load_events, reduce

    found = {"scopes": None, "program_spans_s": {}}
    for path in _trace_files():
        events = load_events(os.path.dirname(path))
        if _window_key(reduce(events)) == _window_key(record["trace"]):
            found = {"scopes": step_names(path, record["trace"]["op_s"]),
                     "program_spans_s": window_spans(
                         load_program_spans(path), events["host"])}
            break
    for key, value in found.items():
        record.setdefault(key, value)


def scopes(record: dict) -> dict | None:
    """Instruction -> ``op_name`` of the run's compiled step."""
    if "scopes" not in record:
        _read_own_trace(record)
    return record["scopes"]


def program_spans(record: dict) -> dict:
    """Span name -> durations in s inside the traced window."""
    if "program_spans_s" not in record:
        _read_own_trace(record)
    return record["program_spans_s"]


# --- what the readers report -------------------------------------------------

def stage_ms(record: dict, stage: str) -> float | None:
    """Device time a traced step of the operations whose ``op_name`` lies
    in ``stage`` (``repro.tracing.stage_of``), in ms; None where the
    program names no stages or the trace holds no step."""
    stage_of, t = _stage_of(), record["trace"]
    if stage_of is None or t["steps"] <= 0:
        return None
    names = scopes(record)
    if names is None:
        return None
    spent = sum(v for n, v in t["op_s"].items()
                if stage_of(names.get(n, "")) == stage)
    return 1e3 * spent / t["steps"]


def span_ms(record: dict, name: str) -> float | None:
    """Mean of the program's ``name`` spans in the traced window, in ms."""
    spans = program_spans(record).get(name)
    if not spans:
        return None
    return 1e3 * sum(spans) / len(spans)
