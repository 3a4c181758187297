"""The names a training step carries in a profiler trace.

Host work runs under :func:`span`, a ``jax.profiler.TraceAnnotation``: it
costs next to nothing while no trace is being taken, and lands in the same
``.xplane.pb`` as the device's operations, on the same clock.  The names
are ``data.*`` (batch build) and ``train.*`` (``launch.train``'s loop:
``input``, ``dispatch``, ``sync``, ``guard``, ``checkpoint``).

Device work is named with ``jax.named_scope(<name>)``: a scope only adds
to each operation's ``op_name`` metadata, so the compiled computation is
the same with or without it.  The model's parts are :data:`PARTS`
(``embed``, ``attn``, ``ffn``, ``head``); everything a train step does
after the gradients (grad-tier cast, clip, optimizer) is :data:`UPDATE`.
Flash attention's launches over head blocks run under :data:`FLASH_ROWS`,
which marks the path a shape took.

:func:`stage_of` reads the stage of one compiled instruction from its
``op_name``.  Forward, backward and recompute come from the name stack JAX
writes itself (``jvp(``, ``transpose(``, ``rematted_computation``); only
the update needs the program's own scope.
"""
from __future__ import annotations

import re

import jax

EMBED, ATTN, FFN, HEAD = "embed", "attn", "ffn", "head"
PARTS = (EMBED, ATTN, FFN, HEAD)
UPDATE = "update"
# Flash launches that take a head block (many whole short sequences a grid
# step); metadata only, read by no stage or part.
FLASH_ROWS = "flash_rows"
STAGES = ("forward", "backward", "recompute", "update")

_SCOPES = re.compile(r"(?:^|[/(])(%s)(?=[/)]|$)" % "|".join(PARTS + (UPDATE,)))


def span(name: str) -> jax.profiler.TraceAnnotation:
    """A host span: ``with span("train.sync"): ...``."""
    return jax.profiler.TraceAnnotation(name)


def _scopes(op_name: str) -> list[str]:
    return _SCOPES.findall(op_name)


def stage_of(op_name: str) -> str | None:
    """``"forward"``, ``"backward"``, ``"recompute"``, ``"update"`` or
    None for an instruction's ``op_name``
    (``jit(train_step)/transpose(jvp())/while/body/closed_call/checkpoint/
    rematted_computation/attn/dot_general`` is a recompute).  A model
    part's work that JAX hoisted out of the differentiated function (RoPE's
    tables) counts as forward."""
    if "rematted_computation" in op_name:
        return "recompute"
    if "transpose(" in op_name:
        return "backward"
    if UPDATE in _scopes(op_name):
        return "update"
    if "jvp(" in op_name or part_of(op_name):
        return "forward"
    return None


def part_of(op_name: str) -> str | None:
    """The innermost model part (:data:`PARTS`) an ``op_name`` lies in."""
    parts = [s for s in _scopes(op_name) if s in PARTS]
    return parts[-1] if parts else None
