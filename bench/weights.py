"""Weights from the seed, made on the device in one jitted call.

The benchmark makes the weights itself, so that the plain reference can be
given the same numbers without taking anything the program made.  The
leaves are named by their key path in the program's parameter tree
(``"['layers'][0]['attn']['q'].cores[2]"``); the scale of each follows from
its name and shape alone:

- TT matrix cores ``(..., r, m, r')``, 2d of them per matrix: the chain of
  cores reconstructs a Glorot-normal matrix, std ``sqrt(2 / (M + N))``,
  with ``M`` the product of the first d cores' ``m`` and ``N`` of the last
  d's;
- TTM embedding cores ``(..., r, v, h, r')``: the table has std 0.02;
- dense matrices ``(..., out, in)`` (a router, a stack of dense experts):
  Glorot-normal, std ``sqrt(2 / (out + in))``;
- a learned position table: std 0.02;
- leaves with ``bias`` in their name, and norm gains: zeros.

A core's rank axes are told by the chain, not by how many axes it has: the
first core opens with rank 1, the last closes with rank 1, and each core's
closing rank is the next one's opening rank.  Axes in front of a core, and
in front of a dense matrix, are stacks (layers, experts): each stacked
matrix gets the scale a single one would.  A stacked layer leaf (under
``['layers']``) carries the layer count as its leading axis.

A model family's file (``bench/models/<family>.py``) may define
``leaf_std(path, shape)`` for leaves these rules do not cover (an SSM's
``A_log``): it is asked first, and a ``None`` from it leaves the leaf to
the rules above.
"""
from __future__ import annotations

import math
import re

import jax
import jax.numpy as jnp
import numpy as np

from bench import spec

_CORE = re.compile(r"^(?P<owner>.*)\.cores\[(?P<idx>\d+)\]$")
STACKED = "['layers']"


def _links(cores, k: int) -> bool:
    """Whether the shapes read as a chain of ``k``-axis cores ``(r, ..., r')``
    behind the same stack axes, from rank 1 to rank 1."""
    if any(len(s) < k for s in cores):
        return False
    stack = cores[0][:-k]
    return (all(s[:-k] == stack for s in cores)
            and cores[0][-k] == 1 and cores[-1][-1] == 1
            and all(a[-1] == b[-k] for a, b in zip(cores, cores[1:])))


def chains(shapes: dict[str, tuple]) -> dict[str, tuple[str, list]]:
    """Every owner of cores: ``(kind, core shapes without stack axes)``,
    ``kind`` ``"tt"`` for a TT matrix ``(r, m, r')`` or ``"ttm"`` for a TTM
    table ``(r, v, h, r')``."""
    owners: dict[str, dict[int, tuple]] = {}
    for p, s in shapes.items():
        m = _CORE.match(p)
        if m is not None:
            idx = int(m.group("idx"))
            owners.setdefault(m.group("owner"), {})[idx] = tuple(s)
    out = {}
    for owner, by_idx in owners.items():
        cores = [by_idx[i] for i in range(len(by_idx))]
        if len(cores) % 2 == 0 and _links(cores, 3):
            out[owner] = ("tt", [s[-3:] for s in cores])
        elif _links(cores, 4):
            out[owner] = ("ttm", [s[-4:] for s in cores])
        else:
            raise ValueError(f"the cores of {owner} form no TT or TTM "
                             f"chain: {cores}")
    return out


def tt_sides(cores) -> tuple[int, int]:
    """``(out, in)`` of a TT matrix: first half of the cores out, second in."""
    d = len(cores) // 2
    return (math.prod(s[1] for s in cores[:d]),
            math.prod(s[1] for s in cores[d:]))


def matrix_sides(layout) -> dict[str, tuple[int, int]]:
    """``(out, in)`` of every TT matrix of a ``(name, shape, dtype)`` layout,
    by owner: the sides the kernels' padded operands are read against."""
    owned = chains({p: s for p, s, _ in layout})
    return {owner: tt_sides(cores)
            for owner, (kind, cores) in owned.items() if kind == "tt"}


def _core_std(kind: str, cores) -> float:
    """Each core's std, so that the chain's product has the target std."""
    if kind == "ttm":
        target = 0.02
    else:
        out_dim, in_dim = tt_sides(cores)
        target = math.sqrt(2.0 / (out_dim + in_dim))
    contracted = [s[-1] for s in cores[:-1]]
    var = target ** 2 / float(math.prod(contracted))
    return float(var ** (1.0 / (2 * len(cores))))


def leaf_std(path: str, shape: tuple, stacked: bool, chain=None,
             family_std=None) -> float:
    """Standard deviation of one leaf; 0.0 for leaves that start at zero.
    ``chain`` is the leaf's owner's entry of ``chains`` where the leaf is a
    core; ``family_std`` the model family's ``leaf_std``, if it has one."""
    if family_std is not None:
        std = family_std(path, tuple(shape))
        if std is not None:
            return float(std)
    if chain is not None:
        return _core_std(*chain)
    last = re.split(r"\.|\[", path)[-1].strip("]'")
    if "bias" in last or "norm" in last:
        return 0.0
    if "pos_table" in path:
        return 0.02
    own = tuple(shape[1:]) if stacked else tuple(shape)
    if len(own) >= 2:
        return math.sqrt(2.0 / (own[-2] + own[-1]))
    raise ValueError(
        f"no rule for a weight named {path} of shape {tuple(shape)}; the "
        f"model family's file bench/models/<family>.py may define "
        f"leaf_std(path, shape) for it")


def leaf_stds(layout, family: str | None = None) -> list[float]:
    """``leaf_std`` of every leaf of a ``(name, shape, dtype)`` layout."""
    family_std = None
    if family is not None:
        family_std = getattr(spec.load_module("models", family), "leaf_std",
                             None)
    shapes = {p: s for p, s, _ in layout}
    owned = chains(shapes)
    out = []
    for p, s, _ in layout:
        m = _CORE.match(p)
        chain = owned[m.group("owner")] if m is not None else None
        out.append(leaf_std(p, s, p.startswith(STACKED), chain, family_std))
    return out


def describe(struct) -> list[tuple[str, tuple, str]]:
    """``(path, shape, dtype)`` of every leaf of a parameter tree of shapes."""
    out = []
    for path, leaf in jax.tree_util.tree_flatten_with_path(struct)[0]:
        out.append((jax.tree_util.keystr(path), tuple(leaf.shape),
                    str(jnp.dtype(leaf.dtype))))
    return out


def seed_words(seed: int) -> np.ndarray:
    """A seed of any size as two uint32 words (low, high)."""
    seed = int(seed)
    return np.array([seed & 0xFFFFFFFF, (seed >> 32) & 0xFFFFFFFF], np.uint32)


def make_weights(layout: list[tuple[str, tuple, str]], family: str | None = None):
    """A jitted ``seed_words -> [leaf, ...]`` in ``layout`` order."""
    stds = leaf_stds(layout, family)

    def make(words):
        key = jax.random.fold_in(jax.random.PRNGKey(words[0]), words[1])
        leaves = []
        for i, ((_, shape, dtype), std) in enumerate(zip(layout, stds)):
            if std == 0.0:
                leaves.append(jnp.zeros(shape, dtype))
                continue
            x = jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)
            leaves.append((x * std).astype(dtype))
        return leaves

    return jax.jit(make)
