"""Weights from the seed, made on the device in one jitted call.

The benchmark makes the weights itself, so that the plain reference can be
given the same numbers without taking anything the program made.  The
leaves are named by their key path in the program's parameter tree
(``"['layers'][0]['attn']['q'].cores[2]"``); the scale of each follows from
its name and shape alone:

- TT cores (3-D, ``(r, m, r')``, 2d of them per matrix): the chain of cores
  reconstructs a Glorot-normal matrix, std ``sqrt(2 / (M + N))``;
- TTM embedding cores (4-D, ``(r, v, h, r')``): the table has std 0.02;
- a learned position table: std 0.02;
- biases and norm gains: zeros.

A stacked layer leaf carries the layer count as a leading axis.
"""
from __future__ import annotations

import math
import re

import jax
import jax.numpy as jnp
import numpy as np

_CORE = re.compile(r"^(?P<owner>.*)\.cores\[(?P<idx>\d+)\]$")


def _core_shape(shape, stacked: bool):
    return tuple(shape[1:]) if stacked else tuple(shape)


def leaf_std(path: str, shapes: dict[str, tuple], stacked: dict[str, bool]) -> float:
    """Standard deviation of one leaf; 0.0 for leaves that start at zero."""
    m = _CORE.match(path)
    if m is None:
        last = re.split(r"\.|\[", path)[-1].strip("]'")
        if last == "bias" or "norm" in last:
            return 0.0
        if "pos_table" in path:
            return 0.02
        raise ValueError(f"no rule for a weight named {path}")
    owner = m.group("owner")
    cores = sorted(((int(_CORE.match(p).group("idx")), p) for p in shapes
                    if _CORE.match(p) and _CORE.match(p).group("owner") == owner))
    core_shapes = [_core_shape(shapes[p], stacked[p]) for _, p in cores]
    n = len(core_shapes)
    contracted = [s[-1] for s in core_shapes[:-1]]
    if len(core_shapes[0]) == 4:          # TTM embedding table
        target = 0.02
    else:                                  # TT matrix: first half out, second in
        d = n // 2
        out_dim = math.prod(s[1] for s in core_shapes[:d])
        in_dim = math.prod(s[1] for s in core_shapes[d:])
        target = math.sqrt(2.0 / (out_dim + in_dim))
    var = target ** 2 / float(math.prod(contracted))
    return float(var ** (1.0 / (2 * n)))


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


def make_weights(layout: list[tuple[str, tuple, str]], stacked_prefix: str = "['layers']"):
    """A jitted ``seed_words -> [leaf, ...]`` in ``layout`` order."""
    shapes = {p: s for p, s, _ in layout}
    stacked = {p: p.startswith(stacked_prefix) for p, _, _ in layout}
    stds = [leaf_std(p, shapes, stacked) for p, _, _ in layout]

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
