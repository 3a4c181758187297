"""The benchmark's weights: the same scale for every leaf of the cells'
configurations as the harness gave before it read cores by their chain,
and a rule for every leaf of the program's mixture-of-experts models
(expert-stacked TT cores, routers), at scaled-down widths on the CPU."""
import math
import os
import re
import sys
import types

import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from bench_tiny import MOE_ARCHS, moe_config  # noqa: E402

from bench import spec  # noqa: E402
from bench import weights as bw  # noqa: E402
from bench.program import layout  # noqa: E402
from bench.reference import tt_factors  # noqa: E402

# Each leaf's std, by the owner of its cores or by its own name, as the
# harness gave them when it told a TT core from a TTM core by its number of
# axes.
GOLDEN = {
    "atis6-tt": {
        "['embed']": 0.0873580464736299,
        "['final_norm']": 0.0,
        "['layers'][0]['attn']['k']": 0.20412414523193154,
        "['layers'][0]['attn']['k'].bias": 0.0,
        "['layers'][0]['attn']['o']": 0.20412414523193154,
        "['layers'][0]['attn']['q']": 0.20412414523193154,
        "['layers'][0]['attn']['q'].bias": 0.0,
        "['layers'][0]['attn']['v']": 0.20412414523193154,
        "['layers'][0]['attn']['v'].bias": 0.0,
        "['layers'][0]['mlp']['down']": 0.20412414523193154,
        "['layers'][0]['mlp']['up']": 0.20412414523193154,
        "['layers'][0]['norm1']": 0.0,
        "['layers'][0]['norm2']": 0.0,
        "['pos_table']": 0.02,
    },
    "granite8b-tt": {
        "['embed']": 0.06786044041487267,
        "['final_norm']": 0.0,
        "['head']": 0.08694316839918581,
        "['layers'][0]['attn']['k']": 0.11581060905988366,
        "['layers'][0]['attn']['o']": 0.11136233976754242,
        "['layers'][0]['attn']['q']": 0.11136233976754242,
        "['layers'][0]['attn']['v']": 0.11581060905988366,
        "['layers'][0]['mlp']['down']": 0.10408539720695055,
        "['layers'][0]['mlp']['gate']": 0.09824353274891151,
        "['layers'][0]['mlp']['up']": 0.09824353274891151,
        "['layers'][0]['norm1']": 0.0,
        "['layers'][0]['norm2']": 0.0,
    },
}


def _owner(path: str) -> str:
    return re.sub(r"\.cores\[\d+\]$", "", path)


@pytest.mark.parametrize("name", sorted(GOLDEN))
def test_cells_keep_their_stds(name):
    config = spec.load_config(name)
    lay = layout(config)
    stds = bw.leaf_stds(lay, config["family"])
    got = {}
    for (p, _, _), s in zip(lay, stds):
        assert got.setdefault(_owner(p), s) == s, p
    assert got == GOLDEN[name]


@pytest.fixture(scope="module", params=MOE_ARCHS)
def moe(request):
    config = moe_config(request.param)
    lay = layout(config)
    make = bw.make_weights(lay, config["family"])
    return {"config": config, "layout": lay, "make": make,
            "stds": dict(zip([p for p, _, _ in lay], bw.leaf_stds(lay))),
            "weights": _named(lay, make(bw.seed_words(2**31 + 17)))}


def _named(lay, leaves) -> dict:
    return {p: a for (p, _, _), a in zip(lay, leaves)}


def test_moe_leaves_each_get_a_rule(moe):
    shapes = {p: s for p, s, _ in moe["layout"]}
    assert any(p.endswith("['router']") for p in shapes)
    for p, s in moe["stds"].items():
        assert math.isfinite(s) and s >= 0.0, p
        assert (s > 0.0) == ("norm" not in p), p
        assert moe["weights"][p].shape == shapes[p]


def _expert_owners(moe):
    return sorted({_owner(p) for p in moe["stds"]
                   if ".cores[" in p and "['moe']" in p and "shared" not in p})


def test_expert_matrices_reconstruct_glorot(moe):
    """Each expert's chain of cores makes one matrix, whose mean square is
    Glorot's ``2 / (out + in)`` in expectation.  One expert's own std swings
    by tens of percent from draw to draw (its matrix is a product of a few
    small random cores), so the mean square is taken over 128 experts or
    more: every expert of every stacked layer, over as many seeds as that
    takes."""
    owners = _expert_owners(moe)
    squares = {o: [] for o in owners}
    seed = 2**31 + 17
    while min(len(v) for v in squares.values()) < 128:
        w = _named(moe["layout"], moe["make"](bw.seed_words(seed)))
        seed += 1
        for owner in owners:
            cores = [w[f"{owner}.cores[{i}]"] for i in range(6)]
            for idx in np.ndindex(*cores[0].shape[:-3]):
                a, b = tt_factors([c[idx] for c in cores], lambda x: x)
                squares[owner].append(float(jnp.mean((a @ b) ** 2)))
    for owner in owners:
        out_dim, in_dim = bw.matrix_sides(moe["layout"])[owner]
        glorot = math.sqrt(2.0 / (out_dim + in_dim))
        rms = math.sqrt(np.mean(squares[owner]))
        assert rms == pytest.approx(glorot, rel=0.1), owner


def test_expert_cores_scale_as_a_single_matrix(moe):
    """An expert's cores get the std the shared expert's cores of the same
    widths get: the expert axis is a stack, not a TTM core's fourth axis."""
    stds, sides = moe["stds"], bw.matrix_sides(moe["layout"])
    for owner in _expert_owners(moe):
        twin = owner.replace("['moe']", "['moe']['shared']")
        assert sides[twin] == sides[owner]
        assert stds[owner + ".cores[0]"] == stds[twin + ".cores[0]"] > 0.0


def test_router_is_glorot(moe):
    routers = [p for p in moe["stds"] if p.endswith("['router']")]
    assert routers
    for p in routers:
        shape = moe["weights"][p].shape
        glorot = math.sqrt(2.0 / (shape[-2] + shape[-1]))
        assert moe["stds"][p] == pytest.approx(glorot, rel=1e-12)
        assert float(jnp.std(moe["weights"][p])) == pytest.approx(glorot, rel=0.05)


def test_bias_leaves_start_at_zero():
    lay = [("['layers'][0]['moe']['e_score_correction_bias']", (2, 64), "float32"),
           ("['layers'][0]['attn']['q'].bias", (2, 256), "float32")]
    assert bw.leaf_stds(lay) == [0.0, 0.0]


def test_dense_matrices_are_glorot_behind_their_stacks():
    lay = [("['layers'][0]['moe']['up']['w']", (2, 8, 128, 256), "float32"),
           ("['router']", (8, 256), "float32")]
    assert bw.leaf_stds(lay) == [math.sqrt(2 / 384), math.sqrt(2 / 264)]


def test_a_family_rule_is_asked_first(monkeypatch):
    lay = [("['layers'][0]['ssm']['A_log']", (2, 16), "float32"),
           ("['layers'][0]['ssm']['dt_bias']", (2, 16), "float32"),
           ("['layers'][0]['norm1']", (2, 256), "float32")]
    with pytest.raises(ValueError, match=r"leaf_std\(path, shape\)"):
        bw.leaf_stds(lay)

    def leaf_std(path, shape):
        assert shape == (2, 16) or "norm" in path
        return {"A_log": 0.5, "dt_bias": 0.1}.get(path.split("'")[-2])

    family = types.SimpleNamespace(leaf_std=leaf_std)
    monkeypatch.setattr(spec, "load_module", lambda kind, name: family)
    assert bw.leaf_stds(lay, "ssm_family") == [0.5, 0.1, 0.0]


@pytest.mark.parametrize("shapes,kind", [
    # a TT matrix behind (layers, experts) stack axes
    ([(2, 8, 1, 4, 3), (2, 8, 3, 4, 5), (2, 8, 5, 2, 1), (2, 8, 1, 2, 1)], "tt"),
    # the embedding's TTM cores (r, v, h, r')
    ([(1, 16, 12, 30), (30, 8, 8, 30), (30, 8, 8, 1)], "ttm"),
])
def test_cores_are_read_by_their_chain(shapes, kind):
    named = {f"['w'].cores[{i}]": s for i, s in enumerate(shapes)}
    got_kind, cores = bw.chains(named)["['w']"]
    assert got_kind == kind
    assert cores == [s[-3:] if kind == "tt" else s[-4:] for s in shapes]


def test_a_broken_chain_is_refused():
    named = {"['w'].cores[0]": (1, 4, 3), "['w'].cores[1]": (2, 4, 1)}
    with pytest.raises(ValueError, match="no TT or TTM chain"):
        bw.chains(named)
