"""A configuration file's nested sections: ``build_cfg`` replaces a
nested section (``moe``) field by field and checks it field by field, as
it checks ``tt``, naming each wrong field (``moe.top_k``)."""
import copy
import dataclasses
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from bench_tiny import MOE_ARCHS, moe_config  # noqa: E402

from bench.program import build_cfg, differences  # noqa: E402


@pytest.mark.parametrize("arch", MOE_ARCHS)
def test_a_right_nested_section_is_taken(arch):
    config = moe_config(arch)
    cfg = build_cfg(config)
    want = config["model"]["moe"]
    assert {k: getattr(cfg.moe, k) for k in want} == want


@pytest.mark.parametrize("field,value", [("top_k", 3), ("d_expert", 96)])
def test_a_wrong_nested_field_is_named(field, value):
    config = copy.deepcopy(moe_config(MOE_ARCHS[0]))
    config["model"]["moe"][field] = value
    with pytest.raises(ValueError, match=rf"'moe\.{field}': \(\d+, {value}\)"):
        build_cfg(config)


def test_replace_keeps_the_fields_a_section_does_not_name():
    config = copy.deepcopy(moe_config(MOE_ARCHS[0]))
    config["replace"]["moe"] = {"num_experts": 8}
    config["model"]["moe"] = {"num_experts": 8}
    cfg = build_cfg(config)
    from repro.configs import get_config

    published = get_config(MOE_ARCHS[0]).moe
    assert cfg.moe.num_experts == 8
    assert (cfg.moe.top_k, cfg.moe.d_expert) == (published.top_k, published.d_expert)


@dataclasses.dataclass(frozen=True)
class _Inner:
    a: int = 1
    b: tuple = (1, 2)


@dataclasses.dataclass(frozen=True)
class _Outer:
    x: int = 0
    inner: _Inner = _Inner()
    none: _Inner | None = None


def test_differences_walk_nested_sections():
    assert differences(_Outer(), {"x": 0, "inner": {"a": 1, "b": [1, 2]}}) == {}
    assert differences(_Outer(), {"x": 1, "inner": {"a": 2, "b": [1, 2]}}) == {
        "x": (0, 1), "inner.a": (1, 2)}
    assert differences(_Outer(), {"none": {"a": 1}}) == {"none": (None, {"a": 1})}
    assert differences(_Inner(), {"a": 1}, "tt.") == {}
    assert differences(_Inner(), {"a": 3}, "tt.") == {"tt.a": (1, 3)}
