"""Production mesh construction.

``make_production_mesh`` is a FUNCTION (not a module constant) so importing
this module never touches jax device state — the dry-run must set XLA_FLAGS
*before* the first jax device query, and smoke tests must keep seeing one
CPU device.

Mesh topology (TPU v5e pods):
  single-pod:  (data=16, model=16)           — 256 chips
  multi-pod:   (pod=2, data=16, model=16)    — 512 chips; "pod" is an outer
               DP axis whose gradient all-reduce crosses the inter-pod links
               (DCN/optical); the dry-run proves the partitioner threads it.
The sharding rule engine (runtime.sharding) is axis-name driven, so larger
meshes (more pods, separate "expert"/"seq" axes) need no model-code changes.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType, Mesh

__all__ = ["make_mesh", "make_production_mesh", "make_host_mesh",
           "SINGLE_POD", "MULTI_POD"]

SINGLE_POD = ((16, 16), ("data", "model"))
MULTI_POD = ((2, 16, 16), ("pod", "data", "model"))


def make_mesh(shape, axes) -> Mesh:
    """``jax.make_mesh`` with every axis ``AxisType.Auto``.

    JAX 0.9 builds Explicit axes by default.  The training step relies on
    GSPMD propagation (``meshctx.constrain``'s ``with_sharding_constraint``,
    gathers over data-sharded ids), which Explicit axes reject.  Every mesh
    in the repo is built here.
    """
    axes = tuple(axes)
    return jax.make_mesh(tuple(shape), axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(data: int = 1, model: int = 1, stage: int = 1):
    """Mesh over the first ``stage * data * model`` local devices.

    Zero or negative requests clamp to 1.  A request for more devices than
    exist RAISES: running on fewer devices than asked for would change the
    run without saying so.

    ``stage > 1`` builds the pipeline topology ("stage", "data", "model")
    used by ``launch.steps.make_pipeline_train_step``.
    """
    n = len(jax.devices())
    stage, data, model = (max(int(v), 1) for v in (stage, data, model))
    if stage * data * model > n:
        raise ValueError(
            f"stage={stage} x data={data} x model={model} needs "
            f"{stage * data * model} devices; {n} available")
    if stage > 1:
        return make_mesh((stage, data, model), ("stage", "data", "model"))
    return make_mesh((data, model), ("data", "model"))
