"""Production meshes.

``make_production_mesh`` is the fixed physical topology (one v5e pod =
16 x 16 chips; two pods add the leading ``pod`` axis).  The recipe factorizes
the ``model`` axis into (pp, tp) via ``repro.core.recipe.factorize_production_mesh``.
``make_plan_mesh`` does the same for the devices a host actually has (one
four-chip v5e host as pp=2 x tp=2, say).

Defined as functions (not module constants) so importing this module never
touches jax device state.
"""

from __future__ import annotations

import jax
import numpy as np
from jax.sharding import Mesh

from repro.core.recipe import ParallelismConfig, factorize_production_mesh


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def make_recipe_mesh(*, pp: int = 1, tp: int = 16, multi_pod: bool = False) -> Mesh:
    """Physical production mesh → logical (pod?, data, pp, tp) recipe mesh.

    TP innermost (contiguous ICI ring — the paper's "TP inside the node"),
    PP next, leftover model-axis capacity folds into the data axis."""
    base = make_production_mesh(multi_pod=multi_pod)
    devs = base.devices
    if devs.ndim == 2:
        devs = devs.reshape(1, *devs.shape)
    pods, data, model = devs.shape
    assert model % (pp * tp) == 0, f"model={model} not divisible by pp*tp={pp*tp}"
    fold = model // (pp * tp)
    new = devs.reshape(pods, data * fold, pp, tp)
    return Mesh(new, ("pod", "data", "pp", "tp"))


def make_plan_mesh(plan: ParallelismConfig, devices=None) -> Mesh:
    """The (pod, data, pp, tp) recipe mesh of ``plan`` over ``devices``
    (default: every device JAX sees), TP innermost as on the pod."""
    devs = np.asarray(jax.devices() if devices is None else devices)
    if devs.size != plan.world:
        raise ValueError(f"plan {plan} spans {plan.world} devices, "
                         f"{devs.size} available")
    base = Mesh(devs.reshape(plan.pods, plan.dp, plan.pp * plan.tp),
                ("pod", "data", "model"))
    return factorize_production_mesh(base, plan)


def describe(mesh: Mesh) -> str:
    return f"mesh{dict(mesh.shape)} over {mesh.devices.size} devices"
