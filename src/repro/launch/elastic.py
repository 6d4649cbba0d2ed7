"""Elastic scaling: rebuild a smaller/larger mesh and reshard state.

At 1000+ nodes, node loss is routine: the runbook is (1) detect (trainer
watchdog / heartbeat), (2) checkpoint-or-use-latest, (3) rebuild a mesh
from surviving hosts, (4) restore with resharding (the checkpointer
stores global shapes, so any mesh whose axes divide them works),
(5) rescale the data pipeline's host shards.  This module implements the
mesh arithmetic + restore plumbing; tests exercise a full
kill→shrink→resume cycle on the host platform.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Tuple

import jax
from jax.sharding import AxisType, Mesh

from repro.checkpoint.checkpointer import Checkpointer
from repro.sharding.policy import AxisRules, params_pspecs


@dataclasses.dataclass
class ElasticPlan:
    old_shape: Dict[str, int]
    new_shape: Dict[str, int]
    note: str


def plan_rescale(mesh_shape: Dict[str, int], surviving_devices: int,
                 *, keep_model_axis: bool = True) -> ElasticPlan:
    """Choose a new mesh shape for the surviving device count.

    Policy: preserve the "model" axis (TP degree is baked into layouts
    and kernel tile choices); shrink the DP axes ("pod" first, then
    "data") to the largest power-of-two fit.  This keeps per-device
    param shards identical, so restore is a pure re-placement for
    params and only the DP-sharded activations change shape.
    """
    model = mesh_shape.get("model", 1)
    assert surviving_devices >= model, "fewer devices than TP degree"
    dp_budget = surviving_devices // model
    # largest power of two <= dp_budget
    dp = 1
    while dp * 2 <= dp_budget:
        dp *= 2
    new: Dict[str, int] = {}
    if "pod" in mesh_shape and dp >= mesh_shape["data"]:
        new["pod"] = dp // mesh_shape["data"]
        new["data"] = mesh_shape["data"]
    else:
        new["data"] = dp
    new["model"] = model
    return ElasticPlan(dict(mesh_shape), new,
                       note=f"rescale {mesh_shape} -> {new} "
                            f"({surviving_devices} devices survive)")


def build_mesh(shape: Dict[str, int]) -> Mesh:
    axes = tuple(shape.keys())
    dims = tuple(shape.values())
    return jax.make_mesh(dims, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def elastic_restore(ckpt: Checkpointer, tree_like, rules: AxisRules,
                    logical_tree, new_mesh: Mesh,
                    step: Optional[int] = None):
    """Restore the latest checkpoint resharded onto ``new_mesh``."""
    shardings = params_pspecs(logical_tree, rules, new_mesh,
                              shapes_tree=tree_like)
    return ckpt.restore(tree_like, step, shardings)
