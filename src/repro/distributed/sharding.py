"""Sharding plans: how each model family maps onto the production mesh.

Meshes (launch/mesh.py): single-pod (16,16) ("data","model"); multi-pod
(2,16,16) ("pod","data","model"). A ``ShardingPlan`` carries the axis names
so model code is mesh-shape-agnostic: batch shards over (pod+data), model
parallelism over "model".

Conventions (all families):
  * every 2-D+ parameter is sharded over BOTH model and data axes
    (megatron TP over `model`, FSDP over `data` for the non-TP dim) —
    optimizer state inherits the same spec, so per-chip bytes scale 1/chips;
  * activations: batch over (pod,data); LM residual stream additionally
    sequence-sharded over `model` (sequence parallelism);
  * embedding/vocab tables row-sharded over `model`.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


@dataclasses.dataclass(frozen=True)
class ShardingPlan:
    mesh: Optional[Mesh]
    batch_axes: Tuple[str, ...] = ("data",)     # ("pod","data") multi-pod
    model_axis: Optional[str] = "model"
    fsdp_axis: object = "data"                  # str or tuple — param FSDP axes

    @property
    def enabled(self) -> bool:
        return self.mesh is not None

    def spec(self, *entries) -> P:
        return P(*entries)

    def named(self, *entries) -> Optional[NamedSharding]:
        if not self.enabled:
            return None
        return NamedSharding(self.mesh, P(*entries))

    def constrain(self, x, *entries):
        """with_sharding_constraint if a mesh is active, else identity."""
        if not self.enabled:
            return x
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(self.mesh, P(*entries)))

    # --- common specs ---------------------------------------------------------
    def batch_spec(self, extra_dims: int = 1) -> P:
        return P(self.batch_axes, *([None] * extra_dims))

    def replicated(self) -> P:
        return P()


def replicated_plan() -> ShardingPlan:
    """CPU/test plan: no mesh, all constraints are no-ops."""
    return ShardingPlan(mesh=None)


def plan_for_mesh(mesh: Mesh) -> ShardingPlan:
    axes = mesh.axis_names
    if "pod" in axes:
        return ShardingPlan(mesh=mesh, batch_axes=("pod", "data"),
                            model_axis="model", fsdp_axis=("pod", "data"))
    return ShardingPlan(mesh=mesh, batch_axes=("data",),
                        model_axis="model", fsdp_axis="data")
