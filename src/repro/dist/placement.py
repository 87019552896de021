"""Replica-aware placement: one rules object shared by every replica.

A serving deployment runs N data-parallel *replicas* of the engine, each on
its own slice of the device fleet. The invariants this module enforces:

* every replica gets a mesh of the same shape and axis names, so one
  :class:`~repro.dist.sharding.ShardingRules` object (and therefore one
  compiled executable) is shared across all replicas — a program migrated
  between replicas by the MORI balancer lands on byte-identical layouts;
* replica device groups are disjoint slices of the fleet when enough
  devices exist; only on the CPU (the test path, where N logical
  replicas share one physical device) do they alias the host device(s).

Consumers: ``repro.serving.engine.Engine`` (real JAX engine, one placement
per replica), ``repro.launch.serve`` (builds the set), ``repro.sim``
(replica-count + layout provenance for simulated fleets) and
``examples/quickstart.py``.
"""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np

from repro.dist.sharding import Axes, ShardingRules, make_decode_rules


@dataclass(frozen=True)
class ReplicaPlacement:
    """One replica's slice of the fleet: its mesh + the shared rules."""

    replica_id: int
    mesh: object
    rules: ShardingRules

    def spec(self, axes: Axes, shape=None):
        return self.rules.spec(self.mesh, axes, shape)

    def sharding(self, axes: Axes, shape=None):
        return self.rules.sharding(self.mesh, axes, shape)


class ReplicaSet:
    """All replicas of one deployment; iterable of :class:`ReplicaPlacement`."""

    def __init__(self, meshes: list, rules: ShardingRules):
        assert meshes, "a replica set needs at least one mesh"
        shape0 = dict(meshes[0].shape)
        for m in meshes[1:]:
            assert dict(m.shape) == shape0, "replica meshes must match"
        self.meshes = meshes
        self.rules = rules

    @property
    def num_replicas(self) -> int:
        return len(self.meshes)

    def placement(self, replica_id: int) -> ReplicaPlacement:
        return ReplicaPlacement(replica_id, self.meshes[replica_id], self.rules)

    def __len__(self) -> int:
        return len(self.meshes)

    def __iter__(self):
        return (self.placement(i) for i in range(len(self.meshes)))


def make_replica_set(
    num_replicas: int,
    *,
    mesh_shape: tuple[int, ...] = (1, 1),
    axis_names: tuple[str, ...] = ("data", "model"),
    devices: list | None = None,
    rules: ShardingRules | None = None,
    num_kv_heads: int = 1,
) -> ReplicaSet:
    """Partition the fleet into ``num_replicas`` same-shape meshes.

    With fewer devices than ``num_replicas * prod(mesh_shape)`` every
    replica aliases the first ``prod(mesh_shape)`` host devices on the CPU
    (the test path); on an accelerator that is an error. ``rules``
    defaults to decode rules for ``num_kv_heads`` built against the
    (identical) replica mesh.
    """
    import jax
    from jax.sharding import Mesh

    assert len(mesh_shape) == len(axis_names), (mesh_shape, axis_names)
    devices = list(devices if devices is not None else jax.devices())
    per = int(np.prod(mesh_shape))
    if len(devices) >= num_replicas * per:
        groups = [devices[i * per:(i + 1) * per] for i in range(num_replicas)]
    elif devices[0].platform != "cpu":
        # an accelerator holds one replica's weights and pool; aliasing
        # would silently stack every replica onto the first devices
        raise ValueError(
            f"{num_replicas} replicas of mesh {mesh_shape} need "
            f"{num_replicas * per} {devices[0].platform} devices, "
            f"have {len(devices)}"
        )
    else:
        assert len(devices) >= per, (
            f"need {per} devices for mesh {mesh_shape}, have {len(devices)}"
        )
        groups = [devices[:per]] * num_replicas
    meshes = [
        Mesh(np.asarray(g, dtype=object).reshape(mesh_shape), axis_names)
        for g in groups
    ]
    if rules is None:
        rules = make_decode_rules(meshes[0], num_kv_heads)
    return ReplicaSet(meshes, rules)
