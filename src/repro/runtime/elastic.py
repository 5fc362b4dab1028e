"""Elastic scaling: rebuild the mesh after node failures and re-shard state.

At 1000+ node scale, node loss is routine. The recovery protocol here:
  1. the coordinator detects dead hosts (heartbeat timeouts — simulated),
  2. ``surviving_mesh`` folds the device grid down to the largest full
     (data', model) rectangle the survivors can form (dropping data-parallel
     rows keeps every TP group intact, so model shards stay complete),
  3. state is restored from the latest checkpoint with the NEW shardings
     (CheckpointManager.restore re-places host arrays), and the data pipeline
     skips ahead deterministically (TokenPipeline is keyed on (seed, step)).

The dry-run environment has fake devices, so failures are injected by
masking device ids; the logic is identical on real fleets.
"""
from __future__ import annotations

import dataclasses

import jax
import numpy as np
from jax.sharding import AxisType


@dataclasses.dataclass
class FleetState:
    devices: np.ndarray  # current device grid [data, model] (or pod,...)
    alive: np.ndarray  # bool mask over devices.reshape(-1)

    @property
    def n_alive(self) -> int:
        return int(self.alive.sum())


def initial_fleet(mesh) -> FleetState:
    devs = np.asarray(mesh.devices)
    return FleetState(devs, np.ones(devs.size, bool))


def fail_hosts(fleet: FleetState, dead_device_ids) -> FleetState:
    alive = fleet.alive.copy()
    flat = fleet.devices.reshape(-1)
    for i, d in enumerate(flat):
        if d.id in set(dead_device_ids):
            alive[i] = False
    return FleetState(fleet.devices, alive)


def surviving_mesh(fleet: FleetState, axis_names=("data", "model")):
    """Largest full-rectangle mesh from surviving devices: keep every
    data-parallel row whose devices are ALL alive (a dead device kills its
    whole TP row — its model-parallel peers hold unusable shard fractions)."""
    devs = fleet.devices
    if devs.ndim == 3:  # fold pod axis into data for recovery
        devs = devs.reshape(-1, devs.shape[-1])
        axis_names = ("data", "model")
    alive = fleet.alive.reshape(devs.shape)
    rows_ok = alive.all(axis=1)
    kept = devs[rows_ok]
    if kept.shape[0] == 0:
        raise RuntimeError("no complete data-parallel row survived")
    return jax.sharding.Mesh(
        kept, axis_names,
        axis_types=(AxisType.Auto,) * len(axis_names),
    )


def reshard_state(state, old_specs, new_mesh):
    """Re-place a (host or device) pytree onto the shrunk mesh with the same
    PartitionSpecs — batch dims divide the smaller data axis as long as the
    global batch is a multiple of the new data size."""
    return jax.tree.map(
        lambda x, s: jax.device_put(
            np.asarray(jax.device_get(x)),
            jax.sharding.NamedSharding(new_mesh, s),
        ),
        state, old_specs,
    )
