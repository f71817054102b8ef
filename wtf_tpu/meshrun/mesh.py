"""Mesh construction and pytree placement over the lane axis.

Design (SURVEY.md §2.7.3): the fuzzer's only parallel axis is *testcases*
(lanes) — the analog of data parallelism.  Machine state is SoA arrays with
a leading lane axis, so sharding is one PartitionSpec over that axis; the
snapshot image and uop table are replicated (every chip interprets against
the same read-only memory image); coverage aggregation is an OR-reduce over
the lane axis whose only cross-chip leg is a small boolean all-reduce
(meshrun/reduce.py).

Multi-host: the same mesh spans processes (jax distributed runtime); the
corpus/crash plane stays host-side and distributes over the reference's TCP
protocol (dist/), which needs no device awareness.
"""

from __future__ import annotations

from typing import Optional

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

LANE_AXIS = "lanes"


def make_mesh(n_devices: Optional[int] = None) -> Mesh:
    """A 1-D lane mesh over the first `n_devices` local devices (None or
    0 = every device jax can see)."""
    devices = jax.devices()
    if n_devices:
        if n_devices > len(devices):
            raise ValueError(
                f"mesh wants {n_devices} devices but jax sees only "
                f"{len(devices)} ({devices[0].platform})")
        devices = devices[:n_devices]
    return Mesh(np.array(devices), (LANE_AXIS,))


def init_multihost(coordinator: Optional[str] = None,
                   num_processes: Optional[int] = None,
                   process_id: Optional[int] = None) -> Mesh:
    """Multi-host campaign entry point: join the jax distributed runtime
    (DCN coordination; args default from the cluster environment) and
    return the global lane mesh over every chip of every host.

    This replaces the reference's process-per-core fan-out INSIDE the
    pod: one mesh, lanes sharded across all chips, coverage OR-reduce
    riding ICI within hosts and DCN across (XLA picks the collectives).
    Across independent pods, the TCP master/node plane (wtf_tpu.dist)
    still applies unchanged — a whole pod is one BatchClient."""
    kwargs = {}
    if coordinator is not None:
        kwargs["coordinator_address"] = coordinator
    if num_processes is not None:
        kwargs["num_processes"] = num_processes
    if process_id is not None:
        kwargs["process_id"] = process_id
    if not jax.distributed.is_initialized():
        jax.distributed.initialize(**kwargs)  # raises on a bad coordinator
    return make_mesh()


def lane_sharding(mesh: Mesh) -> NamedSharding:
    """Leading-axis-split placement for per-lane arrays."""
    return NamedSharding(mesh, P(LANE_AXIS))


def replicated_sharding(mesh: Mesh) -> NamedSharding:
    """Every-device-holds-it placement (image, uop table, aggregates)."""
    return NamedSharding(mesh, P())


def _is_multiprocess(mesh: Mesh) -> bool:
    me = jax.process_index()
    return any(d.process_index != me for d in mesh.devices.flat)


def _place(leaf, sharding, mesh: Mesh):
    """device_put within one process; across processes every host holds
    the same global value (machines broadcast from one snapshot, images
    and uop tables are replicated by construction), so each process
    donates its addressable shards of that value via the callback form."""
    if not _is_multiprocess(mesh):
        return jax.device_put(leaf, sharding)
    arr = np.asarray(leaf)
    return jax.make_array_from_callback(
        arr.shape, sharding, lambda idx: arr[idx])


def shard_machine(machine, mesh: Mesh):
    """Place every per-lane leaf with its leading axis split over the mesh.

    n_lanes must divide by mesh size.  Returns the same pytree with
    device-sharded arrays; everything downstream (run_chunk, coverage
    merge) is shape-identical, so jit compiles SPMD executables with XLA
    inserting the cross-chip collectives.  On a multi-host mesh every
    process must call this with the SAME host value (true for machines
    built from one snapshot) and the array becomes global."""
    sharding = lane_sharding(mesh)
    return jax.tree.map(lambda leaf: _place(leaf, sharding, mesh), machine)


def replicate(tree, mesh: Mesh):
    """Replicate snapshot image / uop table on every mesh device."""
    sharding = replicated_sharding(mesh)
    return jax.tree.map(lambda leaf: _place(leaf, sharding, mesh), tree)
