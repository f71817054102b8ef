"""MeshBackend: a whole device mesh behind the one-logical-backend seam.

`FuzzLoop`, `BatchClient`, the campaign/fuzz CLI drivers and every target
module see an ordinary batched backend whose lane count happens to be
`lanes_per_chip x chips` — the reference's process-per-core fan-out
(README.md:34-110) collapsed into one process driving one SPMD program.

Deltas against the plain TpuBackend, all behind existing seams:

  * the runner is a MeshRunner (machine lane-sharded, image/uop table
    replicated, shard_map executors);
  * the batch coverage merge is the shard-aware variant of the SAME
    prefix-credit core (meshrun/reduce.make_mesh_merge) with aggregates
    replicated on every chip — per-batch interconnect bytes are the
    [shards, words] union gather, nothing else;
  * `mesh.devices` / `mesh.lanes_per_shard` gauges join the telemetry
    registry, the merge has a span of its own
    (`execute/cov-readback/mesh-merge`), and the per-shard
    `device.shard_instructions` / `device.shard_lane_steps` counters and
    `mesh.wait_lane_steps` (MeshRunner.fold_device_counters) feed
    tools/telemetry_report.py's mesh section.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from wtf_tpu.backend.tpu import TpuBackend
from wtf_tpu.meshrun.mesh import make_mesh, replicated_sharding
from wtf_tpu.meshrun.reduce import make_mesh_merge
from wtf_tpu.meshrun.runner import MeshRunner


class MeshBackend(TpuBackend):
    """TpuBackend whose batch spans a lane mesh (CLI: --mesh-devices)."""

    def __init__(self, snapshot, n_lanes: int = 64,
                 mesh_devices: Optional[int] = None, **kwargs):
        super().__init__(snapshot, n_lanes=n_lanes, **kwargs)
        # 0 / None = every device jax can see (the CLI's "auto")
        self._mesh_devices = mesh_devices or None
        self.mesh = None

    def initialize(self) -> None:
        self.mesh = make_mesh(self._mesh_devices)
        self.runner = MeshRunner(self.snapshot, self.n_lanes,
                                 mesh=self.mesh, registry=self.registry,
                                 events=self.events,
                                 supervisor=self.supervisor,
                                 **self._runner_kwargs)
        m = self.runner.machine
        rep = replicated_sharding(self.mesh)
        # aggregates live replicated on every chip, so the merge's only
        # cross-shard traffic is the per-shard union gather
        self._agg_cov = jax.device_put(
            jnp.zeros(m.cov.shape[1:], m.cov.dtype), rep)
        self._agg_edge = jax.device_put(
            jnp.zeros(m.edge.shape[1:], m.edge.dtype), rep)
        merge = make_mesh_merge(self.mesh)

        def spanned_merge(*operands):
            # a child of the cov-readback span it runs in, fenced on its
            # outputs: the all-gather and the prefix credit by themselves
            with self.registry.spans.span("mesh-merge") as sp:
                out = merge(*operands)
                sp.fence(out)
            return out

        self._merge = spanned_merge
        self.registry.gauge("mesh.devices").set(self.mesh.size)
        self.registry.gauge("mesh.lanes_per_shard").set(
            self.n_lanes // self.mesh.size)

    def restore_coverage_state(self, cov, edge) -> None:
        """Checkpointed aggregates re-enter REPLICATED (the mesh merge's
        placement contract) — a bare jnp.asarray would leave them on one
        device and force a reshard inside every batch merge."""
        rep = replicated_sharding(self.mesh)
        self._agg_cov = jax.device_put(jnp.asarray(cov), rep)
        self._agg_edge = jax.device_put(jnp.asarray(edge), rep)
        # same prelaunch-drop contract as the base restore: a window
        # dispatched pre-restore must never be adopted post-restore
        self._mega_inflight = None

    def print_run_stats(self) -> None:
        super().print_run_stats()
        print(f"[tpu] mesh: {self.mesh.size} devices x "
              f"{self.n_lanes // self.mesh.size} lanes/shard "
              f"({self.mesh.devices.flat[0].platform})")
