"""MeshRunner: the Runner with its lane axis sharded over a device mesh.

One MeshRunner == one snapshot loaded on EVERY chip of the mesh == N
total lanes, `n_lanes / mesh.size` per chip.  The host servicing loop,
decode cache, oracle fallback, breakpoint dispatch and telemetry are the
base Runner's, unchanged — the subclass only re-points the device
dispatch surface:

  * machine + template lane-sharded, snapshot image + uop table
    replicated (meshrun/mesh.py placement);
  * chunks run through the shard_map executors (meshrun/executor.py):
    shard-local while loops, zero resharding of machine state, and the
    merged cov/edge bitmaps produced on-chip by the chunk's single
    boolean all-reduce — `merged_coverage()` reads them back without
    ever gathering the [lanes, words] planes;
  * host pushes (servicing writes) re-place the updated leaves with the
    lane sharding so the next dispatch never pays an implicit reshard;
  * the devmut generator runs per shard under shard_map with the corpus
    slab replicated and the lane-seed stream sharded — the same program
    per lane as single-device, so the byte streams are bit-exact against
    hostref.lane_seeds (pinned by tests/test_meshrun.py);
  * device counters fold per shard (`device.shard_instructions{i}`)
    on top of the merged `device.*` view.
"""

from __future__ import annotations

from functools import lru_cache, partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import PartitionSpec as P

from wtf_tpu.interp.machine import CTR_INSTR
from wtf_tpu.interp.runner import Runner
from wtf_tpu.mem import overlay as ovl
from wtf_tpu.meshrun.executor import make_mesh_chunk
from wtf_tpu.meshrun.mesh import (
    LANE_AXIS, lane_sharding, make_mesh, replicate, replicated_sharding,
    shard_machine,
)

_MESH_GEN_CACHE: dict = {}


def _mesh_generate(rounds: int, mesh):
    """The devmut batch generator per shard: slab replicated, seeds
    lane-sharded, output words/lens lane-sharded.  Same per-lane program
    as engine.make_generate, so the stream is bit-exact."""
    key = (rounds, mesh)
    cached = _MESH_GEN_CACHE.get(key)
    if cached is not None:
        return cached
    from wtf_tpu.devmut.engine import generate

    fn = jax.jit(shard_map(
        partial(generate, rounds=rounds), mesh=mesh,
        in_specs=(P(), P(), P(), P(LANE_AXIS)),
        out_specs=(P(LANE_AXIS), P(LANE_AXIS)),
        check_vma=False))
    _MESH_GEN_CACHE[key] = fn
    return fn


@lru_cache(maxsize=None)
def _mesh_read_lane_rows(mesh):
    """`overlay.read_lane_rows` per shard: a lane reads its own rows from
    its shard's part of the planes, so nothing crosses the interconnect
    and no plane is gathered."""
    return jax.jit(shard_map(
        ovl.read_lane_rows, mesh=mesh,
        in_specs=(P(LANE_AXIS), P(LANE_AXIS)),
        out_specs=(P(LANE_AXIS), P(LANE_AXIS)), check_vma=False))


class MeshRunner(Runner):
    """Runner whose batch spans a `jax.sharding.Mesh` over the lane axis."""

    def __init__(self, snapshot, n_lanes: int, mesh=None,
                 mesh_devices: Optional[int] = None, **kwargs):
        self.mesh = mesh if mesh is not None else make_mesh(mesh_devices)
        if n_lanes % self.mesh.size:
            raise ValueError(
                f"n_lanes={n_lanes} does not divide over the "
                f"{self.mesh.size}-device mesh — the lane axis shards "
                f"evenly (lanes_per_chip x chips)")
        super().__init__(snapshot, n_lanes, **kwargs)
        # distinguishes mesh executors in the process-global compile-event
        # dedup (same chunk size, different program)
        self.exec_sig = ("mesh", self.mesh.size)
        self.machine = shard_machine(self.machine, self.mesh)
        self.template = shard_machine(self.template, self.mesh)
        # pages + frame table replicated; the per-lane tenant selector
        # (wtf_tpu/tenancy heterogeneous batches) shards with the lanes
        tenant = self.image.tenant
        self.image = replicate(self.image._replace(tenant=None), self.mesh)
        if tenant is not None:
            self.image = self.image._replace(
                tenant=jax.device_put(tenant, lane_sharding(self.mesh)))
        self._tab_src = None
        self._tab_repl = None
        self._slab_src = None
        self._slab_repl = None
        self._merged_cov = None
        self._merged_edge = None

    @property
    def lanes_per_shard(self) -> int:
        return self.n_lanes // self.mesh.size

    # -- dispatch surface (the only seams the base Runner goes through) ----
    def device_tab(self):
        tab = self.cache.device()
        if tab is not self._tab_src:  # cache.device() memoizes when clean
            self._tab_src = tab
            self._tab_repl = replicate(tab, self.mesh)
        return self._tab_repl

    def _chunk_callable(self, n_steps: int):
        fn = make_mesh_chunk(n_steps, self.mesh, donate=self._donate)

        def dispatch(tab, image, machine, limit):
            machine, self._merged_cov, self._merged_edge = fn(
                tab, image, machine, limit)
            return machine

        return dispatch

    def megachunk_callable(self, max_batches: int, n_pages: int,
                           len_gpr: int, ptr_gpr: int, rounds: int):
        """The megachunk window per shard (fuzz/megachunk.py mesh
        variant): slabs/seeds arrive pre-placed through the driver's
        megachunk_operands; outputs keep canonical shardings."""
        from wtf_tpu.fuzz.megachunk import make_mesh_megachunk

        return make_mesh_megachunk(max_batches, n_pages, len_gpr,
                                   ptr_gpr, rounds,
                                   deliver=self.deliver_exceptions,
                                   mesh=self.mesh,
                                   devdec=self.device_decode,
                                   donate=self._donate)

    def megachunk_place(self, slab_first, slab_rest, seeds):
        """Place one window's operands: slabs replicated (version-
        tracked like devmut_generate's), the seed stream lane-sharded."""
        rep = replicated_sharding(self.mesh)
        if slab_rest[0] is not self._slab_src:
            self._slab_src = slab_rest[0]
            self._slab_repl = tuple(
                jax.device_put(a, rep) for a in slab_rest)
        rest = self._slab_repl
        first = (rest if slab_first[0] is slab_rest[0]
                 else tuple(jax.device_put(a, rep) for a in slab_first))
        seeds = jax.device_put(jnp.asarray(seeds),
                               jax.sharding.NamedSharding(
                                   self.mesh, P(None, LANE_AXIS)))
        return first, rest, seeds

    def _lane_rows_reader(self):
        return _mesh_read_lane_rows(self.mesh)

    # -- host write seams: keep the canonical sharding -----------------------
    def push(self, view) -> None:
        super().push(view)
        # servicing replaced register leaves with host-built arrays;
        # re-place them so the next dispatch sees the canonical lane
        # sharding instead of paying an implicit reshard per chunk
        self.machine = shard_machine(self.machine, self.mesh)

    def device_insert(self, *args, **kwargs) -> None:
        super().device_insert(*args, **kwargs)
        self.machine = shard_machine(self.machine, self.mesh)

    def restore(self) -> None:
        super().restore()
        self.machine = shard_machine(self.machine, self.mesh)
        self._merged_cov = None
        self._merged_edge = None

    # -- on-chip merged coverage ---------------------------------------------
    def merged_coverage(self) -> Optional[Tuple[np.ndarray, np.ndarray]]:
        """(cov, edge) bitmaps OR-merged across ALL lanes of ALL shards,
        as of the last dispatched chunk — produced in-graph by the chunk's
        boolean all-reduce, so reading them costs two [words] transfers,
        never a [lanes, words] gather.  None before the first chunk of a
        run (or right after restore)."""
        if self._merged_cov is None:
            return None
        return (np.asarray(jax.device_get(self._merged_cov)),
                np.asarray(jax.device_get(self._merged_edge)))

    # -- devmut seam ---------------------------------------------------------
    def devmut_generate(self, rounds, data, lens, cumw, seeds):
        # replicate the corpus slab once per slab VERSION, not per batch
        # (DeviceCorpus.arrays memoizes between dirty uploads, so object
        # identity tracks the version — same scheme as device_tab): the
        # point of the device engine is that the stream stays resident,
        # not re-broadcast [slots, words] to every chip each batch
        if data is not self._slab_src:
            rep = replicated_sharding(self.mesh)
            self._slab_src = data
            self._slab_repl = (jax.device_put(data, rep),
                               jax.device_put(lens, rep),
                               jax.device_put(cumw, rep))
        data_r, lens_r, cumw_r = self._slab_repl
        return _mesh_generate(rounds, self.mesh)(
            data_r, lens_r, cumw_r,
            jax.device_put(jnp.asarray(seeds), lane_sharding(self.mesh)))

    # -- telemetry -----------------------------------------------------------
    def fold_device_counters(self) -> np.ndarray:
        """Merged `device.*` fold (base class) plus the per-shard view:
        `device.shard_instructions{<shard>}` — the counters a mesh
        operator reads to spot a cold or straggling chip.  Shard i owns
        lanes [i*L/S, (i+1)*L/S)."""
        ctr = super().fold_device_counters()
        shards = self.mesh.size
        per = ctr.reshape(shards, self.n_lanes // shards,
                          ctr.shape[1]).sum(axis=1, dtype=np.uint64)
        by_shard = self.registry.counter("device.shard_instructions")
        for s in range(shards):
            by_shard.labels(str(s)).inc(int(per[s, CTR_INSTR]))
        return ctr
