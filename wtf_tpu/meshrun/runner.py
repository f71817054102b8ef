"""MeshRunner: the Runner with its lane axis sharded over a device mesh.

One MeshRunner == one snapshot loaded on EVERY chip of the mesh == N
total lanes, `n_lanes / mesh.size` per chip.  The host servicing loop,
decode cache, oracle fallback, breakpoint dispatch and telemetry are the
base Runner's, unchanged — the subclass only re-points the device
dispatch surface:

  * machine + template lane-sharded and BORN so (`machine_init` places
    every leaf as it is made: no array with the whole lane axis is ever
    on one chip), the snapshot image placed from the host rows once,
    replicated (`PhysMem.upload`), the uop table replicated;
  * chunks run through the shard_map executors (meshrun/executor.py):
    shard-local while loops, zero resharding of machine state, and the
    merged cov/edge bitmaps produced on-chip by the chunk's single
    boolean all-reduce — `merged_coverage()` reads them back without
    ever gathering the [lanes, words] planes;
  * every program that indexes a lane's own state outside the chunk
    (the restore, the host's page pushes and row reads, the device
    insert) runs per shard on the planes that shard holds
    (`executor.per_shard`): a plane's indices are local to its part, and
    nothing of the planes crosses the interconnect; the host's register
    mirror goes back lane-sharded from the host;
  * the devmut generator runs per shard under shard_map with the corpus
    slab replicated and the lane-seed stream sharded — the same program
    per lane as single-device, so the byte streams are bit-exact against
    hostref.lane_seeds (pinned by tests/test_meshrun.py);
  * device counters fold per shard (`device.shard_instructions{i}`,
    `device.shard_lane_steps{i}`) on top of the merged `device.*` view,
    and `mesh.wait_lane_steps` counts what the shards waited for each
    other at the chunks' all-reduce.
"""

from __future__ import annotations

from functools import lru_cache, partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import PartitionSpec as P

from wtf_tpu.interp.machine import (
    CTR_INSTR, CTR_SWEPT, machine_restore_impl,
)
from wtf_tpu.interp.runner import (
    Runner, _apply_page_writes, device_insert_impl,
)
from wtf_tpu.mem import overlay as ovl
from wtf_tpu.meshrun.executor import make_mesh_chunk, per_shard
from wtf_tpu.meshrun.mesh import (
    LANE_AXIS, lane_sharding, make_mesh, replicate, replicated_sharding,
    shard_machine,
)

LANES = P(LANE_AXIS)

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
    return per_shard(ovl.read_lane_rows, mesh, (LANES, LANES),
                     (LANES, LANES))


@lru_cache(maxsize=None)
def _mesh_read_row(mesh):
    """`overlay.read_row` per shard: every shard reads the row of ITS lane
    of that index, and the host keeps the part of the lane's shard."""

    def read(overlay, lane, row):
        data, valid = ovl.read_row(overlay, lane, row)
        return data[None], valid[None]

    return per_shard(read, mesh, (LANES, P(), P()), (LANES, LANES))


@lru_cache(maxsize=None)
def _mesh_restore(mesh, donate: bool):
    """`machine_restore` per shard (keep_unused: see interp/machine.py)."""
    return per_shard(machine_restore_impl, mesh, (LANES, LANES), LANES,
                     donate_argnums=(0,) if donate else (),
                     keep_unused=True)


@lru_cache(maxsize=None)
def _mesh_page_writes(mesh, donate: bool):
    """`runner._apply_page_writes` per shard: `Runner.push` deals the
    writes by shard, lanes counted from the shard's first."""
    return per_shard(_apply_page_writes, mesh, (LANES,) * 5, LANES,
                     donate_argnums=(0,) if donate else ())


@lru_cache(maxsize=None)
def _mesh_device_insert(mesh, n_pages: int, n_words: int, len_gpr: int,
                        ptr_gpr: int, donate: bool, masked: bool):
    """`runner.device_insert_impl` per shard: words, lens and the mask
    split with the lanes, the region's frames and address on every chip."""
    del n_words                      # keys the memo, as the plain one's
    return per_shard(
        device_insert_impl(n_pages, len_gpr, ptr_gpr, masked=masked), mesh,
        (LANES, LANES, LANES, P(), P()) + ((LANES,) if masked else ()),
        LANES, donate_argnums=(0,) if donate else ())


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
        if kwargs.get("tenants") is not None:
            # a heterogeneous batch (wtf_tpu/tenancy) is built whole and
            # placed afterwards: pages + frame table replicated, the
            # per-lane tenant selector split with the lanes
            self.machine = shard_machine(self.machine, self.mesh)
            self.template = shard_machine(self.template, self.mesh)
            tenant = self.image.tenant
            self.image = replicate(self.image._replace(tenant=None),
                                   self.mesh)._replace(
                tenant=jax.device_put(tenant, self.lane_sharding()))
        self._tab_src = None
        self._tab_repl = None
        self._slab_src = None
        self._slab_repl = None
        self._merged_cov = None
        self._merged_edge = None
        # the lane-steps every shard took, a vector a chunk, left on the
        # device until the batch's fold reads them all at once
        self._shard_steps: list = []

    @property
    def lanes_per_shard(self) -> int:
        return self.n_lanes // self.mesh.size

    # -- placement -----------------------------------------------------------
    def lane_sharding(self):
        return lane_sharding(self.mesh)

    def image_sharding(self):
        return replicated_sharding(self.mesh)

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
            (machine, self._merged_cov, self._merged_edge,
             shard_steps) = fn(tab, image, machine, limit)
            self._shard_steps.append(shard_steps)
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

    # -- the per-lane programs outside the chunk: each per shard -------------
    def _lane_rows_reader(self):
        return _mesh_read_lane_rows(self.mesh)

    def fetch_overlay_row(self, lane: int, row: int):
        shard, local = divmod(lane, self.lanes_per_shard)
        data, valid = jax.device_get(_mesh_read_row(self.mesh)(
            self.machine.overlay, jnp.int32(local), jnp.int32(row)))
        return data[shard], valid[shard]

    def _page_writer(self):
        return _mesh_page_writes(self.mesh, self._donate)

    def _inserter(self, n_pages: int, n_words: int, len_gpr: int,
                  ptr_gpr: int, masked: bool):
        return _mesh_device_insert(self.mesh, n_pages, n_words, len_gpr,
                                   ptr_gpr, self._donate, masked)

    def _restorer(self):
        return _mesh_restore(self.mesh, self._donate)

    def push(self, view) -> None:
        super().push(view)
        # an oracle burst's coverage bits land through an eager scatter,
        # whose output XLA may place as it likes: back to the canonical
        # sharding (a leaf that lies there already is not touched)
        self.machine = shard_machine(self.machine, self.mesh)

    def restore(self) -> None:
        super().restore()
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
        `device.shard_instructions{<shard>}` and
        `device.shard_lane_steps{<shard>}` — the counters a mesh
        operator reads to spot a cold or straggling chip (shard i owns
        lanes [i*L/S, (i+1)*L/S)) — and `mesh.wait_lane_steps`: chunk by
        chunk, the lane-steps by which each shard fell short of the
        chunk's busiest one, which is what it spent waiting at the
        chunk's all-reduce, priced as the step prices a chip's time."""
        ctr = super().fold_device_counters()
        shards = self.mesh.size
        per = ctr.reshape(shards, self.n_lanes // shards,
                          ctr.shape[1]).sum(axis=1, dtype=np.uint64)
        for name, column in (("device.shard_instructions", CTR_INSTR),
                             ("device.shard_lane_steps", CTR_SWEPT)):
            by_shard = self.registry.counter(name)
            for s in range(shards):
                by_shard.labels(str(s)).inc(int(per[s, column]))
        if self._shard_steps:
            steps = np.stack(jax.device_get(self._shard_steps)
                             )[:, :shards].astype(np.int64)
            self._shard_steps.clear()
            self.registry.counter("mesh.wait_lane_steps").inc(int(
                (steps.max(axis=1, keepdims=True) - steps).sum()))
        return ctr
