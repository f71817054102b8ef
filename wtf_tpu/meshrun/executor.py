"""shard_map-wrapped device executors: the mesh dispatch units.

Where the single-device runner dispatches `make_run_chunk` (a global
while_loop over the whole batch), the mesh runner dispatches these: the
SAME chunk body runs per shard under `shard_map`, so

  * the while-loop's "any lane still RUNNING" condition is shard-LOCAL —
    shards early-exit independently instead of paying a cross-device
    all-reduce per loop iteration;
  * machine state never crosses the interconnect (every per-lane op is
    shard-local by construction — the lint's `mesh` family pins the
    compiled program to zero gather-class collectives);
  * the chunk program ends with the shard-local u32 OR + [words, 32]
    boolean all-reduce of the cov/edge bitmaps, so the host reads back
    ONE merged bitmap per chunk instead of gathering [lanes, words]
    planes — the only cross-chip traffic of the hot loop.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from wtf_tpu.interp.machine import CTR_SWEPT
from wtf_tpu.interp.step import make_run_chunk
from wtf_tpu.mem.physmem import MemImage
from wtf_tpu.meshrun.mesh import LANE_AXIS
from wtf_tpu.meshrun.reduce import bitplane_or

# pages/frame table replicated on every chip; the per-lane tenant
# selector (wtf_tpu/tenancy) shards with the lane axis.  Prefix specs
# match images with tenant=None too (the empty subtree takes no spec).
IMAGE_SPEC = MemImage(pages=P(), frame_table=P(), tenant=P(LANE_AXIS))

_MESH_CHUNK_CACHE: dict = {}


def _chunk_with_coverage(body):
    """Wrap a machine->machine chunk body so the program also emits the
    cross-shard merged cov/edge bitmaps (shard-local OR, then one
    boolean all-reduce over the concatenated planes) and, riding the same
    all-reduce, the lane-steps every shard took in this chunk: what the
    shards that ended early waited for at the all-reduce is the busiest
    shard's count less their own (`MeshRunner.fold_device_counters`)."""

    def local(tab, image, machine, limit):
        swept = machine.ctr[:, CTR_SWEPT]
        m = body(tab, image, machine, limit)
        with jax.named_scope("cov-allreduce"):
            loc = jnp.bitwise_or.reduce(
                jnp.concatenate([m.cov, m.edge], axis=1), axis=0)
            merged, shard_steps = bitplane_or(
                loc, LANE_AXIS, mine=jnp.sum(m.ctr[:, CTR_SWEPT] - swept))
        wc = m.cov.shape[1]
        return m, merged[:wc], merged[wc:], shard_steps

    return local


def per_shard(fn, mesh, in_specs, out_specs, **jit_kwargs):
    """`fn` compiled to run on every shard's part of its operands, with
    no collective: the programs that index a lane's own state (the
    restore, the host's page pushes and row reads, the device insert)
    take machine leaves split on the lane axis, `P(LANE_AXIS)`, whose
    plane indices are local to the part in hand (`overlay.lanes`)."""
    return jax.jit(shard_map(fn, mesh=mesh, in_specs=in_specs,
                             out_specs=out_specs, check_vma=False),
                   **jit_kwargs)


def make_mesh_chunk(n_steps: int, mesh, donate: Optional[bool] = None,
                    jit: bool = True):
    """Build (or fetch) the mesh chunk executor:
    (tab, image, machine, limit) ->
    (machine', merged_cov, merged_edge, shard_lane_steps)
    with tab/image replicated, machine lane-sharded, the rest replicated.

    Same memoization/donation policy as step.make_run_chunk (donation is
    unsound on the XLA CPU backend — see that docstring).  jit=False
    returns the undecorated shard_map callable, a fresh closure per call
    — the static analyzer's trace probe, exactly like make_run_chunk's."""
    if donate is None:
        donate = jax.default_backend() != "cpu"
    key = (n_steps, mesh, donate)
    if jit:
        cached = _MESH_CHUNK_CACHE.get(key)
        if cached is not None:
            return cached
    body = make_run_chunk(n_steps, donate=donate, jit=False)
    fn = shard_map(
        _chunk_with_coverage(body), mesh=mesh,
        in_specs=(P(), IMAGE_SPEC, P(LANE_AXIS), P()),
        out_specs=(P(LANE_AXIS), P(), P(), P()),
        check_vma=False)
    if not jit:
        return fn
    fn = jax.jit(fn, donate_argnums=(2,) if donate else ())
    _MESH_CHUNK_CACHE[key] = fn
    return fn
