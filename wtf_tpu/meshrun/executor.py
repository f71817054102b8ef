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
    boolean all-reduce over the concatenated planes)."""

    def local(tab, image, machine, limit):
        m = body(tab, image, machine, limit)
        loc = jnp.bitwise_or.reduce(
            jnp.concatenate([m.cov, m.edge], axis=1), axis=0)
        merged = bitplane_or(loc, LANE_AXIS)
        wc = m.cov.shape[1]
        return m, merged[:wc], merged[wc:]

    return local


def make_mesh_chunk(n_steps: int, mesh, donate: Optional[bool] = None,
                    jit: bool = True):
    """Build (or fetch) the mesh chunk executor:
    (tab, image, machine, limit) -> (machine', merged_cov, merged_edge)
    with tab/image replicated, machine lane-sharded, merged replicated.

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
        out_specs=(P(LANE_AXIS), P(), P()),
        check_vma=False)
    if not jit:
        return fn
    fn = jax.jit(fn, donate_argnums=(2,) if donate else ())
    _MESH_CHUNK_CACHE[key] = fn
    return fn
