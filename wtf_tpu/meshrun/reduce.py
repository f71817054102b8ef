"""Coverage reduction: the ONE shard-aware OR-reduce family.

Every coverage union in the tree funnels through here — the parallel
`merged_coverage` helper, the batched backend's aggregate merge, and the
mesh backend's cross-shard merge — so the OR-reduce exists once:

  or_reduce_lanes    grouped shard-local OR + boolean bit-plane reduce;
                     the formulation that partitions cleanly when the
                     lane axis spans devices (XLA has no u32 bitwise-or
                     cross-device reduction, booleans it can all-reduce)
  merge_coverage     the reference master's sequential set-union merge
                     (server.h:816-854): union + per-lane new-coverage
                     credit via an exclusive prefix OR — single-device
  make_mesh_merge    the same semantics over a sharded lane axis:
                     shard-local prefix via the SAME core, one all_gather
                     of the tiny per-shard unions for the cross-shard
                     exclusive prefix (S x words — the only bytes that
                     cross the interconnect per batch merge)

The per-CHUNK merged-bitmap readback lives in meshrun/executor.py (it is
fused into the chunk program so the whole chunk carries exactly one
collective); it shares `bitplane_or` below.
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax import shard_map
from jax.sharding import PartitionSpec as P

from wtf_tpu.meshrun.mesh import LANE_AXIS


def or_reduce_lanes(words, groups: Optional[int] = None):
    """OR-reduce u32 bitmaps over the (possibly sharded) lane axis.

    XLA's cross-device reduction set covers sum/min/max but not u32
    bitwise-or, so a plain `bitwise_or.reduce` over a sharded axis fails
    to partition.  Split the reduction instead: the expensive [L, W] part
    is a shard-local bitwise OR (no collective, no expansion), and only
    the small [g, W, 32] per-bit view crosses devices via `jnp.any`'s
    boolean all-reduce.

    The group count must be a multiple of the lane-mesh size or the
    "local" OR itself crosses shards; callers that hold the mesh pass
    `groups` (merged_coverage's static arg).  The default — the largest
    power-of-two divisor of n_lanes, capped at 256 — stays shard-local
    for any power-of-two mesh up to 256 devices."""
    n = words.shape[0]
    g = groups if groups else min(n & -n, 256)
    grouped = words.reshape(g, n // g, -1)
    local = jnp.bitwise_or.reduce(grouped, axis=1)        # [g, W]
    shifts = jnp.arange(32, dtype=jnp.uint32)
    bits = jnp.any((local[..., None] >> shifts) & jnp.uint32(1) != 0,
                   axis=0)                                # [W, 32]
    return jnp.sum(bits.astype(jnp.uint32) << shifts, axis=-1)


@partial(jax.jit, static_argnames=("groups",))
def merged_coverage(machine, groups: Optional[int] = None):
    """Batch-wide coverage union: OR-reduce the per-lane cov/edge bitmaps
    over the lane axis.  Under a sharded lane axis this lowers to an
    all-reduce over ICI — the device-side replacement for the reference
    master's set-union merge (server.h:816-854).

    Pass `groups` = a multiple of the lane-mesh device count (e.g.
    `mesh.size`) on meshes wider than 256 or with non-power-of-two
    device counts; see `or_reduce_lanes`."""
    return (or_reduce_lanes(machine.cov, groups),
            or_reduce_lanes(machine.edge, groups))


def bitplane_or(words, axis_name: str, mine):
    """Cross-shard bitwise OR of a [W] u32 bitmap via the boolean
    bit-plane all-reduce: expand to the [W, 32] 0/1 plane, pmax across
    the named axis (max of 0/1 == OR), repack.  ONE collective.

    `mine` (a u32 scalar) rides that collective: every shard puts its own
    into its slot of a zeroed row of the plane (a row for every 32
    shards), and the maximum of the slots is every shard's number, by
    shard.  Returns the merged bitmap and the u32 vector of the shards'
    numbers (zeros past the last shard)."""
    shifts = jnp.arange(32, dtype=jnp.uint32)
    bits = (words[:, None] >> shifts) & jnp.uint32(1)     # [W, 32]
    rows = -(-lax.axis_size(axis_name) // 32)
    slots = jnp.arange(rows * 32, dtype=jnp.int32).reshape(rows, 32)
    own = jnp.where(slots == lax.axis_index(axis_name),
                    mine.astype(jnp.uint32), jnp.uint32(0))
    merged = lax.pmax(jnp.concatenate([bits, own]), axis_name)
    return (jnp.sum(merged[:-rows] << shifts, axis=-1, dtype=jnp.uint32),
            merged[-rows:].reshape(-1))


def _prefix_scan(x_in, prev):
    """(inclusive cumulative OR, exclusive-prefix OR seeded with `prev`)
    over the lane axis — the scan both the batch merge (`_merge_core`)
    and the triage attribution (`first_hit_credit`) are built on."""
    cum = lax.associative_scan(jnp.bitwise_or, x_in, axis=0)
    before = jnp.concatenate([prev[None], prev | cum[:-1]], axis=0)
    return cum, before


@jax.named_scope("cov-merge")
def _merge_core(agg_cov, agg_edge, cov_in, edge_in, prev_cov, prev_edge):
    """Prefix-credit merge of one contiguous lane block, given the OR of
    every EARLIER lane (`prev_*` — zeros for lane block 0 / the
    single-device path; the lower shards' union on a mesh).

    Per-lane new-coverage credit follows the reference master's
    *sequential* set-union merge: a lane counts as new only for bits not
    in the aggregate AND not already contributed by any earlier lane.
    Without this, every lane finding the same new edge enters the corpus,
    polluting it with coverage-duplicate testcases and measurably
    diluting guided search.  Returns (block cov union, block edge union,
    new_lane flags for the block)."""
    cum_cov, before_cov = _prefix_scan(cov_in, prev_cov)
    cum_edge, before_edge = _prefix_scan(edge_in, prev_edge)
    new_lane = (
        jnp.any((cov_in & ~agg_cov[None] & ~before_cov) != 0, axis=1)
        | jnp.any((edge_in & ~agg_edge[None] & ~before_edge) != 0, axis=1))
    return cum_cov[-1], cum_edge[-1], new_lane


@jax.jit
def merge_coverage(agg_cov, agg_edge, cov, edge, include):
    """Single-device batch merge: OR lane bitmaps (where `include`) into
    the aggregates; returns (agg_cov', agg_edge', new_lane, new_cov_words).
    The mesh path (make_mesh_merge) runs the same `_merge_core` per shard."""
    inc = include[:, None]
    cov_in = jnp.where(inc, cov, 0)
    edge_in = jnp.where(inc, edge, 0)
    zc = jnp.zeros_like(agg_cov)
    ze = jnp.zeros_like(agg_edge)
    cov_union, edge_union, new_lane = _merge_core(
        agg_cov, agg_edge, cov_in, edge_in, zc, ze)
    new_cov_words = cov_union & ~agg_cov
    return (agg_cov | cov_union, agg_edge | edge_union,
            new_lane & include, new_cov_words)


@jax.jit
def first_hit_credit(agg_cov, agg_edge, cov, edge, include):
    """Exact per-lane coverage attribution under replay order — the
    device half of wtf_tpu/triage's corpus distillation.

    Runs the SAME exclusive-prefix scan as `_merge_core` but keeps the
    whole per-lane credit PLANES instead of collapsing them to flags:
    lane i is credited exactly the cov/edge bits it is FIRST to set —
    not in `agg_*` (earlier batches) and not contributed by any earlier
    lane of this batch.  Excluded lanes (`include` false: timeouts,
    overlay-full) contribute and receive nothing, matching the batch
    merge's revocation rule.

    Returns (credit_cov [L, Wc], credit_edge [L, We], agg_cov', agg_edge')
    — summing each lane's credit popcount over a whole corpus sweep gives
    the exact-attribution ledger, and OR-ing the credits reproduces the
    aggregate delta (the host-recount differential tests/test_triage.py
    pins)."""
    inc = include[:, None]
    cov_in = jnp.where(inc, cov, 0)
    edge_in = jnp.where(inc, edge, 0)
    cum_cov, before_cov = _prefix_scan(cov_in, agg_cov)
    cum_edge, before_edge = _prefix_scan(edge_in, agg_edge)
    credit_cov = cov_in & ~before_cov
    credit_edge = edge_in & ~before_edge
    return (credit_cov, credit_edge,
            agg_cov | cum_cov[-1], agg_edge | cum_edge[-1])


_MESH_MERGE_CACHE: dict = {}


def mesh_merge_local(agg_cov, agg_edge, cov, edge, include,
                     axis_name: str = LANE_AXIS):
    """The per-shard body of the mesh batch merge — module-level so the
    megachunk program (wtf_tpu/fuzz/megachunk.py) can inline the SAME
    merge inside its per-batch loop: shard-local prefix credit via
    `_merge_core`, one all_gather of the tiny per-shard unions for the
    cross-shard exclusive prefix.  Bit-identical to `merge_coverage`
    for any lane order."""
    inc = include[:, None]
    cov_in = jnp.where(inc, cov, 0)
    edge_in = jnp.where(inc, edge, 0)
    wc = cov.shape[1]
    zc = jnp.zeros_like(agg_cov)
    ze = jnp.zeros_like(agg_edge)
    uc, ue, _ = _merge_core(agg_cov, agg_edge, cov_in, edge_in, zc, ze)
    allu = lax.all_gather(jnp.concatenate([uc, ue]), axis_name)
    sidx = lax.axis_index(axis_name)
    nshards = allu.shape[0]
    lower = jnp.where((jnp.arange(nshards) < sidx)[:, None], allu, 0)
    prev = jnp.bitwise_or.reduce(lower, axis=0)
    union = jnp.bitwise_or.reduce(allu, axis=0)
    _, _, new_lane = _merge_core(
        agg_cov, agg_edge, cov_in, edge_in, prev[:wc], prev[wc:])
    new_cov_words = union[:wc] & ~agg_cov
    return (agg_cov | union[:wc], agg_edge | union[wc:],
            new_lane & include, new_cov_words)


def make_mesh_merge(mesh):
    """The batch merge over a lane-sharded machine: per shard, the SAME
    `_merge_core` runs on the local lane block; the cross-shard exclusive
    prefix comes from ONE all_gather of the per-shard unions
    ([shards, cov_w + edge_w] u32 — the only interconnect bytes of the
    merge).  Bit-identical to `merge_coverage` for any lane order (the
    parity the mesh-vs-single-device campaign tests pin).

    Returns a jitted callable (agg_cov, agg_edge, cov, edge, include) ->
    (agg_cov', agg_edge', new_lane, new_cov_words) with agg/new_words
    replicated and new_lane lane-sharded."""
    key = mesh
    cached = _MESH_MERGE_CACHE.get(key)
    if cached is not None:
        return cached
    fn = jax.jit(shard_map(
        mesh_merge_local, mesh=mesh,
        in_specs=(P(), P(), P(LANE_AXIS), P(LANE_AXIS), P(LANE_AXIS)),
        out_specs=(P(), P(), P(LANE_AXIS), P()),
        check_vma=False))
    _MESH_MERGE_CACHE[key] = fn
    return fn
