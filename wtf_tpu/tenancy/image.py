"""The stacked image table: many snapshots behind one dispatch operand.

Every tenant's `PhysMem` packs into ONE device image:

  pages        all tenants' present pages concatenated behind the shared
               zero page (slot 0), total row count padded to a power of
               two (the same shape-polymorphism-by-padding policy as
               PhysMem.from_pages);
  frame_table  one pfn->slot row per tenant, padded to a COMMON page
               span (the max of the tenants' spans) — absent/padded pfns
               resolve to slot 0, the shared zero page, preserving the
               reference's zero-fill semantics per tenant;
  tenant       the per-lane row selector (int32[L]) — which base image a
               lane interprets against.

Heterogeneity is thereby pure DATA: the compiled step ladder sees one
pages array, one [T, span] table and one selector vector, so any tenant
mix at a given lane count runs the SAME program bytes (the lint budget
family pins this, analysis/rules.py tenancy rules).

`build_batch_state` also concatenates per-tenant Machine batches (each
lane initialized from its tenant's CpuState — per-lane cr3/rip/MSRs are
already per-lane state, so the heterogeneous machine needs no new
fields) and returns the host-side routing tables the Runner's servicing
loop uses (per-lane PhysMem / CpuState).
"""

from __future__ import annotations

import dataclasses
from typing import List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from wtf_tpu.interp.machine import Machine, machine_init
from wtf_tpu.mem.physmem import MemImage, PAGE_WORDS, _next_pow2

MAX_TENANTS = 1 << 15  # the tag_key tenant field (bits 48..62)


def stack_images(physmems: Sequence) -> MemImage:
    """Pack tenants' PhysMems into one stacked MemImage (tenant=None —
    the caller attaches the per-lane selector)."""
    if not physmems:
        raise ValueError("stack_images needs at least one tenant image")
    if len(physmems) > MAX_TENANTS:
        raise ValueError(f"{len(physmems)} tenants exceed the "
                         f"{MAX_TENANTS} tag-key limit")
    span = max(len(pm.table) for pm in physmems)
    tables = np.zeros((len(physmems), span), dtype=np.int32)
    bodies: List[np.ndarray] = []
    cur = 1  # slot 0 stays the shared zero page
    for t, pm in enumerate(physmems):
        body = pm.rows[1:]          # [slots_t - 1, PW]: drop its zero page
        tbl = pm.table              # [span_t]
        tables[t, :tbl.shape[0]] = np.where(tbl != 0, tbl + (cur - 1), 0)
        bodies.append(body)
        cur += body.shape[0]
    total = _next_pow2(cur)
    stacked = np.zeros((total, PAGE_WORDS), dtype=np.uint64)
    pos = 1
    for body in bodies:
        stacked[pos:pos + body.shape[0]] = body
        pos += body.shape[0]
    return MemImage(pages=jnp.asarray(stacked.view(np.uint32)),
                    frame_table=jnp.asarray(tables))


def _concat_machines(machines: Sequence[Machine]) -> Machine:
    if len(machines) == 1:
        return machines[0]
    return jax.tree.map(lambda *xs: jnp.concatenate(xs, axis=0), *machines)


@dataclasses.dataclass
class BatchState:
    """What a heterogeneous Runner dispatches and routes with."""

    image: MemImage            # stacked, tenant selector populated
    machine: Machine           # per-tenant lane blocks concatenated
    template: Machine          # pristine restore template, same layout
    tenant_of_lane: np.ndarray  # int32[L]
    physmems: List            # per-tenant PhysMem (host reads)
    cpus: List                # per-tenant CpuState (oracle / delivery)


def build_batch_state(tenants: Sequence, n_lanes: int, uop_capacity: int,
                      overlay_slots: int, edge_bits: int) -> BatchState:
    """Build the heterogeneous batch from a tenant table.

    `tenants` is a sequence of objects with `.snapshot` (a loaded
    Snapshot) and `.lanes` (the tenant's lane quota); lane ranges are
    assigned in table order and must tile the batch exactly (the
    scheduler's placement pads quotas to fill)."""
    quotas = [int(t.lanes) for t in tenants]
    if any(q <= 0 for q in quotas):
        raise ValueError(f"tenant lane quotas must be positive: {quotas}")
    if sum(quotas) > n_lanes:
        raise ValueError(
            f"tenant quotas {quotas} sum to {sum(quotas)} but the batch "
            f"has only {n_lanes} lanes")
    physmems = [t.snapshot.physmem for t in tenants]
    cpus = [t.snapshot.cpu for t in tenants]
    image = stack_images(physmems)
    tenant_of_lane = np.repeat(
        np.arange(len(tenants), dtype=np.int32), quotas)
    machines, templates = [], []
    for t, q in zip(tenants, quotas):
        machines.append(machine_init(
            t.snapshot.cpu, q, uop_capacity, overlay_slots, edge_bits))
        templates.append(machine_init(
            t.snapshot.cpu, q, uop_capacity, overlay_slots=0,
            edge_bits=edge_bits))
    pad = n_lanes - sum(quotas)
    if pad:
        # unplaced trailing lanes idle (the backend marks them OK before
        # every run); they carry tenant 0's state so no extra image rows
        tenant_of_lane = np.concatenate(
            [tenant_of_lane, np.zeros(pad, dtype=np.int32)])
        machines.append(machine_init(
            tenants[0].snapshot.cpu, pad, uop_capacity, overlay_slots,
            edge_bits))
        templates.append(machine_init(
            tenants[0].snapshot.cpu, pad, uop_capacity, overlay_slots=0,
            edge_bits=edge_bits))
    return BatchState(
        image=image._replace(tenant=jnp.asarray(tenant_of_lane)),
        machine=_concat_machines(machines),
        template=_concat_machines(templates),
        tenant_of_lane=tenant_of_lane,
        physmems=physmems,
        cpus=cpus,
    )
