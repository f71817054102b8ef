"""In-graph machine-state integrity: invariant predicates + digest fold.

A corrupted lane (HBM bit flip, a miscompiled executor, scripted chaos)
must be caught BEFORE its batch is harvested — a poisoned status would
crash `StatusCode(int(...))` in result mapping, and poisoned coverage
planes would credit edges that were never executed.  The check is one
jitted function over the live machine pytree (lane-parallel elementwise
work plus two tiny reductions — noise next to a chunk dispatch, and it
pipelines behind the batch's own async dispatch):

  status    in [RUNNING .. HARD_ERROR] — every value StatusCode can map
  rip       canonical: the u32 hi limb's bits 63..47 all-zero or all-one
            (the u64 rip is stored as two u32 limbs; no u64 on device)
  overlay   0 <= count <= capacity AND count == #allocated slots
            (pfn >= 0) — a corrupt count would tear COW restore

The digest is a lane-mixed wraparound-sum fold over the same planes — a cheap
whole-state fingerprint for the poisoned-lane event (two occurrences of
one corruption correlate by digest across the fleet's JSONL streams).

`poison_machine` / `mask_idle` are the write-side helpers: scripted
corruption for the chaos harness, and the tenancy-style idle mask that
parks quarantined lanes (status=OK: never stepped, never harvested).
"""

from __future__ import annotations

from functools import partial
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from wtf_tpu.core.results import StatusCode
from wtf_tpu.interp.machine import CTR_INSTR, Machine

_STATUS_MAX = max(int(s) for s in StatusCode)

# the status value scripted poison writes: far outside StatusCode, and
# recognizable in a debugger dump
POISON_STATUS = 77
POISON_RIP_HI = 0x00DEAD00


@jax.jit
def _check(machine: Machine) -> Tuple[jax.Array, jax.Array]:
    status = machine.status
    ok = (status >= 0) & (status <= _STATUS_MAX)
    # rip canonicality on the hi limb: bits 63..47 of the u64 rip are
    # bits 31..15 of rip_l[:, 1] — all zero (user) or all one (kernel)
    hi = machine.rip_l[:, 1] >> 15
    ok &= (hi == 0) | (hi == jnp.uint32(0x1FFFF))
    ov = machine.overlay
    capacity = ov.pfn.shape[1]
    allocated = jnp.sum((ov.pfn >= 0).astype(jnp.int32), axis=1)
    ok &= (ov.count >= 0) & (ov.count <= capacity) & (allocated == ov.count)
    # lane-mixed fingerprint folded with wraparound add (order-free, and
    # unlike a custom XOR lax.reduce it lowers to the stock add-reduction
    # every backend — including sharded host CPU — implements)
    mix = (machine.rip_l[:, 0]
           ^ (machine.rip_l[:, 1] * jnp.uint32(0x9E3779B9))
           ^ (status.astype(jnp.uint32) * jnp.uint32(0x85EBCA6B))
           ^ (machine.ctr[:, CTR_INSTR] * jnp.uint32(0xC2B2AE35)))
    digest = jnp.sum(mix, dtype=jnp.uint32)
    return ~ok, digest


def check_machine(machine: Machine) -> Tuple[jax.Array, jax.Array]:
    """(bad bool[L], digest u32) for the live machine — async device
    values; the caller fences and reads back."""
    return _check(machine)


@partial(jax.jit, static_argnums=1)
def _poison(machine: Machine, lane: int) -> Machine:
    return machine._replace(
        status=machine.status.at[lane].set(POISON_STATUS),
        rip_l=machine.rip_l.at[lane, 1].set(jnp.uint32(POISON_RIP_HI)))


def poison_machine(machine: Machine, lane: int) -> Machine:
    """Scripted corruption (chaos harness): out-of-range status AND a
    non-canonical rip on one lane — either predicate alone catches it."""
    return _poison(machine, int(lane))


def poison_output(out, lane: int):
    """Apply scripted poison to a dispatch output: the Machine itself, or
    a result carrying one under `.machine` (megachunk window out)."""
    if isinstance(out, Machine):
        return poison_machine(out, lane)
    machine = getattr(out, "machine", None)
    if isinstance(machine, Machine):
        return out._replace(machine=poison_machine(machine, lane))
    return out  # non-machine seam: faultinject slides poison off these


_MASK_CACHE: Dict[Tuple[int, ...], object] = {}


def mask_idle(machine: Machine, mask) -> Machine:
    """Park `mask` lanes idle the way the batch paths already treat
    untasked lanes: status=OK (terminal — never stepped by the chunk
    loop, excluded from harvest by the caller's include mask)."""
    fn = _MASK_CACHE.get(machine.status.shape)
    if fn is None:
        @jax.jit
        def fn(machine, mask):
            return machine._replace(status=jnp.where(
                mask, jnp.int32(int(StatusCode.OK)), machine.status))

        _MASK_CACHE[machine.status.shape] = fn
    return fn(machine, jnp.asarray(mask))
