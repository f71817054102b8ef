"""Batched machine state: every lane is one guest, stored SoA on device.

Equivalent of the reference's per-VM register/memory state (`CpuState_t`
loaded into bochs/KVM/WHV at `Initialize`/`Restore`, reference
src/wtf/bochscpu_backend.cc:1026-1122), redesigned for lockstep batch
execution: all architectural state lives in `[lanes, ...]` arrays so one
vmapped transition function advances every guest at once, and `Restore()` is
a functional rebuild from the snapshot broadcast — no per-page rollback loop.

Only the state the interpreter subset actually reads/writes is device
resident (GPRs, rip, rflags, XMM0-15, segment bases, control registers,
syscall MSRs).  The full `CpuState` (x87 stack, debug registers, the other
16 ZMM...) stays host-side in the snapshot and is restored by construction
since the device never mutates it.

Hot-state representation: the fields the transition function touches every
step (GPRs, rip, rflags, the XMM file, fs/gs bases) are stored as explicit
little-endian u32 limb arrays (`*_l` fields, trailing axis = limb) because
the TPU has no native 64-bit integers — XLA would otherwise lower every
u64 op into a u32 pair with carry plumbing the semantics rarely need
(interp/limbs.py).
The u64-named accessors (`machine.gpr`, `.rip`, ...) are free bitcast
views for host mirrors, tests, and cold device paths.
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from wtf_tpu.core.cpustate import CpuState
from wtf_tpu.core.results import StatusCode
from wtf_tpu.interp.limbs import pack_u64, unpack_np
from wtf_tpu.mem import overlay as ovl
from wtf_tpu.mem.overlay import (
    DirtyOverlay, filled, overlay_init, overlay_reset,
)

# Device-side telemetry counter block: one u32 per lane per counter,
# accumulated in-graph (by step_lane unless said otherwise) and folded into
# host metrics once per burst (Runner.fold_counter_totals: no per-step host
# sync).  u32, not u64: the TPU has no native 64-bit ints.  Counters reset
# at each restore and the BASELINE budget is 100M instructions a testcase;
# with limit=0 (unlimited) a testcase retiring over 2^32 instructions wraps
# CTR_INSTR while the u64 icount keeps counting, and CTR_INSTR == icount
# holds mod 2^32.  N_CTRS is the block's width: nothing else states it.
CTR_INSTR = 0        # instructions retired (commit); the oracle fallback
                     # mirrors its host steps in (runner._fallback_step),
                     # so this is icount exactly, fallback paths included
CTR_MEM_FAULT = 1    # translation faults (device page walks and oracle
                     # MemFaults), once per fault event
CTR_DECODE_MISS = 2  # decode-cache misses (NEED_DECODE transitions)
CTR_SWEPT = 3        # lane-steps: every pass of the lane through step_lane,
                     # live or not; the denominator of lane occupancy.
                     # Device passes only: what the oracle retires counts
                     # in CTR_INSTR and not here
CTR_TAIL = 4         # the lane-steps of CTR_SWEPT taken in sweeps of fewer
                     # than all groups of lanes (step.make_run_chunk);
                     # added once a level by the chunk loop
CTR_TLB_WALK = 5     # pages a lane's passes sent to the page walk
                     # (paging.translate_cached: at most K_WALK a pass)
CTR_TLB_FILL = 6     # fill passes: passes of a RUNNING lane that only
                     # filled the translation cache (the instruction needs
                     # more uncached pages at once than a pass walks); in
                     # CTR_SWEPT and not in CTR_INSTR
CTR_STORE = 7        # retired instructions that commit a store to memory,
                     # the oracle's mirrored in as CTR_INSTR's are
CTR_WALK2 = 8        # passes that sent two pages to the walk: each would
                     # be a fill pass at K_WALK = 1
CTR_VECTOR = 9       # retired instructions of the vector datapath
                     # (uops.VECTOR_OPCS), the oracle's mirrored in as
                     # CTR_INSTR's are
CTR_LEVELS = 10      # levels of the chunk loop that swept at least once;
                     # added to every lane's column once a level by the
                     # chunk loop, so a lane carries its chunk's (its
                     # shard's) count
CTR_STORE3 = 11      # passes whose store reached the third word of its
                     # 16-byte window (overlay.StoreSpill): what takes the
                     # sweep's `store-spill` conditional
N_CTRS = 12

IRET_LATCH_SHIFT = 8   # Machine.fault_write: iretq's latch lies above bit 0


class Machine(NamedTuple):
    """All fields carry a leading lane axis, the overlay's two flat planes
    included (lane-major: mem/overlay.py, "resident layout")."""

    # Architectural hot state, as little-endian u32 limbs (limbs.py)
    gpr_l: jax.Array      # uint32[L, 16, 2] (x86 encoding order)
    rip_l: jax.Array      # uint32[L, 2]
    rflags_l: jax.Array   # uint32[L, 2]
    xmm_l: jax.Array      # uint32[L, 16, 8] YMM as 8 u32 limbs: device ops
                          # compute on limbs 0-3 (low XMM); limbs 4-7
                          # (upper YMM) are carried for AVX snapshot
                          # round-trip (reference globals.h:1020-1159)
    fs_base_l: jax.Array  # uint32[L, 2]
    gs_base_l: jax.Array  # uint32[L, 2]
    kernel_gs_base: jax.Array  # uint64[L]
    cr0: jax.Array        # uint64[L]
    cr2: jax.Array        # uint64[L] (set by host exception delivery)
    cr3: jax.Array        # uint64[L]
    cr4: jax.Array        # uint64[L]
    cr8: jax.Array        # uint64[L]
    cs: jax.Array         # uint64[L] CS selector (CPL tracking for delivery)
    ss: jax.Array         # uint64[L] SS selector
    lstar: jax.Array      # uint64[L]
    star: jax.Array       # uint64[L]
    sfmask: jax.Array     # uint64[L]
    efer: jax.Array       # uint64[L]
    tsc: jax.Array        # uint64[L]
    # x87/SSE control state: carried (never computed on device) so the
    # oracle's per-step fallback sees a persistent FPU across steps
    fpst: jax.Array       # uint64[L, 8] f64 bits per physical slot
    fpcw: jax.Array       # uint64[L]
    fpsw: jax.Array       # uint64[L] (incl. TOP bits 11-13)
    fptw: jax.Array       # uint64[L]
    mxcsr: jax.Array      # uint64[L]

    # Run bookkeeping
    status: jax.Array     # int32[L] (core.results.StatusCode)
    icount: jax.Array     # uint64[L] executed instructions this testcase
    rdrand: jax.Array     # uint64[L] deterministic rdrand chain state
    cr3_base: jax.Array   # uint64[L] snapshot cr3 (writes != this stop the lane)
    bp_skip: jax.Array    # int32[L] suppress bp check for one step post-resume
    fault_gva: jax.Array  # uint64[L] faulting address (PAGE_FAULT/SMC detail)
    fault_write: jax.Array  # int32[L] bit 0: the faulting access was a write;
                            # from IRET_LATCH_SHIFT up, iretq's latch between
                            # its two passes (step_lane): 0, or 0x10000 | the
                            # frame's SS once the first has probed all five
                            # qwords (0 in any lane that has faulted)

    # Device-side telemetry (CTR_* indices above); folded into the host
    # metrics registry once per burst, reset on restore
    ctr: jax.Array        # uint32[L, N_CTRS]

    # Coverage (reference: robin_set<Gva_t> per run + edge hash inserts,
    # bochscpu_backend.cc:479-548,699-728 — here: per-lane bitmaps)
    cov: jax.Array        # uint32[L, cap/32] bit per uop-table entry executed
    edge: jax.Array       # uint32[L, EW] splitmix64 edge-hash bitmap

    # Guest memory writes (copy-on-write; reset = Restore)
    overlay: DirtyOverlay  # a LaneOverlay inside a `lane_view`

    @property
    def n_lanes(self) -> int:
        return self.rip_l.shape[0]

    # -- u64 bitcast views of the limb-packed hot state --------------------
    # Free reinterprets (no arithmetic); what host mirrors, tests, and the
    # device step's cold paths read.  Pytree structure is unaffected.
    @property
    def gpr(self) -> jax.Array:        # uint64[L, 16]
        return pack_u64(self.gpr_l)

    @property
    def rip(self) -> jax.Array:        # uint64[L]
        return pack_u64(self.rip_l)

    @property
    def rflags(self) -> jax.Array:     # uint64[L]
        return pack_u64(self.rflags_l)

    @property
    def xmm(self) -> jax.Array:        # uint64[L, 16, 4]
        x = self.xmm_l
        return pack_u64(x.reshape(x.shape[:-1] + (4, 2)))

    @property
    def fs_base(self) -> jax.Array:    # uint64[L]
        return pack_u64(self.fs_base_l)

    @property
    def gs_base(self) -> jax.Array:    # uint64[L]
        return pack_u64(self.gs_base_l)


# vmap in_axes / out_axes of a `lane_view`: every field on the lane axis,
# the overlay's planes unmapped
MACHINE_LANE_AXES = Machine(**{f: 0 for f in Machine._fields})._replace(
    overlay=ovl.LANE_AXES)


def lane_view(machine: Machine) -> Machine:
    """The machine as per-lane code takes it under
    `jax.vmap(..., in_axes=MACHINE_LANE_AXES)`: the overlay as
    `overlay.lanes()` (whole planes + each lane's base)."""
    return machine._replace(overlay=ovl.lanes(machine.overlay))


def _rows(m: Machine) -> Machine:
    """A machine or a `lane_view` without the overlay's two planes: its
    lane-axis leaves alone, as `jax.tree.map` sees them."""
    return m._replace(overlay=m.overlay._replace(data=None, valid=None))


def map_rows(fn, view: Machine, *more: Machine) -> Machine:
    """`fn` over every lane-axis leaf of a `lane_view` (each lane's `base`
    among them) and the same leaf of `more`; the overlay's two planes pass
    through from `view` untouched."""
    out = jax.tree.map(fn, _rows(view), *map(_rows, more))
    return out._replace(overlay=out.overlay._replace(
        data=view.overlay.data, valid=view.overlay.valid))


@jax.named_scope("lane-writeback")
def put_lanes(machine: Machine, lanes, view: Machine) -> Machine:
    """Back from a `lane_view` whose row `k` is lane `lanes[k]` (distinct):
    the rows scattered to their lanes of `machine`, and the planes as the
    view holds them."""
    ov = ovl.resident(view.overlay)
    out = jax.tree.map(
        lambda full, rows: full.at[lanes].set(
            rows, mode="promise_in_bounds", unique_indices=True),
        _rows(machine), _rows(view._replace(overlay=ov)))
    return out._replace(
        overlay=out.overlay._replace(data=ov.data, valid=ov.valid))


def _fpst_f64_bits(v: int) -> int:
    """Snapshot fpst entry -> the f64-bits FPU model: 80-bit extended
    values (real dumps) reduce via the oracle's converter; already-64-bit
    values pass through."""
    if v >> 64:
        from wtf_tpu.cpu.emu import _f80_to_f64_bits

        return _f80_to_f64_bits(v)
    return v & (1 << 64) - 1


def machine_init(
    cpu: CpuState,
    n_lanes: int,
    uop_capacity: int,
    overlay_slots: int = 128,
    edge_bits: int = 17,
    sharding=None,
) -> Machine:
    """Build the batch with every lane at the snapshot state.

    `sharding` places every leaf as it is made: None is the default
    device; a mesh runner passes its lane sharding, and then a leaf
    built on the host goes to the devices part by part and a zeroed one
    is allocated by each device for its own lanes (`overlay.filled`), so
    no leaf is ever whole on one device of several."""
    ones = np.ones(n_lanes, dtype=np.uint64)

    def put(host: np.ndarray) -> jax.Array:
        return jax.device_put(host, sharding)

    def zeros(shape: tuple, dtype) -> jax.Array:
        return filled(shape, 0, dtype, sharding)

    def bcast(value: int) -> jax.Array:
        return put(ones * np.uint64(value & (1 << 64) - 1))

    def bcast_l(value: int) -> jax.Array:
        return put(unpack_np(ones * np.uint64(value & (1 << 64) - 1)))

    gpr = np.tile(np.array(cpu.gpr_list(), dtype=np.uint64), (n_lanes, 1))
    xmm = np.zeros((n_lanes, 16, 4), dtype=np.uint64)
    for i in range(16):
        for limb in range(4):
            xmm[:, i, limb] = np.uint64(cpu.zmm[i][limb])

    return Machine(
        gpr_l=put(unpack_np(gpr)),
        rip_l=bcast_l(cpu.rip),
        rflags_l=bcast_l(cpu.rflags | 0x2),
        xmm_l=put(unpack_np(xmm).reshape(n_lanes, 16, 8)),
        fs_base_l=bcast_l(cpu.fs.base),
        gs_base_l=bcast_l(cpu.gs.base),
        kernel_gs_base=bcast(cpu.kernel_gs_base),
        cr0=bcast(cpu.cr0),
        cr2=bcast(cpu.cr2),
        cr3=bcast(cpu.cr3),
        cr4=bcast(cpu.cr4),
        cr8=bcast(cpu.cr8),
        cs=bcast(cpu.cs.selector),
        ss=bcast(cpu.ss.selector),
        lstar=bcast(cpu.lstar),
        star=bcast(cpu.star),
        sfmask=bcast(cpu.sfmask),
        efer=bcast(cpu.efer),
        tsc=bcast(cpu.tsc),
        fpst=put(np.tile(np.array(
            [_fpst_f64_bits(v) for v in cpu.fpst[:8]],
            dtype=np.uint64), (n_lanes, 1))),
        fpcw=bcast(cpu.fpcw),
        fpsw=bcast(cpu.fpsw),
        fptw=bcast(cpu.fptw),
        mxcsr=bcast(cpu.mxcsr),
        status=filled((n_lanes,), int(StatusCode.RUNNING), jnp.int32,
                      sharding),
        icount=zeros((n_lanes,), jnp.uint64),
        rdrand=zeros((n_lanes,), jnp.uint64),
        cr3_base=bcast(cpu.cr3),
        bp_skip=zeros((n_lanes,), jnp.int32),
        fault_gva=zeros((n_lanes,), jnp.uint64),
        fault_write=zeros((n_lanes,), jnp.int32),
        ctr=zeros((n_lanes, N_CTRS), jnp.uint32),
        cov=zeros((n_lanes, (uop_capacity + 31) // 32), jnp.uint32),
        edge=zeros((n_lanes, (1 << edge_bits) // 32), jnp.uint32),
        overlay=overlay_init(n_lanes, overlay_slots, sharding),
    )


@jax.named_scope("restore")
def machine_restore_impl(machine: Machine,
                         snapshot_template: Machine) -> Machine:
    return snapshot_template._replace(
        # Keep the overlay *storage* from the live machine so no new buffers
        # are allocated; overlay_reset rebuilds just the indexing state.
        overlay=overlay_reset(machine.overlay),
        ctr=jnp.zeros_like(machine.ctr),
        cov=jnp.zeros_like(machine.cov),
        edge=jnp.zeros_like(machine.edge),
    )


# keep_unused: the restored machine reads nothing of the live one but the
# overlay's data plane, and jit would drop the other donated leaves before
# pairing them with outputs: the zeroed planes (overlay.valid, cov, edge)
# would then be NEW buffers beside the old ones instead of memsets in place
_machine_restore_donated = partial(
    jax.jit, donate_argnums=(0,), keep_unused=True)(machine_restore_impl)
_machine_restore_plain = jax.jit(machine_restore_impl)


def machine_restore(machine: Machine, snapshot_template: Machine,
                    donate: bool = None) -> Machine:
    """Restore(): every lane back to the snapshot.  No guest page moves
    (the overlay's validity plane is zeroed: overlay_reset) — replaces
    the reference's dirty-page rewrite loops (SURVEY.md §5.4).

    `snapshot_template` is the pristine machine from machine_init.  Only its
    small per-lane register/bookkeeping arrays are used; the overlay STORAGE
    always comes from the live machine and cov/edge are rebuilt as zeros, so
    build the template with `overlay_slots=0` to avoid holding a second
    multi-GiB overlay buffer alive.

    Donation (donate=True, the off-CPU hot path): `machine` is donated so
    the overlay storage is reset in place (no copy of the
    lanes * slots * 4096-byte slab).  The template is NOT donated — XLA
    copies its leaves into the output, so the result never aliases the
    template and later run_chunk calls may donate the machine freely.
    On the CPU backend donation must stay OFF: XLA CPU's buffer reuse
    for donated inputs corrupts live machine leaves on this graph
    (interp/step.py make_run_chunk documents the failure mode).  The
    default (donate=None) resolves to that policy lazily, exactly like
    make_run_chunk."""
    if donate is None:
        donate = jax.default_backend() != "cpu"
    fn = _machine_restore_donated if donate else _machine_restore_plain
    return fn(machine, snapshot_template)
