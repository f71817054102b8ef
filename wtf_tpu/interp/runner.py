"""Host orchestration of the batched device interpreter.

The reference's run loop alternates guest execution with host servicing
(vmexits: kvm_backend.cc:1371-1566; emulator hooks: bochscpu_backend.cc:
352-548).  Here the device runs *chunks* of vmapped steps (interp/step.py)
and the host services whatever each lane reported in its status word:

  NEED_DECODE  - decode bytes at the lane's rip once, publish to the shared
                 uop table, resume (the JIT-translation-cache fill path)
  SMC          - lane's code bytes diverged from the cache: re-decode and
                 update the entry in place
  UNSUPPORTED  - single-step the lane on the host EmuCpu oracle (precise
                 slow path; mirrors the bochscpu-backs-KVM methodology)
  BREAKPOINT   - dispatch to the backend's registered handler
  terminal     - OK/CRASH/TIMEDOUT/... mapped to results by the backend

Host<->device traffic is batched: one pull of the small per-lane register
arrays per service round (`HostView`), page-granular reads on demand, and
all memory writes buffered host-side and applied in a single jitted scan
(`_apply_page_writes`) before the next chunk.
"""

from __future__ import annotations

import contextlib
import logging
from functools import lru_cache, partial
from typing import Callable, Dict, List, Optional, Set, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from wtf_tpu.core.cpustate import CpuState
from wtf_tpu.core.gxa import PAGE_SHIFT, PAGE_SIZE
from wtf_tpu.core.results import StatusCode
from wtf_tpu.cpu.decoder import decode
from wtf_tpu.cpu import uops as U
from wtf_tpu.cpu.emu import (
    DivideError, EmuCpu, GuestCrash, MemFault, UnsupportedInsn,
)
from wtf_tpu.cpu.interrupts import (
    VEC_DE, VEC_GP, VEC_PF, DeliveryFailed, deliver_exception,
    deliver_page_fault, is_canonical,
)
from wtf_tpu.interp import limbs
from wtf_tpu.interp.machine import (
    CTR_DECODE_MISS, CTR_INSTR, CTR_LEVELS, CTR_MEM_FAULT, CTR_STORE,
    CTR_STORE3, CTR_SWEPT, CTR_TAIL, CTR_TLB_FILL, CTR_TLB_WALK, CTR_VECTOR,
    CTR_WALK2,
    Machine,
    machine_init, machine_restore,
)
from wtf_tpu.interp.step import fp_on_host, make_run_chunk
from wtf_tpu.interp.uoptable import DecodeCache
from wtf_tpu.mem import overlay as ovl
from wtf_tpu.snapshot.loader import Snapshot
from wtf_tpu.supervise import Supervisor
from wtf_tpu.telemetry import NULL, Registry, StatsDict

MASK64 = (1 << 64) - 1

_log = logging.getLogger(__name__)

# Executor shapes (chunk_steps, donate, n_lanes, operand shapes) dispatched
# at least once in this process — mirrors the process-global jit cache, so
# `compile` telemetry events fire exactly when XLA actually compiles
_DISPATCHED_EXECUTORS: Set[Tuple] = set()

# opc int -> lowercase class name ("alu", "ssefp", ...) for fallback stats
_OPC_NAMES = {
    value: name[len("OPC_"):].lower()
    for name, value in vars(U).items() if name.startswith("OPC_")
}

# statuses the host delivers back into the guest through its IDT
_FAULT_STATUSES = (int(StatusCode.PAGE_FAULT), int(StatusCode.DIVIDE_ERROR))
_VECTOR_NAMES = {VEC_DE: "#DE", VEC_GP: "#GP", VEC_PF: "#PF"}

PTE_P = 1
PTE_W = 1 << 1
PTE_PS = 1 << 7
PHYS_MASK = 0x000F_FFFF_FFFF_F000

# Machine leaves mirrored into HostView (everything except overlay/cov/edge).
# The limb-packed hot fields (machine.py) are exposed to ALL host code as
# u64 views under their architectural names — HostView packs on pull and
# unpacks on push, so the seam lives in exactly two places.
_MIRROR_FIELDS = (
    "gpr", "rip", "rflags", "xmm", "fs_base", "gs_base", "kernel_gs_base",
    "cr0", "cr2", "cr3", "cr4", "cr8", "cs", "ss",
    "lstar", "star", "sfmask", "efer", "tsc",
    "fpst", "fpcw", "fpsw", "fptw", "mxcsr",
    "status", "icount", "rdrand", "bp_skip", "fault_gva", "fault_write",
    "ctr",
)

# host mirror name -> u32-limb Machine field
_LIMB_FIELDS = {
    "gpr": "gpr_l", "rip": "rip_l", "rflags": "rflags_l", "xmm": "xmm_l",
    "fs_base": "fs_base_l", "gs_base": "gs_base_l",
}


def _pack_mirror(name: str, arr: np.ndarray) -> np.ndarray:
    """Device limb array -> host u64 mirror (xmm pairs its 8 limbs to 4)."""
    if name == "xmm":
        arr = arr.reshape(arr.shape[:-1] + (4, 2))
    return np.array(limbs.pack_np(arr))


def _unpack_mirror(name: str, arr: np.ndarray) -> np.ndarray:
    """Host u64 mirror -> device limb array.

    Returns a fresh copy, never a view: the result is uploaded into the
    machine, whose buffers are DONATED to the next chunk — on the CPU
    backend jnp.asarray can zero-copy alias host numpy memory, and a
    donated alias of a still-mutable HostView array is silent corruption
    (observed as garbage status/fpsw reads under multi-test processes).
    """
    u = limbs.unpack_np(arr)
    if name == "xmm":
        u = u.reshape(u.shape[:-2] + (8,))
    return u.copy()


class HostFault(Exception):
    """Host-side page walk failed (non-present / non-canonical)."""

    def __init__(self, gva: int, write: bool):
        super().__init__(f"host #PF {'write' if write else 'read'} @ {gva:#x}")
        self.gva = gva
        self.write = write


class HostView:
    """Mutable host mirror of the batch: registers as numpy arrays, guest
    memory as a merged (pending-writes | device overlay | base image) view.

    This is what breakpoint handlers and the target harness operate on — the
    equivalent of the reference's `Backend_t` register/VirtRead/VirtWriteDirty
    surface (backend.cc:30-127), but for all lanes at once.  Mutations stay
    host-side until `Runner._push` applies them in one batch.
    """

    def __init__(self, runner: "Runner"):
        self.runner = runner
        m = runner.machine
        # ONE batched device->host transfer for all mirrored leaves (a
        # per-field pull costs a device round trip each — 22 per
        # servicing round)
        host = jax.device_get(
            {name: getattr(m, _LIMB_FIELDS.get(name, name))
             for name in _MIRROR_FIELDS}
            | {"__ov_pfn": m.overlay.pfn})
        # np.array: device_get may hand back read-only views; handlers
        # mutate.  Limb-packed fields convert to u64 views here (pack_np)
        # so every host consumer keeps architectural u64 semantics.
        self.r: Dict[str, np.ndarray] = {
            name: (_pack_mirror(name, np.asarray(host[name]))
                   if name in _LIMB_FIELDS else np.array(host[name]))
            for name in _MIRROR_FIELDS
        }
        # which machine this view mirrors, by its status leaf: every push,
        # dispatch and restore gives the runner's machine another (`fresh`)
        self._status = m.status
        # overlay index pulled once; data rows fetched lazily per
        # (lane, pfn), or for a list of them at once (`prefetch`)
        self._ov_pfn = host["__ov_pfn"]
        self._page_cache: Dict[Tuple[int, int], bytes] = {}
        self.pending: Dict[Tuple[int, int], bytearray] = {}
        # the lanes of a service round (`round`), and the frames that
        # have come for all of them
        self._round: List[int] = []
        self._round_frames: Set[int] = set()

    def fresh(self) -> bool:
        """Does the runner's machine still stand as this view pulled it?"""
        return self._status is self.runner.machine.status

    # -- registers -------------------------------------------------------
    def get_reg(self, lane: int, idx: int) -> int:
        return int(self.r["gpr"][lane, idx])

    def set_reg(self, lane: int, idx: int, value: int) -> None:
        self.r["gpr"][lane, idx] = np.uint64(value & MASK64)

    def get_rip(self, lane: int) -> int:
        return int(self.r["rip"][lane])

    def set_rip(self, lane: int, value: int) -> None:
        self.r["rip"][lane] = np.uint64(value & MASK64)

    def set_status(self, lane: int, status: StatusCode) -> None:
        self.r["status"][lane] = np.int32(int(status))

    def get_status(self, lane: int) -> StatusCode:
        return StatusCode(int(self.r["status"][lane]))

    # -- physical memory -------------------------------------------------
    def _base_page(self, lane: int, pfn: int) -> bytes:
        # routed per lane: heterogeneous batches read the LANE's base
        # image (wtf_tpu/tenancy); single-image runners route to the one
        # physmem as before
        return self.runner.lane_physmem(lane).host_read(
            pfn << PAGE_SHIFT, PAGE_SIZE)

    def _overlay_row(self, lane: int, pfn: int) -> Optional[int]:
        slots = np.nonzero(self._ov_pfn[lane] == pfn)[0]
        return int(slots[0]) if len(slots) else None

    def _merge_row(self, lane: int, pfn: int, data, valid) -> bytes:
        # delta row: only valid words come from the overlay, the rest
        # from the base image (little-endian words -> bytes on a LE host)
        base = np.frombuffer(self._base_page(lane, pfn), dtype=np.uint64)
        return np.where(valid != 0, data, base).tobytes()

    def _count_row_pull(self, rows: int) -> None:
        self.runner.stats["view_row_pulls"] += 1
        self.runner.stats["view_rows"] += rows

    def _device_overlay_page(self, lane: int, pfn: int) -> Optional[bytes]:
        row = self._overlay_row(lane, pfn)
        if row is None:
            return None
        self._count_row_pull(1)
        data, valid = self.runner.fetch_overlay_row(lane, row)
        return self._merge_row(lane, pfn, data, valid)

    def prefetch(self, pages) -> None:
        """Bring the device-overlay pages among `pages` ((lane, pfn)
        pairs) into the page cache, each exactly as `page` would read it
        alone, in one dispatch and one transfer for all lanes: a second
        one only where a lane wants a second page.  Pages that are
        pending, cached or clean cost nothing, and a list of those alone
        dispatches nothing."""
        wanted: Dict[int, Dict[int, int]] = {}
        for lane, pfn in pages:
            if (lane, pfn) in self.pending or (lane, pfn) in self._page_cache:
                continue
            row = self._overlay_row(lane, pfn)
            if row is not None:
                wanted.setdefault(lane, {})[pfn] = row
        while wanted:
            taken = {lane: pfns.popitem() for lane, pfns in wanted.items()}
            wanted = {lane: pfns for lane, pfns in wanted.items() if pfns}
            rows = np.full(self._ov_pfn.shape[0], -1, dtype=np.int32)
            rows[list(taken)] = [row for _, row in taken.values()]
            self._count_row_pull(len(taken))
            data, valid = self.runner.fetch_overlay_rows(rows)
            for lane, (pfn, _) in taken.items():
                self._page_cache[lane, pfn] = self._merge_row(
                    lane, pfn, data[lane], valid[lane])

    @contextlib.contextmanager
    def round(self, lanes: List[int]):
        """While the runner services `lanes` one after another for one
        kind of stop (a round of faults, a round of breakpoints), a dirty
        frame that one of them reads comes for ALL of them, in one
        transfer: they share their page tables and stopped in the same
        code, so what the first reads (the kernel stack a frame is pushed
        on, a return address) the others read too, and a round costs a
        transfer a frame, not a round trip a lane.  A round whose
        handlers read no dirty memory transfers nothing."""
        self._round, self._round_frames = lanes, set()
        try:
            yield
        finally:
            self._round = []

    def page(self, lane: int, pfn: int) -> bytes:
        """Current contents of a guest-physical page as this lane sees it."""
        key = (lane, pfn)
        if key in self.pending:
            return bytes(self.pending[key])
        cached = self._page_cache.get(key)
        if (cached is None and self._round
                and pfn not in self._round_frames
                and self._overlay_row(lane, pfn) is not None):
            self._round_frames.add(pfn)
            self.prefetch((other, pfn) for other in self._round)
            cached = self._page_cache.get(key)
        if cached is None:
            cached = self._device_overlay_page(lane, pfn)
            if cached is None:
                cached = self._base_page(lane, pfn)
            self._page_cache[key] = cached
        return cached

    def page_dirty(self, lane: int, pfn: int) -> bool:
        return ((lane, pfn) in self.pending
                or bool(np.any(self._ov_pfn[lane] == pfn)))

    def phys_read(self, lane: int, gpa: int, size: int) -> bytes:
        out = bytearray()
        pos = gpa
        while pos < gpa + size:
            pfn = pos >> PAGE_SHIFT
            off = pos & (PAGE_SIZE - 1)
            chunk = min(gpa + size - pos, PAGE_SIZE - off)
            out += self.page(lane, pfn)[off:off + chunk]
            pos += chunk
        return bytes(out)

    def phys_write(self, lane: int, gpa: int, data: bytes) -> None:
        pos = 0
        while pos < len(data):
            addr = gpa + pos
            pfn = addr >> PAGE_SHIFT
            off = addr & (PAGE_SIZE - 1)
            chunk = min(len(data) - pos, PAGE_SIZE - off)
            key = (lane, pfn)
            if key not in self.pending:
                self.pending[key] = bytearray(self.page(lane, pfn))
            self.pending[key][off:off + chunk] = data[pos:pos + chunk]
            pos += chunk

    # -- virtual memory --------------------------------------------------
    def translate(self, lane: int, gva: int, write: bool = False) -> int:
        """4-level long-mode walk through this lane's memory view
        (reference kvm_backend.cc:1937-1998)."""
        gva &= MASK64
        top = gva >> 47
        if top != 0 and top != 0x1FFFF:
            raise HostFault(gva, write)
        table = int(self.r["cr3"][lane]) & PHYS_MASK
        for shift, large_mask in ((39, None), (30, 0x000F_FFFF_C000_0000),
                                  (21, 0x000F_FFFF_FFE0_0000), (12, None)):
            index = (gva >> shift) & 0x1FF
            entry = int.from_bytes(
                self.phys_read(lane, table + index * 8, 8), "little")
            if not entry & PTE_P:
                raise HostFault(gva, write)
            if write and not entry & PTE_W:
                raise HostFault(gva, write)
            if large_mask is not None and entry & PTE_PS:
                return (entry & large_mask) | (gva & ((1 << shift) - 1))
            if shift == 12:
                return (entry & PHYS_MASK) | (gva & 0xFFF)
            table = entry & PHYS_MASK
        raise AssertionError("unreachable")

    def virt_read(self, lane: int, gva: int, size: int) -> bytes:
        out = bytearray()
        pos = gva
        while pos < gva + size:
            off = pos & (PAGE_SIZE - 1)
            chunk = min(gva + size - pos, PAGE_SIZE - off)
            gpa = self.translate(lane, pos)
            out += self.phys_read(lane, gpa, chunk)
            pos += chunk
        return bytes(out)

    def virt_write(self, lane: int, gva: int, data: bytes) -> None:
        """Host-initiated guest write.  Writes through page protection (the
        reference's VirtWrite is a raw memcpy, backend.cc:91-127) and is
        dirty by construction — it lands in the overlay and rolls back at
        Restore, preserving the VirtWriteDirty contract."""
        pos = 0
        while pos < len(data):
            addr = gva + pos
            off = addr & (PAGE_SIZE - 1)
            chunk = min(len(data) - pos, PAGE_SIZE - off)
            gpa = self.translate(lane, addr, write=False)
            self.phys_write(lane, gpa, data[pos:pos + chunk])
            pos += chunk


class _LaneCtx:
    """Exception-delivery ctx (cpu/interrupts.py duck type) over one lane of
    a HostView: register/memory mutations land in the view and reach the
    device on the next push.  The IDT/TSS anchors come from the snapshot
    CpuState (lidt/ltr are not emulated — same fixed-tables model as the
    oracle)."""

    def __init__(self, view: HostView, lane: int, snapshot_cpu: CpuState):
        self.view = view
        self.lane = lane
        self.idt_base = snapshot_cpu.idtr.base
        self.idt_limit = snapshot_cpu.idtr.limit
        self.tss_base = snapshot_cpu.tr.base

    # registers
    @property
    def rip(self) -> int:
        return self.view.get_rip(self.lane)

    @rip.setter
    def rip(self, value: int) -> None:
        self.view.set_rip(self.lane, value)

    @property
    def rsp(self) -> int:
        return self.view.get_reg(self.lane, 4)

    @rsp.setter
    def rsp(self, value: int) -> None:
        self.view.set_reg(self.lane, 4, value)

    @property
    def rflags(self) -> int:
        return int(self.view.r["rflags"][self.lane])

    @rflags.setter
    def rflags(self, value: int) -> None:
        self.view.r["rflags"][self.lane] = np.uint64(value & MASK64)

    @property
    def cs_sel(self) -> int:
        return int(self.view.r["cs"][self.lane])

    @cs_sel.setter
    def cs_sel(self, value: int) -> None:
        self.view.r["cs"][self.lane] = np.uint64(value & 0xFFFF)

    @property
    def ss_sel(self) -> int:
        return int(self.view.r["ss"][self.lane])

    @ss_sel.setter
    def ss_sel(self, value: int) -> None:
        self.view.r["ss"][self.lane] = np.uint64(value & 0xFFFF)

    def set_cr2(self, value: int) -> None:
        self.view.r["cr2"][self.lane] = np.uint64(value & MASK64)

    # memory (through the lane's page tables; raises HostFault)
    def read_virt(self, gva: int, size: int) -> bytes:
        return self.view.virt_read(self.lane, gva, size)

    def read_u64(self, gva: int) -> int:
        return int.from_bytes(self.read_virt(gva, 8), "little")

    def write_u64(self, gva: int, value: int) -> None:
        self.view.virt_write(
            self.lane, gva, (value & MASK64).to_bytes(8, "little"))


class _FallbackMem:
    """EmuMem-compatible adapter running the EmuCpu oracle against one
    lane's HostView (slow-path single-stepping for UNSUPPORTED uops)."""

    def __init__(self, view: HostView, lane: int):
        self.view = view
        self.lane = lane
        self.stored = False     # a guest store went through (CTR_STORE)

    def phys_read(self, gpa: int, size: int) -> bytes:
        return self.view.phys_read(self.lane, gpa, size)

    def phys_write(self, gpa: int, data: bytes) -> None:
        self.stored = True
        self.view.phys_write(self.lane, gpa, data)

    def phys_read_u64(self, gpa: int) -> int:
        return int.from_bytes(self.phys_read(gpa, 8), "little")

    @property
    def overlay(self):
        # EmuCpu probes `pfn in mem.overlay` for its SMC check; expose the
        # lane's dirty-page predicate as a minimal container.
        view, lane = self.view, self.lane

        class _DirtySet:
            def __contains__(self, pfn):
                return view.page_dirty(lane, pfn)

        return _DirtySet()


def _lane_cpu_state(view: HostView, lane: int, snapshot_cpu: CpuState) -> CpuState:
    """Materialize a CpuState for the oracle from a lane's mirror (fields the
    device doesn't carry — segments, dr, x87 — come from the snapshot)."""
    cpu = snapshot_cpu.copy()
    cpu.set_gpr_list(list(view.r["gpr"][lane]))
    cpu.rip = int(view.r["rip"][lane])
    cpu.rflags = int(view.r["rflags"][lane])
    cpu.fs.base = int(view.r["fs_base"][lane])
    cpu.gs.base = int(view.r["gs_base"][lane])
    cpu.kernel_gs_base = int(view.r["kernel_gs_base"][lane])
    cpu.cr0 = int(view.r["cr0"][lane])
    cpu.cr2 = int(view.r["cr2"][lane])
    cpu.cr3 = int(view.r["cr3"][lane])
    cpu.cr4 = int(view.r["cr4"][lane])
    cpu.cr8 = int(view.r["cr8"][lane])
    cpu.cs.selector = int(view.r["cs"][lane])
    cpu.ss.selector = int(view.r["ss"][lane])
    cpu.lstar = int(view.r["lstar"][lane])
    cpu.star = int(view.r["star"][lane])
    cpu.sfmask = int(view.r["sfmask"][lane])
    cpu.efer = int(view.r["efer"][lane])
    cpu.tsc = int(view.r["tsc"][lane])
    cpu.fpst = [int(v) for v in view.r["fpst"][lane]]
    cpu.fpcw = int(view.r["fpcw"][lane])
    cpu.fpsw = int(view.r["fpsw"][lane])
    cpu.fptw = int(view.r["fptw"][lane])
    cpu.mxcsr = int(view.r["mxcsr"][lane])
    for i in range(16):
        for limb in range(4):
            cpu.zmm[i][limb] = int(view.r["xmm"][lane, i, limb])
    return cpu


def _writeback_lane(view: HostView, lane: int, cpu: EmuCpu) -> None:
    view.r["gpr"][lane] = np.array(cpu.gpr, dtype=np.uint64)
    view.r["rip"][lane] = np.uint64(cpu.rip & MASK64)
    view.r["rflags"][lane] = np.uint64(cpu.rflags & MASK64)
    view.r["fs_base"][lane] = np.uint64(cpu.fs_base & MASK64)
    view.r["gs_base"][lane] = np.uint64(cpu.gs_base & MASK64)
    view.r["kernel_gs_base"][lane] = np.uint64(cpu.kernel_gs_base & MASK64)
    view.r["cr0"][lane] = np.uint64(cpu.cr0 & MASK64)
    view.r["cr2"][lane] = np.uint64(cpu.cr2 & MASK64)
    view.r["cr3"][lane] = np.uint64(cpu.cr3 & MASK64)
    view.r["cr4"][lane] = np.uint64(cpu.cr4 & MASK64)
    view.r["cr8"][lane] = np.uint64(cpu.cr8 & MASK64)
    view.r["cs"][lane] = np.uint64(cpu.cs_sel & 0xFFFF)
    view.r["ss"][lane] = np.uint64(cpu.ss_sel & 0xFFFF)
    # MSR-backed fields a wrmsr fallback may have rewritten
    view.r["lstar"][lane] = np.uint64(cpu.lstar & MASK64)
    view.r["star"][lane] = np.uint64(cpu.star & MASK64)
    view.r["sfmask"][lane] = np.uint64(cpu.sfmask & MASK64)
    view.r["efer"][lane] = np.uint64(cpu.efer & MASK64)
    view.r["tsc"][lane] = np.uint64(cpu.tsc & MASK64)
    view.r["fpst"][lane] = np.array(
        [v & MASK64 for v in cpu.fp_state_list()], dtype=np.uint64)
    view.r["fpcw"][lane] = np.uint64(cpu.fpcw & 0xFFFF)
    view.r["fpsw"][lane] = np.uint64(cpu.fpsw_packed() & 0xFFFF)
    view.r["fptw"][lane] = np.uint64(cpu.fptw & 0xFFFF)
    view.r["mxcsr"][lane] = np.uint64(cpu.mxcsr & MASK64)
    for i in range(16):
        view.r["xmm"][lane, i, 0] = np.uint64(cpu.xmm[i][0] & MASK64)
        view.r["xmm"][lane, i, 1] = np.uint64(cpu.xmm[i][1] & MASK64)
        view.r["xmm"][lane, i, 2] = np.uint64(cpu.ymmh[i][0] & MASK64)
        view.r["xmm"][lane, i, 3] = np.uint64(cpu.ymmh[i][1] & MASK64)


def _apply_page_writes(machine: Machine, lanes, pfns, pages, ok_mask):
    """Apply K buffered (lane, pfn, page) writes into the batched overlay in
    one device call (lax.scan; K is padded to a bucket size host-side).

    Jitted below in a donated variant (overlay mutates in place; off-CPU
    hot path) and a plain one (CPU — donation is unsound there, see
    make_run_chunk); machine_restore copies template leaves so the live
    machine never aliases the pristine template."""
    capacity = machine.overlay.pfn.shape[1]

    def body(overlay, item):
        lane, pfn, page, ok = item
        row = overlay.pfn[lane]
        eq = row == pfn
        idx0 = jnp.argmax(eq).astype(jnp.int32)
        hit = eq[idx0]
        can = overlay.count[lane] < capacity
        slot = jnp.where(hit, idx0, overlay.count[lane] % capacity)
        do = ok & (hit | can)
        old_data, old_valid = ovl.read_row(overlay, lane, slot)
        # a whole-page host write makes every word of the delta row valid
        overlay = ovl.write_row(
            overlay, lane, slot, jnp.where(do, page, old_data),
            jnp.where(do, jnp.ones_like(old_valid), old_valid))
        pfn_new = overlay.pfn.at[lane, slot].set(
            jnp.where(do, pfn, overlay.pfn[lane, slot]).astype(jnp.int32))
        count = overlay.count.at[lane].add(
            jnp.where(ok & ~hit & can, 1, 0).astype(jnp.int32))
        overflow = overlay.overflow.at[lane].set(
            overlay.overflow[lane] | (ok & ~hit & ~can))
        # the pushed frame may be a page table of the lane's
        tlb = overlay.tlb
        flags = ovl.tlb_drop(jax.tree.map(lambda x: x[lane], tlb),
                             pfn[None], do[None]).flags
        return overlay._replace(
            pfn=pfn_new, count=count, overflow=overflow,
            tlb=tlb._replace(flags=tlb.flags.at[lane].set(flags))), None

    overlay, _ = lax.scan(body, machine.overlay, (lanes, pfns, pages, ok_mask))
    # A host write that exceeded the lane's slots was dropped — surface the
    # lane as OVERLAY_FULL instead of running on silently-truncated memory
    # (the guest-store path surfaces the same way via step.py's `ovf`).
    status = jnp.where(
        overlay.overflow
        & (machine.status == jnp.int32(int(StatusCode.RUNNING))),
        jnp.int32(int(StatusCode.OVERLAY_FULL)), machine.status)
    return machine._replace(overlay=overlay, status=status)


_apply_page_writes_donated = partial(
    jax.jit, donate_argnums=(0,))(_apply_page_writes)
_apply_page_writes_plain = jax.jit(_apply_page_writes)


def device_insert_impl(n_pages: int, len_gpr: int, ptr_gpr: int,
                       masked: bool = False):
    """The PURE insert transition (machine, words, lens, pfns, gva_l[,
    active]) -> machine' for a given insert-region geometry — shared by
    the jitted standalone seam below and the megachunk program
    (wtf_tpu/fuzz/megachunk.py), so the two dispatch paths cannot drift.
    See `_make_device_insert` for the slot-claim contract."""

    @jax.named_scope("device-insert")
    def impl(machine: Machine, words, lens, pfns, gva_l, *rest):
        n_words = words.shape[1]
        pad = n_pages * (PAGE_SIZE // 4) - n_words
        assert pad >= 0, "testcase words exceed the insert region"
        # `masked` variant (wtf_tpu/tenancy): `active` (bool[L]) limits
        # the insert to one tenant's lanes — inactive lanes keep their
        # overlay rows, counters, status and ABI registers untouched, so
        # per-tenant device batches land with one dispatch per tenant.
        active = rest[0] if masked else None
        n_lanes = machine.status.shape[0]
        w = jnp.pad(words, ((0, 0), (0, pad))) if pad else words
        rows = limbs.pack_u64(
            w.reshape(n_lanes, n_pages, PAGE_SIZE // 8, 2))
        ov = machine.overlay
        capacity = ov.pfn.shape[1]
        # retire rows already holding an insert-region pfn (a pushed
        # host write into the input region; slot leaks until restore)
        dead = (ov.pfn[:, :, None] == pfns[None, None, :]).any(-1)
        if active is not None:
            dead = dead & active[:, None]
        pfn0 = jnp.where(dead, jnp.int32(-1), ov.pfn)
        start = ov.count                                   # i32[L]
        can = start + jnp.int32(n_pages) <= jnp.int32(capacity)
        ok = can if active is None else (can & active)
        li = lax.broadcasted_iota(jnp.int32, (n_lanes, n_pages), 0)
        ridx = jnp.minimum(start[:, None]
                           + lax.broadcasted_iota(
                               jnp.int32, (n_lanes, n_pages), 1),
                           jnp.int32(capacity - 1))
        sel = ok[:, None]
        full = ~can if active is None else (active & ~can)
        overlay = ovl.fill_rows(
            ov, start, rows.reshape(n_lanes, n_pages * (PAGE_SIZE // 8)),
            ok)._replace(
            pfn=pfn0.at[li, ridx].set(
                jnp.where(sel, jnp.broadcast_to(pfns, (n_lanes, n_pages)),
                          pfn0[li, ridx])),
            count=jnp.where(ok, start + jnp.int32(n_pages), start),
            overflow=ov.overflow | full,
            # rows come and go under the lanes in hand: their translation
            # caches start empty
            tlb=ov.tlb._replace(flags=jnp.zeros_like(ov.tlb.flags)
                                if active is None else
                                jnp.where(active[:, None], 0, ov.tlb.flags)),
        )
        status = jnp.where(
            full & (machine.status == jnp.int32(int(StatusCode.RUNNING))),
            jnp.int32(int(StatusCode.OVERLAY_FULL)), machine.status)
        gpr = machine.gpr_l
        if active is None:
            gpr = gpr.at[:, len_gpr, 0].set(lens.astype(jnp.uint32))
            gpr = gpr.at[:, len_gpr, 1].set(jnp.uint32(0))
            gpr = gpr.at[:, ptr_gpr, 0].set(gva_l[0])
            gpr = gpr.at[:, ptr_gpr, 1].set(gva_l[1])
        else:
            gpr = gpr.at[:, len_gpr, 0].set(
                jnp.where(active, lens.astype(jnp.uint32),
                          gpr[:, len_gpr, 0]))
            gpr = gpr.at[:, len_gpr, 1].set(
                jnp.where(active, jnp.uint32(0), gpr[:, len_gpr, 1]))
            gpr = gpr.at[:, ptr_gpr, 0].set(
                jnp.where(active, gva_l[0], gpr[:, ptr_gpr, 0]))
            gpr = gpr.at[:, ptr_gpr, 1].set(
                jnp.where(active, gva_l[1], gpr[:, ptr_gpr, 1]))
        return machine._replace(overlay=overlay, gpr_l=gpr,
                                status=status)

    return impl


@lru_cache(maxsize=None)
def _make_device_insert(n_pages: int, n_words: int, len_gpr: int,
                        ptr_gpr: int, donate: bool, masked: bool = False):
    """The fused insert seam for device-generated testcases (wtf_tpu/
    devmut): one in-graph update that lands a whole batch's bytes in the
    per-lane overlay and sets the target ABI registers — the
    mutate-on-device replacement for per-lane target.insert_testcase.

    Claims n_pages FRESH overlay slots per lane starting at the lane's
    current count, so rows the preceding host push allocated (init-time
    target writes to pages OUTSIDE the insert region) survive.  Any
    existing row already holding an insert-region pfn is retired first
    (pfn -> -1): the testcase must win, and a duplicate-pfn row would
    shadow the new one (overlay lookup takes the FIRST match).  A lane
    without n_pages free slots surfaces as OVERLAY_FULL, exactly like
    the host page-write path.  The u32 word stream bitcasts to the
    overlay's u64 words at the pack seam; rows are fully valid (bytes
    past the testcase length are zero by the engine's padded-slab
    contract, so page contents are deterministic).

    `n_words` only keys the memoization (jit re-specializes on shapes);
    the transition itself comes from `device_insert_impl`, the same
    pure function the megachunk program inlines."""
    del n_words
    impl = device_insert_impl(n_pages, len_gpr, ptr_gpr, masked=masked)
    return jax.jit(impl, donate_argnums=(0,) if donate else ())


class Runner:
    """Owns the device batch + decode cache and drives the chunked run loop.

    One Runner == one snapshot loaded on device == N lanes of that snapshot
    (the reference equivalent is one Backend_t instance == one VM; here the
    VM is the whole batch)."""

    def __init__(
        self,
        snapshot: Snapshot,
        n_lanes: int,
        uop_capacity: int = 1 << 14,
        overlay_slots: int = 128,
        edge_bits: int = 17,
        chunk_steps: int = 256,
        deliver_exceptions: Optional[bool] = None,
        registry: Optional[Registry] = None,
        events=None,
        burst_any_tier: Optional[bool] = None,
        tenants=None,
        supervisor: Optional[Supervisor] = None,
        device_decode: bool = False,
    ):
        # Telemetry: metrics registry (private unless the backend/CLI hands
        # in a shared one) + JSONL event sink (NULL swallows when unwired)
        self.registry = registry if registry is not None else Registry()
        self.events = events if events is not None else NULL
        # Every device dispatch seam routes through the supervisor
        # (wtf_tpu/supervise): inert by default (one `is None` test per
        # dispatch), armed by the backend for watchdog/recovery/chaos.
        # A rebuilt Runner SHARES its backend's supervisor so dispatch
        # indices and telemetry survive recovery.
        self.supervisor = supervisor if supervisor is not None \
            else Supervisor(registry=self.registry, events=self.events)
        self.snapshot = snapshot
        self.physmem = snapshot.physmem
        # extra executor-identity tag mixed into compile-event keys
        # (mesh runners dispatch different programs at the same shapes)
        self.exec_sig: Tuple = ()
        self.n_lanes = n_lanes
        self.cache = DecodeCache(capacity=uop_capacity)
        if tenants is None:
            # the image operand executors dispatch against, and the batch:
            # both placed as they are made (a mesh runner's image on every
            # chip, its lanes split over them); host-side page reads keep
            # going through self.physmem
            self.image = snapshot.physmem.upload(self.image_sharding())
            self.machine = machine_init(
                snapshot.cpu, n_lanes, uop_capacity, overlay_slots,
                edge_bits, sharding=self.lane_sharding())
            self.template = machine_init(
                snapshot.cpu, n_lanes, uop_capacity, overlay_slots=0,
                edge_bits=edge_bits, sharding=self.lane_sharding())
            self.tenant_of_lane = np.zeros(n_lanes, dtype=np.int32)
            self._physmems = [snapshot.physmem]
            self._cpu0s = [snapshot.cpu]
        else:
            # heterogeneous batch (wtf_tpu/tenancy): per-lane base-image
            # ids over a stacked image table; per-lane machine state
            # initialized from each tenant's CpuState.  `snapshot` is the
            # table's primary (tenant 0) for the compat surfaces above.
            from wtf_tpu.tenancy.image import build_batch_state

            built = build_batch_state(tenants, n_lanes, uop_capacity,
                                      overlay_slots, edge_bits)
            self.image = built.image
            self.machine = built.machine
            self.template = built.template
            self.tenant_of_lane = built.tenant_of_lane
            self._physmems = built.physmems
            self._cpu0s = built.cpus
        self.limit = 0
        self.chunk_steps = chunk_steps
        # Guest exception delivery (reference: every fault is serviced by
        # the guest through bochs' IDT emulation / KVM event injection).
        # Auto mode turns it on exactly when the snapshot carries an IDT;
        # IDT-less synthetic guests keep the terminal-fault behavior.
        # Heterogeneous batches gate per lane: the servicing loop only
        # delivers through tenants that carry an IDT (cpu0_of), so an
        # IDT-less tenant's faults stay terminal exactly as they do solo.
        if deliver_exceptions is None:
            deliver_exceptions = any(
                cpu.idtr.limit > 0 for cpu in self._cpu0s)
        self.deliver_exceptions = deliver_exceptions
        # Donation only off-CPU: XLA CPU miscompiles donated machines on
        # this graph (see make_run_chunk's caveat) and donation buys
        # nothing on a host backend anyway.
        self._donate = jax.default_backend() != "cpu"
        if fp_on_host():
            _log.info("float-valued SSE/x87 uops retire on the host "
                      "oracle: f64 on %s is not an IEEE double",
                      jax.default_backend())
        # Device-resident x86 decode (interp/devdec): megachunk windows
        # service decode misses in-graph and host servicing rounds pull
        # only the missing lanes' code windows instead of full page
        # views.  The host decoder stays the authoritative oracle: every
        # device-published entry is re-decoded and cross-checked at
        # harvest (uoptable.adopt_device_entries).
        self.device_decode = device_decode
        self.lane_errors: Dict[int, str] = {}
        self._smc_updates: Dict[int, int] = {}
        # Adaptive chunk growth for deep executions (BASELINE config 5 is
        # 100M instructions/testcase): once a chunk completes with nothing
        # to service and lanes still running — i.e. the decode cache is
        # warm and the guest is just executing — step up to a larger
        # chunk so host round trips stop dominating.  Any serviceable
        # status drops back to the base size for responsive servicing.
        # Sizes are sparse (x16) to bound the number of XLA compiles.
        self.adaptive_chunks = True
        # x16 growth rungs, min-capped so the TOP rung always reaches
        # 65536 (the plain x16 ladder stops short for most bases — e.g.
        # 512 -> 8192 — and a deep execution, BASELINE config 5's 100M
        # instructions, then pays 8x the host round trips)
        self._chunk_sizes = [chunk_steps]
        while self._chunk_sizes[-1] < (1 << 16):
            self._chunk_sizes.append(
                min(self._chunk_sizes[-1] * 16, 1 << 16))
        self._chunk_level = 0
        # consecutive service rounds per lane (oracle burst sizing)
        self._fallback_streak: Dict[int, int] = {}
        # The burst's any-instruction tier amortizes EXPENSIVE dispatch
        # round trips (a real chip); on the CPU
        # platform a dispatch is ~free and the device executes glue
        # instructions faster than the Python oracle, so the platform
        # default is off there.  The explicit override (config/CLI
        # --burst-any-tier) exists so the tier can run — and be benched —
        # on the CPU platform too (VERDICT weak item 4).
        if burst_any_tier is None:
            burst_any_tier = jax.default_backend() != "cpu"
        self.burst_any_tier = burst_any_tier
        # (lane, uop-entry) coverage bits and (lane, edge-index) edge bits
        # owed by oracle burst steps; OR-ed into the device bitmaps at
        # the next push
        self._pending_cov: List[Tuple[int, int]] = []
        self._pending_edge: List[Tuple[int, int]] = []
        # run statistics (reference PrintRunStats role, backend.h:218) —
        # a dict facade over registry counters, so the same numbers feed
        # print_run_stats, the heartbeat line, and the JSONL stream.
        # fallbacks_by_opclass: oracle single-steps keyed by the uop's
        # opcode class name, so campaign output can attribute WHY lanes
        # left the device path (VERDICT r5 item 3).  exceptions_by_vector:
        # the deliveries through the guest's IDT, "#DE" / "#GP" / "#PF".
        self.stats = StatsDict(
            self.registry, "runner",
            fields=("chunks", "decodes", "decodes_prefetched",
                    "decode_windows_gathered", "fallbacks",
                    "fallback_burst_steps", "smc_updates",
                    "bp_dispatches", "exceptions_delivered",
                    "exceptions_undelivered",
                    "view_row_pulls", "view_rows"),
            gauges=("max_chunk_steps",),
            labeled=("fallbacks_by_opclass", "exceptions_by_vector"))
        self.stats["max_chunk_steps"] = chunk_steps
        self.supervisor.attach_runner(self)

    # -- per-lane tenant routing (wtf_tpu/tenancy; single-image batches
    # are tenant 0 everywhere) ----------------------------------------------
    def tenant_of(self, lane: int) -> int:
        return int(self.tenant_of_lane[lane])

    def cpu0_of(self, lane: int):
        """The lane's snapshot CpuState (oracle fallback segments/x87,
        IDT/TSS anchors for exception delivery)."""
        return self._cpu0s[self.tenant_of(lane)]

    def lane_physmem(self, lane: int):
        """The lane's base-image PhysMem (host-side page reads)."""
        return self._physmems[self.tenant_of(lane)]

    def _deliver_lane(self, lane: int) -> bool:
        return self._cpu0s[self.tenant_of(lane)].idtr.limit > 0

    # -- placement (None: the default device; MeshRunner names its mesh) ---
    def lane_sharding(self):
        """Where an array with a leading lane axis lies."""
        return None

    @property
    def lane_groups(self) -> int:
        """The parts the lane axis is held in, a device each."""
        sharding = self.lane_sharding()
        return 1 if sharding is None else sharding.num_devices

    def image_sharding(self):
        """Where the snapshot image lies."""
        return None

    def _put_lanes(self, host: np.ndarray) -> jax.Array:
        return jax.device_put(host, self.lane_sharding())

    # -- device dispatch surface (the seams MeshRunner re-points) ----------
    def device_tab(self):
        """The dispatch-ready uop table (mesh runners hand back a
        replicated placement of the same pytree)."""
        return self.cache.device()

    def _chunk_callable(self, n_steps: int):
        """The executor run() dispatches for one chunk of `n_steps`
        (memoized in step._CHUNK_CACHE; mesh runners swap in the
        shard_map executor, meshrun/executor.py)."""
        return make_run_chunk(n_steps, donate=self._donate)

    def megachunk_callable(self, max_batches: int, n_pages: int,
                           len_gpr: int, ptr_gpr: int, rounds: int):
        """The one-dispatch multi-batch window program (wtf_tpu/fuzz/
        megachunk.py) — the seam the megachunk driver dispatches, so
        mesh runners can swap in the shard_map variant with the same
        signature."""
        from wtf_tpu.fuzz.megachunk import make_megachunk

        return make_megachunk(max_batches, n_pages, len_gpr, ptr_gpr,
                              rounds, deliver=self.deliver_exceptions,
                              devdec=self.device_decode,
                              donate=self._donate)

    def devdec_operands(self) -> Tuple:
        """Extra megachunk operands for the in-graph decoder: the live
        cache count plus the pending-breakpoint key vector, padded to a
        pow2 bucket (0 is a VALID key, so the live length rides along).
        Empty tuple when --device-decode is off, so dispatch sites can
        always splat it."""
        if not self.device_decode:
            return ()
        keys = sorted(self.cache.pending_bps)
        bucket = 8
        while bucket < len(keys):
            bucket *= 2
        padded = np.zeros(bucket, dtype=np.uint64)
        for j, k in enumerate(keys):
            padded[j] = np.uint64(k)
        return (jnp.int32(self.cache.count), jnp.asarray(padded),
                jnp.int32(len(keys)))

    def megachunk_place(self, slab_first, slab_rest, seeds):
        """Placement hook for one window's operands — identity on a
        single device; the mesh runner replicates the slabs and shards
        the seed stream."""
        return slab_first, slab_rest, seeds

    def devmut_generate(self, rounds: int, data, lens, cumw, seeds):
        """Dispatch one devmut batch generation (wtf_tpu/devmut) — the
        seam the device mutator drives, so mesh runners can run the
        generator per shard with the slab replicated and the seed stream
        lane-sharded."""
        from wtf_tpu.devmut.engine import make_generate

        return make_generate(rounds)(data, lens, cumw, jnp.asarray(seeds))

    # -- checkpoint/resume (wtf_tpu/resume) --------------------------------
    def checkpoint_state(self) -> dict:
        """The runner state a campaign checkpoint must carry: the decode
        cache in insertion order (coverage-bitmap bit i is cache entry i,
        so restored aggregate bitmaps are meaningless without identical
        indices) plus the SMC thrash counters that gate the per-rip
        fallback cutover.  Machine state needs nothing — checkpoints are
        taken at batch boundaries, where the machine is freshly
        restored to the snapshot."""
        return {
            "cache": self.cache.checkpoint_entries(),
            # (tenant, rip) keys flatten to JSON-able triples
            "smc_updates": [[t, r, n]
                            for (t, r), n in self._smc_updates.items()],
        }

    def restore_state(self, state: dict) -> None:
        """Restore checkpoint_state() output into a freshly-initialized
        runner (empty decode cache; breakpoints from target.init may
        already be pending — add() re-arms them)."""
        self.cache.restore_entries(state.get("cache", []))
        smc = state.get("smc_updates", [])
        if isinstance(smc, dict):
            # pre-tenancy checkpoints: {rip: n} means tenant 0
            self._smc_updates = {(0, int(k)): int(v)
                                 for k, v in smc.items()}
        else:
            self._smc_updates = {(int(t), int(r)): int(n)
                                 for t, r, n in smc}

    # -- trace-capture hooks (wtf_tpu.analysis, tests/test_chip_compile.py) -
    def executor_operands(self) -> Tuple:
        """(tab, image, machine, limit) — the chunk executor's positional
        operands, exactly as run() dispatches them.  The export hook for the
        static analyzer and the compile tests; no private-state reach-in."""
        return (self.device_tab(), self.image, self.machine,
                jnp.uint64(self.limit))

    def chunk_executor(self, n_steps: Optional[int] = None,
                       donate: Optional[bool] = None):
        """The jitted chunk executor this runner dispatches (memoized in
        step._CHUNK_CACHE).  Defaults follow the runner's own size and
        platform donation policy."""
        return make_run_chunk(
            self.chunk_steps if n_steps is None else n_steps,
            donate=self._donate if donate is None else donate)

    # -- host memory access ------------------------------------------------
    def view(self) -> HostView:
        return HostView(self)

    def fetch_overlay_rows(self, rows):
        """One overlay row per lane (`rows[lane]`, -1: none) in one
        dispatch and one transfer: `HostView.prefetch`'s device seam.
        One shape per runner, compiled by the first batch that wants a
        row."""
        overlay = self.machine.overlay
        key = ("view-rows", overlay.pfn.shape, self.exec_sig)
        if key not in _DISPATCHED_EXECUTORS:
            _DISPATCHED_EXECUTORS.add(key)
            self.events.emit("compile", kind="view-rows",
                             lanes=self.n_lanes)
        return ovl.fetch_lane_rows(overlay, rows, self._lane_rows_reader())

    def _lane_rows_reader(self):
        """The compiled `overlay.read_lane_rows` (the mesh runner's runs
        per shard)."""
        return ovl.read_lane_rows_jit

    def fetch_overlay_row(self, lane: int, row: int):
        """One overlay row of one lane: `HostView.page`'s device seam
        outside a round -> (np.uint64[PAGE_WORDS], np.uint8[PAGE_WORDS])."""
        return ovl.fetch_row(self.machine.overlay, lane, row)

    # -- mutate-on-device insert seam (wtf_tpu/devmut) ---------------------
    def device_insert(self, words, lens, pfns, gva: int,
                      len_gpr: int, ptr_gpr: int, active=None) -> None:
        """Insert a device-generated batch without a host round-trip:
        `words` (u32[L, W]) / `lens` (i32[L]) — typically straight from
        devmut's generate dispatch — land in overlay slots [0, n_pages)
        of every lane and the target's ABI registers are set in the same
        program.  Call on a freshly restored machine (the overlay must
        be empty; the fuzz loop's restore→insert ordering guarantees
        it)."""
        n_pages = len(pfns)
        capacity = self.machine.overlay.pfn.shape[1]
        if n_pages > capacity:
            raise ValueError(
                f"device-insert region spans {n_pages} pages but lanes "
                f"have only {capacity} overlay slots — raise "
                f"overlay_slots or shrink the mutator/spec max_len")
        masked = active is not None
        fn = self._inserter(n_pages, words.shape[1], len_gpr, ptr_gpr,
                            masked)
        key = ("devins", n_pages, words.shape[1], len_gpr, ptr_gpr,
               self.n_lanes, self._donate, masked, self.exec_sig)
        if key not in _DISPATCHED_EXECUTORS:
            _DISPATCHED_EXECUTORS.add(key)
            self.events.emit("compile", kind="device-insert",
                             pages=n_pages, words=int(words.shape[1]))
        gva_l = np.array([gva & 0xFFFF_FFFF, (gva >> 32) & 0xFFFF_FFFF],
                         dtype=np.uint32)
        extra = (jnp.asarray(np.asarray(active, dtype=bool)),) if masked \
            else ()
        self.machine = self.supervisor.dispatch(
            "device-insert", fn, self.machine, words, lens,
            jnp.asarray(np.asarray(pfns, dtype=np.int32)),
            jnp.asarray(gva_l), *extra, sync=lambda m: m.status)

    def _inserter(self, n_pages: int, n_words: int, len_gpr: int,
                  ptr_gpr: int, masked: bool):
        """The compiled `device_insert_impl` (the mesh runner's runs per
        shard)."""
        return _make_device_insert(n_pages, n_words, len_gpr, ptr_gpr,
                                   self._donate, masked=masked)

    def push(self, view: HostView) -> None:
        """Apply a HostView's mutations (registers + buffered page writes +
        burst coverage bits) back to the device batch."""
        updates = {
            _LIMB_FIELDS.get(name, name): self._put_lanes(
                _unpack_mirror(name, view.r[name])
                if name in _LIMB_FIELDS else view.r[name])
            for name in _MIRROR_FIELDS
        }
        self.machine = self.machine._replace(**updates)
        def _apply_bits(bitmap, pending):
            # combine host-side to unique (lane, word) pairs so the
            # device read-modify-write scatter is deterministic
            acc: Dict[Tuple[int, int], int] = {}
            for lane, bit in pending:
                key = (lane, bit >> 5)
                acc[key] = acc.get(key, 0) | (1 << (bit & 31))
            lanes = jnp.asarray([k[0] for k in acc], dtype=jnp.int32)
            words = jnp.asarray([k[1] for k in acc], dtype=jnp.int32)
            bits = jnp.asarray(list(acc.values()), dtype=jnp.uint32)
            return bitmap.at[lanes, words].set(bitmap[lanes, words] | bits)

        if self._pending_cov:
            self.machine = self.machine._replace(
                cov=_apply_bits(self.machine.cov, self._pending_cov))
            self._pending_cov.clear()
        if self._pending_edge:
            self.machine = self.machine._replace(
                edge=_apply_bits(self.machine.edge, self._pending_edge))
            self._pending_edge.clear()
        if view.pending:
            # dealt by lane group (one on a single device; a mesh's
            # shards, each of which applies its own lanes' writes to the
            # planes it holds): group g's writes fill row g of each array,
            # their lanes counted from the group's first
            items = sorted(view.pending.items())
            keys = np.array([key for key, _ in items], dtype=np.int64)
            per_group = self.n_lanes // self.lane_groups
            group = keys[:, 0] // per_group
            first = np.searchsorted(group, np.arange(self.lane_groups + 1))
            at = (group, np.arange(len(items)) - first[group])
            bucket = 8
            while bucket < np.diff(first).max():
                bucket *= 2
            shape = (self.lane_groups, bucket)
            lanes = np.zeros(shape, dtype=np.int32)
            pfns = np.full(shape, -2, dtype=np.int32)
            pages = np.zeros(shape + (PAGE_SIZE,), dtype=np.uint8)
            valid = np.zeros(shape, dtype=bool)
            lanes[at] = keys[:, 0] - group * per_group
            pfns[at] = keys[:, 1]
            pages[at] = np.frombuffer(
                b"".join(page for _, page in items),
                dtype=np.uint8).reshape(len(items), PAGE_SIZE)
            valid[at] = True
            flat = self.lane_groups * bucket
            self.machine = self._page_writer()(
                self.machine, self._put_lanes(lanes.reshape(flat)),
                self._put_lanes(pfns.reshape(flat)),
                self._put_lanes(pages.view(np.uint64).reshape(flat, -1)),
                self._put_lanes(valid.reshape(flat)))
            view.pending.clear()

    def _page_writer(self):
        """The compiled `_apply_page_writes` (the mesh runner's runs per
        shard)."""
        return (_apply_page_writes_donated if self._donate
                else _apply_page_writes_plain)

    # -- servicing ---------------------------------------------------------
    @staticmethod
    def _fetch_window(view: HostView, lane: int, rip: int) -> bytes:
        """The 15-byte decode window at `rip`, or, where it runs into an
        unmapped page, the bytes up to that page: what the oracle's
        EmuCpu._fetch_window gives.  An instruction that ends before the
        boundary decodes and runs as on the oracle; only a `rip` that is
        itself unmapped is a fetch fault (PERF.md section 6, fault (a))."""
        try:
            return view.virt_read(lane, rip, 15)
        except HostFault:
            return view.virt_read(lane, rip,
                                  PAGE_SIZE - (rip & (PAGE_SIZE - 1)))

    def _decode_at(self, view: HostView, lane: int, rip: int,
                   prefetched=None) -> bool:
        """Decode the instruction at `rip` through `lane`'s memory view and
        publish it.  Returns False on hard failure (lane made terminal).

        `prefetched` is an optional (window, fault, pfn0, pfn14) tuple
        from the device window gather (--device-decode): same bytes,
        same fault/pfn facts, no HostView page pulls for the fetch."""
        if prefetched is not None:
            window, faulted, pfn0, pfn14 = prefetched
        else:
            try:
                pfn0 = view.translate(lane, rip) >> PAGE_SHIFT
                window = self._fetch_window(view, lane, rip)
                faulted = False
            except HostFault:
                faulted = True
        if faulted:
            self.lane_errors[lane] = f"fetch fault @ {rip:#x}"
            # host-detected fault: mirror the device's CTR_MEM_FAULT
            # accounting (a device page walk would have counted it)
            view.r["ctr"][lane, CTR_MEM_FAULT] += np.uint32(1)
            view.set_status(lane, StatusCode.PAGE_FAULT)
            view.r["fault_gva"][lane] = np.uint64(rip & MASK64)
            view.r["fault_write"][lane] = np.int32(0)
            return False
        uop = decode(window, rip)
        if prefetched is not None:
            # a successful 15-byte window read guarantees the last
            # instruction byte translates; its frame is pfn0 unless the
            # instruction itself crosses into the window's second page
            crosses = (rip & (PAGE_SIZE - 1)) + max(uop.length - 1, 0) \
                >= PAGE_SIZE
            pfn1 = pfn14 if crosses else pfn0
        else:
            try:
                pfn1 = view.translate(
                    lane, rip + max(uop.length - 1, 0)) >> PAGE_SHIFT
            except HostFault:
                pfn1 = pfn0
        self.cache.add(rip, uop, pfn0, pfn1, tenant=self.tenant_of(lane))
        self.stats["decodes"] += 1
        self._prefetch_block(view, lane, uop, rip)
        return True

    # Decode-ahead bounds: block prefetch publishes up to this many extra
    # instructions per miss, and never within this margin of cache capacity
    PREFETCH_BUDGET = 48
    _PREFETCH_MARGIN = 64

    def _prefetch_block(self, view: HostView, lane: int, uop, rip: int) -> None:
        """Recursive-descent decode-ahead from a fresh miss: follow the
        fallthrough and direct branch targets so a basic block's worth of
        code publishes in ONE servicing round instead of one full
        pull/push/dispatch round trip per instruction — the dominant
        cold-start cost (PERF.md's host<->device term).  Wrong-path
        prefetches are harmless: decode is deterministic on bytes, entries
        are only consulted at executed rips, and OPC_INVALID results are
        simply not published."""
        def succs(u, at):
            nxt = (at + u.length) & MASK64
            opc = u.opc
            if opc in (U.OPC_RET, U.OPC_IRET, U.OPC_HLT, U.OPC_INT,
                       U.OPC_INT1, U.OPC_INVALID, U.OPC_SYSCALL):
                return ()
            if opc == U.OPC_JMP:
                return ((nxt + u.imm) & MASK64,) if u.src_kind == U.K_IMM \
                    else ()
            if opc == U.OPC_JCC:
                return (nxt, (nxt + u.imm) & MASK64)
            if opc == U.OPC_CALL and u.src_kind == U.K_IMM:
                return (nxt, (nxt + u.imm) & MASK64)
            return (nxt,)

        budget = self.PREFETCH_BUDGET
        tenant = self.tenant_of(lane)
        work = list(succs(uop, rip))
        while work and budget > 0:
            if self.cache.count >= self.cache.capacity - self._PREFETCH_MARGIN:
                return
            at = work.pop()
            if self.cache.has(at, tenant):
                continue
            try:
                window = view.virt_read(lane, at, 15)
                pfn0 = view.translate(lane, at) >> PAGE_SHIFT
            except HostFault:
                continue
            u2 = decode(window, at)
            if u2.opc == U.OPC_INVALID:
                continue  # probably swept into data; let a real miss decide
            try:
                pfn1 = view.translate(
                    lane, at + max(u2.length - 1, 0)) >> PAGE_SHIFT
            except HostFault:
                pfn1 = pfn0
            self.cache.add(at, u2, pfn0, pfn1, tenant=tenant)
            self.stats["decodes_prefetched"] += 1
            budget -= 1
            work.extend(succs(u2, at))

    def _service_decode(self, view: HostView, lanes: List[int]) -> None:
        windows = (self._gather_code_windows(view, lanes)
                   if self.device_decode else {})
        done: Set[Tuple[int, int]] = set()
        for lane in lanes:
            rip = view.get_rip(lane)
            key = (self.tenant_of(lane), rip)
            if key not in done:
                if not self.cache.has(rip, key[0]):
                    if not self._decode_at(view, lane, rip,
                                           prefetched=windows.get(lane)):
                        continue
                done.add(key)
            view.set_status(lane, StatusCode.RUNNING)

    def _gather_code_windows(self, view: HostView, lanes: List[int]):
        """--device-decode satellite: ONE device dispatch gathers the
        missing lanes' 15-byte code windows (plus fault/pfn walk facts)
        through the in-kernel page walk + overlay probe, so servicing a
        decode miss transfers k x 15 bytes instead of riding the full
        HostView page-pull path.  Lanes whose rip is already cached (or
        duplicated within the round) are skipped on host before the
        gather."""
        from wtf_tpu.interp import devdec

        want: List[int] = []
        seen: Set[Tuple[int, int]] = set()
        for lane in lanes:
            rip = view.get_rip(lane)
            key = (self.tenant_of(lane), rip)
            if key in seen or self.cache.has(rip, key[0]):
                continue
            seen.add(key)
            want.append(lane)
        if not want:
            return {}
        # pow2 bucket bounds jit re-specialization like push()'s writes
        bucket = 8
        while bucket < len(want):
            bucket *= 2
        idx = np.zeros(bucket, dtype=np.int32)
        idx[:len(want)] = want
        m = self.machine
        out = self.supervisor.dispatch(
            "device-decode", devdec.gather_windows, self.image,
            m.overlay, m.cr3, jnp.asarray(view.r["rip"]),
            jnp.asarray(idx), sync=lambda o: o[1])
        wins, faults, pfn0s, pfn14s = jax.device_get(out)
        self.stats["decode_windows_gathered"] += len(want)
        return {lane: (wins[j].tobytes(), bool(faults[j]),
                       int(pfn0s[j]), int(pfn14s[j]))
                for j, lane in enumerate(want)}

    def _service_smc(self, view: HostView, lanes: List[int]) -> None:
        for lane in lanes:
            rip = view.get_rip(lane)
            skey = (self.tenant_of(lane), rip)
            n = self._smc_updates.get(skey, 0) + 1
            self._smc_updates[skey] = n
            if n > 16:
                # cache thrash: lanes disagree about the bytes at this rip;
                # fall back to the oracle for this lane instead of ping-
                # ponging the shared entry (documented batch-vs-VM tradeoff)
                self._fallback_step(view, lane)
                continue
            try:
                window = view.virt_read(lane, rip, 15)
                pfn0 = view.translate(lane, rip) >> PAGE_SHIFT
            except HostFault:
                view.r["ctr"][lane, CTR_MEM_FAULT] += np.uint32(1)
                view.set_status(lane, StatusCode.PAGE_FAULT)
                continue
            uop = decode(window, rip)
            try:
                pfn1 = view.translate(lane, rip + max(uop.length - 1, 0)) >> PAGE_SHIFT
            except HostFault:
                pfn1 = pfn0
            self.cache.update(rip, uop, pfn0, pfn1, tenant=skey[0])
            self.stats["smc_updates"] += 1
            view.set_status(lane, StatusCode.RUNNING)

    def _fallback_step(self, view: HostView, lane: int) -> None:
        """Single-step one lane on the EmuCpu oracle (the host slow path for
        instructions outside the device subset)."""
        self.stats["fallbacks"] += 1
        # per-opclass attribution (VERDICT r5 item 3: a campaign's fallback
        # total was a single opaque number — e.g. real_pe's 1321 — with no
        # way to tell WHICH instruction classes keep diverting)
        uop = self.cache.uop_at(view.get_rip(lane), self.tenant_of(lane))
        opclass = (_OPC_NAMES.get(uop.opc, f"opc{uop.opc}")
                   if uop is not None else "undecoded")
        by_class = self.stats["fallbacks_by_opclass"]
        by_class[opclass] = by_class.get(opclass, 0) + 1
        cpu_state = _lane_cpu_state(view, lane, self.cpu0_of(lane))
        mem = _FallbackMem(view, lane)
        emu = EmuCpu(mem, cpu_state)
        icount_before = int(view.r["icount"][lane])
        emu.icount = icount_before
        emu.rdrand_state = int(view.r["rdrand"][lane])
        try:
            emu.step()
        except GuestCrash:
            view.set_status(lane, StatusCode.CRASH)
            view.r["fault_gva"][lane] = np.uint64(emu.rip & MASK64)
            return
        except MemFault as e:
            # mirror the device's CTR_MEM_FAULT accounting: a device page
            # walk would have counted this fault in-graph
            view.r["ctr"][lane, CTR_MEM_FAULT] += np.uint32(1)
            view.set_status(lane, StatusCode.PAGE_FAULT)
            view.r["fault_gva"][lane] = np.uint64(e.gva & MASK64)
            view.r["fault_write"][lane] = np.int32(1 if e.write else 0)
            return
        except DivideError:
            view.set_status(lane, StatusCode.DIVIDE_ERROR)
            return
        except UnsupportedInsn as e:
            # first word = the crash name's tail (TpuBackend.
            # _result_from_fields): the oracle's `crash-unsupported-<rip>`
            self.lane_errors[lane] = (
                f"unsupported-{e.rip:#x} instruction {e.raw.hex()}")
            view.set_status(lane, StatusCode.HARD_ERROR)
            return
        _writeback_lane(view, lane, emu)
        view.r["icount"][lane] = np.uint64(emu.icount)
        # keep CTR_INSTR == icount exactly (the differential-test anchor):
        # every oracle-retired instruction lands in the device counter block
        retired = np.uint32(emu.icount - icount_before)
        view.r["ctr"][lane, CTR_INSTR] += retired
        if mem.stored:
            view.r["ctr"][lane, CTR_STORE] += retired
        if uop is not None and uop.opc in U.VECTOR_OPCS:
            view.r["ctr"][lane, CTR_VECTOR] += retired
        view.r["rdrand"][lane] = np.uint64(emu.rdrand_state)
        view.r["bp_skip"][lane] = np.int32(0)
        if emu.cr3_event is not None \
                and emu.cr3_event != self.cpu0_of(lane).cr3:
            view.set_status(lane, StatusCode.CR3_CHANGE)
        elif self.limit and emu.icount >= self.limit:
            view.set_status(lane, StatusCode.TIMEDOUT)
        else:
            view.set_status(lane, StatusCode.RUNNING)

    # Opcode classes only the oracle executes.  The burst below may run
    # AHEAD through these; every burst-stepped rip is published to the
    # decode cache and its coverage bit is OR-ed into the device bitmap
    # at the next push (`_pending_cov`), so burst lanes report exactly
    # the coverage a per-dispatch servicing loop would have.  A device-
    # executable instruction ends the burst so its coverage/edge bits
    # land through the normal device path.
    _ORACLE_OPCS = frozenset((
        U.OPC_SSECVT, U.OPC_PCLMUL, U.OPC_STACKSTR,
    ))
    # x87 executes on-device except the state movers
    _X87_ORACLE_SUBS = frozenset((
        U.X87_FXSAVE, U.X87_FXRSTOR, U.X87_XSAVE, U.X87_XRSTOR,
    ))

    _BRANCH_OPCS = frozenset((U.OPC_JMP, U.OPC_JCC, U.OPC_CALL, U.OPC_RET))

    # statuses whose oracle step COMMITTED (rip advanced): the edge-hash
    # bit is owed even when the run stops right after the branch
    _COMMITTED_STATUSES = frozenset((
        StatusCode.RUNNING, StatusCode.TIMEDOUT, StatusCode.CR3_CHANGE))

    def _entry_at(self, view: HostView, lane: int,
                  rip: int) -> Optional[Tuple[int, "U.Uop"]]:
        """(uop-table entry index, uop) at `rip`, publishing the decode on
        a miss; None when the bytes can't be fetched or don't decode."""
        tenant = self.tenant_of(lane)
        uop = self.cache.uop_at(rip, tenant)
        if uop is None:
            try:
                window = view.virt_read(lane, rip, 15)
                pfn0 = view.translate(lane, rip) >> PAGE_SHIFT
            except HostFault:
                return None
            uop = decode(window, rip)
            if uop.opc == U.OPC_INVALID:
                return None
            try:
                pfn1 = view.translate(
                    lane, rip + max(uop.length - 1, 0)) >> PAGE_SHIFT
            except HostFault:
                pfn1 = pfn0
            self.cache.add(rip, uop, pfn0, pfn1, tenant=tenant)
        return self.cache.entry_index(rip, tenant), uop

    def _is_oracle_uop(self, uop) -> bool:
        return (uop.opc in self._ORACLE_OPCS
                or (uop.opc == U.OPC_X87
                    and uop.sub in self._X87_ORACLE_SUBS)
                # iretq retires on the device; iretd and far ret do not
                or (uop.opc == U.OPC_IRET
                    and not (uop.sub == 0 and uop.opsize == 8)))

    def _fallback_burst(self, view: HostView, lane: int) -> None:
        """Service an UNSUPPORTED lane; when the lane has needed the oracle
        for consecutive rounds, keep stepping it host-side so its progress
        per round grows instead of staying one-instruction-per-chunk.

        Two burst tiers: a short streak runs ahead through further
        oracle-class instructions only; a chronic streak (>= 4 rounds —
        e.g. a lane crunching denormal-range FP where every arith op
        diverts) runs ahead through ANY instruction.  Coverage parity is
        preserved both ways: every burst-stepped rip's coverage bit and
        every branch's edge-hash bit are recorded host-side
        (_pending_cov/_pending_edge) and OR-ed into the device bitmaps at
        the next push.  Stops at armed breakpoints (the device checks
        them pre-execution) and on any terminal/fault status."""
        self._fallback_step(view, lane)
        streak = self._fallback_streak.get(lane, 0) + 1
        self._fallback_streak[lane] = streak
        if streak < 2:
            return
        budget = min(32 << min(streak, 6), 1024)
        # The any-instruction tier is kept SHORT: it exists to carry a
        # chronic lane across the device-class glue between diverting
        # instructions (denormal FP every few ops), not to steal long
        # normal stretches from the device, which executes them faster.
        #
        # FP-reproducibility caveat: this tier runs device-class SSE/x87
        # FP on the host oracle (numpy).  On the CPU backend both engines
        # are IEEE bit-exact, but on a real TPU the device's div/sqrt
        # rounding is the platform's (the documented fast-path fidelity
        # delta, step.py SSE-FP block) — so WHERE an instruction executes
        # can change low FP bits there.  A crash found through a burst
        # therefore reproduces under `--backend=emu` (all-oracle) but a
        # TPU re-run of the same input may divert at different points.
        # The tier is off on CPU (burst_any_tier) and bounded here, so
        # the exposure is a 24-instruction window per chronic round.
        any_budget = 24 if (streak >= 4 and self.burst_any_tier) else 0
        ebits = self.machine.edge.shape[1] * 32
        from wtf_tpu.utils.hashing import mix64

        while budget > 0:
            if view.get_status(lane) != StatusCode.RUNNING:
                return
            rip = view.get_rip(lane)
            if self.cache.has_breakpoint(rip, self.tenant_of(lane)):
                return
            entry = self._entry_at(view, lane, rip)
            if entry is None:
                return
            idx, uop = entry
            if not self._is_oracle_uop(uop):
                if any_budget <= 0:
                    return
                any_budget -= 1
            self._fallback_step(view, lane)
            # the coverage/edge bits the device dispatch would have set.
            # TIMEDOUT/CR3_CHANGE are set AFTER the oracle committed the
            # step (the branch executed; only the run stops afterwards),
            # so those statuses still record the edge — the device path
            # likewise sets edge bits on a committing step that trips the
            # instruction budget (exact-parity claim in the docstring).
            self._pending_cov.append((lane, idx))
            if (uop.opc in self._BRANCH_OPCS
                    and view.get_status(lane) in self._COMMITTED_STATUSES):
                eh = mix64(rip) ^ view.get_rip(lane)
                self._pending_edge.append((lane, eh & (ebits - 1)))
            self.stats["fallback_burst_steps"] += 1
            budget -= 1

    def _service_exception(self, view: HostView, lane: int) -> bool:
        """Vector a faulted lane through the guest IDT (reference: bochs
        delivers internally, bochscpu_backend.cc:917-999; KVM injects,
        kvm_backend.cc:2019-2042).  On success the lane resumes RUNNING at
        the guest handler; an undeliverable fault (absent gate, unmapped
        IDT/TSS/kernel stack — the double-fault analog) keeps the lane's
        terminal status and the crash naming that comes with it.  Returns
        whether the exception was delivered."""
        vector = self._fault_vector(view, lane)
        ctx = _LaneCtx(view, lane, self.cpu0_of(lane))
        try:
            if vector is None:
                return False
            if vector == VEC_DE:
                deliver_exception(ctx, VEC_DE)
            else:
                gva = int(view.r["fault_gva"][lane])
                write = bool(view.r["fault_write"][lane] & 1)

                def reads(g):
                    try:
                        view.translate(lane, g, write=False)
                        return True
                    except HostFault:
                        return False

                deliver_page_fault(ctx, gva, write, reads)
        except (DeliveryFailed, HostFault) as e:
            self.lane_errors.setdefault(lane, f"undelivered exception: {e}")
            self.stats["exceptions_undelivered"] += 1
            return False
        self.stats["exceptions_delivered"] += 1
        by_vector = self.stats["exceptions_by_vector"]
        name = _VECTOR_NAMES[vector]
        by_vector[name] = by_vector.get(name, 0) + 1
        view.set_status(lane, StatusCode.RUNNING)
        return True

    @staticmethod
    def _fault_vector(view: HostView, lane: int) -> Optional[int]:
        """The vector a faulted lane's status asks for (a fault on an
        address that is not canonical is #GP: `deliver_page_fault`)."""
        status = view.get_status(lane)
        if status == StatusCode.DIVIDE_ERROR:
            return VEC_DE
        if status != StatusCode.PAGE_FAULT:
            return None
        return (VEC_PF if is_canonical(int(view.r["fault_gva"][lane]))
                else VEC_GP)

    # -- per-chunk host work (each under one span in run()) ----------------
    def _classify(self, status: np.ndarray,
                  undeliverable: Set[int]) -> dict:
        """Lanes needing host service after a chunk, by status.  Fault
        lanes whose tenant has no IDT join `undeliverable` and stay
        terminal."""
        need = {
            int(StatusCode.NEED_DECODE): [],
            int(StatusCode.SMC): [],
            int(StatusCode.UNSUPPORTED): [],
            int(StatusCode.BREAKPOINT): [],
        }
        if self.deliver_exceptions:
            need[int(StatusCode.PAGE_FAULT)] = []
            need[int(StatusCode.DIVIDE_ERROR)] = []
        for lane in np.nonzero(np.isin(status, list(need)))[0]:
            if int(lane) in undeliverable:
                continue  # delivery already failed: stays terminal
            if (int(status[lane]) in _FAULT_STATUSES
                    and not self._deliver_lane(int(lane))):
                # heterogeneous batch: this lane's tenant has no IDT
                # — its faults are terminal, exactly as they are in
                # a solo campaign of that tenant
                undeliverable.add(int(lane))
                continue
            need[int(status[lane])].append(int(lane))
        return need

    def _service_breakpoints(self, view: HostView, lanes: List[int],
                             bp_handler) -> None:
        for lane in lanes:
            self.stats["bp_dispatches"] += 1
            if bp_handler is None:
                self.lane_errors[lane] = (
                    f"breakpoint @ {view.get_rip(lane):#x} with no handler")
                view.set_status(lane, StatusCode.CRASH)
                continue
            rip_before = view.get_rip(lane)
            bp_handler(self, view, lane)
            if view.get_status(lane) == StatusCode.BREAKPOINT:
                # resume: suppress the bp for one step ONLY if the
                # handler left rip in place; a redirected rip must hit
                # any breakpoint armed at the new address (the emu
                # backend's skip_rip-clearing semantics, emu.py:66-67)
                if view.get_rip(lane) == rip_before:
                    view.r["bp_skip"][lane] = np.int32(1)
                view.set_status(lane, StatusCode.RUNNING)

    # -- run loop ----------------------------------------------------------
    def run(
        self,
        bp_handler: Optional[Callable[["Runner", HostView, int], None]] = None,
        max_chunks: int = 1 << 20,
    ) -> np.ndarray:
        """Drive the batch until every lane reaches a terminal status.

        `bp_handler(runner, view, lane)` services BREAKPOINT lanes (the
        backend layer supplies it; reference breakpoint dispatch is
        backend.h:231 + kvm_backend.cc:1256-1369).  Returns the final status
        array."""
        tab = self.device_tab()
        # jit also keys on operand shapes: a second Runner with the same
        # (size, donate, lanes) but a different physmem image or uop-table
        # capacity still pays a real XLA compile and must report it
        shape_sig = tuple(
            a.shape for a in jax.tree_util.tree_leaves(
                (tab, self.image)))
        limit = jnp.uint64(self.limit)
        self._chunk_level = 0
        self._fallback_streak = {}
        spans = self.registry.spans
        undeliverable: Set[int] = set()  # lanes whose IDT delivery failed
        for _ in range(max_chunks):
            size = (self._chunk_sizes[self._chunk_level]
                    if self.adaptive_chunks else self.chunk_steps)
            self.stats["max_chunk_steps"] = max(
                self.stats["max_chunk_steps"], size)
            run_chunk = self._chunk_callable(size)
            compile_key = (size, self._donate, self.n_lanes, shape_sig,
                           self.exec_sig)
            if compile_key not in _DISPATCHED_EXECUTORS:
                # the first dispatch of this executor shape pays the
                # XLA compile (jit compiles on call, not on
                # make_run_chunk); its wall shows up inside the next
                # device-step span.  Process-global like the jit cache
                # itself — a second Runner at the same (size, donate,
                # lanes) dispatches warm and must not re-report a
                # compile.
                _DISPATCHED_EXECUTORS.add(compile_key)
                # the image tag keeps scheduler placements with
                # different stacked-image shapes (wtf_tpu/tenancy)
                # from reading as shape-churn in telemetry_report
                self.events.emit("compile", chunk_steps=size,
                                 donate=self._donate,
                                 lanes=self.n_lanes,
                                 image="x".join(
                                     str(d) for d in
                                     self.image.frame_table.shape))
            with spans.span("device-step"):
                # two children that sum to the span.  `dispatch`: the jit
                # call (argument flattening, donation, enqueue; a first
                # call's compile); `wait`: the fence.  JAX dispatch is
                # async: without the fence the device's time leaks into
                # whichever later span synchronizes first.  Under
                # --dispatch-timeout the supervisor's bounded wait sits
                # inside `dispatch`, which then holds the device's time,
                # and `wait` nothing
                with spans.span("dispatch"):
                    self.machine = self.supervisor.dispatch(
                        "chunk", run_chunk,
                        tab, self.image, self.machine, limit,
                        steps=size, sync=lambda m: m.status)
                with spans.span("wait") as sp:
                    sp.fence(self.machine.status)
            self.stats["chunks"] += 1
            # one span per chunk: the pull and the per-lane classification
            # are host time that scales with the lanes needing service
            with spans.span("status-pull"):
                # COPY, never a zero-copy view: the machine's buffers are
                # donated into the next chunk call, and a live numpy view
                # of a donated CPU buffer reads whatever XLA reuses the
                # memory for (seen as garbage status/fpsw under
                # multi-test processes)
                status = np.array(jax.device_get(self.machine.status))
                running = status == int(StatusCode.RUNNING)
                need = self._classify(status, undeliverable)
            total = sum(len(v) for v in need.values())
            if total == 0:
                if not running.any():
                    return status
                # nothing to service, lanes still running: grow the chunk
                if (self.adaptive_chunks
                        and self._chunk_level < len(self._chunk_sizes) - 1):
                    self._chunk_level += 1
                continue
            # Chunk-size policy: broad servicing (decode misses, SMC churn,
            # breakpoint dispatch — events that gate the BATCH's forward
            # progress) drops back to fine-grained chunks; a few chronic
            # oracle-bound lanes must NOT stall everyone (VERDICT r4 item
            # 4), so UNSUPPORTED/fault-only rounds keep growing the ladder
            # and the chronic lanes ride the oracle burst instead.
            broad = bool(need[int(StatusCode.NEED_DECODE)]
                         or need[int(StatusCode.SMC)]
                         or need[int(StatusCode.BREAKPOINT)])
            if broad:
                self._chunk_level = 0
            elif (self.adaptive_chunks
                    and self._chunk_level < len(self._chunk_sizes) - 1):
                self._chunk_level += 1

            unsup_lanes = need[int(StatusCode.UNSUPPORTED)]
            self._fallback_streak = {
                lane: self._fallback_streak.get(lane, 0)
                for lane in unsup_lanes}

            with spans.span("service-pull"):
                view = self.view()
            if need[int(StatusCode.NEED_DECODE)] or need[int(StatusCode.SMC)]:
                with spans.span("service-decode"):
                    if need[int(StatusCode.NEED_DECODE)]:
                        self._service_decode(
                            view, need[int(StatusCode.NEED_DECODE)])
                    if need[int(StatusCode.SMC)]:
                        self._service_smc(view, need[int(StatusCode.SMC)])
            if unsup_lanes:
                with spans.span("oracle-fallback"):
                    for lane in unsup_lanes:
                        self._fallback_burst(view, lane)
            fault_lanes = (need.get(int(StatusCode.PAGE_FAULT), [])
                           + need.get(int(StatusCode.DIVIDE_ERROR), []))
            if fault_lanes:
                # one span around the loop, never one per lane
                # (the lanes' dirty pages come inside it: `HostView.round`)
                with spans.span("service-exception"), \
                        view.round(fault_lanes):
                    for lane in fault_lanes:
                        if not self._service_exception(view, lane):
                            undeliverable.add(lane)
            if need[int(StatusCode.BREAKPOINT)]:
                bp_lanes = need[int(StatusCode.BREAKPOINT)]
                with spans.span("service-bp"), view.round(bp_lanes):
                    self._service_breakpoints(view, bp_lanes, bp_handler)
            with spans.span("service-push"):
                self.push(view)
                tab = self.device_tab()
        # max_chunks exhausted: revoke the lanes still making (or
        # awaiting) progress as TIMEDOUT — burst semantics, their chunk
        # budget ran out — instead of aborting the whole batch.  One
        # runaway lane must not kill a campaign; TIMEDOUT lanes are
        # already excluded from the coverage merge by the backend's
        # include mask, so no partial-execution edges are credited.
        with spans.span("status-pull"):
            status = np.array(jax.device_get(self.machine.status))
            nonterminal = [int(StatusCode.RUNNING),
                           int(StatusCode.NEED_DECODE),
                           int(StatusCode.SMC), int(StatusCode.UNSUPPORTED),
                           int(StatusCode.BREAKPOINT)]
            if self.deliver_exceptions:
                # deliverable faults would have gone back to the guest too
                nonterminal += list(_FAULT_STATUSES)
            stuck = [int(lane)
                     for lane in np.nonzero(np.isin(status, nonterminal))[0]
                     if int(lane) not in undeliverable
                     and not (int(status[lane]) in _FAULT_STATUSES
                              and not self._deliver_lane(int(lane)))]
        if stuck:
            with spans.span("service-pull"):
                view = self.view()
            for lane in stuck:
                self.lane_errors.setdefault(
                    lane, f"revoked: exceeded max_chunks={max_chunks}")
                view.set_status(lane, StatusCode.TIMEDOUT)
            with spans.span("service-push"):
                self.push(view)
            self.registry.counter("runner.max_chunks_timeouts").inc(
                len(stuck))
            self.events.emit("timeout", kind="max-chunks", lanes=stuck,
                             chunks=max_chunks)
            with spans.span("status-pull"):
                status = np.array(jax.device_get(self.machine.status))
        return status

    def restore(self) -> None:
        """Every lane back to the snapshot: O(1) overlay reset + register
        broadcast (replaces the reference's dirty-page rewrite loops,
        SURVEY.md §5.4)."""
        with self.registry.spans.span("overlay-restore") as sp:
            self.machine = self._restorer()(self.machine, self.template)
            sp.fence(self.machine.status)
        self.lane_errors.clear()
        self._pending_cov.clear()
        self._pending_edge.clear()
        # per-testcase SMC thrash window: a rip legitimately rewritten many
        # times within ONE run falls back to the oracle, but the count must
        # not accumulate across the campaign (fresh-run behavior parity)
        self._smc_updates.clear()

    def _restorer(self):
        """The compiled `machine_restore` (the mesh runner's runs per
        shard)."""
        return partial(machine_restore, donate=self._donate)

    def statuses(self) -> np.ndarray:
        # copy, not a view — see the donation note in run()
        return np.array(jax.device_get(self.machine.status))

    # -- device-side telemetry counters ------------------------------------
    def device_counters(self) -> np.ndarray:
        """The per-lane counter block (uint32[L, N_CTRS], machine.CTR_*
        indices) accumulated in-graph since the last restore.  One pull;
        a copy, never a view (donation note in run())."""
        return np.array(jax.device_get(self.machine.ctr))

    def fold_counter_totals(self, totals) -> None:
        """Add one [N_CTRS] totals vector into the registry's `device.*`
        counters — shared by the per-burst fold below and the megachunk
        driver (whose in-graph restores zero the per-lane block between
        batches, so the program emits per-batch totals instead)."""
        reg = self.registry
        reg.counter("device.instructions").inc(int(totals[CTR_INSTR]))
        reg.counter("device.mem_faults").inc(int(totals[CTR_MEM_FAULT]))
        reg.counter("device.decode_misses").inc(int(totals[CTR_DECODE_MISS]))
        # lane-steps (every lane's passes through the step, live or not)
        # and sweeps = lane-steps per lane, per shard on a mesh: FULL-WIDTH
        # EQUIVALENTS, not loop iterations (PERF.md section 3);
        # tail_lane_steps are those of sweeps over fewer than all groups
        lane_steps = int(totals[CTR_SWEPT])
        reg.counter("device.lane_steps").inc(lane_steps)
        reg.counter("device.tail_lane_steps").inc(int(totals[CTR_TAIL]))
        # the translation cache (paging.translate_cached): pages sent to
        # the walk, passes that only filled the cache (in lane_steps, not
        # in instructions), passes that sent two pages to the walk
        reg.counter("device.tlb_walks").inc(int(totals[CTR_TLB_WALK]))
        reg.counter("device.tlb_fill_steps").inc(int(totals[CTR_TLB_FILL]))
        reg.counter("device.walk2_steps").inc(int(totals[CTR_WALK2]))
        # what the retired instructions did: a store, the vector datapath;
        # the passes whose store reached its window's third word
        reg.counter("device.store_steps").inc(int(totals[CTR_STORE]))
        reg.counter("device.vector_steps").inc(int(totals[CTR_VECTOR]))
        reg.counter("device.store3_steps").inc(int(totals[CTR_STORE3]))
        # every lane carries its chunks' levels that swept: per lane, as
        # sweeps are
        for key, total in (("device.sweeps", lane_steps),
                           ("device.levels_used", int(totals[CTR_LEVELS]))):
            whole, rest = divmod(total, self.n_lanes)
            reg.counter(key).inc(total / self.n_lanes if rest else whole)

    def fold_device_counters(self) -> np.ndarray:
        """Pull the counter block ONCE per burst and add the batch totals
        into the registry (`device.*` counters) — the host-side fold that
        replaces any per-step sync.  Call between run() and restore();
        returns the per-lane block for callers that want lane detail."""
        ctr = self.device_counters()
        self.fold_counter_totals(ctr.sum(axis=0, dtype=np.uint64))
        # the batch's lockstep floor: every lane is swept as long as the
        # deepest one runs.  With device.lane_steps it splits lost
        # occupancy into depth skew (floor - instructions) and sweeps
        # above the deepest lane (lane_steps - floor).  The megachunk
        # window folds totals only, so it has no floor.
        self.registry.counter("device.lockstep_floor_steps").inc(
            self.n_lanes * int(ctr[:, CTR_INSTR].max()))
        return ctr


def warm_decode_cache(runner: Runner, target, payload: bytes,
                      limit: int = 100_000) -> int:
    """Populate the runner's uop table by running `payload` once on the
    host EmuCpu oracle and decoding every reached rip — pure host work, no
    device compile (used by entry points that must budget XLA compiles).
    Returns the number of rips decoded."""
    from wtf_tpu.backend.emu import EmuBackend

    eb = EmuBackend(runner.snapshot, limit=limit)
    eb.initialize()
    target.init(eb)
    target.insert_testcase(eb, payload)
    eb.run()
    view = runner.view()
    n = 0
    for rip in sorted(eb.last_new_coverage()):
        if not runner.cache.has(rip):
            runner._decode_at(view, 0, rip)
            n += 1
    return n
