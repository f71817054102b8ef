"""`iretq` on the device (interp/step.py: two passes of the lane, nothing
written before the second) against the EmuCpu oracle, and exception
delivery (cpu/interrupts.py, which the device path and the oracle share)
against interrupt frames built by hand from the SDM."""

import struct

import numpy as np
import pytest

from tests.emurunner import CODE_BASE, DATA_BASE, STACK_TOP, build_guest
from wtf_tpu.core.results import StatusCode
from wtf_tpu.cpu import interrupts
from wtf_tpu.cpu import uops as U
from wtf_tpu.cpu.emu import EmuCpu, EmuMem, GuestCrash, MemFault
from wtf_tpu.interp.runner import Runner
from wtf_tpu.snapshot.loader import Snapshot

# `iretq` is two bytes; what it returns to, in the tests' own code page
ASM = "iretq\nmov ebx, 0x1234\nhlt"
TARGET = CODE_BASE + 2
FRAME_AT = STACK_TOP - 0x400
NEW_RSP = STACK_TOP - 0x8F1             # not aligned: iretq takes any
GUARD = DATA_BASE + 0x1000              # the first byte behind the data page
SPARE = DATA_BASE + 0x4000              # a page for the guest to map there
NONCANONICAL = 0x0000_8000_0000_0000

FRAMES = {
    # name: (rip, cs, rflags, rsp, ss)
    "to-cpl0": (TARGET, 0x10, 0x246, NEW_RSP, 0x18),
    "to-cpl3": (TARGET, 0x33, 0x202, NEW_RSP, 0x2B),
    "rflags-all-ones": (TARGET, 0x10, (1 << 64) - 1, NEW_RSP, 0x18),
    "rflags-zero": (TARGET, 0x33, 0, NEW_RSP, 0x2B),
    "rflags-rf-vm-and-above": (TARGET, 0x10, 0x30000 | 0x5 << 40 | 0x46,
                               NEW_RSP, 0x18),
    "selectors-wider-than-16-bits": (TARGET, 0xABCD_0033, 0x202, NEW_RSP,
                                     0x1234_5678_002B),
    "rsp-anywhere": (TARGET, 0x33, 0x202, 0xDEAD_BEEF_0000_0001, 0x2B),
    "rip-not-canonical": (NONCANONICAL, 0x33, 0x202, NEW_RSP, 0x2B),
}


def guest(frame, at=FRAME_AT, mapped=40):
    """A guest about to execute `iretq` with the first `mapped` bytes of
    `frame` at `at`, and a spare data page."""
    data = {DATA_BASE: b"\0" * 8, SPARE: b"\0" * 8,
            at: struct.pack("<5Q", *frame)[:mapped]}
    physmem, cpu, _ = build_guest(ASM, data)
    cpu.rsp = at
    cpu.rbx = 0x77
    return physmem, cpu


def oracle(frame, **kw):
    physmem, cpu = guest(frame, **kw)
    emu = EmuCpu(EmuMem(physmem), cpu)
    return emu


def run_oracle(emu):
    """Steps until the snippet's hlt or a fault escapes; returns it."""
    try:
        for _ in range(16):
            emu.step()
    except (GuestCrash, MemFault) as e:
        return e
    raise AssertionError("the snippet did not end")


def device(frame, n_lanes=2, **kw):
    physmem, cpu = guest(frame, **kw)
    runner = Runner(Snapshot(physmem=physmem, cpu=cpu), n_lanes=n_lanes,
                    chunk_steps=64)
    return runner


def state(runner, lane=0):
    m = runner.machine
    return {"rip": int(m.rip[lane]), "rsp": int(m.gpr[lane, 4]),
            "rbx": int(m.gpr[lane, 3]), "rflags": int(m.rflags[lane]),
            "cs": int(m.cs[lane]), "ss": int(m.ss[lane]),
            "icount": int(m.icount[lane])}


def oracle_state(emu):
    return {"rip": emu.rip, "rsp": emu.gpr[4], "rbx": emu.gpr[3],
            "rflags": emu.rflags, "cs": emu.cs_sel, "ss": emu.ss_sel,
            "icount": emu.icount}


@pytest.mark.parametrize("name", list(FRAMES))
def test_iretq_retires_on_the_device_as_on_the_oracle(name):
    frame = FRAMES[name]
    emu = oracle(frame)
    ended = run_oracle(emu)
    runner = device(frame)
    status = runner.run()
    want = oracle_state(emu)
    for lane in range(2):
        assert state(runner, lane) == want, name
    assert runner.stats["fallbacks"] == 0
    assert int(np.asarray(runner.machine.fault_write).sum()) == 0
    rip, cs, rflags, rsp, ss = frame
    assert want["cs"] == cs & 0xFFFF and want["ss"] == ss & 0xFFFF
    assert want["rflags"] == (rflags | 2) & U.RF_WRITABLE
    if name == "rip-not-canonical":
        # the oracle does not check the frame's rip at the iretq: the
        # instruction retires and the next fetch faults, on both
        assert isinstance(ended, MemFault) and ended.gva == NONCANONICAL
        assert want["rip"] == NONCANONICAL and want["icount"] == 1
        assert want["rsp"] == rsp
        assert StatusCode(int(status[0])) == StatusCode.PAGE_FAULT
        assert int(runner.machine.fault_gva[0]) == NONCANONICAL
    else:
        assert isinstance(ended, GuestCrash)             # the hlt
        assert want["rbx"] == 0x1234 and want["icount"] == 2
        assert want["rsp"] == rsp
        assert StatusCode(int(status[0])) == StatusCode.CRASH


def test_iretq_takes_two_passes_and_writes_nothing_in_the_first():
    runner = device(FRAMES["to-cpl3"])
    before = state(runner)
    runner.chunk_steps, runner.adaptive_chunks = 1, False
    # one sweep a chunk: the decode miss, then the first pass; the lanes
    # are still RUNNING when the chunks run out (and revoked for it)
    runner.run(max_chunks=2)
    assert state(runner) == before
    from wtf_tpu.interp.machine import IRET_LATCH_SHIFT
    assert [int(x) for x in np.asarray(runner.machine.fault_write)] \
        == [(0x10000 | 0x2B) << IRET_LATCH_SHIFT] * 2
    ctr = runner.device_counters()
    from wtf_tpu.interp.machine import CTR_INSTR, CTR_SWEPT
    assert ctr[:, CTR_INSTR].tolist() == [0, 0]
    assert ctr[:, CTR_SWEPT].tolist() == [2, 2]


@pytest.mark.parametrize("mapped", [0, 8, 16, 24, 32, 39])
def test_a_frame_that_straddles_into_an_absent_page(mapped):
    """A #PF on any of the five reads leaves the lane as it was before
    the instruction, the fault where the oracle has it; once the page is
    there the instruction starts again and retires."""
    frame = FRAMES["to-cpl3"]
    at = GUARD - mapped
    emu = oracle(frame, at=at, mapped=mapped)
    before = oracle_state(emu)
    ended = run_oracle(emu)
    assert isinstance(ended, MemFault) and not ended.write
    assert oracle_state(emu) == before

    runner = device(frame, at=at, mapped=mapped)
    status = runner.run()
    for lane in range(2):
        assert StatusCode(int(status[lane])) == StatusCode.PAGE_FAULT
        assert state(runner, lane) == before
        assert int(runner.machine.fault_gva[lane]) == ended.gva
        assert int(runner.machine.fault_write[lane]) == 0
    # a frame that starts in the absent page faults at its first byte,
    # any other at the page's first
    assert ended.gva == (at if mapped == 0 else GUARD)
    assert int(np.asarray(runner.machine.fault_write).sum()) == 0
    assert runner.stats["fallbacks"] == 0

    # what a guest's #PF handler would do: map the page (the spare data
    # frame), with the rest of the frame in it; then resume
    view = runner.view()
    spare = view.translate(0, SPARE) & ~0xFFF
    rest = struct.pack("<5Q", *frame)[mapped:]
    for lane in range(2):
        pte_at = _pte_gpa(lambda gpa: view.phys_read(lane, gpa, 8),
                          int(view.r["cr3"][lane]), GUARD)
        view.phys_write(lane, pte_at, struct.pack("<Q", spare | 7))
        view.phys_write(lane, spare, rest)
        view.set_status(lane, StatusCode.RUNNING)
    runner.push(view)
    status = runner.run()

    pte_at = _pte_gpa(lambda gpa: emu.mem.phys_read(gpa, 8), emu.cr3, GUARD)
    emu.mem.phys_write(pte_at, struct.pack("<Q", spare | 7))
    emu.mem.phys_write(spare, rest)
    assert isinstance(run_oracle(emu), GuestCrash)
    want = oracle_state(emu)
    assert want["rbx"] == 0x1234 and want["cs"] == 0x33
    for lane in range(2):
        assert StatusCode(int(status[lane])) == StatusCode.CRASH
        assert state(runner, lane) == want
    assert runner.stats["fallbacks"] == 0


def _pte_gpa(read, cr3, gva):
    """Where the PTE of `gva` lies (its page table exists: the data
    page is its neighbour)."""
    table = cr3 & ~0xFFF
    for shift in (39, 30, 21):
        entry = int.from_bytes(read(table + ((gva >> shift) & 0x1FF) * 8),
                               "little")
        assert entry & 1
        table = entry & 0x000F_FFFF_FFFF_F000
    return table + ((gva >> 12) & 0x1FF) * 8


@pytest.mark.parametrize("asm,opc", [("iretd", "iret"), ("retfq", "iret")])
def test_iretd_and_far_ret_stay_on_the_oracle(asm, opc):
    frame = (TARGET, 0x33, 0x202, NEW_RSP, 0x2B)
    data = {FRAME_AT: struct.pack("<5Q", *frame)}
    physmem, cpu, _ = build_guest(f"{asm}\nmov ebx, 0x1234\nhlt", data)
    cpu.rsp = FRAME_AT
    runner = Runner(Snapshot(physmem=physmem, cpu=cpu), n_lanes=2,
                    chunk_steps=64)
    runner.run()
    assert runner.stats["fallbacks"] >= 1 or runner.lane_errors
    assert dict(runner.stats["fallbacks_by_opclass"]).get(opc, 0) >= 1 \
        or runner.lane_errors


# -- delivery against the SDM, not against itself --------------------------

class _Flat:
    """The ctx of cpu/interrupts.py over a flat dict of bytes."""

    def __init__(self, mem, **regs):
        self.mem = mem
        self.cr2 = None
        self.idt_base, self.idt_limit, self.tss_base = 0x1000, 0xFFF, 0x3000
        for name, value in regs.items():
            setattr(self, name, value)

    def read_virt(self, gva, n):
        return bytes(self.mem.get(gva + i, 0) for i in range(n))

    def read_u64(self, gva):
        return int.from_bytes(self.read_virt(gva, 8), "little")

    def write_u64(self, gva, value):
        for i, b in enumerate(value.to_bytes(8, "little")):
            self.mem[gva + i] = b

    def set_cr2(self, value):
        self.cr2 = value


HANDLER = 0xFFFF_F800_1234_5678
RSP0 = 0xFFFF_F800_0000_8FF8            # not 16-aligned: the CPU aligns it
IST3 = 0xFFFF_F800_0000_AFFC


def _tables(vector, gate_type=0xE, ist=0, selector=0x10):
    """An IDT gate and a TSS, byte by byte from SDM Vol 3A figures 6-8
    (64-bit IDT gate descriptor) and 8-11 (64-bit TSS)."""
    mem = {}
    gate = bytearray(16)
    gate[0:2] = (HANDLER & 0xFFFF).to_bytes(2, "little")        # offset 15:0
    gate[2:4] = selector.to_bytes(2, "little")                  # selector
    gate[4] = ist                                               # IST, bits 2:0
    gate[5] = 0x80 | gate_type                                  # P, DPL 0, type
    gate[6:8] = ((HANDLER >> 16) & 0xFFFF).to_bytes(2, "little")
    gate[8:12] = (HANDLER >> 32).to_bytes(4, "little")          # offset 63:32
    for i, b in enumerate(gate):
        mem[0x1000 + vector * 16 + i] = b
    for i, b in enumerate(RSP0.to_bytes(8, "little")):          # TSS + 4
        mem[0x3000 + 4 + i] = b
    for i, b in enumerate(IST3.to_bytes(8, "little")):          # IST3: + 0x34
        mem[0x3000 + 0x34 + i] = b
    return mem


def _frame_by_hand(top, ss, rsp, rflags, cs, rip, error=None):
    """SDM Vol 3A 6.14.2 / figure 6-9: from `top` (already aligned) down:
    SS, RSP, RFLAGS, CS, RIP and the error code where the vector has one;
    each slot eight bytes."""
    slots = [ss, rsp, rflags, cs, rip] + ([] if error is None else [error])
    mem = {}
    at = top
    for value in slots:
        at -= 8
        for i, b in enumerate((value & (1 << 64) - 1).to_bytes(8, "little")):
            mem[at + i] = b
    return at, mem


DELIVERIES = {
    # name: (vector, error code or None, gate type, ist, from cs/ss, from
    #        rsp, where the frame's top has to be, ss afterwards)
    "cpl3-to-0-through-rsp0-with-error-code":
        (14, 0x6, 0xE, 0, (0x33, 0x2B), 0x7FFF_0123, RSP0 & ~0xF, 0),
    "cpl3-to-0-through-rsp0-without-error-code":
        (0, None, 0xE, 0, (0x33, 0x2B), 0x7FFF_0123, RSP0 & ~0xF, 0),
    "cpl0-to-0-on-the-current-stack-with-error-code":
        (13, 0, 0xE, 0, (0x10, 0x18), 0xFFFF_F800_0000_7F38,
         0xFFFF_F800_0000_7F30, 0x18),
    "cpl0-to-0-on-the-current-stack-without-error-code":
        (0, None, 0xE, 0, (0x10, 0x18), 0xFFFF_F800_0000_7F3C,
         0xFFFF_F800_0000_7F30, 0x18),
    "trap-gate-leaves-if":
        (3, None, 0xF, 0, (0x10, 0x18), 0xFFFF_F800_0000_7F40,
         0xFFFF_F800_0000_7F40, 0x18),
    "ist-wins-over-the-current-stack":
        (14, 0x2, 0xE, 3, (0x10, 0x18), 0xFFFF_F800_0000_7F40,
         IST3 & ~0xF, 0x18),
}


@pytest.mark.parametrize("name", list(DELIVERIES))
def test_deliver_exception_builds_the_frame_the_sdm_draws(name):
    vector, error, gate_type, ist, (cs, ss), rsp, top, ss_after = \
        DELIVERIES[name]
    rip, rflags = 0x1400_0123, 0x10346        # TF IF RF set, and ZF PF
    mem = _tables(vector, gate_type, ist)
    tables = dict(mem)
    ctx = _Flat(mem, rip=rip, rsp=rsp, rflags=rflags, cs_sel=cs, ss_sel=ss)
    interrupts.deliver_exception(
        ctx, vector, 0 if error is None else error,
        cr2=0xDEAD_B000 if vector == 14 else None)

    new_rsp, frame = _frame_by_hand(top, ss, rsp, rflags | 2, cs, rip, error)
    written = {a: b for a, b in mem.items() if a not in tables}
    assert written == frame
    # six slots from an aligned top leave rsp aligned, five leave it at 8
    assert ctx.rsp == new_rsp and ctx.rsp % 16 == (0 if error is not None
                                                   else 8)
    assert ctx.rip == HANDLER and ctx.cs_sel == 0x10
    assert ctx.ss_sel == ss_after           # NULL on a change of privilege
    # an interrupt gate clears IF, a trap gate does not; TF, NT and RF go
    cleared = 0x100 | 0x4000 | 0x10000 | (0x200 if gate_type == 0xE else 0)
    assert ctx.rflags == (rflags & ~cleared) | 2
    assert ctx.cr2 == (0xDEAD_B000 if vector == 14 else None)
    assert interrupts.has_error_code(vector) == (error is not None)


def test_a_gate_that_is_not_there_is_not_delivered():
    mem = _tables(14)
    mem[0x1000 + 14 * 16 + 5] = 0x0E         # P clear
    ctx = _Flat(mem, rip=1, rsp=0x7000, rflags=2, cs_sel=0x10, ss_sel=0x18)
    with pytest.raises(interrupts.DeliveryFailed):
        interrupts.deliver_exception(ctx, 14, 0)
    ctx.idt_limit = 14 * 16 + 14
    with pytest.raises(interrupts.DeliveryFailed):
        interrupts.deliver_exception(ctx, 14, 0)


@pytest.mark.parametrize("gva,vector", [
    (0x7FFF_FFFF_F000, 14), (0xFFFF_8000_0000_0000, 14),
    (0x0000_8000_0000_0000, 13), (0xFFFF_7FFF_FFFF_FFFF, 13)])
def test_a_fault_is_routed_by_whether_its_address_is_canonical(gva, vector):
    mem = {**_tables(13), **_tables(14)}
    ctx = _Flat(mem, rip=1, rsp=0xFFFF_F800_0000_7F40, rflags=0x202,
                cs_sel=0x10, ss_sel=0x18)
    interrupts.deliver_page_fault(ctx, gva, True, lambda g: False)
    assert interrupts.is_canonical(gva) == (vector == 14)
    error = ctx.read_u64(ctx.rsp)
    assert error == (2 if vector == 14 else 0)   # W, not P, not U / #GP(0)
    assert ctx.cr2 == (gva if vector == 14 else None)
