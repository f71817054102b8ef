"""The vector unit (`interp/step.py`: the XMM file, the SSE byte class, the
SSE-FP shuffles, the x87 stack) reads and writes its files by selects over
their rows and its byte vectors by selects over their sixteen bytes: no
gather, no scatter (PERF.md section 6, PR 41).  Everything here is a
differential against the EmuCpu oracle: every sub-operation of the byte
class, every XMM register and every x87 slot as source and destination.
"""

import numpy as np
import pytest

from tests.emurunner import DATA_BASE

ZMM0 = [[(0x0101010101010101 * (reg + 1) + limb) & ((1 << 64) - 1)
         for limb in range(4)] + [0] * 4 for reg in range(32)]

_BYTES = bytes((37 * i + 11) & 0xFF for i in range(16)) \
    + bytes((37 * i + 11) & 0xFF if i % 3 else (91 * i) & 0xFF
            for i in range(16))        # two rows that agree in two bytes of three
BYTE_CLASS = [
    ("pcmpeqb", "pcmpeqb xmm0, xmm1"),
    ("pcmpeqw", "pcmpeqw xmm0, xmm1"),
    ("pcmpeqd", "pcmpeqd xmm0, xmm1"),
    ("pcmpeqd-self", "pcmpeqd xmm1, xmm1\n pcmpeqw xmm0, xmm0"),
    ("paddb-psubb", "paddb xmm0, xmm1\n psubb xmm1, xmm0"),
    ("pminub", "pminub xmm0, xmm1"),
    ("pand-pandn-por", "pand xmm2, xmm1\n pandn xmm0, xmm1\n por xmm0, xmm2"),
    ("xorps", "xorps xmm0, xmm1"),
    ("punpcklqdq", "punpcklqdq xmm0, xmm1"),
    ("pshufd-1b", "pshufd xmm2, xmm1, 0x1b\n pshufd xmm0, xmm0, 0x4e"),
    ("pshufd-mem", "pshufd xmm2, [rbx+16], 0xe7"),
    ("pslldq", "pslldq xmm0, 3\n pslldq xmm1, 8\n pslldq xmm2, 15"),
    ("psrldq", "psrldq xmm0, 5\n psrldq xmm1, 9\n psrldq xmm2, 0"),
    ("pslldq-out", "pslldq xmm0, 16\n psrldq xmm1, 200"),
    ("pmovmskb", "pmovmskb eax, xmm0\n pmovmskb ecx, xmm1"),
    ("ptest", "ptest xmm0, xmm1\n setz al\n setc cl\n ptest xmm0, xmm0"),
]


@pytest.mark.parametrize("name,body", BYTE_CLASS,
                         ids=[c[0] for c in BYTE_CLASS])
def test_byte_class(name, body):
    from tests.test_step import assert_matches_oracle

    asm = f"""
        mov rbx, {DATA_BASE}
        movdqu xmm0, [rbx]
        movdqu xmm1, [rbx+16]
        movdqa xmm2, xmm0
        {body}
        movdqu [rbx+32], xmm0
        movdqu [rbx+48], xmm2
        hlt"""
    assert_matches_oracle(asm, data={DATA_BASE: _BYTES + b"\x00" * 0x100})


# ---------------------------------------------------------------------------
# the vector file row by row: every XMM register and every x87 slot read
# and written through the unit, device against oracle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("reg", range(16))
def test_every_xmm_register_reads_and_writes(reg):
    """xmm<reg> as a destination (a load, a merge of its low half, the
    byte class), as a source, and as a store's source; its neighbours
    keep what they held (`assert_matches_oracle` compares all sixteen,
    upper halves too)."""
    from tests.test_step import assert_matches_oracle

    other = (reg + 5) % 16
    asm = f"""
        mov rbx, {DATA_BASE}
        movdqu xmm{reg}, [rbx]
        movdqu xmm{other}, [rbx+16]
        paddb xmm{reg}, xmm{other}
        movsd xmm{other}, xmm{reg}
        pshufd xmm{reg}, xmm{other}, 0x93
        movdqu [rbx+32], xmm{reg}
        movq rax, xmm{reg}
        hlt"""
    assert_matches_oracle(asm, data={DATA_BASE: _BYTES + b"\x00" * 0x100},
                          regs={"zmm": [list(r) for r in ZMM0]})


@pytest.mark.parametrize("depth", range(8))
def test_every_x87_slot_reads_and_writes(depth):
    """`depth` + 1 pushes walk TOP down the physical stack, then st(0)
    and st(depth) are read (`fadd`), swapped (`fxch`) and written
    (`fst`): every slot is reached as st(0) and as st(i)."""
    from tests.test_step import assert_matches_oracle

    pushes = "\n".join(f"fld qword ptr [rbx+{8 * i}]" for i in range(depth + 1))
    asm = f"""
        mov rbx, {DATA_BASE}
        {pushes}
        fadd st, st({depth})
        fxch st({depth})
        fst st({depth // 2})
        fstp qword ptr [rbx+0x80]
        hlt"""
    data = b"".join(np.float64(1.5 * i - 3.25).tobytes() for i in range(8))
    assert_matches_oracle(asm, data={DATA_BASE: data + b"\x00" * 0x100})


@pytest.mark.parametrize("imm", [0x00, 0x1b, 0xe4, 0x72])
def test_shufps_picks_by_immediate(imm):
    from tests.test_step import assert_matches_oracle

    asm = f"""
        mov rbx, {DATA_BASE}
        movups xmm0, [rbx]
        movups xmm1, [rbx+16]
        shufps xmm0, xmm1, {imm}
        shufpd xmm1, xmm0, {imm & 3}
        hlt"""
    assert_matches_oracle(asm, data={DATA_BASE: _BYTES + b"\x00" * 0x100})


# ---------------------------------------------------------------------------
# a vector store's third word (PR 44): a store commits two words a pass,
# and the third word of a 16-byte store to an address that is not
# 8-aligned goes through the sweep's one conditional (`store-spill`).
# Lanes of one group store to addresses of their own (rbx by lane), each
# against an oracle run of its own, memory compared byte for byte.
# ---------------------------------------------------------------------------

_RBX = 3                                  # GPR_NAMES order
_PAGE = 0x1000
_STORE_DATA = bytes((29 * i + 7) & 0xFF for i in range(2 * _PAGE))


def _store_by_lane(insn, addresses, data):
    """Every lane runs `<insn> [rbx], xmm0` with its own rbx -> (runner,
    statuses, the oracle of each lane, or the MemFault it raised)."""
    from tests.emurunner import run_emu
    from tests.test_step import make_runner
    from wtf_tpu.cpu.emu import MemFault

    asm = f"""
        movdqu xmm0, [rip + value]
        {insn} [rbx], xmm0
        hlt
        value: .quad 0x1716151413121110, 0x2f2e2d2c2b2a2928"""
    runner = make_runner(asm, data=data, n_lanes=len(addresses))
    view = runner.view()
    for lane, rbx in enumerate(addresses):
        view.set_reg(lane, _RBX, rbx)
    runner.push(view)
    status = runner.run()
    emus = []
    for rbx in addresses:
        try:
            emus.append(run_emu(asm, data=data, regs={"rbx": rbx}))
        except MemFault as fault:
            emus.append(fault)
    return runner, status, emus


def _assert_lanes_match(runner, status, emus):
    from wtf_tpu.core.results import StatusCode

    view = runner.view()
    for lane, emu in enumerate(emus):
        assert StatusCode(int(status[lane])) == StatusCode.CRASH, lane
        assert int(np.asarray(runner.machine.rip)[lane]) == emu.rip
        dirty = emu.mem.dirty_pfns()
        assert dirty, "the store dirtied a page"
        for pfn in dirty:
            got, want = view.page(lane, pfn), bytes(emu.mem.overlay[pfn])
            assert got == want, (lane, hex(pfn), next(
                i for i in range(_PAGE) if got[i] != want[i]))
        assert sum(view.page_dirty(lane, pfn) for pfn in range(
            min(dirty) - 1, max(dirty) + 2)) == len(dirty), lane


def _store3(runner):
    from wtf_tpu.interp.machine import CTR_STORE3

    return runner.device_counters()[:, CTR_STORE3].tolist()


@pytest.mark.parametrize("insn", ["movdqu", "movups"])
@pytest.mark.parametrize("offset", range(1, 16))
def test_unaligned_vector_store_one_lane_of_the_group(insn, offset):
    """Lane 0 stores sixteen bytes at `offset` past a 16-byte boundary,
    lane 1 at an aligned address: only lane 0's store can reach a third
    word (every offset but 8), so the group takes the conditional's taken
    side for one spilling lane and one that rewrites what was there; at
    offset 8 no lane spills and it takes the other side.  `CTR_STORE3`
    counts exactly the spilling passes."""
    runner, status, emus = _store_by_lane(
        insn, [DATA_BASE + 0x40 + offset, DATA_BASE + 0x200],
        {DATA_BASE: _STORE_DATA})
    _assert_lanes_match(runner, status, emus)
    assert _store3(runner) == [int(offset != 8), 0]


@pytest.mark.parametrize("offset", [1, 7, 8, 9, 15])
def test_unaligned_vector_store_across_a_page(offset):
    """The same over the end of a page: the third word lies in the second
    page's row (claimed by this store) for offsets past 8, in the first
    page's for those before."""
    runner, status, emus = _store_by_lane(
        "movdqu", [DATA_BASE + _PAGE - 16 + offset, DATA_BASE + 0x200],
        {DATA_BASE: _STORE_DATA})
    _assert_lanes_match(runner, status, emus)
    assert _store3(runner) == [int(offset != 8), 0]
    assert len(emus[0].mem.dirty_pfns()) == 2


def test_no_lane_of_the_group_spills():
    """Two aligned vector stores and the pushes of a call: the
    conditional's pass-through side alone, `CTR_STORE3` 0."""
    runner, status, emus = _store_by_lane(
        "movdqa", [DATA_BASE + 0x40, DATA_BASE + _PAGE + 0x10],
        {DATA_BASE: _STORE_DATA})
    _assert_lanes_match(runner, status, emus)
    assert _store3(runner) == [0, 0]


def test_a_store_that_spills_and_faults_writes_nothing():
    """A spilling store whose second page is not mapped faults as a whole
    (the fault check is on the span, in the same pass): neither its two
    words nor its third are written, no row is claimed, the pass counts
    no spill; the lane beside it, whose store spills inside a mapped
    page, retires."""
    from wtf_tpu.core.results import StatusCode
    from wtf_tpu.cpu.emu import MemFault

    at = DATA_BASE + _PAGE - 7             # nine bytes into the hole
    runner, status, emus = _store_by_lane(
        "movdqu", [at, DATA_BASE + 0x41], {DATA_BASE: _STORE_DATA[:_PAGE]})
    assert isinstance(emus[0], MemFault)
    assert StatusCode(int(status[0])) == StatusCode.PAGE_FAULT
    assert int(np.asarray(runner.machine.fault_gva)[0]) == DATA_BASE + _PAGE
    assert int(np.asarray(runner.machine.overlay.count)[0]) == 0
    view = runner.view()
    assert view.virt_read(0, at - 9, 16) == _STORE_DATA[_PAGE - 16:_PAGE]
    assert StatusCode(int(status[1])) == StatusCode.CRASH
    assert view.virt_read(1, DATA_BASE + 0x40, 0x20) \
        == emus[1].virt_read(DATA_BASE + 0x40, 0x20)
    assert _store3(runner) == [0, 1]


# ---------------------------------------------------------------------------
# the set-up's guard: what a chunk program's first dispatch traces
# (PERF.md section 6, PR 41: PR 40 was refused for 29 s of tracing)
# ---------------------------------------------------------------------------

def test_the_chunk_program_lowers_the_same_twice():
    """Two fresh traces of the narrowing chunk program (levels, sweeps,
    groups, ONE `_sweep`) lower to the same text, so the compile cache's
    key does not wander between processes (that the step stands in the
    program once is tests/test_analysis.py's).  Nothing here pins the
    program's conditionals: PR 41's c41a traced PR 40's restructured step
    as slowly with no conditional in it as with one, so a later
    `lax.cond` is to be judged by a warm pair on the chip (RUNBOOK.md,
    "Pairing set-ups") and not by its count."""
    from wtf_tpu.analysis.rules import BUDGET_CONFIG, TAIL_WIDTH
    from wtf_tpu.analysis.trace import (
        build_tlv_runner, step_executor_lowering)

    runner = build_tlv_runner(BUDGET_CONFIG["n_lanes"],
                              BUDGET_CONFIG["chunk_steps"])
    texts = [step_executor_lowering(
        runner, n_steps=BUDGET_CONFIG["n_steps"],
        donate=BUDGET_CONFIG["donate"], tail=TAIL_WIDTH).as_text()
        for _ in range(2)]
    assert texts[0] == texts[1]
