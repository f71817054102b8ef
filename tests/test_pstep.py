"""Interpret-mode differential tests for the fused Pallas step kernel
(wtf_tpu/interp/pstep.py) and its park-and-resume ladder.

The fused fast path must be INVISIBLE except for speed: every test here
runs the same guest through the XLA-only ladder and the fused ladder
(`fused_step="on"`, kernel under pallas interpret mode on the CPU
platform) and requires bit-exact agreement on the complete machine state —
registers, rflags, rip, icount, statuses, coverage and edge bitmaps, and
dirty memory — plus oracle agreement where the EmuCpu reference applies.
The randomized grids sweep every hot-subset opclass; the seam tests pin
that a lane parked mid-chunk resumes on the XLA path with identical final
state, and that occupancy accounting (CTR_FUSED) is exact.
"""

import random

import numpy as np
import pytest

from tests.emurunner import DATA_BASE, build_guest, run_emu
from wtf_tpu.core.results import StatusCode
from wtf_tpu.interp.machine import (
    CTR_FUSED, CTR_INSTR, CTR_PARK_MEM, CTR_PARK_SUBSET, CTR_SWEPT,
)
from wtf_tpu.interp.runner import Runner
from wtf_tpu.snapshot.loader import Snapshot

RF_CMP = 0x8D5 | 0x400  # same modeled-flags mask as tests/test_step.py

STATE_FIELDS = ("gpr", "rip", "rflags", "icount", "cov", "edge",
                "bp_skip", "ctr")


def _make_runner(asm, data=None, regs=None, n_lanes=2, limit=0, **kw):
    physmem, cpustate, _ = build_guest(asm, data)
    if regs:
        for name, value in regs.items():
            setattr(cpustate, name, value)
    snap = Snapshot(physmem=physmem, cpu=cpustate)
    runner = Runner(snap, n_lanes=n_lanes, chunk_steps=64, **kw)
    runner.limit = limit
    return runner


def _run_pair(asm, data=None, regs=None, n_lanes=2, limit=0, **kw):
    """The same guest through the XLA-only and the fused ladder."""
    out = []
    for mode in ("off", "on"):
        r = _make_runner(asm, data, regs, n_lanes, limit,
                         fused_step=mode, **kw)
        status = r.run()
        out.append((r, status))
    return out


def _assert_ladders_equal(r0, s0, r1, s1, check_mem=False):
    assert np.array_equal(s0, s1), (
        [StatusCode(int(x)).name for x in s0],
        [StatusCode(int(x)).name for x in s1])
    for field in STATE_FIELDS:
        a = np.asarray(getattr(r0.machine, field))
        b = np.asarray(getattr(r1.machine, field))
        if field == "ctr":
            # the fused-only counters (kernel occupancy + park split)
            # legitimately differ (that's the point), and so do the
            # lane-steps (the kernel passes every lane k times a
            # dispatch); every other device counter must agree exactly
            fused_only = [CTR_FUSED, CTR_PARK_SUBSET, CTR_PARK_MEM,
                          CTR_SWEPT]
            a = np.delete(a, fused_only, axis=1)
            b = np.delete(b, fused_only, axis=1)
        assert np.array_equal(a, b), f"{field} diverged under fused ladder"
    if check_mem:
        v0, v1 = r0.view(), r1.view()
        pfns = {int(p) for lane in range(r0.n_lanes)
                for p in np.asarray(r0.machine.overlay.pfn)[lane] if p >= 0}
        for lane in range(r0.n_lanes):
            for pfn in pfns:
                assert v0.page(lane, pfn) == v1.page(lane, pfn), (
                    f"lane {lane} page {pfn:#x}")


def _occupancy(runner):
    ctr = np.asarray(runner.machine.ctr)
    instr = int(ctr[:, CTR_INSTR].sum(dtype=np.uint64))
    fused = int(ctr[:, CTR_FUSED].sum(dtype=np.uint64))
    return fused, instr


# ---------------------------------------------------------------------------
# randomized grids over the hot-subset opclasses
# ---------------------------------------------------------------------------

_R64 = ["rax", "rbx", "rcx", "rdx", "rsi", "rdi", "r8", "r9", "r10", "r11"]
_R32 = ["eax", "ebx", "ecx", "edx", "esi", "edi", "r8d", "r9d", "r10d",
        "r11d"]
_R16 = ["ax", "bx", "cx", "dx", "si", "di", "r8w", "r9w", "r10w", "r11w"]
_R8_LEGACY = ["al", "bl", "cl", "dl", "ah", "bh", "ch", "dh"]
_R8_REX = ["sil", "dil", "r8b", "r9b", "r10b", "r11b"]
_ALU = ["add", "adc", "sub", "sbb", "and", "or", "xor", "cmp", "test"]
_UNARY = ["inc", "dec", "neg", "not"]
_CC = ["o", "no", "b", "ae", "e", "ne", "be", "a", "s", "ns", "p", "np",
       "l", "ge", "le", "g"]


def _gen_hot_program(rng: random.Random, n: int = 40) -> str:
    """A random straight-line-plus-forward-branches program made entirely
    of hot-subset instructions (MOV/MOVZX/MOVSX, ALU, UNARY, LEA, SETcc,
    CMOVcc, Jcc taken and not taken, JMP, jrcxz, NOP), ending in int3."""
    lines = []

    def regpair(width):
        if width == 8:
            fam = rng.choice((_R8_LEGACY, _R8_REX))
            return rng.choice(fam), rng.choice(fam)
        pool = {64: _R64, 32: _R32, 16: _R16}[width]
        return rng.choice(pool), rng.choice(pool)

    for _ in range(n):
        kind = rng.randrange(10)
        width = rng.choice((64, 32, 16, 8))
        ra, rb = regpair(width)
        if kind == 0:
            if width == 64:
                lines.append(f"mov {ra}, {rng.getrandbits(64):#x}")
            else:
                lines.append(f"mov {ra}, {rng.getrandbits(width):#x}")
        elif kind == 1:
            lines.append(f"mov {ra}, {rb}")
        elif kind == 2:
            op = rng.choice(("movzx", "movsx"))
            dst = rng.choice(_R64 if rng.random() < 0.5 else _R32)
            src = rng.choice(_R8_REX + ["al", "bl", "cl", "dl"]
                             if rng.random() < 0.5 else _R16)
            lines.append(f"{op} {dst}, {src}")
        elif kind == 3:
            op = rng.choice(_ALU)
            if rng.random() < 0.5:
                lines.append(f"{op} {ra}, {rb}")
            else:
                imm = rng.randrange(-2**31, 2**31) if width >= 32 \
                    else rng.getrandbits(width - 1)
                lines.append(f"{op} {ra}, {imm}")
        elif kind == 4:
            lines.append(f"{rng.choice(_UNARY)} {ra}")
        elif kind == 5:
            base = rng.choice(_R64)
            idx = rng.choice([r for r in _R64 if r != "rsp"])
            scale = rng.choice((1, 2, 4, 8))
            disp = rng.randrange(-0x1000, 0x1000)
            lines.append(f"lea {rng.choice(_R64)}, "
                         f"[{base} + {idx}*{scale} + {disp}]")
        elif kind == 6:
            lines.append(f"set{rng.choice(_CC)} "
                         f"{rng.choice(_R8_LEGACY + _R8_REX)}")
        elif kind == 7:
            w = rng.choice((64, 32, 16))
            ca, cb = regpair(w)
            lines.append(f"cmov{rng.choice(_CC)} {ca}, {cb}")
        elif kind == 8:
            # forward branch (taken or not decided by live flags / rcx)
            op = rng.choice([f"j{cc}" for cc in _CC] + ["jmp", "jrcxz"])
            filler = f"{rng.choice(_UNARY)} {rng.choice(_R64)}"
            lines.extend([f"{op} 1f", filler, "1:"])
        else:
            lines.append("nop")
    lines.append("int3")
    return "\n".join(lines)


def _random_regs(rng: random.Random):
    regs = {name: rng.getrandbits(64)
            for name in ("rax", "rbx", "rcx", "rdx", "rsi", "rdi",
                         "r8", "r9", "r10", "r11")}
    # small rcx sometimes, so jrcxz goes both ways across programs
    if rng.random() < 0.5:
        regs["rcx"] = rng.randrange(4)
    return regs


@pytest.mark.parametrize("seed", range(6))
def test_fused_hot_grids_match_xla_and_oracle(seed):
    """Randomized grids over every hot-subset opclass: the fused ladder,
    the XLA ladder, and the EmuCpu oracle agree on state, rflags, rip,
    icount, and the coverage/edge bits; occupancy is 100% (all-hot code
    never retires an instruction on the XLA leg thanks to the resume
    hold)."""
    rng = random.Random(0xF05E + seed)
    asm = _gen_hot_program(rng)
    regs = _random_regs(rng)
    emu = run_emu(asm, regs=regs)
    (r0, s0), (r1, s1) = _run_pair(asm, regs=regs)
    for s in (s0, s1):
        assert all(StatusCode(int(x)) == StatusCode.CRASH for x in s)
    _assert_ladders_equal(r0, s0, r1, s1)
    g = np.asarray(r1.machine.gpr)
    rf = np.asarray(r1.machine.rflags)
    for lane in range(2):
        assert [int(v) for v in g[lane]] == list(emu.gpr)
        assert int(rf[lane]) & RF_CMP == emu.rflags & RF_CMP
        assert int(np.asarray(r1.machine.rip)[lane]) == emu.rip
        assert int(np.asarray(r1.machine.icount)[lane]) == emu.icount
    fused, instr = _occupancy(r1)
    assert instr == 2 * emu.icount
    assert fused == instr, (fused, instr)  # all-hot => 100% in-kernel


def test_fused_kernel_timeout_exact_vs_chunk():
    """In-kernel TIMEDOUT: with an instruction budget that trips in the
    middle of a hot stretch, the fused and XLA ladders stop on the same
    instruction with identical state (the kernel's limit check mirrors
    step_lane's)."""
    asm = """
        mov rax, 1
        mov rcx, 1000
    1:
        add rax, rcx
        lea rdx, [rax + rcx*4 + 7]
        xor rsi, rdx
        dec rcx
        jnz 1b
        int3
    """
    (r0, s0), (r1, s1) = _run_pair(asm, limit=137)
    assert all(StatusCode(int(x)) == StatusCode.TIMEDOUT for x in s1)
    _assert_ladders_equal(r0, s0, r1, s1)
    assert int(np.asarray(r1.machine.icount)[0]) == 137
    fused, instr = _occupancy(r1)
    assert fused == instr


@pytest.mark.parametrize("seed", range(3))
def test_fused_mem_grids_match_xla_and_oracle(seed):
    """The widened-subset acceptance grid: programs made of MEMORY-
    OPERAND forms — loads/stores through the in-kernel page walk and
    delta overlay, mem-dst ALU/SHIFT/UNARY read-modify-writes, widening
    and 2/3-operand mul, PUSH/POP/CALL/RET through the stack — execute
    ENTIRELY in-kernel: bit-exact vs the XLA ladder (state, dirty
    memory) and the EmuCpu oracle, at 100% occupancy."""
    rng = random.Random(0x3E30 + seed)
    body = []
    for _ in range(28):
        disp = rng.randrange(0, 0xE00) & ~7
        width, reg = rng.choice(
            (("qword", "rcx"), ("dword", "ecx"), ("word", "cx"),
             ("byte", "cl")))
        body.append(rng.choice([
            f"mov [rbx + {disp}], {reg}",
            f"mov {reg}, [rbx + {disp}]",
            f"mov {width} ptr [rbx + {disp}], {rng.getrandbits(7)}",
            f"add [rbx + {disp}], {reg}",
            f"xor rax, [rbx + {disp}]",
            f"cmp [rbx + {disp}], {reg}",
            f"movzx r10, {width.replace('qword', 'word')} ptr "
            f"[rbx + {disp}]" if width != "qword" else
            f"mov r10, [rbx + {disp}]",
            f"shl {width} ptr [rbx + {disp}], {rng.randrange(1, 7)}",
            f"neg {width} ptr [rbx + {disp}]",
            f"inc qword ptr [rbx + {disp}]",
            "shl rax, 3",
            f"ror rdx, {rng.randrange(1, 63)}",
            "shld rax, rdx, 11",
            "imul rdx, rax, 3",
            "mul rcx",
            "imul r9, rdx",
            f"setc byte ptr [rbx + {disp}]",
            f"cmovnz r10, qword ptr [rbx + {disp}]",
            "push rax\npop rsi",
            f"push qword ptr [rbx + {disp}]\npop r11",
            "push 0x1234\npop r10",
            "call 1f\njmp 2f\n1: add rax, 7\nret\n2:",
        ]))
    asm = (f"mov rbx, {DATA_BASE}\nmov rcx, 0x1122334455667788\n"
           f"mov r14, 5\n3:\n" + "\n".join(body)
           + "\ndec r14\njnz 3b\nint3")
    data = {DATA_BASE: bytes(0x1000)}
    emu = run_emu(asm, data=data)
    (r0, s0), (r1, s1) = _run_pair(asm, data=data)
    assert all(StatusCode(int(x)) == StatusCode.CRASH for x in s1)
    _assert_ladders_equal(r0, s0, r1, s1, check_mem=True)
    assert int(np.asarray(r1.machine.icount)[0]) == emu.icount
    g = np.asarray(r1.machine.gpr)
    assert [int(v) for v in g[0]] == list(emu.gpr)
    fused, instr = _occupancy(r1)
    assert fused == instr, (fused, instr)  # memory forms are hot now


@pytest.mark.parametrize("seed", range(3))
def test_fused_park_resume_seam_randomized(seed):
    """The acceptance seam: programs interleaving hot code (now
    including memory operands and stack ops) with genuinely NON-hot
    instructions (bswap, xchg, popcnt, bt, cqo, lahf) park mid-chunk and
    resume on the XLA path — final state including dirty memory is
    identical to the XLA-only ladder, and the fused/instruction counters
    partition exactly."""
    rng = random.Random(0x5EA9 + seed)
    cold_pool = [
        "bswap rax",
        "xchg rax, rdx",
        "popcnt r10, rax",
        "bt rax, 3",
        "cqo",
        "lahf",
    ]
    body = []
    for _ in range(24):
        if rng.random() < 0.4:
            body.append(rng.choice(cold_pool))
        else:
            body.append(rng.choice([
                f"add rax, {rng.randrange(1, 1 << 20)}",
                f"mov [rbx + {rng.randrange(0, 0xE00)}], rcx",
                f"add rax, [rbx + {rng.randrange(0, 0xE00)}]",
                "push rax", "pop rsi",
                "inc r9", "dec rdx", "xor rsi, rax",
                "lea rdi, [rax + rdx*2 + 5]",
                "cmovnz r10, rax", "setc r11b",
            ]))
    asm = (f"mov rbx, {DATA_BASE}\nmov rcx, 3\n1:\n"
           + "\n".join(body) + "\ndec rcx\njnz 1b\nint3")
    data = {DATA_BASE: bytes(0x1000)}
    emu = run_emu(asm, data=data)
    (r0, s0), (r1, s1) = _run_pair(asm, data=data)
    assert all(StatusCode(int(x)) == StatusCode.CRASH for x in s1)
    _assert_ladders_equal(r0, s0, r1, s1, check_mem=True)
    assert int(np.asarray(r1.machine.icount)[0]) == emu.icount
    fused, instr = _occupancy(r1)
    assert 0 < fused < instr  # genuinely mixed: both engines retired work
    # park attribution: every park here is a SUBSET park (cold opclass),
    # never a memory park — the split must say so
    ctr = np.asarray(r1.machine.ctr)
    assert ctr[:, CTR_PARK_SUBSET].sum() > 0
    assert ctr[:, CTR_PARK_MEM].sum() == 0
    # CTR_INSTR == icount invariant survives the fused ladder
    icount = np.asarray(r1.machine.icount)
    assert (ctr[:, CTR_INSTR] == icount.astype(np.uint32)).all()


@pytest.mark.parametrize("case", ("large2m", "fault", "overlay"))
def test_fused_walk_differential(case):
    """In-kernel page walk vs translate_vec_l, differentially: the XLA
    ladder translates through mem/paging.py, the kernel through its own
    scalar u32-limb walk — 2MiB large-page mappings, non-present holes
    (PAGE_FAULT with the exact faulting address), and overlay-shadowed
    frames (a host write into the lane overlay that loads must observe)
    all agree bit-exactly between the ladders."""
    if case == "large2m":
        from tests.asmhelper import assemble
        from wtf_tpu.mem.physmem import PhysMem
        from wtf_tpu.snapshot.synthetic import SyntheticSnapshotBuilder

        big_gva = 0x4000_0000
        code_base = 0x0001_4000_1000
        asm = f"""
            mov rbx, {big_gva}
            mov rax, [rbx + 0x1F0000]
            add rax, [rbx + 8]
            mov [rbx + 0x100], rax
            push rax
            pop rcx
            int3
        """
        b = SyntheticSnapshotBuilder()
        b.write(code_base, assemble(asm))
        b.map(0x7FFF_B000, 0x5000)              # stack
        # sibling 4K mapping so the 2MiB PS entry's PML4E/PDPTE parents
        # exist (same 1GiB region, different 2MiB region)
        b.map(big_gva + 0x20_0000, 0x1000)
        gpa = 0x0060_0000
        b.add_large_page_mapping(big_gva, gpa, 21)

        def phys_write(at, blob):
            b._phys_page(at >> 12)[at & 0xFFF:(at & 0xFFF) + len(blob)] \
                = blob

        phys_write(gpa + 0x1F0000,
                   (0x1111_2222_3333_4444).to_bytes(8, "little"))
        phys_write(gpa + 8, (0x10).to_bytes(8, "little"))
        pages, cpu = b.build(rip=code_base, rsp=0x7FFF_F000 - 0x100)
        snap = Snapshot(physmem=PhysMem.from_pages(pages), cpu=cpu)
        out = []
        for mode in ("off", "on"):
            r = Runner(snap, n_lanes=2, chunk_steps=64, fused_step=mode)
            out.append((r, r.run()))
        (r0, s0), (r1, s1) = out
        _assert_ladders_equal(r0, s0, r1, s1, check_mem=True)
        assert int(np.asarray(r1.machine.gpr)[0, 1]) \
            == 0x1111_2222_3333_4454
        fused, instr = _occupancy(r1)
        assert fused == instr  # the large-page walk stayed in-kernel
        return

    if case == "fault":
        asm = f"""
            mov rbx, {DATA_BASE}
            mov rax, [rbx]
            mov rcx, [rbx + 0x200000]
            int3
        """
        data = {DATA_BASE: b"\x55" * 0x1000}
        (r0, s0), (r1, s1) = _run_pair(asm, data=data)
        assert all(StatusCode(int(x)) == StatusCode.PAGE_FAULT
                   for x in s1)
        _assert_ladders_equal(r0, s0, r1, s1)
        for field in ("fault_gva", "fault_write"):
            assert np.array_equal(np.asarray(getattr(r0.machine, field)),
                                  np.asarray(getattr(r1.machine, field)))
        assert int(np.asarray(r1.machine.fault_gva)[0]) \
            == DATA_BASE + 0x200000
        # the park split attributes this as a MEMORY park, not subset
        ctr = np.asarray(r1.machine.ctr)
        assert ctr[:, CTR_PARK_MEM].sum() > 0
        return

    # overlay: a HOST write lands in the lane overlay (delta row); the
    # kernel's loads must read through it, and a kernel store to the
    # same page must merge with it
    asm = f"""
        mov rbx, {DATA_BASE}
        mov rax, [rbx + 0x10]
        mov [rbx + 0x18], rax
        add rax, [rbx + 0x18]
        int3
    """
    data = {DATA_BASE: bytes(0x1000)}
    results = []
    for mode in ("off", "on"):
        r = _make_runner(asm, data=data, n_lanes=2, fused_step=mode)
        view = r.view()
        for lane in range(2):
            view.virt_write(lane, DATA_BASE + 0x10,
                            (0xDEAD_BEEF_0BAD_F00D).to_bytes(8, "little"))
        r.push(view)
        results.append((r, r.run()))
    (r0, s0), (r1, s1) = results
    _assert_ladders_equal(r0, s0, r1, s1, check_mem=True)
    assert int(np.asarray(r1.machine.gpr)[0, 0]) \
        == (2 * 0xDEAD_BEEF_0BAD_F00D) & ((1 << 64) - 1)
    fused, instr = _occupancy(r1)
    assert fused == instr


def test_fused_breakpoint_park_and_bp_skip_resume():
    """An armed breakpoint inside hot code parks the lane (the kernel
    checks M_BP pre-execution like step_lane) and the post-handler
    bp_skip=1 resume executes the breakpointed instruction exactly once —
    handler counts and final state match the XLA ladder."""
    asm = """
        mov rax, 0
        mov rcx, 5
    1:
        add rax, rcx
        inc rdx
        dec rcx
        jnz 1b
        int3
    """
    hits = {}

    def make_handler(key):
        def handler(runner, view, lane):
            hits[key] = hits.get(key, 0) + 1
            # leave status BREAKPOINT and rip in place -> runner resumes
            # the lane with bp_skip=1
        return handler

    from tests.asmhelper import assemble
    from tests.emurunner import CODE_BASE

    code = assemble(asm)
    bp_off = code.index(bytes.fromhex("48ffc2"))  # the one `inc rdx`
    results = {}
    for mode in ("off", "on"):
        r = _make_runner(asm, n_lanes=2, fused_step=mode)
        r.cache.set_breakpoint(CODE_BASE + bp_off)
        status = r.run(bp_handler=make_handler(mode))
        assert all(StatusCode(int(x)) == StatusCode.CRASH for x in status)
        results[mode] = r
    assert hits["off"] == hits["on"] == 2 * 5  # per lane, per iteration
    r0, r1 = results["off"], results["on"]
    for field in ("gpr", "rip", "rflags", "icount", "cov", "edge"):
        assert np.array_equal(np.asarray(getattr(r0.machine, field)),
                              np.asarray(getattr(r1.machine, field))), field


@pytest.mark.slow
def test_fused_occupancy_demo_tlv_hot_loop():
    """The acceptance bar (PR 12): >= 95% of retired instructions
    execute in-kernel on the demo_tlv hot loop — with the page walk and
    delta-overlay probe in-kernel, the parser's memory-operand loop body
    no longer parks (measured 100%: the only parks left are the finish
    breakpoint's).

    `slow`: the demo_tlv image shapes force a second one-shot
    trace+compile of the fused executor (~20s on the 1-core CI box) on
    top of the synthetic-guest one the in-budget differentials pay;
    occupancy itself is also measured by `bench.py --fused-compare`
    (0.861, recorded in PERF.md)."""
    from wtf_tpu.harness import demo_tlv
    from wtf_tpu.interp.runner import warm_decode_cache

    payload = b"\x01\x08AAAAAAAA" * 50
    r = Runner(demo_tlv.build_snapshot(), n_lanes=2, chunk_steps=64,
               fused_step="on")
    # 4k instructions keep the interpret-mode dispatch count tier-1-cheap;
    # occupancy is a property of the instruction MIX, not the budget
    # (bench.py --fused-compare measures the same workload 5x deeper)
    r.limit = 4_000
    warm_decode_cache(r, demo_tlv.TARGET, payload)
    view = r.view()
    for lane in range(2):
        view.virt_write(lane, demo_tlv.INPUT_GVA, payload)
        view.r["gpr"][lane, 2] = np.uint64(len(payload))
    r.push(view)
    r.run()
    fused, instr = _occupancy(r)
    assert instr > 1000
    assert fused / instr >= 0.95, (fused, instr, fused / instr)


@pytest.mark.slow
def test_fused_campaign_parity_demo_tlv():
    """--fused-step=on drives a demo_tlv campaign end-to-end through
    FuzzLoop with crash/coverage parity vs off (same seeds, same
    batches).

    `slow`: two full campaigns through the interpret-mode kernel blow
    the tier-1 wall budget; the in-budget differentials above cover the
    same ladder at Runner level, and this runs in the slow tier."""
    from wtf_tpu.backend import create_backend
    from wtf_tpu.fuzz.corpus import Corpus
    from wtf_tpu.fuzz.loop import FuzzLoop
    from wtf_tpu.fuzz.native_mutator import best_mangle_mutator
    from wtf_tpu.harness import demo_tlv

    def campaign(fused):
        rng = random.Random(0x77F)
        corpus = Corpus(rng=rng)
        corpus.add(b"\x01\x08AAAAAAAA" * 20 + b"\x03\x30" + b"B" * 0x30)
        backend = create_backend(
            "tpu", demo_tlv.build_snapshot(), n_lanes=4, limit=20_000,
            chunk_steps=256, overlay_slots=32,
            fused_step="on" if fused else "off")
        backend.initialize()
        demo_tlv.TARGET.init(backend)
        loop = FuzzLoop(backend, demo_tlv.TARGET,
                        best_mangle_mutator(rng, max_len=0x200), corpus)
        for _ in range(3):
            loop.run_one_batch()
        return loop, backend

    l0, b0 = campaign(False)
    l1, b1 = campaign(True)
    assert l0.stats.testcases == l1.stats.testcases
    assert l0.stats.crashes == l1.stats.crashes
    assert l0.stats.timeouts == l1.stats.timeouts
    assert b0.aggregate_coverage() == b1.aggregate_coverage()
    # the fast path genuinely carried the campaign
    fused = b1.registry.counter("device.fused_steps").value
    instr = b1.registry.counter("device.instructions").value
    assert fused > 0 and instr > 0


def test_fused_step_config_validation():
    """Config surface: bad values raise; 'auto' resolves to the XLA
    ladder (on every platform, until the TPU compiler accepts the kernel
    — ROADMAP S2); 'on' forces the fused ladder."""
    from wtf_tpu.harness import demo_tlv

    snap = demo_tlv.build_snapshot()
    with pytest.raises(ValueError):
        Runner(snap, n_lanes=2, fused_step="sometimes")
    assert Runner(snap, n_lanes=2, fused_step="auto").fused_enabled is False
    assert Runner(snap, n_lanes=2, fused_step="on").fused_enabled is True
