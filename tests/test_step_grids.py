"""Randomized differential grids: the device step against the EmuCpu oracle.

tests/test_step.py holds the device step (interp/step.py, dispatched by
Runner through make_run_chunk) to the oracle on hand-written cases; this
file does it on SEEDED RANDOM programs: straight-line integer code with
forward branches, memory-operand forms through the page walk and the
delta overlay, and mixes of limb-ported and cold opclasses.  Every case
compares registers, modeled flags, rip, icount, the device counter block,
the coverage bitmap (decoded to rips) and all dirty memory; both lanes
must agree with the oracle and with each other.
"""

import random

import numpy as np
import pytest

from tests.emurunner import CODE_BASE, DATA_BASE, build_guest
from wtf_tpu.core.results import StatusCode
from wtf_tpu.cpu.emu import EmuCpu, EmuMem, GuestCrash
from wtf_tpu.interp.machine import CTR_INSTR, CTR_SWEPT
from wtf_tpu.interp.runner import Runner
from wtf_tpu.snapshot.loader import Snapshot

RF_CMP = 0x8D5 | 0x400  # same modeled-flags mask as tests/test_step.py


def _guest(asm, data=None, regs=None):
    physmem, cpustate, _ = build_guest(asm, data)
    for name, value in (regs or {}).items():
        setattr(cpustate, name, value)
    return Snapshot(physmem=physmem, cpu=cpustate)


def _run_oracle(snap, limit=0, host_writes=(), max_steps=100_000):
    """EmuCpu until the guest crashes (int3) or `limit` instructions have
    retired.  Returns (cpu, rips reached): the device records a rip in its
    coverage bitmap when the instruction is reached, crashing or not."""
    cpu = EmuCpu(EmuMem(snap.physmem), snap.cpu)
    for gva, blob in host_writes:
        cpu.virt_write(gva, blob)
    reached = set()
    for _ in range(max_steps):
        if limit and cpu.icount >= limit:
            break
        reached.add(cpu.rip)
        try:
            cpu.step()
        except GuestCrash:
            break
    else:
        raise AssertionError(f"no crash within {max_steps} steps")
    return cpu, reached


def _run_device(snap, n_lanes=2, limit=0, host_writes=(), breakpoints=(),
                bp_handler=None):
    runner = Runner(snap, n_lanes=n_lanes, chunk_steps=64)
    runner.limit = limit
    if host_writes:
        view = runner.view()
        for lane in range(n_lanes):
            for gva, blob in host_writes:
                view.virt_write(lane, gva, blob)
        runner.push(view)
    for gva in breakpoints:
        runner.cache.set_breakpoint(gva)
    return runner, runner.run(bp_handler=bp_handler)


def _assert_matches_oracle(runner, status, emu, reached,
                           want=StatusCode.CRASH):
    m = runner.machine
    gpr, rflags = np.asarray(m.gpr), np.asarray(m.rflags)
    rip, icount = np.asarray(m.rip), np.asarray(m.icount)
    ctr, cov, edge = np.asarray(m.ctr), np.asarray(m.cov), np.asarray(m.edge)
    view = runner.view()
    for lane in range(runner.n_lanes):
        assert StatusCode(int(status[lane])) == want, (
            lane, StatusCode(int(status[lane])).name, runner.lane_errors)
        assert [int(v) for v in gpr[lane]] == list(emu.gpr), lane
        assert int(rflags[lane]) & RF_CMP == emu.rflags & RF_CMP, lane
        assert int(rip[lane]) == emu.rip, lane
        assert int(icount[lane]) == emu.icount, lane
        # the counter block: CTR_INSTR is icount, fallbacks included, and
        # a lane is swept at least once per instruction the device retires
        assert int(ctr[lane, CTR_INSTR]) == emu.icount, lane
        assert int(ctr[lane, CTR_SWEPT]) > 0, lane
        assert sorted(runner.cache.rips_of_bits(cov[lane])) \
            == sorted(reached), lane
        for pfn in emu.mem.dirty_pfns():
            assert view.page(lane, pfn) == bytes(emu.mem.overlay[pfn]), (
                f"lane {lane} page {pfn:#x}")
    # equal lanes ran one program: equal bitmaps, equal sweep counts
    assert (cov == cov[0]).all() and (edge == edge[0]).all()
    assert (ctr[:, CTR_SWEPT] == ctr[0, CTR_SWEPT]).all()


def _check(asm, data=None, regs=None, limit=0, want=StatusCode.CRASH,
           host_writes=(), **dev):
    """One program on both engines (a fresh guest each: neither may see
    the other's writes); `dev` is the device's alone (breakpoints)."""
    emu, reached = _run_oracle(_guest(asm, data, regs), limit=limit,
                               host_writes=host_writes)
    runner, status = _run_device(_guest(asm, data, regs), limit=limit,
                                 host_writes=host_writes, **dev)
    _assert_matches_oracle(runner, status, emu, reached, want=want)
    return runner, emu


# ---------------------------------------------------------------------------
# randomized grids over the limb-ported integer core
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
    of the limb-ported integer core (MOV/MOVZX/MOVSX, ALU, UNARY, LEA,
    SETcc, CMOVcc, Jcc taken and not taken, JMP, jrcxz, NOP), ending in
    int3."""
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


@pytest.mark.parametrize("seed", range(12))
def test_hot_grids_match_oracle(seed):
    """Randomized grids over every limb-ported opclass: the device step
    and the EmuCpu oracle agree on registers, rflags, rip, icount, the
    coverage bits and the counter block."""
    rng = random.Random(0xF05E + seed)
    asm = _gen_hot_program(rng)
    _check(asm, regs=_random_regs(rng))


def test_timeout_exact_vs_oracle():
    """TIMEDOUT on the exact instruction: an instruction budget that trips
    in the middle of a hot stretch stops the device where the oracle
    stands after that many steps, with the same state and coverage."""
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
    runner, _ = _check(asm, limit=137, want=StatusCode.TIMEDOUT)
    assert int(np.asarray(runner.machine.icount)[0]) == 137


@pytest.mark.parametrize("seed", range(8))
def test_mem_grids_match_oracle(seed):
    """Programs made of MEMORY-OPERAND forms: loads/stores through the
    page walk and delta overlay, mem-dst ALU/SHIFT/UNARY
    read-modify-writes, widening and 2/3-operand mul, PUSH/POP/CALL/RET
    through the stack: bit-exact against the oracle, dirty memory
    included."""
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
            # zero-extending load at the drawn width (movzx has no
            # dword form: a 32-bit mov zero-extends)
            f"movzx r10, {width} ptr [rbx + {disp}]"
            if width in ("word", "byte") else
            f"mov {'r10' if width == 'qword' else 'r10d'}, [rbx + {disp}]",
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
    _check(asm, data={DATA_BASE: bytes(0x1000)})


@pytest.mark.parametrize("seed", range(6))
def test_mixed_class_grids_match_oracle(seed):
    """Programs interleaving the limb-ported core (memory operands and
    stack ops included) with the cold opclasses that read u64 views
    (bswap, xchg, popcnt, bt, cqo, lahf): the seam between the two inside
    one step is bit-exact against the oracle."""
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
    _check(asm, data={DATA_BASE: bytes(0x1000)})


def test_large_page_walk_vs_oracle():
    """A program whose loads, stores and stack traffic go through a 2 MiB
    large-page mapping: translate_vec_l's PS path inside the step, against
    the oracle's host walk, dirty memory included."""
    from tests.asmhelper import assemble
    from wtf_tpu.mem.physmem import PhysMem
    from wtf_tpu.snapshot.synthetic import SyntheticSnapshotBuilder

    big_gva = 0x4000_0000
    asm = f"""
        mov rbx, {big_gva}
        mov rax, [rbx + 0x1F0000]
        add rax, [rbx + 8]
        mov [rbx + 0x100], rax
        push rax
        pop rcx
        int3
    """

    def guest():
        b = SyntheticSnapshotBuilder()
        b.write(CODE_BASE, assemble(asm))
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
        pages, cpu = b.build(rip=CODE_BASE, rsp=0x7FFF_F000 - 0x100)
        return Snapshot(physmem=PhysMem.from_pages(pages), cpu=cpu)

    emu, reached = _run_oracle(guest())
    runner, status = _run_device(guest())
    _assert_matches_oracle(runner, status, emu, reached)
    assert int(np.asarray(runner.machine.gpr)[0, 1]) == 0x1111_2222_3333_4454


def test_overlay_shadowed_frame_vs_oracle():
    """A HOST write lands in the lane overlay (delta row) before the run:
    the step's loads read through it, and a guest store to the same page
    merges with it."""
    asm = f"""
        mov rbx, {DATA_BASE}
        mov rax, [rbx + 0x10]
        mov [rbx + 0x18], rax
        add rax, [rbx + 0x18]
        int3
    """
    word = 0xDEAD_BEEF_0BAD_F00D
    runner, _ = _check(
        asm, data={DATA_BASE: bytes(0x1000)},
        host_writes=[(DATA_BASE + 0x10, word.to_bytes(8, "little"))])
    assert int(np.asarray(runner.machine.gpr)[0, 0]) \
        == (2 * word) & ((1 << 64) - 1)


def test_breakpoint_resume_vs_oracle():
    """An armed breakpoint inside a loop parks the lane before the
    instruction (step_lane checks M_BP pre-execution); the handler leaves
    status and rip in place, so the runner resumes with bp_skip=1 and the
    breakpointed instruction executes exactly once per hit: the handler
    fires once per lane per iteration and the final state is the oracle's,
    which has no breakpoint."""
    from tests.asmhelper import assemble

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
    hits = []

    def handler(runner, view, lane):
        hits.append(lane)

    bp_off = assemble(asm).index(bytes.fromhex("48ffc2"))  # the `inc rdx`
    _check(asm, breakpoints=[CODE_BASE + bp_off], bp_handler=handler)
    assert sorted(hits) == [0] * 5 + [1] * 5
