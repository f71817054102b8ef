"""Device-executor differential tests: interp/step.py vs the EmuCpu oracle.

The decoder is shared between both paths, so every assertion here pins down
exactly one thing: that the JAX transition function implements the same
*semantics* per uop as cpu/emu.py (which itself is pinned to real hardware
by tests/test_emu.py's hw-differential suite).  This is the rebuild's
version of the reference's cross-backend trace-diffing methodology
(SURVEY.md §4.3: develop on deterministic bochscpu, validate fast backends
against its traces).
"""

import numpy as np
import pytest

from tests.emurunner import CODE_BASE, DATA_BASE, STACK_TOP, build_guest, run_emu
from tests.test_emu import HW_CASES, _ALT_REGS, _INIT_REGS
from wtf_tpu.core.cpustate import GPR_NAMES
from wtf_tpu.core.results import StatusCode
from wtf_tpu.interp.runner import Runner
from wtf_tpu.snapshot.loader import Snapshot

# Flags the device path intentionally models (TF/IF excluded: test guests
# don't exercise interrupt masking beyond FLAGOP, which both paths share).
RF_CMP = 0x8D5 | 0x400


def make_runner(asm, data=None, regs=None, n_lanes=2, limit=0):
    physmem, cpustate, _ = build_guest(asm, data)
    if regs:
        for name, value in regs.items():
            setattr(cpustate, name, value)
    snap = Snapshot(physmem=physmem, cpu=cpustate)
    runner = Runner(snap, n_lanes=n_lanes, chunk_steps=64)
    runner.limit = limit
    return runner


def run_tpu(asm, data=None, regs=None, n_lanes=2, limit=0):
    runner = make_runner(asm, data, regs, n_lanes, limit)
    status = runner.run()
    return runner, status


def assert_matches_oracle(asm, data=None, regs=None, n_lanes=2,
                          check_mem=True):
    """Run `asm` (must end in hlt) on both engines; compare the complete
    device-resident architectural state and all dirty memory."""
    emu = run_emu(asm, data=data, regs=regs)
    runner, status = run_tpu(asm, data=data, regs=regs, n_lanes=n_lanes)

    for lane in range(n_lanes):
        assert StatusCode(int(status[lane])) == StatusCode.CRASH, (
            f"lane {lane}: {StatusCode(int(status[lane])).name}, "
            f"errors={runner.lane_errors}")
    g = np.asarray(runner.machine.gpr)
    rf = np.asarray(runner.machine.rflags)
    rip = np.asarray(runner.machine.rip)
    xmm = np.asarray(runner.machine.xmm)
    for lane in range(n_lanes):
        for i, name in enumerate(GPR_NAMES):
            assert int(g[lane, i]) == emu.gpr[i], (
                f"lane {lane} {name}: tpu={int(g[lane, i]):#x} "
                f"emu={emu.gpr[i]:#x}")
        assert int(rf[lane]) & RF_CMP == emu.rflags & RF_CMP, (
            f"lane {lane} rflags: tpu={int(rf[lane]):#x} emu={emu.rflags:#x}")
        assert int(rip[lane]) == emu.rip
        for i in range(16):
            assert int(xmm[lane, i, 0]) == emu.xmm[i][0], f"xmm{i} lo"
            assert int(xmm[lane, i, 1]) == emu.xmm[i][1], f"xmm{i} hi"
            assert int(xmm[lane, i, 2]) == emu.ymmh[i][0], f"ymm{i} up lo"
            assert int(xmm[lane, i, 3]) == emu.ymmh[i][1], f"ymm{i} up hi"
        fpst = np.asarray(runner.machine.fpst)
        for p in range(8):
            assert int(fpst[lane, p]) == emu.fpst[p], (
                f"lane {lane} fpst[{p}]: tpu={int(fpst[lane, p]):#x} "
                f"emu={emu.fpst[p]:#x}")
        assert int(runner.machine.fpsw[lane]) & 0xFFFF == emu.fpsw_packed()
        assert int(runner.machine.fptw[lane]) & 0xFFFF == emu.fptw
        assert int(runner.machine.fpcw[lane]) & 0xFFFF == emu.fpcw
    if check_mem:
        view = runner.view()
        for pfn in emu.mem.dirty_pfns():
            want = bytes(emu.mem.overlay[pfn])
            for lane in range(n_lanes):
                got = view.page(lane, pfn)
                assert got == want, (
                    f"lane {lane} page {pfn:#x} diverges at offset "
                    f"{next(i for i in range(4096) if got[i] != want[i])}")
    return runner, emu


# ---------------------------------------------------------------------------
# 1. the full hardware-differential corpus, now three-way:
#    hardware (via test_emu) == oracle == device
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name,snippet,fmask",
                         [(c[0], c[1], c[2]) for c in HW_CASES])
@pytest.mark.parametrize("initset", ["a", "b"])
def test_device_vs_oracle_hw_cases(name, snippet, fmask, initset):
    init = list(_INIT_REGS if initset == "a" else _ALT_REGS)
    regs = {n: v for n, v in zip(GPR_NAMES, init)}
    regs.pop("rsp")
    assert_matches_oracle(snippet + "\nhlt", regs=regs)


# ---------------------------------------------------------------------------
# 2. memory / control flow / strings / SSE snippets
# ---------------------------------------------------------------------------

DIFF_CASES = [
    ("mem_load_store", f"""
        mov rbx, {DATA_BASE}
        mov r9, 0x1122334455667788
        mov [rbx], r9
        mov rcx, [rbx]
        mov [rbx+8], ecx
        mov dx, [rbx+8]
        mov byte ptr [rbx+0x10], 0x7F
        movzx rsi, byte ptr [rbx+0x10]
        hlt""", {DATA_BASE: b"\x00" * 0x1000}),
    ("page_crossing", f"""
        mov rbx, {DATA_BASE + 0xFFC}
        mov rax, 0x0123456789ABCDEF
        mov [rbx], rax
        mov rcx, [rbx]
        hlt""", {DATA_BASE: b"\x00" * 0x2000}),
    ("rip_relative", """
        lea rax, [rip + data_here]
        mov rbx, [rip + data_here]
        hlt
        data_here: .quad 0xFEEDFACECAFEBEEF""", None),
    ("call_ret", """
        call func
        add rax, 1
        hlt
        func:
        mov rax, 41
        ret""", None),
    ("fib_loop", """
        mov rax, 0
        mov rbx, 1
        mov rcx, 20
        fib:
        mov rdx, rax
        add rax, rbx
        mov rbx, rdx
        dec rcx
        jnz fib
        hlt""", None),
    ("rep_movsb", f"""
        mov rsi, {DATA_BASE}
        mov rdi, {DATA_BASE + 0x800}
        mov rcx, 300
        rep movsb
        mov al, [rdi-1]
        hlt""", {DATA_BASE: bytes(range(256)) + b"\xAB" * 44 + b"\x00" * 0x700}),
    ("rep_stosq_scasb", f"""
        mov rdi, {DATA_BASE}
        mov rax, 0x4141414141414141
        mov rcx, 32
        rep stosq
        mov rdi, {DATA_BASE}
        mov al, 0x42
        mov rcx, 512
        repne scasb
        hlt""", {DATA_BASE: b"\x00" * 0x1000}),
    ("repe_cmpsb", f"""
        mov rsi, {DATA_BASE}
        mov rdi, {DATA_BASE + 0x100}
        mov rcx, 64
        repe cmpsb
        hlt""",
     {DATA_BASE: b"same-prefix-data" * 2 + b"X" + b"\x00" * 0xD0
      + b"same-prefix-data" * 2 + b"Y" + b"\x00" * 0xD0}),
    ("movs_df_backwards", f"""
        mov rsi, {DATA_BASE + 0x78}
        mov rdi, {DATA_BASE + 0x178}
        mov rcx, 16
        std
        rep movsq
        cld
        hlt""", {DATA_BASE: bytes((i * 7) & 0xFF for i in range(0x200))}),
    ("jcc_spectrum", """
        xor r15, r15
        mov rax, 5
        cmp rax, 5
        je l1
        or r15, 1
        l1:
        cmp rax, 6
        jb l2
        or r15, 2
        l2:
        cmp rax, 4
        jg l3
        or r15, 4
        l3:
        test rax, rax
        js l4
        or r15, 8
        l4:
        hlt""", None),
    ("jrcxz", """
        mov rcx, 1
        jrcxz skip1
        mov rax, 1
        xor rcx, rcx
        jrcxz skip2
        mov rax, 99
        skip2:
        skip1:
        hlt""", None),
    ("fsgsbase_ops", """
        mov rax, 0x5678DEADBEEF
        wrfsbase rax
        rdfsbase rbx
        wrgsbase rax
        rdgsbase rcx
        mov esi, 0xCAFE0000
        wrfsbase esi
        rdfsbase rdx
        rdfsbase r8d
        hlt""", None),
    ("enter_leave_roundtrip", """
        mov rbp, 0x1122334455667788
        mov rdi, rsp
        enter 0x40, 0
        mov rax, rbp
        mov rbx, [rbp]
        lea rcx, [rbp-0x40]
        leave
        mov rdx, rsp
        hlt""", None),
    ("msr_roundtrip", """
        mov ecx, 0xC0000082
        mov eax, 0x11223344
        mov edx, 0x55667788
        wrmsr
        xor eax, eax
        xor edx, edx
        rdmsr
        mov rbx, rax
        mov rsi, rdx
        mov ecx, 0xC0000101
        rdmsr
        mov r8, rax
        mov ecx, 0xC0000080
        rdmsr
        hlt""", None),
    ("wrmsr_lstar_steers_syscall", """
        lea rax, [rip + handler]
        mov rdx, rax
        shr rdx, 32
        mov ecx, 0xC0000082
        wrmsr
        syscall
        mov rbx, 0xBAD
        hlt
    handler:
        mov rbx, 0x600D
        hlt""", None),
    ("jecxz_a32", """
        mov rcx, 0xF00000000
        jecxz taken
        mov rax, 99
        taken:
        mov rbx, 7
        mov ecx, 1
        jecxz bad
        mov rbx, 1
        bad:
        hlt""", None),
    ("push_imm_leave", """
        push 0x1234
        pop rax
        push rbp
        mov rbp, rsp
        sub rsp, 0x20
        mov qword ptr [rbp-8], 0x77
        mov rbx, [rbp-8]
        leave
        hlt""", None),
    ("bt_mem_bitstring", f"""
        mov rbx, {DATA_BASE}
        mov rax, 100
        bts [rbx], rax
        mov rax, -9
        bts qword ptr [rbx+0x40], rax
        mov rcx, 100
        bt [rbx], rcx
        setc dl
        hlt""", {DATA_BASE: b"\x00" * 0x1000}),
    ("xchg_mem", f"""
        mov rbx, {DATA_BASE}
        mov qword ptr [rbx], 0x1111
        mov rax, 0x2222
        xchg [rbx], rax
        hlt""", {DATA_BASE: b"\x00" * 0x1000}),
    ("sse_roundtrip_pxor", f"""
        mov rbx, {DATA_BASE}
        movdqu xmm0, [rbx]
        movdqu xmm1, [rbx+16]
        pxor xmm0, xmm1
        movdqu [rbx+32], xmm0
        movdqa xmm2, xmm0
        por xmm2, xmm1
        hlt""", {DATA_BASE: bytes(range(32)) + b"\x00" * 0x100}),
    ("sse_punpckldq_paddq", f"""
        mov rbx, {DATA_BASE}
        movdqu xmm0, [rbx]
        movdqu xmm1, [rbx+16]
        punpckldq xmm0, xmm1
        movdqu xmm2, [rbx]
        paddq xmm2, xmm1
        paddq xmm2, [rbx+16]
        movdqu [rbx+32], xmm0
        movdqu [rbx+48], xmm2
        hlt""", {DATA_BASE: bytes(range(200, 232)) + b"\x00" * 0x100}),
    ("sse_pinsrw_pextrw", f"""
        mov rbx, {DATA_BASE}
        movdqu xmm0, [rbx]
        mov eax, 0xBEEF
        pinsrw xmm0, eax, 3
        pinsrw xmm0, eax, 7
        pextrw ecx, xmm0, 3
        pextrw edx, xmm0, 0
        movdqu [rbx+32], xmm0
        hlt""", {DATA_BASE: bytes(range(64)) + b"\x00" * 0x100}),
    ("sse_psllq_psrlq_imm", f"""
        mov rbx, {DATA_BASE}
        movdqu xmm0, [rbx]
        psllq xmm0, 5
        movdqu xmm1, [rbx]
        psrlq xmm1, 23
        movdqu xmm2, [rbx]
        psrlq xmm2, 64
        movdqu xmm3, [rbx]
        psllq xmm3, 63
        movdqu [rbx+32], xmm0
        movdqu [rbx+48], xmm1
        hlt""", {DATA_BASE: bytes(range(100, 132)) + b"\x00" * 0x100}),
    ("sse_movlps_movhps", f"""
        mov rbx, {DATA_BASE}
        movdqu xmm0, [rbx]
        movlps xmm0, [rbx+16]
        movhps xmm0, [rbx+24]
        movdqu xmm1, [rbx+32]
        movhlps xmm1, xmm0
        movlhps xmm1, xmm0
        movlps [rbx+48], xmm0
        movhps [rbx+56], xmm1
        hlt""", {DATA_BASE: bytes(range(64, 128)) + b"\x00" * 0x100}),
    ("sse_movq_movd", f"""
        mov rax, 0x1122334455667788
        movq xmm0, rax
        movd xmm1, eax
        movq rbx, xmm0
        movd ecx, xmm1
        pcmpeqb xmm2, xmm2
        pmovmskb edx, xmm2
        hlt""", None),
    ("sse_scalar_merge", f"""
        mov rbx, {DATA_BASE}
        movdqu xmm0, [rbx]
        movss xmm1, [rbx+4]
        movsd xmm2, [rbx+8]
        movss xmm0, xmm1
        movaps xmm3, xmm0
        hlt""", {DATA_BASE: bytes(range(64)) + b"\x00" * 0x100}),
    ("string_single_ops", f"""
        mov rsi, {DATA_BASE}
        mov rdi, {DATA_BASE + 0x20}
        lodsq
        stosq
        movsb
        mov rdi, {DATA_BASE}
        scasb
        hlt""", {DATA_BASE: bytes(range(64)) + b"\x00" * 0x100}),
    ("shift_mem_forms", f"""
        mov rbx, {DATA_BASE}
        mov rdx, 0x8000000000000001
        mov [rbx], rdx
        shl qword ptr [rbx], 3
        mov cl, 5
        shr qword ptr [rbx+8], cl
        sar dword ptr [rbx+16], 2
        rol word ptr [rbx+24], 9
        hlt""",
     {DATA_BASE: b"\xFF" * 32 + b"\x00" * 0x100}),
    ("div_mem_8bit", f"""
        mov rbx, {DATA_BASE}
        mov byte ptr [rbx], 7
        mov ax, 1234
        div byte ptr [rbx]
        mov rcx, 0
        mov rdx, 0
        mov rax, 0xFFFFFFFF
        mov rcx, 16
        div rcx
        hlt""", {DATA_BASE: b"\x00" * 0x1000}),
    ("imul_neg_mem", f"""
        mov rbx, {DATA_BASE}
        mov qword ptr [rbx], -7
        mov rax, 3
        imul qword ptr [rbx]
        imul rcx, rax, -9
        hlt""", {DATA_BASE: b"\x00" * 0x1000}),
    ("cmpxchg_mem", f"""
        mov rbx, {DATA_BASE}
        mov qword ptr [rbx], 5
        mov rax, 5
        mov rcx, 9
        cmpxchg [rbx], rcx
        mov rax, 123
        cmpxchg [rbx], rcx
        hlt""", {DATA_BASE: b"\x00" * 0x1000}),
    ("xadd_inc_dec_mem", f"""
        mov rbx, {DATA_BASE}
        mov qword ptr [rbx], 10
        mov rax, 32
        xadd [rbx], rax
        inc qword ptr [rbx]
        dec word ptr [rbx+8]
        neg dword ptr [rbx+16]
        not byte ptr [rbx+24]
        hlt""", {DATA_BASE: b"\x11" * 32 + b"\x00" * 0x100}),
]


@pytest.mark.parametrize("name,snippet,data",
                         [(c[0], c[1], c[2]) for c in DIFF_CASES])
def test_device_vs_oracle_mem_cases(name, snippet, data):
    assert_matches_oracle(snippet, data=data)


def test_wrfsbase_noncanonical_faults():
    """Hardware #GPs on a non-canonical wr{fs,gs}base source; both
    engines surface it through the non-canonical fault seam (review
    fix) instead of silently loading the base."""
    runner = make_runner(
        "mov rax, 0x8000000000000000\nwrfsbase rax\nhlt", n_lanes=2)
    status = runner.run()
    for lane in range(2):
        assert StatusCode(int(status[lane])) == StatusCode.PAGE_FAULT
        assert int(np.asarray(runner.machine.fault_gva)[lane]) \
            == 0x8000000000000000
    # the base must NOT have been loaded
    assert int(np.asarray(runner.machine.fs_base)[0]) == 0


def test_syscall_transition():
    asm = """
    mov r14, 0x123
    syscall
    hlt
    .org 0x40
    mov rax, 0x5CA11
    hlt
    """
    pad = CODE_BASE + 0x40
    emu = run_emu(asm, regs={"lstar": pad, "sfmask": 0x700})
    runner, status = run_tpu(asm, regs={"lstar": pad, "sfmask": 0x700})
    g = np.asarray(runner.machine.gpr)
    assert int(np.asarray(runner.machine.rip)[0]) == emu.rip
    assert int(g[0, 1]) == emu.gpr[1]    # rcx = return rip
    assert int(g[0, 11]) == emu.gpr[11]  # r11 = saved rflags
    rf = np.asarray(runner.machine.rflags)
    assert int(rf[0]) & RF_CMP == emu.rflags & RF_CMP


def test_rdrand_deterministic():
    asm = "rdrand rax\nrdrand rbx\nrdrand rcx\nhlt"
    runner, _ = run_tpu(asm)
    emu = run_emu(asm)
    g = np.asarray(runner.machine.gpr)
    for lane in range(2):
        assert int(g[lane, 0]) == emu.gpr[0]
        assert int(g[lane, 3]) == emu.gpr[3]
        assert int(g[lane, 1]) == emu.gpr[1]


# ---------------------------------------------------------------------------
# 3. batch semantics: divergent lanes, limits, restore
# ---------------------------------------------------------------------------

def test_divergent_lanes():
    """Each lane branches on its own input -> different paths, different
    results, all correct (the core lockstep-with-masking property)."""
    asm = f"""
    mov rbx, {DATA_BASE}
    mov rax, [rbx]
    cmp rax, 4
    jb small
    mov rcx, 0xB1B
    jmp done
    small:
    mov rcx, 0xA1A
    done:
    imul rdx, rax, 100
    hlt
    """
    runner = make_runner(asm, data={DATA_BASE: b"\x00" * 0x1000}, n_lanes=8)
    view = runner.view()
    for lane in range(8):
        view.virt_write(lane, DATA_BASE, lane.to_bytes(8, "little"))
    runner.push(view)
    status = runner.run()
    g = np.asarray(runner.machine.gpr)
    for lane in range(8):
        assert StatusCode(int(status[lane])) == StatusCode.CRASH
        want_rcx = 0xA1A if lane < 4 else 0xB1B
        assert int(g[lane, 1]) == want_rcx, f"lane {lane}"
        assert int(g[lane, 2]) == lane * 100
    # divergent lanes produce divergent coverage bitmaps
    cov = np.asarray(runner.machine.cov)
    assert not np.array_equal(cov[0], cov[7])


def test_instruction_limit_timeout():
    runner, status = run_tpu("spin: jmp spin", n_lanes=2, limit=500)
    for lane in range(2):
        assert StatusCode(int(status[lane])) == StatusCode.TIMEDOUT
    icount = np.asarray(runner.machine.icount)
    assert int(icount[0]) == 500


def test_restore_roundtrip_batch():
    asm = f"""
    mov rbx, {DATA_BASE}
    add qword ptr [rbx], 1
    mov rax, [rbx]
    hlt
    """
    runner = make_runner(asm, data={DATA_BASE: b"\x00" * 0x1000}, n_lanes=4)
    runner.run()
    first = int(np.asarray(runner.machine.gpr)[0, 0])
    runner.restore()
    runner.run()
    second = int(np.asarray(runner.machine.gpr)[0, 0])
    assert first == second == 1  # memory rolled back between runs
    runner.restore()
    ov = runner.machine.overlay
    assert int(np.asarray(ov.count)[0]) == 0


@pytest.mark.parametrize("asm,data,gva,retired", [
    ("mov rax, [0x1234]\nhlt", None, 0x1234, 0),
    # a non-present hole 2 MiB past a mapped page, after a load that hit
    (f"mov rbx, {DATA_BASE}\nmov rax, [rbx]\n"
     f"mov rcx, [rbx + 0x200000]\nint3",
     {DATA_BASE: b"\x55" * 0x1000}, DATA_BASE + 0x200000, 2),
], ids=["null-page", "hole-after-load"])
def test_page_fault_reported(asm, data, gva, retired):
    runner, status = run_tpu(asm, data=data, n_lanes=1)
    assert StatusCode(int(status[0])) == StatusCode.PAGE_FAULT
    assert int(np.asarray(runner.machine.fault_gva)[0]) == gva
    assert int(np.asarray(runner.machine.fault_write)[0]) == 0
    assert int(np.asarray(runner.machine.icount)[0]) == retired


def test_divide_error_reported():
    runner, status = run_tpu("xor rcx, rcx\nmov rax, 5\ndiv rcx\nhlt",
                             n_lanes=1)
    assert StatusCode(int(status[0])) == StatusCode.DIVIDE_ERROR


def test_div128_host_fallback():
    """64-bit div with rdx != 0 exceeds the device path -> oracle fallback."""
    asm = """
    mov rdx, 1
    mov rax, 0
    mov rcx, 16
    div rcx
    hlt
    """
    runner, status = run_tpu(asm, n_lanes=2)
    emu = run_emu(asm)
    assert runner.stats["fallbacks"] >= 1
    g = np.asarray(runner.machine.gpr)
    for lane in range(2):
        assert StatusCode(int(status[lane])) == StatusCode.CRASH
        assert int(g[lane, 0]) == emu.gpr[0] == 0x1000000000000000
        assert int(g[lane, 2]) == emu.gpr[2]


@pytest.mark.parametrize("leaf,subleaf", [
    (0x0, 0),             # vendor string
    (0x1, 0),             # feature bits
    (0x9, 0),             # in-range basic leaf absent from the table
    (0x1234, 0),          # out-of-range basic -> highest basic leaf
    (0x40000000, 0),      # hypervisor range -> zeros
    (0x80000001, 0),      # extended features
    (0x1, 7),             # nonzero subleaf -> (leaf, 0) fallback
])
def test_cpuid_on_device(leaf, subleaf):
    """CPUID executes on the device (no oracle fallback) and matches the
    oracle's table + fallback chain for every class of leaf."""
    asm = f"mov eax, {leaf:#x}\nmov ecx, {subleaf:#x}\ncpuid\nhlt"
    runner, status = run_tpu(asm, n_lanes=2)
    emu = run_emu(asm)
    g = np.asarray(runner.machine.gpr)
    assert runner.stats["fallbacks"] == 0
    for lane in range(2):
        for reg in (0, 1, 2, 3):
            assert int(g[lane, reg]) == emu.gpr[reg], \
                f"gpr{reg}: tpu={int(g[lane, reg]):#x} emu={emu.gpr[reg]:#x}"


def test_coverage_bitmap_matches_unique_rips():
    asm = """
    mov rax, 3
    again:
    dec rax
    jnz again
    hlt
    """
    runner, _ = run_tpu(asm, n_lanes=1)
    cov = np.asarray(runner.machine.cov)[0]
    rips = sorted(runner.cache.rips_of_bits(cov))
    # mov, dec, jnz, hlt = 4 unique rips regardless of loop count
    assert len(rips) == 4
    assert rips[0] == CODE_BASE


def test_edge_bitmap_set_on_branches():
    runner, _ = run_tpu("jmp fwd\nnop\nfwd: hlt", n_lanes=1)
    edge = np.asarray(runner.machine.edge)[0]
    assert edge.sum() > 0


def test_iretq_matches_oracle():
    """iretq is serviced by the per-lane oracle fallback (UNSUPPORTED on
    device, like the reference's bochs-backs-KVM split); end state must
    match a pure-oracle run."""
    from tests.test_emu import IRETQ_ASM

    assert_matches_oracle(IRETQ_ASM)


def test_rdmsr_wrmsr_match_oracle():
    """rdmsr/wrmsr are serviced by the oracle fallback; MSR-backed fields
    written there must round-trip (and reach the device mirror)."""
    asm = """
    mov ecx, 0xC0000082
    rdmsr
    shl rdx, 32
    or rax, rdx
    mov r12, rax
    mov ecx, 0xC0000102
    mov eax, 0x11223344
    mov edx, 0x55667788
    wrmsr
    xor eax, eax
    xor edx, edx
    mov ecx, 0xC0000102
    rdmsr
    hlt
    """
    regs = {"lstar": 0xFFFFF00012345678}
    runner, emu = assert_matches_oracle(asm, regs=regs)
    assert emu.gpr[12] == 0xFFFFF00012345678          # rdmsr read lstar
    assert emu.gpr[0] == 0x11223344                   # wrmsr round-trip lo
    assert emu.gpr[2] == 0x55667788                   # hi
    kgs = np.asarray(runner.machine.kernel_gs_base)
    assert int(kgs[0]) == 0x5566778811223344          # device mirror updated


def test_wrmsr_efer_persists_across_fallbacks():
    """EFER is device-mirrored: a wrmsr through one oracle fallback must be
    visible to a later rdmsr fallback (each fallback rebuilds the oracle
    CPU from the mirror)."""
    asm = """
    mov ecx, 0xC0000080
    rdmsr
    or eax, 0x4000
    wrmsr
    xor eax, eax
    xor edx, edx
    mov ecx, 0xC0000080
    rdmsr
    hlt
    """
    runner, emu = assert_matches_oracle(asm)
    assert emu.gpr[0] & 0x4000
