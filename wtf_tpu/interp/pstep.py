"""Pallas fused-step fast path: K interpreter steps in ONE kernel dispatch.

PERF.md's performance model says the batched step's wall-clock on real TPUs
is proportional to the NUMBER of unfusable gather/scatter kernels XLA emits
(decode probe, uop fetch, page walks, window reads, coverage scatters —
~13 per step), not to FLOPs.  This module is open lever 3: the hot integer
core of the interpreter runs as one Pallas kernel that advances every lane
up to K instructions per dispatch, so a hot stretch costs ONE kernel launch
instead of ~13 per instruction.  It is the de-risking prototype for the
fully fused interpreter — the persistent-kernel shape Concordia uses to keep
inference inside one long-lived device kernel, and the Linear-Algebraic
Hypervisor's "interpretation belongs inside the accelerator's execution
model" argument, landed as shippable code.

Hot subset — now including the MEMORY path: the 4-level page walk
(`translate_vec_l`'s semantics, scalar per lane) and the delta-overlay
probe run INSIDE the kernel, so memory-operand forms execute in-kernel:

  decode-cache hash probe, uop fetch, breakpoint/bp_skip gate, the
  overlay-aware SMC byte compare, MOV (register, immediate AND memory
  operands, incl. movzx/movsx), LEA, the integer ALU class (reg/imm/mem
  src, reg/mem dst — CMP/TEST included), SHIFT/ROT (incl. shld/shrd and
  mem-dst forms), MUL (2/3-op imul + widening mul/imul), UNARY (reg/mem),
  SETCC (reg/mem), CMOVCC (reg/mem src), Jcc/JMP (imm/reg/mem targets),
  the stack ops PUSH/POP/CALL/RET, condition evaluation, coverage +
  edge-hash bits, the icount/limit (TIMEDOUT) bookkeeping, and the device
  counter block.  Guest stores commit straight into the lane's delta
  overlay (allocation included) inside the kernel.

Anything else — strings, DIV, BT/BITSCAN/BSWAP/XCHG/CMPXCHG, SSE/x87,
system instructions, an armed breakpoint, code bytes that diverge from the
decode-time raw bytes — PARKS the lane BEFORE executing: state is
untouched and status becomes NEEDS_XLA.  A lane whose memory access would
FAULT (non-present / non-writable walk, out-of-range store frame, overlay
slot exhaustion) also parks — the XLA leg then re-executes that one
instruction on the precise path and raises the exact PAGE_FAULT /
OVERLAY_FULL status, fault address and counters.  The two park families
are attributed separately (CTR_PARK_SUBSET vs CTR_PARK_MEM) so occupancy
loss is diagnosable from telemetry.  The runner's chunk ladder
(interp/runner.py) resumes parked lanes with a short XLA chunk and
re-enters the kernel, so the fused path is a pure fast path layered UNDER
the existing executor: every instruction retires through exactly one of
the two engines and the final state is bit-exact vs the XLA-only ladder
(tests/test_pstep.py pins this differentially, including the
park-and-resume seam, the in-kernel walk vs translate_vec_l, and
in-kernel stores vs the overlay word-window path).

Authoring notes (TPU target, validated via interpret=True on CPU):
  * all arithmetic is u32 limb math (interp/limbs.py) — Pallas TPU kernels
    cannot hold 64-bit integers, which is exactly why PR 2 packed the hot
    state; every u64-typed machine leaf (incl. cr3, the overlay word/valid
    planes) crosses into the kernel through a free bitcast at the wrapper
    seam
  * the grid iterates lanes; per-lane work is scalar (dynamic-index loads
    from the uop table / image / overlay implement the gather emulation
    the XLA path pays per-step dispatches for), with the K-step fori_loop
    carrying the register file as a value and the overlay living in
    in+out refs (copy-in at kernel start, RMW in place) so loads observe
    earlier in-kernel stores
  * tier-1 runs the kernel under `interpret=True` on the CPU platform.
    The TPU lowering REFUSES the kernel as written (every per-lane spec
    is a rank-1 `(1,)` block on a `[n_lanes]` array; behind that wait the
    whole-image VMEM blocks — ROADMAP S2, pinned by
    tests/test_chip_compile.py), so on a TPU `interpret=None` surfaces
    the compiler's own message and `--fused-step auto` stays off
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from wtf_tpu.core.results import StatusCode
from wtf_tpu.cpu import uops as U
from wtf_tpu.interp import limbs as L
from wtf_tpu.interp import step as S
from wtf_tpu.interp.machine import (
    CTR_DECODE_MISS, CTR_FUSED, CTR_INSTR, CTR_PARK_MEM, CTR_PARK_SUBSET,
    CTR_SWEPT, Machine, N_CTRS,
)
from wtf_tpu.interp.uoptable import (
    F_A32, F_BASE_REG, F_COND, F_DST_KIND, F_DST_REG, F_IDX_REG, F_LENGTH,
    F_OPC, F_OPSIZE, F_SCALE, F_SEG, F_SEXT, F_SRCSIZE, F_SRC_KIND,
    F_SRC_REG, F_SUB, M_BP, M_PFN0, M_PFN1, PROBES, UopTable,
)
from wtf_tpu.mem.physmem import MemImage, PAGE_WORDS, lane_image

_RUNNING = int(StatusCode.RUNNING)
_NEED_DECODE = int(StatusCode.NEED_DECODE)
_NEEDS_XLA = int(StatusCode.NEEDS_XLA)
_TIMEDOUT = int(StatusCode.TIMEDOUT)

# paging constants (mem/paging.py, as u32 limb pairs at trace time)
_PHYS_MASK = 0x000F_FFFF_FFFF_F000
_PHYS_MASK_1G = 0x000F_FFFF_C000_0000
_PHYS_MASK_2M = 0x000F_FFFF_FFE0_0000
_PFN_OOB = 0x7FFFFFFF  # mem/overlay.py sentinel: never matches, slot 0

# The opclass set this kernel CLAIMS to execute in-kernel.  Since the
# page walk and overlay live in-kernel, the claim is a PURE opclass
# test — memory operands are fully fused, and a lane leaves the kernel
# only on DYNAMIC outcomes (failing/unwritable walk, overlay
# exhaustion, SMC-risk code, an armed breakpoint), not on static
# operand shapes.  The static analyzer (wtf_tpu/analysis/parity.py)
# AST-checks this claim against the actual `hot_class` predicate AND
# against step.py's dispatch / `unsupported` expressions, so the two
# engines cannot drift silently.
FUSED_OPCLASSES = frozenset({
    "NOP", "FENCE", "MOV", "LEA", "ALU", "UNARY", "SETCC", "CMOVCC",
    "JCC", "JMP", "SHIFT", "MUL", "PUSH", "POP", "CALL", "RET",
})

# memoized jitted entry points, keyed (k_steps, interpret) /
# (n_steps, donate); jit itself re-specializes per array shapes
_FUSED_CACHE: dict = {}
_RESUME_CACHE: dict = {}


def _u32(x) -> jnp.ndarray:
    return jnp.uint32(x)


def _pair(v: int):
    return (_u32(v & 0xFFFFFFFF), _u32((v >> 32) & 0xFFFFFFFF))


def _build_kernel(k_steps: int, n_fields: int, hash_size: int,
                  nframes: int, ebits: int, capacity: int):
    """The kernel body, specialized on the static table geometry.
    `capacity` is the per-lane overlay slot count."""
    from jax.experimental import pallas as pl

    hmask = hash_size - 1
    vwords = PAGE_WORDS // 4        # u32-packed valid bytes per page

    def kernel(hash_ref, trip_ref, tmeta_ref, tmu_ref, pages_ref, ftab_ref,
               limit_ref, tenant_ref, cr3_ref, fs_ref, gs_ref,
               gpr_in, rip_in, rf_in, st_in, ic_in, bp_in, ctr_in, cov_in,
               edge_in, ovpfn_in, ovdata_in, ovvalid_in, ovcount_in,
               gpr_out, rip_out, rf_out, st_out, ic_out, bp_out, ctr_out,
               cov_out, edge_out, ovpfn_out, ovdata_out, ovvalid_out,
               ovcount_out):
        # state the loop RMWs lives in the out refs: copy through once,
        # then every read below observes earlier in-kernel stores
        cov_out[...] = cov_in[...]
        edge_out[...] = edge_in[...]
        ovpfn_out[...] = ovpfn_in[...]
        ovdata_out[...] = ovdata_in[...]
        ovvalid_out[...] = ovvalid_in[...]
        ovcount_out[...] = ovcount_in[...]

        limit_l = (limit_ref[0], limit_ref[1])
        limit_on = (limit_ref[0] | limit_ref[1]) != _u32(0)
        z = _u32(0)
        one = _u32(1)
        zero2 = (z, z)
        cr3_l = (cr3_ref[0, 0], cr3_ref[0, 1])
        fs_l = (fs_ref[0, 0], fs_ref[0, 1])
        gs_l = (gs_ref[0, 0], gs_ref[0, 1])
        PM = _pair(_PHYS_MASK)
        PM1G = _pair(_PHYS_MASK_1G)
        PM2M = _pair(_PHYS_MASK_2M)
        iota_slots = lax.iota(jnp.int32, capacity)
        # the lane's base-image id (wtf_tpu/tenancy): selects the frame-
        # table row and tags the decode-probe key, exactly like step_lane
        tenant = tenant_ref[0]
        ttag = tenant.astype(jnp.uint32) << 16      # bit 48 = hi limb bit 16

        def probe(rip_l):
            """uop_lookup's open-addressed probe, one slot at a time (the
            scalar gather emulation of the XLA path's 8-slot row gather;
            first live match wins, same result by insertion uniqueness).
            Probes the tenant-tagged key, like step_lane — the key limbs
            ride in the hash row, so no dependent rip_l chase."""
            key_l = (rip_l[0], rip_l[1] ^ ttag)
            h_lo, _ = L.splitmix64(key_l)

            def body(k, found):
                slot = ((h_lo + _u32(0) + k.astype(jnp.uint32))
                        & _u32(hmask)).astype(jnp.int32)
                e = hash_ref[slot, 0]
                ok = ((e >= 0)
                      & (hash_ref[slot, 1].astype(jnp.uint32) == key_l[0])
                      & (hash_ref[slot, 2].astype(jnp.uint32) == key_l[1]))
                return jnp.where((found < 0) & ok, e, found)

            return lax.fori_loop(0, PROBES, body, jnp.int32(-1))

        def slot_of(pfn):
            """frame_slot: pfn -> image page slot (0 = absent/zero page),
            through the lane's tenant row of the stacked frame table."""
            in_range = (pfn >= 0) & (pfn < nframes)
            safe = jnp.clip(pfn, 0, nframes - 1)
            return jnp.where(in_range, ftab_ref[tenant, safe], 0)

        def ov_lookup(pfn):
            """overlay.lookup: first slot holding `pfn` (min-rank, not
            argmax — argmax's reduce would run an s64 iota under x64)."""
            eq = ovpfn_out[0] == pfn
            rank = jnp.where(eq, iota_slots, jnp.int32(capacity))
            first = jnp.min(rank)
            return jnp.minimum(first, capacity - 1), first < capacity

        def read_word(pfn, widx):
            """One overlay-aware aligned u64 word as a u32 pair — the
            in-kernel form of overlay.read_words_vec (delta rows: a word
            routes to the overlay only when its valid byte is set)."""
            row, hit = ov_lookup(pfn)
            vword = ovvalid_out[0, row, widx >> 2]
            sh8 = ((widx & 3) * 8).astype(jnp.uint32)
            use_ov = hit & (((vword >> sh8) & _u32(0xFF)) != z)
            slot = slot_of(pfn)
            lo = jnp.where(use_ov, ovdata_out[0, row, 2 * widx],
                           pages_ref[slot, 2 * widx])
            hi = jnp.where(use_ov, ovdata_out[0, row, 2 * widx + 1],
                           pages_ref[slot, 2 * widx + 1])
            return lo, hi

        def pfn_of(addr_l):
            """split_gpa: physical address -> int32 pfn with the OOB
            sentinel (never matches an overlay row; slot 0 image page)."""
            p = L.shr64_const(addr_l, 12)
            in_range = (p[1] == z) & (p[0] < _u32(nframes))
            return jnp.where(in_range, p[0],
                             _u32(_PFN_OOB)).astype(jnp.int32)

        def read_phys_u64(addr_l):
            widx = ((addr_l[0] & _u32(0xFFF)) >> 3).astype(jnp.int32)
            return read_word(pfn_of(addr_l), widx)

        def walk(gva_l):
            """translate_vec_l's 4-level long-mode walk, scalar per lane
            on u32 limbs: PTE reads go through the lane's overlay (guest-
            modified tables honored), 1GiB/2MiB large pages supported,
            A/D bits not set (the documented divergence).  Returns
            (gpa pair, ok, writable)."""
            top = L.shr64_const(gva_l, 47)
            ok = (((top[0] == z) & (top[1] == z))
                  | ((top[0] == _u32(0x1FFFF)) & (top[1] == z)))
            writable = jnp.bool_(True)
            done = jnp.bool_(False)
            gpa = zero2
            table = L.and64(cr3_l, PM)
            for shift, large_mask, page_bits in (
                    (39, None, 0), (30, PM1G, 30), (21, PM2M, 21),
                    (12, None, 0)):
                idx9 = L.shr64_const(gva_l, shift)[0] & _u32(0x1FF)
                entry = read_phys_u64(L.add64(table, (idx9 << 3, z)))
                present = (entry[0] & one) != z
                ok = ok & (done | present)
                writable = writable & (done | ((entry[0] & _u32(2)) != z))
                if large_mask is not None:
                    is_large = present & ((entry[0] & _u32(0x80)) != z) \
                        & ~done
                    pmask = _pair((1 << page_bits) - 1)
                    large_gpa = L.or64(L.and64(entry, large_mask),
                                       L.and64(gva_l, pmask))
                    gpa = L.where64(is_large, large_gpa, gpa)
                    done = done | is_large
                if shift == 12:
                    leaf = L.or64(L.and64(entry, PM),
                                  (gva_l[0] & _u32(0xFFF), z))
                    gpa = L.where64(done, gpa, leaf)
                table = L.and64(entry, PM)
            return gpa, ok, writable

        def load_win16_pfn(pfn_a, pfn_b, off):
            """16 bytes starting at page offset `off` (u32) of pfn_a,
            straddling into pfn_b — 3 aligned words + shifts, exactly
            overlay.load_window3/extract_pair but overlay-aware per
            word."""
            w0 = (off >> 3).astype(jnp.int32)
            words = []
            for j in range(3):
                on_first = (w0 + j) < PAGE_WORDS
                widx = jnp.where(on_first, w0 + j, w0 + j - PAGE_WORDS)
                pfn = jnp.where(on_first, pfn_a, pfn_b)
                words.append(read_word(pfn, widx))
            sh = (off & _u32(7)) * _u32(8)
            inv = _u32(64) - sh
            lo = L.or64(L.shr64(words[0], sh), L.shl64(words[1], inv))
            hi = L.or64(L.shr64(words[1], sh), L.shl64(words[2], inv))
            return lo, hi

        def load_win16(gpa0_l, gpa1_l):
            return load_win16_pfn(pfn_of(gpa0_l), pfn_of(gpa1_l),
                                  gpa0_l[0] & _u32(0xFFF))

        def step_body(_, carry):
            (gl, rip_l, rf_lo, status, ic_l, bpskip, d_instr, d_miss,
             d_ps, d_pm) = carry
            run = status == jnp.int32(_RUNNING)

            # -- 1. decode-cache probe (identical to step.uop_lookup) ----
            idx = probe(rip_l)
            miss = run & (idx < 0)
            idxc = jnp.maximum(idx, 0)
            f = tmeta_ref[idxc]                     # [NF+3] i32 row
            mu = tmu_ref[idxc]                      # [8] u32 row
            opc = f[F_OPC]
            sub = f[F_SUB]
            cond = f[F_COND]
            length = f[F_LENGTH]
            opsize = f[F_OPSIZE]
            srcsize0 = f[F_SRCSIZE]
            sext_f = f[F_SEXT]
            dk, dr = f[F_DST_KIND], f[F_DST_REG]
            sk, sr = f[F_SRC_KIND], f[F_SRC_REG]
            disp_l = (mu[0], mu[1])
            imm_l = (mu[2], mu[3])
            raw_lo_l = (mu[4], mu[5])
            raw_hi_l = (mu[6], mu[7])

            # -- 2. breakpoint gate (honoring bp_skip, like step_lane) ---
            at_bp = run & ~miss & (f[M_BP] == 1) & (bpskip == 0)

            # -- 3. hot-subset eligibility: the claimed opclasses
            # (FUSED_OPCLASSES); memory operands are fair game now that
            # the walk + overlay live in-kernel.  Everything else parks.
            is_mov = opc == U.OPC_MOV
            is_lea = opc == U.OPC_LEA
            is_alu = opc == U.OPC_ALU
            is_shift = opc == U.OPC_SHIFT
            is_mul = opc == U.OPC_MUL
            is_unary = opc == U.OPC_UNARY
            is_setcc = opc == U.OPC_SETCC
            is_cmov = opc == U.OPC_CMOVCC
            is_jcc = opc == U.OPC_JCC
            is_jmp = opc == U.OPC_JMP
            is_push = opc == U.OPC_PUSH
            is_pop = opc == U.OPC_POP
            is_call = opc == U.OPC_CALL
            is_ret = opc == U.OPC_RET
            hot_class = (
                (opc == U.OPC_NOP) | (opc == U.OPC_FENCE)
                | is_mov | is_lea | is_alu | is_shift | is_mul
                | is_unary | is_setcc | is_cmov | is_jcc | is_jmp
                | is_push | is_pop | is_call | is_ret)

            # -- 4. addresses (ported paths of step_lane, u32 limbs) -----
            next_rip_l = L.add64_u32(rip_l, length.astype(jnp.uint32))
            base_val_l = L.where64(f[F_BASE_REG] == U.REG_RIP, next_rip_l,
                                   S._read64_l(gl, f[F_BASE_REG]))
            idx_val_l = S._scale_idx_l(S._read64_l(gl, f[F_IDX_REG]),
                                       f[F_SCALE])
            seg_l = L.select64(
                [f[F_SEG] == U.SEG_FS, f[F_SEG] == U.SEG_GS],
                [fs_l, gs_l], zero2)
            ea_l = S.ea_limb(disp_l, base_val_l, idx_val_l, seg_l,
                             f[F_A32])
            rsp_l = (gl[4, 0], gl[4, 1])
            srcsize = jnp.where(srcsize0 == 0, opsize, srcsize0)
            push_size = jnp.where(is_call, jnp.int32(8), opsize)

            l1_need = run & ~miss & hot_class & (
                (sk == U.K_MEM) | is_pop | is_ret)
            l1_addr = L.where64(is_pop | is_ret, rsp_l, ea_l)
            l1_size = jnp.where(is_ret, jnp.int32(8),
                                jnp.where(is_pop, opsize, srcsize))
            # store-only destinations (MOV/SETCC/POP) never read [mem],
            # so only the read-modify classes issue the l2 load — their
            # fault is then a WRITE fault, matching step_lane
            l2_need = run & ~miss & hot_class & (dk == U.K_MEM) \
                & (is_alu | is_shift | is_unary)
            st_addr = L.where64(is_push | is_call,
                                L.sub64(rsp_l,
                                        (push_size.astype(jnp.uint32), z)),
                                ea_l)
            # stores and pushes span the same byte count (step.py's
            # st_size only diverges for x87 stores, which are not fused)
            st_size = push_size

            def span_last(addr_l, size):
                return L.add64_u32(addr_l, (size - 1).astype(jnp.uint32))

            # -- 4a. six in-kernel page walks (first/last byte of the
            # l1 load, the l2 read-modify operand, and the store) --------
            l1g0, l1ok0, _w0 = walk(l1_addr)
            l1g1, l1ok1, _w1 = walk(span_last(l1_addr, l1_size))
            l2g0, l2ok0, _w2 = walk(ea_l)
            l2g1, l2ok1, _w3 = walk(span_last(ea_l, opsize))
            stg0, stok0, stw0 = walk(st_addr)
            stg1, stok1, stw1 = walk(span_last(st_addr, st_size))

            # -- 4b. SMC check through the overlay (live code bytes vs
            # decode-time raw — exactly step_lane's verdict; in-kernel
            # stores that dirty a code page are caught the same way) -----
            code_off = rip_l[0] & _u32(0xFFF)
            code_crosses = (code_off + _u32(16)) > _u32(4096)
            pfn0c, pfn1c = f[M_PFN0], f[M_PFN1]
            code_lo, code_hi = load_win16_pfn(
                pfn0c, jnp.where(code_crosses, pfn1c, pfn0c), code_off)
            lm_lo = L.size_mask(jnp.minimum(length, 8))
            lm_hi = L.size_mask(jnp.maximum(length - 8, 0))
            smc_risk = (
                ~L.is_zero64(L.and64(L.xor64(code_lo, raw_lo_l), lm_lo))
                | ~L.is_zero64(L.and64(L.xor64(code_hi, raw_hi_l), lm_hi)))

            # -- 4c. operand loads through the overlay -------------------
            l1_pair = load_win16(l1g0, l1g1)[0]     # low 8 bytes
            l2_pair = load_win16(l2g0, l2g1)[0]

            # -- 5. execute (ported paths of step_lane, scalar per lane) -
            src_raw_l = L.where64(
                sk == U.K_REG, S._read_reg_l(gl, sr, srcsize),
                L.where64(sk == U.K_MEM, L.zext(l1_pair, srcsize), zero2))
            src_ext_l = L.where64(
                sext_f == 1, L.zext(L.sext(src_raw_l, srcsize), opsize),
                L.zext(src_raw_l, opsize))
            src_val_l = L.where64(sk == U.K_IMM, L.zext(imm_l, opsize),
                                  src_ext_l)
            dst_val_l = L.where64(
                dk == U.K_REG, S._read_reg_l(gl, dr, opsize),
                L.where64(dk == U.K_MEM, L.zext(l2_pair, opsize), zero2))
            cf_in = (rf_lo & _u32(L.CF)) != z
            alu_r, alu_rf_lo, alu_writes = S.alu_limb(
                sub, dst_val_l, src_val_l, cf_in, opsize, rf_lo)
            un_r, un_rf_lo = S.unary_limb(sub, dst_val_l, cf_in, opsize,
                                          rf_lo)
            filler_l = S._read_reg_l(gl, sr, opsize)
            sh_r, sh_rf_lo, sh_writes = S.shift_limb(
                sub, sext_f, dst_val_l, filler_l, gl[1, 0], src_val_l[0],
                imm_l[0], cf_in, opsize, rf_lo)
            is_mul2 = sub == U.MUL_2OP
            mul_r1, mul_r2, mul_rf_lo = S.mul_limb(
                sub, sext_f, dst_val_l, src_val_l,
                S._read_reg_l(gl, jnp.int32(0), opsize), imm_l, opsize,
                rf_lo)
            rcx_l = (gl[1, 0], gl[1, 1])
            cc = L.eval_cond(rf_lo, rcx_l, cond)
            cc01 = (jnp.where(cc, one, z), z)
            jcc_t = L.add64(next_rip_l, imm_l)
            jmp_t = L.where64(sk == U.K_IMM, jcc_t, src_val_l)
            pop_val = L.zext(l1_pair, opsize)

            # -- 5a. store plan + park decision (BEFORE any mutation) ----
            mem_writes = (is_mov | (is_alu & alu_writes)
                          | (is_shift & sh_writes) | is_unary | is_setcc
                          | is_pop)
            st_need = run & ~miss & hot_class & (
                ((dk == U.K_MEM) & mem_writes) | is_push | is_call)
            s_off = stg0[0] & _u32(0xFFF)
            st_size_u = st_size.astype(jnp.uint32)
            crosses = (s_off + st_size_u) > _u32(4096)
            s_pfn0 = pfn_of(stg0)
            s_pfn1 = pfn_of(stg1)
            row0, hit0 = ov_lookup(s_pfn0)
            row1, hit1 = ov_lookup(s_pfn1)
            # aliased mappings: a virtual page crossing can land both
            # halves on ONE physical frame — the second half must reuse
            # the first's (possibly just-claimed) row, never a duplicate
            # (overlay lookup takes the first match; step.py's second
            # ensure_page hits the row the first one claimed)
            st_alias = s_pfn1 == s_pfn0
            oob = (s_pfn0 == _PFN_OOB) | (crosses & (s_pfn1 == _PFN_OOB))
            need_new = ((~hit0).astype(jnp.int32)
                        + (crosses & ~hit1 & ~st_alias).astype(jnp.int32))
            cnt_now = ovcount_out[0]
            can_alloc = (cnt_now + need_new) <= capacity

            f_l1 = l1_need & ~(l1ok0 & l1ok1)
            f_l2 = l2_need & ~(l2ok0 & l2ok1)
            f_st = st_need & ~(stok0 & stok1 & stw0 & stw1)
            mem_park = f_l1 | f_l2 | f_st | (st_need & (oob | ~can_alloc))

            park = run & ~miss & (at_bp | ~hot_class | smc_risk | mem_park)
            commit = run & ~miss & ~park
            # park-reason attribution: a MEM park is a lane the subset
            # would have run (hot class, clean code, no bp) that the
            # memory path diverted — the occupancy-loss split telemetry
            # and bench.py --fused-compare report
            park_mem_evt = (run & ~miss & ~at_bp & hot_class & ~smc_risk
                            & mem_park)
            park_sub_evt = park & ~park_mem_evt
            do_store = commit & st_need

            # -- 5b. in-kernel store: overlay slot claim (delta rows:
            # claiming clears word validity, never copies the base page)
            # + the <=8-byte 3-word masked read-modify-write of
            # overlay.store_window3 ---------------------------------------
            @pl.when(do_store)
            def _store():
                cnt0 = ovcount_out[0]
                alloc0 = ~hit0
                rowa = jnp.where(alloc0, cnt0, row0)

                @pl.when(alloc0)
                def _():
                    ovpfn_out[0, rowa] = s_pfn0
                    ovvalid_out[0, rowa, :] = jnp.zeros((vwords,),
                                                        jnp.uint32)

                cnt1 = cnt0 + alloc0.astype(jnp.int32)
                alloc1 = crosses & ~hit1 & ~st_alias
                rowb = jnp.where(
                    alloc1, cnt1,
                    jnp.where(crosses & hit1, row1, rowa))

                @pl.when(alloc1)
                def _():
                    ovpfn_out[0, rowb] = s_pfn1
                    ovvalid_out[0, rowb, :] = jnp.zeros((vwords,),
                                                        jnp.uint32)

                ovcount_out[0] = cnt1 + alloc1.astype(jnp.int32)

                st_val = L.select64(
                    [is_mov | is_push, is_alu, is_shift, is_unary,
                     is_setcc, is_pop, is_call],
                    [src_val_l, alu_r, sh_r, un_r, cc01, pop_val,
                     next_rip_l], zero2)
                sh = (s_off & _u32(7)) * _u32(8)
                end_bit = sh + st_size_u * _u32(8)
                v0 = L.shl64(st_val, sh)
                v1 = L.shr64(st_val, _u32(64) - sh)
                w0i = (s_off >> 3).astype(jnp.int32)
                for j, vj in enumerate((v0, v1, zero2)):
                    lo_bit = _u32(64 * j)
                    start_in = jnp.maximum(sh, lo_bit)
                    end_in = jnp.minimum(end_bit, lo_bit + _u32(64))
                    has = end_in > start_in
                    n_bits = jnp.where(has, end_in - start_in, z)
                    off_in = jnp.where(has, start_in - lo_bit, z)
                    # n_bits == 64 wraps (1 << 64 -> 0) to all-ones
                    mask = L.shl64(
                        L.sub64(L.shl64((one, z), n_bits), (one, z)),
                        off_in)
                    on_first = (w0i + j) < PAGE_WORDS
                    widx = jnp.where(on_first, w0i + j,
                                     w0i + j - PAGE_WORDS)
                    row = jnp.where(on_first, rowa, rowb)
                    pfn_j = jnp.where(on_first, s_pfn0, s_pfn1)
                    vword = ovvalid_out[0, row, widx >> 2]
                    sh8 = ((widx & 3) * 8).astype(jnp.uint32)
                    was_valid = ((vword >> sh8) & _u32(0xFF)) != z
                    slot = slot_of(pfn_j)
                    old_lo = jnp.where(was_valid,
                                       ovdata_out[0, row, 2 * widx],
                                       pages_ref[slot, 2 * widx])
                    old_hi = jnp.where(was_valid,
                                       ovdata_out[0, row, 2 * widx + 1],
                                       pages_ref[slot, 2 * widx + 1])
                    touched = (mask[0] | mask[1]) != z
                    # an untouched word writes `old` back (a no-op by
                    # value), so the block needs no nested predication
                    ovdata_out[0, row, 2 * widx] = \
                        (old_lo & ~mask[0]) | (vj[0] & mask[0])
                    ovdata_out[0, row, 2 * widx + 1] = \
                        (old_hi & ~mask[1]) | (vj[1] & mask[1])
                    ovvalid_out[0, row, widx >> 2] = jnp.where(
                        touched, vword | (one << sh8), vword)

            # -- 5c. register writes (step_lane order: rsp, aux, primary)
            w3_cond = is_push | is_call | is_pop | is_ret
            w3_val = L.select64(
                [is_push | is_call, is_pop],
                [L.sub64(rsp_l, (push_size.astype(jnp.uint32), z)),
                 L.add64_u32(rsp_l, opsize.astype(jnp.uint32))],
                L.add64(L.add64_u32(rsp_l, _u32(8)), imm_l))
            gl1 = S._gpr_write_l(gl, commit & w3_cond, jnp.int32(4),
                                 w3_val, jnp.int32(8))
            w2_cond = is_mul & ~is_mul2 & (opsize > 1)
            gl2 = S._gpr_write_l(gl1, commit & w2_cond, jnp.int32(2),
                                 mul_r2, opsize)
            w1_cond = L.sel(
                [is_mov, is_lea, is_alu, is_shift, is_unary, is_mul,
                 is_pop, is_setcc, is_cmov],
                [dk == U.K_REG, jnp.bool_(True),
                 alu_writes & (dk == U.K_REG),
                 sh_writes & (dk == U.K_REG), dk == U.K_REG,
                 jnp.bool_(True), dk == U.K_REG, dk == U.K_REG,
                 jnp.bool_(True)],
                jnp.bool_(False))
            w1_idx = jnp.where(is_mul,
                               jnp.where(is_mul2, dr, jnp.int32(0)), dr)
            w1_val = L.select64(
                [is_mov, is_lea, is_alu, is_shift, is_unary, is_mul,
                 is_pop, is_setcc, is_cmov],
                [src_val_l, ea_l, alu_r, sh_r, un_r, mul_r1, pop_val,
                 cc01, L.where64(cc, src_val_l, dst_val_l)], zero2)
            w1_size = jnp.where(
                is_mul,
                jnp.where(is_mul2, opsize,
                          jnp.where(opsize == 1, jnp.int32(2), opsize)),
                opsize)
            gl_new = S._gpr_write_l(gl2, commit & w1_cond, w1_idx, w1_val,
                                    w1_size)

            # -- 5d. rflags / rip ----------------------------------------
            hot_rf = is_alu | is_unary | is_shift | is_mul
            rf_exec_lo = L.sel([is_alu, is_unary, is_shift],
                               [alu_rf_lo, un_rf_lo, sh_rf_lo], mul_rf_lo)
            rf_cand = jnp.where(hot_rf, rf_exec_lo, rf_lo)
            new_rf_lo = jnp.where(commit, rf_cand | _u32(0x2), rf_lo)
            rip_exec = L.select64(
                [is_jmp | is_call, is_jcc, is_ret],
                [jmp_t, L.where64(cc, jcc_t, next_rip_l), l1_pair],
                next_rip_l)
            new_rip = L.where64(commit, rip_exec, rip_l)

            # -- 6. bookkeeping: icount/limit, counters, coverage, edges -
            new_ic = L.where64(commit, L.add64_u32(ic_l, _u32(1)), ic_l)
            timed = commit & limit_on & ~L.ltu64(new_ic, limit_l)
            new_bpskip = jnp.where(commit, jnp.int32(0), bpskip)
            new_status = jnp.where(
                miss, jnp.int32(_NEED_DECODE),
                jnp.where(park, jnp.int32(_NEEDS_XLA),
                          jnp.where(timed, jnp.int32(_TIMEDOUT), status)))

            wi = idxc >> 5
            cov_bit = jnp.where(
                commit, one << (idxc & 31).astype(jnp.uint32), z)
            cov_out[0, wi] = cov_out[0, wi] | cov_bit
            eh_lo = L.mix64(rip_l)[0] ^ rip_exec[0]
            ei = (eh_lo & _u32(ebits - 1)).astype(jnp.int32)
            is_branch = is_jmp | is_jcc | is_call | is_ret
            edge_bit = jnp.where(
                commit & is_branch,
                one << (ei & 31).astype(jnp.uint32), z)
            edge_out[0, ei >> 5] = edge_out[0, ei >> 5] | edge_bit

            inc = jnp.where(commit, one, z)
            return (gl_new, new_rip, new_rf_lo, new_status, new_ic,
                    new_bpskip, d_instr + inc,
                    d_miss + jnp.where(miss, one, z),
                    d_ps + jnp.where(park_sub_evt, one, z),
                    d_pm + jnp.where(park_mem_evt, one, z))

        init = (gpr_in[0], (rip_in[0, 0], rip_in[0, 1]), rf_in[0, 0],
                st_in[0], (ic_in[0, 0], ic_in[0, 1]), bp_in[0],
                _u32(0), _u32(0), _u32(0), _u32(0))
        (gl, rip_l, rf_lo, status, ic_l, bpskip, d_instr, d_miss,
         d_ps, d_pm) = lax.fori_loop(0, k_steps, step_body, init)

        gpr_out[0] = gl
        rip_out[0, 0], rip_out[0, 1] = rip_l[0], rip_l[1]
        rf_out[0, 0] = rf_lo
        rf_out[0, 1] = rf_in[0, 1]      # hot classes never touch bits 32+
        st_out[0] = status
        ic_out[0, 0], ic_out[0, 1] = ic_l[0], ic_l[1]
        bp_out[0] = bpskip
        delta = jnp.zeros(N_CTRS, jnp.uint32)
        delta = delta.at[CTR_INSTR].set(d_instr)
        delta = delta.at[CTR_DECODE_MISS].set(d_miss)
        # every kernel-retired instruction is by definition a fused one
        delta = delta.at[CTR_FUSED].set(d_instr)
        delta = delta.at[CTR_PARK_SUBSET].set(d_ps)
        delta = delta.at[CTR_PARK_MEM].set(d_pm)
        # the loop above passes every lane k_steps times, live or not
        delta = delta.at[CTR_SWEPT].set(_u32(k_steps))
        ctr_out[0] = ctr_in[0] + delta

    return kernel


def fused_call_impl(tab: UopTable, image: MemImage, machine: Machine,
                    limit, *, k_steps: int, interpret: bool):
    """One fused-kernel dispatch, un-jitted: the bitcast pack seam, the
    pallas_call, and the unpack seam back to a Machine.  Shared by the
    jitted standalone executor (make_run_fused) and the fused megachunk
    window body (fuzz/megachunk.py), which inlines this inside its own
    while_loop so the kernel IS the window's step engine.

    The kernel's machine-state operands (gpr..overlay, positions 11-23)
    are aliased 1:1 to its 13 outputs via `input_output_aliases`, so the
    overlay slab (lanes * slots * PAGE_WORDS words) — the largest
    HBM-resident operand — updates in place instead of copying through
    the kernel per dispatch.  XLA still inserts a defensive copy when an
    operand is a non-donated entry parameter; pairing this with donation
    on the enclosing executable (the window's donate_argnums) removes
    that last copy too."""
    from jax.experimental import pallas as pl

    n_lanes = machine.status.shape[0]
    image = lane_image(image, n_lanes)
    n_fields = tab.meta_i32.shape[1]
    hash_size = tab.hash_tab.shape[0]
    capacity = tab.rip_l.shape[0]
    n_tenants, nframes = image.frame_table.shape
    slots = machine.overlay.pfn.shape[1]
    cov_w = machine.cov.shape[1]
    edge_w = machine.edge.shape[1]
    ebits = edge_w * 32
    n_slots_img = image.pages.shape[0]
    vwords = PAGE_WORDS // 4

    # u64 leaves cross the kernel boundary as free u32 bitcasts; the
    # overlay's u8 valid plane packs 4 bytes per u32 the same way.  The
    # overlay's data plane is u32 limbs already (mem/overlay.py); the
    # kernel's per-lane blocks take both planes as [lanes, slots, words]
    tmu32 = lax.bitcast_convert_type(
        tab.meta_u64, jnp.uint32).reshape(capacity, 8)
    pages32 = lax.bitcast_convert_type(
        image.pages, jnp.uint32).reshape(n_slots_img, 2 * PAGE_WORDS)
    ic32 = lax.bitcast_convert_type(machine.icount, jnp.uint32)
    cr32 = lax.bitcast_convert_type(machine.cr3, jnp.uint32)
    limit32 = lax.bitcast_convert_type(
        jnp.asarray(limit, jnp.uint64).reshape(1),
        jnp.uint32).reshape(2)
    ov = machine.overlay
    ovdata32 = ov.data.reshape(n_lanes, slots, 2 * PAGE_WORDS)
    ovvalid32 = lax.bitcast_convert_type(
        ov.valid.reshape(n_lanes, slots, vwords, 4), jnp.uint32)

    kernel = _build_kernel(k_steps, n_fields, hash_size, nframes,
                           ebits, slots)

    def full(shape):
        nd = len(shape)
        return pl.BlockSpec(shape, lambda i, _n=nd: (0,) * _n)

    def lane(shape_tail):
        nd = 1 + len(shape_tail)
        return pl.BlockSpec((1,) + shape_tail,
                            lambda i, _n=nd: (i,) + (0,) * (_n - 1))

    out = pl.pallas_call(
        kernel,
        grid=(n_lanes,),
        in_specs=[
            full((hash_size, 3)),
            full((capacity, 2)),
            full((capacity, n_fields)),
            full((capacity, 8)),
            full((n_slots_img, 2 * PAGE_WORDS)),
            full((n_tenants, nframes)),
            full((2,)),
            lane(()),
            lane((2,)),
            lane((2,)),
            lane((2,)),
            lane((16, 2)),
            lane((2,)),
            lane((2,)),
            lane(()),
            lane((2,)),
            lane(()),
            lane((N_CTRS,)),
            lane((cov_w,)),
            lane((edge_w,)),
            lane((slots,)),
            lane((slots, 2 * PAGE_WORDS)),
            lane((slots, vwords)),
            lane(()),
        ],
        out_specs=[
            lane((16, 2)),
            lane((2,)),
            lane((2,)),
            lane(()),
            lane((2,)),
            lane(()),
            lane((N_CTRS,)),
            lane((cov_w,)),
            lane((edge_w,)),
            lane((slots,)),
            lane((slots, 2 * PAGE_WORDS)),
            lane((slots, vwords)),
            lane(()),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n_lanes, 16, 2), jnp.uint32),
            jax.ShapeDtypeStruct((n_lanes, 2), jnp.uint32),
            jax.ShapeDtypeStruct((n_lanes, 2), jnp.uint32),
            jax.ShapeDtypeStruct((n_lanes,), jnp.int32),
            jax.ShapeDtypeStruct((n_lanes, 2), jnp.uint32),
            jax.ShapeDtypeStruct((n_lanes,), jnp.int32),
            jax.ShapeDtypeStruct((n_lanes, N_CTRS), jnp.uint32),
            jax.ShapeDtypeStruct((n_lanes, cov_w), jnp.uint32),
            jax.ShapeDtypeStruct((n_lanes, edge_w), jnp.uint32),
            jax.ShapeDtypeStruct((n_lanes, slots), jnp.int32),
            jax.ShapeDtypeStruct((n_lanes, slots, 2 * PAGE_WORDS),
                                 jnp.uint32),
            jax.ShapeDtypeStruct((n_lanes, slots, vwords),
                                 jnp.uint32),
            jax.ShapeDtypeStruct((n_lanes,), jnp.int32),
        ],
        interpret=interpret,
        # machine-state operands 11..23 alias kernel outputs 0..12 — the
        # overlay slab and machine planes update in place instead of
        # copying through the kernel every dispatch
        input_output_aliases={11 + i: i for i in range(13)},
    )(tab.hash_tab, tab.rip_l, tab.meta_i32, tmu32, pages32,
      image.frame_table, limit32, image.tenant, cr32,
      machine.fs_base_l, machine.gs_base_l,
      machine.gpr_l, machine.rip_l, machine.rflags_l, machine.status,
      ic32, machine.bp_skip, machine.ctr, machine.cov, machine.edge,
      ov.pfn, ovdata32, ovvalid32, ov.count)
    (gpr_l, rip_l, rf_l, status, ic_out, bp_skip, ctr, cov, edge,
     ovpfn, ovdata, ovvalid, ovcount) = out
    overlay = ov._replace(
        pfn=ovpfn,
        data=ovdata.reshape(-1),
        valid=lax.bitcast_convert_type(ovvalid, jnp.uint8).reshape(-1),
        count=ovcount)
    return machine._replace(
        gpr_l=gpr_l, rip_l=rip_l, rflags_l=rf_l, status=status,
        icount=lax.bitcast_convert_type(ic_out, jnp.uint64),
        bp_skip=bp_skip, ctr=ctr, cov=cov, edge=edge, overlay=overlay)


def make_run_fused(k_steps: int, interpret: Optional[bool] = None):
    """Build (or fetch) the jitted fused-step executor: up to `k_steps`
    hot-subset instructions per lane per dispatch.  Thin jit wrapper over
    fused_call_impl (the megachunk window inlines the impl directly).

    `interpret=None` auto-selects: real Mosaic lowering on a TPU backend,
    the Pallas interpreter elsewhere (the tier-1/CPU validation mode)."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    key = (k_steps, interpret)
    cached = _FUSED_CACHE.get(key)
    if cached is not None:
        return cached

    @jax.jit
    def run_fused(tab: UopTable, image: MemImage, machine: Machine, limit):
        return fused_call_impl(tab, image, machine, limit,
                               k_steps=k_steps, interpret=interpret)

    _FUSED_CACHE[key] = run_fused
    return run_fused


def fused_resume_impl(tab: UopTable, image: MemImage, machine: Machine,
                      limit, *, n_steps: int):
    """The resume leg, un-jitted: returns (machine, xla_sweeps) where
    xla_sweeps counts the step_v iterations the bounded while executed —
    the fused window's ladder-engine round currency.  Shared by the
    jitted standalone leg (make_run_resume, which discards the count)
    and the fused megachunk window body."""
    from wtf_tpu.interp.step import step_lanes

    running = jnp.int32(_RUNNING)
    parked = jnp.int32(_NEEDS_XLA)

    image = lane_image(image, machine.status.shape[0])
    st = machine.status
    machine = machine._replace(status=jnp.where(
        st == parked, running, jnp.where(st == running, parked, st)))

    def cond(carry):
        i, m = carry
        return (i < n_steps) & jnp.any(m.status == running)

    def body(carry):
        i, m = carry
        return i + 1, step_lanes(tab, image, m, limit)

    iters, out = lax.while_loop(cond, body, (jnp.int32(0), machine))
    # release held lanes (step_lane never emits NEEDS_XLA itself, so
    # every remaining NEEDS_XLA is a lane held above)
    out = out._replace(status=jnp.where(
        out.status == parked, running, out.status))
    return out, iters


def make_run_resume(n_steps: int, donate: bool = None):
    """The fused ladder's XLA resume leg: run a SHORT chunk of the full
    transition function (interp/step.py) for the lanes the kernel parked,
    so the one instruction that parked each lane retires on the precise
    path, then control returns to the kernel.

    The leg swaps statuses around the chunk: parked (NEEDS_XLA) lanes run,
    while still-RUNNING lanes — hot lanes that simply exhausted the
    kernel's K steps — are HELD for its duration and released after.
    Without the hold every round would retire `n_steps` hot instructions
    on the XLA path per lane, capping fused occupancy at K/(K+n) even on
    all-hot code; with it, occupancy equals the stream's hot fraction.
    `n_steps` stays small (default 1) because every XLA-retired
    instruction is lost occupancy for lanes that park.

    Same memoization/donation policy as step.make_run_chunk (donation is
    unsound on the XLA CPU backend — see that docstring)."""
    if donate is None:
        donate = jax.default_backend() != "cpu"
    key = (n_steps, donate)
    cached = _RESUME_CACHE.get(key)
    if cached is not None:
        return cached

    from functools import partial

    @partial(jax.jit, donate_argnums=(2,) if donate else ())
    def run_resume(tab: UopTable, image: MemImage, machine: Machine, limit):
        out, _ = fused_resume_impl(tab, image, machine, limit,
                                   n_steps=n_steps)
        return out

    _RESUME_CACHE[key] = run_resume
    return run_resume
