"""Device-side x86-64 transition function: one instruction, one lane, vmapped.

This is the TPU-native replacement for the reference's emulator hot loop
(bochscpu's fetch-decode-execute + hook chain, reference
src/wtf/bochscpu_backend.cc:352-548): instead of one guest stepping through
branchy C++ per instruction, every lane of the batch advances one
*pre-decoded* uop per call, fully vectorized, with lane masking for
divergence.  The host decodes (cpu/decoder.py), publishes uops to the device
table (interp/uoptable.py), and this module consumes them.

Structure of `step_lane` (single lane; `step_lanes` adds the lane axis):
  1. hash-probe the uop table with rip          -> NEED_DECODE on miss
  2. breakpoint check (honoring bp_skip)        -> BREAKPOINT (pre-execution,
     like the reference's BeforeExecutionHook dispatch, bochscpu:545-547)
  3. self-modifying-code check: current code bytes (through the lane's dirty
     overlay) vs the decode-time raw bytes      -> SMC
  4. effective address, at most two generic loads (src-like / dst-like),
     ALU/flag select over op classes mirroring cpu/emu.py semantics exactly,
     one store, register writebacks
  5. rip / rflags / status / icount update; coverage bit (per uop-table
     entry) + edge-hash bit (reference RecordEdge, bochscpu:699-728) set in
     the per-lane bitmaps

Anything the device path does not implement surfaces as per-lane UNSUPPORTED
and is single-stepped on the host by the EmuCpu oracle (interp/runner.py) —
the same "precise slow path backs a fast path" split the reference gets from
bochscpu vs KVM, collapsed into one machine.

Representation: the hot machine state is u32 limb pairs (interp/limbs.py;
the TPU has no native 64-bit integers).  The ported paths — decode-cache
hash probe, integer ALU and unary ops, flag images, effective addressing,
condition evaluation, and the fallthrough/Jcc rip updates — run entirely on
u32 limbs (`alu_limb`, `unary_limb`, `ea_limb` below are compiled standalone
by tests/test_limbs.py to pin the absence of 64-bit ops).  Cold classes
(shifts, mul/div, strings, SSE/x87, syscalls, the memory/paging subsystem)
read u64 bitcast views and convert back at the pack_u64/unpack_u64 seam,
which XLA lowers for free.
"""

from __future__ import annotations

import operator
from functools import partial, reduce
from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax

import numpy as np

from wtf_tpu.core.results import StatusCode
from wtf_tpu.cpu import uops as U
from wtf_tpu.cpu.emu import MSR_ATTR
from wtf_tpu.cpu.cpuid import CPUID_TABLE, MAX_BASIC_LEAF
from wtf_tpu.interp import limbs as L
from wtf_tpu.interp.machine import (
    CTR_LEVELS, CTR_TAIL, IRET_LATCH_SHIFT, MACHINE_LANE_AXES, N_CTRS,
    Machine, lane_view, map_rows, put_lanes,
)
from wtf_tpu.interp.uoptable import (
    F_A32,
    F_BASE_REG, F_COND, F_DST_KIND, F_DST_REG, F_IDX_REG, F_LENGTH, F_LOCK,
    F_OPC, F_OPSIZE, F_REP, F_SCALE, F_SEG, F_SEXT, F_SRCSIZE, F_SRC_KIND,
    F_SRC_REG, F_SUB, M_BP, M_PFN0, M_PFN1, MU_DISP, MU_IMM, MU_RAW_HI,
    MU_RAW_LO, PROBES, UopTable,
)
from wtf_tpu.mem.overlay import (
    StoreCommit, StoreSpill, commit_spills, commit_stores, extract_pair,
    load_windows3_vec, resident, store_window3,
)
from wtf_tpu.mem.paging import Translation, translate_cached
from wtf_tpu.mem.physmem import IMAGE_IN_AXES, MemImage, lane_image

MASK64 = (1 << 64) - 1

# rflags bits
_CF, _PF, _AF, _ZF, _SF, _OF = 0x1, 0x4, 0x10, 0x40, 0x80, 0x800
_TF, _IF, _DF = 0x100, 0x200, 0x400
FLAGS_ARITH = _CF | _PF | _AF | _ZF | _SF | _OF  # 0x8D5


def _u(x: int) -> jnp.ndarray:
    return jnp.uint64(x & MASK64)


# Device copy of the oracle's CPUID model (cpu/cpuid.py): plain numpy at
# module scope (must not touch the jax backend at import time); becomes a
# compile-time constant of the traced step.
_CPUID_KEYS = np.array([[l, s] for (l, s) in CPUID_TABLE], dtype=np.uint32)
_CPUID_VALS = np.array([CPUID_TABLE[k] for k in CPUID_TABLE], dtype=np.uint32)
_CPUID_BASIC_ROW = list(CPUID_TABLE).index((MAX_BASIC_LEAF, 0))


def _mix64(z):
    """splitmix64 mixing steps only — bit-for-bit the reference's RecordEdge
    RIP hash (bochscpu_backend.cc:705-715); must match utils.hashing.mix64."""
    z = (z ^ (z >> _u(30))) * _u(0xBF58476D1CE4E5B9)
    z = (z ^ (z >> _u(27))) * _u(0x94D049BB133111EB)
    return z ^ (z >> _u(31))


def _splitmix64(x):
    return _mix64(x + _u(0x9E3779B97F4A7C15))


def _size_mask(nbytes):
    """nbytes (int32 scalar) -> u64 value mask; >=8 bytes = full mask."""
    sh = (jnp.minimum(nbytes, 8).astype(jnp.uint64)) * _u(8)
    partial = (_u(1) << jnp.minimum(sh, _u(63))) - _u(1)
    return jnp.where(sh >= _u(64), _u(MASK64), partial)


def _shl(x, s):
    """x << s with s (u64) >= 64 yielding 0 (XLA leaves it undefined)."""
    return jnp.where(s >= _u(64), _u(0), x << jnp.minimum(s, _u(63)))


def _shr(x, s):
    return jnp.where(s >= _u(64), _u(0), x >> jnp.minimum(s, _u(63)))


def _sext(val, nbytes):
    """Sign-extend the low nbytes of val to 64 bits."""
    sh = ((8 - jnp.minimum(nbytes, 8)).astype(jnp.uint64)) * _u(8)
    widened = (val << sh).astype(jnp.int64) >> sh.astype(jnp.int64)
    return widened.astype(jnp.uint64)


def _canon(gva):
    """Canonical 48-bit address predicate (bits 63:47 all equal)."""
    top = gva >> _u(47)
    return (top == _u(0)) | (top == _u(0x1FFFF))


def _parity_even(r):
    v = r & _u(0xFF)
    v = v ^ (v >> _u(4))
    v = v ^ (v >> _u(2))
    v = v ^ (v >> _u(1))
    return (v & _u(1)) == _u(0)


def _popcnt(x):
    x = x - ((x >> _u(1)) & _u(0x5555555555555555))
    x = (x & _u(0x3333333333333333)) + ((x >> _u(2)) & _u(0x3333333333333333))
    x = (x + (x >> _u(4))) & _u(0x0F0F0F0F0F0F0F0F)
    return (x * _u(0x0101010101010101)) >> _u(56)


def _bitlen(x):
    """Position of highest set bit + 1 (0 for x == 0)."""
    x = x | (x >> _u(1))
    x = x | (x >> _u(2))
    x = x | (x >> _u(4))
    x = x | (x >> _u(8))
    x = x | (x >> _u(16))
    x = x | (x >> _u(32))
    return _popcnt(x)


_EXP2_LADDER = tuple(1 << i for i in range(9, -1, -1))   # 512 .. 1


def _f64_bits(f):
    """IEEE-754 bit pattern of a float64 as uint64, WITHOUT the f64 -> int
    bitcast: the TPU compiler's 64-bit rewrite refuses that one direction
    (`bitcast-convert` u64 <- f64 is UNIMPLEMENTED; int -> float is fine).

    Built from operations it accepts, every one exact on an IEEE double:
    sign from a compare (1/x for a signed zero), exponent by power-of-two
    scaling into [1, 2) (ten rounds down from 2**512, ten up), mantissa
    by the f64 -> i64 convert of (m - 1) * 2**52.  Zero/Inf/NaN come from
    bit constants: NaN reads as the positive quiet NaN (callers pick NaN
    results from operand bits, never from the float) and a subnormal
    reads as a signed zero, which is what a flushing platform hands the
    old bitcast and what the FTZ divert below keys on."""
    neg = (f < 0) | ((f == 0) & (1.0 / f < 0))
    a = jnp.abs(f)
    finite = a < jnp.inf
    normal = finite & (a >= 2.0 ** -1022)
    m = jnp.where(normal, a, 1.0)
    e = jnp.zeros(f.shape, jnp.int32)
    for k in _EXP2_LADDER:
        big = m >= 2.0 ** k
        m = jnp.where(big, m * 2.0 ** -k, m)
        e = jnp.where(big, e + k, e)
    for k in _EXP2_LADDER:
        small = m < 2.0 ** (1 - k)
        m = jnp.where(small, m * 2.0 ** k, m)
        e = jnp.where(small, e - k, e)
    frac = ((m - 1.0) * 2.0 ** 52).astype(jnp.int64).astype(jnp.uint64)
    mag = ((e + 1023).astype(jnp.uint64) << _u(52)) | frac
    mag = jnp.where(normal, mag,
                    jnp.where(finite, _u(0),
                              jnp.where(a == jnp.inf,
                                        _u(0x7FF0000000000000),
                                        _u(0x7FF8000000000000))))
    return jnp.where(neg, _u(1 << 63), _u(0)) | mag


def _mkflags(cf, pf, af, zf, sf, of):
    def bit(c, v):
        return jnp.where(c, _u(v), _u(0))

    return (bit(cf, _CF) | bit(pf, _PF) | bit(af, _AF) | bit(zf, _ZF)
            | bit(sf, _SF) | bit(of, _OF))


def _msb(r, opsize):
    return (r >> ((opsize.astype(jnp.uint64) * _u(8)) - _u(1))) & _u(1)


def _flags_add(a, b, r, opsize, carry):
    m = _size_mask(opsize)
    am, bm, rm = a & m, b & m, r & m
    c = jnp.where(carry, _u(1), _u(0))
    cf = jnp.where(opsize >= 8,
                   (rm < am) | ((c == _u(1)) & (rm == am)),
                   (am + bm + c) > m)
    return _mkflags(
        cf=cf,
        pf=_parity_even(rm),
        af=((a ^ b ^ r) & _u(0x10)) != _u(0),
        zf=rm == _u(0),
        sf=_msb(rm, opsize) != _u(0),
        of=(((a ^ r) & (b ^ r)) >> ((opsize.astype(jnp.uint64) * _u(8)) - _u(1))) & _u(1) != _u(0),
    )


def _flags_sub(a, b, r, opsize, borrow):
    m = _size_mask(opsize)
    am, bm, rm = a & m, b & m, r & m
    cf = jnp.where(borrow, am <= bm, am < bm)
    return _mkflags(
        cf=cf,
        pf=_parity_even(rm),
        af=((a ^ b ^ r) & _u(0x10)) != _u(0),
        zf=rm == _u(0),
        sf=_msb(rm, opsize) != _u(0),
        of=(((a ^ b) & (a ^ r)) >> ((opsize.astype(jnp.uint64) * _u(8)) - _u(1))) & _u(1) != _u(0),
    )


def _flags_logic(r, opsize):
    m = _size_mask(opsize)
    rm = r & m
    return _mkflags(
        cf=jnp.bool_(False),
        pf=_parity_even(rm),
        af=jnp.bool_(False),
        zf=rm == _u(0),
        sf=_msb(rm, opsize) != _u(0),
        of=jnp.bool_(False),
    )


# (condition evaluation lives in limbs.eval_cond — the arithmetic flags all
# sit in the low rflags limb, so the ported path is u32-only by nature)


# ---------------------------------------------------------------------------
# register file helpers
# ---------------------------------------------------------------------------

@jax.named_scope("regfile-read")
def _read_reg(gpr, idx, nbytes):
    high = idx >= U.REG_AH_BASE
    base = jnp.clip(jnp.where(high, idx - U.REG_AH_BASE, idx), 0, 15)
    v = gpr[base]
    return jnp.where(high, (v >> _u(8)) & _u(0xFF), v & _size_mask(nbytes))


def _read64(gpr, idx):
    """Full qword read; REG_NONE (or any out-of-file index) reads 0."""
    ok = (idx >= 0) & (idx < 16)
    return jnp.where(ok, gpr[jnp.clip(idx, 0, 15)], _u(0))


@jax.named_scope("regfile-write")
def _gpr_write(gpr, cond, idx, val, nbytes):
    """Partial-register merge semantics of cpu/emu.py write_reg: 32-bit
    writes zero-extend, 8/16-bit merge, AH-view writes hit bits 15:8."""
    high = idx >= U.REG_AH_BASE
    base = jnp.clip(jnp.where(high, idx - U.REG_AH_BASE, idx), 0, 15)
    old = gpr[base]
    m = _size_mask(nbytes)
    merged = jnp.where(
        high, (old & ~_u(0xFF00)) | ((val & _u(0xFF)) << _u(8)),
        jnp.where(nbytes >= 8, val,
                  jnp.where(nbytes == 4, val & _u(0xFFFFFFFF),
                            (old & ~m) | (val & m))))
    return gpr.at[base].set(jnp.where(cond, merged, old))


# ---------------------------------------------------------------------------
# limb register file helpers (the u32-packed mirror of the three above;
# `gl` is the uint32[16, 2] per-lane file)
# ---------------------------------------------------------------------------

def _z32():
    return jnp.uint32(0)


@jax.named_scope("regfile-read")
def _read64_l(gl, idx):
    """Full qword read as a limb pair; REG_NONE (out-of-file) reads 0."""
    ok = (idx >= 0) & (idx < 16)
    row = gl[jnp.clip(idx, 0, 15)]
    z = _z32()
    return jnp.where(ok, row[0], z), jnp.where(ok, row[1], z)


@jax.named_scope("regfile-read")
def _read_reg_l(gl, idx, nbytes):
    high = idx >= U.REG_AH_BASE
    base = jnp.clip(jnp.where(high, idx - U.REG_AH_BASE, idx), 0, 15)
    row = gl[base]
    lo, hi = L.zext((row[0], row[1]), nbytes)
    ah = (row[0] >> 8) & jnp.uint32(0xFF)
    return jnp.where(high, ah, lo), jnp.where(high, _z32(), hi)


# ---------------------------------------------------------------------------
# ported hot paths (pure u32 limb arithmetic — tests/test_limbs.py compiles
# these standalone and fails if a 64-bit integer op appears in the HLO)
# ---------------------------------------------------------------------------

@jax.named_scope("alu")
def alu_limb(sub, a, b, cf_in, opsize, rf_lo):
    """Integer ALU class on u32 limbs: add/adc/sub/sbb/cmp/and/or/xor/test
    plus the CF/PF/AF/ZF/SF/OF image — semantics mirror cpu/emu.py exactly
    (the same contract the deleted u64 block carried).

    Returns (masked result pair, new low-rflags limb, writes-result)."""
    r_add = L.zext(L.add64(a, b), opsize)
    r_adc = L.zext(L.adc64(a, b, cf_in)[0], opsize)
    r_sub = L.zext(L.sub64(a, b), opsize)
    r_sbb = L.zext(L.sbb64(a, b, cf_in)[0], opsize)
    r_and, r_or, r_xor = L.and64(a, b), L.or64(a, b), L.xor64(a, b)
    zero = (_z32(), _z32())
    r = L.select64(
        [sub == U.ALU_ADD, sub == U.ALU_ADC, sub == U.ALU_SUB,
         sub == U.ALU_SBB, sub == U.ALU_CMP, sub == U.ALU_AND,
         sub == U.ALU_OR, sub == U.ALU_XOR, sub == U.ALU_TEST],
        [r_add, r_adc, r_sub, r_sbb, r_sub, r_and, r_or, r_xor, r_and],
        zero)
    fl_add = L.flags_add(a, b, r, opsize, (sub == U.ALU_ADC) & cf_in)
    fl_sub = L.flags_sub(a, b, r, opsize, (sub == U.ALU_SBB) & cf_in)
    fl_logic = L.flags_logic(r, opsize)
    is_add = (sub == U.ALU_ADD) | (sub == U.ALU_ADC)
    is_sub = (sub == U.ALU_SUB) | (sub == U.ALU_SBB) | (sub == U.ALU_CMP)
    fl = jnp.where(is_add, fl_add, jnp.where(is_sub, fl_sub, fl_logic))
    new_rf_lo = (rf_lo & jnp.uint32(~L.FLAGS_ARITH & 0xFFFFFFFF)) | fl
    writes = ~((sub == U.ALU_CMP) | (sub == U.ALU_TEST))
    return r, new_rf_lo, writes


@jax.named_scope("unary")
def unary_limb(sub, a, cf_in, opsize, rf_lo):
    """inc/dec/not/neg on u32 limbs (inc/dec preserve CF; neg CF = a != 0;
    not leaves rflags alone) — mirrors the deleted u64 UNARY block."""
    one = (jnp.uint32(1), _z32())
    zero = (_z32(), _z32())
    r_inc = L.zext(L.add64(a, one), opsize)
    r_dec = L.zext(L.sub64(a, one), opsize)
    r_neg = L.zext(L.neg64(a), opsize)
    r_not = L.zext(L.not64(a), opsize)
    r = L.select64(
        [sub == U.UN_INC, sub == U.UN_DEC, sub == U.UN_NOT, sub == U.UN_NEG],
        [r_inc, r_dec, r_not, r_neg], zero)
    false = jnp.bool_(False)
    fl = jnp.where(
        sub == U.UN_INC, L.flags_add(a, one, r_inc, opsize, false),
        jnp.where(sub == U.UN_DEC, L.flags_sub(a, one, r_dec, opsize, false),
                  L.flags_sub(zero, a, r_neg, opsize, false)))
    cf = jnp.where((sub == U.UN_INC) | (sub == U.UN_DEC), cf_in,
                   ~L.is_zero64(L.zext(a, opsize)))
    new_rf_lo = jnp.where(
        sub == U.UN_NOT, rf_lo,
        (rf_lo & jnp.uint32(~L.FLAGS_ARITH & 0xFFFFFFFF))
        | (fl & jnp.uint32(~L.CF & 0xFFFFFFFF))
        | jnp.where(cf, jnp.uint32(L.CF), _z32()))
    return r, new_rf_lo


def _scale_idx_l(v, scale):
    """index * scale for SIB scales {0,1,2,4,8} as a limb shift (where-
    chain, not jnp.select — select's case index would reintroduce s64).
    The shift is at most 3, so the cross-limb carry needs no >=32 cases
    (and lg==0 makes the 32-lg carry shift a harmless full shift-out)."""
    lg = jnp.where(scale == 2, jnp.uint32(1),
                   jnp.where(scale == 4, jnp.uint32(2),
                             jnp.where(scale == 8, jnp.uint32(3), _z32())))
    carry = jnp.where(lg == 0, _z32(), v[0] >> (jnp.uint32(32) - lg))
    lo, hi = v[0] << lg, (v[1] << lg) | carry
    keep = scale != 0
    return jnp.where(keep, lo, _z32()), jnp.where(keep, hi, _z32())


@jax.named_scope("ea")
def ea_limb(disp, base, idx_scaled, seg, a32):
    """Effective address on u32 limbs: disp + base + scaled index, 67h
    truncation to 32 bits BEFORE the segment base (SDM address-size
    override in 64-bit mode — the truncation is literally zeroing the
    high limb, the representation's home turf)."""
    flat_lo, flat_hi = L.add64(L.add64(disp, base), idx_scaled)
    flat_hi = jnp.where(a32 != 0, _z32(), flat_hi)
    return L.add64((flat_lo, flat_hi), seg)


@jax.named_scope("shift")
def shift_limb(sub, sext_f, a, filler, cl_lo, src_lo, imm_lo, cf_in,
               opsize, rf_lo):
    """SHIFT/ROT class on u32 limbs: shl/shr/sar/rol/ror/rcl/rcr/shld/shrd
    plus the partial CF/OF(/ZF/SF/PF) flag image — semantics mirror the
    deleted u64 SHIFT block bit-for-bit (which mirrored cpu/emu.py).

    `a` is the dst value pair, `filler` the shld/shrd fill register (read
    at opsize), `cl_lo`/`src_lo`/`imm_lo` the low limbs of rcx / the src
    operand / the immediate (every count fits 6 bits after masking, so
    the high limbs never participate).

    Returns (masked result pair, new low-rflags limb, writes-result)."""
    z = _z32()
    one = jnp.uint32(1)
    false = jnp.bool_(False)
    bits = opsize.astype(jnp.uint32) * jnp.uint32(8)
    is_shxd = (sub == U.SH_SHLD) | (sub == U.SH_SHRD)
    cl = cl_lo & jnp.uint32(0xFF)
    cnt_src = jnp.where(is_shxd, jnp.where(sext_f == 3, imm_lo, cl), src_lo)
    cnt_mask = jnp.where(opsize >= 8, jnp.uint32(0x3F), jnp.uint32(0x1F))
    count0 = cnt_src & cnt_mask
    # rcl/rcr rotate through CF over bits+1 positions
    is_rc = (sub == U.SH_RCL) | (sub == U.SH_RCR)
    count = jnp.where(is_rc, count0 % (bits + one), count0)
    # shld/shrd 16-bit with count > bits: arch-undefined; emu reduces mod bits
    count = jnp.where(is_shxd & (count > bits), count % bits, count)
    cnz = count != z
    am = L.zext(a, opsize)
    sa = L.sext(a, opsize)
    cf01 = (jnp.where(cf_in, one, z), z)
    c1m = count - one            # count==0 wraps >= 64: shifts yield 0

    def bit0(p):
        return (p[0] & one) != z

    sh_shl_r = L.zext(L.shl64(am, count), opsize)
    sh_shl_cf = (count <= bits) & bit0(L.shr64(am, bits - count))
    sh_shr_r = L.shr64(am, count)
    sh_shr_cf = (count <= bits) & bit0(L.shr64(am, c1m))
    sh_sar_r = L.zext(L.sar64(sa, count), opsize)
    sh_sar_cf = bit0(L.sar64(sa, c1m))        # sar64 clamps counts at 63
    rot_c = count % bits
    rot_cz = rot_c == z
    sh_rol_r = L.where64(
        rot_cz, am,
        L.zext(L.or64(L.shl64(am, rot_c), L.shr64(am, bits - rot_c)), opsize))
    sh_rol_cf = bit0(sh_rol_r)
    sh_ror_r = L.where64(
        rot_cz, am,
        L.zext(L.or64(L.shr64(am, rot_c), L.shl64(am, bits - rot_c)), opsize))
    sh_ror_cf = L.msb(sh_ror_r, opsize)
    # rcl/rcr: (bits+1)-bit rotate through carry, expressed without u128
    zero2 = (z, z)
    sh_rcl_r = L.zext(
        L.or64(L.or64(L.shl64(am, count), L.shl64(cf01, c1m)),
               L.where64(count > one,
                         L.shr64(am, bits + one - count), zero2)),
        opsize)
    sh_rcl_cf = jnp.where(cnz, bit0(L.shr64(am, bits - count)), cf_in)
    sh_rcr_r = L.zext(
        L.or64(L.or64(L.shr64(am, count), L.shl64(cf01, bits - count)),
               L.where64(count > one,
                         L.shl64(am, bits + one - count), zero2)),
        opsize)
    sh_rcr_cf = jnp.where(cnz, bit0(L.shr64(am, c1m)), cf_in)
    sh_shld_r = L.zext(
        L.or64(L.shl64(am, count), L.shr64(filler, bits - count)), opsize)
    sh_shld_cf = bit0(L.shr64(am, bits - count))
    sh_shrd_r = L.zext(
        L.or64(L.shr64(am, count), L.shl64(filler, bits - count)), opsize)
    sh_shrd_cf = bit0(L.shr64(am, c1m))

    conds = [(sub == U.SH_SHL) | (sub == U.SH_SAL), sub == U.SH_SHR,
             sub == U.SH_SAR, sub == U.SH_ROL, sub == U.SH_ROR,
             sub == U.SH_RCL, sub == U.SH_RCR, sub == U.SH_SHLD,
             sub == U.SH_SHRD]
    r = L.select64(conds,
                   [sh_shl_r, sh_shr_r, sh_sar_r, sh_rol_r, sh_ror_r,
                    sh_rcl_r, sh_rcr_r, sh_shld_r, sh_shrd_r], zero2)
    cf = L.sel(conds,
               [sh_shl_cf, sh_shr_cf, sh_sar_cf, sh_rol_cf, sh_ror_cf,
                sh_rcl_cf, sh_rcr_cf, sh_shld_cf, sh_shrd_cf], false)
    count1 = count == one
    of_keep = (rf_lo & jnp.uint32(L.OF)) != z
    r_msb = L.msb(r, opsize)
    am_msb = L.msb(am, opsize)
    ror_b2 = bit0(L.shr64(sh_ror_r, bits - jnp.uint32(2)))
    of = L.sel(conds, [
        jnp.where(count1, r_msb != cf, of_keep),
        jnp.where(count1, am_msb, of_keep),
        jnp.where(count1, false, of_keep),
        jnp.where(count1, r_msb != cf, of_keep),
        jnp.where(count1, r_msb != ror_b2, of_keep),
        jnp.where(count1, r_msb != cf, of_keep),
        jnp.where(count1, am_msb != cf_in, of_keep),
        jnp.where(count1, L.msb(L.xor64(sh_shld_r, am), opsize), false),
        jnp.where(count1, L.msb(L.xor64(sh_shrd_r, am), opsize), false),
    ], of_keep)
    full = L.mkflags(cf, L.parity_even(r[0]), false,
                     L.is_zero64(r), r_msb, of)
    # rcl/rcr update only CF|OF; others CF|OF|ZF|SF|PF (AF untouched,
    # mirroring the oracle's partial set_flags in emu._exec_shift)
    mask = jnp.where(is_rc, jnp.uint32(L.CF | L.OF),
                     jnp.uint32(L.CF | L.OF | L.ZF | L.SF | L.PF))
    new_rf_lo = jnp.where(cnz, (rf_lo & ~mask) | (full & mask), rf_lo)
    return r, new_rf_lo, cnz


@jax.named_scope("mul")
def mul_limb(sub, sext_f, a, b, rax, imm, opsize, rf_lo):
    """MUL class on u32 limbs: 2/3-operand imul plus the widening
    mul/imul forms (lo to rAX/dst, hi to rDX, 8-bit widening writes the
    full product to AX) with the CF/OF image — mirrors the deleted u64
    MUL block bit-for-bit.

    For opsize < 8 every signed/unsigned product fits 64 bits exactly, so
    the wide product is one mul64_lo; opsize 8 takes the high half from
    limbs.umulhi64/smulhi64.  Returns (w1 pair — the primary write —,
    w2 pair — the widening high half —, new low-rflags limb)."""
    z = _z32()
    zero2 = (z, z)
    ones2 = (jnp.uint32(0xFFFFFFFF), jnp.uint32(0xFFFFFFFF))
    false = jnp.bool_(False)
    bits = opsize.astype(jnp.uint32) * jnp.uint32(8)
    sb = L.sext(b, opsize)
    is_mul2 = sub == U.MUL_2OP
    mul2_a = L.where64(sext_f == 2, b, a)          # 3-op: r/m * imm
    mul2_b = L.where64(sext_f == 2, L.zext(imm, opsize), b)
    m2sa = L.sext(mul2_a, opsize)
    m2sb = L.sext(mul2_b, opsize)
    m2_full = L.mul64_lo(m2sa, m2sb)
    mul2_lo = L.zext(m2_full, opsize)
    mul2_of_small = ~L.eq64(m2_full, L.sext(mul2_lo, opsize))
    m2_hi = L.smulhi64(m2sa, m2sb)
    m2_fill = L.where64((mul2_lo[1] >> 31) != 0, ones2, zero2)
    mul2_of = jnp.where(opsize >= 8, ~L.eq64(m2_hi, m2_fill), mul2_of_small)

    # unsigned widening
    muw_full_u = L.mul64_lo(rax, b)    # exact for opsize < 8; low64 at 8
    muw_u_lo = L.where64(opsize >= 8, muw_full_u,
                         L.zext(muw_full_u, opsize))
    muw_u_hi = L.where64(opsize >= 8, L.umulhi64(rax, b),
                         L.zext(L.shr64(muw_full_u, bits), opsize))
    muw_u_of = ~L.is_zero64(muw_u_hi)
    # signed widening
    sax = L.sext(rax, opsize)
    muw_full_s = L.mul64_lo(sax, sb)   # exact two's complement for < 8
    muw_s_lo_small = L.zext(muw_full_s, opsize)
    muw_s_hi64 = L.smulhi64(sax, sb)
    muw_s_lo = L.where64(opsize >= 8, muw_full_s, muw_s_lo_small)
    muw_s_hi = L.where64(opsize >= 8, muw_s_hi64,
                         L.zext(L.shr64(muw_full_s, bits), opsize))
    s_fill = L.where64((muw_full_s[1] >> 31) != 0, ones2, zero2)
    muw_s_of = jnp.where(
        opsize >= 8, ~L.eq64(muw_s_hi64, s_fill),
        ~L.eq64(muw_full_s, L.sext(muw_s_lo_small, opsize)))
    mul_wide_s = sub == U.MUL_WIDE_S
    muw_lo = L.where64(mul_wide_s, muw_s_lo, muw_u_lo)
    muw_hi = L.where64(mul_wide_s, muw_s_hi, muw_u_hi)
    muw_of = jnp.where(mul_wide_s, muw_s_of, muw_u_of)
    mul_of = jnp.where(is_mul2, mul2_of, muw_of)
    # 8-bit widening mul writes the full product to AX (emu _exec_mul)
    prod16 = L.zext(L.where64(mul_wide_s, muw_full_s, muw_full_u),
                    jnp.int32(2))
    w1 = L.where64(is_mul2, mul2_lo,
                   L.where64(opsize == 1, prod16, muw_lo))
    rf2 = ((rf_lo & jnp.uint32(~L.FLAGS_ARITH & 0xFFFFFFFF))
           | L.mkflags(mul_of, L.parity_even(mul2_lo[0]), false, false,
                       L.msb(mul2_lo, opsize), mul_of))
    rfw = ((rf_lo & jnp.uint32(~(L.CF | L.OF) & 0xFFFFFFFF))
           | jnp.where(mul_of, jnp.uint32(L.CF | L.OF), z))
    new_rf_lo = jnp.where(is_mul2, rf2, rfw)
    return w1, muw_hi, new_rf_lo


# ---------------------------------------------------------------------------
# memory spans (dynamic size <= 16 bytes, overlay-aware, two pages max)
#
# Word-window design: any <=16-byte span is covered by 3 aligned u64 words
# (the page boundary is word-aligned, so each window word maps wholly to
# one of the two translated pages).  Loads are 3 word gathers + shifts;
# stores are a 3-word masked read-modify-write (mem/overlay.py).
# ---------------------------------------------------------------------------

def _bytes_of(lo, hi):
    sh = jnp.arange(8, dtype=jnp.uint64) * _u(8)
    b_lo = ((lo >> sh) & _u(0xFF)).astype(jnp.uint8)
    b_hi = ((hi >> sh) & _u(0xFF)).astype(jnp.uint8)
    return jnp.concatenate([b_lo, b_hi])


def _unpack_bytes(lo, hi):
    """(lo, hi) u64 pair -> u8[16] vector (for SSE byte ops)."""
    return _bytes_of(lo, hi)


def _pack_pair(b16):
    """u8[16] -> (lo, hi) u64 pair."""
    sh = jnp.arange(8, dtype=jnp.uint64) * _u(8)
    lo = jnp.sum(b16[:8].astype(jnp.uint64) << sh)
    hi = jnp.sum(b16[8:].astype(jnp.uint64) << sh)
    return lo, hi

# ---------------------------------------------------------------------------
# the transition function
# ---------------------------------------------------------------------------

@jax.named_scope("decode-probe")
def uop_lookup(tab: UopTable, rip_l):
    """Open-addressed probe (host inserter bounds chains to PROBES) ->
    entry index or -1 (NEED_DECODE).  All PROBES slots are fetched in ONE
    gather — the hash rows carry the probe key's limbs next to the entry
    index ([hash_size, 3]), so the verification compare reads the same
    [PROBES, 3] block instead of chasing entry indices through a second
    dependent gather of rip_l (probe count is a latency, not a work,
    concern on TPU; dependent gathers are both).

    Ported path: `rip_l` is a u32 limb pair and the whole probe — the
    splitmix64 hash, the slot indices, the key verification compare — is
    u32-only (the table mask always fits 32 bits, so slot = (hash + k) &
    mask needs only the low hash limb)."""
    hmask = jnp.uint32(tab.hash_tab.shape[0] - 1)
    h_lo, _h_hi = L.splitmix64(rip_l)
    slots = ((h_lo + jnp.arange(PROBES, dtype=jnp.uint32))
             & hmask).astype(jnp.int32)
    rows = tab.hash_tab[slots]
    e = rows[:, 0]
    match = ((e >= 0)
             & (rows[:, 1].astype(jnp.uint32) == rip_l[0])
             & (rows[:, 2].astype(jnp.uint32) == rip_l[1]))
    # first-match via i32 min-rank (argmax's reduce runs an s64 iota under
    # x64, which would be the probe's only 64-bit op)
    rank = jnp.where(match, jnp.arange(PROBES, dtype=jnp.int32),
                     jnp.int32(PROBES))
    first = jnp.min(rank)
    return jnp.where(first < PROBES,
                     e[jnp.minimum(first, PROBES - 1)], jnp.int32(-1))


# Export hook for the static analyzer (wtf_tpu/analysis): every ported
# u32-limb hot path, compiled standalone under the zero-u64 dtype rule.
# Adding a newly ported path here (and an argument recipe in
# analysis/rules.py — the lint fails on an export without one) is how it
# comes under the pin; tests/test_limbs.py runs the same rule family.
PORTED_LIMB_PATHS = {
    "step.alu_limb": alu_limb,
    "step.unary_limb": unary_limb,
    "step.shift_limb": shift_limb,
    "step.mul_limb": mul_limb,
    "step.ea_limb": ea_limb,
    "step.scale_idx_l": _scale_idx_l,
    "step.uop_lookup": uop_lookup,
}


@jax.named_scope("coverage")
def _cover(cov, edge, reached, idxc, took_edge, rip, rip_exec):
    """One lane's coverage and edge bitmaps after a step."""
    # coverage: the instruction was reached (reference records RIP in
    # before_execution even when the insn then faults, bochscpu:479-505)
    wi = idxc >> 5
    cov_bit = jnp.where(reached,
                        jnp.uint32(1) << (idxc & 31).astype(jnp.uint32),
                        jnp.uint32(0))
    new_cov = cov.at[wi].set(cov[wi] | cov_bit)

    # edges: taken AND not-taken control transfers (reference registers
    # cnear_branch_taken/not_taken + ucnear hooks, bochscpu:235-257)
    eh = _mix64(rip) ^ rip_exec
    ebits = edge.shape[0] * 32
    ei = (eh & _u(ebits - 1)).astype(jnp.int32)
    edge_bit = jnp.where(took_edge,
                         jnp.uint32(1) << (ei & 31).astype(jnp.uint32),
                         jnp.uint32(0))
    new_edge = edge.at[ei >> 5].set(edge[ei >> 5] | edge_bit)
    return new_cov, new_edge


def fp_on_host() -> bool:
    """Whether float-valued uops must retire on the host oracle here.

    The TPU has no IEEE double: its compiler carries an f64 as a pair of
    f32s (measured on a v5e, PERF.md "On the chip": f32 range, ~48
    mantissa bits — add/mul/div/sqrt/convert all differ from the oracle
    in the low bits, and anything past 2**128 reads as Inf), and its f32
    div/sqrt are not correctly rounded.  A snapshot fuzzer that disagrees
    with its own oracle cannot reproduce its findings, so there the device
    keeps only what it gets exactly (see `fp_hosted` in step_lane)."""
    return jax.default_backend() == "tpu"


def _row(table, idx):
    """Row `idx` of a lane's small table (the XMM file, the x87 stack, a
    vector's elements) by a select over its rows and a sum: under `vmap`
    an elementwise pass over the file, where `table[idx]` is a gather
    priced by the lane (PERF.md section 5)."""
    hot = jnp.arange(table.shape[0]) == idx
    hot = hot.reshape(hot.shape + (1,) * (table.ndim - 1))
    return jnp.sum(jnp.where(hot, table, jnp.zeros_like(table)), axis=0,
                   dtype=table.dtype)


def _set_row(table, idx, on, row):
    """`table` with row `idx` replaced by `row` where `on`: a select over
    the rows, no scatter."""
    hot = (jnp.arange(table.shape[0]) == idx) & on
    hot = hot.reshape(hot.shape + (1,) * (table.ndim - 1))
    return jnp.where(hot, row, table)


def step_lane(tab: UopTable, image: MemImage, st: Machine, limit,
              fp_host: bool = None
              ) -> Tuple[Machine, StoreCommit, StoreSpill]:
    """Advance one lane by one instruction (vmapped over the batch by
    `step_lanes`, which hands it a `lane_view` and commits the store).
    Returns (lane', store, spill): the overlay's planes pass through
    untouched, the two words every pass commits come back as a
    `StoreCommit`, and the third word of the store's window, which only
    an unaligned store wider than 8 bytes reaches, as a `StoreSpill`.

    Lanes whose status != RUNNING are a no-op.  `limit` is the instruction
    budget (u64; 0 = unlimited) -> TIMEDOUT, the deterministic equivalent of
    the reference's after_execution counter (bochscpu_backend.cc:458-469).
    `fp_host` is trace-time static (None = this platform's fp_on_host())."""
    if fp_host is None:
        fp_host = fp_on_host()
    enabled = st.status == jnp.int32(int(StatusCode.RUNNING))
    # limb-packed hot state (ported paths) + free u64 bitcast views (cold
    # paths and the memory subsystem convert at this seam)
    glimb = st.gpr_l                                  # uint32[16, 2]
    gpr, rip, rf = st.gpr, st.rip, st.rflags
    overlay = st.overlay
    with jax.named_scope("regfile-read"):
        # rip, rflags, the segment bases and the registers instructions
        # name implicitly, at fixed indices (the chip's compiler serves a
        # static slice as a dynamic one)
        rip_l = (st.rip_l[0], st.rip_l[1])
        rf_lo, rf_hi = st.rflags_l[0], st.rflags_l[1]
        fs_base_l = (st.fs_base_l[0], st.fs_base_l[1])
        gs_base_l = (st.gs_base_l[0], st.gs_base_l[1])
        rax, rcx, rdx, r11 = gpr[0], gpr[1], gpr[2], gpr[11]
        rsp, rbp, rsi, rdi = gpr[4], gpr[5], gpr[6], gpr[7]
        rax_l = (glimb[0, 0], glimb[0, 1])
        rcx_l = (glimb[1, 0], glimb[1, 1])
        rsp_l = (glimb[4, 0], glimb[4, 1])
        rbp_l = (glimb[5, 0], glimb[5, 1])
        rsi_l = (glimb[6, 0], glimb[6, 1])
        rdi_l = (glimb[7, 0], glimb[7, 1])

    # -- 1. decode-cache lookup (u32-only hash probe) -------------------
    # Heterogeneous batches (wtf_tpu/tenancy) probe a TENANT-TAGGED key:
    # rip ^ (tenant << 48).  Canonical rips keep bits 62:48 as sign bits,
    # so the tag never collides with a real address and two base images
    # sharing a virtual address resolve to distinct cache entries (each
    # with its own raw bytes / code pfns — no cross-tenant SMC thrash).
    # Single-image dispatch (tenant=None) probes the bare rip: key == rip.
    if image.tenant is None:
        key_l = rip_l
    else:
        ttag = (image.tenant.astype(jnp.uint32) << 16)  # bit 48 = hi bit 16
        key_l = (rip_l[0], rip_l[1] ^ ttag)
    idx = uop_lookup(tab, key_l)
    miss = enabled & (idx < 0)
    idxc = jnp.maximum(idx, 0)

    with jax.named_scope("uop-fetch"):
        f = tab.meta_i32[idxc]          # one row gather: fields + pfn0/pfn1/bp
        mu = tab.meta_u64[idxc]         # one row gather: disp/imm/raw_lo/raw_hi
        opc = f[F_OPC]
        sub = f[F_SUB]
        cond = f[F_COND]
        length = f[F_LENGTH]
        opsize = f[F_OPSIZE]
        srcsize0 = f[F_SRCSIZE]
        sext_f = f[F_SEXT]
        dk = f[F_DST_KIND]
        dr = f[F_DST_REG]
        sk = f[F_SRC_KIND]
        sr = f[F_SRC_REG]
        breg = f[F_BASE_REG]
        ireg = f[F_IDX_REG]
        scale = f[F_SCALE]
        seg = f[F_SEG]
        rep = f[F_REP]
        disp = mu[MU_DISP]
        imm = mu[MU_IMM]
        disp_l = L.pair(disp)
        imm_l = L.pair(imm)

    opmask = _size_mask(opsize)
    bits_u = opsize.astype(jnp.uint64) * _u(8)
    next_rip_l = L.add64_u32(rip_l, length.astype(jnp.uint32))
    next_rip = L.to_u64(next_rip_l)

    # -- 2. breakpoint (pre-execution, like BeforeExecutionHook dispatch) --
    at_bp = enabled & ~miss & (f[M_BP] == 1) & (st.bp_skip == 0)

    # -- 3. SMC check addresses: live code bytes vs decode-time raw -------
    # Code physical frames come from the decode-time translation (pfn0/pfn1
    # table columns) so no page walk is needed for fetch; a *mapping* change
    # without a byte change is not detected (documented divergence — the
    # oracle flushes uops from dirtied pages the same way).  The window
    # itself loads below, batched with the operand loads.
    code_off = (rip & _u(0xFFF)).astype(jnp.int32)
    code_crosses = (code_off + 16) > 4096
    gpa_c0 = (f[M_PFN0].astype(jnp.uint64) << _u(12)) \
        + code_off.astype(jnp.uint64)
    gpa_c15 = jnp.where(
        code_crosses,
        (f[M_PFN1].astype(jnp.uint64) << _u(12))
        + (code_off + 15 - 4096).astype(jnp.uint64),
        gpa_c0 + _u(15))

    # `live`'s final value needs the SMC verdict, which needs the batched
    # window load; the predicates feeding address computation only need
    # enabled/miss/bp (an SMC or about-to-fault lane computes garbage
    # addresses whose loads are simply not `need`ed — same as before).
    pre_live = enabled & ~miss & ~at_bp

    # -- class predicates (opc/fields only — stale for an SMC lane, but an
    # SMC lane never commits: `live` below excludes it) -------------------
    def is_(o):
        return opc == o

    opc_list = lambda pairs, default: jnp.select(  # noqa: E731
        [p[0] for p in pairs], [p[1] for p in pairs], default=default)

    is_string = is_(U.OPC_STRING)
    s_movs = is_string & (sub == U.STR_MOVS)
    s_stos = is_string & (sub == U.STR_STOS)
    s_lods = is_string & (sub == U.STR_LODS)
    s_scas = is_string & (sub == U.STR_SCAS)
    s_cmps = is_string & (sub == U.STR_CMPS)
    rep_on = is_string & (rep != U.REP_NONE)
    rep_skip = rep_on & (rcx == _u(0))  # rep w/ rcx=0: architectural no-op

    is_push, is_pop = is_(U.OPC_PUSH), is_(U.OPC_POP)
    is_pushf, is_popf = is_(U.OPC_PUSHF), is_(U.OPC_POPF)
    is_call, is_ret = is_(U.OPC_CALL), is_(U.OPC_RET)
    # iretq (REX.W CF) retires here in TWO passes of the lane: its frame is
    # five qwords and a pass reads two 16-byte windows.  The first pass
    # probes [rsp, rsp+32) and reads SS at rsp+32, in the oracle's order
    # of reads, and latches SS in the spare bits of `st.fault_write`; it
    # writes nothing else, so a #PF on any of the five reads leaves the
    # lane as it was and the instruction restarts after delivery.  The
    # second pass reads rip, cs, rflags and rsp and commits all five.
    # iretd and far ret stay on the oracle (`unsupported` below).
    is_iretq = is_(U.OPC_IRET) & (sub == 0) & (opsize == 8)
    iret_latch = st.fault_write >> IRET_LATCH_SHIFT
    iret_b = is_iretq & (iret_latch != 0)
    iret_a = is_iretq & ~iret_b
    is_leave = is_(U.OPC_LEAVE) & (sub == 0)
    is_enter = is_(U.OPC_LEAVE) & (sub == 1)
    is_sse = is_(U.OPC_SSEMOV) | is_(U.OPC_SSEALU)
    is_ssefp = is_(U.OPC_SSEFP)
    is_x87 = is_(U.OPC_X87)
    # x87 state save/restore (512+ byte images) stays oracle-serviced;
    # everything else in the decoded x87 subset executes below
    x87_oracle = is_x87 & (
        (sub == U.X87_FXSAVE) | (sub == U.X87_FXRSTOR)
        | (sub == U.X87_XSAVE) | (sub == U.X87_XRSTOR))
    # store-shaped x87 subs must not issue the l1 read (their fault is a
    # WRITE fault, like the MOV/SETCC/POP store_only set)
    x87_store = is_x87 & (
        (sub == U.X87_FST_M) | (sub == U.X87_FIST) | (sub == U.X87_FIST_T)
        | (sub == U.X87_FNSTCW) | (sub == U.X87_FNSTSW_M)
        | (sub == U.X87_STMXCSR))
    # SSE-FP memory-operand byte counts mirror the oracle's virt_read sizes
    # exactly (emu._exec_ssefp) so page-boundary fault behavior matches:
    # elementwise forms read 16 (packed) / elem; converts have their own
    # shapes (the DQ/PS/PD block reads a full 16 even for cvtdq2pd, an
    # oracle-internal convention both engines share).
    fp_is_ew = (sub <= U.FP_SQRT) | (sub == U.FP_CMP)  # arith/minmax/sqrt/cmp
    fp_ldsize = jnp.select(
        [sub == U.FP_CVT_I2F,
         (sub == U.FP_CVT_F2I) | (sub == U.FP_CVT_F2I_T)
         | (sub == U.FP_UCOMI) | (sub == U.FP_COMI),
         sub == U.FP_CVT_F2F,
         fp_is_ew],
        [opsize, srcsize0,
         jnp.where(sext_f == 1, srcsize0 * 2, srcsize0),
         jnp.where(sext_f == 1, jnp.int32(16), srcsize0)],
        default=jnp.int32(16))

    # -- unsupported classes -> host oracle fallback ----------------------
    # MSRs the machine carries — derived from the oracle's MSR_ATTR map
    # (single source of truth; attr names are Machine field names);
    # unknown ids stay oracle-serviced
    msr_id = rcx & _u(0xFFFFFFFF)
    msr_known = jnp.zeros((), bool)
    for _mid in MSR_ATTR:
        msr_known = msr_known | (msr_id == _u(_mid))
    div64_hard = is_(U.OPC_DIV) & (opsize >= 8) & ~jnp.where(
        sub == U.DIV_U, rdx == _u(0),
        rdx == jnp.where((rax >> _u(63)) != 0, _u(MASK64), _u(0)))
    movcr_bad = is_(U.OPC_MOVCR) & ~(
        (sub == 0) | (sub == 3) | (sub == 4) | (sub == 8)
        | ((sext_f == 0) & (sub == 2)))
    unsupported = pre_live & (
        is_(U.OPC_INVALID) | (is_(U.OPC_IRET) & ~is_iretq)
        | (is_(U.OPC_MSR) & ~msr_known)
        | is_(U.OPC_SSECVT) | is_(U.OPC_PCLMUL)
        | is_(U.OPC_STACKSTR)
        | x87_oracle
        # pinsrw m16: a 2-byte load outside the 16-byte operand window
        | (is_(U.OPC_SSEALU) & (sub == U.SSE_PINSRW) & (sk == U.K_MEM))
        # non-canonical wr{fs,gs}base #GPs on hardware: divert so the
        # oracle raises it through the non-canonical -> #GP seam
        | (is_(U.OPC_RDGSBASE) & ((sub == 2) | (sub == 3))
           & ~_canon(_read_reg(gpr, dr, opsize)))
        # 67h string forms use 32-bit rsi/rdi/rcx; neither engine models
        # that — surface loudly instead of executing with 64-bit regs
        | (is_string & (f[F_A32] != 0))
        | movcr_bad | div64_hard)
    if fp_host:
        # every uop whose result depends on a float VALUE the platform
        # cannot compute like the oracle: all f64-element SSE forms, all
        # converts, f32 div/sqrt, and the value-touching x87 subs (the
        # same set x87_denorm guards, plus fild).  Bit movers (shuffles,
        # unpacks, fld/fst m64, fxch, control words) and the f32
        # add/sub/mul/min/max/compare forms stay on the device.
        fp32_exact = (srcsize0 == 4) & (
            (sub <= U.FP_MUL) | (sub == U.FP_MIN) | (sub == U.FP_MAX)
            | (sub == U.FP_UCOMI) | (sub == U.FP_COMI) | (sub == U.FP_CMP))
        fp_bits_only = (sub == U.FP_SHUF) | (sub == U.FP_UNPCKL) \
            | (sub == U.FP_UNPCKH)
        x87_valued = (
            (sub == U.X87_ARITH_M) | (sub == U.X87_ARITH_ST)
            | (((sub == U.X87_FLD_M) | (sub == U.X87_FST_M))
               & (srcsize0 < 8))
            | (sub == U.X87_FILD) | (sub == U.X87_FIST)
            | (sub == U.X87_FIST_T) | (sub == U.X87_COM)
            | (sub == U.X87_COMI))
        fp_hosted = (is_ssefp & ~fp32_exact & ~fp_bits_only) \
            | (is_x87 & x87_valued)
        unsupported = unsupported | (pre_live & fp_hosted)

    # -- 4a. effective address (ported: u32 limbs end to end) -------------
    base_val_l = L.where64(breg == U.REG_RIP, next_rip_l,
                           _read64_l(glimb, breg))
    idx_val_l = _scale_idx_l(_read64_l(glimb, ireg), scale)
    seg_base_l = L.select64(
        [seg == U.SEG_FS, seg == U.SEG_GS],
        [fs_base_l, gs_base_l],
        (jnp.uint32(0), jnp.uint32(0)))
    ea_l = ea_limb(disp_l, base_val_l, idx_val_l, seg_base_l, f[F_A32])
    ea = L.to_u64(ea_l)

    # BT bit-string addressing: register bit index moves the EA by opsize
    # for every `bits` of signed offset (emu _exec_bt).
    bt_sel = _read_reg(gpr, sr, opsize)
    bt_signed = _sext(bt_sel, opsize)
    log2bits = jnp.where(opsize >= 8, 6, jnp.where(opsize == 4, 5, 4)).astype(jnp.int64)
    bt_adjust = ((bt_signed.astype(jnp.int64) >> log2bits)
                 * opsize.astype(jnp.int64)).astype(jnp.uint64)
    bt_mem_reg = is_(U.OPC_BT) & (dk == U.K_MEM) & (sk == U.K_REG)
    ea = jnp.where(bt_mem_reg, ea + bt_adjust, ea)
    bt_off = bt_signed & (bits_u - _u(1))

    # -- 4b. memory roles (ported: span addresses assemble in u32 limbs;
    # the translations convert to u64 at the translate_cached seam) -------
    # post-BT-adjust EA (the BT bit-string displacement stays u64-cold)
    ea_mem_l = L.pair(ea)
    srcsize = jnp.where(srcsize0 == 0, opsize, srcsize0)

    l1_need = pre_live & ~unsupported & ~rep_skip & (
        ((sk == U.K_MEM) & ~x87_store) | is_pop | is_popf | is_ret
        | is_iretq | is_leave | s_movs | s_lods | s_cmps | s_scas)
    l1_addr_l = L.select64(
        [s_movs | s_lods | s_cmps, s_scas,
         is_pop | is_popf | is_ret | is_iretq, is_leave],
        [rsi_l, rdi_l, rsp_l, rbp_l], ea_mem_l)
    l1_addr = L.to_u64(l1_addr_l)
    l1_size = jnp.where(is_popf | is_ret | is_leave, 8,
               jnp.where(is_pop | is_string | is_sse, opsize,
                jnp.where(is_ssefp, fp_ldsize, srcsize)))
    with jax.named_scope("iret"):
        # first pass: l1 spans rip..rsp (translated, not used), l2 is SS;
        # second pass: l1 = rip, cs and l2 = rflags, rsp
        l1_size = jnp.where(is_iretq, jnp.where(iret_b, 16, 32), l1_size)
        iret_l2_l = L.add64_u32(rsp_l, jnp.where(
            iret_b, jnp.uint32(16), jnp.uint32(32)))

    # store-only destinations (MOV/SETCC/POP write [mem] without reading it)
    # must NOT issue a dst-read load: their fault is the *store* fault, so
    # crash names report write access like the oracle's translate(write=True)
    store_only = is_(U.OPC_MOV) | is_(U.OPC_SETCC) | is_pop
    l2_need = pre_live & ~unsupported & ~rep_skip & (
        ((dk == U.K_MEM) & ~is_sse & ~store_only) | s_cmps | is_iretq)
    l2_addr_l = L.select64([s_cmps, is_iretq], [rdi_l, iret_l2_l],
                           ea_mem_l)
    l2_addr = L.to_u64(l2_addr_l)
    l2_size = jnp.where(iret_b, 16, opsize)

    # store address/size (the store itself commits at the end of the step;
    # computing its span here lets its translation batch with the loads')
    push_size = jnp.where(is_pushf | is_call, jnp.int32(8), opsize)
    push_size_l = (push_size.astype(jnp.uint32), jnp.uint32(0))
    st_addr_l = L.select64(
        [is_push | is_pushf | is_call, is_enter, s_movs | s_stos],
        [L.sub64(rsp_l, push_size_l),
         L.sub64(rsp_l, (jnp.uint32(8), jnp.uint32(0))),
         rdi_l],
        ea_mem_l)
    st_addr = L.to_u64(st_addr_l)
    # stores and pushes span the same byte count; x87 stores their
    # operand width (fst m32/m64, fist m16/32/64, fnstcw/fnstsw m16,
    # stmxcsr m32)
    st_size = jnp.where(x87_store, srcsize, push_size)

    # -- 4b'. the six translations (first and last byte of the load, of
    # the second load and of the store) through the lane's translation
    # cache: those the instruction does not use ask for nothing, a cached
    # page is served from its entry, and what is left goes to ONE walk of
    # paging.K_WALK addresses.  On TPU the step's cost is the count of
    # gathered indices, and the walk's were 144 of them at six addresses.
    # An instruction that needs more uncached pages than that at once takes
    # a FILL PASS: the pass fills the cache and changes nothing else of
    # the lane (`fill` below; `live` excludes it), and the next pass finds
    # the entries.  `st_want` is `st_need` (below, after the ALU) as far
    # as the decoded fields say it: a superset, on l2's address where it
    # is wider.
    def _span_last(addr_l, size):
        return L.add64_u32(addr_l, (size - 1).astype(jnp.uint32))

    st_want = pre_live & ~unsupported & ~rep_skip & (
        (dk == U.K_MEM) | is_push | is_pushf | is_call | is_enter
        | s_movs | s_stos | x87_store)
    with jax.named_scope("tlb"):
        gva6 = L.pack_u64(jnp.stack([
            jnp.stack(p, axis=-1) for p in (
                l1_addr_l, _span_last(l1_addr_l, l1_size),
                l2_addr_l, _span_last(l2_addr_l, l2_size),
                st_addr_l, _span_last(st_addr_l, st_size))]))
    t6, overlay, tlb_walks, fill = translate_cached(
        image, overlay, st.cr3, gva6, jnp.stack(
            [l1_need, l1_need, l2_need, l2_need, st_want, st_want]))

    def _tr(i):
        return Translation(gpa=t6.gpa[i], ok=t6.ok[i],
                           writable=t6.writable[i], user=t6.user[i])

    l1t0, l1t1, l2t0, l2t1, ts0, ts1 = (_tr(i) for i in range(6))
    fault1 = l1_need & ~(l1t0.ok & l1t1.ok)
    fault2 = l2_need & ~(l2t0.ok & l2t1.ok)

    wf = jnp.stack([gpa_c0, l1t0.gpa, l2t0.gpa])
    wl = jnp.stack([gpa_c15, l1t1.gpa, l2t1.gpa])
    w3_0, w3_1, w3_2 = load_windows3_vec(image, overlay, wf, wl)
    lo3, hi3 = extract_pair(w3_0, w3_1, w3_2, wf)
    code_lo, code_hi = lo3[0], hi3[0]
    l1_lo, l1_hi = lo3[1], hi3[1]
    l2_lo, l2_hi = lo3[2], hi3[2]

    # -- SMC verdict + the final live mask --------------------------------
    lmask_lo = _size_mask(jnp.minimum(length, 8))
    lmask_hi = jnp.where(length > 8, _size_mask(length - 8), _u(0))
    smc = pre_live & ~fill & (
        (((code_lo ^ mu[MU_RAW_LO]) & lmask_lo) != _u(0))
        | (((code_hi ^ mu[MU_RAW_HI]) & lmask_hi) != _u(0)))
    live = pre_live & ~smc & ~fill
    is_crash = live & (is_(U.OPC_INT) | is_(U.OPC_HLT) | is_(U.OPC_INT1))

    # -- 4c. operand values (ported: read/extend/mask in u32 limbs; the
    # u64 views below are free bitcasts for the cold classes) -------------
    l1_lo_l = L.pair(l1_lo)
    l2_lo_l = L.pair(l2_lo)
    zero_l = (jnp.uint32(0), jnp.uint32(0))
    src_raw_l = L.where64(
        sk == U.K_REG, _read_reg_l(glimb, sr, srcsize),
        L.where64(sk == U.K_MEM, L.zext(l1_lo_l, srcsize), zero_l))
    src_ext_l = L.where64(
        sext_f == 1, L.zext(L.sext(src_raw_l, srcsize), opsize),
        L.zext(src_raw_l, opsize))
    src_val_l = L.where64(sk == U.K_IMM, L.zext(imm_l, opsize), src_ext_l)
    dst_val_l = L.where64(
        dk == U.K_REG, _read_reg_l(glimb, dr, opsize),
        L.where64(dk == U.K_MEM, L.zext(l2_lo_l, opsize), zero_l))
    src_val = L.to_u64(src_val_l)
    dst_val = L.to_u64(dst_val_l)

    # -- 4d. integer ALU classes (ported; mirrors cpu/emu.py exactly) -----
    a, b = dst_val, src_val
    cf_in = (rf_lo & jnp.uint32(_CF)) != jnp.uint32(0)

    # ALU (u32 limb path; the u64 image is a bitcast for mem-dst stores)
    alu_r_l, alu_rf_lo, alu_writes = alu_limb(
        sub, dst_val_l, src_val_l, cf_in, opsize, rf_lo)
    alu_r = L.to_u64(alu_r_l)

    # SHIFT (ported u32 limb path; shift_limb is compiled standalone by
    # tests/test_limbs.py to pin the absence of 64-bit ops) -----------
    filler_l = _read_reg_l(glimb, sr, opsize)
    sh_r_l, sh_rf_lo, sh_writes = shift_limb(
        sub, sext_f, dst_val_l, filler_l, rcx_l[0], src_val_l[0],
        imm_l[0], cf_in, opsize, rf_lo)
    sh_r = L.to_u64(sh_r_l)

    # UNARY (ported u32 limb path) ------------------------------------
    un_r_l, un_rf_lo = unary_limb(sub, dst_val_l, cf_in, opsize, rf_lo)
    un_r = L.to_u64(un_r_l)

    # MUL (ported u32 limb path; mul_limb is compiled standalone by
    # tests/test_limbs.py to pin the absence of 64-bit ops) ----------
    rax_op = _read_reg(gpr, jnp.int32(0), opsize)
    is_mul2 = sub == U.MUL_2OP
    # rax's limbs by a static slice: the compiler turns a register read at
    # a constant index into a dynamic-slice and drops its `op_name`, so no
    # scope could name it (the census in tests/test_analysis.py)
    rax_op_l = L.zext(rax_l, opsize)
    mul_r1_l, mul_r2_l, mul_rf_lo = mul_limb(
        sub, sext_f, dst_val_l, src_val_l, rax_op_l, imm_l, opsize, rf_lo)

    # DIV (device path: dividend fits in 64 bits; else host fallback) --
    d_lo = rax_op
    d_hi = _read_reg(gpr, jnp.int32(2), opsize)
    dividend_u = jnp.where(opsize == 1, _read_reg(gpr, jnp.int32(0), jnp.int32(2)),
                           jnp.where(opsize >= 8, d_lo,
                                     _shl(d_hi, bits_u) | d_lo))
    div_b = b & opmask
    div_bz = div_b == _u(0)
    safe_b = jnp.where(div_bz, _u(1), div_b)
    q_u = dividend_u // safe_b
    rem_u = dividend_u % safe_b
    # signed: sign-extend the (bits*2 <=64 or rdx:rax w/ rdx=sign) dividend
    sdividend = jnp.where(
        opsize == 1, _sext(dividend_u, jnp.int32(2)),
        jnp.where(opsize == 2, _sext(dividend_u, jnp.int32(4)),
                  jnp.where(opsize == 4, _sext(dividend_u, jnp.int32(8)),
                            d_lo))).astype(jnp.int64)
    sb_div = _sext(div_b, opsize).astype(jnp.int64)
    safe_sb = jnp.where(div_bz, jnp.int64(1), sb_div)
    # guard INT64_MIN / -1 (hardware #DE; lax.div would trap-free wrap)
    int_min_edge = (sdividend == jnp.int64(-2**63)) & (sb_div == jnp.int64(-1))
    q_s = lax.div(sdividend, jnp.where(int_min_edge, jnp.int64(1), safe_sb))
    rem_s = lax.rem(sdividend, jnp.where(int_min_edge, jnp.int64(1), safe_sb))
    is_sdiv = sub == U.DIV_S
    half_mask = _shr(opmask, _u(1))  # max positive quotient
    q_over = jnp.where(
        is_sdiv,
        (q_s > half_mask.astype(jnp.int64))
        | (q_s < (-(half_mask.astype(jnp.int64)) - 1)) | int_min_edge,
        q_u > opmask)
    de = live & is_(U.OPC_DIV) & ~div64_hard & (div_bz | q_over)
    div_q = jnp.where(is_sdiv, q_s.astype(jnp.uint64), q_u) & opmask
    div_rem = jnp.where(is_sdiv, rem_s.astype(jnp.uint64), rem_u) & opmask

    # CONVERT ---------------------------------------------------------
    half_bytes = jnp.maximum(opsize // 2, 1)
    cvt_widen = _sext(rax_op & _size_mask(half_bytes), half_bytes) & opmask
    cvt_sign = jnp.where(_msb(rax_op, opsize) != 0, opmask, _u(0))

    # BT --------------------------------------------------------------
    bt_imm_off = imm & (bits_u - _u(1))
    bt_offset = jnp.where(sk == U.K_IMM, bt_imm_off, bt_off)
    bt_val = dst_val
    bt_bit = (_shr(bt_val, bt_offset) & _u(1)) != 0
    bt_one = _shl(_u(1), bt_offset)
    bt_r = jnp.select(
        [sub == U.BT_BT, sub == U.BT_BTS, sub == U.BT_BTR, sub == U.BT_BTC],
        [bt_val, bt_val | bt_one, bt_val & ~bt_one, bt_val ^ bt_one],
        default=bt_val)
    bt_rf = (rf & ~_u(_CF)) | jnp.where(bt_bit, _u(_CF), _u(0))
    bt_writes = sub != U.BT_BT

    # BITSCAN ---------------------------------------------------------
    bs_src = src_val & opmask
    bs_zero = bs_src == _u(0)
    bs_pop = _popcnt(bs_src)
    bs_tz = _popcnt((~bs_src) & (bs_src - _u(1)))
    bs_len = _bitlen(bs_src)
    bs_lz = bits_u - bs_len
    bs_r = jnp.select(
        [sub == U.BS_POPCNT, sub == U.BS_TZCNT, sub == U.BS_LZCNT,
         sub == U.BS_BSF, sub == U.BS_BSR],
        [bs_pop,
         jnp.where(bs_zero, bits_u, bs_tz),
         jnp.where(bs_zero, bits_u, bs_lz),
         bs_tz, bs_len - _u(1)], default=_u(0))
    bs_writes = ~(((sub == U.BS_BSF) | (sub == U.BS_BSR)) & bs_zero)
    bs_rf = jnp.select(
        [sub == U.BS_POPCNT,
         (sub == U.BS_TZCNT) | (sub == U.BS_LZCNT)],
        [(rf & ~_u(FLAGS_ARITH)) | jnp.where(bs_zero, _u(_ZF), _u(0)),
         (rf & ~_u(_CF | _ZF))
         | jnp.where(bs_zero, _u(_CF), _u(0))
         | jnp.where(bs_r == _u(0), _u(_ZF), _u(0))],
        default=(rf & ~_u(_ZF)) | jnp.where(bs_zero, _u(_ZF), _u(0)))

    # BMI1/BMI2 (OPC_PEXT): VEX scalar bit ops; the third operand
    # (VEX.vvvv) rides in `cond` per the decoder's convention ----------
    bmi_third = _read_reg(gpr, cond, opsize)
    bmi_src = src_val & opmask
    bmi_n8 = bmi_third & _u(0xFF)
    bzhi_keep = bmi_n8 >= bits_u
    bmi_bzhi = jnp.where(bzhi_keep, bmi_src,
                         bmi_src & (_shl(_u(1), bmi_n8) - _u(1)))
    bx_start = bmi_third & _u(0xFF)
    bx_len = (bmi_third >> _u(8)) & _u(0xFF)
    bx_mask = _shl(_u(1), bx_len) - _u(1)   # len >= 64 wraps to all-ones
    bmi_bextr = jnp.where(bx_start < bits_u,
                          _shr(bmi_src, bx_start) & bx_mask, _u(0)) & opmask
    bmi_cnt = bmi_third & jnp.where(opsize >= 8, _u(63), _u(31))
    bmi_shlx = _shl(bmi_src, bmi_cnt) & opmask
    bmi_shrx = _shr(bmi_src, bmi_cnt)
    bmi_sarx = (_sext(bmi_src, opsize).astype(jnp.int64)
                >> jnp.minimum(bmi_cnt, _u(63)).astype(jnp.int64)
                ).astype(jnp.uint64) & opmask
    # pdep/pext: rank-based bit scatter/gather over 64 lanes
    bit_i = jnp.arange(64, dtype=jnp.uint64)
    src_bit = (bmi_src >> bit_i) & _u(1)
    bit_rank = jnp.cumsum(src_bit) - src_bit    # exclusive prefix count
    bmi_pext = jnp.sum(jnp.where(src_bit != 0,
                                 ((bmi_third >> bit_i) & _u(1)) << bit_rank,
                                 _u(0)))
    bmi_pdep = jnp.sum(jnp.where(src_bit != 0,
                                 ((bmi_third >> bit_rank) & _u(1)) << bit_i,
                                 _u(0)))
    bmi_blsr = bmi_src & (bmi_src - _u(1)) & opmask
    bmi_blsmsk = (bmi_src ^ (bmi_src - _u(1))) & opmask
    bmi_blsi = bmi_src & ((_u(0) - bmi_src) & opmask) & opmask
    rorx_n = imm & jnp.where(opsize >= 8, _u(63), _u(31))
    bmi_rorx = jnp.where(
        rorx_n == _u(0), bmi_src,
        (_shr(bmi_src, rorx_n) | _shl(bmi_src, bits_u - rorx_n)) & opmask)
    bmi_andn = (~bmi_third & bmi_src) & opmask
    bmi_res = jnp.select(
        [sub == U.BMI_ANDN, sub == U.BMI_BZHI, sub == U.BMI_BEXTR,
         sub == U.BMI_SHLX, sub == U.BMI_SHRX, sub == U.BMI_SARX,
         sub == U.BMI_PDEP, sub == U.BMI_PEXT_, sub == U.BMI_BLSR,
         sub == U.BMI_BLSMSK, sub == U.BMI_BLSI],
        [bmi_andn, bmi_bzhi, bmi_bextr, bmi_shlx, bmi_shrx, bmi_sarx,
         bmi_pdep, bmi_pext, bmi_blsr, bmi_blsmsk, bmi_blsi],
        default=bmi_rorx)
    # flag images: andn/bzhi/bls* touch SF/ZF/CF/OF, bextr ZF/CF/OF
    # (SF untouched), shifts/pdep/pext/rorx none — oracle set_flags kwargs
    bmi_sf = _msb(bmi_res, opsize) != 0
    bmi_zf = bmi_res == _u(0)
    bmi_cf = jnp.select(
        [sub == U.BMI_BZHI, sub == U.BMI_BLSR, sub == U.BMI_BLSMSK,
         sub == U.BMI_BLSI],
        [bmi_n8 > (bits_u - _u(1)), bmi_src == _u(0), bmi_src == _u(0),
         bmi_src != _u(0)],
        default=jnp.bool_(False))
    bmi_szco = _u(_SF | _ZF | _CF | _OF)
    bmi_flag_bits = _mkflags(bmi_cf, jnp.bool_(False), jnp.bool_(False),
                             bmi_zf, bmi_sf, jnp.bool_(False))
    bmi_rf = jnp.select(
        [(sub == U.BMI_ANDN) | (sub == U.BMI_BZHI) | (sub == U.BMI_BLSR)
         | (sub == U.BMI_BLSMSK) | (sub == U.BMI_BLSI),
         sub == U.BMI_BEXTR],
        [(rf & ~bmi_szco) | (bmi_flag_bits & bmi_szco),
         (rf & ~_u(_ZF | _CF | _OF)) | (bmi_flag_bits & _u(_ZF | _CF | _OF))],
        default=rf)

    # MSR (rdmsr/wrmsr) over the MSR-backed machine fields (msr_known
    # computed with the unsupported gate above; same MSR_ATTR source)
    msr_rval = jnp.select(
        [msr_id == _u(mid) for mid in MSR_ATTR],
        [st.tsc + st.icount if attr == "tsc" else getattr(st, attr)
         for attr in MSR_ATTR.values()],
        default=_u(0))
    msr_wval = ((rdx & _u(0xFFFFFFFF)) << _u(32)) | (rax & _u(0xFFFFFFFF))

    # CMPXCHG / XADD --------------------------------------------------
    cx_acc = rax_op
    cx_eq = cx_acc == dst_val
    cx_store = jnp.where(cx_eq, _read_reg(gpr, sr, opsize), dst_val)
    cx_rf = (rf & ~_u(FLAGS_ARITH)) | _flags_sub(
        cx_acc, dst_val, (cx_acc - dst_val) & opmask, opsize, jnp.bool_(False))
    xadd_r = (dst_val + _read_reg(gpr, sr, opsize)) & opmask
    xadd_rf = (rf & ~_u(FLAGS_ARITH)) | _flags_add(
        dst_val, _read_reg(gpr, sr, opsize), xadd_r, opsize, jnp.bool_(False))

    # BSWAP -----------------------------------------------------------
    bsw_in = dst_val & opmask
    sh8 = jnp.arange(8, dtype=jnp.uint64) * _u(8)
    bsw_bytes = (bsw_in >> sh8) & _u(0xFF)
    nb_u = opsize.astype(jnp.uint64)
    rev_sh = jnp.where(jnp.arange(8, dtype=jnp.uint64) < nb_u,
                       (nb_u - _u(1) - jnp.arange(8, dtype=jnp.uint64)) * _u(8),
                       _u(0))
    bsw_r = jnp.sum(jnp.where(jnp.arange(8, dtype=jnp.uint64) < nb_u,
                              bsw_bytes << rev_sh, _u(0)))

    # STRING (one element per step; REP iterates by re-executing) ------
    with jax.named_scope("string"):
        df_set = (rf & _u(_DF)) != 0
        str_delta = jnp.where(df_set, _u(0) - opsize.astype(jnp.uint64),
                              opsize.astype(jnp.uint64))
        str_a = jnp.where(s_cmps, l1_lo & opmask,
                          rax_op)                       # scas: rax, cmps: [rsi]
        str_b = jnp.where(s_cmps, l2_lo & opmask, l1_lo & opmask)  # [rdi]
        str_cmp_r = (str_a - str_b) & opmask
        str_rf = (rf & ~_u(FLAGS_ARITH)) | _flags_sub(
            str_a, str_b, str_cmp_r, opsize, jnp.bool_(False))
        str_zf_new = (str_cmp_r == _u(0))
        rcx_dec = rcx - _u(1)
        str_cc_done = (s_scas | s_cmps) & jnp.where(
            rep == U.REP_REP, ~str_zf_new, str_zf_new)
        str_done = jnp.where(rep_on,
                             rep_skip | (rcx_dec == _u(0)) | str_cc_done,
                             jnp.bool_(True))
        str_upd = live & is_string & ~unsupported & ~rep_skip

    # control flow (ported: condition eval + relative targets in limbs;
    # indirect targets come from registers/memory through the u64 seam) --
    with jax.named_scope("cond"):
        cc_true = L.eval_cond(rf_lo, rcx_l, cond)
    jcc_target_l = L.add64(next_rip_l, imm_l)
    jmp_target = jnp.where(sk == U.K_IMM, L.to_u64(jcc_target_l), src_val)
    ret_target = l1_lo
    syscall_entry = sub == 0

    # PUSHF / POPF / FLAGOP -------------------------------------------
    popf_rf = (l1_lo & _u(0x40FD5)) | _u(0x2)
    flagop_rf = jnp.select(
        [sub == U.FL_CLC, sub == U.FL_STC, sub == U.FL_CMC,
         sub == U.FL_CLD, sub == U.FL_STD, sub == U.FL_CLI,
         sub == U.FL_STI, sub == U.FL_SAHF],
        [rf & ~_u(_CF), rf | _u(_CF), rf ^ _u(_CF),
         rf & ~_u(_DF), rf | _u(_DF), rf & ~_u(_IF),
         rf | _u(_IF),
         (rf & ~_u(0xD5)) | (_read_reg(gpr, jnp.int32(U.REG_AH_BASE), jnp.int32(1)) & _u(0xD5)) | _u(0x2)],
        default=rf)  # LAHF leaves rflags alone (writes AH instead)
    lahf_val = (rf & _u(0xD7)) | _u(0x2)

    # CPUID: same table + fallback chain as the oracle (cpu/cpuid.py
    # `cpuid()`): exact (leaf, subleaf), then (leaf, 0), then the highest
    # basic leaf for out-of-range basic leaves, else zeros ---------------
    cpuid_keys = jnp.asarray(_CPUID_KEYS)
    cpuid_vals = jnp.asarray(_CPUID_VALS)
    cp_eax = (rax & _u(0xFFFFFFFF)).astype(jnp.uint32)
    cp_ecx = (rcx & _u(0xFFFFFFFF)).astype(jnp.uint32)
    cp_exact = (cpuid_keys[:, 0] == cp_eax) & (cpuid_keys[:, 1] == cp_ecx)
    cp_leaf0 = (cpuid_keys[:, 0] == cp_eax) & (cpuid_keys[:, 1] == 0)
    cp_in_basic_fb = ((cp_eax < jnp.uint32(0x80000000))
                      & (cp_eax > jnp.uint32(MAX_BASIC_LEAF)))
    # Masked-sum row selection instead of a dynamic-slice gather of the
    # matching row: CPUID_TABLE keys are unique so at most one row
    # matches each mask and the sum IS that row; the basic-leaf fallback
    # row is a static index, so it constant-folds.  One fewer
    # data-dependent kernel in the compiled ladder (budgets.json).
    cp_exact_row = jnp.sum(
        jnp.where(cp_exact[:, None], cpuid_vals, jnp.uint32(0)), axis=0,
        dtype=jnp.uint32)
    cp_leaf0_row = jnp.sum(
        jnp.where(cp_leaf0[:, None], cpuid_vals, jnp.uint32(0)), axis=0,
        dtype=jnp.uint32)
    cp_basic_row = jnp.asarray(_CPUID_VALS[_CPUID_BASIC_ROW])
    cpuid_out = jnp.where(
        jnp.any(cp_exact), cp_exact_row,
        jnp.where(jnp.any(cp_leaf0), cp_leaf0_row,
                  jnp.where(cp_in_basic_fb, cp_basic_row,
                            jnp.zeros(4, jnp.uint32)))).astype(jnp.uint64)

    # RDTSC / RDRAND / XGETBV / SYSCALL / SWAPGS / MOVCR ---------------
    tsc_now = st.tsc + st.icount
    rdrand_next = _splitmix64(st.rdrand)
    rdrand_rf = (rf & ~_u(FLAGS_ARITH)) | _u(_CF)
    syscall_rf = (rf & ~(st.sfmask | _u(_TF))) | _u(0x2)
    sysret_rf = (r11 & _u(U.RF_WRITABLE)) | _u(0x2)
    cr_read = jnp.select(
        [sub == 0, sub == 2, sub == 3, sub == 4, sub == 8],
        [st.cr0, st.cr2, st.cr3, st.cr4, st.cr8], default=_u(0))
    movcr_is_write = is_(U.OPC_MOVCR) & (sext_f != 0)
    cr_wval = _read_reg(gpr, sr, jnp.int32(8))

    # SSE --------------------------------------------------------------
    with jax.named_scope("vector-file"):
        xmm = st.xmm
        x_dst = _row(xmm, jnp.clip(dr, 0, 15))
        x_sr = _row(xmm, jnp.clip(sr, 0, 15))
        x_dst_lo, x_dst_hi = x_dst[0], x_dst[1]
        x_sr_lo, x_sr_hi = x_sr[0], x_sr[1]
        x_src_lo = jnp.where(sk == U.K_XMM, x_sr_lo, l1_lo)
        x_src_hi = jnp.where(sk == U.K_XMM, x_sr_hi, l1_hi)
    is_ssemov = is_(U.OPC_SSEMOV)
    is_ssealu = is_(U.OPC_SSEALU)
    # movd/movq gpr->xmm (sub 1): value zero-extended into the register
    gpr_to_x = _read_reg(gpr, sr, opsize)
    ssm_in_lo = jnp.where(sub == 1, gpr_to_x, x_src_lo)
    ssm_in_hi = jnp.where(sub == 1, _u(0),
                          jnp.where(opsize >= 16, x_src_hi, _u(0)))
    # movss/movsd xmm,xmm merge low lanes; loads and movq (sub 3) zero upper
    ssm_merge = (sk == U.K_XMM) & (opsize < 16) & (sub != 3) & (sub != 1)
    sz_mask_x = _size_mask(opsize)  # opsize 4/8/16
    ssm_lo = jnp.where(opsize >= 8, ssm_in_lo,
                       jnp.where(ssm_merge,
                                 (x_dst_lo & ~sz_mask_x) | (ssm_in_lo & sz_mask_x),
                                 ssm_in_lo & sz_mask_x))
    ssm_hi = jnp.where(opsize >= 16, ssm_in_hi,
                       jnp.where(ssm_merge, x_dst_hi, _u(0)))
    ssm_hi = jnp.where(sub == 1, _u(0), ssm_hi)
    # movlps/movhps family (sub 4 = low half, 5 = high half): memory loads
    # take l1; reg forms cross halves (movhlps: src HIGH, movlhps: src LOW)
    half4 = jnp.where(sk == U.K_XMM, x_src_hi, l1_lo)
    half5 = jnp.where(sk == U.K_XMM, x_src_lo, l1_lo)
    ssm_lo = jnp.where(sub == 4, half4, jnp.where(sub == 5, x_dst_lo, ssm_lo))
    ssm_hi = jnp.where(sub == 4, x_dst_hi, jnp.where(sub == 5, half5, ssm_hi))

    # byte-level SSE ALU on unpacked u8[16] vectors
    with jax.named_scope("sse-bytes"):
        ba = _unpack_bytes(x_dst_lo, x_dst_hi)
        bb = jnp.where(sk == U.K_XMM,
                       _unpack_bytes(x_sr_lo, x_sr_hi),
                       _unpack_bytes(l1_lo, l1_hi))
        i16u = jnp.arange(16, dtype=jnp.int32)
        eq_b = (ba == bb)
        # word/dword equality via group-reduction of byte equality
        eq_w16 = jnp.repeat(jnp.all(eq_b.reshape(8, 2), axis=1), 2)
        eq_d16 = jnp.repeat(jnp.all(eq_b.reshape(4, 4), axis=1), 4)
        # the shuffles and the byte shifts as selects over the sixteen
        # bytes (one-hot, then a max: the others are 0): no table gather
        pshufd_sel = ((imm >> (jnp.arange(4, dtype=jnp.uint64) * _u(2)))
                      & _u(3)).astype(jnp.int32)
        pshufd_out = jnp.max(
            jnp.where((pshufd_sel[:, None] == jnp.arange(4)[None, :])[:, :, None],
                      bb.reshape(4, 4)[None, :, :], jnp.uint8(0)),
            axis=1).reshape(16)
        pslldq_n = jnp.minimum(imm, _u(16)).astype(jnp.int32)
        pslldq_out = jnp.max(
            jnp.where(i16u[:, None] - pslldq_n == i16u[None, :],
                      ba[None, :], jnp.uint8(0)), axis=1)
        psrldq_out = jnp.max(
            jnp.where(i16u[:, None] + pslldq_n == i16u[None, :],
                      ba[None, :], jnp.uint8(0)), axis=1)
        # pinsrw: word `cond` replaced by the gpr's low word (mem form is
        # oracle-serviced: its 2-byte load doesn't fit the 16-byte window)
        pinsrw_word = _read_reg(gpr, sr, jnp.int32(2))
        pinsrw_byte = jnp.where(i16u % 2 == 0, pinsrw_word & _u(0xFF),
                                (pinsrw_word >> _u(8)) & _u(0xFF)).astype(jnp.uint8)
        sse_bytes = jnp.select(
            [sub == U.SSE_PXOR, sub == U.SSE_XORPS, sub == U.SSE_POR,
             sub == U.SSE_PAND, sub == U.SSE_PANDN,
             sub == U.SSE_PCMPEQB, sub == U.SSE_PCMPEQW, sub == U.SSE_PCMPEQD,
             sub == U.SSE_PSUBB, sub == U.SSE_PADDB, sub == U.SSE_PMINUB,
             sub == U.SSE_PUNPCKLQDQ, sub == U.SSE_PSHUFD,
             sub == U.SSE_PSLLDQ, sub == U.SSE_PSRLDQ,
             sub == U.SSE_PUNPCKLDQ, sub == U.SSE_PINSRW],
            [ba ^ bb, ba ^ bb, ba | bb, ba & bb, (~ba) & bb,
             jnp.where(eq_b, jnp.uint8(0xFF), jnp.uint8(0)),
             jnp.where(eq_w16, jnp.uint8(0xFF), jnp.uint8(0)),
             jnp.where(eq_d16, jnp.uint8(0xFF), jnp.uint8(0)),
             ba - bb, ba + bb, jnp.minimum(ba, bb),
             jnp.concatenate([ba[:8], bb[:8]]),
             pshufd_out,
             pslldq_out,
             psrldq_out,
             # punpckldq: interleave the low dwords -> [a0 b0 a1 b1]
             jnp.concatenate([ba[0:4], bb[0:4], ba[4:8], bb[4:8]]),
             jnp.where(i16u // 2 == cond, pinsrw_byte, ba)],
            default=ba)
        sse_out_lo, sse_out_hi = _pack_pair(sse_bytes)
    # paddq works on the u64 limbs directly (byte-wise adds lose carries)
    is_paddq = is_ssealu & (sub == U.SSE_PADDQ)
    sse_out_lo = jnp.where(is_paddq, x_dst_lo + x_src_lo, sse_out_lo)
    sse_out_hi = jnp.where(is_paddq, x_dst_hi + x_src_hi, sse_out_hi)
    # psllq/psrlq imm: per-qword bit shifts on the limbs (count > 63
    # architecturally zeroes the register)
    shq = jnp.minimum(imm, _u(63))
    shq_zero = imm > _u(63)
    is_psllq = is_ssealu & (sub == U.SSE_PSLLQ_I)
    is_psrlq = is_ssealu & (sub == U.SSE_PSRLQ_I)
    sse_out_lo = jnp.where(
        is_psllq, jnp.where(shq_zero, _u(0), x_dst_lo << shq), sse_out_lo)
    sse_out_hi = jnp.where(
        is_psllq, jnp.where(shq_zero, _u(0), x_dst_hi << shq), sse_out_hi)
    sse_out_lo = jnp.where(
        is_psrlq, jnp.where(shq_zero, _u(0), x_dst_lo >> shq), sse_out_lo)
    sse_out_hi = jnp.where(
        is_psrlq, jnp.where(shq_zero, _u(0), x_dst_hi >> shq), sse_out_hi)
    # pmovmskb: sign bit of each src byte -> gpr bit i
    bsrc_msk = _unpack_bytes(x_sr_lo, x_sr_hi)
    pmov_mask = jnp.sum(
        jnp.where((bsrc_msk & jnp.uint8(0x80)) != 0,
                  _u(1) << i16u.astype(jnp.uint64), _u(0)))
    # pextrw: word `cond` of the src register, zero-extended into the gpr
    pextrw_val = (jnp.where(cond < 4, x_sr_lo, x_sr_hi)
                  >> ((cond & 3).astype(jnp.uint64) * _u(16))) & _u(0xFFFF)
    # ptest
    ptest_zf = ((x_dst_lo & x_src_lo) == _u(0)) & ((x_dst_hi & x_src_hi) == _u(0))
    ptest_cf = (((~x_dst_lo) & x_src_lo) == _u(0)) & (((~x_dst_hi) & x_src_hi) == _u(0))
    ptest_rf = (rf & ~_u(FLAGS_ARITH)) | _mkflags(
        ptest_cf, jnp.bool_(False), jnp.bool_(False), ptest_zf,
        jnp.bool_(False), jnp.bool_(False))

    # -- SSE/SSE2 floating point (OPC_SSEFP), device execution ------------
    # Same semantics as the oracle's _SseFp (emu.py), element-level: NaN
    # payloads preserved and SNaNs quieted at the BIT level (never relying
    # on what NaN the platform's FP unit produces), the dst NaN wins for
    # arithmetic, min/max/cmp forward the second operand on NaN/equality,
    # out-of-range converts produce the integer indefinite.  Normal-range
    # arithmetic rides the platform's f32/f64 units (IEEE bit-exact on the
    # CPU backend — tests/test_step_fp.py pins device == oracle == host
    # CPU); denormal-touching lanes detect themselves and divert to the
    # oracle (see below).  Residual TPU-only caveat: div/sqrt rounding is
    # the platform's — a documented fidelity delta of the fast path,
    # mirroring the bochs-vs-KVM precision split in the reference design.
    with jax.named_scope("sse-fp"):
        fp_elem8 = srcsize0 == 8       # 4 = float32 lanes, 8 = float64 lanes
        _m32 = _u(0xFFFFFFFF)
        fpa32 = jnp.stack([x_dst_lo & _m32, x_dst_lo >> _u(32),
                           x_dst_hi & _m32, x_dst_hi >> _u(32)]).astype(jnp.uint32)
        fpb32 = jnp.stack([x_src_lo & _m32, x_src_lo >> _u(32),
                           x_src_hi & _m32, x_src_hi >> _u(32)]).astype(jnp.uint32)
        fpa64 = jnp.stack([x_dst_lo, x_dst_hi])
        fpb64 = jnp.stack([x_src_lo, x_src_hi])
        fa32 = lax.bitcast_convert_type(fpa32, jnp.float32)
        fb32 = lax.bitcast_convert_type(fpb32, jnp.float32)
        fa64 = lax.bitcast_convert_type(fpa64, jnp.float64)
        fb64 = lax.bitcast_convert_type(fpb64, jnp.float64)

        _QBIT32, _QBIT64 = jnp.uint32(0x00400000), _u(0x0008000000000000)
        _INDEF32, _INDEF64 = jnp.uint32(0xFFC00000), _u(0xFFF8000000000000)

        def _nan32(u):
            return (u & jnp.uint32(0x7FFFFFFF)) > jnp.uint32(0x7F800000)

        def _nan64(u):
            return (u & _u(0x7FFFFFFFFFFFFFFF)) > _u(0x7FF0000000000000)

        def _b32(f):
            return lax.bitcast_convert_type(f, jnp.uint32)

        _b64 = _f64_bits

        nan_a32, nan_b32 = _nan32(fpa32), _nan32(fpb32)
        nan_a64, nan_b64 = _nan64(fpa64), _nan64(fpb64)

        # Denormals: XLA flushes them (FTZ/DAZ) on both the CPU and TPU
        # backends, where the oracle (numpy on the host thread) honors them.
        # Any lane whose FP op *touches* the denormal range — denormal input,
        # or a result the hardware would flush — is routed to the oracle
        # through the same UNSUPPORTED servicing seam, so the fast path keeps
        # the overwhelming normal-range majority and the rare denormal op
        # stays bit-exact.  Detection is conservative (over-flagging is only
        # a performance event, never a correctness one).
        def _den32(u):
            return ((u & jnp.uint32(0x7F800000)) == 0) \
                & ((u & jnp.uint32(0x7FFFFFFF)) != 0)

        def _den64(u):
            return ((u & _u(0x7FF0000000000000)) == _u(0)) \
                & ((u & _u(0x7FFFFFFFFFFFFFFF)) != _u(0))

        @jax.named_scope("sse-fp")
        def _fp_elementwise(fa, fb, ua, ub, nan_a, nan_b, bits, qbit, indef,
                            nanf, denf, magmask, expmask):
            """arith/minmax/sqrt/cmp over one lane-width's vector (f32[4]/f64[2]).

            Returns (result_bits, denormal_risk) per lane."""
            r_arith = jnp.select(
                [sub == U.FP_ADD, sub == U.FP_SUB, sub == U.FP_MUL,
                 sub == U.FP_DIV],
                [fa + fb, fa - fb, fa * fb, fa / fb], default=fa)
            r_bits = bits(r_arith)
            arith_out = jnp.where(
                nan_a, ua | qbit,
                jnp.where(nan_b, ub | qbit,
                          jnp.where(nanf(r_bits), indef, r_bits)))
            take_a = jnp.where(sub == U.FP_MIN, fa < fb, fa > fb)
            mm_out = jnp.where(nan_a | nan_b | (fa == fb), ub,
                               jnp.where(take_a, ua, ub))
            sq_out = jnp.where(
                nan_b, ub | qbit,
                jnp.where(fb < 0, indef, bits(jnp.sqrt(fb))))
            unord = nan_a | nan_b
            pred = (imm & _u(7)).astype(jnp.int32)
            eq, lt, le = fa == fb, fa < fb, fa <= fb
            cmp_res = jnp.select(
                [pred == 0, pred == 1, pred == 2, pred == 3,
                 pred == 4, pred == 5, pred == 6],
                [~unord & eq, ~unord & lt, ~unord & le, unord,
                 unord | ~eq, unord | ~lt, unord | ~le],
                default=~unord)
            ones = ~jnp.zeros_like(ua)
            cmp_out = jnp.where(cmp_res, ones, jnp.zeros_like(ua))
            out = jnp.select(
                [(sub >= U.FP_ADD) & (sub <= U.FP_DIV),
                 (sub == U.FP_MIN) | (sub == U.FP_MAX),
                 sub == U.FP_SQRT],
                [arith_out, mm_out, sq_out], default=cmp_out)
            # FTZ risk: a flushed result reads as +/-0 where the true result
            # was a nonzero denormal; true zeros are exactly the listed cases
            r_zero = (r_bits & magmask) == 0
            true_zero = jnp.select(
                [sub == U.FP_ADD, sub == U.FP_SUB, sub == U.FP_MUL],
                [fa == -fb, fa == fb,
                 ((ua & magmask) == 0) | ((ub & magmask) == 0)],
                default=((ua & magmask) == 0) | ((ub & magmask) == expmask))
            ftz = ((sub >= U.FP_ADD) & (sub <= U.FP_DIV)) \
                & r_zero & ~true_zero & ~nan_a & ~nan_b
            den_in = jnp.where(sub == U.FP_SQRT, denf(ub), denf(ua) | denf(ub))
            return out, ftz | den_in

        ew32, ewrisk32 = _fp_elementwise(
            fa32, fb32, fpa32, fpb32, nan_a32, nan_b32, _b32, _QBIT32,
            _INDEF32, _nan32, _den32, jnp.uint32(0x7FFFFFFF),
            jnp.uint32(0x7F800000))
        ew64, ewrisk64 = _fp_elementwise(
            fa64, fb64, fpa64, fpb64, nan_a64, nan_b64, _b64, _QBIT64,
            _INDEF64, _nan64, _den64, _u(0x7FFFFFFFFFFFFFFF),
            _u(0x7FF0000000000000))

        def _limbs32(v32):
            v = v32.astype(jnp.uint64)
            return v[0] | (v[1] << _u(32)), v[2] | (v[3] << _u(32))

        ew_lo32, ew_hi32 = _limbs32(ew32)
        ew_lo = jnp.where(fp_elem8, ew64[0], ew_lo32)
        ew_hi = jnp.where(fp_elem8, ew64[1], ew_hi32)

        fp_is_f2i = (sub == U.FP_CVT_F2I) | (sub == U.FP_CVT_F2I_T)
        fp_is_comi = (sub == U.FP_UCOMI) | (sub == U.FP_COMI)

        # lanes an op actually reads (scalar forms must not flag junk in the
        # upper lanes of the destination register)
        used32 = jnp.where(sext_f == 1, jnp.ones(4, bool),
                           jnp.arange(4) == 0)
        used64 = jnp.where(sext_f == 1, jnp.ones(2, bool),
                           jnp.arange(2) == 0)
        ew_risk = jnp.where(fp_elem8, jnp.any(ewrisk64 & used64),
                            jnp.any(ewrisk32 & used32))
        comi_risk = jnp.where(fp_elem8,
                              _den64(fpa64[0]) | _den64(fpb64[0]),
                              _den32(fpa32[0]) | _den32(fpb32[0]))
        # f2f: s2d flags denormal f32 inputs (DAZ); d2s flags any f64 input
        # small enough that the f32 result lands at/under the normal minimum
        d2s_small = (((fpb64 >> _u(52)) & _u(0x7FF)) <= _u(897)) \
            & ((fpb64 & _u(0x7FFFFFFFFFFFFFFF)) != _u(0))
        f2f_risk = jnp.where(fp_elem8, jnp.any(d2s_small & used64),
                             jnp.any(_den32(fpb32)
                                     & jnp.where(sext_f == 1,
                                                 jnp.arange(4) < 2,
                                                 jnp.arange(4) == 0)))
        fp_denorm = is_ssefp & jnp.select(
            [fp_is_ew, fp_is_comi, sub == U.FP_CVT_F2F],
            [ew_risk, comi_risk, f2f_risk], default=jnp.bool_(False))

        # ucomis/comis flag image: unordered -> ZF=PF=CF=1; else ZF=(a==b),
        # CF=(a<b), PF=0; OF/AF/SF cleared (oracle set_flags call)
        uc_unord = jnp.where(fp_elem8, nan_a64[0] | nan_b64[0],
                             nan_a32[0] | nan_b32[0])
        uc_eq = jnp.where(fp_elem8, fa64[0] == fb64[0], (fa32[0] == fb32[0]))
        uc_lt = jnp.where(fp_elem8, fa64[0] < fb64[0], (fa32[0] < fb32[0]))
        ucomi_rf = (rf & ~_u(FLAGS_ARITH)) | _mkflags(
            uc_unord | (~uc_unord & uc_lt), uc_unord, jnp.bool_(False),
            uc_unord | (~uc_unord & uc_eq), jnp.bool_(False), jnp.bool_(False))

        # int -> fp scalar (cvtsi2ss/sd): integer comes from a GPR or an
        # opsize-wide memory load, sign-extended, rounded ONCE by the convert
        i2f_raw = jnp.where(sk == U.K_REG, _read_reg(gpr, sr, opsize),
                            l1_lo & _size_mask(opsize))
        i2f_int = _sext(i2f_raw, opsize).astype(jnp.int64)
        i2f_b32 = _b32(i2f_int.astype(jnp.float32)).astype(jnp.uint64)
        i2f_b64 = _b64(i2f_int.astype(jnp.float64))
        i2f_lo = jnp.where(fp_elem8, i2f_b64, i2f_b32)

        # fp -> int (cvt/cvtt to gpr, and the packed PS2DQ/PD2DQ families):
        # rounding/range logic runs in f64 exactly like the oracle's to_int
        # (f32 widens losslessly first), indefinite = 1 << (bits-1)
        @jax.named_scope("sse-fp")
        def _fp_to_int(v64, int_bits, truncate, src_nan):
            limit = jnp.float64(2.0) ** (int_bits - 1)
            r = jnp.where(truncate, jnp.trunc(v64),
                          lax.round(v64, lax.RoundingMethod.TO_NEAREST_EVEN))
            bad = src_nan | jnp.isnan(v64) | (r >= limit) | (r < -limit)
            indef = _u(1) << jnp.uint64(int_bits - 1)
            safe = jnp.clip(r, -limit, limit - 1)
            return jnp.where(bad, indef,
                             safe.astype(jnp.int64).astype(jnp.uint64)
                             & _size_mask(jnp.int32(int_bits // 8)))

        f2i_src64 = jnp.where(fp_elem8, fb64[0], fb32[0].astype(jnp.float64))
        f2i_nan = jnp.where(fp_elem8, nan_b64[0], nan_b32[0])
        f2i_trunc = sub == U.FP_CVT_F2I_T
        f2i_val = jnp.where(
            opsize >= 8,
            _fp_to_int(f2i_src64, 64, f2i_trunc, f2i_nan),
            _fp_to_int(f2i_src64, 32, f2i_trunc, f2i_nan))

        # f32 <-> f64 converts: NaNs rebuilt at the bit level (payload shifted,
        # quiet bit forced) so the device never depends on platform NaN rules
        @jax.named_scope("sse-fp")
        def _cvt_s2d(u32v, f32v, isnan):
            sign = (u32v.astype(jnp.uint64) >> _u(31)) << _u(63)
            frac = (u32v.astype(jnp.uint64) & _u(0x7FFFFF)) << _u(29)
            nan_bits = sign | _u(0x7FF0000000000000) | _QBIT64 | frac
            return jnp.where(isnan, nan_bits, _b64(f32v.astype(jnp.float64)))

        @jax.named_scope("sse-fp")
        def _cvt_d2s(u64v, f64v, isnan):
            sign = (u64v >> _u(63)).astype(jnp.uint32) << jnp.uint32(31)
            frac = ((u64v >> _u(29)) & _u(0x3FFFFF)).astype(jnp.uint32)
            nan_bits = sign | jnp.uint32(0x7F800000) | _QBIT32 | frac
            return jnp.where(isnan, nan_bits, _b32(f64v.astype(jnp.float32)))

        s2d = _cvt_s2d(fpb32, fb32, nan_b32)          # u64[4], lanes 0/1 used
        d2s = _cvt_d2s(fpb64, fb64, nan_b64)          # u32[2]
        f2f_packed_lo4 = s2d[0]                        # cvtps2pd
        f2f_packed_hi4 = s2d[1]
        f2f_packed_lo8 = (d2s[0].astype(jnp.uint64)
                          | (d2s[1].astype(jnp.uint64) << _u(32)))  # cvtpd2ps
        f2f_lo = jnp.where(fp_elem8, f2f_packed_lo8, f2f_packed_lo4)
        f2f_hi = jnp.where(fp_elem8, _u(0), f2f_packed_hi4)

        # packed int <-> fp families (each writes the full register)
        dq2ps = _b32(fpb32.astype(jnp.int32).astype(jnp.float32))
        ps2dq_t = sub == U.FP_CVT_PS2DQ_T
        ps2dq = jnp.stack([
            _fp_to_int(fb32[i].astype(jnp.float64), 32, ps2dq_t, nan_b32[i])
            for i in range(4)]).astype(jnp.uint32)
        dq2pd_lo = _b64(fpb32[0].astype(jnp.int32).astype(jnp.float64))
        dq2pd_hi = _b64(fpb32[1].astype(jnp.int32).astype(jnp.float64))
        pd2dq_t = sub == U.FP_CVT_PD2DQ_T
        pd2dq = jnp.stack([
            _fp_to_int(fb64[i], 32, pd2dq_t, nan_b64[i]) for i in range(2)])
        pd2dq_lo = (pd2dq[0] & _m32) | ((pd2dq[1] & _m32) << _u(32))

        # shufps/shufpd, unpckl/h ps/pd: pure lane shuffles
        shuf_sel = imm
        sh32_src = jnp.concatenate([fpa32, fpb32])    # picks: dst,dst,src,src
        shufps = jnp.stack([
            _row(sh32_src, (0 if i < 2 else 4)
                 + ((shuf_sel >> _u(2 * i)) & _u(3)).astype(jnp.int32))
            for i in range(4)])
        shufpd_lo = jnp.where((shuf_sel & _u(1)) != 0, x_dst_hi, x_dst_lo)
        shufpd_hi = jnp.where((shuf_sel & _u(2)) != 0, x_src_hi, x_src_lo)
        shufps_lo, shufps_hi = _limbs32(shufps)
        unp_h = sub == U.FP_UNPCKH
        unpck32 = jnp.stack([
            jnp.where(unp_h, fpa32[2], fpa32[0]), jnp.where(unp_h, fpb32[2], fpb32[0]),
            jnp.where(unp_h, fpa32[3], fpa32[1]), jnp.where(unp_h, fpb32[3], fpb32[1])])
        unpck32_lo, unpck32_hi = _limbs32(unpck32)
        unpck64_lo = jnp.where(unp_h, x_dst_hi, x_dst_lo)
        unpck64_hi = jnp.where(unp_h, x_src_hi, x_src_lo)

        fp_sub_sel = [
            sub == U.FP_CVT_I2F,
            sub == U.FP_CVT_F2F,
            sub == U.FP_CVT_DQ2PS,
            (sub == U.FP_CVT_PS2DQ) | (sub == U.FP_CVT_PS2DQ_T),
            sub == U.FP_CVT_DQ2PD,
            (sub == U.FP_CVT_PD2DQ) | (sub == U.FP_CVT_PD2DQ_T),
            sub == U.FP_SHUF,
            (sub == U.FP_UNPCKL) | (sub == U.FP_UNPCKH),
        ]
        dq2ps_lo, dq2ps_hi = _limbs32(dq2ps)
        ps2dq_lo, ps2dq_hi = _limbs32(ps2dq)
        fp_res_lo = jnp.select(fp_sub_sel, [
            i2f_lo, f2f_lo, dq2ps_lo, ps2dq_lo, dq2pd_lo, pd2dq_lo,
            jnp.where(fp_elem8, shufpd_lo, shufps_lo),
            jnp.where(fp_elem8, unpck64_lo, unpck32_lo),
        ], default=ew_lo)
        fp_res_hi = jnp.select(fp_sub_sel, [
            _u(0), f2f_hi, dq2ps_hi, ps2dq_hi, dq2pd_hi, _u(0),
            jnp.where(fp_elem8, shufpd_hi, shufps_hi),
            jnp.where(fp_elem8, unpck64_hi, unpck32_hi),
        ], default=ew_hi)
        # destination write width: 16 = whole register, else low bytes merge
        fp_wsz = jnp.select(
            [sub == U.FP_CVT_I2F,
             sub == U.FP_CVT_F2F,
             fp_is_ew],
            [srcsize0,
             jnp.where(sext_f == 1, jnp.int32(16), 12 - srcsize0),
             jnp.where(sext_f == 1, jnp.int32(16), srcsize0)],
            default=jnp.int32(16))
        fp_wlo_mask = _size_mask(jnp.minimum(fp_wsz, 8))
        fp_out_lo = (x_dst_lo & ~fp_wlo_mask) | (fp_res_lo & fp_wlo_mask)
        fp_out_hi = jnp.where(fp_wsz >= 16, fp_res_hi, x_dst_hi)
        fp_writes_xmm = is_ssefp & ~fp_is_f2i & ~fp_is_comi

    # -- x87 (OPC_X87) device execution -----------------------------------
    # The same f64-value model as the oracle (emu._exec_x87; bit-exact vs
    # hardware under Windows' PC=53 control word): the register stack is
    # fpst[8] physical slots with TOP in fpsw bits 11-13, values are f64
    # bits, and arithmetic rides the same NaN-routing helpers as the SSE
    # block (dst-NaN-wins, quieting, real-indefinite).  FXSAVE-class
    # state movers stay oracle-serviced (x87_oracle above); denormal-
    # touching lanes divert like SSE-FP lanes do.
    with jax.named_scope("x87"):
        fpst_v, fpcw_v, fpsw_v, fptw_v = st.fpst, st.fpcw, st.fpsw, st.fptw
        x_top = (fpsw_v >> _u(11)) & _u(7)
        x_i = imm & _u(7)

        @jax.named_scope("x87")
        def _xphys(k):
            return ((x_top + k) & _u(7)).astype(jnp.int32)

        x_ph0 = _xphys(_u(0))
        x_phi = _xphys(x_i)
        with jax.named_scope("vector-file"):
            st0_b = _row(fpst_v, x_ph0)
            sti_b = _row(fpst_v, x_phi)
        st0_f = lax.bitcast_convert_type(st0_b, jnp.float64)

        # memory operand -> f64 bits: m64 is a raw bit move, m32 converts
        # with the NaN-safe widening, integers convert exactly like the
        # oracle's int64 -> float64
        xm32_u = (l1_lo & _m32).astype(jnp.uint32)
        xm32_f = lax.bitcast_convert_type(xm32_u, jnp.float32)
        x_mem_b = jnp.where(srcsize0 >= 8, l1_lo,
                            _cvt_s2d(xm32_u, xm32_f, _nan32(xm32_u)))
        x_fild_b = _b64(_sext(l1_lo, srcsize0).astype(jnp.int64)
                        .astype(jnp.float64))

        # arithmetic (ADD/MUL/SUB/SUBR/DIV/DIVR by the encoded digit; COM/
        # COMP digits compare instead)
        x_arith_m = sub == U.X87_ARITH_M
        x_arith_st = sub == U.X87_ARITH_ST
        x_dsti = x_arith_st & (dr == 1)       # DC/DE: st(i) is the destination
        xa_b = jnp.where(x_dsti, sti_b, st0_b)
        xb_b = jnp.where(x_arith_m, x_mem_b,
                         jnp.where(x_dsti, st0_b, sti_b))
        xa_f = lax.bitcast_convert_type(xa_b, jnp.float64)
        xb_f = lax.bitcast_convert_type(xb_b, jnp.float64)
        x_r = jnp.select(
            [cond == U.X87_OP_ADD, cond == U.X87_OP_MUL,
             cond == U.X87_OP_SUB, cond == U.X87_OP_SUBR,
             cond == U.X87_OP_DIV],
            [xa_f + xb_f, xa_f * xb_f, xa_f - xb_f, xb_f - xa_f, xa_f / xb_f],
            default=xb_f / xa_f)  # X87_OP_DIVR
        x_r_b = _b64(x_r)
        nan_xa, nan_xb = _nan64(xa_b), _nan64(xb_b)
        # NaN routing follows the OPERATION's operand order: hardware
        # propagates the first source operand's NaN, and for the reversed
        # forms (fsubr/fdivr: b OP a) that is xb — matching the oracle's
        # `bn - an` / `bn / an`
        x_rev = (cond == U.X87_OP_SUBR) | (cond == U.X87_OP_DIVR)
        x_n1 = jnp.where(x_rev, nan_xb, nan_xa)
        x_n1_b = jnp.where(x_rev, xb_b, xa_b)
        x_n2 = jnp.where(x_rev, nan_xa, nan_xb)
        x_n2_b = jnp.where(x_rev, xa_b, xb_b)
        x_arith_out = jnp.where(
            x_n1, x_n1_b | _QBIT64,
            jnp.where(x_n2, x_n2_b | _QBIT64,
                      jnp.where(_nan64(x_r_b), _INDEF64, x_r_b)))
        x_is_com_digit = (cond == U.X87_OP_COM) | (cond == U.X87_OP_COMP)
        x_arith_writes = (x_arith_m | x_arith_st) & ~x_is_com_digit

        # compares: fcom/fucom (C3/C2/C0 in the status word), fcomi/fucomi
        # (ZF/PF/CF in rflags) — same unordered rules as ucomis
        x_cmp_b = jnp.where(x_arith_m, x_mem_b, sti_b)
        x_cmp_bf = lax.bitcast_convert_type(x_cmp_b, jnp.float64)
        x_unord = _nan64(st0_b) | _nan64(x_cmp_b)
        x_eq = st0_f == x_cmp_bf
        x_lt = st0_f < x_cmp_bf
        x87_comi_rf = (rf & ~_u(FLAGS_ARITH)) | _mkflags(
            x_unord | (~x_unord & x_lt), x_unord, jnp.bool_(False),
            x_unord | (~x_unord & x_eq), jnp.bool_(False), jnp.bool_(False))
        x_com_bits = (jnp.where(x_unord | (~x_unord & x_eq), _u(0x4000), _u(0))
                      | jnp.where(x_unord, _u(0x400), _u(0))
                      | jnp.where(x_unord | (~x_unord & x_lt), _u(0x100), _u(0)))
        x_is_com = is_x87 & ((sub == U.X87_COM)
                             | ((x_arith_m | x_arith_st) & x_is_com_digit))

        # fist(p)/fisttp: fpcw.RC rounding (fisttp always chops), integer
        # indefinite on NaN/overflow — the oracle's _exec_x87 FIST logic
        x_rc = jnp.where(sub == U.X87_FIST_T, _u(3), (fpcw_v >> _u(10)) & _u(3))
        x_bits_n = srcsize0 * 8
        x_limit = jnp.exp2((x_bits_n - 1).astype(jnp.float64))
        x_round = jnp.select(
            [x_rc == _u(0), x_rc == _u(1), x_rc == _u(2)],
            [lax.round(st0_f, lax.RoundingMethod.TO_NEAREST_EVEN),
             jnp.floor(st0_f), jnp.ceil(st0_f)],
            default=jnp.trunc(st0_f))
        x_fist_bad = _nan64(st0_b) | (x_round >= x_limit) | (x_round < -x_limit)
        x_fist_safe = jnp.clip(x_round, -x_limit, x_limit - 1)
        x_fist_val = jnp.where(
            x_fist_bad,
            _shl(_u(1), (x_bits_n - 1).astype(jnp.uint64)),
            x_fist_safe.astype(jnp.int64).astype(jnp.uint64)
            ) & _size_mask(srcsize0)

        # fst m32: NaN-safe narrowing of st0
        x_fst32 = _cvt_d2s(st0_b, st0_f, _nan64(st0_b)).astype(jnp.uint64)
        x87_store_val = jnp.select(
            [sub == U.X87_FST_M,
             (sub == U.X87_FIST) | (sub == U.X87_FIST_T),
             sub == U.X87_FNSTCW,
             sub == U.X87_FNSTSW_M],
            [jnp.where(srcsize0 >= 8, st0_b, x_fst32),
             x_fist_val, fpcw_v & _u(0xFFFF), fpsw_v & _u(0xFFFF)],
            default=st.mxcsr & _u(0xFFFFFFFF))  # STMXCSR

        # pushes
        x_is_push = is_x87 & (
            (sub == U.X87_FLD_M) | (sub == U.X87_FILD)
            | (sub == U.X87_FLD_STI) | (sub == U.X87_FLD_CONST))
        x_push_b = jnp.select(
            [sub == U.X87_FLD_M, sub == U.X87_FILD, sub == U.X87_FLD_STI],
            [x_mem_b, x_fild_b, sti_b],
            default=jnp.where(imm == _u(0), _u(0x3FF0000000000000), _u(0)))
        x_push_slot = ((x_top - _u(1)) & _u(7)).astype(jnp.int32)

        # register-stack writes: one generic write + the FXCH partner write
        x_fxch = sub == U.X87_FXCH
        x_w1_en = is_x87 & (
            x_is_push | x_arith_writes | (sub == U.X87_FST_STI)
            | (sub == U.X87_FCHS) | (sub == U.X87_FABS) | x_fxch)
        x_w1_idx = jnp.select(
            [x_is_push, x_arith_writes & x_dsti, sub == U.X87_FST_STI],
            [x_push_slot, x_phi, x_phi], default=x_ph0)
        x_w1_val = jnp.select(
            [x_is_push, x_arith_writes, sub == U.X87_FST_STI,
             sub == U.X87_FCHS, sub == U.X87_FABS],
            [x_push_b, x_arith_out, st0_b,
             st0_b ^ _u(1 << 63), st0_b & _u((1 << 63) - 1)],
            default=sti_b)  # FXCH: st0 <- st(i)

        # stack top / tag word / control+status words
        x_pops = jnp.where(is_x87, sext_f, jnp.int32(0))
        x_fninit = sub == U.X87_FNINIT
        x_new_top = jnp.where(
            x_fninit, _u(0),
            jnp.where(x_is_push, (x_top - _u(1)) & _u(7),
                      (x_top + x_pops.astype(jnp.uint64)) & _u(7)))

        @jax.named_scope("x87")
        def _tag_set(tw, phys_i32, val):
            sh = phys_i32.astype(jnp.uint64) * _u(2)
            return (tw & ~(_u(3) << sh)) | (_u(val) << sh)

        x_tw = fptw_v
        x_tw = jnp.where(x_is_push, _tag_set(x_tw, x_push_slot, 0), x_tw)
        x_tw = jnp.where(is_x87 & (sub == U.X87_FST_STI),
                         _tag_set(x_tw, x_phi, 0), x_tw)
        x_tw = jnp.where(is_x87 & (x_pops >= 1), _tag_set(x_tw, x_ph0, 3), x_tw)
        x_tw = jnp.where(is_x87 & (x_pops >= 2),
                         _tag_set(x_tw, _xphys(_u(1)), 3), x_tw)
        x_tw = jnp.where(is_x87 & (sub == U.X87_FFREE),
                         _tag_set(x_tw, x_phi, 3), x_tw)
        x_tw = jnp.where(is_x87 & (x_fninit | (sub == U.X87_EMMS)),
                         _u(0xFFFF), x_tw)

        x_cw = jnp.where(is_x87 & (sub == U.X87_FLDCW), l1_lo & _u(0xFFFF),
                         jnp.where(is_x87 & x_fninit, _u(0x37F), fpcw_v))
        x_sw = fpsw_v
        x_sw = jnp.where(x_is_com, (x_sw & ~_u(0x4500)) | x_com_bits, x_sw)
        x_sw = jnp.where(is_x87 & (sub == U.X87_FNCLEX), x_sw & ~_u(0x80FF), x_sw)
        x_sw = jnp.where(is_x87 & x_fninit, _u(0), x_sw)
        x_sw = jnp.where(is_x87,
                         (x_sw & ~_u(0x3800)) | (x_new_top << _u(11)), x_sw)

        # denormal / FTZ risk -> oracle divert, same policy as the SSE block
        x_r_zero = (x_r_b & _u(0x7FFFFFFFFFFFFFFF)) == _u(0)
        x_true_zero = jnp.select(
            [cond == U.X87_OP_ADD,
             (cond == U.X87_OP_SUB) | (cond == U.X87_OP_SUBR),
             cond == U.X87_OP_MUL],
            [xa_f == -xb_f, xa_f == xb_f,
             ((xa_b & _u(0x7FFFFFFFFFFFFFFF)) == _u(0))
             | ((xb_b & _u(0x7FFFFFFFFFFFFFFF)) == _u(0))],
            default=((jnp.where(cond == U.X87_OP_DIV, xa_b, xb_b)
                      & _u(0x7FFFFFFFFFFFFFFF)) == _u(0))
            | ((jnp.where(cond == U.X87_OP_DIV, xb_b, xa_b)
                & _u(0x7FFFFFFFFFFFFFFF)) == _u(0x7FF0000000000000)))
        x_ftz = (x_arith_m | x_arith_st) & ~x_is_com_digit \
            & x_r_zero & ~x_true_zero & ~nan_xa & ~nan_xb
        # an m32 arith operand needs the f32-level denormal check: DAZ in
        # the widening flushes it before _den64 could ever see it (a
        # converted f32 denormal is a NORMAL f64)
        x_den_arith = (x_arith_m | x_arith_st) & (
            _den64(xa_b) | _den64(xb_b)
            | (x_arith_m & (srcsize0 < 8) & _den32(xm32_u)))
        x_fst32_small = (((st0_b >> _u(52)) & _u(0x7FF)) <= _u(897)) \
            & ((st0_b & _u(0x7FFFFFFFFFFFFFFF)) != _u(0))
        x87_denorm = is_x87 & ~x87_oracle & jnp.select(
            [x_arith_m | x_arith_st,
             (sub == U.X87_FLD_M) & (srcsize0 < 8),
             (sub == U.X87_FST_M) & (srcsize0 < 8),
             (sub == U.X87_FIST) | (sub == U.X87_FIST_T),
             (sub == U.X87_COM) | (sub == U.X87_COMI)],
            [x_ftz | x_den_arith,
             _den32(xm32_u),
             x_fst32_small,
             _den64(st0_b),
             _den64(st0_b) | _den64(x_cmp_b)],
            default=jnp.bool_(False))

    # -- 5. result routing -------------------------------------------------
    cc01 = jnp.where(cc_true, _u(1), _u(0))
    is_mul = is_(U.OPC_MUL)
    is_swapgs = is_(U.OPC_RDGSBASE) & (sub == 4)
    i0, i1_, i2_, i4_, i5_, i11_ = (jnp.int32(0), jnp.int32(1), jnp.int32(2),
                                    jnp.int32(4), jnp.int32(5), jnp.int32(11))

    # primary register write (the generic `store_dst` reg case of emu.py).
    # Ported-class values (MOV/LEA/ALU/UNARY/SETCC/CMOVCC) were computed
    # on u32 limbs above and enter this chain as free bitcasts — one
    # shared register-file scatter for hot and cold classes alike.
    w1_cond = opc_list([
        (is_(U.OPC_MOV), dk == U.K_REG),
        (is_(U.OPC_LEA), jnp.bool_(True)),
        (is_(U.OPC_ALU), alu_writes & (dk == U.K_REG)),
        (is_(U.OPC_SHIFT), sh_writes & (dk == U.K_REG)),
        (is_(U.OPC_UNARY), dk == U.K_REG),
        (is_mul, jnp.bool_(True)),
        (is_(U.OPC_DIV), jnp.bool_(True)),
        (is_pop, dk == U.K_REG),
        (is_(U.OPC_SETCC), dk == U.K_REG),
        (is_(U.OPC_CMOVCC), jnp.bool_(True)),
        (is_(U.OPC_BT), bt_writes & (dk == U.K_REG)),
        (is_(U.OPC_BITSCAN), bs_writes),
        (is_(U.OPC_CONVERT), jnp.bool_(True)),
        (is_(U.OPC_FLAGOP), sub == U.FL_LAHF),
        (is_(U.OPC_BSWAP), jnp.bool_(True)),
        (is_(U.OPC_CMPXCHG), dk == U.K_REG),
        (is_(U.OPC_XADD), dk == U.K_REG),
        (is_leave | is_enter, jnp.bool_(True)),
        (is_(U.OPC_RDTSC), jnp.bool_(True)),
        (is_(U.OPC_RDRAND), jnp.bool_(True)),
        (is_(U.OPC_XGETBV), jnp.bool_(True)),
        (is_string, s_lods & ~rep_skip),
        (is_(U.OPC_SYSCALL), syscall_entry),
        (is_(U.OPC_MOVCR), ~movcr_is_write),
        (is_(U.OPC_XCHG), dk == U.K_REG),
        (is_ssemov, (sub == 2) & (dk == U.K_REG)),
        (is_ssealu, (sub == U.SSE_PMOVMSKB) | (sub == U.SSE_PEXTRW)),
        (is_ssefp, fp_is_f2i),
        (is_x87, sub == U.X87_FNSTSW_AX),
        (is_(U.OPC_PEXT), jnp.bool_(True)),
        (is_(U.OPC_MSR), sub == 0),   # rdmsr -> eax
        (is_(U.OPC_RDGSBASE), (sub == 0) | (sub == 1)),  # rd{fs,gs}base
    ], jnp.bool_(False))
    w1_idx = opc_list([
        (is_mul, jnp.where(is_mul2, dr, i0)),
        (is_(U.OPC_DIV) | is_(U.OPC_MSR), i0),
        (is_(U.OPC_CONVERT), jnp.where(sub == 0, i0, i2_)),
        (is_(U.OPC_FLAGOP), jnp.int32(U.REG_AH_BASE)),
        (is_leave | is_enter, i5_),
        (is_(U.OPC_RDTSC) | is_(U.OPC_XGETBV), i0),
        (is_string, i0),
        (is_(U.OPC_SYSCALL), i11_),
    ], dr)
    w1_val = opc_list([
        (is_(U.OPC_MOV), src_val),
        (is_(U.OPC_LEA), ea),
        (is_(U.OPC_ALU), alu_r),
        (is_(U.OPC_SHIFT), sh_r),
        (is_(U.OPC_UNARY), un_r),
        (is_mul, L.to_u64(mul_r1_l)),
        (is_(U.OPC_DIV), div_q),
        (is_pop, l1_lo & opmask),
        (is_(U.OPC_SETCC), cc01),
        (is_(U.OPC_CMOVCC), jnp.where(cc_true, src_val, dst_val)),
        (is_(U.OPC_BT), bt_r),
        (is_(U.OPC_BITSCAN), bs_r),
        (is_(U.OPC_CONVERT), jnp.where(sub == 0, cvt_widen, cvt_sign)),
        (is_(U.OPC_FLAGOP), lahf_val),
        (is_(U.OPC_BSWAP), bsw_r),
        (is_(U.OPC_CMPXCHG), cx_store),
        (is_(U.OPC_XADD), xadd_r),
        (is_leave, l1_lo),
        (is_enter, rsp - _u(8)),   # rbp = frame pointer
        (is_(U.OPC_RDTSC), tsc_now & _u(0xFFFFFFFF)),
        (is_(U.OPC_RDRAND), rdrand_next & opmask),
        (is_(U.OPC_XGETBV), _u(7)),
        (is_string, l1_lo & opmask),
        (is_(U.OPC_SYSCALL), rf & ~_u(0x10000)),
        (is_(U.OPC_MOVCR), cr_read),
        (is_(U.OPC_XCHG), src_val),
        (is_ssemov, x_sr_lo),
        (is_ssealu, jnp.where(sub == U.SSE_PEXTRW, pextrw_val, pmov_mask)),
        (is_ssefp, f2i_val),
        (is_x87, fpsw_v & _u(0xFFFF)),
        (is_(U.OPC_PEXT), bmi_res),
        (is_(U.OPC_MSR), msr_rval & _u(0xFFFFFFFF)),
        (is_(U.OPC_RDGSBASE),
         jnp.where(sub == 0, st.fs_base, st.gs_base)),
    ], _u(0))
    w1_size = opc_list([
        (is_mul, jnp.where(is_mul2, opsize,
                           jnp.where(opsize == 1, jnp.int32(2), opsize))),
        (is_(U.OPC_FLAGOP), jnp.int32(1)),
        (is_leave | is_enter | is_(U.OPC_RDTSC) | is_(U.OPC_SYSCALL)
         | is_(U.OPC_MOVCR) | is_(U.OPC_MSR), jnp.int32(8)),
        (is_(U.OPC_XGETBV) | is_ssealu, jnp.int32(4)),
        (is_x87, jnp.int32(2)),  # fnstsw ax
    ], opsize)

    # secondary register write
    w2_cond = opc_list([
        (is_(U.OPC_XCHG), sk == U.K_REG),
        (is_mul, ~is_mul2 & (opsize > 1)),
        (is_(U.OPC_DIV), jnp.bool_(True)),
        (is_(U.OPC_CMPXCHG), ~cx_eq),
        (is_(U.OPC_XADD), jnp.bool_(True)),
        (is_(U.OPC_RDTSC) | is_(U.OPC_XGETBV), jnp.bool_(True)),
        (is_(U.OPC_SYSCALL), syscall_entry),
        (is_(U.OPC_MSR), sub == 0),   # rdmsr -> edx
    ], jnp.bool_(False))
    w2_idx = opc_list([
        (is_(U.OPC_XCHG) | is_(U.OPC_XADD), sr),
        (is_(U.OPC_DIV), jnp.where(opsize == 1,
                                   jnp.int32(U.REG_AH_BASE), i2_)),
        (is_(U.OPC_CMPXCHG), i0),
        (is_(U.OPC_SYSCALL), i1_),
    ], i2_)
    w2_val = opc_list([
        (is_(U.OPC_XCHG) | is_(U.OPC_XADD) | is_(U.OPC_CMPXCHG), dst_val),
        (is_mul, L.to_u64(mul_r2_l)),
        (is_(U.OPC_DIV), div_rem),
        (is_(U.OPC_RDTSC), tsc_now >> _u(32)),
        (is_(U.OPC_XGETBV), _u(0)),
        (is_(U.OPC_SYSCALL), next_rip),
        (is_(U.OPC_MSR), msr_rval >> _u(32)),
    ], _u(0))
    w2_size = opc_list([
        (is_(U.OPC_DIV), jnp.where(opsize == 1, jnp.int32(1), opsize)),
        (is_(U.OPC_RDTSC) | is_(U.OPC_SYSCALL) | is_(U.OPC_MSR),
         jnp.int32(8)),
        (is_(U.OPC_XGETBV), jnp.int32(4)),
    ], opsize)

    # rsp adjustment (push_size computed with the store span, section 4b)
    w3_cond = (is_push | is_pushf | is_call | is_pop | is_popf | is_ret
               | is_leave | is_enter | is_iretq)
    w3_val = opc_list([
        (is_iretq, l2_hi),
        (is_push | is_pushf | is_call, rsp - push_size.astype(jnp.uint64)),
        (is_pop, rsp + opsize.astype(jnp.uint64)),
        (is_popf, rsp + _u(8)),
        (is_ret, rsp + _u(8) + imm),
        (is_leave, rbp + _u(8)),
        (is_enter, rsp - _u(8) - imm),  # push rbp then alloc imm bytes
    ], rsp)

    # string pointer/count updates
    w4_cond = (s_movs | s_lods | s_cmps) & ~rep_skip   # rsi
    w5_cond = (s_movs | s_stos | s_scas | s_cmps) & ~rep_skip  # rdi
    w6_cond = rep_on & ~rep_skip                        # rcx

    # -- memory store ------------------------------------------------------
    mem_class_writes = opc_list([
        (is_(U.OPC_MOV), jnp.bool_(True)),
        (is_(U.OPC_ALU), alu_writes),
        (is_(U.OPC_SHIFT), sh_writes),
        (is_(U.OPC_UNARY) | is_(U.OPC_SETCC) | is_(U.OPC_CMPXCHG)
         | is_(U.OPC_XADD) | is_pop | is_(U.OPC_XCHG) | is_ssemov,
         jnp.bool_(True)),
        (is_(U.OPC_BT), bt_writes),
    ], jnp.bool_(False))
    st_need = live & ~unsupported & ~rep_skip & (
        ((dk == U.K_MEM) & mem_class_writes)
        | is_push | is_pushf | is_call | is_enter
        | s_movs | s_stos | x87_store)
    st_lo = opc_list([
        (is_(U.OPC_MOV) | is_push, src_val),
        (is_(U.OPC_ALU), alu_r),
        (is_(U.OPC_SHIFT), sh_r),
        (is_(U.OPC_UNARY), un_r),
        (is_(U.OPC_SETCC), cc01),
        (is_(U.OPC_BT), bt_r),
        (is_(U.OPC_CMPXCHG), cx_store),
        (is_(U.OPC_XADD), xadd_r),
        (is_pop, l1_lo & opmask),
        (is_(U.OPC_XCHG), src_val),
        (is_call, next_rip),
        (is_pushf, rf | _u(0x2)),
        (is_enter, rbp),
        (s_stos, rax_op),
        (s_movs, l1_lo),
        # movhps-store (sub 5) writes the HIGH xmm limb; everything else
        # in the class stores from the low limb
        (is_ssemov, jnp.where(sub == 5, x_sr_hi, x_sr_lo)),
        (is_x87, x87_store_val),
    ], _u(0))
    st_hi = jnp.where(is_ssemov, x_sr_hi,
                      jnp.where(s_movs, l1_hi, _u(0)))

    # store translations (ts0/ts1) come from the step's single batched walk
    store_fault = st_need & ~(ts0.ok & ts1.ok & ts0.writable & ts1.writable)

    page_fault = live & ~unsupported & ~is_crash & (fault1 | fault2 | store_fault)
    fp_oracle = live & ~unsupported & ~page_fault & (fp_denorm | x87_denorm)
    commit_pre = live & ~unsupported & ~is_crash & ~de & ~page_fault \
        & ~fp_oracle & ~iret_a

    overlay, store_ok, store, spill = store_window3(
        image, overlay, ts0, ts1, st_size, st_lo, st_hi,
        st_need & commit_pre)
    ovf = st_need & commit_pre & ~store_ok
    commit = commit_pre & ~ovf

    # -- register file application (order: rsp/rsi/rdi/rcx, aux, primary) --
    with jax.named_scope("regfile-write"):
        new_gpr = gpr
        new_gpr = new_gpr.at[4].set(jnp.where(commit & w3_cond, w3_val, new_gpr[4]))
        new_gpr = new_gpr.at[6].set(jnp.where(commit & w4_cond,
                                              rsi + str_delta, new_gpr[6]))
        new_gpr = new_gpr.at[7].set(jnp.where(commit & w5_cond,
                                              rdi + str_delta, new_gpr[7]))
        new_gpr = new_gpr.at[1].set(jnp.where(commit & w6_cond,
                                              rcx_dec, new_gpr[1]))
        new_gpr = _gpr_write(new_gpr, commit & w2_cond, w2_idx, w2_val, w2_size)
        new_gpr = _gpr_write(new_gpr, commit & w1_cond, w1_idx, w1_val, w1_size)
        # CPUID writes all four GPRs (32-bit, zero-extending), one more than
        # the generic two-write router carries (oracle: emu.py OPC_CPUID)
        cpw = commit & is_(U.OPC_CPUID)
        for ridx, col in ((0, 0), (3, 1), (1, 2), (2, 3)):  # eax, ebx, ecx, edx
            new_gpr = new_gpr.at[ridx].set(
                jnp.where(cpw, cpuid_out[col], new_gpr[ridx]))

        # all writes (hot values entered the chains as bitcasts) land through
        # the one shared u64 scatter; the limb file is a free bitcast back
        glimb_out = L.unpack_u64(new_gpr)

    # -- rflags ------------------------------------------------------------
    with jax.named_scope("flags-status"):
        # Ported classes (ALU/UNARY) produce a u32 low-limb image; everything
        # else rides the u64 chain and splits at the seam below.
        rf_exec = opc_list([
            (is_(U.OPC_BT), bt_rf),
            (is_(U.OPC_BITSCAN), bs_rf),
            (is_string, jnp.where((s_scas | s_cmps) & ~rep_skip, str_rf, rf)),
            (is_(U.OPC_CMPXCHG), cx_rf),
            (is_(U.OPC_XADD), xadd_rf),
            (is_(U.OPC_RDRAND), rdrand_rf),
            (is_(U.OPC_FLAGOP), flagop_rf),
            (is_popf, popf_rf),
            (is_iretq, l2_lo & _u(U.RF_WRITABLE)),
            (is_(U.OPC_SYSCALL), jnp.where(syscall_entry, syscall_rf, sysret_rf)),
            (is_ssealu & (sub == U.SSE_PTEST), ptest_rf),
            (is_ssefp & fp_is_comi, ucomi_rf),
            (is_x87 & (sub == U.X87_COMI), x87_comi_rf),
            (is_(U.OPC_PEXT), bmi_rf),
        ], rf)
        hot_rf = (is_(U.OPC_ALU) | is_(U.OPC_UNARY) | is_(U.OPC_SHIFT)
                  | is_mul)
        rf_cold_lo, rf_cold_hi = L.pair(rf_exec)
        rf_exec_lo = jnp.where(
            hot_rf,
            L.sel([is_(U.OPC_ALU), is_(U.OPC_UNARY), is_(U.OPC_SHIFT)],
                  [alu_rf_lo, un_rf_lo, sh_rf_lo], mul_rf_lo),
            rf_cold_lo)
        new_rf_lo = jnp.where(commit, rf_exec_lo | jnp.uint32(0x2), rf_lo)
        # hot classes never touch bits 32+ (arith flags live in the low limb)
        new_rf_hi = jnp.where(commit & ~hot_rf, rf_cold_hi, rf_hi)

    # -- rip ---------------------------------------------------------------
    # ported: fallthrough and Jcc targets come from the limb adder
    jcc_rip_l = L.where64(cc_true, jcc_target_l, next_rip_l)
    rip_exec = opc_list([
        (is_(U.OPC_JMP) | is_call, jmp_target),
        (is_(U.OPC_JCC), L.to_u64(jcc_rip_l)),
        (is_ret | is_iretq, ret_target),
        (is_(U.OPC_SYSCALL), jnp.where(syscall_entry, st.lstar, rcx)),
        (is_string, jnp.where(str_done, next_rip, rip)),
    ], next_rip)
    new_rip = jnp.where(commit, rip_exec, rip)

    # -- control registers / gs bases -------------------------------------
    cr_w = commit & movcr_is_write
    new_cr0 = jnp.where(cr_w & (sub == 0), cr_wval, st.cr0)
    new_cr3 = jnp.where(cr_w & (sub == 3), cr_wval, st.cr3)
    new_cr4 = jnp.where(cr_w & (sub == 4), cr_wval, st.cr4)
    new_cr8 = jnp.where(cr_w & (sub == 8), cr_wval, st.cr8)
    cr3_changed = cr_w & (sub == 3) & (cr_wval != st.cr3_base)
    sw = commit & is_swapgs
    new_gs = jnp.where(sw, st.kernel_gs_base, st.gs_base)
    new_kgs = jnp.where(sw, st.gs_base, st.kernel_gs_base)

    # wrfsbase/wrgsbase (r32 forms zero-extend via the masked reg read)
    fsgs_val = _read_reg(gpr, dr, opsize)
    fsgsw = commit & is_(U.OPC_RDGSBASE)
    new_gs = jnp.where(fsgsw & (sub == 3), fsgs_val, new_gs)
    fs_pre = jnp.where(fsgsw & (sub == 2), fsgs_val, st.fs_base)

    # wrmsr state writes, driven by the same MSR_ATTR map (tsc keeps
    # rdtsc = tsc_base + icount coherent, same adjustment as the oracle);
    # gs/fs bases chain after the swapgs/wrfsbase values
    msrw = commit & is_(U.OPC_MSR) & (sub == 1)
    _msr_state = {"gs_base": new_gs, "kernel_gs_base": new_kgs,
                  "fs_base": fs_pre}
    for _mid, _attr in MSR_ATTR.items():
        base = _msr_state.get(_attr, getattr(st, _attr))
        val = msr_wval - st.icount if _attr == "tsc" else msr_wval
        _msr_state[_attr] = jnp.where(msrw & (msr_id == _u(_mid)), val, base)
    new_lstar = _msr_state["lstar"]
    new_star = _msr_state["star"]
    new_sfmask = _msr_state["sfmask"]
    new_efer = _msr_state["efer"]
    new_tsc = _msr_state["tsc"]
    new_fs = _msr_state["fs_base"]
    new_gs = _msr_state["gs_base"]
    new_kgs = _msr_state["kernel_gs_base"]

    # -- CS/SS selectors (CPL tracking for host exception delivery) -------
    # SYSCALL loads CPL-0 selectors from IA32_STAR[47:32]; SYSRET the CPL-3
    # pair from IA32_STAR[63:48] (SDM); iretq restores its frame's.
    sysc = commit & is_(U.OPC_SYSCALL)
    star_k = (st.star >> _u(32)) & _u(0xFFFC)
    star_u = (st.star >> _u(48)) & _u(0xFFFF)
    new_cs = jnp.where(
        sysc, jnp.where(syscall_entry, star_k, (star_u + _u(16)) | _u(3)),
        st.cs)
    new_ss = jnp.where(
        sysc, jnp.where(syscall_entry, star_k + _u(8), (star_u + _u(8)) | _u(3)),
        st.ss)
    with jax.named_scope("iret"):
        iretc = commit & is_iretq
        new_cs = jnp.where(iretc, l1_hi & _u(0xFFFF), new_cs)
        new_ss = jnp.where(iretc, (iret_latch & 0xFFFF).astype(jnp.uint64),
                           new_ss)
        # the latch: set by a first pass that read its frame whole; any
        # other pass of a live lane (the commit, a fault, a breakpoint or
        # a decode miss in between) clears it, and the instruction then
        # starts again from its first pass.  A fill pass is no pass of the
        # instruction: the latch stands
        new_latch = jnp.where(
            live & iret_a & ~page_fault,
            jnp.int32(0x10000) | (l2_lo & _u(0xFFFF)).astype(jnp.int32),
            jnp.where(enabled & ~fill, jnp.int32(0), iret_latch))

    # -- xmm ---------------------------------------------------------------
    with jax.named_scope("vector-file"):
        wx_cond = commit & (
            (is_ssemov & (sub != 2) & (dk == U.K_XMM))
            | (is_ssealu & (sub != U.SSE_PMOVMSKB) & (sub != U.SSE_PTEST)
               & (sub != U.SSE_PEXTRW))
            | fp_writes_xmm)
        wx_lo = jnp.where(is_ssefp, fp_out_lo,
                          jnp.where(is_ssealu, sse_out_lo, ssm_lo))
        wx_hi = jnp.where(is_ssefp, fp_out_hi,
                          jnp.where(is_ssealu, sse_out_hi, ssm_hi))
        xr = jnp.clip(dr, 0, 15)
        # limbs 0-1 only: upper YMM halves (limbs 2-3) are carried state the
        # legacy-SSE subset never computes on (AVX snapshots round-trip;
        # reference CpuState_t holds 32xZMM, globals.h:1020-1159)
        new_xmm = _set_row(
            xmm, xr, wx_cond,
            jnp.where(jnp.arange(4) < 2,
                      jnp.where(jnp.arange(4) == 0, wx_lo, wx_hi), x_dst))
        # vzeroall (sub 0) zeroes the whole file; vzeroupper (sub 1) the
        # upper halves only — whole-file writes, no dst register
        vz = commit & is_(U.OPC_VZEROALL)
        vz_limb = jnp.where(vz & (sub == 0), jnp.arange(4) >= 0,
                            jnp.where(vz, jnp.arange(4) >= 2,
                                      jnp.zeros(4, bool)))
        new_xmm = jnp.where(vz_limb[None, :], _u(0), new_xmm)

    # -- x87 state application --------------------------------------------
    with jax.named_scope("vector-file"):
        x87c = commit & is_x87
        new_fpst = _set_row(fpst_v, x_w1_idx, x87c & x_w1_en, x_w1_val)
        new_fpst = _set_row(new_fpst, x_phi, x87c & x_fxch, st0_b)
        new_fpcw = jnp.where(x87c, x_cw, fpcw_v)
        new_fpsw = jnp.where(x87c, x_sw, fpsw_v)
        new_fptw = jnp.where(x87c, x_tw, fptw_v)
        new_mxcsr = jnp.where(x87c & (sub == U.X87_LDMXCSR),
                              l1_lo & _u(0xFFFFFFFF), st.mxcsr)

    # -- bookkeeping -------------------------------------------------------
    with jax.named_scope("counters"):
        new_icount = st.icount + jnp.where(commit, _u(1), _u(0))
        # device-side telemetry block (machine.CTR_* order): accumulated
        # in-graph every step, folded into host metrics once per burst — the
        # per-step host sync this exists to avoid.  page_fault/miss already
        # imply `enabled`, commit implies `live`.  CTR_SWEPT counts the pass
        # itself, whatever the lane's status; CTR_TAIL and CTR_LEVELS are
        # the chunk loop's.  A fill pass counts in CTR_SWEPT and
        # CTR_TLB_FILL and not in CTR_INSTR, as iretq's latching pass counts
        # in CTR_SWEPT alone.  `spill.spill` implies commit: the store was
        # enabled (st_need & commit_pre) and claimed its rows (~ovf).
        is_vector = reduce(operator.or_, (is_(o) for o in U.VECTOR_OPCS))
        new_ctr = st.ctr + jnp.stack(
            [commit, page_fault, miss, jnp.bool_(True), jnp.bool_(False),
             tlb_walks, fill, commit & st_need, tlb_walks == 2,
             commit & is_vector, jnp.bool_(False),
             spill.spill]).astype(jnp.uint32)
        timed = commit & (limit > _u(0)) & (new_icount >= limit)
    new_rdrand = jnp.where(commit & is_(U.OPC_RDRAND), rdrand_next, st.rdrand)
    new_bp_skip = jnp.where(commit, jnp.int32(0), st.bp_skip)

    is_branch = is_(U.OPC_JMP) | is_(U.OPC_JCC) | is_call | is_ret
    new_cov, new_edge = _cover(st.cov, st.edge, live, idxc,
                               commit & is_branch, rip, rip_exec)

    # -- status ------------------------------------------------------------
    with jax.named_scope("flags-status"):
        S = StatusCode
        status_chain = jnp.select(
            [miss, at_bp, smc, unsupported, page_fault, fp_oracle, de, is_crash,
             ovf, cr3_changed, timed],
            [jnp.int32(int(S.NEED_DECODE)), jnp.int32(int(S.BREAKPOINT)),
             jnp.int32(int(S.SMC)), jnp.int32(int(S.UNSUPPORTED)),
             jnp.int32(int(S.PAGE_FAULT)), jnp.int32(int(S.UNSUPPORTED)),
             jnp.int32(int(S.DIVIDE_ERROR)),
             jnp.int32(int(S.CRASH)), jnp.int32(int(S.OVERLAY_FULL)),
             jnp.int32(int(S.CR3_CHANGE)), jnp.int32(int(S.TIMEDOUT))],
            default=jnp.int32(int(S.RUNNING)))
        new_status = jnp.where(enabled, status_chain, st.status)

        # faulting address: when the access's first page translates but the
        # access straddles into a bad page, the faulting byte is at the next
        # page boundary (the oracle's per-page walk reports it there)
        def _fault_at(addr, first_ok):
            return jnp.where(first_ok, (addr & ~_u(0xFFF)) + _u(0x1000), addr)

        st_first_ok = ts0.ok & ts0.writable
        new_fault_gva = jnp.where(
            enabled & page_fault,
            jnp.where(fault1, _fault_at(l1_addr, l1t0.ok),
                      jnp.where(fault2, _fault_at(l2_addr, l2t0.ok),
                                _fault_at(st_addr, st_first_ok))),
            jnp.where(enabled & is_crash, rip, st.fault_gva))
        new_fault_write = jnp.where(
            enabled & page_fault & ~fault1 & ~fault2, jnp.int32(1),
            jnp.where(enabled & page_fault, jnp.int32(0), st.fault_write & 1)) \
            | (new_latch << IRET_LATCH_SHIFT)

    return st._replace(
        gpr_l=glimb_out,
        rip_l=L.unpack_u64(new_rip),
        rflags_l=jnp.stack([new_rf_lo, new_rf_hi]),
        xmm_l=L.unpack_u64(new_xmm).reshape(16, 8),
        fpst=new_fpst, fpcw=new_fpcw, fpsw=new_fpsw, fptw=new_fptw,
        mxcsr=new_mxcsr,
        fs_base_l=L.unpack_u64(new_fs),
        gs_base_l=L.unpack_u64(new_gs),
        kernel_gs_base=new_kgs,
        lstar=new_lstar, star=new_star, sfmask=new_sfmask,
        efer=new_efer, tsc=new_tsc,
        cr0=new_cr0, cr3=new_cr3, cr4=new_cr4, cr8=new_cr8,
        cs=new_cs, ss=new_ss,
        status=new_status, icount=new_icount, rdrand=new_rdrand,
        bp_skip=new_bp_skip, fault_gva=new_fault_gva,
        fault_write=new_fault_write, ctr=new_ctr, cov=new_cov,
        edge=new_edge, overlay=overlay), store, spill


# ---------------------------------------------------------------------------
# chunked batch run
# ---------------------------------------------------------------------------

def _sweep(tab: UopTable, image: MemImage, view: Machine, limit,
           fp_host: bool = None) -> Machine:
    """ONE sweep over the lanes of a `lane_view` (all of them, or one
    group of its rows): `step_lane` over every lane in hand, then every
    lane's store in one flat scatter per overlay plane, and under one
    conditional the third words of the stores that spill, if any lane's
    does (`overlay.commit_spills`).  The planes ride
    through the vmap unmapped (each lane gathers from the resident buffer
    at its own `base`), so inside a while loop the carry is updated in
    place and no operation reads or writes a whole plane (mem/overlay.py,
    "resident layout").  `image` is a `lane_image` of the same lanes."""
    m, store, spill = jax.vmap(
        partial(step_lane, fp_host=fp_host),
        in_axes=(None, IMAGE_IN_AXES, MACHINE_LANE_AXES, None),
        out_axes=(MACHINE_LANE_AXES, 0, 0),
    )(tab, image, view, limit)
    return m._replace(overlay=commit_spills(
        image, commit_stores(m.overlay, store), spill))


def step_lanes(tab: UopTable, image: MemImage, machine: Machine, limit,
               fp_host: bool = None) -> Machine:
    """ONE sweep of every lane of a resident machine (`_sweep`)."""
    m = _sweep(tab, image, lane_view(machine), limit, fp_host)
    return m._replace(overlay=resident(m.overlay))


# The chunk executor's narrow sweeps (make_run_chunk): a chunk sweeps its
# lanes in TAIL_SHARE groups and drops a group whenever the lanes still
# RUNNING fit in one fewer.  What a sweep costs is linear in the lanes it
# takes (22.4 ms at 4096, 5.4 at 1024, 1.6 at 256: PERF.md section 5), so
# narrower groups track the RUNNING lanes closer, at 6.4 us a lane where
# groups of 1024 pay 5.3 and at one sort of the rows per level: sixteen
# groups beat four on both of the benchmark's cells (PERF.md section 6,
# PR 31, call c4); below TAIL_MIN_LANES a sweep is too cheap to split.
TAIL_MIN_LANES = 1024
TAIL_SHARE = 16


def tail_width(n_lanes: int) -> int:
    """Lanes in one group of the chunk executor's sweeps at `n_lanes` (a
    device's, or a mesh shard's); 0: no groups, every sweep full width."""
    return n_lanes // TAIL_SHARE if n_lanes >= TAIL_MIN_LANES else 0


_CHUNK_CACHE: dict = {}


def make_run_chunk(n_steps: int, donate: bool = None, jit: bool = True,
                   fp_host: bool = None, tail: int = None):
    """Build (or fetch) the jitted chunk executor: up to n_steps vmapped
    transitions with early exit when no lane is RUNNING.  The host runner
    (interp/runner.py) calls this in a loop, servicing lane statuses between
    chunks — the batched analog of the reference's vmexit servicing
    (kvm_backend.cc:1371-1566).

    From TAIL_MIN_LANES lanes up the sweeps NARROW with the lanes still
    running: the rows are held as `groups` groups of `tail_width(lanes)`
    lanes, one sweep is `_sweep` over the first `n_active` groups in turn
    (ONE body of the step, at the group's width: a sweep is priced by the
    lane, PERF.md section 5), and a level sweeps `n_active` groups while
    more lanes are RUNNING than `n_active - 1` groups hold.  Entering a
    level sorts the rows, RUNNING lanes to the front (a stable argsort,
    each row keeping its lane's `base` into the overlay's planes, which
    are neither gathered nor moved); nothing on the device sets a lane
    RUNNING, so the rows behind can go unswept, and at the end the rows
    are scattered back to their lanes.  `step_lane` is per lane and passes
    a parked lane through, so every leaf comes out as full-width sweeps
    leave it but `ctr[:, CTR_SWEPT]` (fewer passes) and `CTR_TAIL` (the
    passes a lane took in sweeps of fewer than all groups).  `tail`
    overrides the width (tests; 0: every sweep at full width, which is
    the whole program below TAIL_MIN_LANES).

    Memoized per (n_steps, donate, fp_host, tail) so every Runner with the
    same chunk size shares one jit cache entry (XLA recompiles only on
    new array *shapes*, not per Runner instance).

    donate=True (the runner's hot path): the machine argument is donated so
    the dominant buffers (overlay data, cov/edge bitmaps) update in place
    instead of being copied every chunk call — safe because machine_restore
    copies template leaves rather than aliasing them, and the runner
    reassigns its machine from the result.  Callers that reuse an argument
    tuple across calls (the driver's entry() compile check) need
    donate=False.

    CAVEAT — CPU backend: donation is demonstrably unsound there with this
    graph (XLA CPU's buffer reuse around donated while_loop carries plus
    the u32<->u64 bitcast views corrupts live machine leaves — observed as
    garbage status/fpsw/xmm reads, reproducible and gone with donation
    off).  The Runner therefore requests donation only off-CPU, where it
    actually matters (HBM); pass donate explicitly if you know better.
    donate=None (the default) resolves to that policy lazily.

    jit=False returns the UNDECORATED body (a fresh closure every call,
    never memoized): the static analyzer's retrace-stability probe needs
    a genuinely fresh trace per lowering — jax's trace cache keys on
    function identity, so re-lowering the memoized jitted executor would
    never re-trace and the probe would be vacuous.

    fp_host (None = this platform's fp_on_host()) is step_lane's: float-
    valued uops retire on the host oracle where the f64 is not IEEE."""
    if donate is None:
        donate = jax.default_backend() != "cpu"
    if fp_host is None:
        fp_host = fp_on_host()
    key = (n_steps, donate, fp_host, tail)
    if jit:
        cached = _CHUNK_CACHE.get(key)
        if cached is not None:
            return cached

    def run_chunk(tab: UopTable, image: MemImage, machine: Machine, limit):
        n_lanes = machine.status.shape[0]
        width = tail_width(n_lanes) if tail is None else tail
        running = jnp.int32(int(StatusCode.RUNNING))
        # normalize in-body: the per-lane tenant selector is always
        # populated past this point (zeros for single-image callers), so
        # one vmap structure serves both dispatch shapes
        image = lane_image(image, n_lanes)

        if not width:
            def cond(carry):
                i, m = carry
                return (i < n_steps) & jnp.any(m.status == running)

            def body(carry):
                i, m = carry
                return i + 1, step_lanes(tab, image, m, limit, fp_host)

            _, out = lax.while_loop(cond, body, (jnp.int32(0), machine))
            return out

        assert n_lanes % width == 0, (n_lanes, width)
        groups = n_lanes // width

        def sweep_groups(n_active, tenant, view):
            """One sweep of the first `n_active` groups of `width` rows."""
            def one(g, view):
                at = g * width
                with jax.named_scope("group-slice"):
                    rows = map_rows(
                        lambda x: lax.dynamic_slice_in_dim(x, at, width),
                        view)
                    tenants = lax.dynamic_slice_in_dim(tenant, at, width)
                rows = _sweep(tab, image._replace(tenant=tenants), rows,
                              limit, fp_host)
                with jax.named_scope("group-slice"):
                    return map_rows(
                        lambda part, full: lax.dynamic_update_slice_in_dim(
                            full, part, at, 0), rows, view)

            return lax.fori_loop(0, n_active, one, view)

        def level(e, carry):
            """Sweeps at `groups - e` groups, while more lanes run than
            one group fewer would hold.  The rows are sorted first,
            RUNNING lanes to the front (nothing on the device sets a lane
            RUNNING, so those are the only ones that will run), which is
            what lets the rows behind go unswept: step_lane passes a
            parked lane through."""
            i, lanes, tenant, view = carry
            with jax.named_scope("level-sort"):
                order = jnp.argsort(view.status != running,
                                    stable=True).astype(jnp.int32)
                lanes, tenant = lanes[order], tenant[order]
                view = map_rows(
                    lambda x: x.at[order].get(mode="promise_in_bounds",
                                              unique_indices=True), view)
            n_active = groups - e
            floor = (n_active - 1) * width

            def cond(c):
                j, v = c
                return (j < n_steps) & (jnp.sum(v.status == running) > floor)

            def body(c):
                j, v = c
                return j + 1, sweep_groups(n_active, tenant, v)

            j, view = lax.while_loop(cond, body, (i, view))
            with jax.named_scope("level-account"):
                narrow = (jnp.arange(n_lanes) < n_active * width) & (e > 0)
                # one elementwise add over the block: the level's sweeps
                # to the narrow lanes' CTR_TAIL, and to every lane's
                # CTR_LEVELS whether the level swept at all
                col = jnp.arange(N_CTRS)[None, :]
                delta = jnp.where(
                    col == CTR_TAIL, jnp.where(narrow, j - i, 0)[:, None],
                    ((col == CTR_LEVELS) & (j > i)).astype(jnp.int32))
                view = view._replace(
                    ctr=view.ctr + delta.astype(jnp.uint32))
            return j, lanes, tenant, view

        _, lanes, _, view = lax.fori_loop(
            0, groups, level,
            (jnp.int32(0), jnp.arange(n_lanes, dtype=jnp.int32),
             image.tenant, lane_view(machine)))
        return put_lanes(machine, lanes, view)

    if not jit:
        return run_chunk
    run_chunk = partial(jax.jit, donate_argnums=(2,) if donate else ())(
        run_chunk)
    _CHUNK_CACHE[key] = run_chunk
    return run_chunk
