"""Little-endian u32 limb arithmetic: the register width the TPU executes.

TPUs have no native 64-bit integers — XLA lowers every u64 op in the
transition function to a pair of u32 ops with full carry/borrow plumbing,
whether or not the semantics need it.  This module is the hand-packed
representation: a guest 64-bit value is an explicit pair of uint32 limbs
``(lo, hi)`` (limb 0 = least significant 32 bits, matching the memory
byte order of the snapshot image), and every helper here is built from
32-bit adds/shifts/multiplies ONLY.  The hot paths of the device step
(interp/step.py: ALU, flags, addressing, condition evaluation, the
decode-cache hash probe) run on these helpers; cold paths convert at the
``pack_u64``/``unpack_u64`` seam, which XLA lowers to a free bitcast.

Conventions:
  * a "pair" is a tuple ``(lo, hi)`` of uint32 arrays (scalars under vmap)
  * byte-count operands (``nbytes``) are int32 like the uop table fields;
    they are cast to uint32 internally before any shift
  * nothing in this module may create a 64-bit value — the tier-1 HLO
    inspection test (tests/test_limbs.py) compiles the public helpers and
    fails if a u64/s64 op appears in the lowered code
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np
from jax import lax

U32_MASK = 0xFFFFFFFF

# rflags bits (duplicated from step.py's u64 constants; kept as plain ints
# so they weak-type-promote against u32 arrays)
CF, PF, AF, ZF, SF, OF = 0x1, 0x4, 0x10, 0x40, 0x80, 0x800
FLAGS_ARITH = CF | PF | AF | ZF | SF | OF


def _u32(x) -> jnp.ndarray:
    return jnp.uint32(x & U32_MASK)


# ---------------------------------------------------------------------------
# pack/unpack seam (device): XLA bitcasts, no arithmetic
# ---------------------------------------------------------------------------

def pack_u64(x32):
    """uint32[..., 2] -> uint64[...] (little-endian limbs; free bitcast)."""
    return lax.bitcast_convert_type(x32, jnp.uint64)


def unpack_u64(x64):
    """uint64[...] -> uint32[..., 2] (limb 0 = low; free bitcast)."""
    return lax.bitcast_convert_type(x64, jnp.uint32)


def pair(x64):
    """uint64[...] -> (lo, hi) tuple of uint32[...]."""
    y = unpack_u64(x64)
    return y[..., 0], y[..., 1]


def to_u64(p):
    """(lo, hi) tuple -> uint64[...]."""
    return pack_u64(jnp.stack([p[0], p[1]], axis=-1))


def const_pair(v: int):
    """Python int -> (lo, hi) uint32 constants."""
    return _u32(v), _u32(v >> 32)


# ---------------------------------------------------------------------------
# pack/unpack seam (host): numpy views for HostView mirrors
# ---------------------------------------------------------------------------

def pack_np(a: np.ndarray) -> np.ndarray:
    """uint32[..., 2] -> uint64[...] on the host (little-endian view)."""
    a = np.ascontiguousarray(a, dtype=np.uint32)
    return a.view(np.uint64).reshape(a.shape[:-1])


def unpack_np(a: np.ndarray) -> np.ndarray:
    """uint64[...] -> uint32[..., 2] on the host."""
    a = np.ascontiguousarray(a, dtype=np.uint64)
    return a.view(np.uint32).reshape(a.shape + (2,))


# ---------------------------------------------------------------------------
# logic
# ---------------------------------------------------------------------------

def and64(a, b):
    return a[0] & b[0], a[1] & b[1]


def or64(a, b):
    return a[0] | b[0], a[1] | b[1]


def xor64(a, b):
    return a[0] ^ b[0], a[1] ^ b[1]


def not64(a):
    return ~a[0], ~a[1]


def where64(c, a, b):
    return jnp.where(c, a[0], b[0]), jnp.where(c, a[1], b[1])


def select64(conds, pairs, default):
    """jnp.select semantics (first true cond wins) over limb pairs.

    Built as a where-fold rather than jnp.select: select's lowering runs
    its case index in 64-bit scalars under x64, which would put s64 ops
    back into every ported path this library exists to keep u32-only.
    """
    lo, hi = default
    for c, p in zip(reversed(conds), reversed(pairs)):
        lo = jnp.where(c, p[0], lo)
        hi = jnp.where(c, p[1], hi)
    return lo, hi


def sel(conds, vals, default):
    """jnp.select semantics over scalar (non-pair) values as a where-fold —
    same rationale as select64: jnp.select's lowering runs its case index
    in 64-bit scalars under x64, which the ported paths must not emit."""
    out = default
    for c, v in zip(reversed(conds), reversed(vals)):
        out = jnp.where(c, v, out)
    return out


# ---------------------------------------------------------------------------
# add/sub with carry/borrow
# ---------------------------------------------------------------------------

def add64(a, b):
    """(a + b) mod 2^64."""
    return adc64(a, b, jnp.bool_(False))[0]


def adc64(a, b, carry_in):
    """a + b + carry_in -> (sum_pair, carry_out bool)."""
    cin = jnp.where(carry_in, _u32(1), _u32(0))
    s0 = a[0] + b[0]
    c0 = s0 < a[0]
    lo = s0 + cin
    c0 = c0 | (lo < s0)
    cu = jnp.where(c0, _u32(1), _u32(0))
    s1 = a[1] + b[1]
    c1 = s1 < a[1]
    hi = s1 + cu
    c1 = c1 | (hi < s1)
    return (lo, hi), c1


def add64_u32(a, small):
    """a + small (u32, zero-extended) — the cheap adder for +length /
    +span-1 style increments: one compare instead of a full carry chain."""
    lo = a[0] + small
    return lo, a[1] + jnp.where(lo < small, _u32(1), _u32(0))


def sub64(a, b):
    """(a - b) mod 2^64."""
    return sbb64(a, b, jnp.bool_(False))[0]


def sbb64(a, b, borrow_in):
    """a - b - borrow_in -> (diff_pair, borrow_out bool)."""
    bin_ = jnp.where(borrow_in, _u32(1), _u32(0))
    d0 = a[0] - b[0]
    w0 = a[0] < b[0]
    lo = d0 - bin_
    w0 = w0 | (d0 < bin_)
    bu = jnp.where(w0, _u32(1), _u32(0))
    d1 = a[1] - b[1]
    w1 = a[1] < b[1]
    hi = d1 - bu
    w1 = w1 | (d1 < bu)
    return (lo, hi), w1


def neg64(a):
    return sub64(const_pair(0), a)


# ---------------------------------------------------------------------------
# comparisons
# ---------------------------------------------------------------------------

def eq64(a, b):
    return (a[0] == b[0]) & (a[1] == b[1])


def is_zero64(a):
    return (a[0] | a[1]) == _u32(0)


def ltu64(a, b):
    return (a[1] < b[1]) | ((a[1] == b[1]) & (a[0] < b[0]))


def leu64(a, b):
    return (a[1] < b[1]) | ((a[1] == b[1]) & (a[0] <= b[0]))


# ---------------------------------------------------------------------------
# shifts / rotates (dynamic count, crossing the limb boundary)
# ---------------------------------------------------------------------------

def _ucount(s):
    return s.astype(jnp.uint32) if hasattr(s, "astype") else _u32(s)


def shl64(a, s):
    """a << s; s >= 64 yields 0 (the XLA-undefined region is defined here)."""
    s = _ucount(s)
    z = _u32(0)
    sh = jnp.minimum(s, _u32(31))           # in-limb shift (valid < 32)
    shb = jnp.minimum(s - _u32(32), _u32(31))  # cross-limb shift for s>=32
    carry = jnp.where(s == z, z, a[0] >> (_u32(32) - jnp.minimum(s, _u32(31))))
    # s in [1,31]: carry = lo >> (32-s); s==0 handled; s>=32 selected away
    lo = jnp.where(s >= 64, z, jnp.where(s >= 32, z, a[0] << sh))
    hi = jnp.where(
        s >= 64, z,
        jnp.where(s >= 32, a[0] << shb, (a[1] << sh) | carry))
    return lo, hi


def shr64(a, s):
    """Logical a >> s; s >= 64 yields 0."""
    s = _ucount(s)
    z = _u32(0)
    sh = jnp.minimum(s, _u32(31))
    shb = jnp.minimum(s - _u32(32), _u32(31))
    carry = jnp.where(s == z, z, a[1] << (_u32(32) - jnp.minimum(s, _u32(31))))
    lo = jnp.where(
        s >= 64, z,
        jnp.where(s >= 32, a[1] >> shb, (a[0] >> sh) | carry))
    hi = jnp.where(s >= 64, z, jnp.where(s >= 32, z, a[1] >> sh))
    return lo, hi


def shl64_const(a, k: int):
    """a << k for a trace-time-constant k — no dynamic-count selects."""
    assert 0 <= k < 64
    if k == 0:
        return a
    if k >= 32:
        return jnp.zeros_like(a[0]), a[0] << (k - 32)
    return a[0] << k, (a[1] << k) | (a[0] >> (32 - k))


def shr64_const(a, k: int):
    """Logical a >> k for a trace-time-constant k."""
    assert 0 <= k < 64
    if k == 0:
        return a
    if k >= 32:
        return a[1] >> (k - 32), jnp.zeros_like(a[1])
    return (a[0] >> k) | (a[1] << (32 - k)), a[1] >> k


def sar64(a, s):
    """Arithmetic a >> s; s >= 64 fills with the sign like s == 63."""
    s = jnp.minimum(_ucount(s), _u32(63))
    sign = jnp.where((a[1] >> 31) != 0, _u32(U32_MASK), _u32(0))
    z = _u32(0)
    sh = jnp.minimum(s, _u32(31))
    shb = jnp.minimum(s - _u32(32), _u32(31))
    hi_s = (a[1].astype(jnp.int32) >> sh.astype(jnp.int32)).astype(jnp.uint32)
    hi_b = (a[1].astype(jnp.int32) >> shb.astype(jnp.int32)).astype(jnp.uint32)
    carry = jnp.where(s == z, z, a[1] << (_u32(32) - jnp.minimum(s, _u32(31))))
    lo = jnp.where(s >= 32, hi_b, (a[0] >> sh) | carry)
    hi = jnp.where(s >= 32, sign, hi_s)
    return lo, hi


def rol64(a, s):
    """Rotate left by s (mod 64)."""
    s = _ucount(s) & _u32(63)
    return where64(s == _u32(0), a,
                   or64(shl64(a, s), shr64(a, _u32(64) - s)))


def ror64(a, s):
    """Rotate right by s (mod 64)."""
    s = _ucount(s) & _u32(63)
    return where64(s == _u32(0), a,
                   or64(shr64(a, s), shl64(a, _u32(64) - s)))


# ---------------------------------------------------------------------------
# multiply
# ---------------------------------------------------------------------------

def mul32_wide(a32, b32):
    """Widening 32x32 -> 64 multiply from 16-bit partial products.

    Every operand of every multiply stays < 2^32, so XLA never sees a
    64-bit multiplier.
    """
    m16 = _u32(0xFFFF)
    a0, a1 = a32 & m16, a32 >> 16
    b0, b1 = b32 & m16, b32 >> 16
    ll = a0 * b0
    lh = a0 * b1
    hl = a1 * b0
    hh = a1 * b1
    mid = (ll >> 16) + (lh & m16) + (hl & m16)       # <= 3*(2^16-1): no wrap
    lo = (ll & m16) | (mid << 16)
    hi = hh + (lh >> 16) + (hl >> 16) + (mid >> 16)
    return lo, hi


def mul64_lo(a, b):
    """Low 64 bits of a 64x64 multiply (the splitmix64/hash workhorse)."""
    lo, hi = mul32_wide(a[0], b[0])
    hi = hi + a[0] * b[1] + a[1] * b[0]
    return lo, hi


def umulhi64(a, b):
    """High 64 bits of the unsigned 128-bit product a * b, from four
    mul32_wide partial products (the step's widening-MUL port and the
    only place the full 128-bit product shape exists in limb form)."""
    p00l, p00h = mul32_wide(a[0], b[0])
    p01l, p01h = mul32_wide(a[0], b[1])
    p10l, p10h = mul32_wide(a[1], b[0])
    p11 = mul32_wide(a[1], b[1])
    # bits 32..63 of the product: p00h + p01l + p10l, carry count 0..2
    s1 = p00h + p01l
    c1 = s1 < p01l
    s2 = s1 + p10l
    c2 = s2 < p10l
    midcarry = jnp.where(c1, _u32(1), _u32(0)) + jnp.where(c2, _u32(1), _u32(0))
    hi = add64(p11, (p01h, _u32(0)))
    hi = add64(hi, (p10h, _u32(0)))
    return add64(hi, (midcarry, _u32(0)))


def smulhi64(a, b):
    """High 64 bits of the signed 128-bit product (two's-complement
    correction of umulhi64, mirroring step.py's deleted _smulhi)."""
    hi = umulhi64(a, b)
    zero = (_u32(0), _u32(0))
    hi = sub64(hi, where64((a[1] >> 31) != 0, b, zero))
    return sub64(hi, where64((b[1] >> 31) != 0, a, zero))


# ---------------------------------------------------------------------------
# splitmix64 (decode-cache hash probe; must match utils.hashing bit-for-bit)
# ---------------------------------------------------------------------------

# plain-int limb pairs, NOT jnp arrays: importing wtf_tpu must not touch
# the device; python ints weak-type against the u32 operands and lower to
# u32 literals
_GOLDEN = (0x9E3779B97F4A7C15 & U32_MASK, 0x9E3779B97F4A7C15 >> 32)
_MIX1 = (0xBF58476D1CE4E5B9 & U32_MASK, 0xBF58476D1CE4E5B9 >> 32)
_MIX2 = (0x94D049BB133111EB & U32_MASK, 0x94D049BB133111EB >> 32)


def mix64(z):
    z = mul64_lo(xor64(z, shr64_const(z, 30)), _MIX1)
    z = mul64_lo(xor64(z, shr64_const(z, 27)), _MIX2)
    return xor64(z, shr64_const(z, 31))


def splitmix64(x):
    return mix64(add64(x, _GOLDEN))


# ---------------------------------------------------------------------------
# size masks / extensions
# ---------------------------------------------------------------------------

def mask32(nbits):
    """(1 << nbits) - 1 for nbits in [0, 32] (32 -> all ones)."""
    nbits = _ucount(nbits)
    partial = (_u32(1) << jnp.minimum(nbits, _u32(31))) - _u32(1)
    return jnp.where(nbits >= 32, _u32(U32_MASK), partial)


def size_mask(nbytes):
    """nbytes (int32) -> (lo, hi) value mask; >= 8 bytes = full mask."""
    bits = jnp.minimum(nbytes, 8).astype(jnp.uint32) * _u32(8)
    return mask32(bits), mask32(jnp.maximum(bits, _u32(32)) - _u32(32))


def zext(a, nbytes):
    """Zero-extend the low nbytes of a to 64 bits (i.e. mask)."""
    mlo, mhi = size_mask(nbytes)
    return a[0] & mlo, a[1] & mhi


def sext(a, nbytes):
    """Sign-extend the low nbytes (1/2/4/8+) of a to 64 bits."""
    bits32 = jnp.minimum(nbytes, 4).astype(jnp.uint32) * _u32(8)
    sh = (_u32(32) - bits32).astype(jnp.int32)
    lo_se = ((a[0] << sh.astype(jnp.uint32)).astype(jnp.int32)
             >> sh).astype(jnp.uint32)
    hi_se = (lo_se.astype(jnp.int32) >> 31).astype(jnp.uint32)
    wide = nbytes >= 8
    return (jnp.where(wide, a[0], lo_se), jnp.where(wide, a[1], hi_se))


def msb(a, nbytes):
    """Sign bit of the low-nbytes value (nbytes in {1,2,4,8+}) as bool."""
    hi_bit = (a[1] >> 31) & _u32(1)
    sh = (jnp.minimum(nbytes, 4).astype(jnp.uint32) * _u32(8)) - _u32(1)
    lo_bit = (a[0] >> sh) & _u32(1)
    return jnp.where(nbytes >= 8, hi_bit, lo_bit) != _u32(0)


# ---------------------------------------------------------------------------
# x86 flag images (CF/PF/AF/ZF/SF/OF live in rflags bits 0-11: u32-only)
# ---------------------------------------------------------------------------

def parity_even(lo):
    v = lo & _u32(0xFF)
    v = v ^ (v >> 4)
    v = v ^ (v >> 2)
    v = v ^ (v >> 1)
    return (v & _u32(1)) == _u32(0)


def mkflags(cf, pf, af, zf, sf, of):
    def bit(c, v):
        return jnp.where(c, _u32(v), _u32(0))

    return (bit(cf, CF) | bit(pf, PF) | bit(af, AF) | bit(zf, ZF)
            | bit(sf, SF) | bit(of, OF))


def _of_bit(x, y, nbytes):
    """msb of (x & y) at the operand width — the overflow predicates."""
    return msb(and64(x, y), nbytes)


def flags_add(a, b, r, nbytes, carry):
    """Flag image of a + b (+carry) = r at nbytes width (r pre-masked ok).

    Mirrors step.py's u64 ``_flags_add`` bit-for-bit: the masked-result
    carry formula (rm < am) | (carry & (rm == am)) holds at every width.
    """
    am, rm = zext(a, nbytes), zext(r, nbytes)
    cf = ltu64(rm, am) | (carry & eq64(rm, am))
    return mkflags(
        cf=cf,
        pf=parity_even(rm[0]),
        af=((a[0] ^ b[0] ^ r[0]) & _u32(0x10)) != _u32(0),
        zf=is_zero64(rm),
        sf=msb(rm, nbytes),
        of=_of_bit(xor64(a, r), xor64(b, r), nbytes),
    )


def flags_sub(a, b, r, nbytes, borrow):
    """Flag image of a - b (-borrow) = r at nbytes width."""
    am, bm, rm = zext(a, nbytes), zext(b, nbytes), zext(r, nbytes)
    cf = jnp.where(borrow, leu64(am, bm), ltu64(am, bm))
    return mkflags(
        cf=cf,
        pf=parity_even(rm[0]),
        af=((a[0] ^ b[0] ^ r[0]) & _u32(0x10)) != _u32(0),
        zf=is_zero64(rm),
        sf=msb(rm, nbytes),
        of=_of_bit(xor64(a, b), xor64(a, r), nbytes),
    )


def flags_logic(r, nbytes):
    """Flag image of a logic result (CF=OF=AF=0)."""
    rm = zext(r, nbytes)
    false = jnp.bool_(False)
    return mkflags(
        cf=false,
        pf=parity_even(rm[0]),
        af=false,
        zf=is_zero64(rm),
        sf=msb(rm, nbytes),
        of=false,
    )


# ---------------------------------------------------------------------------
# condition evaluation (Jcc/SETcc/CMOVcc; arith flags are all in the low limb)
# ---------------------------------------------------------------------------

def eval_cond(rf_lo, rcx, cc):
    """cc 0-15: the x86 condition table; 16: jrcxz; 17: jecxz."""
    cf = (rf_lo & _u32(CF)) != 0
    pf = (rf_lo & _u32(PF)) != 0
    zf = (rf_lo & _u32(ZF)) != 0
    sf = (rf_lo & _u32(SF)) != 0
    of = (rf_lo & _u32(OF)) != 0
    conds = jnp.stack([
        of, ~of, cf, ~cf, zf, ~zf, cf | zf, ~(cf | zf),
        sf, ~sf, pf, ~pf, sf != of, sf == of,
        zf | (sf != of), ~zf & (sf == of),
    ])
    base = conds[jnp.clip(cc, 0, 15)]
    base = jnp.where(cc == 16, is_zero64(rcx), base)
    return jnp.where(cc == 17, rcx[0] == _u32(0), base)
