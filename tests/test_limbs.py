"""Unit tests for the u32 limb-arithmetic library (interp/limbs.py) and the
HLO guard for the ported step paths.

Two jobs:

1. Property-style corner grids: every limb helper checked against Python
   big-int ground truth at the places limb code breaks — carry-out chains,
   cross-limb shifts by 0/31/32/33/63(/64), widening multiply highs, flag
   bits at every operand width.

2. The no-u64 guard (ISSUE 2 acceptance): compile the ported functions —
   the limb library itself, the step's ALU/unary/addressing cores, the
   decode-cache hash probe — and assert the optimized HLO contains ZERO
   64-bit integer ops.  This is what keeps a future edit from silently
   reintroducing u64 (XLA would lower it to a u32 pair on TPU) on the
   paths this PR ported.  Since ISSUE 5
   the contract lives in the analysis rule API (wtf_tpu/analysis/rules.py
   dtype family, enumerated from step.PORTED_LIMB_PATHS) — this file just
   runs the family, so the tests and `wtf-tpu lint` can never disagree.
"""

import numpy as np
import pytest

import jax.numpy as jnp

from wtf_tpu.interp import limbs as L
from wtf_tpu.interp import step as S
from wtf_tpu.utils.hashing import mix64, splitmix64

MASK64 = (1 << 64) - 1

# corner values: limb boundaries, sign boundaries, all-ones, and a few
# irregular bit patterns
CORNERS = [
    0, 1, 2, 0xFF, 0x100, 0x7FFF, 0x8000, 0xFFFF, 0x10000,
    0x7FFFFFFF, 0x80000000, 0xFFFFFFFF, 0x100000000, 0x123456789,
    0x7FFFFFFFFFFFFFFF, 0x8000000000000000, 0xFFFFFFFFFFFFFFFF,
    0x1122334455667788, 0xFEDCBA9876543210, 0x00000001FFFFFFFF,
    0xFFFFFFFF00000000, 0x0F0F0F0F0F0F0F0F,
]
SHIFTS = [0, 1, 7, 31, 32, 33, 63, 64, 65, 127]


def _pairs(values):
    v = np.array(values, dtype=np.uint64)
    u = L.unpack_np(v)
    return jnp.asarray(u[:, 0]), jnp.asarray(u[:, 1])


def _ints(pair):
    lo = np.asarray(pair[0], dtype=np.uint64)
    hi = np.asarray(pair[1], dtype=np.uint64)
    return [int(l) | (int(h) << 32) for l, h in zip(lo.ravel(), hi.ravel())]


def _cross(xs, ys):
    """All (x, y) combinations as two flat lists."""
    ax = [x for x in xs for _ in ys]
    ay = [y for _ in xs for y in ys]
    return ax, ay


def test_pack_unpack_roundtrip_np():
    v = np.array(CORNERS, dtype=np.uint64)
    assert (L.pack_np(L.unpack_np(v)) == v).all()
    m = np.arange(32, dtype=np.uint64).reshape(2, 4, 4)
    assert (L.pack_np(L.unpack_np(m)) == m).all()


def test_pack_unpack_roundtrip_device():
    v = jnp.asarray(np.array(CORNERS, dtype=np.uint64))
    assert (L.pack_u64(L.unpack_u64(v)) == v).all()
    p = L.pair(v)
    assert (L.to_u64(p) == v).all()


def test_add_sub_carry_chains():
    ax, bx = _cross(CORNERS, CORNERS)
    a, b = _pairs(ax), _pairs(bx)
    for carry in (False, True):
        cin = jnp.full(len(ax), carry)
        s, cout = L.adc64(a, b, cin)
        d, bout = L.sbb64(a, b, cin)
        for i, (x, y) in enumerate(zip(ax, bx)):
            add = x + y + carry
            assert _ints(s)[i] == add & MASK64, f"adc {x:#x}+{y:#x}+{carry}"
            assert bool(np.asarray(cout)[i]) == (add > MASK64)
            sub = x - y - carry
            assert _ints(d)[i] == sub & MASK64, f"sbb {x:#x}-{y:#x}-{carry}"
            assert bool(np.asarray(bout)[i]) == (sub < 0)


def test_logic_neg_compare():
    ax, bx = _cross(CORNERS, CORNERS)
    a, b = _pairs(ax), _pairs(bx)
    assert _ints(L.and64(a, b)) == [x & y for x, y in zip(ax, bx)]
    assert _ints(L.or64(a, b)) == [x | y for x, y in zip(ax, bx)]
    assert _ints(L.xor64(a, b)) == [x ^ y for x, y in zip(ax, bx)]
    assert _ints(L.not64(a)) == [x ^ MASK64 for x in ax]
    assert _ints(L.neg64(a)) == [(-x) & MASK64 for x in ax]
    assert list(np.asarray(L.eq64(a, b))) == [x == y for x, y in zip(ax, bx)]
    assert list(np.asarray(L.ltu64(a, b))) == [x < y for x, y in zip(ax, bx)]
    assert list(np.asarray(L.leu64(a, b))) == [x <= y for x, y in zip(ax, bx)]
    assert list(np.asarray(L.is_zero64(a))) == [x == 0 for x in ax]


@pytest.mark.parametrize("op,ref", [
    ("shl64", lambda x, s: (x << s) & MASK64 if s < 64 else 0),
    ("shr64", lambda x, s: x >> s if s < 64 else 0),
    ("sar64", lambda x, s: (x - ((x >> 63) << 64)) >> min(s, 63) & MASK64),
])
def test_shifts_across_limb_boundary(op, ref):
    ax, sx = _cross(CORNERS, SHIFTS)
    a = _pairs(ax)
    s = jnp.asarray(np.array(sx, dtype=np.uint32))
    got = _ints(getattr(L, op)(a, s))
    for i, (x, sh) in enumerate(zip(ax, sx)):
        assert got[i] == ref(x, sh) & MASK64, f"{op}({x:#x}, {sh})"


def test_rotates():
    ax, sx = _cross(CORNERS, SHIFTS)
    a = _pairs(ax)
    s = jnp.asarray(np.array(sx, dtype=np.uint32))
    rol = _ints(L.rol64(a, s))
    ror = _ints(L.ror64(a, s))
    for i, (x, sh) in enumerate(zip(ax, sx)):
        k = sh % 64
        want_rol = ((x << k) | (x >> (64 - k))) & MASK64 if k else x
        want_ror = ((x >> k) | (x << (64 - k))) & MASK64 if k else x
        assert rol[i] == want_rol, f"rol64({x:#x}, {sh})"
        assert ror[i] == want_ror, f"ror64({x:#x}, {sh})"


def test_mul32_wide_highs():
    vals = [0, 1, 2, 0xFF, 0xFFFF, 0x10000, 0x10001, 0x7FFFFFFF,
            0x80000000, 0xFFFFFFFF, 0xDEADBEEF, 0x12345678]
    ax, bx = _cross(vals, vals)
    a = jnp.asarray(np.array(ax, dtype=np.uint32))
    b = jnp.asarray(np.array(bx, dtype=np.uint32))
    lo, hi = L.mul32_wide(a, b)
    for i, (x, y) in enumerate(zip(ax, bx)):
        p = x * y
        assert int(np.asarray(lo)[i]) == p & 0xFFFFFFFF
        assert int(np.asarray(hi)[i]) == p >> 32, f"mulhi {x:#x}*{y:#x}"


def test_umulhi_smulhi64():
    ax, bx = _cross(CORNERS, CORNERS[:14])
    a, b = _pairs(ax), _pairs(bx)
    uhi = _ints(L.umulhi64(a, b))
    shi = _ints(L.smulhi64(a, b))
    for i, (x, y) in enumerate(zip(ax, bx)):
        assert uhi[i] == (x * y) >> 64, f"umulhi64 {x:#x}*{y:#x}"
        sx = x - (1 << 64) if x >> 63 else x
        sy = y - (1 << 64) if y >> 63 else y
        assert shi[i] == ((sx * sy) >> 64) & MASK64, f"smulhi64 {x:#x}*{y:#x}"


def test_mul64_lo_and_splitmix():
    ax, bx = _cross(CORNERS, CORNERS[:12])
    a, b = _pairs(ax), _pairs(bx)
    got = _ints(L.mul64_lo(a, b))
    for i, (x, y) in enumerate(zip(ax, bx)):
        assert got[i] == (x * y) & MASK64, f"mul64_lo {x:#x}*{y:#x}"
    # splitmix64/mix64 must match the host reference bit-for-bit (the
    # decode-cache probe and edge hash depend on it)
    v = _pairs(CORNERS)
    assert _ints(L.splitmix64(v)) == [splitmix64(x) for x in CORNERS]
    assert _ints(L.mix64(v)) == [mix64(x) for x in CORNERS]


@pytest.mark.parametrize("nbytes", [1, 2, 4, 8])
def test_extend_mask_msb(nbytes):
    a = _pairs(CORNERS)
    n = jnp.full(len(CORNERS), nbytes, dtype=jnp.int32)
    bits = min(nbytes, 8) * 8
    m = (1 << bits) - 1
    assert _ints(L.zext(a, n)) == [x & m for x in CORNERS]
    want_sext = []
    for x in CORNERS:
        v = x & m
        if v >> (bits - 1):
            v |= MASK64 ^ m
        want_sext.append(v)
    assert _ints(L.sext(a, n)) == want_sext
    assert list(np.asarray(L.msb(a, n))) == [
        bool((x >> (bits - 1)) & 1) for x in CORNERS]


def _ref_flags_add(a, b, bits, carry):
    m = (1 << bits) - 1
    am, bm = a & m, b & m
    r = am + bm + carry
    rm = r & m
    return _mk_ref(cf=r > m, r=rm, bits=bits, af=(a ^ b ^ rm) & 0x10,
                   of=((am ^ rm) & (bm ^ rm)) >> (bits - 1) & 1)


def _ref_flags_sub(a, b, bits, borrow):
    m = (1 << bits) - 1
    am, bm = a & m, b & m
    rm = (am - bm - borrow) & m
    return _mk_ref(cf=am < bm + borrow, r=rm, bits=bits,
                   af=(a ^ b ^ rm) & 0x10,
                   of=((am ^ bm) & (am ^ rm)) >> (bits - 1) & 1)


def _mk_ref(cf, r, bits, af, of):
    pf = bin(r & 0xFF).count("1") % 2 == 0
    return ((L.CF if cf else 0) | (L.PF if pf else 0) | (L.AF if af else 0)
            | (L.ZF if r == 0 else 0)
            | (L.SF if (r >> (bits - 1)) & 1 else 0)
            | (L.OF if of else 0))


@pytest.mark.parametrize("nbytes", [1, 2, 4, 8])
def test_flag_bits_against_bigint(nbytes):
    bits = nbytes * 8
    m = (1 << bits) - 1
    ops = [v & m for v in CORNERS]
    ax, bx = _cross(ops, ops)
    n = jnp.full(len(ax), nbytes, dtype=jnp.int32)
    a, b = _pairs(ax), _pairs(bx)
    for carry in (False, True):
        cin = jnp.full(len(ax), carry)
        r_add = L.zext(L.adc64(a, b, cin)[0], n)
        fl_add = np.asarray(L.flags_add(a, b, r_add, n, cin))
        r_sub = L.zext(L.sbb64(a, b, cin)[0], n)
        fl_sub = np.asarray(L.flags_sub(a, b, r_sub, n, cin))
        for i, (x, y) in enumerate(zip(ax, bx)):
            assert int(fl_add[i]) == _ref_flags_add(x, y, bits, carry), (
                f"flags_add({x:#x}, {y:#x}, c={carry}, n={nbytes})")
            assert int(fl_sub[i]) == _ref_flags_sub(x, y, bits, carry), (
                f"flags_sub({x:#x}, {y:#x}, b={carry}, n={nbytes})")
    fl_logic = np.asarray(L.flags_logic(L.zext(L.and64(a, b), n), n))
    for i, (x, y) in enumerate(zip(ax, bx)):
        r = (x & y) & m
        assert int(fl_logic[i]) == _mk_ref(cf=False, r=r, bits=bits,
                                           af=0, of=0)


def test_eval_cond_table():
    # every flag combination over CF/PF/ZF/SF/OF x every condition code
    combos = []
    for mask in range(32):
        rf = ((mask & 1) * L.CF | ((mask >> 1) & 1) * L.PF
              | ((mask >> 2) & 1) * L.ZF | ((mask >> 3) & 1) * L.SF
              | ((mask >> 4) & 1) * L.OF)
        combos.append(rf)
    for cc in range(18):
        for rcx in (0, 1, 0xFFFFFFFF, 0x100000000, 0x1_0000_0001):
            rf = jnp.asarray(np.array(combos, dtype=np.uint32))
            rcx_l = _pairs([rcx] * len(combos))
            got = np.asarray(L.eval_cond(rf, rcx_l, jnp.int32(cc)))
            for i, flags in enumerate(combos):
                cf, pf = bool(flags & L.CF), bool(flags & L.PF)
                zf, sf = bool(flags & L.ZF), bool(flags & L.SF)
                of = bool(flags & L.OF)
                table = [of, not of, cf, not cf, zf, not zf,
                         cf or zf, not (cf or zf), sf, not sf, pf, not pf,
                         sf != of, sf == of, zf or (sf != of),
                         not zf and (sf == of)]
                if cc == 16:
                    want = rcx == 0
                elif cc == 17:
                    want = rcx & 0xFFFFFFFF == 0
                else:
                    want = table[cc]
                assert bool(got[i]) == want, f"cc={cc} flags={flags:#x}"


# ---------------------------------------------------------------------------
# the no-u64 guard for the ported step paths (one source of truth:
# wtf_tpu/analysis — ISSUE 5 satellite migrated the ad-hoc string greps)
# ---------------------------------------------------------------------------

def test_hlo_ported_paths_are_u64_free():
    """The zero-u64/s64 (and float-free) HLO pin over EVERY enumerated
    ported path — the limb library, the step ALU/unary/shift/mul/EA cores,
    the decode-cache probe, and the pack/unpack bitcast-only seam — via the
    analysis dtype rule family (what `wtf-tpu lint` runs;
    step.PORTED_LIMB_PATHS is the enumeration, so a newly ported path is
    covered by being exported)."""
    from wtf_tpu.analysis.rules import run_dtype_family

    findings = run_dtype_family()
    assert not findings, [str(f) for f in findings]


def test_limb_shift_mul_match_bigint_reference():
    """shift_limb / mul_limb against Python big-int recomputation of the
    x86 semantics at every width — the contract the deleted u64 SHIFT/MUL
    blocks embodied (results only; the flag images are pinned three-way by
    tests/test_step.py's hardware-differential corpus)."""
    from wtf_tpu.cpu import uops as U

    rng = np.random.default_rng(0x5417)
    k = 128
    a64 = rng.integers(0, 1 << 64, k, dtype=np.uint64)
    f64 = rng.integers(0, 1 << 64, k, dtype=np.uint64)
    cnt = rng.integers(0, 256, k, dtype=np.uint64)
    for nbytes in (1, 2, 4, 8):
        bits = nbytes * 8
        m = (1 << bits) - 1
        n = jnp.full(k, nbytes, dtype=jnp.int32)
        a = L.zext(L.pair(jnp.asarray(a64)), n)
        fill = L.zext(L.pair(jnp.asarray(f64)), n)
        cl = jnp.asarray(cnt, dtype=np.uint32)

        def run_shift(subval, sextv=0):
            r, _rf, writes = S.shift_limb(
                jnp.full(k, subval, jnp.int32), jnp.full(k, sextv, jnp.int32),
                a, fill, cl, cl, cl, jnp.full(k, True), n,
                jnp.uint32(0x246))
            return _ints(r), np.asarray(writes)

        cmask = 0x3F if nbytes == 8 else 0x1F
        got_shl, w_shl = run_shift(U.SH_SHL)
        got_shr, _ = run_shift(U.SH_SHR)
        got_sar, _ = run_shift(U.SH_SAR)
        got_rol, _ = run_shift(U.SH_ROL)
        got_rcl, _ = run_shift(U.SH_RCL)
        for i in range(k):
            av = int(a64[i]) & m
            c = int(cnt[i]) & cmask
            if c == 0:
                assert not w_shl[i]
                continue
            assert got_shl[i] == (av << c) & m if c < 64 else 0
            assert got_shr[i] == (av >> c) if c < 64 else 0
            sv = av - (1 << bits) if av >> (bits - 1) else av
            assert got_sar[i] == (sv >> min(c, 63)) & m
            rc = c % bits
            want_rol = av if rc == 0 else ((av << rc) | (av >> (bits - rc))) & m
            assert got_rol[i] == want_rol, f"rol n={nbytes} a={av:#x} c={c}"
            crc = c % (bits + 1)
            wide = (1 << bits) | av          # CF=1 : bits+1-bit value
            want_rcl = av if crc == 0 else (
                ((wide << crc) | (wide >> (bits + 1 - crc))) & m)
            assert got_rcl[i] == want_rcl, f"rcl n={nbytes} a={av:#x} c={c}"

        b = L.zext(L.pair(jnp.asarray(f64)), n)
        for subval, signed in ((U.MUL_WIDE_U, False), (U.MUL_WIDE_S, True),
                               (U.MUL_2OP, True)):
            r1, r2, _rf = S.mul_limb(
                jnp.full(k, subval, jnp.int32), jnp.zeros(k, jnp.int32),
                a, b, a, b, n, jnp.uint32(0x246))
            g1, g2 = _ints(r1), _ints(r2)
            for i in range(k):
                av, bv = int(a64[i]) & m, int(f64[i]) & m
                sa = av - (1 << bits) if signed and av >> (bits - 1) else av
                sb = bv - (1 << bits) if signed and bv >> (bits - 1) else bv
                prod = sa * sb
                if subval == U.MUL_2OP:
                    assert g1[i] == prod & m, f"imul2 n={nbytes}"
                elif nbytes == 1:
                    assert g1[i] == prod & 0xFFFF, f"mul8 {av:#x}*{bv:#x}"
                else:
                    assert g1[i] == prod & m
                    assert g2[i] == (prod >> bits) & m, (
                        f"mulhi n={nbytes} {av:#x}*{bv:#x} sub={subval}")


def test_limb_alu_matches_u64_reference():
    """alu_limb against a direct u64 recompute of the same semantics —
    the contract the deleted u64 ALU block used to embody."""
    rng = np.random.default_rng(0x11B5)
    k = 256
    a64 = rng.integers(0, 1 << 64, k, dtype=np.uint64)
    b64 = rng.integers(0, 1 << 64, k, dtype=np.uint64)
    a = L.pair(jnp.asarray(a64))
    b = L.pair(jnp.asarray(b64))
    for nbytes in (1, 2, 4, 8):
        m = (1 << (nbytes * 8)) - 1
        n = jnp.full(k, nbytes, dtype=jnp.int32)
        for subname, subval, ref in [
            ("add", 0, lambda x, y: (x + y) & m),
            ("or", 1, lambda x, y: (x | y) & m),
            ("adc", 2, lambda x, y: (x + y + 1) & m),
            ("sbb", 3, lambda x, y: (x - y - 1) & m),
            ("and", 4, lambda x, y: (x & y) & m),
            ("sub", 5, lambda x, y: (x - y) & m),
            ("xor", 6, lambda x, y: (x ^ y) & m),
            ("cmp", 7, lambda x, y: (x - y) & m),
        ]:
            sub = jnp.full(k, subval, dtype=jnp.int32)
            cin = jnp.full(k, True)
            am = L.zext(a, n)
            bm = L.zext(b, n)
            r, _rf, writes = S.alu_limb(sub, am, bm, cin, n, jnp.uint32(0x2))
            got = _ints(r)
            for i in range(k):
                assert got[i] == ref(int(a64[i]) & m, int(b64[i]) & m), (
                    f"{subname} n={nbytes} a={a64[i]:#x} b={b64[i]:#x}")
            assert bool(np.asarray(writes)[0]) == (subname != "cmp")


def test_const_shifts_and_small_add():
    a = _pairs(CORNERS)
    for k in (0, 1, 7, 31, 32, 33, 63):
        assert _ints(L.shl64_const(a, k)) == [
            (x << k) & MASK64 for x in CORNERS], f"shl64_const {k}"
        assert _ints(L.shr64_const(a, k)) == [
            x >> k for x in CORNERS], f"shr64_const {k}"
    for small in (0, 1, 0xFF, 0xFFFFFFFF):
        s = jnp.full(len(CORNERS), small, dtype=jnp.uint32)
        assert _ints(L.add64_u32(a, s)) == [
            (x + small) & MASK64 for x in CORNERS], f"add64_u32 {small:#x}"
