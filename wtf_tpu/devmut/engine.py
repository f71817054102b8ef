"""The device-resident mangle engine: a whole batch of testcases in-graph.

This is the devmangle generator core — ROADMAP item 3's "move the
mangle-class mutators on-device as vectorized u32 ops so the testcase
stream never leaves HBM".  One `generate` dispatch produces every lane's
next testcase from the HBM corpus slab (devmut/corpus.py): per-lane
splitmix64 PRNG streams built on interp/limbs.py, the 8-op mangle table
(hostref.OP_NAMES) vectorized over [lanes, max_len] byte planes, and a
pack back to the u32 words the fused insert seam (interp/runner.py
`device_insert`) writes straight into the per-lane overlay.

Contracts:
  * bit-for-bit equal to devmut/hostref.py (the spec; property-tested)
  * u32/i32/bool ONLY — every public helper here is exported through
    `PORTED_LIMB_PATHS` so `wtf-tpu lint`'s dtype family compiles it
    under the zero-u64/f64 pin, exactly like the step's ported paths
  * all shapes static: jit keys on (slots, words, lanes); `rounds` is a
    python int closed over by `make_generate`

Byte plane: ops run on u32[L, max_len] arrays holding one BYTE per
element (unpacked from the slab's packed u32 words, repacked at the
end).  Positional ops are broadcast compares against an iota — no
scatter; the shifting ops (insert/erase/copy/splice) are ONE gather
each via clamped source-index maps.
"""

from __future__ import annotations

from functools import lru_cache, partial
from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax

from wtf_tpu.devmut.hostref import (
    GOLDEN, MAG_BYTES_NP, MAG_LEN_NP, N_MAGIC, N_OPS,
)
from wtf_tpu.interp import limbs


def prng_next(state):
    """One splitmix64 draw on a (lo, hi) u32 limb-pair state:
    state += GOLDEN; out = mix64(state).  Returns (state', out)."""
    state = limbs.add64(state, limbs.const_pair(GOLDEN))
    return state, limbs.mix64(state)


def pick_slot(cumw, r32):
    """Weighted corpus-slot pick for a batch of u32 draws `r32[L]`:
    inverse of the inclusive cumulative-weight table `cumw[S]` —
    count-of-(cumw <= r % total), so zero-weight slots are never chosen.
    Returns int32[L]."""
    total = jnp.maximum(cumw[-1], jnp.uint32(1))
    rr = r32 % total
    cnt = jnp.sum((cumw[None, :] <= rr[:, None]).astype(jnp.uint32),
                  axis=1, dtype=jnp.uint32)
    return jnp.minimum(
        cnt, jnp.uint32(cumw.shape[-1] - 1)).astype(jnp.int32)


def unpack_bytes(rows):
    """Packed u32 words [..., W] -> byte plane [..., W*4] (little-endian;
    each output element holds one byte value 0..255 in a u32)."""
    shifts = jnp.asarray([0, 8, 16, 24], dtype=jnp.uint32)
    b = (rows[..., None] >> shifts) & jnp.uint32(0xFF)
    return b.reshape(rows.shape[:-1] + (rows.shape[-1] * 4,))


def pack_words(b):
    """Byte plane [..., 4*W] -> packed u32 words [..., W]."""
    return (b[..., 0::4] | (b[..., 1::4] << jnp.uint32(8))
            | (b[..., 2::4] << jnp.uint32(16))
            | (b[..., 3::4] << jnp.uint32(24)))


@jax.named_scope("devmut-generate")
def generate(data, lens, cumw, seeds, *, rounds: int = 5
             ) -> Tuple[jax.Array, jax.Array]:
    """Generate one testcase per lane, entirely in-graph.

    data  uint32[S, W]   corpus slab (zero-padded past each length)
    lens  int32[S]       per-slot byte lengths (>= 1 for live slots)
    cumw  uint32[S]      inclusive cumulative favor weights (0-total =
                         empty corpus -> fresh synthesis path)
    seeds uint32[L, 2]   per-lane splitmix64 seeds (hostref.lane_seeds)

    Returns (words uint32[L, W], lens int32[L]).  Mirror of
    hostref.host_generate — see that module for the op spec.
    """
    n_slots, n_words = data.shape
    n_lanes = seeds.shape[0]
    max_len = n_words * 4
    ml = jnp.uint32(max_len)
    idx = lax.broadcasted_iota(jnp.uint32, (n_lanes, max_len), 1)
    lane = lax.broadcasted_iota(jnp.int32, (n_lanes, max_len), 0)
    mag_bytes = jnp.asarray(MAG_BYTES_NP)
    mag_lens = jnp.asarray(MAG_LEN_NP)

    def take(b, src_u32):
        """b[lane, min(src, max_len-1)] — the clamped gather every
        shifting op uses (out-of-window sources are selected away)."""
        src = jnp.minimum(src_u32, ml - jnp.uint32(1)).astype(jnp.int32)
        return b[lane, src]

    st = (seeds[:, 0], seeds[:, 1])
    st, r_slot = prng_next(st)
    st, r_len = prng_next(st)
    st, r_fill = prng_next(st)
    st, r_other = prng_next(st)

    have = cumw[-1] > jnp.uint32(0)

    def slab_row(r):
        slot = pick_slot(cumw, r[0])
        row_ln = jnp.clip(lens[slot], 1, max_len).astype(jnp.uint32)
        return unpack_bytes(data[slot]), row_ln

    base_b, base_ln = slab_row(r_slot)
    # empty-corpus synthesis: 1..64 stream bytes (generate_fresh role)
    fresh_ln = jnp.uint32(1) + (r_len[0] % jnp.uint32(min(64, max_len)))
    fill = limbs.mix64(limbs.add64(
        (jnp.broadcast_to(r_fill[0][:, None], idx.shape),
         jnp.broadcast_to(r_fill[1][:, None], idx.shape)),
        (idx, jnp.zeros_like(idx))))[0] & jnp.uint32(0xFF)
    b = jnp.where(have, base_b, fill)
    ln = jnp.where(have, base_ln, jnp.minimum(fresh_ln, ml))
    ln = jnp.maximum(ln, jnp.uint32(1))
    b = jnp.where(idx < ln[:, None], b, jnp.uint32(0))

    # splice partner: drawn once per testcase (self when corpus empty)
    ob_slab, oln_slab = slab_row(r_other)
    ob = jnp.where(have, ob_slab, b)
    oln = jnp.where(have, oln_slab, ln)

    def body(_, carry):
        b, ln, slo, shi = carry
        st = (slo, shi)
        st, r_op = prng_next(st)
        st, r1 = prng_next(st)
        st, r2 = prng_next(st)
        st, r3 = prng_next(st)
        op = r_op[0] % jnp.uint32(N_OPS)
        lnc = ln[:, None]

        # 0/1/2: byte overwrite / word overwrite / arith delta at r1%len
        pos = (r1[0] % ln)[:, None]
        b_byte = jnp.where(idx == pos,
                           (r2[0] & jnp.uint32(0xFF))[:, None], b)
        wwin = (idx >= pos) & (idx < pos + jnp.uint32(4)) & (idx < lnc)
        wsh = ((idx - pos) & jnp.uint32(3)) * jnp.uint32(8)
        b_word = jnp.where(
            wwin, (r2[0][:, None] >> wsh) & jnp.uint32(0xFF), b)
        delta = ((r2[0] % jnp.uint32(71)) + jnp.uint32(221)) & jnp.uint32(0xFF)
        b_arith = jnp.where(
            idx == pos, (b + delta[:, None]) & jnp.uint32(0xFF), b)

        # 3: magic value (clipped to len)
        mrow = mag_bytes[(r1[0] % jnp.uint32(N_MAGIC)).astype(jnp.int32)]
        mlen = mag_lens[(r1[0] % jnp.uint32(N_MAGIC)).astype(jnp.int32)]
        mpos = (r2[0] % ln)[:, None]
        mwin = (idx >= mpos) & (idx < mpos + mlen[:, None]) & (idx < lnc)
        mj = ((idx - mpos) & jnp.uint32(7)).astype(jnp.int32)
        b_magic = jnp.where(mwin, mrow[lane, mj], b)

        # 4: block copy (reads the round-input bytes, memcpy-from-snapshot)
        csrc = r1[0] % ln
        cdst = (r2[0] % ln)[:, None]
        ck = (jnp.uint32(1) + (r3[0] % jnp.uint32(16)))[:, None]
        sidx = csrc[:, None] + (idx - cdst)
        cwin = ((idx >= cdst) & (idx < cdst + ck) & (idx < lnc)
                & (sidx < lnc))
        b_copy = jnp.where(cwin, take(b, sidx), b)

        # 5: insert — duplicate the k bytes at pos, tail shifts right
        ipos = r1[0] % ln
        ik = jnp.minimum(jnp.uint32(1) + (r2[0] % jnp.uint32(16)), ml - ln)
        isrc = jnp.where(idx < (ipos + ik)[:, None], idx, idx - ik[:, None])
        b_ins = take(b, isrc)
        ln_ins = ln + ik

        # 6: erase k bytes at pos (len stays >= 1)
        can = ln > jnp.uint32(1)
        epos = r1[0] % ln
        ek = jnp.uint32(1) + (r2[0] % jnp.uint32(16))
        ek = jnp.minimum(jnp.minimum(ek, ln - epos), ln - jnp.uint32(1))
        ek = jnp.where(can, ek, jnp.uint32(0))
        esrc = jnp.where(idx < epos[:, None], idx, idx + ek[:, None])
        b_erase = take(b, esrc)
        ln_erase = ln - ek

        # 7: splice — our prefix [0, cut) + partner's bytes from cut2
        cut = r2[0] % (ln + jnp.uint32(1))
        cut2 = r3[0] % (oln + jnp.uint32(1))
        stake = jnp.minimum(oln - cut2, ml - cut)
        ssrc = cut2[:, None] + (idx - cut[:, None])
        b_spl = jnp.where(idx < cut[:, None], b, take(ob, ssrc))
        ln_spl = jnp.maximum(cut + stake, jnp.uint32(1))

        cands = ((b_byte, ln), (b_word, ln), (b_arith, ln), (b_magic, ln),
                 (b_copy, ln), (b_ins, ln_ins), (b_erase, ln_erase),
                 (b_spl, ln_spl))
        nb, nl = b, ln
        for code, (cb, cl) in enumerate(cands):
            is_op = op == jnp.uint32(code)
            nb = jnp.where(is_op[:, None], cb, nb)
            nl = jnp.where(is_op, cl, nl)
        # padded-slab contract: bytes past the new length are zero
        nb = jnp.where(idx < nl[:, None], nb, jnp.uint32(0))
        return nb, nl, st[0], st[1]

    b, ln, _, _ = lax.fori_loop(0, rounds, body, (b, ln, st[0], st[1]))
    return pack_words(b), ln.astype(jnp.int32)


@lru_cache(maxsize=None)
def make_generate(rounds: int = 5):
    """The jitted batch generator for a given round count (shape
    specialization is jit's own; one executor per (slots, words, lanes))."""
    return jax.jit(partial(generate, rounds=rounds))


# Export hook for the static analyzer, mirroring step.PORTED_LIMB_PATHS:
# every engine path is compiled standalone under the zero-u64/f64 dtype
# rule by `wtf-tpu lint` and tests/test_limbs.py (argument recipes live
# in analysis/rules._dtype_arg_recipes).
PORTED_LIMB_PATHS = {
    "devmut.prng_next": prng_next,
    "devmut.pick_slot": pick_slot,
    "devmut.unpack_bytes": unpack_bytes,
    "devmut.pack_words": pack_words,
    "devmut.generate": generate,
}
