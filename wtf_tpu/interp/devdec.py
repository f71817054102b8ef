"""Device-resident x86-64 decode: the in-graph half of the decode seam.

PR 12's megachunk made a whole window of batches ONE dispatch, but every
decode-cache miss still early-returns the window for a host round trip
through `cpu.decoder.decode`.  This module closes that seam for the hot
subset: a lane that parks NEED_DECODE inside a megachunk window decodes
its own bytes *on device* (LUT-driven prefix/REX scan, ModRM/SIB/disp/imm
extraction, length decode, uop synthesis), claims a uop-table slot — and
with it the entry's coverage bit — through an atomic-free sequential
reservation replay, and keeps running.  Only encodings outside the device
subset park to the host as before; the host decoder stays the
authoritative oracle that back-fills and cross-checks every
device-published entry at harvest (`DecodeCache.adopt_device_entries`).

Bit-identity contract (what makes the published entries indistinguishable
from host-serviced ones):

  * the byte->uop mapping replicates `cpu/decoder.py` rule for rule — the
    descriptor LUT below is a transcription of `_decode_primary` /
    `_decode_0f`, and anything the transcription does not cover with
    certainty decodes as UNKNOWN, which parks the lane to the host
    (conservative: a park costs a round trip, a wrong publish would
    corrupt the cache);
  * code fetch goes through `mem.paging.virt_read` — the same
    overlay-aware walk the host's `HostView.virt_read` mirrors — so the
    window bytes, the fetch-fault surface and the pfn0/pfn1 SMC tags are
    the host's exactly;
  * the service order replicates `runner._service_decode`: lanes in lane
    order, one `_decode_at` + `_prefetch_block` per missing rip (publish
    even OPC_INVALID at the miss rip; LIFO successor walk with budget
    PREFETCH_BUDGET, capacity margin MARGIN, prefetched INVALIDs
    skipped), with hash-probe slots computed by the same splitmix64 + 8
    linear probes as `DecodeCache._hash_insert` so host adoption at
    harvest reproduces identical slots and entry indices.

Mesh form: block computation is lane-local (each shard fetches/decodes
with its own overlay), then the per-lane publish records are
all-gathered and EVERY shard replays the identical global commit over
its replica of the table — the replicated-table analogue of the host's
single sequential service loop.  Commit-time key dedup drops records an
earlier lane already published; a lane whose *miss* rip was published by
an earlier lane resumes without contributing records, exactly like the
host's `cache.has` gate.  (The one documented divergence from a pure
host replay: a lane's prefetch WALK is computed against the table as of
the round start plus its own records, so when two lanes' prefetch
regions overlap at differing miss rips the walk shape may differ from
the host's strictly-sequential walk.  Identical-miss lanes — the cold
start case — dedup at the lane level and match the host bit for bit.)
"""

from __future__ import annotations

from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from wtf_tpu.cpu import uops as U
from wtf_tpu.cpu.uops import INT_FIELDS
from wtf_tpu.mem import overlay as ovl
from wtf_tpu.mem import paging
from wtf_tpu.mem.physmem import MemImage

NF = len(INT_FIELDS)
_IX = {name: i for i, name in enumerate(INT_FIELDS)}
MAX_LEN = 15          # cpu.decoder.MAX_INSN_LEN
PROBES = 8            # uoptable.PROBES
PREFETCH_BUDGET = 48  # runner.PREFETCH_BUDGET
MARGIN = 64           # runner._PREFETCH_MARGIN
RECS = 50             # 1 miss + PREFETCH_BUDGET prefetched + slack
STACK = 64            # LIFO worklist depth bound (net +1 per publish)
WALK_ITERS = 112      # >= initial(2) + 2*budget pops + skipped pops slack

_M64 = jnp.uint64(0xFFFFFFFFFFFFFFFF)

# ---------------------------------------------------------------------------
# Descriptor LUT: [2 maps, 256 opcodes, 8 modrm digits, N_COL] int32, built
# once at import (numpy) and folded into the graph as a constant.  One row
# fully describes an opcode's decode rule; rows without a ModRM byte are
# replicated across the 8 digit slots.
# ---------------------------------------------------------------------------
(C_KIND, C_OPC, C_SUB, C_COND, C_MODRM, C_FORM, C_SIZE8, C_RM8, C_OSZ,
 C_IMM, C_KIMM, C_SRCSIZE, C_SEXT, C_REP, C_SPECIAL) = range(15)
N_COL = 15

# C_KIND
K_UNKNOWN, K_KNOWN = 0, 1
# C_FORM (operand wiring)
(F_NONE, F_RM_REG, F_REG_RM, F_RM_DST, F_RM_SRC, F_OPREG_SRC, F_OPREG_DST,
 F_OPREG_DST8, F_ACC, F_LEA, F_XCHG_ACC, F_RM_CL) = range(12)
# C_OSZ (operation size rule; C_SIZE8 overrides to 1)
OSZ_STD, OSZ_PP, OSZ_8, OSZ_W84 = range(4)
# C_IMM (immediate rule)
(IMM_NONE, IMM_8SX, IMM_8ZX, IMM_16ZX, IMM_STD, IMM_32SX, IMM_MOVABS,
 IMM_ONE) = range(8)
# C_SPECIAL
(SP_NONE, SP_VEX, SP_E3, SP_CD, SP_C8, SP_AE, SP_C7RD, SP_BSCAN,
 SP_POPCNT) = range(9)


def _build_lut() -> np.ndarray:
    lut = np.zeros((2, 256, 8, N_COL), dtype=np.int32)
    # default: everything UNKNOWN (parks to host) until a rule claims it
    lut[:, :, :, C_KIND] = K_UNKNOWN

    def row(m, op, digit=None, kind=K_KNOWN, opc=U.OPC_INVALID, sub=0,
            cond=0, modrm=0, form=F_NONE, size8=0, rm8=0, osz=OSZ_STD,
            imm=IMM_NONE, kimm=0, srcsize=0, sext=0, rep=0, special=SP_NONE):
        r = np.array([kind, opc, sub, cond, modrm, form, size8, rm8, osz,
                      imm, kimm, srcsize, sext, rep, special],
                     dtype=np.int32)
        digits = range(8) if digit is None else [digit]
        for d in digits:
            lut[m, op, d] = r

    def invalid(m, op, digit=None, modrm=0):
        # host `_decode_primary`/`_decode_0f` fall-through: OPC_INVALID
        # keeping a32/lock and the bytes consumed so far
        row(m, op, digit=digit, opc=U.OPC_INVALID, modrm=modrm)

    # ---- primary map ------------------------------------------------------
    # every primary opcode host-decodes deterministically; rows not claimed
    # below are the decoder's unmatched `else` -> INVALID after the opcode
    for op in range(256):
        invalid(0, op)

    # 00-3D ALU block: op>>3 = sub, op&7 = form (skip the x6/x7/xE/xF gaps)
    for hi in range(8):
        base = hi << 3
        for lo, (f, s8, im) in enumerate((
                (F_RM_REG, 1, IMM_NONE), (F_RM_REG, 0, IMM_NONE),
                (F_REG_RM, 1, IMM_NONE), (F_REG_RM, 0, IMM_NONE),
                (F_ACC, 1, IMM_8SX), (F_ACC, 0, IMM_STD))):
            row(0, base + lo, opc=U.OPC_ALU, sub=hi, modrm=(lo < 4),
                form=f, size8=s8, imm=im, kimm=(im != IMM_NONE))
    for op in range(0x50, 0x58):  # push r
        row(0, op, opc=U.OPC_PUSH, form=F_OPREG_SRC, osz=OSZ_PP)
    for op in range(0x58, 0x60):  # pop r
        row(0, op, opc=U.OPC_POP, form=F_OPREG_DST, osz=OSZ_PP)
    row(0, 0x63, opc=U.OPC_MOV, modrm=1, form=F_REG_RM, srcsize=4, sext=1)
    row(0, 0x68, opc=U.OPC_PUSH, osz=OSZ_8, imm=IMM_32SX, kimm=1)
    row(0, 0x69, opc=U.OPC_MUL, sub=U.MUL_2OP, modrm=1, form=F_REG_RM,
        imm=IMM_STD, sext=2)
    row(0, 0x6A, opc=U.OPC_PUSH, osz=OSZ_8, imm=IMM_8SX, kimm=1)
    row(0, 0x6B, opc=U.OPC_MUL, sub=U.MUL_2OP, modrm=1, form=F_REG_RM,
        imm=IMM_8SX, sext=2)
    for op in range(0x70, 0x80):  # jcc rel8
        row(0, op, opc=U.OPC_JCC, cond=op & 0xF, osz=OSZ_8, imm=IMM_8SX)
    for d in range(8):  # group 1
        row(0, 0x80, digit=d, opc=U.OPC_ALU, sub=d, modrm=1, form=F_RM_DST,
            size8=1, imm=IMM_8SX, kimm=1)
        row(0, 0x81, digit=d, opc=U.OPC_ALU, sub=d, modrm=1, form=F_RM_DST,
            imm=IMM_STD, kimm=1)
        row(0, 0x83, digit=d, opc=U.OPC_ALU, sub=d, modrm=1, form=F_RM_DST,
            imm=IMM_8SX, kimm=1)
    row(0, 0x84, opc=U.OPC_ALU, sub=U.ALU_TEST, modrm=1, form=F_RM_REG,
        size8=1)
    row(0, 0x85, opc=U.OPC_ALU, sub=U.ALU_TEST, modrm=1, form=F_RM_REG)
    row(0, 0x86, opc=U.OPC_XCHG, modrm=1, form=F_RM_REG, size8=1)
    row(0, 0x87, opc=U.OPC_XCHG, modrm=1, form=F_RM_REG)
    row(0, 0x88, opc=U.OPC_MOV, modrm=1, form=F_RM_REG, size8=1)
    row(0, 0x89, opc=U.OPC_MOV, modrm=1, form=F_RM_REG)
    row(0, 0x8A, opc=U.OPC_MOV, modrm=1, form=F_REG_RM, size8=1)
    row(0, 0x8B, opc=U.OPC_MOV, modrm=1, form=F_REG_RM)
    row(0, 0x8D, opc=U.OPC_LEA, modrm=1, form=F_LEA)
    row(0, 0x8F, opc=U.OPC_POP, modrm=1, form=F_RM_DST, osz=OSZ_PP)
    row(0, 0x90, opc=U.OPC_NOP, osz=OSZ_8)
    for op in range(0x91, 0x98):
        row(0, op, opc=U.OPC_XCHG, form=F_XCHG_ACC)
    row(0, 0x98, opc=U.OPC_CONVERT, sub=0)
    row(0, 0x99, opc=U.OPC_CONVERT, sub=1)
    row(0, 0x9B, opc=U.OPC_NOP, osz=OSZ_8)  # fwait
    row(0, 0x9C, opc=U.OPC_PUSHF, osz=OSZ_8)
    row(0, 0x9D, opc=U.OPC_POPF, osz=OSZ_8)
    row(0, 0x9E, opc=U.OPC_FLAGOP, sub=U.FL_SAHF, osz=OSZ_8)
    row(0, 0x9F, opc=U.OPC_FLAGOP, sub=U.FL_LAHF, osz=OSZ_8)
    for op, sub in ((0xA4, U.STR_MOVS), (0xA6, U.STR_CMPS),
                    (0xAA, U.STR_STOS), (0xAC, U.STR_LODS),
                    (0xAE, U.STR_SCAS)):
        row(0, op, opc=U.OPC_STRING, sub=sub, size8=1, rep=1)
        row(0, op + 1, opc=U.OPC_STRING, sub=sub, rep=1)
    row(0, 0xA8, opc=U.OPC_ALU, sub=U.ALU_TEST, form=F_ACC, size8=1,
        imm=IMM_8SX, kimm=1)
    row(0, 0xA9, opc=U.OPC_ALU, sub=U.ALU_TEST, form=F_ACC, imm=IMM_STD,
        kimm=1)
    for op in range(0xB0, 0xB8):  # mov r8, imm8 (unsigned)
        row(0, op, opc=U.OPC_MOV, form=F_OPREG_DST8, size8=1, imm=IMM_8ZX,
            kimm=1)
    for op in range(0xB8, 0xC0):  # mov r, imm (movabs family, unsigned)
        row(0, op, opc=U.OPC_MOV, form=F_OPREG_DST, imm=IMM_MOVABS, kimm=1)
    for d in range(8):  # group 2
        row(0, 0xC0, digit=d, opc=U.OPC_SHIFT, sub=d, modrm=1,
            form=F_RM_DST, size8=1, imm=IMM_8ZX, kimm=1)
        row(0, 0xC1, digit=d, opc=U.OPC_SHIFT, sub=d, modrm=1,
            form=F_RM_DST, imm=IMM_8ZX, kimm=1)
        row(0, 0xD0, digit=d, opc=U.OPC_SHIFT, sub=d, modrm=1,
            form=F_RM_DST, size8=1, imm=IMM_ONE, kimm=1)
        row(0, 0xD1, digit=d, opc=U.OPC_SHIFT, sub=d, modrm=1,
            form=F_RM_DST, imm=IMM_ONE, kimm=1)
        row(0, 0xD2, digit=d, opc=U.OPC_SHIFT, sub=d, modrm=1,
            form=F_RM_CL, size8=1, srcsize=1)
        row(0, 0xD3, digit=d, opc=U.OPC_SHIFT, sub=d, modrm=1,
            form=F_RM_CL, srcsize=1)
    row(0, 0xC2, opc=U.OPC_RET, osz=OSZ_8, imm=IMM_16ZX)
    row(0, 0xC3, opc=U.OPC_RET, osz=OSZ_8)
    row(0, 0xC6, digit=0, opc=U.OPC_MOV, modrm=1, form=F_RM_DST, size8=1,
        imm=IMM_8ZX, kimm=1)
    for d in range(1, 8):
        invalid(0, 0xC6, digit=d, modrm=1)
    row(0, 0xC7, digit=0, opc=U.OPC_MOV, modrm=1, form=F_RM_DST,
        imm=IMM_STD, kimm=1)
    for d in range(1, 8):
        invalid(0, 0xC7, digit=d, modrm=1)
    row(0, 0xC8, kind=K_UNKNOWN)  # enter: rare; host-serviced
    row(0, 0xC9, opc=U.OPC_LEAVE, osz=OSZ_8)
    row(0, 0xCA, opc=U.OPC_IRET, sub=1, osz=OSZ_8, imm=IMM_16ZX)
    row(0, 0xCB, opc=U.OPC_IRET, sub=1, osz=OSZ_8)
    row(0, 0xCC, opc=U.OPC_INT, sub=3, osz=OSZ_8)
    row(0, 0xCD, opc=U.OPC_INT, osz=OSZ_8, special=SP_CD)
    row(0, 0xCF, opc=U.OPC_IRET, osz=OSZ_W84)
    for op in range(0xD8, 0xE0):  # x87 -> host
        row(0, op, kind=K_UNKNOWN)
    row(0, 0xE3, opc=U.OPC_JCC, osz=OSZ_8, imm=IMM_8SX, special=SP_E3)
    row(0, 0xE8, opc=U.OPC_CALL, osz=OSZ_8, imm=IMM_32SX, kimm=1)
    row(0, 0xE9, opc=U.OPC_JMP, osz=OSZ_8, imm=IMM_32SX, kimm=1)
    row(0, 0xEB, opc=U.OPC_JMP, osz=OSZ_8, imm=IMM_8SX, kimm=1)
    # 0xF1 (icebp): the oracle decoder leaves it unmatched -> INVALID
    row(0, 0xF4, opc=U.OPC_HLT, osz=OSZ_8)
    row(0, 0xF5, opc=U.OPC_FLAGOP, sub=U.FL_CMC, osz=OSZ_8)
    for op, sub in ((0xF8, U.FL_CLC), (0xF9, U.FL_STC), (0xFA, U.FL_CLI),
                    (0xFB, U.FL_STI), (0xFC, U.FL_CLD), (0xFD, U.FL_STD)):
        row(0, op, opc=U.OPC_FLAGOP, sub=sub, osz=OSZ_8)
    for op, s8, im in ((0xF6, 1, IMM_8SX), (0xF7, 0, IMM_STD)):  # group 3
        for d in (0, 1):
            row(0, op, digit=d, opc=U.OPC_ALU, sub=U.ALU_TEST, modrm=1,
                form=F_RM_DST, size8=s8, imm=im, kimm=1)
        row(0, op, digit=2, opc=U.OPC_UNARY, sub=U.UN_NOT, modrm=1,
            form=F_RM_DST, size8=s8)
        row(0, op, digit=3, opc=U.OPC_UNARY, sub=U.UN_NEG, modrm=1,
            form=F_RM_DST, size8=s8)
        row(0, op, digit=4, opc=U.OPC_MUL, sub=U.MUL_WIDE_U, modrm=1,
            form=F_RM_SRC, size8=s8)
        row(0, op, digit=5, opc=U.OPC_MUL, sub=U.MUL_WIDE_S, modrm=1,
            form=F_RM_SRC, size8=s8)
        row(0, op, digit=6, opc=U.OPC_DIV, sub=U.DIV_U, modrm=1,
            form=F_RM_SRC, size8=s8)
        row(0, op, digit=7, opc=U.OPC_DIV, sub=U.DIV_S, modrm=1,
            form=F_RM_SRC, size8=s8)
    row(0, 0xFE, digit=0, opc=U.OPC_UNARY, sub=U.UN_INC, modrm=1,
        form=F_RM_DST, size8=1)
    row(0, 0xFE, digit=1, opc=U.OPC_UNARY, sub=U.UN_DEC, modrm=1,
        form=F_RM_DST, size8=1)
    for d in range(2, 8):
        invalid(0, 0xFE, digit=d, modrm=1)
    row(0, 0xFF, digit=0, opc=U.OPC_UNARY, sub=U.UN_INC, modrm=1,
        form=F_RM_DST)
    row(0, 0xFF, digit=1, opc=U.OPC_UNARY, sub=U.UN_DEC, modrm=1,
        form=F_RM_DST)
    row(0, 0xFF, digit=2, opc=U.OPC_CALL, modrm=1, form=F_RM_SRC, osz=OSZ_8)
    row(0, 0xFF, digit=4, opc=U.OPC_JMP, modrm=1, form=F_RM_SRC, osz=OSZ_8)
    row(0, 0xFF, digit=6, opc=U.OPC_PUSH, modrm=1, form=F_RM_SRC,
        osz=OSZ_PP)
    for d in (3, 5, 7):
        invalid(0, 0xFF, digit=d, modrm=1)
    # C4/C5: VEX when no legacy/REX prefix (device -> host), else the
    # primary map's unmatched INVALID
    row(0, 0xC4, special=SP_VEX)
    row(0, 0xC5, special=SP_VEX)
    # moffs forms + far/IO/loop encodings the transcription does not pin:
    # park rather than guess (host decode is cheap and authoritative)
    for op in (0xA0, 0xA1, 0xA2, 0xA3, 0xE0, 0xE1, 0xE2):
        row(0, op, kind=K_UNKNOWN)

    # ---- 0F map -----------------------------------------------------------
    # default UNKNOWN (the `_decode_0f_sse` fall-through and everything not
    # explicitly matched parks to the host) — NOT invalid: the host decodes
    # SSE/x87 forms this subset does not model
    row(1, 0x05, opc=U.OPC_SYSCALL, osz=OSZ_8)
    row(1, 0x07, opc=U.OPC_SYSCALL, sub=1, osz=OSZ_8)
    row(1, 0x0B, opc=U.OPC_INT, sub=6, osz=OSZ_8)
    row(1, 0x0D, opc=U.OPC_NOP, modrm=1, osz=OSZ_8)  # prefetchw
    for op in range(0x18, 0x20):          # hint nops: ModRM consumed
        row(1, op, opc=U.OPC_NOP, modrm=1, osz=OSZ_8)
    row(1, 0x30, opc=U.OPC_MSR, sub=1, osz=OSZ_8)
    row(1, 0x31, opc=U.OPC_RDTSC, osz=OSZ_8)
    row(1, 0x32, opc=U.OPC_MSR, sub=0, osz=OSZ_8)
    for op in range(0x40, 0x50):
        row(1, op, opc=U.OPC_CMOVCC, cond=op & 0xF, modrm=1, form=F_REG_RM)
    for op in range(0x80, 0x90):
        row(1, op, opc=U.OPC_JCC, cond=op & 0xF, osz=OSZ_8, imm=IMM_32SX)
    for op in range(0x90, 0xA0):
        row(1, op, opc=U.OPC_SETCC, cond=op & 0xF, modrm=1, form=F_RM_DST,
            size8=1)
    row(1, 0xA2, opc=U.OPC_CPUID, osz=OSZ_8)
    for op, sub in ((0xA3, U.BT_BT), (0xAB, U.BT_BTS), (0xB3, U.BT_BTR),
                    (0xBB, U.BT_BTC)):
        row(1, op, opc=U.OPC_BT, sub=sub, modrm=1, form=F_RM_REG)
    for op, sub in ((0xA4, U.SH_SHLD), (0xAC, U.SH_SHRD)):
        row(1, op, opc=U.OPC_SHIFT, sub=sub, modrm=1, form=F_RM_REG,
            imm=IMM_8ZX, sext=3)
        row(1, op + 1, opc=U.OPC_SHIFT, sub=sub, modrm=1, form=F_RM_REG,
            sext=4)
    row(1, 0xAF, opc=U.OPC_MUL, sub=U.MUL_2OP, modrm=1, form=F_REG_RM)
    row(1, 0xB0, opc=U.OPC_CMPXCHG, modrm=1, form=F_RM_REG, size8=1)
    row(1, 0xB1, opc=U.OPC_CMPXCHG, modrm=1, form=F_RM_REG)
    row(1, 0xB6, opc=U.OPC_MOV, modrm=1, form=F_REG_RM, rm8=1, srcsize=1)
    row(1, 0xB7, opc=U.OPC_MOV, modrm=1, form=F_REG_RM, srcsize=2)
    row(1, 0xBE, opc=U.OPC_MOV, modrm=1, form=F_REG_RM, rm8=1, srcsize=1,
        sext=1)
    row(1, 0xBF, opc=U.OPC_MOV, modrm=1, form=F_REG_RM, srcsize=2, sext=1)
    row(1, 0xB8, opc=U.OPC_BITSCAN, sub=U.BS_POPCNT, modrm=1,
        form=F_REG_RM, special=SP_POPCNT)
    for d in range(4):
        invalid(1, 0xBA, digit=d, modrm=1)
    for d in range(4, 8):
        row(1, 0xBA, digit=d, opc=U.OPC_BT, sub=d - 4, modrm=1,
            form=F_RM_DST, imm=IMM_8ZX, kimm=1)
    row(1, 0xBC, opc=U.OPC_BITSCAN, sub=U.BS_BSF, modrm=1, form=F_REG_RM,
        special=SP_BSCAN)
    row(1, 0xBD, opc=U.OPC_BITSCAN, sub=U.BS_BSR, modrm=1, form=F_REG_RM,
        special=SP_BSCAN)
    row(1, 0xC0, opc=U.OPC_XADD, modrm=1, form=F_RM_REG, size8=1)
    row(1, 0xC1, opc=U.OPC_XADD, modrm=1, form=F_RM_REG)
    for op in range(0xC8, 0xD0):
        row(1, op, opc=U.OPC_BSWAP, form=F_OPREG_DST, osz=OSZ_W84)
    return lut


_LUT = _build_lut()

# ---------------------------------------------------------------------------
# Traced scalar decode of one 15-byte window -> uop record (vmap for lanes)
# ---------------------------------------------------------------------------


class DecUop(NamedTuple):
    known: jax.Array   # bool: within the device subset (False -> park)
    f: jax.Array       # int32[NF] in uops.INT_FIELDS order
    disp: jax.Array    # uint64 (sign-extended, masked)
    imm: jax.Array     # uint64


def _rd(win: jax.Array, i: jax.Array) -> jax.Array:
    """Clamped byte read: out-of-window indices only occur on encodings
    whose consumed length exceeds the window, which decode as the host's
    _Truncated all-default INVALID — the clamped value is never used."""
    return win[jnp.clip(i, 0, MAX_LEN - 1)].astype(jnp.int32)


def _sx_u64(v: jax.Array, bits: int) -> jax.Array:
    sign = jnp.uint64(1 << (bits - 1))
    return (v ^ sign) - sign  # u64 wraparound == host _sx mask


def _read_le_u64(win: jax.Array, i: jax.Array) -> jax.Array:
    v = jnp.uint64(0)
    for k in range(8):
        v = v | (_rd(win, i + k).astype(jnp.uint64) << jnp.uint64(8 * k))
    return v


_LUT_FLAT = jnp.asarray(_LUT.reshape(2 * 256 * 8, N_COL))


def decode_window(win: jax.Array) -> DecUop:
    """Decode the instruction at win[0:15] (uint8[15]).  Replicates
    cpu.decoder.decode bit for bit over the device subset; anything the
    LUT marks UNKNOWN returns known=False for a host park."""
    i32 = jnp.int32

    # prefix scan (cpu.decoder._decode_prefixes): legacy prefixes in any
    # order/count, then at most one REX immediately before the opcode
    def pfx_body(_, c):
        pos, done, osize, asize, lock, repne, rep, seg, anyleg = c
        b = _rd(win, pos)
        is66, is67 = b == 0x66, b == 0x67
        isf0, isf2, isf3 = b == 0xF0, b == 0xF2, b == 0xF3
        is64, is65 = b == 0x64, b == 0x65
        isnull = (b == 0x26) | (b == 0x2E) | (b == 0x36) | (b == 0x3E)
        legacy = is66 | is67 | isf0 | isf2 | isf3 | is64 | is65 | isnull
        take = jnp.logical_and(~done, legacy)
        seg = jnp.where(take & is64, i32(U.SEG_FS),
                        jnp.where(take & is65, i32(U.SEG_GS), seg))
        return (pos + take.astype(i32), done | ~legacy,
                osize | (take & is66), asize | (take & is67),
                lock | (take & isf0), repne | (take & isf2),
                rep | (take & isf3), seg,
                anyleg | (take & (is66 | isf0 | isf2 | isf3)))

    f_ = jnp.bool_(False)
    pos, _, osize, asize, lock, repne, rep, seg, anyleg = lax.fori_loop(
        0, MAX_LEN, pfx_body,
        (i32(0), f_, f_, f_, f_, f_, f_, i32(U.SEG_NONE), f_))

    b = _rd(win, pos)
    isrex = (b >= 0x40) & (b <= 0x4F)
    rex = jnp.where(isrex, b & 0xF, 0)
    rexp = isrex
    pos = pos + isrex.astype(i32)
    rex_w, rex_r = (rex >> 3) & 1, (rex >> 2) & 1
    rex_x, rex_b = (rex >> 1) & 1, rex & 1

    op = _rd(win, pos)
    pos = pos + 1
    map1 = op == 0x0F
    op2 = _rd(win, pos)
    pos = pos + map1.astype(i32)          # position after the opcode
    opv = jnp.where(map1, op2, op)

    row = _LUT_FLAT[(map1.astype(i32) * 256 + opv) * 8
                    + ((_rd(win, pos) >> 3) & 7)]
    known = row[C_KIND] == K_KNOWN
    special = row[C_SPECIAL]
    has_modrm = (row[C_MODRM] > 0) & known

    # speculative ModRM/SIB/disp parse (cpu.decoder._ModRM)
    modrm = _rd(win, pos)
    mod = modrm >> 6
    regf = ((modrm >> 3) & 7) | (rex_r << 3)
    rm = modrm & 7
    is_mem = mod != 3
    rm_reg = rm | (rex_b << 3)
    sib = _rd(win, pos + 1)
    sib_present = has_modrm & is_mem & (rm == 4)
    sidx = ((sib >> 3) & 7) | (rex_x << 3)
    sbase = (sib & 7) | (rex_b << 3)
    rip_rel = has_modrm & is_mem & (rm == 5) & (mod == 0)
    sib_disp32 = sib_present & ((sbase & 7) == 5) & (mod == 0)
    disp8 = has_modrm & is_mem & (mod == 1)
    disp32 = (has_modrm & is_mem & (mod == 2)) | rip_rel | sib_disp32
    disp_off = pos + 1 + sib_present.astype(i32)
    disp_len = jnp.where(disp8, 1, jnp.where(disp32, 4, 0))
    modrm_len = jnp.where(has_modrm,
                          1 + sib_present.astype(i32) + disp_len, 0)
    draw = _read_le_u64(win, disp_off)
    disp = jnp.where(disp8, _sx_u64(draw & jnp.uint64(0xFF), 8),
                     jnp.where(disp32,
                               _sx_u64(draw & jnp.uint64(0xFFFFFFFF), 32),
                               jnp.uint64(0)))
    base_reg = jnp.where(
        rip_rel, i32(U.REG_RIP),
        jnp.where(sib_present,
                  jnp.where(sib_disp32, i32(U.REG_NONE), sbase),
                  jnp.where(is_mem, rm_reg, i32(U.REG_NONE))))
    base_reg = jnp.where(has_modrm & is_mem, base_reg, i32(U.REG_NONE))
    idx_reg = jnp.where(sib_present & (sidx != 4), sidx, i32(U.REG_NONE))
    scale = jnp.where(sib_present, i32(1) << (sib >> 6), i32(1))

    # operation size
    size8 = row[C_SIZE8] > 0
    osz = row[C_OSZ]
    opsize = jnp.where(
        size8, 1,
        jnp.where(osz == OSZ_PP, jnp.where(osize, 2, 8),
                  jnp.where(osz == OSZ_8, 8,
                            jnp.where(osz == OSZ_W84,
                                      jnp.where(rex_w > 0, 8, 4),
                                      jnp.where(rex_w > 0, 8,
                                                jnp.where(osize, 2, 4))))))

    # immediate
    immc = row[C_IMM]
    imm_len = jnp.where(
        (immc == IMM_8SX) | (immc == IMM_8ZX), 1,
        jnp.where(immc == IMM_16ZX, 2,
                  jnp.where(immc == IMM_STD,
                            jnp.where(opsize == 2, 2, 4),
                            jnp.where(immc == IMM_32SX, 4,
                                      jnp.where(immc == IMM_MOVABS,
                                                jnp.where(opsize == 8, 8,
                                                          jnp.where(opsize == 2,
                                                                    2, 4)),
                                                0)))))
    ipos = pos + modrm_len
    iraw = _read_le_u64(win, ipos)
    shift = jnp.uint64(64) - (imm_len.astype(jnp.uint64) << jnp.uint64(3))
    masked = jnp.where(imm_len > 0, (iraw << shift) >> shift, jnp.uint64(0))
    imm = jnp.where(
        immc == IMM_8SX, _sx_u64(masked, 8),
        jnp.where(immc == IMM_STD,
                  jnp.where(opsize == 2, _sx_u64(masked, 16),
                            _sx_u64(masked, 32)),
                  jnp.where(immc == IMM_32SX, _sx_u64(masked, 32),
                            jnp.where(immc == IMM_ONE, jnp.uint64(1),
                                      masked))))
    length = ipos + imm_len + (special == SP_CD).astype(i32)

    # specials
    sub = row[C_SUB]
    cond = row[C_COND]
    kind_unknown = ~known
    sub = jnp.where(special == SP_CD, _rd(win, ipos), sub)
    cond = jnp.where(special == SP_E3,
                     jnp.where(asize, i32(17), i32(16)), cond)
    bs = special == SP_BSCAN
    sub = jnp.where(bs & rep & (sub == U.BS_BSF), i32(U.BS_TZCNT),
                    jnp.where(bs & rep & (sub == U.BS_BSR),
                              i32(U.BS_LZCNT), sub))
    kind_unknown = kind_unknown | ((special == SP_POPCNT) & ~rep)
    # C4/C5: VEX (-> host) unless a legacy/REX prefix #UDs it into the
    # primary map's unmatched INVALID
    vex = special == SP_VEX
    vex_invalid = vex & (anyleg | rexp)
    kind_unknown = kind_unknown | (vex & ~vex_invalid)

    # operand synthesis
    def g8(r):
        return jnp.where((rex == 0) & (r >= 4) & (r <= 7),
                         U.REG_AH_BASE + (r - 4), r)

    form = row[C_FORM]
    opreg = (opv & 7) | (rex_b << 3)
    rm_is_dst = ((form == F_RM_REG) | (form == F_RM_DST)
                 | (form == F_RM_CL))
    rm_is_src = (form == F_REG_RM) | (form == F_RM_SRC)
    rm_used = rm_is_dst | rm_is_src
    rm8 = size8 | (row[C_RM8] > 0)
    rm_regv = jnp.where(rm8, g8(rm_reg), rm_reg)
    reg_regv = jnp.where(size8, g8(regf), regf)
    mem_side = rm_used & is_mem

    dst_kind = jnp.where(
        rm_is_dst, jnp.where(is_mem, i32(U.K_MEM), i32(U.K_REG)),
        jnp.where((form == F_REG_RM) | (form == F_LEA)
                  | (form == F_OPREG_DST) | (form == F_OPREG_DST8)
                  | (form == F_XCHG_ACC) | (form == F_ACC),
                  i32(U.K_REG), i32(U.K_NONE)))
    dst_reg = jnp.where(
        rm_is_dst & ~is_mem, rm_regv,
        jnp.where(form == F_REG_RM, reg_regv,
                  jnp.where(form == F_LEA, regf,
                            jnp.where(form == F_OPREG_DST, opreg,
                                      jnp.where(form == F_OPREG_DST8,
                                                g8(opreg),
                                                jnp.where(form == F_XCHG_ACC,
                                                          opreg, i32(0)))))))
    dst_reg = jnp.where(dst_kind == U.K_REG, dst_reg, i32(0))
    src_kind = jnp.where(
        rm_is_src, jnp.where(is_mem, i32(U.K_MEM), i32(U.K_REG)),
        jnp.where((form == F_RM_REG) | (form == F_OPREG_SRC)
                  | (form == F_XCHG_ACC) | (form == F_RM_CL),
                  i32(U.K_REG), i32(U.K_NONE)))
    src_reg = jnp.where(
        rm_is_src & ~is_mem, rm_regv,
        jnp.where(form == F_RM_REG, reg_regv,
                  jnp.where(form == F_OPREG_SRC, opreg,
                            jnp.where(form == F_RM_CL, i32(1), i32(0)))))
    src_reg = jnp.where(src_kind == U.K_REG, src_reg, i32(0))
    src_kind = jnp.where(row[C_KIMM] > 0, i32(U.K_IMM), src_kind)

    lea_mem = (form == F_LEA) & is_mem
    use_mem = mem_side | lea_mem
    segv = jnp.where(mem_side, seg, i32(U.SEG_NONE))  # lea ignores seg
    base_reg = jnp.where(use_mem, base_reg, i32(U.REG_NONE))
    idx_reg = jnp.where(use_mem, idx_reg, i32(U.REG_NONE))
    scale = jnp.where(use_mem, scale, i32(1))
    disp = jnp.where(use_mem, disp, jnp.uint64(0))

    repv = jnp.where(row[C_REP] > 0,
                     jnp.where(rep, i32(U.REP_REP),
                               jnp.where(repne, i32(U.REP_REPNE),
                                         i32(U.REP_NONE))),
                     i32(U.REP_NONE))

    opc = row[C_OPC]
    # lea reg-form: INVALID after the (consumed) ModRM
    lea_invalid = (form == F_LEA) & ~is_mem
    invalid = (opc == U.OPC_INVALID) | lea_invalid | vex_invalid

    def inv(val, default):
        return jnp.where(invalid, default, val)

    opc = jnp.where(invalid, i32(U.OPC_INVALID), opc)
    length = jnp.where(vex_invalid, pos, length)
    # lea reg-form: the host sets opsize before bailing to INVALID
    opsize_out = jnp.where(invalid & ~lea_invalid, i32(8), opsize)
    fields = [
        opc, inv(sub, i32(0)), inv(cond, i32(0)), length,
        opsize_out, inv(row[C_SRCSIZE], i32(0)),
        inv(row[C_SEXT], i32(0)),
        inv(dst_kind, i32(U.K_NONE)), inv(dst_reg, i32(0)),
        inv(src_kind, i32(U.K_NONE)), inv(src_reg, i32(0)),
        inv(base_reg, i32(U.REG_NONE)), inv(idx_reg, i32(U.REG_NONE)),
        inv(scale, i32(1)), inv(segv, i32(U.SEG_NONE)),
        inv(repv, i32(U.REP_NONE)), lock.astype(i32), asize.astype(i32)]
    disp = inv(disp, jnp.uint64(0))
    imm = inv(imm, jnp.uint64(0))

    # truncation: the host raises _Truncated at the first needed byte past
    # the window and returns the ALL-default INVALID (a32/lock included)
    f = jnp.stack(fields)
    trunc = length > MAX_LEN
    default = jnp.zeros((NF,), i32).at[_IX["opc"]].set(U.OPC_INVALID) \
        .at[_IX["length"]].set(1).at[_IX["opsize"]].set(8) \
        .at[_IX["base_reg"]].set(U.REG_NONE) \
        .at[_IX["idx_reg"]].set(U.REG_NONE).at[_IX["scale"]].set(1)
    f = jnp.where(trunc, default, f)
    disp = jnp.where(trunc, jnp.uint64(0), disp)
    imm = jnp.where(trunc, jnp.uint64(0), imm)
    return DecUop(known=~kind_unknown, f=f, disp=disp, imm=imm)

# ---------------------------------------------------------------------------
# Service pass: per-lane block compute (parallel) + global sequential commit
# ---------------------------------------------------------------------------
#
# A service pass replicates one round of `runner._service_decode` in-graph:
#
#   phase 1 (lane-parallel, vmapped; mesh: shard-local): each NEED_DECODE
#     lane fetches and decodes its miss and runs the LIFO prefetch walk
#     against the ROUND-START table plus its own records, yielding a block
#     of up to RECS publish records;
#   phase 2 (sequential, replicated on every shard after an all-gather):
#     blocks commit in global lane order against the LIVE table.  The
#     commit enforces the host's gates exactly — `cache.has` at the miss
#     (drop block, resume lane), the capacity margin mid-walk (drop the
#     block's tail, keep the lane serviced) — and detects every case the
#     phase-1 walk could have diverged from the host's strictly-sequential
#     service (a record already published by an earlier lane THIS round, a
#     hash-probe failure, capacity, or an encoding outside the device
#     subset).  Any such lane rolls back its partial block and parks, and
#     so does EVERY needy lane after it: the host then services the parked
#     lanes in lane order, which preserves the one invariant everything
#     downstream leans on — entry indices (and so coverage-bitmap bits)
#     identical to a run where the host serviced every miss itself.

_STATUS_NEED_DECODE = 8   # StatusCode.NEED_DECODE (core/results.py)
_STATUS_RUNNING = 0       # StatusCode.RUNNING
_STATUS_PAGE_FAULT = 7    # StatusCode.PAGE_FAULT
_CTR_MEM_FAULT = 1        # machine.CTR_MEM_FAULT

_N_META = NF + 3          # uoptable meta_i32 columns (fields, pfn0, pfn1, bp)

# uoptable.meta_u64 column order
MU_DISP, MU_IMM, MU_RAW_LO, MU_RAW_HI = range(4)


def _splitmix_lo(key: jax.Array) -> jax.Array:
    """splitmix64 low 32 bits (utils/hashing.py bit for bit); the hash
    mask is < 2^32 so (h + k) & mask == (h_lo + k) & mask."""
    x = key + jnp.uint64(0x9E3779B97F4A7C15)
    x = (x ^ (x >> jnp.uint64(30))) * jnp.uint64(0xBF58476D1CE4E5B9)
    x = (x ^ (x >> jnp.uint64(27))) * jnp.uint64(0x94D049BB133111EB)
    x = x ^ (x >> jnp.uint64(31))
    return (x & jnp.uint64(0xFFFFFFFF)).astype(jnp.uint32)


def _probe_slots(hash_rows: jax.Array, key: jax.Array) -> jax.Array:
    """The 8 probe slot indices for `key` (same sequence as
    `DecodeCache._hash_insert`)."""
    mask = jnp.uint32(hash_rows.shape[0] - 1)
    h = _splitmix_lo(key)
    return ((h + jnp.arange(PROBES, dtype=jnp.uint32)) & mask).astype(
        jnp.int32)


def _probe_entry(hash_rows: jax.Array, key: jax.Array) -> jax.Array:
    """Live-table lookup: entry index or -1.  `hash_rows` is the widened
    [hash_size, 3] (entry, key_lo, key_hi) table (uoptable.device)."""
    rows = hash_rows[_probe_slots(hash_rows, key)]
    klo = (key & jnp.uint64(0xFFFFFFFF)).astype(jnp.uint32).astype(jnp.int32)
    khi = (key >> jnp.uint64(32)).astype(jnp.uint32).astype(jnp.int32)
    hit = (rows[:, 0] >= 0) & (rows[:, 1] == klo) & (rows[:, 2] == khi)
    return jnp.max(jnp.where(hit, rows[:, 0], -1))


def _key_of(rip: jax.Array, tenant: jax.Array) -> jax.Array:
    return rip ^ (tenant.astype(jnp.uint64) << jnp.uint64(48))


class LaneBlock(NamedTuple):
    """One lane's phase-1 result: its miss outcome plus publish records
    (record 0 = the miss; the rest the prefetch walk, in walk order)."""

    needy: jax.Array    # bool: lane was NEED_DECODE
    fault: jax.Array    # bool: 15-byte fetch at the miss rip faulted
    parked: jax.Array   # bool: miss or walk left the device subset
    rip: jax.Array      # u64 miss rip (fault_gva on the fault path)
    n: jax.Array        # i32 record count (0 on fault/park-at-miss)
    keys: jax.Array     # u64[RECS] tagged probe keys
    fi: jax.Array       # i32[RECS, NF+3] uoptable meta_i32 rows
    fu: jax.Array       # u64[RECS, 4] uoptable meta_u64 rows


def _pack_raw_u64(win: jax.Array, length: jax.Array):
    """Device `_pack_raw`: the first `length` window bytes LE-packed into
    (lo, hi), zero beyond — bit-identical to the host's ljust-with-NULs
    since decode lengths never exceed MAX_LEN < 16."""
    w16 = jnp.concatenate([win, jnp.zeros((1,), jnp.uint8)])
    lo = jnp.uint64(0)
    hi = jnp.uint64(0)
    for k in range(8):
        lo = lo | (w16[k].astype(jnp.uint64) << jnp.uint64(8 * k))
        hi = hi | (w16[8 + k].astype(jnp.uint64) << jnp.uint64(8 * k))
    nlo = jnp.minimum(length, 8)
    nhi = jnp.maximum(length - 8, 0)
    lo_mask = _M64 >> (jnp.uint64(64) - jnp.uint64(8) * nlo.astype(jnp.uint64))
    hi_mask = jnp.where(
        nhi > 0,
        _M64 >> (jnp.uint64(64) - jnp.uint64(8) * nhi.astype(jnp.uint64)),
        jnp.uint64(0))
    return lo & lo_mask, hi & hi_mask


def _record_row(image, overlay, cr3, at: jax.Array, d: DecUop,
                pfn0: jax.Array, win: jax.Array, bp_keys, n_bp,
                key: jax.Array):
    """Assemble the uoptable meta rows for a decoded instruction —
    pfn1 (`runner._decode_at`: translate of the last byte, pfn0 on
    fault), bp (pending-breakpoint membership), raw packing."""
    length = d.f[_IX["length"]]
    t1 = paging.translate(
        image, overlay, cr3,
        at + jnp.maximum(length - 1, 0).astype(jnp.uint64))
    pfn1 = jnp.where(t1.ok, (t1.gpa >> jnp.uint64(12)).astype(jnp.int32),
                     pfn0)
    nb = jnp.arange(bp_keys.shape[0], dtype=jnp.int32) < n_bp
    bp = jnp.any(nb & (bp_keys == key)).astype(jnp.int32)
    fi = jnp.concatenate([d.f, jnp.stack([pfn0, pfn1, bp])])
    lo, hi = _pack_raw_u64(win, length)
    fu = jnp.stack([d.disp, d.imm, lo, hi])
    return fi, fu


def _succs(fi: jax.Array, fu: jax.Array, at: jax.Array):
    """`runner._prefetch_block.succs` — (push_a, push_b, count) with the
    host's extend order (fallthrough pushed first, so the branch target
    pops first off the LIFO stack)."""
    opc = fi[_IX["opc"]]
    nxt = at + fi[_IX["length"]].astype(jnp.uint64)
    tgt = nxt + fu[MU_IMM]
    terminal = ((opc == U.OPC_RET) | (opc == U.OPC_IRET)
                | (opc == U.OPC_HLT) | (opc == U.OPC_INT)
                | (opc == U.OPC_INT1) | (opc == U.OPC_INVALID)
                | (opc == U.OPC_SYSCALL))
    is_imm = fi[_IX["src_kind"]] == U.K_IMM
    two = (opc == U.OPC_JCC) | ((opc == U.OPC_CALL) & is_imm)
    jmp = opc == U.OPC_JMP
    i32 = jnp.int32
    n = jnp.where(terminal, i32(0),
                  jnp.where(two, i32(2),
                            jnp.where(jmp,
                                      jnp.where(is_imm, i32(1), i32(0)),
                                      i32(1))))
    a = jnp.where(jmp, tgt, nxt)
    return a, tgt, n


def lane_block(tab, image, overlay, cr3: jax.Array, rip: jax.Array,
               status: jax.Array, bp_keys: jax.Array,
               n_bp: jax.Array) -> LaneBlock:
    """Phase 1 for ONE lane (vmap over lanes; every argument scalar or
    lane-sliced, `tab` the ROUND-START table).  Runs regardless of
    status — the commit gates on `needy` — so the vmapped pass has one
    uniform shape."""
    i32 = jnp.int32
    tenant = image.tenant
    needy = status == i32(_STATUS_NEED_DECODE)

    win, fault = paging.virt_read(image, overlay, cr3, rip, MAX_LEN)
    t0 = paging.translate(image, overlay, cr3, rip)
    pfn0 = (t0.gpa >> jnp.uint64(12)).astype(i32)
    d = decode_window(win)
    key0 = _key_of(rip, tenant)
    fi0, fu0 = _record_row(image, overlay, cr3, rip, d, pfn0, win,
                           bp_keys, n_bp, key0)

    keys = jnp.zeros((RECS,), jnp.uint64).at[0].set(key0)
    fis = jnp.zeros((RECS, _N_META), i32).at[0].set(fi0)
    fus = jnp.zeros((RECS, 4), jnp.uint64).at[0].set(fu0)

    parked0 = ~fault & ~d.known
    ok0 = ~fault & d.known

    # LIFO walk seeded with the miss uop's successors
    a, b, ns = _succs(fi0, fu0, rip)
    stack = jnp.zeros((STACK,), jnp.uint64).at[0].set(a).at[1].set(b)
    sp = jnp.where(ok0, ns, i32(0))

    def body(_, c):
        keys, fis, fus, stack, sp, n, budget, parked = c
        act = (sp > 0) & (budget > 0) & ~parked & (n < RECS)
        at = stack[jnp.maximum(sp - 1, 0)]
        sp2 = jnp.where(act, sp - 1, sp)
        key = _key_of(at, tenant)
        seen = (_probe_entry(tab.hash_tab, key) >= 0) | jnp.any(
            (jnp.arange(RECS, dtype=i32) < n) & (keys == key))
        w, f = paging.virt_read(image, overlay, cr3, at, MAX_LEN)
        t = paging.translate(image, overlay, cr3, at)
        p0 = (t.gpa >> jnp.uint64(12)).astype(i32)
        dd = decode_window(w)
        take = act & ~seen & ~f
        parked2 = parked | (take & ~dd.known)
        add = take & dd.known & (dd.f[_IX["opc"]] != U.OPC_INVALID)
        fi, fu = _record_row(image, overlay, cr3, at, dd, p0, w,
                             bp_keys, n_bp, key)
        slot = jnp.where(add, n, RECS - 1)
        keys2 = jnp.where(add, keys.at[slot].set(key), keys)
        fis2 = jnp.where(add, fis.at[slot].set(fi), fis)
        fus2 = jnp.where(add, fus.at[slot].set(fu), fus)
        n2 = n + add.astype(i32)
        budget2 = budget - add.astype(i32)
        sa, sb, sn = _succs(fi, fu, at)
        push = jnp.where(add, sn, 0)
        stack2 = stack.at[jnp.minimum(sp2, STACK - 1)].set(
            jnp.where(push >= 1, sa, stack[jnp.minimum(sp2, STACK - 1)]))
        stack3 = stack2.at[jnp.minimum(sp2 + 1, STACK - 1)].set(
            jnp.where(push >= 2, sb,
                      stack2[jnp.minimum(sp2 + 1, STACK - 1)]))
        sp3 = sp2 + push
        # stack bound: net growth is +1 per published record, so STACK
        # cannot overflow before RECS does; park if it ever would
        parked3 = parked2 | (sp3 > STACK - 1)
        return (keys2, fis2, fus2, stack3, jnp.minimum(sp3, STACK - 1),
                n2, budget2, parked3)

    keys, fis, fus, _, _, n, _, parked = lax.fori_loop(
        0, WALK_ITERS, body,
        (keys, fis, fus, stack, sp, jnp.where(ok0, i32(1), i32(0)),
         i32(PREFETCH_BUDGET), parked0))

    return LaneBlock(
        needy=needy, fault=fault & needy, parked=parked & needy, rip=rip,
        n=jnp.where(ok0, n, i32(0)), keys=keys, fi=fis, fu=fus)


def compute_blocks(tab, image: MemImage, machine, bp_keys: jax.Array,
                   n_bp: jax.Array) -> LaneBlock:
    """Vmapped phase 1 over all local lanes."""
    from wtf_tpu.mem.physmem import IMAGE_IN_AXES

    return jax.vmap(
        lane_block,
        in_axes=(None, IMAGE_IN_AXES, ovl.LANE_AXES, 0, 0, 0, None, None),
    )(tab, image, ovl.lanes(machine.overlay), machine.cr3, machine.rip,
      machine.status, bp_keys, n_bp)


@jax.jit
def gather_windows(image: MemImage, overlay, cr3: jax.Array,
                   rips: jax.Array, idx: jax.Array):
    """Code windows for the HOST service path (the `--device-decode`
    satellite of runner._service_decode): for each lane index in `idx`,
    the 15-byte fetch window at its rip plus the page-walk facts the
    host decode needs — gathered ON DEVICE in one dispatch, so the host
    transfers k x 15 bytes instead of pulling whole overlay pages and
    walking page tables through the HostView.

    Returns (win u8[k, 15], fault bool[k], pfn0 i32[k], pfn14 i32[k]):
    `fault` mirrors HostView.virt_read's any-byte-faults contract;
    `pfn14` is the frame of the window's last byte (== pfn0 unless the
    window crosses a page), which is all the host needs to reproduce
    `_decode_at`'s pfn1 without a second walk — a successful 15-byte
    read guarantees the last instruction byte's translation succeeds."""
    from wtf_tpu.mem.physmem import IMAGE_IN_AXES, lane_image

    n_lanes = cr3.shape[0]
    img = lane_image(image, n_lanes)
    img_g = img._replace(tenant=img.tenant[idx])
    ov_g = ovl.lane(overlay, idx)

    def one(image_l, overlay_l, cr3_l, rip):
        win, fault = paging.virt_read(image_l, overlay_l, cr3_l, rip,
                                      MAX_LEN)
        t0 = paging.translate(image_l, overlay_l, cr3_l, rip)
        pfn0 = (t0.gpa >> jnp.uint64(12)).astype(jnp.int32)
        t14 = paging.translate(image_l, overlay_l, cr3_l,
                               rip + jnp.uint64(MAX_LEN - 1))
        pfn14 = jnp.where(
            t14.ok, (t14.gpa >> jnp.uint64(12)).astype(jnp.int32), pfn0)
        return win, fault, pfn0, pfn14

    return jax.vmap(one, in_axes=(IMAGE_IN_AXES, ovl.LANE_AXES, 0, 0))(
        img_g, ov_g, cr3[idx], rips[idx])


class CommitOut(NamedTuple):
    """Phase-2 result: updated table + per-GLOBAL-lane machine deltas
    (the caller applies its local slice) + stats."""

    tab: object          # UopTable with committed rows
    count: jax.Array     # i32 live entries
    status: jax.Array    # i32[Lg] post-service status
    fault_gva: jax.Array   # u64[Lg]
    fault_mask: jax.Array  # bool[Lg] lanes whose fault fields apply
    mem_fault_inc: jax.Array  # u32[Lg] CTR_MEM_FAULT increments
    parked: jax.Array    # bool[Lg] lanes left for the host
    stats: jax.Array     # i32[3]: serviced lanes, published entries, parks


def commit_blocks(tab, count: jax.Array, blocks: LaneBlock,
                  statuses: jax.Array, capacity: int) -> CommitOut:
    """Phase 2: replay every lane's block in global lane order against
    the live table.  Pure function of (tab, count, blocks, statuses) —
    identical on every shard when blocks/statuses are all-gathered."""
    i32 = jnp.int32
    n_lanes = statuses.shape[0]

    def insert(hash_rows, key, idx):
        """Claim the first free probe slot (host `_hash_insert`);
        returns (rows, slot, ok)."""
        slots = _probe_slots(hash_rows, key)
        free = hash_rows[slots, 0] < 0
        anyfree = jnp.any(free)
        k = jnp.argmax(free)          # first free slot in probe order
        slot = slots[k]
        klo = (key & jnp.uint64(0xFFFFFFFF)).astype(
            jnp.uint32).astype(i32)
        khi = (key >> jnp.uint64(32)).astype(jnp.uint32).astype(i32)
        row = jnp.stack([idx, klo, khi])
        rows2 = jnp.where(anyfree, hash_rows.at[slot].set(row), hash_rows)
        return rows2, slot, anyfree

    def lane_step(g, carry):
        (hash_rows, rip_l, mi, mu, count, park_rest, status, fault_gva,
         fault_mask, mf_inc, parked, stats) = carry
        blk = jax.tree_util.tree_map(lambda a: a[g], blocks)
        needy = blk.needy & (status[g] == i32(_STATUS_NEED_DECODE))

        hit0 = _probe_entry(hash_rows, blk.keys[0]) >= 0
        resume = needy & ~park_rest & hit0      # host `cache.has` gate
        faulted = needy & ~park_rest & ~hit0 & blk.fault
        try_commit = needy & ~park_rest & ~hit0 & ~blk.fault & ~blk.parked
        park_now = needy & ~park_rest & ~hit0 & ~blk.fault & blk.parked

        def rec_step(j, rc):
            (rows, rl, mi2, mu2, cnt, slots_used, ncommit, aborted,
             stopped) = rc
            live = try_commit & (j < blk.n) & ~aborted & ~stopped
            # host walk margin: checked before every pop AFTER the miss
            stop2 = stopped | (live & (j > 0)
                               & (cnt >= i32(capacity - MARGIN)))
            live = live & ~stop2
            key = blk.keys[j]
            # divergence vs an earlier lane's same-round commit
            dup = live & (j > 0) & (_probe_entry(rows, key) >= 0)
            full = live & (cnt >= i32(capacity))
            ins = live & ~dup & ~full
            rows2, slot, ok = insert(rows, key, cnt)
            rows3 = jnp.where(ins, rows2, rows)
            abort2 = aborted | dup | full | (ins & ~ok)
            did = ins & ok
            klo = (key & jnp.uint64(0xFFFFFFFF)).astype(jnp.uint32)
            khi = (key >> jnp.uint64(32)).astype(jnp.uint32)
            at = jnp.where(did, cnt, i32(0))
            rl2 = jnp.where(did,
                            rl.at[at].set(jnp.stack([klo, khi])), rl)
            mi3 = jnp.where(did, mi2.at[at].set(blk.fi[j]), mi2)
            mu3 = jnp.where(did, mu2.at[at].set(blk.fu[j]), mu2)
            su2 = slots_used.at[j].set(jnp.where(did, slot, -1))
            return (rows3, rl2, mi3, mu3, cnt + did.astype(i32), su2,
                    ncommit + did.astype(i32), abort2, stop2)

        slots0 = jnp.full((RECS,), -1, i32)
        (rows, rl, mi2, mu2, cnt, slots_used, ncommit, aborted,
         _stopped) = lax.fori_loop(
            0, RECS, rec_step,
            (hash_rows, rip_l, mi, mu, count, slots0, i32(0),
             jnp.bool_(False), jnp.bool_(False)))

        # an aborted block needs no explicit rollback: the whole-table
        # `where` below re-selects the pre-lane arrays, dropping every
        # slot it claimed
        committed = try_commit & ~aborted
        hash_rows2 = jnp.where(committed, rows, hash_rows)
        rip_l2 = jnp.where(committed, rl, rip_l)
        mi3 = jnp.where(committed, mi2, mi)
        mu3 = jnp.where(committed, mu2, mu)
        count2 = jnp.where(committed, cnt, count)

        parked_g = park_now | (aborted & try_commit) | (park_rest & needy)
        status2 = status.at[g].set(jnp.where(
            resume | committed, i32(_STATUS_RUNNING),
            jnp.where(faulted, i32(_STATUS_PAGE_FAULT), status[g])))
        fault_gva2 = jnp.where(faulted, fault_gva.at[g].set(blk.rip),
                               fault_gva)
        fault_mask2 = fault_mask.at[g].set(faulted)
        mf2 = jnp.where(faulted,
                        mf_inc.at[g].set(jnp.uint32(1)), mf_inc)
        parked2 = parked.at[g].set(parked_g)
        stats2 = (stats
                  .at[0].add(committed.astype(i32))
                  .at[1].add(jnp.where(committed, ncommit, 0))
                  .at[2].add(parked_g.astype(i32)))
        return (hash_rows2, rip_l2, mi3, mu3, count2,
                park_rest | parked_g, status2, fault_gva2, fault_mask2,
                mf2, parked2, stats2)

    init = (tab.hash_tab, tab.rip_l, tab.meta_i32, tab.meta_u64, count,
            jnp.bool_(False), statuses,
            jnp.zeros((n_lanes,), jnp.uint64),
            jnp.zeros((n_lanes,), bool),
            jnp.zeros((n_lanes,), jnp.uint32),
            jnp.zeros((n_lanes,), bool), jnp.zeros((3,), i32))
    (hash_rows, rip_l, mi, mu, count, _pr, status, fault_gva, fault_mask,
     mf_inc, parked, stats) = lax.fori_loop(0, n_lanes, lane_step, init)
    tab2 = tab._replace(hash_tab=hash_rows, rip_l=rip_l, meta_i32=mi,
                        meta_u64=mu)
    return CommitOut(tab=tab2, count=count, status=status,
                     fault_gva=fault_gva, fault_mask=fault_mask,
                     mem_fault_inc=mf_inc, parked=parked, stats=stats)
