"""Memory subsystem tests: physmem image, dirty overlay, paging, virt I/O."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from wtf_tpu.core.gxa import PAGE_SIZE
from wtf_tpu.mem.overlay import (
    LANE_AXES,
    commit_spills,
    commit_stores,
    lane,
    lanes,
    overlay_init,
    overlay_reset,
    phys_read,
    phys_read_u64,
    phys_write,
    resident,
    scatter_span,
)
from wtf_tpu.mem.paging import translate, virt_read, virt_read_u64, virt_write
from wtf_tpu.mem.physmem import PhysMem
from wtf_tpu.snapshot.synthetic import SyntheticSnapshotBuilder


def _lane(overlay, i=0):
    """Lane i's overlay view (what vmap hands the per-lane fns): its own
    index state, the whole flat planes and its base into them."""
    return lane(overlay, i)


def _merge_lane(overlay, lane_overlay, i=0):
    """Lane i's view back into the batched overlay (the view's planes
    ARE the batch's planes)."""
    return overlay._replace(
        pfn=overlay.pfn.at[i].set(lane_overlay.pfn),
        data=lane_overlay.data, valid=lane_overlay.valid,
        count=overlay.count.at[i].set(lane_overlay.count),
        overflow=overlay.overflow.at[i].set(lane_overlay.overflow))


@pytest.fixture(scope="module")
def simple_mem():
    pages = {
        3: bytes(range(256)) * 16,
        5: b"\xAA" * PAGE_SIZE,
        6: b"\xBB" * PAGE_SIZE,
    }
    return PhysMem.from_pages(pages)


def test_phys_read_base(simple_mem):
    ov = _lane(overlay_init(1, 4))
    data = phys_read(simple_mem.image, ov, jnp.uint64(3 * PAGE_SIZE + 1), 4)
    assert list(np.asarray(data)) == [1, 2, 3, 4]


def test_phys_read_absent_page_is_zero(simple_mem):
    ov = _lane(overlay_init(1, 4))
    data = phys_read(simple_mem.image, ov, jnp.uint64(0x100 * PAGE_SIZE), 8)
    assert list(np.asarray(data)) == [0] * 8
    # out of frame-table range too
    data = phys_read(simple_mem.image, ov, jnp.uint64(1 << 40), 8)
    assert list(np.asarray(data)) == [0] * 8


def test_phys_read_page_crossing(simple_mem):
    ov = _lane(overlay_init(1, 4))
    gpa = jnp.uint64(5 * PAGE_SIZE + PAGE_SIZE - 2)
    data = phys_read(simple_mem.image, ov, gpa, 4)
    assert list(np.asarray(data)) == [0xAA, 0xAA, 0xBB, 0xBB]


def test_phys_write_copy_on_write(simple_mem):
    ov = _lane(overlay_init(1, 4))
    gpa = jnp.uint64(3 * PAGE_SIZE + 10)
    ov, ok = phys_write(
        simple_mem.image, ov, gpa, jnp.array([9, 9], dtype=jnp.uint8), jnp.bool_(True)
    )
    assert bool(ok)
    assert int(ov.count) == 1
    # Readback sees the overlay; neighbors keep base content (CoW copied page).
    data = phys_read(simple_mem.image, ov, gpa - jnp.uint64(1), 4)
    assert list(np.asarray(data)) == [9, 9, 9, 12]
    # Base image untouched.
    assert simple_mem.host_read(3 * PAGE_SIZE + 10, 2) == bytes([10, 11])


def test_phys_write_disabled_is_noop(simple_mem):
    ov = _lane(overlay_init(1, 4))
    ov, _ = phys_write(
        simple_mem.image,
        ov,
        jnp.uint64(3 * PAGE_SIZE),
        jnp.array([1], dtype=jnp.uint8),
        jnp.bool_(False),
    )
    assert int(ov.count) == 0
    data = phys_read(simple_mem.image, ov, jnp.uint64(3 * PAGE_SIZE), 1)
    assert int(data[0]) == 0


def test_phys_write_crossing_and_reset(simple_mem):
    ov = _lane(overlay_init(1, 4))
    gpa = jnp.uint64(5 * PAGE_SIZE + PAGE_SIZE - 1)
    ov, ok = phys_write(
        simple_mem.image, ov, gpa, jnp.array([1, 2], dtype=jnp.uint8), jnp.bool_(True)
    )
    assert bool(ok)
    assert int(ov.count) == 2  # both pages went dirty
    data = phys_read(simple_mem.image, ov, gpa, 2)
    assert list(np.asarray(data)) == [1, 2]
    # Restore: O(1) reset drops all dirty data.
    ov = _lane(overlay_reset(_merge_lane(overlay_init(1, 4), ov)))
    assert int(ov.count) == 0
    data = phys_read(simple_mem.image, ov, gpa, 2)
    assert list(np.asarray(data)) == [0xAA, 0xBB]


def test_overlay_overflow_flag(simple_mem):
    ov = _lane(overlay_init(1, 2))
    for pfn in (3, 5, 6):
        ov, ok = phys_write(
            simple_mem.image,
            ov,
            jnp.uint64(pfn * PAGE_SIZE),
            jnp.array([7], dtype=jnp.uint8),
            jnp.bool_(True),
        )
    assert bool(ov.overflow)
    assert not bool(ok)


def test_overlay_vmap_lanes(simple_mem):
    """Each lane's overlay is independent under vmap."""
    n = 4
    ov = overlay_init(n, 4)
    gpas = jnp.array([3 * PAGE_SIZE, 5 * PAGE_SIZE, 6 * PAGE_SIZE, 3 * PAGE_SIZE], dtype=jnp.uint64)
    vals = jnp.arange(n, dtype=jnp.uint8)[:, None]

    def write_one(ov_lane, gpa, val):
        # under vmap the planes are unmapped: the lane's words come back
        # as a commit, applied for every lane at once outside the vmap
        return scatter_span(simple_mem.image, ov_lane, gpa, gpa, val,
                            jnp.bool_(True))

    view, oks, commit = jax.vmap(
        write_one, in_axes=(LANE_AXES, 0, 0),
        out_axes=(LANE_AXES, 0, 0))(lanes(ov), gpas, vals)
    ov2 = commit_stores(resident(view), commit)
    assert bool(jnp.all(oks))

    def read_one(ov_lane, gpa):
        return phys_read(simple_mem.image, ov_lane, gpa, 1)

    out = jax.vmap(read_one, in_axes=(LANE_AXES, 0))(lanes(ov2), gpas)
    assert list(np.asarray(out[:, 0])) == [0, 1, 2, 3]


@pytest.fixture(scope="module")
def paged_guest():
    b = SyntheticSnapshotBuilder()
    b.write(0x140000000, b"CODEPAGE" * 512)           # 4 KiB at an exe-like GVA
    b.write(0x7FFE0000, bytes([0x11] * 32))           # another mapping
    b.map_discontiguous_pair(0x200000000)             # crossing test region
    b.write(0x200000000 + PAGE_SIZE - 4, b"ABCDEFGH", map_if_needed=False)
    pages, cpu = b.build(rip=0x140000000, rsp=0x7FFE0F00)
    return PhysMem.from_pages(pages), cpu


def test_translate_4k(paged_guest):
    mem, cpu = paged_guest
    ov = _lane(overlay_init(1, 4))
    tr = translate(mem.image, ov, jnp.uint64(cpu.cr3), jnp.uint64(0x140000123))
    assert bool(tr.ok)
    data = phys_read(mem.image, ov, tr.gpa, 5)
    assert bytes(np.asarray(data)) == (b"CODEPAGE" * 512)[0x123:0x128]


def test_translate_unmapped(paged_guest):
    mem, cpu = paged_guest
    ov = _lane(overlay_init(1, 4))
    tr = translate(mem.image, ov, jnp.uint64(cpu.cr3), jnp.uint64(0xDEADBEEF000))
    assert not bool(tr.ok)
    # non-canonical
    tr = translate(mem.image, ov, jnp.uint64(cpu.cr3), jnp.uint64(0x8000_0000_0000))
    assert not bool(tr.ok)


def test_translate_large_page():
    b = SyntheticSnapshotBuilder()
    b.write(0x1000000, b"X" * 16)  # force PML4/PDPT/PD creation nearby
    b.add_large_page_mapping(0x1200000, 0x400000, 21)  # 2 MiB page GVA->GPA
    pages, cpu = b.build()
    pages[0x400000 >> 12] = b"\xCC" * PAGE_SIZE
    mem = PhysMem.from_pages(pages)
    ov = _lane(overlay_init(1, 4))
    tr = translate(mem.image, ov, jnp.uint64(cpu.cr3), jnp.uint64(0x1200000 + 0x1234))
    assert bool(tr.ok)
    assert int(tr.gpa) == 0x400000 + 0x1234


def test_virt_read_write_roundtrip(paged_guest):
    mem, cpu = paged_guest
    ov = _lane(overlay_init(1, 8))
    cr3 = jnp.uint64(cpu.cr3)
    gva = jnp.uint64(0x7FFE0000)
    ov, fault = virt_write(
        mem.image, ov, cr3, gva, jnp.asarray(list(b"hello!"), dtype=jnp.uint8), jnp.bool_(True)
    )
    assert not bool(fault)
    data, fault = virt_read(mem.image, ov, cr3, gva, 6)
    assert not bool(fault)
    assert bytes(np.asarray(data)) == b"hello!"


def test_virt_crossing_discontiguous_phys(paged_guest):
    """Virtually contiguous pages map to non-adjacent frames; reads and
    writes must stitch the two spans correctly."""
    mem, cpu = paged_guest
    ov = _lane(overlay_init(1, 8))
    cr3 = jnp.uint64(cpu.cr3)
    gva = jnp.uint64(0x200000000 + PAGE_SIZE - 4)
    data, fault = virt_read(mem.image, ov, cr3, gva, 8)
    assert not bool(fault)
    assert bytes(np.asarray(data)) == b"ABCDEFGH"

    ov, fault = virt_write(
        mem.image, ov, cr3, gva, jnp.asarray(list(b"12345678"), dtype=jnp.uint8), jnp.bool_(True)
    )
    assert not bool(fault)
    assert int(ov.count) == 2
    data, _ = virt_read(mem.image, ov, cr3, gva, 8)
    assert bytes(np.asarray(data)) == b"12345678"


def test_virt_read_u64(paged_guest):
    mem, cpu = paged_guest
    ov = _lane(overlay_init(1, 4))
    val, fault = virt_read_u64(
        mem.image, ov, jnp.uint64(cpu.cr3), jnp.uint64(0x140000000)
    )
    assert not bool(fault)
    import struct

    assert int(val) == struct.unpack("<Q", b"CODEPAGE")[0]


def test_virt_fault_on_unmapped(paged_guest):
    mem, cpu = paged_guest
    ov = _lane(overlay_init(1, 4))
    data, fault = virt_read(
        mem.image, ov, jnp.uint64(cpu.cr3), jnp.uint64(0x666000), 4
    )
    assert bool(fault)
    ov, fault = virt_write(
        mem.image,
        ov,
        jnp.uint64(cpu.cr3),
        jnp.uint64(0x666000),
        jnp.array([1], dtype=jnp.uint8),
        jnp.bool_(True),
    )
    assert bool(fault)
    assert int(ov.count) == 0  # faulting write allocated nothing


def test_virt_write_readonly_enforcement():
    b = SyntheticSnapshotBuilder()
    b.map(0x5000000, PAGE_SIZE, writable=False)
    b.write(0x5000000, b"RO" * 8, map_if_needed=False)
    pages, cpu = b.build()
    mem = PhysMem.from_pages(pages)
    ov = _lane(overlay_init(1, 4))
    cr3 = jnp.uint64(cpu.cr3)
    vals = jnp.asarray(list(b"XX"), dtype=jnp.uint8)
    # Guest-store path faults on the read-only mapping...
    ov, fault = virt_write(mem.image, ov, cr3, jnp.uint64(0x5000000), vals,
                           jnp.bool_(True), enforce_writable=True)
    assert bool(fault)
    # ...but the host path writes through protection (reference VirtWrite
    # semantics, backend.cc:91-127).
    ov, fault = virt_write(mem.image, ov, cr3, jnp.uint64(0x5000000), vals,
                           jnp.bool_(True))
    assert not bool(fault)
    data, _ = virt_read(mem.image, ov, cr3, jnp.uint64(0x5000000), 2)
    assert bytes(np.asarray(data)) == b"XX"


def test_translate_vec_matches_host_walk(paged_guest):
    """The device's vectorized walk agrees with the independent host-side
    Python walk (runner.HostView.translate) for mapped, unmapped, and
    non-canonical addresses — the two implementations must never diverge
    (crash triage compares their results)."""
    from wtf_tpu.mem.paging import translate_vec

    mem, cpu = paged_guest
    ov = _lane(overlay_init(1, 8))
    gvas = [
        0x140000000, 0x140000123, 0x140000FFF,   # code page
        0x7FFE0000, 0x7FFE001F,                  # data page
        0x200000000 + PAGE_SIZE - 4,             # crossing pair, 1st page
        0x200000000 + PAGE_SIZE,                 # crossing pair, 2nd page
        0x1234,                                  # unmapped low
        0xDEAD00000000,                          # unmapped high
        0x8000_0000_0000,                        # non-canonical
    ]
    t = translate_vec(mem.image, ov, jnp.uint64(cpu.cr3),
                      jnp.asarray(gvas, dtype=jnp.uint64))

    # independent reference: pure-python 4-level walk over the page dict
    import wtf_tpu.interp.runner as R

    class _FakeView:
        def __init__(self):
            self.r = {"cr3": np.asarray([np.uint64(cpu.cr3)])}

        def phys_read(self, lane, gpa, size):
            out = bytearray()
            for i in range(size):
                a = gpa + i
                page = np.asarray(mem.image.pages[
                    int(mem.image.frame_table[0, a >> 12])]).tobytes()
                out.append(page[a & 0xFFF])
            return bytes(out)

    fv = _FakeView()
    for i, gva in enumerate(gvas):
        try:
            gpa = R.HostView.translate(fv, 0, gva)
            assert bool(t.ok[i]), hex(gva)
            assert int(t.gpa[i]) == gpa, hex(gva)
        except R.HostFault:
            assert not bool(t.ok[i]), hex(gva)


def test_load_windows3_vec_matches_bytes(paged_guest):
    """Batched window loads return the same bytes as the byte-granular
    compatibility path, including across a discontiguous page crossing
    and through a dirty overlay page."""
    from wtf_tpu.mem.overlay import extract_pair, load_windows3_vec
    from wtf_tpu.mem.paging import translate_vec, virt_read

    mem, cpu = paged_guest
    ov = _lane(overlay_init(1, 8))
    # dirty one page so a window reads through the overlay
    ov, fault = virt_write(mem.image, ov, jnp.uint64(cpu.cr3),
                           jnp.uint64(0x7FFE0005),
                           jnp.asarray(list(b"overlaid!"), dtype=jnp.uint8),
                           jnp.bool_(True))
    assert not bool(fault)
    starts = [0x140000000, 0x140000803,          # aligned / unaligned code
              0x7FFE0003,                        # through the dirty page
              0x200000000 + PAGE_SIZE - 4]       # discontiguous crossing
    firsts = jnp.asarray(starts, dtype=jnp.uint64)
    lasts = firsts + jnp.uint64(15)
    tf = translate_vec(mem.image, ov, jnp.uint64(cpu.cr3), firsts)
    tl = translate_vec(mem.image, ov, jnp.uint64(cpu.cr3), lasts)
    w0, w1, w2 = load_windows3_vec(mem.image, ov, tf.gpa, tl.gpa)
    lo, hi = extract_pair(w0, w1, w2, tf.gpa)
    for i, start in enumerate(starts):
        expect, fault = virt_read(mem.image, ov, jnp.uint64(cpu.cr3),
                                  jnp.uint64(start), 16)
        assert not bool(fault)
        got = int(lo[i]).to_bytes(8, "little") + int(hi[i]).to_bytes(8, "little")
        assert got == bytes(np.asarray(expect)), hex(start)


# ---------------------------------------------------------------------------
# the hot store: store_window3 (+ ensure_page), the twin of the load test
# ---------------------------------------------------------------------------

class _T:
    """Translation-like: store_window3 reads only `.gpa`."""

    def __init__(self, gpa):
        self.gpa = gpa


_PFN_A, _PFN_NEXT, _PFN_FAR, _PFN_B = 3, 4, 9, 11
_PLACEMENTS = ("mid", "end", "far")     # in-page / contiguous / discontiguous
_PROBES = (_PFN_A * PAGE_SIZE + 0x100 - 16,      # around the "mid" store
           (_PFN_A + 1) * PAGE_SIZE - 48,        # the last bytes of page A
           _PFN_NEXT * PAGE_SIZE,                # the first bytes of A + 1
           _PFN_FAR * PAGE_SIZE)                 # ... and of the far frame
_PROBE_LEN = 64


@pytest.fixture(scope="module")
def store_mem():
    rng = np.random.default_rng(26)
    return PhysMem.from_pages({
        pfn: rng.integers(0, 256, PAGE_SIZE, dtype=np.uint8).tobytes()
        for pfn in (_PFN_A, _PFN_NEXT, _PFN_FAR, _PFN_B)})


def _span(placement, align, size):
    """(gpa of the first byte, gpa of the last byte) of one store."""
    if placement == "mid":
        first = _PFN_A * PAGE_SIZE + 0x100 + align
        return first, first + size - 1
    first = (_PFN_A + 1) * PAGE_SIZE - 8 + align
    over = align + size - 8            # bytes past the end of page A
    if over <= 0 or placement == "end":
        return first, first + size - 1
    return first, _PFN_FAR * PAGE_SIZE + over - 1


def _emu_store(emu, first, last, data):
    """The oracle's memory takes the same store: bytes up to the end of
    the first page at `first`, the rest ending at `last`."""
    head = min(len(data), PAGE_SIZE - (first & (PAGE_SIZE - 1)))
    emu.phys_write(first, data[:head])
    if head < len(data):
        emu.phys_write(last - (len(data) - head) + 1, data[head:])


def _store_fns(image):
    """(batched store, per-lane store, batched probe read, batched window
    load) over THIS layout of the overlay — the only part of the test that
    knows how lanes are held."""
    from wtf_tpu.mem.overlay import (
        extract_pair, load_windows3_vec, store_window3)

    def store_one(ov, first, last, size, lo, hi, en):
        return store_window3(image, ov, _T(first), _T(last), size, lo, hi,
                             en)

    @jax.jit
    def store_lanes(ov, *args):
        view, ok, commit, spill = jax.vmap(
            store_one, in_axes=(LANE_AXES,) + (0,) * 6,
            out_axes=(LANE_AXES, 0, 0, 0))(lanes(ov), *args)
        return commit_spills(
            image, commit_stores(resident(view), commit), spill), ok

    @jax.jit
    def store_jit(ov, *args):
        one, ok, commit, spill = store_one(ov, *args)
        return commit_spills(image, commit_stores(one, commit), spill), ok

    def store_each(ov, *args):
        oks = []
        for i in range(ov.pfn.shape[0]):
            one, ok = store_jit(_lane(ov, i), *(a[i] for a in args))
            ov = _merge_lane(ov, one, i)
            oks.append(ok)
        return ov, jnp.stack(oks)

    def probe_one(ov):
        return jnp.stack([phys_read(image, ov, jnp.uint64(p), _PROBE_LEN)
                          for p in _PROBES])

    def load_one(ov, first, last):
        w0, w1, w2 = load_windows3_vec(image, ov, first[None], last[None])
        lo, hi = extract_pair(w0, w1, w2, first[None])
        return lo[0], hi[0]

    def probe(ov):
        return jax.vmap(probe_one, in_axes=(LANE_AXES,))(lanes(ov))

    def load(ov, first, last):
        return jax.vmap(load_one, in_axes=(LANE_AXES, 0, 0))(
            lanes(ov), first, last)

    return store_lanes, store_each, jax.jit(probe), jax.jit(load)


@pytest.fixture(scope="module")
def store_fns(store_mem):
    return _store_fns(store_mem.image)


def _expect_probes(physmem, stores):
    """What the oracle's memory reads at the probes after `stores`
    ((first, last, bytes) in order)."""
    from wtf_tpu.cpu.emu import EmuMem

    emu = EmuMem(physmem)
    for first, last, data in stores:
        _emu_store(emu, first, last, data)
    return [emu.phys_read(p, _PROBE_LEN) for p in _PROBES]


def _args(spans, sizes, values, enabled):
    return (jnp.asarray([s[0] for s in spans], jnp.uint64),
            jnp.asarray([s[1] for s in spans], jnp.uint64),
            jnp.asarray(sizes, jnp.int32),
            jnp.asarray([int.from_bytes(v[:8], "little") for v in values],
                        jnp.uint64),
            jnp.asarray([int.from_bytes(v[8:], "little") for v in values],
                        jnp.uint64),
            jnp.asarray(enabled, bool))


@pytest.mark.parametrize("case", list(range(1, 17)) + ["full", "reset"])
def test_store_window3_matches_emu_memory(store_mem, store_fns, case):
    """The hot store against the oracle's memory (cpu/emu.py EmuMem) and
    against itself lane by lane: every size 1-16 at every alignment 0-7,
    inside a page, across a contiguous and a discontiguous page boundary;
    disabled lanes write nothing; `load_windows3_vec` reads back what was
    stored; a full overlay sets `overflow` and ok=False and writes
    nothing; a store after `overlay_reset` never reads stale `valid`."""
    store_lanes, store_each, probe, load = store_fns
    rng = np.random.default_rng(1000 + (case if isinstance(case, int) else 0))

    if case == "full":
        # one row per lane: lane 0 stores twice to page A (fits), lane 1
        # to A then B (full), lane 2 across A | A+1 (second claim fails)
        ov = overlay_init(3, 1)
        val = bytes(range(1, 17))
        a = _PFN_A * PAGE_SIZE + 0x100
        first = _args([(a, a + 15)] * 2 + [_span("end", 4, 16)],
                      [16] * 3, [val] * 3, [True] * 3)
        ov, ok = store_lanes(ov, *first)
        assert list(np.asarray(ok)) == [True, True, False]
        b = _PFN_B * PAGE_SIZE + 8
        second = _args([(a + 32, a + 47), (b, b + 15), (a, a + 15)],
                       [16] * 3, [val] * 3, [True, True, False])
        ov, ok = store_lanes(ov, *second)
        assert list(np.asarray(ok)[:2]) == [True, False]
        assert list(np.asarray(ov.overflow)) == [False, True, True]
        assert list(np.asarray(ov.count)) == [1, 1, 1]
        got = np.asarray(probe(ov))
        want0 = _expect_probes(store_mem, [(a, a + 15, val),
                                           (a + 32, a + 47, val)])
        want1 = _expect_probes(store_mem, [(a, a + 15, val)])
        want2 = _expect_probes(store_mem, [])
        for lane_i, want in enumerate((want0, want1, want2)):
            for j, w in enumerate(want):
                assert bytes(got[lane_i, j]) == w, (lane_i, j)
        return

    if case == "reset":
        ov = overlay_init(2, 2)
        a = _PFN_A * PAGE_SIZE + 0x100
        val = bytes(rng.integers(0, 256, 16, dtype=np.uint8))
        ov, ok = store_lanes(ov, *_args([(a, a + 15)] * 2, [16] * 2,
                                        [val] * 2, [True] * 2))
        assert bool(jnp.all(ok))
        ov = overlay_reset(ov)
        got = np.asarray(probe(ov))
        for j, w in enumerate(_expect_probes(store_mem, [])):
            assert bytes(got[0, j]) == w
        # the same row is claimed again for the same page: of the words
        # stored before the reset only the one rewritten now is overlaid
        ov, ok = store_lanes(ov, *_args([(a + 8, a + 8)] * 2, [1] * 2,
                                        [b"\x5a" + bytes(15)] * 2,
                                        [True, False]))
        assert list(np.asarray(ov.count)) == [1, 0]
        got = np.asarray(probe(ov))
        want = (_expect_probes(store_mem, [(a + 8, a + 8, b"\x5a")]),
                _expect_probes(store_mem, []))
        for lane_i in range(2):
            for j, w in enumerate(want[lane_i]):
                assert bytes(got[lane_i, j]) == w, (lane_i, j)
        return

    size = case
    lanes = [(p, al, en) for p in _PLACEMENTS for al in range(8)
             for en in (True, False)]
    spans = [_span(p, al, size) for p, al, _ in lanes]
    values = [bytes(rng.integers(0, 256, 16, dtype=np.uint8))
              for _ in lanes]
    enabled = [en for _, _, en in lanes]
    args = _args(spans, [size] * len(lanes),
                 [v[:size] + bytes(16 - size) for v in values], enabled)

    ov0 = overlay_init(len(lanes), 4)
    ov, ok = store_lanes(ov0, *args)
    ov_each, ok_each = store_each(ov0, *args)

    en = np.asarray(enabled)
    assert bool(np.all(np.asarray(ok)[en]))
    # batched == lane by lane: index state, verdicts, and what memory reads
    for name in ("pfn", "count", "overflow"):
        assert np.array_equal(np.asarray(getattr(ov, name)),
                              np.asarray(getattr(ov_each, name))), name
    assert np.array_equal(np.asarray(ok), np.asarray(ok_each))
    got = np.asarray(probe(ov))
    assert np.array_equal(got, np.asarray(probe(ov_each)))
    # disabled lanes claimed nothing
    assert np.all(np.asarray(ov.pfn)[~en] == -1)
    assert np.all(np.asarray(ov.count)[~en] == 0)
    assert not np.any(np.asarray(ov.overflow))

    lo, hi = load(ov, args[0], args[1])
    for i, (span, val) in enumerate(zip(spans, values)):
        stores = [(span[0], span[1], val[:size])] if enabled[i] else []
        for j, w in enumerate(_expect_probes(store_mem, stores)):
            assert bytes(got[i, j]) == w, (lanes[i], j)
        if enabled[i]:
            back = (int(lo[i]).to_bytes(8, "little")
                    + int(hi[i]).to_bytes(8, "little"))
            assert back[:size] == val[:size], lanes[i]


# ---------------------------------------------------------------------------
# the store's third word (PR 44): `store_window3` commits two words a pass
# and hands the third to `commit_spills`, which touches the planes only
# when a lane in hand spills.  Against a byte-wise reference.
# ---------------------------------------------------------------------------

_STATES = ("valid", "claimed", "fresh")


def _spill_span(placement, off, size):
    """A store of `size` bytes whose first byte has `gpa & 15 == off`:
    inside page A, or over its last sixteen bytes into A + 1 ("end") or
    into the far frame ("far")."""
    if placement == "mid":
        first = _PFN_A * PAGE_SIZE + 0x100 + off
        return first, first + size - 1
    first = (_PFN_A + 1) * PAGE_SIZE - 16 + off
    over = off + size - 16             # bytes past the end of page A
    if over <= 0 or placement == "end":
        return first, first + size - 1
    return first, _PFN_FAR * PAGE_SIZE + over - 1


def _byte_gpas(first, last, size):
    """The physical address of every byte of a span, one by one."""
    head = min(size, PAGE_SIZE - (first & (PAGE_SIZE - 1)))
    return ([first + i for i in range(head)]
            + [last - (size - 1 - i) for i in range(head, size)])


def _window_words(first, last, size):
    """(gpa, gpa + 7) of the three aligned words of a store's window, the
    second page's taken where the store's own bytes lie."""
    gpas = _byte_gpas(first, last, size)
    next_page = ((last if last >> 12 != first >> 12 else first + PAGE_SIZE)
                 & ~(PAGE_SIZE - 1))
    words = []
    for k in range(3):
        at = (first & ~7) + 8 * k
        if at >> 12 != first >> 12:
            at = next_page + (at & (PAGE_SIZE - 1))
        words.append((at, at + 7))
    assert all(any(w[0] <= g <= w[1] for w in words) for g in gpas)
    return words


class _ByteRef:
    """Guest memory byte by byte: the base pages, and what was stored."""

    def __init__(self, physmem):
        from wtf_tpu.cpu.emu import EmuMem

        base = EmuMem(physmem)
        self.base = {pfn: base.phys_read(pfn * PAGE_SIZE, PAGE_SIZE)
                     for pfn in (_PFN_A, _PFN_NEXT, _PFN_FAR, _PFN_B)}

    def lane(self):
        return {}

    def store(self, mem, first, last, data):
        for gpa, byte in zip(_byte_gpas(first, last, len(data)), data):
            mem[gpa] = byte

    def read(self, mem, gpa, size):
        return bytes(
            mem.get(g, self.base[g >> 12][g & (PAGE_SIZE - 1)])
            for g in range(gpa, gpa + size))


@pytest.fixture(scope="module")
def byte_ref(store_mem):
    return _ByteRef(store_mem)


@pytest.fixture(scope="module")
def spill_flags(store_mem):
    """`spill` of every lane's `StoreSpill`, by `store_window3` alone."""
    from wtf_tpu.mem.overlay import store_window3

    @jax.jit
    def flags(ov, first, last, size, lo, hi, en):
        def one(ov, first, last, size, lo, hi, en):
            return store_window3(store_mem.image, ov, _T(first), _T(last),
                                 size, lo, hi, en)[3].spill

        return jax.vmap(one, in_axes=(LANE_AXES,) + (0,) * 6)(
            lanes(ov), first, last, size, lo, hi, en)

    return flags


@pytest.mark.parametrize("size", range(1, 17))
def test_store_spill_matches_bytewise_reference(store_mem, store_fns,
                                                byte_ref, spill_flags, size):
    """Sizes 1-16 at every `gpa & 15`, inside a page and over a page's end
    into the next frame and into a far one, into words that are valid
    already, into a claimed row's words that are not, and into rows this
    store claims: what memory reads afterwards is what a byte-wise store
    leaves, a lane spills exactly when `(gpa & 7) + size > 16`, and a
    batch in which no lane does (every size up to 9) takes the
    conditional's other side."""
    store_lanes, _, probe, _ = store_fns
    rng = np.random.default_rng(4400 + size)
    cases = [(p, off, state) for p in _PLACEMENTS for off in range(16)
             for state in _STATES]
    spans = [_spill_span(p, off, size) for p, off, _ in cases]
    mems = [byte_ref.lane() for _ in cases]
    ov = overlay_init(len(cases), 4)

    def store_all(stores):
        """One batched store: stores[i] = (first, last, bytes) or None."""
        nonlocal ov
        live = [s is not None for s in stores]
        stores = [s or (spans[0][0], spans[0][0], b"\0") for s in stores]
        args = _args([(f, l) for f, l, _ in stores],
                     [len(d) for _, _, d in stores],
                     [d + bytes(16 - len(d)) for _, _, d in stores], live)
        ov, ok = store_lanes(ov, *args)
        assert bool(np.all(np.asarray(ok)[np.asarray(live)]))
        for mem, (f, l, d), on in zip(mems, stores, live):
            if on:
                byte_ref.store(mem, f, l, d)
        return args

    # the states: every word of the window stored whole beforehand; or one
    # byte elsewhere in each page, so that the rows are claimed and the
    # window's words are not valid; or nothing
    for k in range(3):
        store_all([
            (*_window_words(*span, size)[k],
             bytes(rng.integers(0, 256, 8, dtype=np.uint8)))
            if state == "valid" else None
            for span, (_, _, state) in zip(spans, cases)])
    for which in (0, 1):
        store_all([
            ((span[which] & ~(PAGE_SIZE - 1)) + 0x800,) * 2 + (b"\xa5",)
            if state == "claimed" else None
            for span, (_, _, state) in zip(spans, cases)])

    values = [bytes(rng.integers(0, 256, size, dtype=np.uint8))
              for _ in cases]
    the_store = [(f, l, v) for (f, l), v in zip(spans, values)]
    before = np.asarray(ov.count)
    args = store_all(the_store)
    claimed = np.asarray(ov.count) - before
    spilled = np.asarray(spill_flags(ov, *args))
    got = np.asarray(probe(ov))
    for i, ((placement, off, state), (first, last)) in enumerate(
            zip(cases, spans)):
        assert bool(spilled[i]) == ((first & 7) + size > 16), cases[i]
        pages = 1 + (last >> 12 != first >> 12)
        assert claimed[i] == (pages if state == "fresh" else 0), cases[i]
        for j, at in enumerate(_PROBES):
            assert bytes(got[i, j]) == byte_ref.read(
                mems[i], at, _PROBE_LEN), (cases[i], hex(at))
    assert bool(spilled.any()) == (size > 9)
