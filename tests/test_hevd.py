"""The `hevd-faults` cell's deployment (demo_hevd: HEVD's ioctl table under
`__try/__except`, a real IDT/GDT/TSS, guest fault handlers that end in an
`iretq` or in a bugcheck) held to the EmuCpu oracle on the CPU, lane by
lane: every handler, every ending, the dump at 64 MiB, the seeds a
campaign starts from, and equal seeds giving equal campaigns.  The oracle
(wtf_tpu/cpu/emu.py) shares no code with the device step; exception
delivery (cpu/interrupts.py) it does share, and tests/test_step_iret.py
holds that to frames built by hand."""

import contextlib
import hashlib
import io
import json
import pathlib
import re
import shutil
import struct

import numpy as np
import pytest

from wtf_tpu.backend import create_backend
from wtf_tpu.core.results import Crash, Ok, StatusCode
from wtf_tpu.harness import demo_hevd as dh
from wtf_tpu.harness import demo_hevd_dump

LANES = 16
LIMIT = 100_000
q = struct.Struct("<Q").pack
d = struct.Struct("<I").pack

WILD = 0x0000_1337_DEAD_1EEF            # canonical, user half, not mapped
NONCANON = 0x4141_4141_4141_4141
KERNEL_HOLE = dh.KSTACK - 8             # the guard under the kernel stack
B = dh.BUFFER_SIZE

# name -> (testcase, result's name or None for Ok, rax when it ends Ok,
#          exceptions the guest has to be handed at least)
ENDINGS = {
    "invalid-code": (dh.request(0x222004, b"zz"), None,
                     dh.STATUS_INVALID_DEVICE_REQUEST, 0),
    "unknown-code": (dh.request(0x22203B), None,
                     dh.STATUS_INVALID_DEVICE_REQUEST, 0),
    "short-testcase": (b"\x03", None, dh.STATUS_INVALID_DEVICE_REQUEST, 0),
    "bad-size": (dh.request(dh.IOCTL_DOUBLE_FETCH,
                            q(dh.USER_DATA) + q(B + 1)), None,
                 dh.STATUS_INVALID_BUFFER_SIZE, 0),
    # a #PF in ring 0 that the handler's __except turns into a status
    "seh-null-deref": (dh.request(dh.IOCTL_NULL_POINTER, d(0)), None,
                       dh.STATUS_ACCESS_VIOLATION, 1),
    "seh-short-structure": (dh.request(dh.IOCTL_ALLOCATE_FAKE, b"x" * 8),
                            None, dh.STATUS_ACCESS_VIOLATION, 1),
    "seh-copy-size-lies": (dh.request(dh.IOCTL_STACK_OVERFLOW,
                                      d(33) + b"A" * 32), None,
                           dh.STATUS_ACCESS_VIOLATION, 1),
    "seh-pool-copy-size-lies": (dh.request(dh.IOCTL_POOL_OVERFLOW,
                                           d(0xFFFFFFFF) + b"C" * 8), None,
                                dh.STATUS_ACCESS_VIOLATION, 1),
    "seh-size-field-lies": (dh.request(dh.IOCTL_INTEGER_OVERFLOW,
                                       d(B - 4) + b"E" * 8), None,
                            dh.STATUS_ACCESS_VIOLATION, 1),
    "seh-empty-payload": (dh.request(dh.IOCTL_TYPE_CONFUSION), None,
                          dh.STATUS_ACCESS_VIOLATION, 1),
    "seh-wild-user-pointer": (dh.request(dh.IOCTL_DOUBLE_FETCH,
                                         q(WILD) + q(8)), None,
                              dh.STATUS_ACCESS_VIOLATION, 1),
    "seh-write-to-user-hole": (dh.request(
        dh.IOCTL_ARBITRARY_WRITE, q(dh.USER_DATA) + q(WILD)), None,
        dh.STATUS_ACCESS_VIOLATION, 1),
    # #GP on a pointer that is not canonical
    "gp-noncanonical-read": (dh.request(
        dh.IOCTL_ARBITRARY_WRITE, q(NONCANON) + q(dh.USER_DATA)), None,
        dh.STATUS_ACCESS_VIOLATION, 1),
    "gp-noncanonical-write": (dh.request(
        dh.IOCTL_ARBITRARY_WRITE, q(dh.USER_DATA) + q(1 << 47)), None,
        dh.STATUS_ACCESS_VIOLATION, 1),
    # a handler finds the damage itself
    "bugcheck-cookie": (dh.request(dh.IOCTL_STACK_OVERFLOW_GS,
                                   d(B + 8) + b"B" * (B + 8)),
                        "crash-bugcheck-0xf7-0x4242424242424242", None, 0),
    "bugcheck-pool-header": (dh.request(dh.IOCTL_POOL_OVERFLOW,
                                        d(B + 1) + b"C" * (B + 1)),
                             "crash-bugcheck-0x19-0x20", None, 0),
    "bugcheck-double-free": (dh.request(dh.IOCTL_FREE_UAF),
                             "crash-bugcheck-0xc2-0x7", None, 0),
    # the fault path finds nothing to resume
    "bugcheck-return-into-wild-code": (dh.request(
        dh.IOCTL_STACK_OVERFLOW, d(B + 8) + b"A" * B + q(WILD)),
        f"crash-bugcheck-0x50-{WILD:#x}", None, 1),
    "bugcheck-return-not-canonical": (dh.request(
        dh.IOCTL_STACK_OVERFLOW, d(B + 8) + b"A" * (B + 8)),
        "crash-bugcheck-0x1e-0xc0000005", None, 1),
    "bugcheck-use-after-free": (dh.request(dh.IOCTL_USE_UAF),
                                "crash-bugcheck-0x1e-0xc0000005", None, 1),
    "bugcheck-write-to-kernel-hole": (dh.request(
        dh.IOCTL_ARBITRARY_WRITE, q(dh.USER_DATA) + q(KERNEL_HOLE)),
        f"crash-bugcheck-0x50-{KERNEL_HOLE:#x}", None, 1),
    # a type taken for an object whose table the user built: the call
    # goes where the user says
    "bugcheck-type-confusion": (dh.request(
        dh.IOCTL_TYPE_CONFUSION, q(1) + q(dh.USER_BUF_END - 0x18)
        + q(dh.USER_BUF_END - 0x10) + q(0) + q(WILD)),
        f"crash-bugcheck-0x50-{WILD:#x}", None, 1),
    "bugcheck-stale-stack-object": (dh.request(
        dh.IOCTL_UNINIT_STACK, d(0) + q(dh.USER_BUF_END - 0x18)
        + q(dh.USER_BUF_END - 0x10) + q(0) + q(NONCANON)),
        "crash-bugcheck-0x1e-0xc0000005", None, 1),
    "bugcheck-terminator-never-comes": (dh.request(
        dh.IOCTL_INTEGER_OVERFLOW, d(0xFFFFFFFF) + b"E" * (B + 0x20)),
        "crash-bugcheck-0x1e-0xc0000005", None, 2),
    # the same bugs with a pointer that leads nowhere: a fault the
    # __except takes
    "seh-type-confusion": (dh.request(
        dh.IOCTL_TYPE_CONFUSION, q(1) + q(WILD)), None,
        dh.STATUS_ACCESS_VIOLATION, 1),
    "seh-type-confusion-table-of-int3": (dh.request(
        dh.IOCTL_TYPE_CONFUSION, q(1) + q(dh.USER_DATA)), None,
        dh.STATUS_ACCESS_VIOLATION, 1),
    "seh-stale-stack-object": (dh.request(
        dh.IOCTL_UNINIT_STACK, d(0) + q(NONCANON)), None,
        dh.STATUS_ACCESS_VIOLATION, 1),
    "seh-stale-pool-object": (dh.request(dh.IOCTL_UNINIT_POOL, d(0)), None,
                              dh.STATUS_ACCESS_VIOLATION, 1),
    # sysretq to a smashed user rip: the #PF comes from ring 3, through
    # TSS.RSP0, and the handler has to swapgs
    "bugcheck-sysret-into-wild-code": (dh.request(
        dh.IOCTL_STACK_OVERFLOW,
        d(B + 24) + b"A" * B + q(dh.K_EXIT) + q(0x202) + q(WILD)),
        f"crash-bugcheck-0x50-{WILD:#x}", None, 1),
    "stale-stack-callback-null": (dh.request(
        dh.IOCTL_UNINIT_STACK, d(0) + q(0)), None,
        dh.STATUS_SUCCESS, 0),
    "fake-object-allocated": (dh.request(
        dh.IOCTL_ALLOCATE_FAKE, q(WILD) + b"D" * 0x30), None,
        dh.STATUS_SUCCESS, 0),
}
SEEDS = {f"seed-{int.from_bytes(s[:4], 'little'):#x}": s
         for s in dh.seeds()}
# a callback that is the driver's own entry: swapgs gives the fault path
# the user's gs, it faults on its first `gs:` access, again and again,
# until the frame no longer fits the kernel stack: undeliverable
FAULT_IN_FAULT_PATH = dh.request(
    dh.IOCTL_TYPE_CONFUSION, q(1) + q(dh.USER_BUF_END - 0x18)
    + q(dh.USER_BUF_END - 0x10) + q(0) + q(dh.KERN_CODE))


def _backend(name, module=dh, snapshot=None, **kw):
    backend = create_backend(name, snapshot or module.build_snapshot(),
                             limit=LIMIT, **kw)
    backend.initialize()
    module.TARGET.init(backend)
    return backend


@pytest.fixture(scope="module")
def tpu():
    return _backend("tpu", n_lanes=LANES)


@pytest.fixture(scope="module")
def emu():
    return _backend("emu")


def oracle_run(emu, data: bytes) -> dict:
    """One testcase on the oracle, read before the restore."""
    dh.TARGET.insert_testcase(emu, data)
    result = emu.run()
    cpu = emu.cpu
    seen = {"result": result, "rip": cpu.rip, "gpr": list(cpu.gpr),
            "icount": cpu.icount, "cov": set(emu._run_cov),
            "cs": cpu.cs_sel, "ss": cpu.ss_sel, "rflags": cpu.rflags,
            "gs": (cpu.gs_base, cpu.kernel_gs_base),
            "dirty": {pfn: bytes(page)
                      for pfn, page in cpu.mem.overlay.items()}}
    emu.restore()
    return seen


def device_batch(tpu, testcases: list) -> list:
    """One batch on the device, every lane read before the restore; the
    runner's counters of the batch ride on lane 0's entry."""
    stats = tpu.runner.stats
    before = dict(stats)
    results = tpu.run_batch(testcases, dh.TARGET)
    view = tpu.runner.view()
    icount = np.asarray(tpu.runner.machine.icount)
    seen = []
    for lane, result in enumerate(results):
        pfns = sorted(int(p) for p in view._ov_pfn[lane] if p >= 0)
        r = view.r
        seen.append({"result": result, "rip": view.get_rip(lane),
                     "gpr": [view.get_reg(lane, i) for i in range(16)],
                     "icount": int(icount[lane]),
                     "cov": tpu.lane_coverage(lane),
                     "cs": int(r["cs"][lane]), "ss": int(r["ss"][lane]),
                     "rflags": int(r["rflags"][lane]),
                     "gs": (int(r["gs_base"][lane]),
                            int(r["kernel_gs_base"][lane])),
                     "dirty": {pfn: view.page(lane, pfn) for pfn in pfns}})
    seen[0]["stats"] = {k: stats[k] - before.get(k, 0) for k in (
        "exceptions_delivered", "exceptions_undelivered", "fallbacks")}
    tpu.restore()
    return seen


def same(device: dict, oracle: dict) -> None:
    assert type(device["result"]) is type(oracle["result"]), (device, oracle)
    if isinstance(oracle["result"], Crash):
        assert device["result"].name == oracle["result"].name
    for key in ("rip", "gpr", "icount", "cs", "ss", "rflags", "gs"):
        assert device[key] == oracle[key], key
    assert device["cov"] == oracle["cov"]
    assert sorted(device["dirty"]) == sorted(oracle["dirty"])
    for pfn, page in oracle["dirty"].items():
        assert device["dirty"][pfn] == page, f"pfn {pfn:#x}"


def _batches(cases: dict) -> list:
    names = list(cases)
    return [names[i:i + LANES] for i in range(0, len(names), LANES)]


@pytest.fixture(scope="module")
def on_device(tpu):
    """Every ending and every seed, run on the device in batches."""
    cases = {**ENDINGS, **{k: (v, ...) for k, v in SEEDS.items()}}
    seen = {}
    for names in _batches(cases):
        batch = device_batch(tpu, [cases[n][0] for n in names])
        assert batch[0]["stats"]["fallbacks"] == 0   # iretq is the device's
        seen.update(zip(names, batch))
    return seen


@pytest.mark.parametrize("name", list(ENDINGS))
def test_an_ending_matches_the_oracle_and_the_driver(on_device, emu, name):
    data, crash, rax, _ = ENDINGS[name]
    oracle = oracle_run(emu, data)
    if crash is None:
        assert isinstance(oracle["result"], Ok), oracle["result"]
        assert oracle["gpr"][0] == rax
        assert oracle["rip"] == dh.FINISH_GVA
        assert oracle["cs"] == 0x33 and oracle["ss"] == 0x2B
        assert oracle["gs"] == (0, dh.KPCR)
    else:
        assert oracle["result"].name == crash
    same(on_device[name], oracle)


@pytest.mark.parametrize("name", list(SEEDS))
def test_a_seed_matches_the_oracle(on_device, emu, name):
    oracle = oracle_run(emu, SEEDS[name])
    same(on_device[name], oracle)
    code = int(name[5:], 16)
    if code in (dh.IOCTL_USE_UAF, dh.IOCTL_FREE_UAF):
        # the snapshot's object is freed: these two are the planted bugs
        assert isinstance(oracle["result"], Crash)
    else:
        assert isinstance(oracle["result"], Ok)
        assert oracle["gpr"][0] == dh.STATUS_SUCCESS


def test_the_seeds_are_one_small_request_per_ioctl():
    seeds = dh.seeds()
    assert [int.from_bytes(s[:4], "little") for s in seeds] \
        == list(dh.IOCTLS)
    assert all(4 <= len(s) <= 64 for s in seeds)
    assert dh.TARGET.seeds is dh.seeds
    assert demo_hevd_dump.TARGET.seeds is dh.seeds


@pytest.mark.parametrize("name", [n for n, e in ENDINGS.items() if e[3]])
def test_the_guest_is_handed_its_exceptions(tpu, name):
    """Each of these endings goes through the IDT on the device path."""
    data, _, _, least = ENDINGS[name]
    seen = device_batch(tpu, [data])
    assert seen[0]["stats"]["exceptions_delivered"] >= least
    assert seen[0]["stats"]["exceptions_undelivered"] == 0
    assert seen[0]["stats"]["fallbacks"] == 0


@pytest.mark.parametrize("name,faults,transfers", [
    ("seh-null-deref", 1, 2),       # DbgPrint's return address, the frame
    ("seed-0x222013", 0, 1),        # DbgPrint's alone
    ("bugcheck-return-into-wild-code", 1, 2),
])
def test_a_round_costs_a_transfer_a_frame_not_a_round_trip_a_lane(
        tpu, name, faults, transfers):
    """Every lane's kernel stack page is dirty when DbgPrint is returned
    from and when a fault's frame is pushed on it: the page comes for all
    the lanes of a service round in ONE transfer (`HostView.round`), a
    row a lane."""
    case = (ENDINGS.get(name) or (SEEDS[name],))[0]
    stats = tpu.runner.stats
    before = dict(stats)
    tpu.run_batch([case] * LANES, dh.TARGET)
    moved = {k: stats[k] - before[k] for k in (
        "view_row_pulls", "view_rows", "exceptions_delivered",
        "bp_dispatches")}
    tpu.restore()
    assert moved == {"view_row_pulls": transfers,
                     "view_rows": transfers * LANES,
                     "exceptions_delivered": faults * LANES,
                     "bp_dispatches": 2 * LANES}


def test_a_fault_inside_the_fault_path_stays_terminal(tpu, emu):
    """No bugcheck and no handler: the lane ends on the raw fault, on
    both engines alike, after as many nested frames as the stack held."""
    oracle = oracle_run(emu, FAULT_IN_FAULT_PATH)
    device = device_batch(tpu, [FAULT_IN_FAULT_PATH])
    assert isinstance(oracle["result"], Crash)
    assert not oracle["result"].name.startswith("crash-bugcheck")
    same(device[0], oracle)
    assert device[0]["stats"]["exceptions_delivered"] > 50
    assert device[0]["stats"]["exceptions_undelivered"] == 1
    status = tpu.runner.statuses()
    assert int((status == int(StatusCode.HARD_ERROR)).sum()) == 0


# -- the I/O manager's path in front of the dispatcher ---------------------

IO_PATH_CASES = ["invalid-code", "short-testcase", "bad-size",
                 "seh-null-deref", "gp-noncanonical-read",
                 "fake-object-allocated", "seed-0x222003", "seed-0x222013",
                 "seed-0x222027", "seed-0x222037"]
POOL_AT = dict(zip(dh.POOL_PAGES, (dh.POOL + i * dh.POOL_STRIDE
                                   for i in range(len(dh.POOL_PAGES)))))


def _u(emu, gva, size=8):
    return int.from_bytes(emu.virt_read(gva, size), "little")


@pytest.mark.parametrize("name", IO_PATH_CASES)
def test_every_request_goes_through_the_io_manager(emu, name):
    """Handle -> file object -> device -> driver -> MajorFunction[14], an
    IRP off the lookaside list, zeroed, and back on it, the status in the
    caller's IO_STATUS_BLOCK, the reference dropped: read on the oracle
    before the restore."""
    data = (ENDINGS.get(name) or (SEEDS[name],))[0]
    dh.TARGET.insert_testcase(emu, data)
    code, gva, length = emu.get_reg(10), emu.get_reg(6), emu.get_reg(2)
    assert isinstance(emu.run(), Ok)
    cpu = emu.cpu
    assert 130 <= cpu.icount <= 350
    assert {dh.K_IO_CONTROL, dh.K_CALL_DRIVER, dh.K_DISPATCH,
            dh.K_COMPLETE, dh.K_EXIT} <= set(emu._run_cov)
    status = cpu.gpr[0]
    assert _u(emu, dh.IOSB, 4) == status and _u(emu, dh.IOSB + 8) == 0
    irps, objects = POOL_AT["irps"], POOL_AT["objects"]
    irp = irps + dh.IRPS_OFF
    # the IRP is the list's first again, its link over its Type and Size,
    # the rest as the request left it
    assert (_u(emu, irps), _u(emu, irps + 8, 4)) == (irp, dh.N_IRPS)
    assert _u(emu, irp) == irp + dh.IRP_STRIDE
    assert _u(emu, irp + dh.IRP_STATUS_OFF, 4) == status
    assert _u(emu, irp + dh.IRP_USER_IOSB_OFF) == dh.IOSB
    assert _u(emu, irp + 0x42, 2) == 0x0101     # StackCount, CurrentLocation
    location = irp + dh.IRP_STACK_OFF
    assert _u(emu, irp + 0xB8) == location
    assert _u(emu, location, 1) == dh.IRP_MJ_DEVICE_CONTROL
    assert (_u(emu, location + 0x18, 4), _u(emu, location + 0x10, 4),
            _u(emu, location + 0x20)) == (code, length, gva)
    assert _u(emu, location + 0x28) == objects + dh.DEVICE_OBJECT_OFF
    assert _u(emu, location + 0x30) == objects + dh.FILE_OBJECT_OFF
    assert _u(emu, objects + dh.FILE_OBJECT_OFF
              - dh.OBJECT_HEADER_SIZE) == 1     # PointerCount
    emu.restore()


def _refused(emu, status):
    assert isinstance(emu.run(), Ok)
    assert emu.cpu.gpr[0] == status
    assert dh.K_DISPATCH not in set(emu._run_cov)
    emu.restore()


@pytest.mark.parametrize("handle,status", [
    (dh.FILE_HANDLE | 1, 0xC0000008),           # not a multiple of four
    (dh.HANDLES_A_PAGE * 4, 0xC0000008),        # past the table's end
    (dh.FILE_HANDLE + 4, 0xC0000008),           # an empty entry
])
def test_a_handle_that_names_nothing_is_refused(emu, handle, status):
    dh.TARGET.insert_testcase(emu, SEEDS["seed-0x222013"])
    emu.set_reg(8, handle)
    _refused(emu, status)


def test_a_handle_to_another_type_and_an_empty_lookaside_list(emu):
    objects, irps = POOL_AT["objects"], POOL_AT["irps"]
    dh.TARGET.insert_testcase(emu, SEEDS["seed-0x222013"])
    emu.virt_write(objects + dh.FILE_OBJECT_OFF, b"\x03\x00")
    _refused(emu, 0xC0000024)                   # a device, not a file
    dh.TARGET.insert_testcase(emu, SEEDS["seed-0x222013"])
    emu.virt_write(irps, bytes(8))
    _refused(emu, 0xC000009A)
    dh.TARGET.insert_testcase(emu, SEEDS["seed-0x222013"])
    assert isinstance(emu.run(), Ok) and emu.cpu.gpr[0] == 0
    emu.restore()


def test_the_handle_table_has_two_levels_and_the_objects_their_types(emu):
    handles, entries, objects = (POOL_AT[n] for n in (
        "handles", "entries", "objects"))
    table = _u(emu, dh.KPCR + dh.PCR_HANDLE_TABLE)
    assert table == handles + dh.HANDLE_TABLE_OFF
    code = _u(emu, table)
    assert code & 3 == 1 and code & ~3 == handles + dh.HANDLE_MIDDLE_OFF
    index = dh.FILE_HANDLE >> 2
    assert _u(emu, (code & ~3) + 8 * (index // dh.HANDLES_A_PAGE)) \
        == entries
    header = _u(emu, entries + 16 * (index % dh.HANDLES_A_PAGE)) & ~7
    file_object = header + dh.OBJECT_HEADER_SIZE
    assert file_object == objects + dh.FILE_OBJECT_OFF
    device = _u(emu, file_object + 8)
    driver = _u(emu, device + 8)
    assert [_u(emu, o, 2) for o in (file_object, device, driver)] \
        == [dh.IO_TYPE_FILE, dh.IO_TYPE_DEVICE, dh.IO_TYPE_DRIVER]
    assert _u(emu, driver + dh.MAJOR_FUNCTION_OFF
              + 8 * dh.IRP_MJ_DEVICE_CONTROL) == dh.K_DISPATCH
    assert _u(emu, dh.KPCR + dh.PCR_IRP_LOOKASIDE) == POOL_AT["irps"]
    assert dh.IRP_SIZE <= dh.IRP_STRIDE
    assert dh.IRPS_OFF + dh.N_IRPS * dh.IRP_STRIDE <= 0x1000


def test_the_io_path_is_plain_integer_code():
    """No string forms and no division between the entry and the first
    handler: S11's islands are not this path's business."""
    path = dh._KERN_ASM.split("k_entry:")[1].split("k_try:")[0]
    words = set(re.findall(r"[a-z]+", "\n".join(
        line.split("#")[0] for line in path.splitlines())))
    assert not words & {"rep", "repe", "repne", "movsb", "movsq", "stosb",
                        "stosq", "div", "idiv", "lock"}
    assert {"swapgs", "sysretq", "push", "pop", "call", "ret"} <= words


def test_a_request_is_as_long_as_the_configuration_says(emu):
    """benchmark/configs/hevd-fulldump.json `reduced`: a refused code
    retires 136 instructions, a seed through its handler 133-295."""
    counts = {}
    for name, data in {**SEEDS, "refused": ENDINGS["invalid-code"][0]}.items():
        dh.TARGET.insert_testcase(emu, data)
        emu.run()
        counts[name] = emu.cpu.icount
        emu.restore()
    refused = counts.pop("refused")
    assert refused == 136
    assert min(counts.values()) >= 133 and max(counts.values()) <= 295
    mean = sum(counts.values()) / len(counts)
    assert 150 <= mean <= 200
    # the campaign's mutants lie between the two: half are refused at once
    config = json.loads((pathlib.Path(__file__).parent.parent / "benchmark"
                         / "configs" / "hevd-fulldump.json").read_text())
    assert refused < config["request_path_instructions"] < mean
    assert list(config["reduced"]) == ["request_path_instructions"]


def test_the_payload_ends_at_the_end_of_its_page(emu):
    dh.TARGET.insert_testcase(emu, dh.request(dh.IOCTL_POOL_OVERFLOW,
                                              b"0123456789"))
    assert emu.get_reg(10) == dh.IOCTL_POOL_OVERFLOW
    assert emu.get_reg(6) == dh.USER_BUF_END - 10 and emu.get_reg(2) == 10
    assert emu.virt_read(dh.USER_BUF_END - 10, 10) == b"0123456789"
    with pytest.raises(Exception):
        emu.virt_read(dh.USER_BUF_END, 1)
    emu.restore()


def test_the_kernel_half_is_supervisor_only_and_has_its_tables(emu):
    snapshot = dh.build_snapshot()
    cpu, phys_read = snapshot.cpu, snapshot.physmem.host_read
    assert (cpu.idtr.base, cpu.gdtr.base, cpu.tr.base) \
        == (dh.KIDT, dh.KGDT, dh.KTSS)
    assert int.from_bytes(emu.virt_read(dh.KTSS + 4, 8), "little") \
        == dh.KSTACK_TOP
    gate = emu.virt_read(dh.KIDT + 14 * 16, 16)
    assert gate == dh._gate(dh.K_PF) and gate[5] == 0x8E
    tss = emu.virt_read(dh.KGDT + 0x40, 16)
    base = tss[2] | tss[3] << 8 | tss[4] << 16 | tss[7] << 24 \
        | int.from_bytes(tss[8:12], "little") << 32
    assert base == dh.KTSS

    def leaf(gva):
        table = cpu.cr3 & ~0xFFF
        for shift in (39, 30, 21, 12):
            entry = int.from_bytes(phys_read(
                table + ((gva >> shift) & 0x1FF) * 8, 8), "little")
            table = entry & 0x000F_FFFF_FFFF_F000
        return entry

    for gva in (dh.KERN_CODE, dh.KSTACK, dh.KPCR, dh.KIDT,
                *POOL_AT.values()):
        assert leaf(gva) & 5 == 1, hex(gva)          # present, not user
    for gva in (dh.USER_CODE, dh.USER_BUF, dh.USER_DATA):
        assert leaf(gva) & 5 == 5, hex(gva)


def handlers():
    """The fourteen addresses of the dispatcher's table, from the code."""
    at = dh.K_TABLE - dh.KERN_CODE
    return struct.unpack_from("<14Q", dh._KERN_CODE, at)


def test_the_dispatch_table_lies_in_the_code_and_nothing_is_paged_out(emu):
    """HEVD's dispatcher is a switch: its table is part of the driver's
    image, mapped with it by the page table that maps its neighbours, and
    a request that runs to its end takes no exception at all."""
    table = handlers()
    assert len(set(table)) == 14 and list(table) == sorted(table)
    assert all(dh.KERN_CODE < h < dh.K_PF for h in table)
    assert emu.virt_read(dh.K_TABLE, 8 * 14) \
        == dh._KERN_CODE[dh.K_TABLE - dh.KERN_CODE:][:8 * 14]
    assert dh.K_TABLE >> 12 == dh.KERN_CODE >> 12
    assert not hasattr(dh, "KDATA")


def test_labels_point_at_their_code():
    code = dh._KERN_CODE
    at = lambda gva, n: code[gva - dh.KERN_CODE:gva - dh.KERN_CODE + n]
    assert at(dh.K_DBGPRINT, 2) == b"\x90\xc3"
    assert at(dh.K_CALLBACK, 1) == b"\xc3"
    assert at(dh.K_BUGCHECK, 2) == b"\x90\xf4"
    assert at(dh.K_EXCEPT, 2) == b"\x65\x8b"          # mov eax, gs:[0x20]
    assert at(dh.K_EXIT, 2) == b"\x41\x5b"            # pop r11
    assert at(dh.K_IO_CONTROL, 3) == b"\x4c\x89\xc0"  # mov rax, r8
    assert at(dh.K_CALL_DRIVER, 4) == bytes.fromhex("41fe4b43")
    assert at(dh.K_DISPATCH, 4) == bytes.fromhex("4d8b93b8")
    assert at(dh.K_COMPLETE, 4) == bytes.fromhex("41894330")
    for handler in (dh.K_PF, dh.K_GP):
        assert at(handler, 5) == bytes.fromhex("f644241003")
    assert at(dh.K_DE, 5) == bytes.fromhex("f644240803")
    assert code.count(b"\x48\xcf") == 2               # two iretq
    k_try = dh.K_EXCEPT - dh.KERN_CODE - 0x1F
    assert code[k_try:k_try + 3] == bytes.fromhex("488d05")   # lea rax, ..
    for handler in handlers():                        # each: call k_try
        off = handler - dh.KERN_CODE
        assert code[off] == 0xE8
        assert off + 5 + int.from_bytes(code[off + 1:off + 5], "little",
                                        signed=True) == k_try


@pytest.mark.skipif(shutil.which("as") is None, reason="binutils missing")
def test_embedded_hex_matches_sources():
    from asmhelper import assemble

    def clean(src):
        return "\n".join(line.split("#")[0] for line in src.splitlines())

    assert assemble(clean(dh._USER_ASM)) == dh._USER_CODE
    assert assemble(clean(dh._KERN_ASM)) == dh._KERN_CODE


# -- the guest inside a dump (64 MiB here, 4 GiB in the cell) --------------

DUMP_CASES = ["seh-null-deref", "gp-noncanonical-read", "bugcheck-cookie",
              "bugcheck-pool-header", "bugcheck-return-into-wild-code",
              "bugcheck-use-after-free", "bugcheck-sysret-into-wild-code",
              "seed-0x222013", "seed-0x22201f", "seed-0x222027",
              "seed-0x222037"]


@pytest.fixture(scope="module")
def dump():
    return demo_hevd_dump.build_snapshot(64 << 20)


@pytest.fixture(scope="module")
def dump_on_device(dump):
    tpu = _backend("tpu", demo_hevd_dump, snapshot=dump, n_lanes=LANES)
    cases = {**ENDINGS, **{k: (v,) for k, v in SEEDS.items()}}
    return dict(zip(DUMP_CASES, device_batch(
        tpu, [cases[n][0] for n in DUMP_CASES])))


@pytest.fixture(scope="module")
def dump_emu(dump):
    return _backend("emu", demo_hevd_dump, snapshot=dump)


@pytest.mark.parametrize("name", DUMP_CASES)
def test_in_the_dump_the_device_matches_the_oracle(dump_on_device, dump_emu,
                                                   emu, name):
    cases = {**ENDINGS, **{k: (v,) for k, v in SEEDS.items()}}
    oracle = oracle_run(dump_emu, cases[name][0])
    same(dump_on_device[name], oracle)
    # and the dump's guest ends as the small one does
    small = oracle_run(emu, cases[name][0])
    assert type(oracle["result"]) is type(small["result"])
    assert oracle["gpr"][0] == small["gpr"][0]
    assert oracle["icount"] == small["icount"]


def test_the_dump_holds_the_guest_and_the_pool_where_it_says(dump):
    from wtf_tpu.snapshot.synthetic import KERNEL_BASE, RamLayout

    layout = RamLayout.of_bytes(64 << 20)
    mem = dump.physmem
    assert mem.frames_present == layout.frames
    emu = _backend("emu", demo_hevd_dump, snapshot=dump)
    pcr = emu.virt_read(dh.KPCR, 0x60)
    chunk, fresh = (int.from_bytes(pcr[o:o + 8], "little") for o in (
        dh.PCR_POOL_CHUNK, dh.PCR_FRESH_CHUNK))
    n_large = -(-layout.span // 512) // demo_hevd_dump.LARGE_SHARE
    # the chunk lies where the direct map uses 2-MiB pages, the fresh
    # chunks behind 4-KiB tables; both read back what pool_pages() wrote,
    # the driver's object with its own address in it
    assert KERNEL_BASE <= chunk < KERNEL_BASE + n_large * (2 << 20)
    assert fresh >= KERNEL_BASE + n_large * (2 << 20)
    assert emu.virt_read(chunk + B, 8) \
        == dh.POOL_HEADER.to_bytes(8, "little")
    assert emu.virt_read(fresh + dh.STALE_OFF + 8, 8) \
        == dh.FREED_FILL.to_bytes(8, "little")
    assert emu.virt_read(fresh + dh.OBJECT_OFF, 8) \
        == (fresh + dh.OBJECT_OFF + 0x10).to_bytes(8, "little")

    def levels(gva):
        table, n = dump.cpu.cr3 & ~0xFFF, 0
        for shift in (39, 30, 21, 12):
            entry = int.from_bytes(mem.host_read(
                table + ((gva >> shift) & 0x1FF) * 8, 8), "little")
            n += 1
            if entry & 0x80 and shift in (30, 21):
                break
            table = entry & 0x000F_FFFF_FFFF_F000
        return n

    assert levels(chunk) == 3 and levels(fresh) == 4
    assert levels(dh.KERN_CODE) == 4
    # every request's IRP lies under a 2-MiB mapping, the handle table
    # and the objects behind 4-KiB tables
    irps, handles = (int.from_bytes(pcr[o:o + 8], "little") for o in (
        dh.PCR_IRP_LOOKASIDE, dh.PCR_HANDLE_TABLE))
    assert levels(irps) == 3 and levels(handles) == 4
    assert KERNEL_BASE <= irps < KERNEL_BASE + n_large * (2 << 20)


def test_the_dump_is_one_dump_whatever_the_run():
    a = demo_hevd_dump.build_snapshot(64 << 20)
    b = demo_hevd_dump.build_snapshot(64 << 20)
    assert hashlib.sha256(a.physmem.rows.tobytes()).digest() \
        == hashlib.sha256(b.physmem.rows.tobytes()).digest()
    assert a.cpu.cr3 == b.cpu.cr3


# -- the campaign ----------------------------------------------------------

def _campaign(tmp, seed):
    from wtf_tpu import cli

    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        cli.main(["campaign", "--name", "demo_hevd", "--backend", "tpu",
                  "--lanes", "8", "--limit", str(LIMIT), "--max_len",
                  "1028", "--seed", str(seed), "--runs", "160",
                  "--target", str(tmp), "--telemetry-dir",
                  str(tmp / "telem")])
    files = {sub: {p.name: p.read_bytes()
                   for p in sorted((tmp / sub).iterdir())}
             for sub in ("outputs", "crashes")}
    line = out.getvalue().strip().splitlines()[-1]
    # the clock's part of the line is not the campaign's
    return re.sub(r" (exec/s|uptime): \S+", "", line), files


def test_equal_seeds_give_equal_campaigns_from_the_targets_seeds(tmp_path):
    """inputs/ is empty (as the benchmark makes it): the campaign starts
    from the target's fourteen seeds, and twice the same."""
    from wtf_tpu.telemetry.events import read_events
    from wtf_tpu.utils.hashing import hex_digest

    runs = []
    for name in ("a", "b"):
        (tmp_path / name / "inputs").mkdir(parents=True)
        runs.append(_campaign(tmp_path / name, 7))
    assert runs[0] == runs[1]
    line, files = runs[0]
    assert line.startswith("#160 cov: ") and "crash: " in line
    assert {hex_digest(s) for s in dh.seeds()} <= set(files["outputs"])
    # the seeds themselves are run first, ahead of the mutator's: the two
    # that meet the freed object crash, and the oracle is shown nothing
    # the campaign did not run
    assert len(files["outputs"]) > 14
    assert {"crash-bugcheck-0x1e-0xc0000005", "crash-bugcheck-0xc2-0x7"} \
        <= set(files["crashes"])
    last = [e for e in read_events(tmp_path / "a" / "telem" / "events.jsonl")
            if e.get("type") == "heartbeat"][-1]["metrics"]
    assert last["runner.exceptions_delivered"] \
        == sum(last["runner.exceptions_by_vector"].values()) > 0
    assert set(last["runner.exceptions_by_vector"]) <= {"#DE", "#GP", "#PF"}
    assert last["runner.exceptions_undelivered"] == 0
    assert last["runner.fallbacks"] == 0
    assert last["phase.seconds"]["execute/service-exception"] > 0
    # pushes, an IRP's fields, trap frames: no store wider than 8 bytes
    assert last["device.store3_steps"] == 0 < last["device.store_steps"]


def test_a_file_in_inputs_wins_over_the_targets_seeds(tmp_path):
    (tmp_path / "inputs").mkdir()
    (tmp_path / "inputs" / "only").write_bytes(
        dh.request(dh.IOCTL_NULL_POINTER, d(dh.MAGIC)))
    _, files = _campaign(tmp_path, 7)
    from wtf_tpu.utils.hashing import hex_digest

    assert hex_digest(dh.seeds()[0]) not in files["outputs"]


def test_files_in_inputs_are_run_before_anything_is_mutated(tmp_path):
    """ONE path for what a corpus starts with: an inputs/ file stands in
    outputs/, so the campaign runs it as it is, first.  This one meets the
    freed object: its crash is the first under its name, and the name's
    file holds its bytes, not a mutant's."""
    from wtf_tpu.telemetry.events import read_events

    (tmp_path / "inputs").mkdir()
    crasher = dh.request(dh.IOCTL_USE_UAF, b"left as it is")
    (tmp_path / "inputs" / "use-after-free").write_bytes(crasher)
    _, files = _campaign(tmp_path, 7)
    assert files["crashes"]["crash-bugcheck-0x1e-0xc0000005"] == crasher
    first = next(e for e in read_events(tmp_path / "telem" / "events.jsonl")
                 if e.get("type") == "crash")
    assert first["name"] == "crash-bugcheck-0x1e-0xc0000005"
    assert first["size"] == len(crasher) and first["new"]
