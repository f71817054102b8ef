"""The main path's programs compile for a TPU v5e — from this sandbox, with
no chip: the TPU compiler is installed here and compiles for a chip that
is DESCRIBED, not attached (`topologies.get_topology_desc`).  A compile
that passes is not a chip run and says nothing about results or times;
what it guards is every later PR against the refusals PR 22 found (the
f64 -> u64 bitcast in the step, a u64 psum on the mesh).

Shapes are chip_smoke.py's: demo_tlv, 1024 lanes, the campaign's default
runner (256-step base chunk, 128 overlay slots); the chunk program takes
its image operand at the shapes of a 4-GiB full dump (demo_tlv_dump, the
`big-image` cell's: 1,052,672 page rows), so that what is pinned is the
program a deployment runs.  Compile time is set by
the step body (~75-90 s per program that contains it, whatever its
shape), so ONE tier-1 case contains `step_lane`; the window, the
four-chip mesh chunk and the four-chip mesh window carry the `slow` mark
and are run by hand before a chip call
(`pytest tests/test_chip_compile.py -m slow`, about nine minutes).

Everything that touches the topology lives in fixtures of THIS file (one
process loads libtpu; see the on-chip-measurement guide, section 2), and
the persistent compile cache is off around each compile: an executable
for a described chip is written to it but can never be read back.
"""

import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

LANES = 1024
HBM_BYTES = 16 * 2 ** 30    # one v5e chip


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def compile_for():
    """compile_for(fn, args, shardings, **jit_kwargs): `fn` jitted and
    compiled on the shapes of `args` (arrays, or shapes alone) placed by
    `shardings` (one sharding for every leaf, or a pytree prefix of
    `args`), cache off around this file's tests."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    def run(fn, args, shardings, **jit_kwargs):
        def spec(sharding, subtree):
            return jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(
                    np.shape(x), jnp.result_type(x), sharding=sharding),
                subtree)

        specs = jax.tree.map(
            spec, shardings, args,
            is_leaf=lambda s: isinstance(s, jax.sharding.Sharding))
        return jax.jit(fn, **jit_kwargs).lower(*specs).compile()

    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield run
    jax.config.update("jax_enable_compilation_cache", True)
    cc.reset_cache()


def _campaign(lanes, megachunk=0, **backend_kwargs):
    """The device-resident demo_tlv campaign chip_smoke.py runs, built on
    the CPU at the real width (nothing is dispatched here)."""
    from wtf_tpu.analysis.trace import build_tlv_campaign

    return build_tlv_campaign(n_lanes=lanes, mutator="devmangle",
                              limit=100_000, max_len=1024,
                              megachunk=megachunk, device_decode=True,
                              **backend_kwargs)


@pytest.fixture(scope="module")
def loop():
    return _campaign(LANES)


_RELAYOUT_OPCODES = ("copy", "reshape", "transpose", "select", "concatenate")
_HLO_COMPUTATION = re.compile(r"^(ENTRY )?%?([\w.\-]+) \(.*\) -> .* \{$")
_HLO_INSTRUCTION = re.compile(
    r"^\s*(?:ROOT )?%?[\w.\-]+ = (.*?) ([a-z][\w\-]*)\(")
_HLO_ARRAY = re.compile(r"\b[a-z]+\d+\[([\d,]*)\]")
_HLO_CALLEE = re.compile(r"(?:body|condition|calls|to_apply)=%?([\w.\-]+)")
_HLO_BRANCHES = re.compile(r"branch_computations=\{([^}]*)\}")
_HLO_OPERANDS = re.compile(r"%([\w.\-]+)")


def _computations(hlo_text):
    """{name: lines} of every computation of an HLO module, and the entry
    computation's name."""
    comps, entry, name = {}, None, None
    for line in hlo_text.splitlines():
        head = _HLO_COMPUTATION.match(line)
        if head:
            name = head.group(2)
            comps[name] = []
            entry = name if head.group(1) else entry
        elif line.startswith("}"):
            name = None
        elif name is not None:
            comps[name].append(line)
    return comps, entry


def _branches(line):
    """The computations a conditional's line names as its sides."""
    return [name.strip().lstrip("%")
            for sides in _HLO_BRANCHES.findall(line)
            for name in sides.split(",")]


def _reached(comps, root):
    """Lines of every computation `root` reaches (conditions, nested
    loops, fusions and so their roots), itself included."""
    todo, seen = [root], set()
    while todo:
        comp = todo.pop()
        if comp in seen:
            continue
        seen.add(comp)
        for line in comps[comp]:
            todo += _HLO_CALLEE.findall(line) + _branches(line)
            yield line


def entry_loop_bodies(hlo_text):
    """(computations, the bodies of the entry computation's while loops)."""
    comps, entry = _computations(hlo_text)
    bodies = [body for line in comps[entry] if " while(" in line
              for body in re.findall(r"body=%?([\w.\-]+)", line)]
    assert bodies, "the entry computation starts no while loop"
    return comps, bodies


def slab_relayouts(hlo_text, n_elems):
    """Instructions inside the entry computation's while loops (every
    computation a loop body reaches) whose opcode is a relayout or a copy
    and whose result holds `n_elems` elements or more.  The loop carry
    itself is a parameter / get-tuple-element / tuple / bitcast and never
    listed."""
    comps, bodies = entry_loop_bodies(hlo_text)
    found = []
    for body in bodies:
        for line in _reached(comps, body):
            ins = _HLO_INSTRUCTION.match(line)
            if not ins or ins.group(2) not in _RELAYOUT_OPCODES:
                continue
            sizes = [int(np.prod([int(d) for d in dims.split(",") if d]))
                     for dims in _HLO_ARRAY.findall(ins.group(1))]
            if sizes and max(sizes) >= n_elems:
                found.append(line.strip()[:200])
    return found


def sweeping_loops(hlo_text):
    """The entry computation's while loops that sweep: their body reaches
    the overlay's commit scatter (named scope `overlay-store`)."""
    comps, bodies = entry_loop_bodies(hlo_text)
    return [body for body in bodies
            if any("overlay-store" in line for line in _reached(comps, body))]


def dump_image_shapes():
    """The image operand of a campaign on demo_tlv_dump at the
    configuration's 4 GiB, as shapes: nothing of that size is built."""
    from wtf_tpu.harness import demo_tlv_dump
    from wtf_tpu.mem import physmem
    from wtf_tpu.snapshot.synthetic import RamLayout

    layout = RamLayout.of_bytes(demo_tlv_dump.RAM_BYTES)
    return physmem.MemImage(
        pages=jax.ShapeDtypeStruct(
            (physmem.padded(layout.frames + 1, physmem.ROW_GRANULE),
             2 * physmem.PAGE_WORDS), jnp.uint32),
        frame_table=jax.ShapeDtypeStruct(
            (1, physmem.padded(layout.span, physmem.SPAN_GRANULE)),
            jnp.int32))


@pytest.fixture(scope="module")
def chunk_program(loop, one_chip, compile_for):
    """(compiled, operands) of the step ladder's chunk program, as the
    chip traces and donates it, over a 4-GiB dump's image: ONE compile of
    the step for the tests that pin it."""
    from wtf_tpu.interp.step import make_run_chunk

    runner = loop.backend.runner
    tab, _, machine, limit = runner.executor_operands()
    operands = (tab, dump_image_shapes(), machine, limit)
    compiled = compile_for(
        make_run_chunk(runner.chunk_steps, donate=False, jit=False,
                       fp_host=True),
        operands, one_chip, donate_argnums=(2,))
    return compiled, operands


def test_chunk_program_compiles_for_v5e(chunk_program):
    """The step ladder's chunk program, as the chip traces and donates it
    (float-valued uops hosted: fp_host=True; the machine donated; at
    1024 lanes the sweeps go over groups of 64 lanes, sixteen down to one),
    fits the chip, holds the step ONCE (one loop nest, one body at the
    group's width), and a sweep moves no overlay plane: inside the loops
    no copy / reshape / transpose / select / concatenate is as large as
    one plane (ROADMAP S14: the parent of PR 26 relayouted the slab
    around the store's scatter in every sweep; the planes ride through
    the levels, the sweeps and the groups as in-place carries), every
    donated machine leaf is aliased to its output through the sort of the
    rows and their scatter back, and the program's temporaries stay under
    one data plane (PR 26's parent held the relayouted planes and the u64
    plane's two u32 halves there)."""
    from wtf_tpu.interp import step

    compiled, operands = chunk_program
    mem = compiled.memory_analysis()
    assert (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes) < HBM_BYTES, mem

    ov = operands[2].overlay
    plane_words = ov.valid.size            # lanes * slots * PAGE_WORDS
    assert plane_words == LANES * ov.pfn.shape[1] * 512
    text = compiled.as_text()
    width = step.tail_width(LANES)
    assert width == LANES // step.TAIL_SHARE == 64
    assert len(sweeping_loops(text)) == 1, "one loop nest"
    # the step is held once: every operation of the commit scatter sits
    # under one path of loops, and works on one group of lanes
    sites = set(re.findall(r'op_name="([^"]*?)/vmap\(overlay-store\)', text))
    assert len(sites) == 1, sites
    assert f"u32[{width},{operands[2].edge.shape[1]}]" in text
    found = slab_relayouts(text, plane_words)
    assert not found, "\n".join(found)
    machine_bytes = sum(leaf.nbytes for leaf in jax.tree.leaves(operands[2]))
    assert mem.alias_size_in_bytes >= machine_bytes, mem
    assert mem.temp_size_in_bytes < ov.data.nbytes, mem


def indices_by_scope(hlo_text, within):
    """{scope: [gathered, scattered]}: the index vectors of every gather
    and scatter whose `op_name` holds `within`, by the innermost scope of
    THE list (`telemetry.xprof.scope_of`).  An operation's indices are
    its second operand, less the dimension that holds one index's
    coordinates: what a sweep is priced by (PERF.md section 5)."""
    from wtf_tpu.telemetry.xprof import scope_of

    counts, shapes = {}, {}
    for line in hlo_text.splitlines():
        if _HLO_COMPUTATION.match(line):
            shapes = {}
        ins = _HLO_INSTRUCTION.match(line)
        if not ins:
            continue
        name = line.split("=")[0].replace("ROOT", "").strip().lstrip("%")
        dims = _HLO_ARRAY.findall(ins.group(1))
        if dims:
            shapes[name] = [int(d) for d in dims[0].split(",") if d]
        if ins.group(2) not in ("gather", "scatter"):
            continue
        op_name = re.search(r'op_name="([^"]*)"', line).group(1)
        if within not in op_name:
            continue
        operands = _HLO_OPERANDS.findall(line[ins.end():].split(")")[0])
        index = shapes[operands[1]]
        vector_dim = int(re.search(r"index_vector_dim=(\d+)", line).group(1))
        n = int(np.prod(index)) // (
            index[vector_dim] if vector_dim < len(index) else 1)
        row = counts.setdefault(scope_of(op_name), [0, 0])
        row[ins.group(2) == "scatter"] += n
    return counts


def test_the_store_commits_two_words_a_pass_and_spills_under_one_conditional(
        chunk_program):
    """PR 44: the per-pass store reads, merges and commits TWO words; the
    third word of its 16-byte window stands under the sweep's ONE
    conditional (`store-spill`).  By the chip's compile, a lane-step's
    `overlay-store` gathers 16 indices where it gathered 21 (a word is
    five: its valid byte, the overlay word's two limbs, the image word's
    two; the frame table's two and the row claims' four stay) and
    scatters 8 where it scattered 11 (the commit's 6 where 9 were, the
    row claims' two), and `store-spill` holds the third word's 5 and 3
    on its taken side alone.  The conditional copies no plane: its other
    side is the planes it was handed and nothing else, neither side nor
    the loop around them holds a copy or relayout as large as a plane
    (`slab_relayouts` follows a conditional's sides), so both alias the
    planes as the sweep's loops do."""
    from wtf_tpu.interp import step

    compiled, operands = chunk_program
    text = compiled.as_text()
    width = step.tail_width(LANES)
    sweep = "while/body/while/body"         # levels / sweeps / groups
    counts = indices_by_scope(text, sweep)
    assert counts["overlay-store"] == [16 * width, 8 * width], counts
    assert counts["store-spill"] == [5 * width, 3 * width], counts
    # what EVERY pass indexes: the parent's 149 gathered and 15 scattered
    # less the third word's
    every_pass = [sum(row[k] for scope, row in counts.items()
                      if scope != "store-spill") for k in (0, 1)]
    assert every_pass == [144 * width, 12 * width], counts

    comps, _ = entry_loop_bodies(text)
    conditionals = [line for lines in comps.values() for line in lines
                    if " conditional(" in line]
    assert len(conditionals) == 1 and "store-spill" in conditionals[0]
    sides = [comps[name] for name in _branches(conditionals[0])]
    assert len(sides) == 2
    handed_on = [side for side in sides if all(
        _HLO_INSTRUCTION.match(line).group(2)
        in ("parameter", "get-tuple-element", "tuple")
        for line in side if _HLO_INSTRUCTION.match(line))]
    assert len(handed_on) == 1, "one side hands the planes on"
    spilled = "\n".join(line for side in sides for line in side)
    assert "branch_1_fun/scatter" in spilled
    plane_words = operands[2].overlay.valid.size
    assert not slab_relayouts(text, plane_words)


def test_the_vector_unit_indexes_nothing_on_the_chip(chunk_program):
    """The chip's compile of the chunk program holds no gather, scatter or
    dynamic slice under the vector unit's scopes (PR 41: its 25 gathers
    and 4 scatters were 309 us of a 1,260-us group sweep; the XMM file and
    the x87 stack are read and written by selects over their rows); the
    unit's scopes still stand in the sweep's body."""
    from wtf_tpu.analysis.rules import vector_unit_indexed_ops

    compiled, _ = chunk_program
    text = compiled.as_text()
    assert vector_unit_indexed_ops(text) == []
    comps, _ = entry_loop_bodies(text)
    (body,) = sweeping_loops(text)
    swept = "\n".join(_reached(comps, body))
    assert "vmap(sse-bytes)" in swept and "vmap(vector-file)" in swept


def test_chunk_program_makes_nothing_as_large_as_the_image(chunk_program):
    """The image is gathered from and never copied: over a 4-GiB dump's
    image (4.3 GB of page rows) no instruction of the chunk program, inside
    its loops or before them, has a result as large as the page rows (a
    copy, a convert or a relayout of them would be one; PR 34's parent
    split the uint64 rows into two uint32 planes at the head of every
    dispatch, 4.4 GB of temporaries), the argument is counted once, and
    the program's temporaries stay under a twentieth of it."""
    compiled, operands = chunk_program
    pages = operands[1].pages
    image_bytes = int(np.prod(pages.shape)) * 4
    assert image_bytes > 4 * 2 ** 30
    text = compiled.as_text()
    assert "image_pages" in text        # the operand is there by name
    found = plane_sized_results(text, int(np.prod(pages.shape)) // 2)
    assert not found, "\n".join(found)
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < image_bytes // 20, mem
    assert mem.argument_size_in_bytes < image_bytes + HBM_BYTES // 8, mem


def plane_sized_results(hlo_text, n_elems):
    """Instructions of any computation whose result holds `n_elems`
    elements or more, but for what hands an operand on by reference:
    parameter, bitcast, tuple plumbing, and a while loop's carry (the
    TPU compiler serves a gather of whole rows as a loop over them that
    carries the plane it reads)."""
    found = []
    for line in hlo_text.splitlines():
        ins = _HLO_INSTRUCTION.match(line)
        if not ins or ins.group(2) in ("parameter", "bitcast", "tuple",
                                       "get-tuple-element", "while"):
            continue
        sizes = [int(np.prod([int(d) for d in dims.split(",") if d]))
                 for dims in _HLO_ARRAY.findall(ins.group(1))]
        if sizes and max(sizes) >= n_elems:
            found.append(line.strip()[:200])
    return found


@pytest.mark.parametrize("chips", [1, 4])
def test_lane_row_reader_compiles_for_v5e_and_moves_no_plane(
        loop, topo, compile_for, chips):
    """`overlay.read_lane_rows` (HostView.prefetch's device half) at the
    campaign's slab, on one chip and per shard on four: it gathers one
    row per lane out of the resident planes and nothing else.  No
    instruction's result is as large as a (shard's) plane, so no copy or
    relayout of the slab; no collective, so no all-gather of lane-sharded
    planes; temporaries stay within a few times what it returns."""
    from wtf_tpu.mem import overlay as ovl
    from wtf_tpu.meshrun.mesh import LANE_AXIS
    from wtf_tpu.meshrun.runner import _mesh_read_lane_rows

    ov = loop.backend.runner.machine.overlay
    rows = np.zeros(LANES, np.int32)
    if chips == 1:
        fn, placed = ovl.read_lane_rows, SingleDeviceSharding(
            topo.devices[0])
    else:
        mesh = Mesh(np.array(topo.devices[:chips]), (LANE_AXIS,))
        fn, placed = _mesh_read_lane_rows(mesh), NamedSharding(
            mesh, P(LANE_AXIS))
    compiled = compile_for(fn, (ov, rows), placed)
    text = compiled.as_text()
    for collective in ("all-gather", "all-reduce", "all-to-all",
                       "collective-permute"):
        assert collective not in text, collective
    found = plane_sized_results(text, ov.valid.size // chips)
    assert not found, "\n".join(found)
    mem = compiled.memory_analysis()
    returned = LANES // chips * (4096 + 512)
    assert mem.output_size_in_bytes >= returned, mem
    assert mem.temp_size_in_bytes < 4 * returned, mem


def test_step_source_has_no_float_to_int_bitcast():
    """The one direction the TPU's 64-bit rewrite refuses stays out of
    the step: no bitcast in step.py targets a 64-bit integer (f32 <-> u32
    is a 32-bit move and accepted)."""
    import inspect
    import re

    from wtf_tpu.interp import step

    targets = re.findall(r"bitcast_convert_type\(\s*[^,]+,\s*jnp\.(\w+)",
                         inspect.getsource(step))
    assert targets and not {"uint64", "int64"} & set(targets), targets


def test_decode_service_compiles_for_v5e(loop, one_chip, compile_for):
    from wtf_tpu.analysis.rules import decode_service_program

    fn, args = decode_service_program(loop.backend.runner)
    compile_for(fn, args, one_chip)


def test_devmut_generate_compiles_for_v5e(loop, one_chip, compile_for):
    from wtf_tpu.devmut import hostref
    from wtf_tpu.devmut.engine import generate

    mut = loop.mutator
    data, lens, cumw, _ = mut.corpus.arrays()
    seeds = hostref.lane_seeds(mut.seed, 0, LANES)
    compile_for(lambda *a: generate(*a, rounds=mut.rounds),
                (data, lens, cumw, seeds), one_chip)


def test_device_insert_compiles_for_v5e(loop, one_chip, compile_for):
    from wtf_tpu.interp.runner import device_insert_impl

    mut = loop.mutator
    words = np.zeros((LANES, mut.max_len // 4), np.uint32)
    compile_for(
        device_insert_impl(len(mut.pfns), mut.spec.len_gpr,
                           mut.spec.ptr_gpr),
        (loop.backend.runner.machine, words, np.zeros(LANES, np.int32),
         np.asarray(mut.pfns, np.int32), np.zeros(2, np.uint32)),
        one_chip)


class _Captured(Exception):
    pass


def _window_dispatch(loop):
    """(callable-seam arguments, dispatch operands) of the first
    megachunk window `loop` would dispatch: the runner's callable seam is
    spied on and the dispatch aborted once its operands are in hand."""
    runner = loop.backend.runner
    real, seen = runner.megachunk_callable, {}

    def spy(*a, **k):
        seen["fn"], seen["seam_args"] = real(*a, **k), a

        def grab(*operands):
            raise _Captured(operands)
        return grab

    runner.megachunk_callable = spy
    with pytest.raises(_Captured) as caught:
        loop.fuzz(runs=runner.n_lanes * 8)
    return seen["fn"], seen["seam_args"], caught.value.args[0]


@pytest.mark.slow
def test_window_program_compiles_for_v5e(one_chip, compile_for):
    """The megachunk window on the operands the backend really
    dispatches."""
    fn, _, operands = _window_dispatch(_campaign(LANES, megachunk=8))
    mem = compile_for(fn, operands, one_chip).memory_analysis()
    assert (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes) < HBM_BYTES, mem


@pytest.mark.slow
def test_mesh_window_compiles_for_four_v5e(topo, compile_for):
    """The shard_map window on four described chips, 512 lanes each,
    with each operand placed as the mesh backend places it on the
    conftest's virtual CPU mesh.  PR 22 found a u64 psum here that the
    TPU's 64-bit rewrite refuses (`Supported lowering only of Sum all
    reduce`)."""
    from wtf_tpu.fuzz.megachunk import make_mesh_megachunk
    from wtf_tpu.meshrun.mesh import LANE_AXIS

    loop = _campaign(4 * 512, megachunk=8, mesh_devices=4)
    runner = loop.backend.runner
    _, seam_args, operands = _window_dispatch(loop)
    mesh = Mesh(np.array(topo.devices[:4]), (LANE_AXIS,))
    placed = jax.tree.map(
        lambda x: NamedSharding(mesh, x.sharding.spec if isinstance(
            getattr(x, "sharding", None), NamedSharding) else P()),
        operands)
    fn = make_mesh_megachunk(*seam_args, deliver=runner.deliver_exceptions,
                             mesh=mesh, devdec=True, donate=False)
    compile_for(fn, operands, placed)


@pytest.mark.slow
def test_mesh_chunk_compiles_for_four_v5e(topo, compile_for):
    """make_mesh_chunk on a Mesh of four described chips at the shape of
    the deployment `hevd-fulldump-mesh4`: 16,384 lanes, 4,096 a chip, the
    campaign's default runner (128 overlay slots: a data plane of 2**31
    limbs, 2**29 a chip), the image at the size of the 4-GiB dump on every
    chip.  One all-reduce, the lane planes split and not parked on the
    first chip, and every chip's bytes under its 16 GB.  The machine's
    shapes are a four-lane runner's with the lane axis scaled: nothing of
    that size is built here."""
    from wtf_tpu.harness import demo_hevd
    from wtf_tpu.interp.runner import Runner
    from wtf_tpu.mem.physmem import (
        ROW_GRANULE, SPAN_GRANULE, MemImage, padded,
    )
    from wtf_tpu.meshrun.executor import make_mesh_chunk
    from wtf_tpu.meshrun.mesh import LANE_AXIS

    lanes = 4 * 4096
    small = Runner(demo_hevd.build_snapshot(), 4)
    mesh = Mesh(np.array(topo.devices[:4]), (LANE_AXIS,))
    rep, split = NamedSharding(mesh, P()), NamedSharding(mesh, P(LANE_AXIS))
    machine = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(
            (x.shape[0] * lanes // 4,) + x.shape[1:], x.dtype),
        small.machine)
    image = MemImage(
        pages=jax.ShapeDtypeStruct(
            (padded((1 << 20) + 1, ROW_GRANULE), 1024), jnp.uint32),
        frame_table=jax.ShapeDtypeStruct(
            (1, padded(0x108000, SPAN_GRANULE)), jnp.int32))
    assert machine.overlay.data.shape == (1 << 31,)
    compiled = compile_for(
        make_mesh_chunk(256, mesh, jit=False),
        (small.cache.device(), image, machine, jnp.uint64(0)),
        (rep, rep, split, rep), donate_argnums=(2,))
    assert compiled.as_text().count("all-reduce(") + \
        compiled.as_text().count("all-reduce-start(") == 1
    mem = compiled.memory_analysis()
    whole = sum(int(np.prod(x.shape)) * x.dtype.itemsize
                for x in jax.tree.leaves(machine))
    shared = sum(int(np.prod(x.shape)) * x.dtype.itemsize
                 for x in jax.tree.leaves((small.cache.device(), image)))
    assert mem.argument_size_in_bytes < shared + whole / 4 * 1.1, mem
    assert (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes) \
        < HBM_BYTES / 2, mem
