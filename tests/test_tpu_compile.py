"""Compile the main path's kernels for a TPU v5e that is described, not
attached: Mosaic refuses here what the Pallas interpreter never checks
(block shapes off the (8, 128) tiling, scoped-VMEM overruns, operands that
cannot batch under ``vmap``).

Nothing runs: each test lowers and compiles for the described chip and
reads the HLO, which also shows the kernels' names and the phase tags on
the ops around them.  The kernels are steered to their
compiled (Mosaic) branch by patching :func:`repro.hw.pallas_interpret`,
which would otherwise pick the interpreter on this CPU backend.  The
topology is described in a fixture, never at import, so every test worker
collects the same tests and only the one that runs this file loads the TPU
compiler.
"""

import contextlib
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from repro import hw, obs
from repro.apps import (pw_advection, pw_advection_update, tracer_advection,
                        tracer_advection_update)
from repro.core import compile_program
from repro.core.dataflow import lower_to_dataflow
from repro.core.frontend import ProgramBuilder
from repro.serve import bucket_for, serving_program, wrap_update

GRID_8M = (256, 256, 128)
GRID_32M = (512, 256, 256)
#: the benchmark cells' own grids
CELL_GRIDS = {"pw_advection": (1024, 512, 256),
              "tracer_advection": (512, 256, 256),
              "tracer_advection_cyclic": (512, 256, 256)}
PROGRAMS = {"pw_advection": (pw_advection, lambda: pw_advection_update(0.1)),
            "tracer_advection": (tracer_advection, tracer_advection_update),
            # NEMO's east-west cyclic domain, as the benchmark states it
            "tracer_advection_cyclic": (
                lambda: tracer_advection(boundary=["periodic", "zero", "zero"]),
                tracer_advection_update)}


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described chip's executables can be written to a persistent cache
    # but never read back: keep any configured cache out of these compiles
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", enabled)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def mosaic(monkeypatch):
    """Build kernels for compiled Mosaic, as on a TPU backend."""
    monkeypatch.setattr(hw, "pallas_interpret", lambda: False)


def shapes(p, grid, sharding, batch=(), replicated=None):
    """ShapeDtypeStructs of (fields, scalars, coeffs) for ``p`` on
    ``grid``; ``replicated`` places scalars and coefficients."""
    rep = replicated or sharding

    def sds(shape, sh):
        return jax.ShapeDtypeStruct(batch + tuple(shape), jnp.float32,
                                    sharding=sh)
    return ({f: sds(grid, sharding) for f in p.input_fields()},
            {s: sds((), rep) for s in p.scalars},
            {c: sds((grid[ax],), rep) for c, ax in p.coeffs.items()})


def compile_fused(name, grid, sharding, steps=4, **opts):
    make, update = PROGRAMS[name]
    p = make()
    ex = compile_program(p, grid, steps=steps, update=update(), **opts)
    return ex, ex.lower(*shapes(p, grid, sharding)).compile()


@pytest.fixture(scope="module")
def compiled_8m(one_chip):
    """Each program's fused loop at 8M under a schedule (None: the
    default), compiled once for Mosaic, as on a TPU backend."""
    cache = {}

    def get(name, schedule):
        if (name, schedule) not in cache:
            with pytest.MonkeyPatch.context() as mp:
                mp.setattr(hw, "pallas_interpret", lambda: False)
                cache[name, schedule] = compile_fused(
                    name, GRID_8M, one_chip, schedule=schedule)
        return cache[name, schedule]
    return get


@pytest.fixture(scope="module")
def compiled_cells(one_chip):
    """Each program's fused loop at its benchmark cell's grid, under the
    default options, compiled once for Mosaic, as on a TPU backend."""
    cache = {}

    def get(name):
        if name not in cache:
            with pytest.MonkeyPatch.context() as mp:
                mp.setattr(hw, "pallas_interpret", lambda: False)
                cache[name] = compile_fused(name, CELL_GRIDS[name], one_chip)
        return cache[name]
    return get


@pytest.mark.parametrize("name", sorted(PROGRAMS))
def test_block_kernel_compiles_at_8m(name, compiled_8m):
    """The default (block) schedule: overlapping windows whose last two
    axes span the padded array, as Mosaic's block rule requires."""
    ex, compiled = compiled_8m(name, None)
    assert ex.plan.schedule == "block"
    assert tuple(ex.plan.block[1:]) == GRID_8M[1:]
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("name", sorted(PROGRAMS))
def test_stream_fused_loop_compiles_at_8m(name, compiled_8m):
    ex, compiled = compiled_8m(name, "stream")
    assert ex.plan.schedule == "stream"
    assert "tpu_custom_call" in compiled.as_text()


def test_block_kernel_slices_a_tiled_axis0_coeff(one_chip, mosaic):
    """A coefficient along a leading axis that the block plan tiles: the
    kernel slices each tile's part of the resident vector at a start that
    depends on the grid step."""
    b = ProgramBuilder("coeff_axis0", ndim=3)
    u = b.input("u")
    c = b.coeff("c", axis=0)
    o = b.output("o")
    b.define(o, c[-1] * u[-1, 0, 0] + c[0] * u[0, 1, 0] + c[1] * u[1, 0, 0])
    p = b.build()
    grid = (64, 16, 128)
    ex = compile_program(p, grid, backend="pallas")
    assert ex.plan.schedule == "block" and ex.plan.block[0] < grid[0]
    compiled = ex.lower(*shapes(p, grid, one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_stream_chain_compiles_undemoted(one_chip, mosaic):
    """time_tile=4 x plane_tile=4: the deepest scratch the stream kernel
    carries, inside the scoped-VMEM limit the kernels are compiled with."""
    ex, compiled = compile_fused("pw_advection", (16, 16, 128), one_chip,
                                 steps=8, schedule="stream", time_tile=4,
                                 plane_tile=4)
    assert (ex.plan.stream.time_tile, ex.plan.stream.plane_tile) == (4, 4)
    assert "tpu_custom_call" in compiled.as_text()


def test_vmapped_serve_executor_compiles(one_chip, mosaic):
    """What StencilEngine dispatches for a batch of two stream requests:
    the small (1, n) operands batch in a layout Mosaic accepts."""
    p = pw_advection()
    sp = serving_program(p)
    spec = bucket_for(sp, (16, 16, 128))
    ex = compile_program(sp, spec.bucket, backend="pallas", schedule="stream",
                         jit=False, steps=3,
                         update=wrap_update(sp, spec, pw_advection_update(0.1)))
    batched = jax.jit(jax.vmap(ex._fn))
    compiled = batched.lower(*shapes(sp, spec.bucket, one_chip,
                                     batch=(2,))).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_mesh_stream_loop_compiles_on_2x2(topo, mosaic):
    """The 2x2-mesh fused stream loop at 32M global (one 8M block per
    chip): halos travel by collective-permute between the kernels."""
    mesh = Mesh(np.array(topo.devices).reshape(2, 2), ("X", "Y"))
    p = pw_advection()
    ex = compile_program(p, GRID_32M, schedule="stream", steps=4,
                         update=pw_advection_update(0.1), mesh=mesh,
                         mesh_axes=("X", "Y", None))
    compiled = ex.lower(*shapes(
        p, GRID_32M, NamedSharding(mesh, P("X", "Y", None)),
        replicated=NamedSharding(mesh, P()))).compile()
    text = compiled.as_text()
    assert "collective-permute" in text
    assert "tpu_custom_call" in text


# ------------------------------------------------- kernel names, phase tags

_COMPUTATION = re.compile(r"^(?:ENTRY )?%([\w.\-]+) .*\{$")
_INSTRUCTION = re.compile(r"^(?:ROOT )?%([\w.\-]+) = (.*?) ([a-z][a-z0-9\-]*)\(")
_PHASE = re.compile(r'repro_phase="(\w+)"')
_KERNEL = 'custom_call_target="tpu_custom_call"'
#: what carries no phase of its own: the loop's plumbing and the halves
#: of the async prefetches memory-space assignment adds (slices of a
#: buffer moved into VMEM, reassembled by a ``ConcatBitcast``)
_PLUMBING = ("parameter", "get-tuple-element", "tuple", "constant", "bitcast",
             "copy-start", "copy-done", "slice-start", "slice-done")


def computations(text: str) -> dict:
    """Optimized HLO text -> {computation: [(name, shape, opcode, line)]}."""
    out, cur = {}, None
    for line in text.split("\n"):
        m = _COMPUTATION.match(line)
        if m:
            cur = out.setdefault(m.group(1), [])
        elif line.startswith("}"):
            cur = None
        elif cur is not None and line.strip():
            name, shape, op = _INSTRUCTION.match(line.strip()).groups()
            cur.append((name, shape, op, line))
    return out


def untagged_in_loop(text: str) -> list:
    """Instructions of the fused loop's body that carry no ``repro_phase``
    and are not plumbing: not a kernel, the loop counter, an async
    prefetch half, or a constant XLA sank into the loop as a broadcast."""
    comps = computations(text)
    out = []
    for body in set(re.findall(r"body=%([\w.\-]+)", text)):
        consts = {n for n, _, op, _ in comps[body] if op == "constant"}
        for name, shape, op, line in comps[body]:
            if (op in _PLUMBING or _KERNEL in line or _PHASE.search(line)
                    or 'custom_call_target="ConcatBitcast"' in line
                    or (op == "add" and shape.startswith("s32[]"))
                    or (op == "broadcast" and re.search(
                        r"broadcast\(%([\w.\-]+)\)", line).group(1) in consts)):
                continue
            out.append(line.strip()[:160])
    return out


@pytest.mark.parametrize("schedule", [None, "stream"], ids=["default", "stream"])
@pytest.mark.parametrize("name", sorted(PROGRAMS))
def test_fused_loop_ops_carry_a_phase_and_kernels_a_name(name, schedule,
                                                         compiled_8m):
    """Every op of the loop body that does work around the kernels carries
    a ``repro_phase`` tag, and every kernel is named by its fuse group or
    stream region, uniquely."""
    ex, compiled = compiled_8m(name, schedule)
    text = compiled.as_text()
    assert untagged_in_loop(text) == []
    kernels = [n.rsplit(".", 1)[0] for c in computations(text).values()
               for n, _, _, line in c if _KERNEL in line]
    prefix = "blk_" if ex.plan.schedule == "block" else "str_"
    assert kernels and all(k.startswith(prefix) for k in kernels)
    # a loop whose kernels write the back buffer runs two steps per
    # iteration: each kernel once per half
    counts = ex.time_spec.carry_counts()
    halves = 2 if counts["kernel"] else 1
    assert sorted(kernels) == sorted(list(set(kernels)) * halves)
    expected = (ex.plan.groups if prefix == "blk_" else lower_to_dataflow(
        ex.program, ex.plan, GRID_8M).regions)
    assert len(set(kernels)) == len(expected)
    tags = {m.group(1) for m in _PHASE.finditer(text)}
    assert {"entry", "exit"} <= tags
    # XLA writes the carry only for fields no kernel stores there, and
    # traces the rule only for fields it updates
    assert ("carry_write" in tags) == (counts["refill"] + counts["inplace"]
                                       > 0)
    assert ("update" in tags) == (ex.time_spec.update_counts()["xla"] > 0)


def _instructions(text: str) -> list:
    """(name, opcode, shape) of every instruction in order, each name
    without the numeric suffixes XLA's uniquifier adds: a tagged
    ``jnp.pad`` traces its inner jit once per phase, which shifts the
    numbering but not the program."""
    return [(re.sub(r"\.\d+", "", n), op, shape)
            for c in computations(text).values() for n, shape, op, _ in c]


@pytest.mark.parametrize("name", sorted(PROGRAMS))
def test_phase_tags_change_only_frontend_attributes(name, one_chip, mosaic,
                                                    monkeypatch,
                                                    compiled_cells):
    """At the benchmark cell's grid, the compiled fused loop with the tags
    and without them has the same instructions, in the same order, with
    the same opcodes and shapes."""
    grid = CELL_GRIDS[name]
    _, tagged = compiled_cells(name)
    monkeypatch.setattr(obs, "phase", lambda _: contextlib.nullcontext())
    _, plain = compile_fused(name, grid, one_chip)
    assert "repro_phase" in tagged.as_text()
    assert "repro_phase" not in plain.as_text()
    assert _instructions(tagged.as_text()) == _instructions(plain.as_text())


def _dims(shape: str) -> tuple:
    m = re.match(r"\w+\[([\d,]*)\]", shape)
    return tuple(int(d) for d in m.group(1).split(",") if d) if m else None


def loop_ops(text: str, phase: str) -> list:
    """(opcode, dims) of the fused loop body's instructions tagged
    ``phase``."""
    comps = computations(text)
    return [(op, _dims(shape))
            for body in set(re.findall(r"body=%([\w.\-]+)", text))
            for _, shape, op, line in comps[body]
            if f'repro_phase="{phase}"' in line]


def loop_writes(text: str, dims: tuple) -> list:
    """Opcodes of the fused loop body's instructions that produce an
    array of ``dims`` and are neither a kernel nor the loop's plumbing:
    a pad, a copy (async halves included) or a fusion writing it."""
    comps = computations(text)
    return [op for body in set(re.findall(r"body=%([\w.\-]+)", text))
            for _, shape, op, line in comps[body]
            if _dims(shape) == dims and _KERNEL not in line
            and op not in ("parameter", "get-tuple-element", "tuple",
                           "bitcast", "constant")]


#: where each cell's fused loop updates each field, and how the new value
#: reaches the carry (``update_counts()``, ``carry_counts()``)
CELL_PLACEMENT = {
    "pw_advection": ({"kernel": 3, "kept": 0, "xla": 0},
                     {"kernel": 3, "refill": 0, "inplace": 0, "kept": 0}),
    "tracer_advection": ({"kernel": 1, "kept": 5, "xla": 0},
                         {"kernel": 1, "refill": 0, "inplace": 0, "kept": 5}),
    "tracer_advection_cyclic": ({"kernel": 1, "kept": 5, "xla": 0},
                                {"kernel": 0, "refill": 1, "inplace": 0,
                                 "kept": 5}),
}


@pytest.mark.parametrize("name", sorted(PROGRAMS))
def test_cell_loop_updates_in_kernel_and_keeps_steady_fields(name,
                                                             compiled_cells):
    """At each benchmark cell's grid: PW's three updates run in its one
    kernel's epilogue, so no op of the loop body is tagged ``update``;
    tracer's rule only renames ``ta`` to ``t``, so the five steady fields
    are neither sliced for the update nor re-padded.  The kernel that
    computes each changed field zero on every axis stores it straight into
    its padded back buffer, so no op of the body is tagged
    ``carry_write``, and none pads or copies an array of that field's
    carry shape: the two-step body hands the buffers back without a copy.
    The cyclic ``t`` wraps along axis 0, so XLA refills its carry, with
    one fusion tagged ``wrap``, and the body runs one step.  The kernels
    are one per fuse group in each step of the body, and every op of the
    loop body still carries a phase."""
    ex, compiled = compiled_cells(name)
    text = compiled.as_text()
    assert untagged_in_loop(text) == []
    assert loop_ops(text, "update") == []
    assert loop_ops(text, "carry_write") == []
    spec = ex.time_spec
    assert (spec.update_counts(), spec.carry_counts()) == CELL_PLACEMENT[name]
    kernels = [n for c in computations(text).values()
               for n, _, _, line in c if _KERNEL in line]
    halves = 2 if spec.carry_counts()["kernel"] else 1
    assert len(kernels) == halves * len(ex.plan.groups)
    carry = {f: tuple(g + int(spec.field_pad[f][a].sum())
                      for a, g in enumerate(CELL_GRIDS[name]))
             for f in spec.persistent}
    if name != "pw_advection":
        steady = {carry[f] for f in ("un", "vn", "wn", "e3t", "msk")}
        assert carry["t"] not in steady     # shapes tell the fields apart
    for f, how in spec.carry_placement.items():
        if how == "kernel":
            assert loop_writes(text, carry[f]) == [], f
        elif how == "refill":
            assert loop_writes(text, carry[f]) == ["fusion"], f
            assert ("fusion", carry[f]) in loop_ops(text, "wrap"), f


def test_epilogue_beside_a_field_left_on_xla_compiles(one_chip, mosaic):
    """At 8M, a PW rule whose ``w`` reads a grid-shaped array it closes
    over: ``u`` and ``v`` are still updated in the kernel's epilogue, which
    Mosaic accepts because it holds only their ops, and ``w`` alone is
    updated by XLA in the loop body."""
    p = pw_advection()
    mask = (np.arange(np.prod(GRID_8M)).reshape(GRID_8M) % 3 > 0).astype(
        np.float32)

    def update(fl, out):
        return {"u": fl["u"] + 0.1 * out["su"], "v": fl["v"] + 0.1 * out["sv"],
                "w": fl["w"] + 0.1 * mask * out["sw"]}
    ex = compile_program(p, GRID_8M, steps=4, update=update)
    assert ex.plan.block[0] < GRID_8M[0]        # an epilogue sees a tile
    assert ex.time_spec.update_placement == {"u": "kernel", "v": "kernel",
                                             "w": "xla"}
    text = ex.lower(*shapes(p, GRID_8M, one_chip)).compile().as_text()
    assert untagged_in_loop(text) == []
    assert {dims for _, dims in loop_ops(text, "update")} <= {GRID_8M, ()}
    assert loop_ops(text, "update")
