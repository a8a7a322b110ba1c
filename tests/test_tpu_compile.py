"""Compile the main path's kernels for a TPU v5e that is described, not
attached: Mosaic refuses here what the Pallas interpreter never checks
(block shapes off the (8, 128) tiling, scoped-VMEM overruns, operands that
cannot batch under ``vmap``).

Nothing runs: each test lowers and compiles one executable for the
described chip and reads its HLO.  The kernels are steered to their
compiled (Mosaic) branch by patching :func:`repro.hw.pallas_interpret`,
which would otherwise pick the interpreter on this CPU backend.  The
topology is described in a fixture, never at import, so every test worker
collects the same tests and only the one that runs this file loads the TPU
compiler.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from repro import hw
from repro.apps import (pw_advection, pw_advection_update, tracer_advection,
                        tracer_advection_update)
from repro.core import compile_program
from repro.core.frontend import ProgramBuilder
from repro.serve import bucket_for, serving_program, wrap_update

GRID_8M = (256, 256, 128)
GRID_32M = (512, 256, 256)
PROGRAMS = {"pw_advection": (pw_advection, lambda: pw_advection_update(0.1)),
            "tracer_advection": (tracer_advection, tracer_advection_update)}


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


@pytest.mark.parametrize("name", sorted(PROGRAMS))
def test_block_kernel_compiles_at_8m(name, one_chip, mosaic):
    """The default (block) schedule: overlapping windows whose last two
    axes span the padded array, as Mosaic's block rule requires."""
    ex, compiled = compile_fused(name, GRID_8M, one_chip)
    assert ex.plan.schedule == "block"
    assert tuple(ex.plan.block[1:]) == GRID_8M[1:]
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("name", sorted(PROGRAMS))
def test_stream_fused_loop_compiles_at_8m(name, one_chip, mosaic):
    ex, compiled = compile_fused(name, GRID_8M, one_chip, schedule="stream")
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
