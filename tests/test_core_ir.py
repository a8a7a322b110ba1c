"""Unit tests: stencil IR, frontend, passes."""

import numpy as np
import pytest

from repro.apps import pw_advection, tracer_advection
from repro.core.frontend import ProgramBuilder
from repro.core.ir import Access, FieldRole
from repro.core.passes import (classify, cse_stats, field_halo, infer_halo,
                               stage_split)
from repro.core.schedule import auto_plan, vmem_cost


def test_builder_roundtrip():
    b = ProgramBuilder("p", ndim=2)
    x, y = b.inputs("x", "y")
    s = b.scalar("s")
    o = b.output("o")
    b.define(o, s * x[-1, 0] + y[0, 1] * 2.0 - x[0, 0])
    p = b.build()
    assert p.input_fields() == ["x", "y"]
    assert p.output_fields() == ["o"]
    assert p.scalars == ["s"]
    assert "stencil.apply" in p.to_text()
    assert p.flops_per_point() > 0


def test_builder_rejects_bad_programs():
    b = ProgramBuilder("p", ndim=2)
    x = b.input("x")
    o = b.output("o")
    with pytest.raises(ValueError):
        x[1]  # wrong rank
    with pytest.raises(ValueError):
        b.define(x, x[0, 0])  # writing an input
    b.define(o, x[0, 0])
    with pytest.raises(ValueError):
        b.define(o, x[0, 0])  # double definition
    b2 = ProgramBuilder("q", ndim=1)
    t = b2.temp("t")
    o2 = b2.output("o")
    b2.define(o2, t[1])  # reads t before produced
    with pytest.raises(ValueError):
        b2.build()


def test_classify_pw():
    p = pw_advection()
    c = classify(p)
    assert set(c.inputs) == {"u", "v", "w"}
    assert set(c.outputs) == {"su", "sv", "sw"}
    assert c.scalars == ["tcx", "tcy"]
    assert set(p.coeffs) == {"tzc1", "tzc2", "tzd1", "tzd2"}


def test_halo_simple():
    b = ProgramBuilder("p", ndim=2)
    x = b.input("x")
    o = b.output("o")
    b.define(o, x[-2, 0] + x[1, 3])
    p = b.build()
    gh = infer_halo(p, [0])
    assert gh.input_halo.tolist() == [[2, 1], [0, 3]]
    assert field_halo(p).tolist() == [[2, 1], [0, 3]]


def test_halo_dependency_margins():
    """Producer consumed at offset must be recomputed on extended margin."""
    b = ProgramBuilder("p", ndim=1)
    x = b.input("x")
    t = b.temp("t")
    o = b.output("o")
    b.define(t, x[-1] + x[1])
    b.define(o, t[-1] + t[1])
    p = b.build()
    gh = infer_halo(p, [0, 1])
    assert gh.margins[0].tolist() == [[1, 1]]   # t needed one beyond tile
    assert gh.margins[1].tolist() == [[0, 0]]
    assert gh.input_halo.tolist() == [[2, 2]]   # x window needs 2
    assert gh.internal == ["t"]
    assert gh.group_outputs == ["o"]


def test_halo_chain_depth():
    """Margins accumulate along chains (tracer-advection structure)."""
    b = ProgramBuilder("p", ndim=1)
    x = b.input("x")
    prev = x
    handles = [x]
    for i in range(4):
        t = b.temp(f"t{i}") if i < 3 else b.output("o")
        b.define(t, handles[-1][-1] + handles[-1][1])
        handles.append(t)
    p = b.build()
    gh = infer_halo(p, [0, 1, 2, 3])
    assert gh.margins[0].tolist() == [[3, 3]]
    assert gh.input_halo.tolist() == [[4, 4]]


def test_stage_split_strategies():
    p = tracer_advection()
    per_field = stage_split(p, "per_field")
    assert len(per_field) == len(p.ops) == 24
    fused = stage_split(p, "fused")
    assert len(fused) == 1
    auto = stage_split(p, "auto")
    assert 1 <= len(auto) <= 24


def test_cse_sees_sharing_in_tracer():
    stats = cse_stats(tracer_advection())
    assert stats["reused_evals_saved"] > 0


def test_auto_plan_fits_budget():
    p = pw_advection()
    grid = (256, 256, 1024)
    plan = auto_plan(p, grid)
    assert vmem_cost(p, plan, grid) <= 32 * 1024**2
    assert plan.block[-1] % 128 == 0 or plan.block[-1] == grid[-1]


def test_auto_plan_small_grid_clamps():
    p = pw_advection()
    plan = auto_plan(p, (8, 8, 32))
    assert all(b >= 1 for b in plan.block)


def test_stage_split_bad_strategy_names_valid_ones():
    with pytest.raises(ValueError) as exc:
        stage_split(pw_advection(), "wat")
    msg = str(exc.value)
    assert "'fused'" in msg and "'per_field'" in msg and "'auto'" in msg


def test_mesh_axes_normalised_to_program_ndim():
    """Regression: the default was a hard-coded 3-tuple, wrong for 2-D."""
    b = ProgramBuilder("p2", ndim=2)
    x, = b.inputs("x")
    o = b.output("o")
    b.define(o, x[-1, 0] + x[0, 1])
    p2 = b.build()
    assert auto_plan(p2, (32, 128)).mesh_axes == (None, None)
    assert auto_plan(pw_advection(), (8, 8, 32)).mesh_axes == (None,) * 3
    from repro.core.schedule import DataflowPlan
    plan = DataflowPlan(groups=[[0]], block=(32, 128))
    assert plan.mesh_axes is None
    assert plan.mesh_axes_for(2) == (None, None)
    assert DataflowPlan(groups=[[0]], block=(32, 128),
                        mesh_axes=("x",)).mesh_axes_for(2) == ("x", None)


def test_vmem_cost_accounts_for_fused_loop_carry():
    """Regression: a plan can fit the budget single-step yet claim more
    VMEM under steps=N, where windows are sliced from the align_hi-padded
    carry; the steps-aware cost must be >= the single-step cost."""
    import dataclasses

    p = pw_advection()
    grid = (9, 8, 128)      # 9 -> 2 tiles of 8 on axis 0: align_hi = 7
    plan = dataclasses.replace(auto_plan(p, grid, backend="pallas"),
                               block=(8, 8, 128))
    single = vmem_cost(p, plan, grid)
    looped = vmem_cost(p, plan, grid, steps=3)
    assert looped > single
    # and on an exactly-aligned grid the two geometries coincide
    grid2 = (8, 8, 128)
    plan2 = auto_plan(p, grid2, backend="pallas")
    assert vmem_cost(p, plan2, grid2, steps=3) == vmem_cost(p, plan2, grid2)
