"""The planner's budget split: a fuse group too large for VMEM at the
smallest tile is cut, in program order, into the fewest contiguous runs a
greedy pass finds that fit, not into one kernel per op.

Invariants:
* NEMO tracer advection at the benchmark's 512x256x256 grid plans two
  groups at a one-plane tile, on the zero and the cyclic domain; PW at
  1024x512x256 keeps its one group;
* a budget no two ops fit under gives exactly ``stage_split(p,
  "per_field")``, and every split covers the live ops once, in order,
  without crossing a cut of the strategy's groups;
* the fused loop under a budget split gives the per-field plan's result
  and agrees with the plain references of the benchmark.
"""

import importlib.util
from pathlib import Path

import numpy as np
import pytest

from repro import hw
from repro.apps import (pw_advection, tracer_advection,
                        tracer_advection_update)
from repro.core import compile_program
from repro.core.passes import live_ops, stage_split
from repro.core.schedule import (DataflowPlan, auto_plan, budget_split,
                                 fuse_split, vmem_cost)

from test_boundary import tracer_data

ROOT = Path(__file__).resolve().parents[1]
CYCLIC = ["periodic", "zero", "zero"]
TRACER_GRID = (512, 256, 256)
PW_GRID = (1024, 512, 256)


def _whole_cost(p, plan, grid, steps):
    """What the strategy's one group costs at ``plan``'s tile."""
    whole = DataflowPlan(groups=stage_split(p, "auto"), block=plan.block,
                         mesh_axes=(None,) * p.ndim)
    return vmem_cost(p, whole, grid, steps=steps)


@pytest.mark.parametrize("boundary", ["zero", CYCLIC],
                         ids=["zero", "cyclic"])
def test_tracer_cell_splits_in_two_by_budget(boundary):
    p = tracer_advection(boundary=boundary)
    plan = auto_plan(p, TRACER_GRID, steps=100)
    assert plan.groups == [list(range(13)), list(range(13, 24))]
    assert plan.block == (1, 256, 256)
    assert fuse_split(p, plan) == "budget"
    assert [p.ops[g[-1]].out for g in plan.groups] == ["zta1", "ta"]
    for grp in plan.groups:
        one = DataflowPlan(groups=[grp], block=plan.block,
                           mesh_axes=(None,) * p.ndim)
        assert vmem_cost(p, one, TRACER_GRID) <= hw.VMEM_PLAN_BUDGET
    assert vmem_cost(p, plan, TRACER_GRID,
                     steps=100) <= hw.VMEM_PLAN_BUDGET
    assert _whole_cost(p, plan, TRACER_GRID, 100) > hw.VMEM_PLAN_BUDGET


@pytest.mark.parametrize("steps", [None, 100])
def test_pw_cell_keeps_its_one_group(steps):
    p = pw_advection()
    plan = auto_plan(p, PW_GRID, steps=steps)
    assert plan.groups == [[0, 1, 2]]
    assert plan.block == (1, 512, 256)
    assert fuse_split(p, plan) == "whole"


@pytest.mark.parametrize("steps", [None, 3])
@pytest.mark.parametrize("program", [tracer_advection, pw_advection],
                         ids=["tracer", "pw"])
def test_budget_no_two_ops_fit_under_is_per_field(program, steps):
    p = program()
    plan = auto_plan(p, (16, 16, 128), steps=steps, vmem_budget=1)
    assert plan.groups == stage_split(p, "per_field")
    assert plan.block == (1, 16, 128)


def _budgets(p, grid, steps):
    """Budgets from the per-field plan's cost to the whole group's, at the
    one-plane tile the split runs at."""
    block = (1,) + tuple(grid[1:])

    def cost(groups):
        return vmem_cost(p, DataflowPlan(groups=groups, block=block,
                                         mesh_axes=(None,) * p.ndim),
                         grid, steps=steps)
    lo = cost(stage_split(p, "per_field"))
    hi = cost(stage_split(p, "auto"))
    return [int(lo + (hi - lo) * f) for f in (0.0, 0.1, 0.3, 0.5, 0.9)]


@pytest.mark.parametrize("steps", [None, 3])
@pytest.mark.parametrize("program", [tracer_advection, pw_advection],
                         ids=["tracer", "pw"])
def test_every_split_covers_live_ops_once_in_order(program, steps):
    p = program()
    grid = (16, 16, 128)
    for budget in _budgets(p, grid, steps):
        plan = auto_plan(p, grid, steps=steps, vmem_budget=budget)
        assert [i for g in plan.groups for i in g] == live_ops(p)
        assert all(g for g in plan.groups)
        # a split that is any cut at all keeps within the budget it was cut
        # for, since the per-field plan does
        assert vmem_cost(p, plan, grid, steps=steps) <= budget


def test_budget_split_never_crosses_a_cut_of_the_strategy():
    groups = [[0, 1, 2], [3, 4]]
    assert budget_split(groups, lambda gs: True) == groups
    assert budget_split(groups, lambda gs: False) == [[0], [1], [2], [3],
                                                      [4]]
    # a plan fits while no group holds more than two ops
    two = lambda gs: max(len(g) for g in gs) <= 2
    assert budget_split(groups, two) == [[0, 1], [2], [3, 4]]


# ------------------------------------------------ the fused loop under it

def _reference(name):
    path = ROOT / "bench" / "configs" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"{name}_reference", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _change_err(start, got, ref):
    """The benchmark's ``change_err`` for the tracer: error as a share of
    the change the reference made."""
    den = np.max(np.abs(np.asarray(ref) - np.asarray(start)))
    return float(np.max(np.abs(np.asarray(got) - np.asarray(ref))) / den)


@pytest.mark.parametrize("boundary,reference", [
    ("zero", "tracer_advection"),
    (CYCLIC, "tracer_advection_cyclic")], ids=["zero", "cyclic"])
def test_fused_loop_under_budget_split_matches_per_field_and_reference(
        boundary, reference):
    grid, steps = (8, 8, 128), 3
    p = tracer_advection(boundary=boundary)
    budget = _budgets(p, grid, steps)[2]
    plan = auto_plan(p, grid, steps=steps, vmem_budget=budget)
    assert fuse_split(p, plan) == "budget"
    assert plan.block[0] < grid[0]          # groups recompute across tiles
    per_field = auto_plan(p, grid, steps=steps, vmem_budget=1)
    fields, scalars, coeffs = tracer_data(grid)
    update = tracer_advection_update()
    got = compile_program(p, grid, steps=steps, update=update,
                          plan=plan)(fields, scalars, coeffs)
    want = compile_program(p, grid, steps=steps, update=update,
                           plan=per_field)(fields, scalars, coeffs)
    for k in want:
        np.testing.assert_array_equal(np.asarray(got[k]),
                                      np.asarray(want[k]))
    ref = _reference(reference).run(fields, scalars, coeffs, steps, [])
    assert _change_err(fields["t"], got["t"], ref["t"]) <= 1e-4
