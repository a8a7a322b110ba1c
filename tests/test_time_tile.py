"""Temporal blocking (``time_tile``) + the consolidated CompileOptions API.

Acceptance invariants:
* chaining T time steps through one stream sweep is numerically invisible:
  1e-5 fused-loop parity against the unchained stream for T in {1, 2, 4}
  for both paper kernels under zero AND periodic boundaries, remainder
  (``steps % T != 0``) included;
* the chain stays one compiled program (the update rule traces once per
  chain stage at compile, never per step or per call);
* legalisation demotes illegal chains to an effective depth of 1 instead
  of miscompiling (multi-region programs, periodic persistent fields);
* the tuner enumerates chained stream candidates and a tuned ``time_tile``
  survives the JSON plan-cache round trip into ``strategy="tuned"``;
* ``vmem_cost`` prices the T-deepened buffers (deeper chain = more VMEM);
* ``CompileOptions`` and loose kwargs are the same API: equal results,
  single validation point, loud conflicts, loud unknown keys; and
  ``adapt_update`` accepts exactly the two documented update signatures.
"""

import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from repro.apps import pw_advection, pw_advection_update, tracer_advection
from repro.core import (CompileOptions, PlanCache, TuneConfig, adapt_update,
                        chain_split_reason, compile_program,
                        effective_time_tile, lower_to_dataflow,
                        plan_to_dict)
from repro.core.schedule import auto_plan, vmem_cost
from repro.core.tune import cache_key
from test_stream import KERNELS


# ------------------------------------------------------------ parity

@pytest.mark.parametrize("boundary", ["zero", "periodic"])
@pytest.mark.parametrize("kernel", sorted(KERNELS))
@pytest.mark.parametrize("tile", [2, 4])
def test_chained_stream_matches_unchained(kernel, boundary, tile):
    """time_tile=T (T in {1,2,4}) is numerically invisible: the chained
    sweep matches the unchained stream loop to 1e-5.  Periodic boundaries
    and multi-region programs exercise the demote-to-1 fallback — parity
    must hold either way."""
    prog_fn, update, data_fn, grid = KERNELS[kernel]
    p = prog_fn(boundary=boundary)
    fields, scalars, coeffs = data_fn(grid)
    steps = 4
    ex1 = compile_program(p, grid, schedule="stream", steps=steps,
                          update=update)
    exT = compile_program(p, grid, options=CompileOptions(
        schedule="stream", steps=steps, update=update, time_tile=tile))
    assert exT.plan.time_tile == tile          # the request is recorded
    assert exT.plan.stream.time_tile in (1, tile)   # effective: legalised
    r1 = ex1(fields, scalars, coeffs)
    rT = exT(fields, scalars, coeffs)
    for f in r1:
        np.testing.assert_allclose(np.asarray(rT[f]), np.asarray(r1[f]),
                                   atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("steps,tile", [(5, 4), (7, 2), (3, 4)])
def test_chained_stream_remainder_epilogue(steps, tile):
    """steps not divisible by T: the ``steps % T`` remainder runs once
    through a shallower chain after the fused loop (steps < T means the
    loop body never runs at all) — same numbers as the unchained stream."""
    prog_fn, update, data_fn, grid = KERNELS["pw_advection"]
    p = prog_fn()
    fields, scalars, coeffs = data_fn(grid)
    ex1 = compile_program(p, grid, schedule="stream", steps=steps,
                          update=update)
    exT = compile_program(p, grid, schedule="stream", steps=steps,
                          update=update, time_tile=tile)
    assert exT.plan.stream.time_tile == tile
    r1 = ex1(fields, scalars, coeffs)
    rT = exT(fields, scalars, coeffs)
    for f in r1:
        np.testing.assert_allclose(np.asarray(rT[f]), np.asarray(r1[f]),
                                   atol=1e-5, rtol=1e-5)


#: The eight fused-loop rows the CPU speed floors once timed (schedule,
#: time_tile T, plane_tile P), each with the number of times one compile
#: traces PW's update rule; tracer's is traced once in every row.  No
#: count grows with the step count:
#:
#: * block: 1, the build-time read that places the updates; PW's three
#:   then run in its kernel's epilogue, tracer's ``t`` is the kernel's
#:   ``ta`` and its other five fields stay in the carry, so the loop body
#:   never calls the rule.
#: * stream at T = 1 (any P): PW 2, the build-time read and one call into
#:   the loop body, where its updates stay on XLA (a stream kernel takes
#:   no epilogue); tracer 1, its rule only renames ``ta``.
#: * a chain (T = 4): the chain kernel calls the rule once per stage and
#:   makes no build-time read.  Seven stages at P = 1: four in the loop's
#:   chain (traced at 3 steps too, where the loop never runs) and three in
#:   the chain after it that runs the ``steps % 4 = 3`` left over (3 and 7
#:   steps leave the same).  P = 4 runs every stage on each of the four
#:   planes of a sweep step: 28.
#: * tracer legalises to four stream regions, so every chain demotes to
#:   T = 1, and its count stays 1.
TRACE_ROWS = {
    "block": ({"schedule": "block"}, 1),
    "stream": ({"schedule": "stream"}, 2),
    "stream-T1": ({"schedule": "stream", "time_tile": 1}, 2),
    "stream-T4": ({"schedule": "stream", "time_tile": 4}, 7),
    "P1T1": ({"schedule": "stream", "plane_tile": 1, "time_tile": 1}, 2),
    "P1T4": ({"schedule": "stream", "plane_tile": 1, "time_tile": 4}, 7),
    "P4T1": ({"schedule": "stream", "plane_tile": 4, "time_tile": 1}, 2),
    "P4T4": ({"schedule": "stream", "plane_tile": 4, "time_tile": 4}, 28),
}


@pytest.mark.parametrize("kernel", sorted(KERNELS))
@pytest.mark.parametrize("row", list(TRACE_ROWS))
def test_chained_update_traces_once_per_stage(row, kernel):
    """The update rule is traced at compile only (``TRACE_ROWS`` says how
    often), never per step or per call: a second call traces nothing, and
    3 steps trace as often as 7.  So the whole loop stays one dispatch
    that a warm request does not rebuild."""
    opts, pw_traces = TRACE_ROWS[row]
    prog_fn, _update, data_fn, _grid = KERNELS[kernel]
    grid = (16, 16, 16)
    p = prog_fn()
    fields, scalars, coeffs = data_fn(grid)
    pw = kernel == "pw_advection"
    want = pw_traces if pw else 1
    for steps in (3, 7):
        traces = {"n": 0}

        def counting_update(flds, out):
            traces["n"] += 1
            if pw:
                return {"u": flds["u"] + 0.1 * out["su"],
                        "v": flds["v"] + 0.1 * out["sv"],
                        "w": flds["w"] + 0.1 * out["sw"]}
            return dict(flds, t=out["ta"])

        ex = compile_program(p, grid, options=CompileOptions(
            steps=steps, update=counting_update, **opts))
        if opts["schedule"] == "stream":
            tile = opts.get("time_tile", 1) if pw else 1
            assert (ex.plan.stream.time_tile, ex.plan.stream.plane_tile) \
                == (tile, opts.get("plane_tile", 1))
        ex(fields, scalars, coeffs)
        assert traces["n"] == want, steps
        ex(fields, scalars, coeffs)           # second call: jit cache hit
        assert traces["n"] == want, steps


# ------------------------------------------------------------ legalisation

def test_chain_demotes_multi_region_and_periodic():
    pw = pw_advection()
    plan = auto_plan(pw, (8, 8, 32), schedule="stream", time_tile=4)
    graph = lower_to_dataflow(pw, plan)
    assert graph.time_tile == 4               # single region, zero boundary
    assert chain_split_reason(pw, [list(r.ops) for r in graph.regions]) \
        is None

    # tracer_advection legalises to multiple stream regions: no chain
    tr = tracer_advection()
    plan = auto_plan(tr, (6, 8, 32), schedule="stream", time_tile=4)
    assert plan.time_tile == 4                # the request survives
    assert plan.stream.time_tile == 1         # ...the chain does not
    graph = lower_to_dataflow(tr, plan)
    reason = chain_split_reason(tr, [list(r.ops) for r in graph.regions])
    assert reason is not None and "region" in reason

    # periodic persistent fields wrap through planes the chain already
    # consumed: demoted
    pwp = pw_advection(boundary="periodic")
    plan = auto_plan(pwp, (8, 8, 32), schedule="stream", time_tile=4)
    assert plan.stream.time_tile == 1
    graph = lower_to_dataflow(pwp, plan)
    regions = [list(r.ops) for r in graph.regions]
    assert "periodic" in chain_split_reason(pwp, regions)
    assert effective_time_tile(pwp, regions, 4) == 1


def test_time_tile_validation():
    p = pw_advection()
    grid = (8, 8, 32)
    update = pw_advection_update(0.1)
    # temporal blocking needs a fused loop to chain updates through
    with pytest.raises(ValueError, match="steps"):
        compile_program(p, grid, schedule="stream", time_tile=4)
    with pytest.raises(ValueError):
        compile_program(p, grid, schedule="stream", steps=4, update=update,
                        time_tile=0)
    # ...and the stream schedule (block tiles have no chain to ride)
    with pytest.raises(ValueError, match="stream"):
        auto_plan(p, grid, time_tile=2)
    with pytest.raises(ValueError, match="stream"):
        dataclasses.replace(auto_plan(p, grid), time_tile=2)


def test_vmem_cost_prices_chain_depth():
    """Deeper chains hold deeper windows, per-stage plane rings, and
    margin-extended temps in VMEM — the cost model must see that, or the
    tuner would admit chains that cannot fit."""
    p = pw_advection()
    grid = (8, 8, 32)
    costs = [vmem_cost(p, auto_plan(p, grid, schedule="stream",
                                    time_tile=t, vmem_budget=1 << 40), grid)
             for t in (1, 2, 4)]
    assert costs[0] < costs[1] < costs[2]


# ------------------------------------------------------------ tuner + cache

def test_tuner_enumerates_chained_stream_candidates():
    from repro.core.tune import _candidates
    cfg = TuneConfig(steps=4, timer=lambda fn: 1.0)
    cands = _candidates(pw_advection(), (8, 8, 32), "pallas",
                        "float32", cfg, with_loop=True)
    eff = {c.plan.stream.time_tile for c in cands
           if c.plan.schedule == "stream" and c.plan.stream is not None}
    assert {1, 2, 4} <= eff
    # single-step sweeps never chain: the T variants dedup away
    cands1 = _candidates(pw_advection(), (8, 8, 32), "pallas",
                         "float32", cfg, with_loop=False)
    assert all(c.plan.stream.time_tile == 1 for c in cands1
               if c.plan.schedule == "stream" and c.plan.stream is not None)


def test_tuned_time_tile_round_trips_through_plan_cache(tmp_path):
    """A tuned chained plan survives the on-disk JSON cache: the stored
    ``time_tile`` deserialises into ``strategy="tuned"`` with zero timed
    runs and drives the chained lowering to the same numbers."""
    prog_fn, update, data_fn, grid = KERNELS["pw_advection"]
    p = prog_fn()
    fields, scalars, coeffs = data_fn(grid)
    plan = auto_plan(p, grid, schedule="stream", time_tile=4)
    assert plan.stream.time_tile == 4
    path = str(tmp_path / "plan_cache.json")
    PlanCache(path=path).store(
        cache_key(p, grid, "pallas", "float32", "loop"),
        {"plan": plan_to_dict(plan), "carry_write": "repad"})

    def no_timer(fn):                        # a timed run would be a bug
        raise AssertionError("cache hit must not measure")

    ex = compile_program(p, grid, options=CompileOptions(
        strategy="tuned", steps=4, update=update,
        tune_config=TuneConfig(timer=no_timer),
        plan_cache=PlanCache(path=path)))    # fresh object: real file read
    assert ex.plan.schedule == "stream"
    assert ex.plan.time_tile == 4 and ex.plan.stream.time_tile == 4
    ref = compile_program(p, grid, schedule="stream", steps=4,
                          update=update)(fields, scalars, coeffs)
    got = ex(fields, scalars, coeffs)
    for k in ref:
        np.testing.assert_allclose(np.asarray(got[k]), np.asarray(ref[k]),
                                   atol=1e-5, rtol=1e-5)


# ------------------------------------------------------------ CompileOptions

def test_options_and_kwargs_are_the_same_api():
    prog_fn, update, data_fn, grid = KERNELS["pw_advection"]
    p = prog_fn()
    fields, scalars, coeffs = data_fn(grid)
    opts = CompileOptions(schedule="stream", steps=2, update=update)
    a = compile_program(p, grid, options=opts)(fields, scalars, coeffs)
    b = compile_program(p, grid, schedule="stream", steps=2,
                        update=update)(fields, scalars, coeffs)
    for k in a:
        np.testing.assert_array_equal(np.asarray(a[k]), np.asarray(b[k]))
    # kwargs may refine knobs the options left at their defaults...
    ex = compile_program(p, grid, options=opts, jit=False)
    assert not ex.jitted
    # ...and repeating a knob with the SAME value is harmless
    compile_program(p, grid, options=opts, steps=2)


def test_options_kwarg_conflict_is_loud():
    p = pw_advection()
    opts = CompileOptions(steps=4, update=pw_advection_update(0.1))
    with pytest.raises(ValueError, match="steps"):
        compile_program(p, (8, 8, 32), options=opts, steps=8)
    with pytest.raises(TypeError, match="stepz"):
        compile_program(p, (8, 8, 32), stepz=4)
    with pytest.raises(TypeError, match="CompileOptions"):
        compile_program(p, (8, 8, 32), options={"steps": 4})


# ------------------------------------------------------------ adapt_update

def test_adapt_update_signatures():
    two = adapt_update(lambda flds, outs: {"a": 1})
    assert two({}, {}, {"s": 9}) == {"a": 1}
    three = adapt_update(lambda flds, outs, scal: {"a": scal["s"]})
    assert three({}, {}, {"s": 9}) == {"a": 9}
    assert adapt_update(None) is None
    for bad in (lambda flds: flds,
                lambda a, b, c, d: a):
        with pytest.raises(TypeError) as err:
            adapt_update(bad)
        # the error names the two accepted forms
        assert "(fields, outputs)" in str(err.value)
        assert "(fields, outputs, scalars)" in str(err.value)
