"""Stream schedule (repro.core.lower_stream): shift-register Pallas kernels.

Acceptance invariants for the streaming dataflow backend:
* numerically equivalent to the block schedule: single-step parity against
  the jnp oracle, and steps=4 *fused-loop* parity (1e-5) against
  ``schedule="block"`` for both paper kernels under zero AND periodic
  boundaries;
* the fused loop stays one compiled program on the stream path: the update
  rule traces into it exactly once regardless of N (plus the one build-time
  read that places the updates);
* ``strategy="tuned"`` can serve a stream-scheduled plan end to end from
  the cache (StreamSpec round-trip through compile);
* streaming is pallas-only and single-device (clear errors elsewhere).
"""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.apps import (pw_advection, pw_advection_update, tracer_advection,
                        tracer_advection_update)
from repro.core import (PlanCache, TuneConfig, compile_program,
                        plan_to_dict, run_time_loop)
from repro.core.schedule import auto_plan
from repro.core.tune import cache_key


def pw_data(grid, seed=0):
    rng = np.random.default_rng(seed)
    fields = {f: jnp.asarray(rng.normal(size=grid).astype(np.float32) * 0.1)
              for f in ("u", "v", "w")}
    scalars = {"tcx": jnp.float32(0.05), "tcy": jnp.float32(0.05)}
    coeffs = {c: jnp.asarray(
        np.linspace(0.9, 1.1, grid[2]).astype(np.float32))
        for c in ("tzc1", "tzc2", "tzd1", "tzd2")}
    return fields, scalars, coeffs


def tracer_data(grid, seed=1):
    rng = np.random.default_rng(seed)
    fields = {
        "t": jnp.asarray(rng.normal(size=grid).astype(np.float32) + 15.0),
        "un": jnp.asarray(rng.normal(size=grid).astype(np.float32) * 0.2),
        "vn": jnp.asarray(rng.normal(size=grid).astype(np.float32) * 0.2),
        "wn": jnp.asarray(rng.normal(size=grid).astype(np.float32) * 0.05),
        "e3t": jnp.asarray(
            np.abs(rng.normal(size=grid)).astype(np.float32) + 1.0),
        "msk": jnp.asarray(
            (rng.uniform(size=grid) > 0.05).astype(np.float32)),
    }
    scalars = {"rdt": jnp.float32(0.05), "zeps": jnp.float32(1e-6)}
    coeffs = {"ztfreez": jnp.asarray(np.full(grid[2], -1.8, np.float32))}
    return fields, scalars, coeffs


KERNELS = {
    "pw_advection": (pw_advection, pw_advection_update(0.1), pw_data,
                     (8, 8, 32)),
    "tracer_advection": (tracer_advection, tracer_advection_update(),
                         tracer_data, (6, 8, 32)),
}


# -------------------------------------------------- single-step vs oracle

@pytest.mark.parametrize("kernel", list(KERNELS))
@pytest.mark.parametrize("boundary", ["zero", "periodic"])
def test_stream_single_step_matches_oracle(kernel, boundary):
    prog_fn, _update, data_fn, grid = KERNELS[kernel]
    p = prog_fn(boundary=boundary)
    fields, scalars, coeffs = data_fn(grid)
    ref = compile_program(p, grid, backend="jnp_fused")(fields, scalars,
                                                        coeffs)
    got = compile_program(p, grid, schedule="stream")(fields, scalars,
                                                      coeffs)
    assert set(got) == set(ref)
    for k in ref:
        np.testing.assert_allclose(
            np.asarray(got[k]), np.asarray(ref[k]), atol=1e-5, rtol=1e-5,
            err_msg=f"{kernel}/{boundary}/{k}")


# ------------------------------------- fused loop: stream vs block parity

@pytest.mark.parametrize("kernel", list(KERNELS))
@pytest.mark.parametrize("boundary", ["zero", "periodic"])
def test_stream_fused_loop_matches_block_schedule(kernel, boundary):
    """Acceptance: steps=4 fused-loop parity (1e-5) between the schedules
    for both paper kernels, zero and periodic."""
    prog_fn, update, data_fn, grid = KERNELS[kernel]
    p = prog_fn(boundary=boundary)
    fields, scalars, coeffs = data_fn(grid)
    blk = compile_program(p, grid, steps=4, update=update,
                          schedule="block")(fields, scalars, coeffs)
    stm = compile_program(p, grid, steps=4, update=update,
                          schedule="stream")(fields, scalars, coeffs)
    assert set(stm) == set(blk)
    for k in blk:
        np.testing.assert_allclose(
            np.asarray(stm[k]), np.asarray(blk[k]), atol=1e-5, rtol=1e-5,
            err_msg=f"{kernel}/{boundary}/{k}")


def test_stream_fused_loop_matches_host_loop():
    """...and against the host-driven reference, not just block-vs-stream."""
    prog_fn, update, data_fn, grid = KERNELS["pw_advection"]
    p = prog_fn()
    fields, scalars, coeffs = data_fn(grid)
    ex1 = compile_program(p, grid, schedule="stream")
    ref = run_time_loop(ex1, dict(fields), scalars, coeffs, 4, update)
    got = compile_program(p, grid, steps=4, update=update,
                          schedule="stream")(fields, scalars, coeffs)
    for k in ref:
        np.testing.assert_allclose(np.asarray(got[k]), np.asarray(ref[k]),
                                   atol=1e-5, rtol=1e-5)


# ------------------------------------------------------------- trace once

def test_stream_update_traced_once():
    prog_fn, _update, data_fn, grid = KERNELS["pw_advection"]
    p = prog_fn()
    fields, scalars, coeffs = data_fn(grid)
    traces = {"n": 0}

    def counting_update(flds, out):
        traces["n"] += 1
        return {"u": flds["u"] + 0.1 * out["su"],
                "v": flds["v"] + 0.1 * out["sv"],
                "w": flds["w"] + 0.1 * out["sw"]}

    ex = compile_program(p, grid, steps=4, update=counting_update,
                         schedule="stream")
    # one read at build time that places the updates, one into the loop
    ex(fields, scalars, coeffs)
    assert traces["n"] == 2
    ex(fields, scalars, coeffs)              # second call: jit cache hit
    assert traces["n"] == 2


# ------------------------------------------------ tuned plans + dispatch

def test_tuned_strategy_serves_stream_plan_from_cache():
    """A cached stream winner drives ``strategy="tuned"`` end to end: the
    StreamSpec survives the JSON round trip and the compile dispatches to
    the shift-register lowering with zero timed runs."""
    prog_fn, update, data_fn, grid = KERNELS["pw_advection"]
    p = prog_fn()
    fields, scalars, coeffs = data_fn(grid)
    plan = auto_plan(p, grid, schedule="stream")
    cache = PlanCache(path=None)
    key = cache_key(p, grid, "pallas", "float32", "loop")
    cache.store(key, {"plan": plan_to_dict(plan), "carry_write": "repad"})

    def no_timer(fn):                        # a timed run would be a bug
        raise AssertionError("cache hit must not measure")

    ex = compile_program(p, grid, strategy="tuned", steps=4, update=update,
                         tune_config=TuneConfig(timer=no_timer),
                         plan_cache=cache)
    assert ex.plan.schedule == "stream"
    assert ex.plan.stream is not None
    ref = compile_program(p, grid, steps=4, update=update,
                          schedule="block")(fields, scalars, coeffs)
    got = ex(fields, scalars, coeffs)
    for k in ref:
        np.testing.assert_allclose(np.asarray(got[k]), np.asarray(ref[k]),
                                   atol=1e-5, rtol=1e-5)


def test_stream_requires_pallas_backend():
    p = pw_advection()
    with pytest.raises(ValueError, match="pallas"):
        compile_program(p, (8, 8, 32), backend="jnp_fused",
                        schedule="stream")


def test_stream_composes_with_degenerate_mesh():
    # stream + mesh= is first-class; a 1x1 mesh must bit-match the local
    # stream lowering (the sharded path constant-folds to the same graph)
    from repro.core import make_auto_mesh
    p = pw_advection()
    grid = (8, 8, 32)
    plan = auto_plan(p, grid, schedule="stream")
    rng = np.random.default_rng(3)
    fields = {f: rng.normal(size=grid).astype(np.float32) * 0.1
              for f in ("u", "v", "w")}
    scalars = {"tcx": 0.05, "tcy": 0.05}
    coeffs = {c: np.linspace(0.9, 1.1, grid[2]).astype(np.float32)
              for c in ("tzc1", "tzc2", "tzd1", "tzd2")}
    mesh = make_auto_mesh((1,), ("X",))
    got = compile_program(p, grid, plan=plan, mesh=mesh,
                          mesh_axes=("X", None, None))(fields, scalars,
                                                       coeffs)
    ref = compile_program(p, grid, plan=plan)(fields, scalars, coeffs)
    for k in ref:
        assert np.array_equal(np.asarray(got[k]), np.asarray(ref[k])), k
