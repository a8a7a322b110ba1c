"""End-to-end system behaviour: the paper's full pipeline, frontend -> IR
-> auto-plan -> Pallas dataflow kernels -> time-stepped solve (PW
advection, the paper's benchmark 1), through the public APIs.
"""

import jax.numpy as jnp
import numpy as np

from repro.apps import pw_advection, tracer_advection
from repro.core import compile_program, run_time_loop
from repro.core.schedule import vmem_cost


def _pw_data(grid, seed=0):
    rng = np.random.default_rng(seed)
    fields = {f: (rng.normal(size=grid) * 0.1).astype(np.float32)
              for f in ("u", "v", "w")}
    scalars = {"tcx": np.float32(0.05), "tcy": np.float32(0.05)}
    coeffs = {c: np.linspace(0.9, 1.1, grid[2]).astype(np.float32)
              for c in ("tzc1", "tzc2", "tzd1", "tzd2")}
    return fields, scalars, coeffs


def test_pw_advection_time_loop_stable():
    """Several coupled explicit steps through the Pallas backend: finite,
    and identical to the jnp oracle stepped the same way."""
    grid = (24, 20, 64)
    p = pw_advection()
    fields, scalars, coeffs = _pw_data(grid)
    dt = 0.05

    def update(fl, out):
        return {"u": fl["u"] + dt * out["su"],
                "v": fl["v"] + dt * out["sv"],
                "w": fl["w"] + dt * out["sw"]}

    ex_p = compile_program(p, grid, backend="pallas")
    ex_r = compile_program(p, grid, backend="jnp_naive")
    fp = run_time_loop(ex_p, {k: jnp.asarray(v) for k, v in fields.items()},
                       scalars, coeffs, steps=4, update=update)
    fr = run_time_loop(ex_r, {k: jnp.asarray(v) for k, v in fields.items()},
                       scalars, coeffs, steps=4, update=update)
    for k in fp:
        assert bool(jnp.isfinite(fp[k]).all())
        np.testing.assert_allclose(np.asarray(fp[k]), np.asarray(fr[k]),
                                   atol=1e-4, rtol=1e-4)


def test_plan_respects_vmem_budget_on_both_apps():
    from repro import hw
    for prog in (pw_advection(), tracer_advection()):
        grid = (256, 256, 512)
        ex = compile_program(prog, grid, backend="jnp_fused")  # plan only
        assert vmem_cost(prog, ex.plan, grid) <= hw.VMEM_PLAN_BUDGET

