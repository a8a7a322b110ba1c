"""Fused on-device time loop: ``compile_program(..., steps=N, update=...)``.

Invariants:
* N fused on-device iterations match N host-side ``run_time_loop``
  iterations to 1e-5 on every backend (pallas interpret, jnp_fused,
  jnp_naive), including programs with scalars and per-level coefficients.
* The whole loop is one compiled program: the user's update rule is traced
  exactly once regardless of N, and repeated calls hit the jit cache.
* Both carry-write styles ("repad" rebuild and "inplace" scatter) agree.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.apps import (pw_advection, pw_advection_update, tracer_advection,
                        tracer_advection_update)
from repro.core import compile_program, plan_time_loop, run_time_loop
from repro.core.schedule import auto_plan

BACKENDS = ["jnp_naive", "jnp_fused", "pallas"]


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


def check_fused(p, grid, data, update, steps, backend, atol=1e-5,
                **compile_kw):
    fields, scalars, coeffs = data
    ex = compile_program(p, grid, backend=backend, **compile_kw)
    ref = run_time_loop(ex, dict(fields), scalars, coeffs, steps, update)
    exN = compile_program(p, grid, backend=backend, steps=steps,
                          update=update, **compile_kw)
    got = exN(fields, scalars, coeffs)
    assert set(got) == set(ref)
    for k in ref:
        np.testing.assert_allclose(
            np.asarray(got[k]), np.asarray(ref[k]), atol=atol, rtol=atol,
            err_msg=f"{p.name}/{k} backend={backend} steps={steps}")
    return exN


# ------------------------------------------------- parity (scalars + coeffs)

@pytest.mark.parametrize("backend", BACKENDS)
def test_pw_advection_fused_matches_host_loop(backend):
    grid = (8, 8, 128)
    check_fused(pw_advection(), grid, pw_data(grid),
                pw_advection_update(0.1), steps=4, backend=backend)


@pytest.mark.parametrize("backend", BACKENDS)
def test_tracer_advection_fused_matches_host_loop(backend):
    grid = (6, 8, 64)
    check_fused(tracer_advection(), grid, tracer_data(grid),
                tracer_advection_update(), steps=3, backend=backend)


@pytest.mark.parametrize("grid", [(5, 7, 130), (9, 6, 96)])
def test_fused_loop_odd_grids_alignment(grid):
    """Non-divisible grids: the carry keeps lane-alignment padding."""
    check_fused(pw_advection(), grid, pw_data(grid),
                pw_advection_update(0.1), steps=3, backend="pallas")


@pytest.mark.parametrize("strategy", ["fused", "per_field", "auto"])
def test_fused_loop_multi_group_strategies(strategy):
    """Cross-group temps re-materialise per step inside the loop."""
    grid = (6, 8, 64)
    check_fused(tracer_advection(), grid, tracer_data(grid),
                tracer_advection_update(), steps=2, backend="pallas",
                strategy=strategy)


@pytest.mark.parametrize("carry_write", ["repad", "inplace"])
def test_fused_loop_carry_write_styles(carry_write):
    grid = (8, 8, 128)
    check_fused(pw_advection(), grid, pw_data(grid),
                pw_advection_update(0.1), steps=3, backend="pallas",
                carry_write=carry_write)


def test_steps_one_equals_single_step_plus_update():
    grid = (8, 8, 64)
    p = pw_advection()
    fields, scalars, coeffs = pw_data(grid)
    update = pw_advection_update(0.1)
    out = compile_program(p, grid, backend="jnp_fused")(fields, scalars,
                                                        coeffs)
    want = update(fields, out)
    got = compile_program(p, grid, backend="jnp_fused", steps=1,
                          update=update)(fields, scalars, coeffs)
    for k in want:
        np.testing.assert_allclose(np.asarray(got[k]), np.asarray(want[k]),
                                   atol=1e-6, rtol=1e-6)


# ------------------------------------------------------ single-dispatch jit

@pytest.mark.parametrize("backend", BACKENDS)
def test_update_traced_once_per_compile(backend):
    """The loop lowers into ONE jitted program: the update rule is traced
    exactly once for steps=5 (a host-driven loop traces/dispatches it per
    step), and a second executable call hits the jit cache (no retrace)."""
    grid = (6, 6, 64)
    p = pw_advection()
    fields, scalars, coeffs = pw_data(grid)
    inner = pw_advection_update(0.1)
    traces = [0]

    def update(fl, out):
        traces[0] += 1
        return inner(fl, out)

    ex = compile_program(p, grid, backend=backend, steps=5, update=update)
    ex(fields, scalars, coeffs)
    ex(fields, scalars, coeffs)
    assert traces[0] == 1


def test_partial_update_keeps_untouched_fields():
    """An update returning a subset of fields leaves the rest unchanged."""
    grid = (6, 6, 64)
    p = tracer_advection()
    fields, scalars, coeffs = tracer_data(grid)
    exN = compile_program(p, grid, backend="jnp_fused", steps=2,
                          update=lambda fl, out: {"t": out["ta"]})
    got = exN(fields, scalars, coeffs)
    for f in ("un", "vn", "wn", "e3t", "msk"):
        np.testing.assert_array_equal(np.asarray(got[f]),
                                      np.asarray(fields[f]))


# ------------------------------------------------------------ plan layer

def test_time_loop_spec_geometry():
    import dataclasses

    p = pw_advection()
    grid = (9, 8, 128)
    plan = dataclasses.replace(auto_plan(p, grid, backend="pallas"),
                               block=(8, 8, 128))
    spec = plan_time_loop(p, plan, grid, 7)
    assert spec.steps == 7
    assert spec.persistent == ["u", "v", "w"]
    assert set(spec.double_buffer) == {"u", "v", "w"}
    slots = [s for pair in spec.double_buffer.values() for s in pair]
    assert len(slots) == len(set(slots))  # disjoint front/back slots
    for f in spec.persistent:
        pad = spec.field_pad[f]
        assert pad.shape == (3, 2)
        assert (pad >= 0).all()
        # tile alignment: 9 -> 2x8 tiles on axis 0 pads 7 on the hi side
        assert pad[0, 1] >= 7
    # offsets place every group window inside the carry
    for offs in spec.group_offsets:
        for f, o in offs.items():
            assert all(v >= 0 for v in o)


def dead_op_program():
    """A live 1-wide stencil plus a DCE'd op reaching 4 cells up-axis-0."""
    from repro.core.frontend import ProgramBuilder
    b = ProgramBuilder("deadop", ndim=3)
    u, = b.inputs("u")
    dead = b.temp("dead")                     # produced, never consumed
    su = b.output("su")
    b.define(dead, u[4, 0, 0] * 2.0)
    b.define(su, u[-1, 0, 0] + u[1, 0, 0] - 2.0 * u[0, 0, 0])
    return b.build()


def test_dead_op_carry_padding_gated_on_backend():
    """Regression: the raw-access widening workaround is for the jnp
    lowerings (which evaluate every op, no DCE); the pallas backend only
    runs live fuse groups, so its carry must not be over-allocated for a
    dead op's reach."""
    p = dead_op_program()
    grid = (8, 8, 128)
    pallas_spec = plan_time_loop(p, auto_plan(p, grid, backend="pallas"),
                                 grid, 2)
    jnp_spec = plan_time_loop(p, auto_plan(p, grid, backend="jnp_fused"),
                              grid, 2)
    # live halo on axis 0 is 1; the dead op reads at +4
    assert pallas_spec.field_pad["u"][0, 1] == 1
    assert jnp_spec.field_pad["u"][0, 1] == 4


@pytest.mark.parametrize("backend", BACKENDS)
def test_dead_op_fused_loop_parity(backend):
    """Both carry geometries stay numerically correct with a dead op."""
    grid = (8, 8, 64)
    p = dead_op_program()
    rng = np.random.default_rng(3)
    fields = {"u": jnp.asarray(rng.normal(size=grid).astype(np.float32))}
    check_fused(p, grid, (fields, {}, {}),
                lambda fl, out: {"u": fl["u"] + 0.1 * out["su"]},
                steps=3, backend=backend)


def test_steps_requires_update():
    p = pw_advection()
    with pytest.raises(ValueError, match="update"):
        compile_program(p, (8, 8, 64), backend="jnp_fused", steps=3)


def test_bad_carry_write_rejected():
    p = pw_advection()
    with pytest.raises(ValueError, match="carry_write"):
        compile_program(p, (8, 8, 64), backend="jnp_fused", steps=3,
                        update=pw_advection_update(), carry_write="wat")
