"""Backend parity: Pallas (interpret) vs jnp oracles, incl. property fuzzing.

Every Pallas kernel configuration is checked against the pure-jnp oracle
(``jnp_naive``) — the repo-wide invariant that the generated dataflow code
computes exactly the mathematics of the IR.
"""

import jax.numpy as jnp
import numpy as np
import pytest

try:  # property fuzzing needs the test extra; plain parity tests don't
    from hypothesis import given, settings, HealthCheck
    HAVE_HYPOTHESIS = True
except ImportError:  # pragma: no cover
    HAVE_HYPOTHESIS = False

from repro.apps import pw_advection, tracer_advection
from repro.core import compile_program
from repro.core.schedule import DataflowPlan, auto_plan
from repro.core.passes import stage_split

from strategies import make_data

if HAVE_HYPOTHESIS:
    from strategies import programs


def physical_data(p, grid, seed=0):
    fields, scalars, coeffs = make_data(p, grid, seed)
    if "e3t" in fields:
        fields["e3t"] = np.abs(fields["e3t"]) + 1.0
    if "msk" in fields:
        fields["msk"] = (fields["msk"] > 0).astype(np.float32)
    if "zeps" in scalars:
        scalars["zeps"] = np.float32(1e-6)
    return fields, scalars, coeffs


def check_parity(p, grid, strategy="auto", atol=1e-4, rtol=1e-4, seed=0):
    fields, scalars, coeffs = physical_data(p, grid, seed)
    ref = compile_program(p, grid, backend="jnp_naive")(fields, scalars, coeffs)
    for backend in ["jnp_fused", "pallas"]:
        got = compile_program(p, grid, backend=backend,
                              strategy=strategy)(fields, scalars, coeffs)
        for k in ref:
            np.testing.assert_allclose(
                np.asarray(got[k]), np.asarray(ref[k]), atol=atol, rtol=rtol,
                err_msg=f"{p.name}/{k} backend={backend} grid={grid}")


# ---------------------------------------------------------------- paper apps

@pytest.mark.parametrize("grid", [(8, 8, 32), (12, 10, 130), (16, 16, 256)])
def test_pw_advection_parity(grid):
    check_parity(pw_advection(), grid)


@pytest.mark.parametrize("strategy", ["fused", "per_field", "auto"])
def test_pw_advection_strategies(strategy):
    check_parity(pw_advection(), (10, 12, 128), strategy=strategy)


@pytest.mark.parametrize("grid", [(8, 8, 64), (12, 16, 130)])
def test_tracer_advection_parity(grid):
    check_parity(tracer_advection(), grid)


@pytest.mark.parametrize("strategy", ["fused", "per_field", "auto"])
def test_tracer_advection_strategies(strategy):
    check_parity(tracer_advection(), (8, 10, 64), strategy=strategy)


# ------------------------------------------------------- shape / dtype sweep

@pytest.mark.parametrize("grid", [(32,), (65,), (8, 48), (9, 130),
                                  (4, 6, 64), (5, 7, 96)])
def test_shape_sweep_odd_grids(grid):
    """Non-divisible grids exercise tile-alignment padding + crop."""
    from repro.core.frontend import ProgramBuilder
    b = ProgramBuilder("sweep", ndim=len(grid))
    x = b.input("x")
    o = b.output("o")
    z = (0,) * len(grid)
    off1 = tuple(1 if i == 0 else 0 for i in range(len(grid)))
    off2 = tuple(-1 if i == len(grid) - 1 else 0 for i in range(len(grid)))
    b.define(o, x[z] * 2.0 + x[off1] - x[off2])
    check_parity(b.build(), grid)


def test_coeff_along_a_tiled_leading_axis():
    """The block kernel slices each tile's part of a coefficient along a
    leading axis at a start that moves with the grid step, margins for a
    fused temp included; 40 planes in tiles of 32 also pad the last tile."""
    from repro.core.frontend import ProgramBuilder
    b = ProgramBuilder("coeff_tiled", ndim=3)
    x = b.input("x")
    c = b.coeff("c", axis=0)
    t = b.temp("t")
    o = b.output("o")
    b.define(t, c[1] * x[-1, 0, 0] - c[-1] * x[1, 0, 1])
    b.define(o, c[0] * t[-1, 0, 0] + t[1, 1, 0])
    p = b.build()
    grid = (40, 4, 16)
    assert auto_plan(p, grid, strategy="fused").block[0] < grid[0]
    check_parity(p, grid, strategy="fused")


def test_bfloat16_dtype():
    p = pw_advection()
    grid = (8, 8, 128)
    fields, scalars, coeffs = physical_data(p, grid)
    ref = compile_program(p, grid, backend="jnp_naive")(fields, scalars, coeffs)
    got = compile_program(p, grid, backend="pallas",
                          dtype="bfloat16")(fields, scalars, coeffs)
    for k in ref:
        np.testing.assert_allclose(np.asarray(got[k], dtype=np.float32),
                                   np.asarray(ref[k]), atol=0.15, rtol=0.15)


# ------------------------------------------------------------ property tests

if HAVE_HYPOTHESIS:
    @settings(max_examples=25, deadline=None,
              suppress_health_check=[HealthCheck.too_slow])
    @given(p=programs())
    def test_property_random_programs_pallas_matches_oracle(p):
        grid = {1: (24,), 2: (10, 32), 3: (6, 8, 32)}[p.ndim]
        check_parity(p, grid, atol=1e-3, rtol=1e-3)

    @settings(max_examples=10, deadline=None,
              suppress_health_check=[HealthCheck.too_slow])
    @given(p=programs(ndim=3))
    def test_property_per_field_equals_fused(p):
        """Paper step 4: the per-field dataflow split must not change
        results."""
        grid = (6, 8, 32)
        fields, scalars, coeffs = make_data(p, grid, seed=3)
        a = compile_program(p, grid, backend="pallas",
                            strategy="fused")(fields, scalars, coeffs)
        b = compile_program(p, grid, backend="pallas",
                            strategy="per_field")(fields, scalars, coeffs)
        for k in a:
            np.testing.assert_allclose(np.asarray(a[k]), np.asarray(b[k]),
                                       atol=1e-3, rtol=1e-3)
