"""Per-kernel shape/dtype sweeps vs the pure-jnp oracles (deliverable c)."""

import numpy as np
import pytest

from repro.apps import pw_advection, tracer_advection
from repro.core import compile_program
from repro.kernels.ops import stencil_apply
from repro.kernels.ref import stencil_reference

from strategies import make_data


# ------------------------------------------------------------- stencil3d

@pytest.mark.parametrize("grid", [(8, 8, 64), (16, 4, 128), (5, 9, 130)])
@pytest.mark.parametrize("dtype,atol", [("float32", 1e-4), ("bfloat16", 0.2)])
def test_stencil3d_shape_dtype_sweep(grid, dtype, atol):
    p = pw_advection()
    fields, scalars, coeffs = make_data(p, grid, seed=5)
    ref = stencil_reference(p, fields, scalars, coeffs)
    ex = compile_program(p, grid, backend="pallas", dtype=dtype)
    got = ex(fields, scalars, coeffs)
    for k in ref:
        np.testing.assert_allclose(np.asarray(got[k], np.float32),
                                   np.asarray(ref[k]), atol=atol, rtol=atol)


def test_stencil_apply_wrapper():
    p = tracer_advection()
    grid = (8, 8, 64)
    fields, scalars, coeffs = make_data(p, grid, seed=6)
    fields["e3t"] = np.abs(fields["e3t"]) + 1.0
    scalars["zeps"] = np.float32(1e-6)
    got = stencil_apply(p, grid, fields, scalars, coeffs)
    ref = stencil_reference(p, fields, scalars, coeffs)
    np.testing.assert_allclose(np.asarray(got["ta"]), np.asarray(ref["ta"]),
                               atol=1e-3, rtol=1e-3)

