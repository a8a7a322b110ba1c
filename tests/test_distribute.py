"""Distributed single-step executor: halo exchange vs single-device oracle.

Exercised through the unified ``compile_program(..., mesh=, mesh_axes=)``
entry point (the planner-driven sharded lowering); the deprecated
``make_sharded_executor`` wrapper is checked once for back-compat.

Runs in a subprocess so the 8-device XLA host-platform override never leaks
into other tests (which must see 1 device).
"""

import os
import subprocess
import sys

import pytest

SCRIPT = r"""
import warnings
import numpy as np, jax, jax.numpy as jnp
from repro.apps import pw_advection, tracer_advection
from repro.core import compile_program, make_auto_mesh
from repro.core.frontend import ProgramBuilder
from repro.core.distribute import make_sharded_executor

rng = np.random.default_rng(7)

def data(p, grid):
    fields = {f: rng.normal(size=grid).astype(np.float32) for f in p.input_fields()}
    if "e3t" in fields: fields["e3t"] = np.abs(fields["e3t"]) + 1.0
    if "msk" in fields: fields["msk"] = (fields["msk"] > 0).astype(np.float32)
    scalars = {s: np.float32(0.1) for s in p.scalars}
    coeffs = {c: rng.normal(size=(grid[ax],)).astype(np.float32)
              for c, ax in p.coeffs.items()}
    return fields, scalars, coeffs

def check(p, grid, mesh_shape, names, mesh_axes, backend="pallas"):
    mesh = make_auto_mesh(mesh_shape, names)
    fields, scalars, coeffs = data(p, grid)
    ref = compile_program(p, grid, backend="jnp_naive")(fields, scalars, coeffs)
    ex = compile_program(p, grid, backend=backend, mesh=mesh,
                         mesh_axes=mesh_axes)
    out = ex(fields, scalars, coeffs)
    for k in ref:
        np.testing.assert_allclose(np.asarray(out[k]), np.asarray(ref[k]),
                                   atol=1e-4, rtol=1e-4,
                                   err_msg=f"{p.name}/{k} mesh={mesh_shape} "
                                           f"backend={backend}")

# 3-axis decomposition of both paper kernels
check(pw_advection(), (16, 12, 256), (2, 2, 2), ("X","Y","Z"), ("X","Y","Z"))
check(tracer_advection(), (16, 16, 128), (2, 2, 2), ("X","Y","Z"), ("X","Y","Z"))
# 1-axis and 2-axis layouts (unsharded trailing axes)
check(pw_advection(), (32, 8, 128), (8,), ("X",), ("X", None, None))
check(tracer_advection(), (8, 32, 128), (2, 4), ("X","Y"), ("X", "Y", None))
# jnp backends are sharded citizens too (satellite: backend forwarding)
check(pw_advection(), (16, 12, 128), (2, 2), ("X","Y"), ("X","Y",None),
      backend="jnp_fused")
check(tracer_advection(), (8, 16, 64), (2, 2), ("X","Y"), ("X","Y",None),
      backend="jnp_naive")
# periodic torus across shard boundaries
check(pw_advection(boundary="periodic"), (16, 12, 128), (2, 2, 2),
      ("X","Y","Z"), ("X","Y","Z"))
check(tracer_advection(boundary="periodic"), (8, 16, 64), (2, 4),
      ("X","Y"), ("X","Y",None))
# diagonal-offset corner correctness
b = ProgramBuilder("diag", ndim=2)
x = b.input("x"); o = b.output("o")
b.define(o, x[-1, -1] + x[1, 1] + x[-2, 2])
check(b.build(), (16, 32), (2, 4), ("X","Y"), ("X","Y"))
# same stencil on a torus (wraparound corners)
bp = ProgramBuilder("diagp", ndim=2, boundary="periodic")
xp = bp.input("x"); op = bp.output("o")
bp.define(op, xp[-1, -1] + xp[1, 1] + xp[-2, 2])
check(bp.build(), (16, 32), (2, 4), ("X","Y"), ("X","Y"))
# dependency chain across shard boundary (margin recompute in halo)
b2 = ProgramBuilder("chain", ndim=1)
x2 = b2.input("x"); t2 = b2.temp("t"); o2 = b2.output("o")
b2.define(t2, x2[-1] + x2[1])
b2.define(o2, t2[-1] * t2[1])
check(b2.build(), (64,), (8,), ("X",), ("X",))

# deprecated wrapper: warns, forwards backend, still correct
p = pw_advection()
grid = (16, 12, 128)
mesh = make_auto_mesh((2, 2), ("X", "Y"))
fields, scalars, coeffs = data(p, grid)
ref = compile_program(p, grid, backend="jnp_naive")(fields, scalars, coeffs)
with warnings.catch_warnings(record=True) as w:
    warnings.simplefilter("always")
    legacy = make_sharded_executor(p, grid, mesh, ("X", "Y", None),
                                   backend="jnp_fused")
assert any(issubclass(x.category, DeprecationWarning) for x in w)
assert legacy.plan.backend == "jnp_fused"   # backend forwarded to the plan
assert legacy.local_grid == (8, 6, 128)
out = legacy(fields, scalars, coeffs)
for k in ref:
    np.testing.assert_allclose(np.asarray(out[k]), np.asarray(ref[k]),
                               atol=1e-4, rtol=1e-4)
print("DIST_OK")
"""


@pytest.mark.slow
def test_distributed_halo_exchange():
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src"))
    r = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, f"stdout={r.stdout}\nstderr={r.stderr}"
    assert "DIST_OK" in r.stdout


DEPRECATION_SCRIPT = r"""
import warnings
import numpy as np, jax
from repro.apps import pw_advection
from repro.core import compile_program, make_auto_mesh
from repro.core.distribute import make_sharded_executor

assert jax.device_count() == 2
rng = np.random.default_rng(11)
p = pw_advection()
grid = (8, 8, 128)
fields = {f: rng.normal(size=grid).astype(np.float32) * 0.1
          for f in ("u", "v", "w")}
scalars = {"tcx": np.float32(0.05), "tcy": np.float32(0.05)}
coeffs = {c: np.linspace(0.9, 1.1, grid[2]).astype(np.float32)
          for c in ("tzc1", "tzc2", "tzd1", "tzd2")}

for shape, axes in (((1, 1), ("X", "Y", None)), ((1, 2), ("X", "Y", None))):
    mesh = make_auto_mesh(shape, ("X", "Y"))
    ref = compile_program(p, grid, backend="jnp_fused", mesh=mesh,
                          mesh_axes=axes)(fields, scalars, coeffs)
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        legacy = make_sharded_executor(p, grid, mesh, axes,
                                       backend="jnp_fused")
    assert any(issubclass(x.category, DeprecationWarning) for x in w), shape
    # legacy attribute surface still present
    assert legacy.local_grid == legacy.shard.local_grid
    assert legacy.mesh_axes == legacy.shard.mesh_axes
    out = legacy(fields, scalars, coeffs)
    for k in ref:
        # the wrapper forwards to compile_program with identical arguments,
        # so the compiled graphs are the same: results must BIT-match
        a, b = np.asarray(out[k]), np.asarray(ref[k])
        assert a.tobytes() == b.tobytes(), (shape, k,
                                            np.abs(a - b).max())
print("DEPRECATION_OK")
"""


@pytest.mark.slow
def test_make_sharded_executor_deprecation_bitmatch():
    """The deprecated wrapper warns and its results bit-match
    ``compile_program`` on a degenerate 1x1 and a real 1x2 mesh."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src"))
    r = subprocess.run([sys.executable, "-c", DEPRECATION_SCRIPT], env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, f"stdout={r.stdout}\nstderr={r.stderr}"
    assert "DEPRECATION_OK" in r.stdout
