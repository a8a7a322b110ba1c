"""Distributed fused time loop: ``compile_program(..., mesh=, steps=N)``.

Runs in a subprocess so the 8-device XLA host-platform override never leaks
into other tests (which must see 1 device).  Asserts the PR-4 acceptance
criteria:

* N distributed steps (pw_advection and tracer_advection, steps=4, zero
  AND periodic boundaries) match the host-side ``run_time_loop`` reference
  to 1e-5, with halo exchange inside the loop carry;
* the whole loop is ONE compiled dispatch: the update rule traces exactly
  once regardless of N and repeated calls hit the jit cache;
* a degenerate 1x1 mesh bit-matches the single-device fused loop;
* ``strategy="tuned"`` works under a mesh, with a cache key separating
  mesh topologies (zero timed runs on the second compile);
* the jnp backends are first-class sharded citizens (temp accesses route
  through ppermute shifts, coefficients slice at the shard origin).
"""

import os
import subprocess
import sys

import pytest

SCRIPT = r"""
import numpy as np, jax, jax.numpy as jnp
from repro.apps import (pw_advection, pw_advection_update, tracer_advection,
                        tracer_advection_update)
from repro.core import (compile_program, make_auto_mesh, run_time_loop,
                        PlanCache, TuneConfig)

rng = np.random.default_rng(7)
assert jax.device_count() == 8

def pw_data(grid):
    fields = {f: rng.normal(size=grid).astype(np.float32) * 0.1
              for f in ("u", "v", "w")}
    scalars = {"tcx": np.float32(0.05), "tcy": np.float32(0.05)}
    coeffs = {c: np.linspace(0.9, 1.1, grid[2]).astype(np.float32)
              for c in ("tzc1", "tzc2", "tzd1", "tzd2")}
    return fields, scalars, coeffs

def tracer_data(grid):
    fields = {
        "t": rng.normal(size=grid).astype(np.float32) + 15.0,
        "un": rng.normal(size=grid).astype(np.float32) * 0.2,
        "vn": rng.normal(size=grid).astype(np.float32) * 0.2,
        "wn": rng.normal(size=grid).astype(np.float32) * 0.05,
        "e3t": np.abs(rng.normal(size=grid)).astype(np.float32) + 1.0,
        "msk": (rng.uniform(size=grid) > 0.05).astype(np.float32)}
    scalars = {"rdt": np.float32(0.05), "zeps": np.float32(1e-6)}
    coeffs = {"ztfreez": np.full(grid[2], -1.8, np.float32)}
    return fields, scalars, coeffs

MESH = make_auto_mesh((2, 2, 2), ("X", "Y", "Z"))
AXES = ("X", "Y", "Z")

def check_loop(prog_fn, update, grid, data, backends, steps=4):
    for bnd in ("zero", "periodic"):
        p = prog_fn(boundary=bnd)
        fields, scalars, coeffs = data
        ref = run_time_loop(compile_program(p, grid, backend="jnp_naive"),
                            dict(fields), scalars, coeffs, steps, update)
        for bk in backends:
            ex = compile_program(p, grid, backend=bk, mesh=MESH,
                                 mesh_axes=AXES, steps=steps, update=update)
            assert ex.shard is not None and ex.shard.local_grid == tuple(
                g // 2 for g in grid)
            got = ex(fields, scalars, coeffs)
            for k in ref:
                np.testing.assert_allclose(
                    np.asarray(got[k]), np.asarray(ref[k]),
                    atol=1e-5, rtol=1e-5,
                    err_msg=f"{p.name}/{k} backend={bk} boundary={bnd}")

# --- steps=4 parity, zero + periodic, pallas and jnp backends ------------
g = (8, 8, 128)
check_loop(pw_advection, pw_advection_update(0.1), g, pw_data(g),
           ("pallas", "jnp_fused"))
print("LOOP_PW_OK")
gt = (8, 8, 64)
check_loop(tracer_advection, tracer_advection_update(), gt, tracer_data(gt),
           ("pallas", "jnp_fused"))
print("LOOP_TRACER_OK")

# --- one dispatch: update traced once, second call hits the jit cache ----
p = pw_advection()
fields, scalars, coeffs = pw_data(g)
inner = pw_advection_update(0.1)
traces = [0]
def update(fl, out):
    traces[0] += 1
    return inner(fl, out)
ex = compile_program(p, g, backend="jnp_fused", mesh=MESH, mesh_axes=AXES,
                     steps=5, update=update)
ex(fields, scalars, coeffs)
ex(fields, scalars, coeffs)
assert traces[0] == 1, f"update traced {traces[0]}x, want once"
print("TRACE_ONCE_OK")

# --- 1x1 mesh bit-matches the single-device fused loop -------------------
mesh1 = make_auto_mesh((1,), ("X",))
for bk in ("pallas", "jnp_fused", "jnp_naive"):
    a = compile_program(p, g, backend=bk, steps=4,
                        update=inner)(fields, scalars, coeffs)
    b = compile_program(p, g, backend=bk, mesh=mesh1, mesh_axes=("X",),
                        steps=4, update=inner)(fields, scalars, coeffs)
    for k in a:
        np.testing.assert_array_equal(np.asarray(a[k]), np.asarray(b[k]),
                                      err_msg=f"{bk}/{k}")
print("MESH1_BITMATCH_OK")

# --- single-step sharded parity on all three backends --------------------
ref = compile_program(p, g, backend="jnp_naive")(fields, scalars, coeffs)
for bk in ("pallas", "jnp_fused", "jnp_naive"):
    out = compile_program(p, g, backend=bk, mesh=MESH,
                          mesh_axes=AXES)(fields, scalars, coeffs)
    for k in ref:
        np.testing.assert_allclose(np.asarray(out[k]), np.asarray(ref[k]),
                                   atol=1e-4, rtol=1e-4, err_msg=f"{bk}/{k}")
print("SINGLE_STEP_OK")

# --- tuned strategy under a mesh: search once, then a pure cache hit -----
calls = [0]
def fake_timer(fn):
    calls[0] += 1
    fn()
    return float(calls[0])
cfg = TuneConfig(timer=fake_timer, max_measured=2, steps=2)
cache = PlanCache(path=None)
ex = compile_program(p, g, backend="jnp_fused", strategy="tuned", mesh=MESH,
                     mesh_axes=AXES, steps=2, update=inner,
                     tune_config=cfg, plan_cache=cache)
n_measured = calls[0]
assert n_measured > 0
compile_program(p, g, backend="jnp_fused", strategy="tuned", mesh=MESH,
                mesh_axes=AXES, steps=2, update=inner,
                tune_config=cfg, plan_cache=cache)
assert calls[0] == n_measured, "second tuned compile must measure nothing"
out = ex(fields, scalars, coeffs)
assert set(out) == {"u", "v", "w"}
print("TUNED_MESH_OK")
print("DIST_LOOP_OK")
"""


@pytest.mark.slow
def test_distributed_fused_loop():
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src"))
    r = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                       capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, f"stdout={r.stdout}\nstderr={r.stderr}"
    assert "DIST_LOOP_OK" in r.stdout
