"""Distributed streaming: ``schedule="stream"`` composed with ``mesh=``.

Runs in a subprocess so the 8-device XLA host-platform override never leaks
into other tests.  Asserts the PR-8 acceptance criteria:

* steps=4 stream-under-mesh (2x2, the stream axis itself sharded) matches
  block-under-mesh AND the single-device stream lowering to 1e-5, for
  pw_advection and tracer_advection, zero and periodic boundaries,
  time_tile in {1, 2};
* the fused distributed stream loop is ONE compiled dispatch: repeated
  calls re-trace nothing;
* a degenerate 1x1 mesh bit-matches the local stream lowering;
* ``strategy="tuned"`` under a mesh measures stream candidates, and a
  warm cache serves a stream plan with zero timed runs — the StreamSpec
  surviving the JSON round-trip.
"""

import os
import subprocess
import sys

import pytest

SCRIPT = r"""
import tempfile
import numpy as np, jax, jax.numpy as jnp
from repro.apps import (pw_advection, pw_advection_update, tracer_advection,
                        tracer_advection_update)
from repro.core import (PlanCache, TuneConfig, auto_plan, compile_program,
                        make_auto_mesh)
from repro.core.tune import plan_from_dict, plan_to_dict, tune_plan

rng = np.random.default_rng(11)
assert jax.device_count() == 8

GRID = (16, 16, 32)
MESH = make_auto_mesh((2, 2), ("X", "Y"))   # shards the stream axis (0)
AXES = ("X", "Y", None)

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

CASES = [("pw", pw_advection, pw_advection_update, pw_data),
         ("tracer", tracer_advection, tracer_advection_update, tracer_data)]

# --- parity sweep: stream+mesh vs block+mesh vs local stream -------------
for name, prog_fn, update_fn, data_fn in CASES:
    for bnd in ("zero", "periodic"):
        p = prog_fn(boundary=bnd)
        fields, scalars, coeffs = data_fn(GRID)
        for tt in (1, 2):
            upd = update_fn()
            got = compile_program(
                p, GRID, schedule="stream", time_tile=tt, steps=4,
                update=upd, mesh=MESH, mesh_axes=AXES)(fields, scalars,
                                                       coeffs)
            blk = compile_program(
                p, GRID, schedule="block", steps=4, update=upd,
                mesh=MESH, mesh_axes=AXES)(fields, scalars, coeffs)
            loc = compile_program(
                p, GRID, schedule="stream", time_tile=tt, steps=4,
                update=upd)(fields, scalars, coeffs)
            for k in loc:
                np.testing.assert_allclose(
                    np.asarray(got[k]), np.asarray(blk[k]),
                    atol=1e-5, rtol=1e-5,
                    err_msg=f"{name}/{bnd}/T={tt}/{k} vs block-under-mesh")
                np.testing.assert_allclose(
                    np.asarray(got[k]), np.asarray(loc[k]),
                    atol=1e-5, rtol=1e-5,
                    err_msg=f"{name}/{bnd}/T={tt}/{k} vs local stream")
print("PARITY_OK")

# --- one dispatch: repeated calls re-trace nothing -----------------------
p = pw_advection(boundary="zero")
fields, scalars, coeffs = pw_data(GRID)
traces = [0]
base = pw_advection_update()
def counted(fields_, outputs, scalars_=None):
    traces[0] += 1
    return base(fields_, outputs)
ex = compile_program(p, GRID, schedule="stream", time_tile=2, steps=4,
                     update=counted, mesh=MESH, mesh_axes=AXES)
out1 = ex(fields, scalars, coeffs)
jax.block_until_ready(list(out1.values()))
n = traces[0]
assert n >= 1
for _ in range(2):
    out = ex(fields, scalars, coeffs)
    jax.block_until_ready(list(out.values()))
assert traces[0] == n, f"warm calls re-traced: {traces[0]} != {n}"
print("TRACE_ONCE_OK")

# --- degenerate 1x1 mesh bit-matches the local stream lowering -----------
mesh1 = make_auto_mesh((1,), ("X",))
upd = pw_advection_update()
g1 = compile_program(p, GRID, schedule="stream", time_tile=2, steps=4,
                     update=upd, mesh=mesh1,
                     mesh_axes=("X", None, None))(fields, scalars, coeffs)
l1 = compile_program(p, GRID, schedule="stream", time_tile=2, steps=4,
                     update=upd)(fields, scalars, coeffs)
for k in l1:
    assert np.array_equal(np.asarray(g1[k]), np.asarray(l1[k])), k
print("BITMATCH_1X1_OK")

# --- tuned under mesh: stream candidates measured; warm cache serves a
# --- stream plan with zero timed runs (StreamSpec JSON round-trip) -------
calls = [0]
def fake_timer(fn):
    calls[0] += 1
    fn()
    return float(calls[0])
cfg = TuneConfig(timer=fake_timer, steps=2, max_measured=8,
                 strategies=("fused",), carry_writes=("repad",),
                 time_tiles=(2,))
with tempfile.TemporaryDirectory() as tmp:
    cache = PlanCache(path=tmp + "/plans.json")
    res = tune_plan(p, GRID, backend="pallas", update=pw_advection_update(),
                    config=cfg, cache=cache, mesh=MESH, mesh_axes=AXES)
    assert calls[0] > 0
    assert any(c.plan.schedule == "stream" for c in res.measured), \
        "no stream candidate measured under the mesh"
    # pin a stream winner into the record, then verify the warm path
    splan = auto_plan(p, GRID, schedule="stream", time_tile=2, steps=2)
    cache.store(res.key, {**res.record, "plan": plan_to_dict(splan),
                          "carry_write": "repad"})
    n_timed = calls[0]
    ex = compile_program(p, GRID, backend="pallas", strategy="tuned",
                         steps=4, update=pw_advection_update(),
                         tune_config=cfg, plan_cache=cache,
                         mesh=MESH, mesh_axes=AXES)
    assert calls[0] == n_timed, "warm tuned compile must measure nothing"
    assert ex.plan.schedule == "stream" and ex.plan.stream is not None
    # the legalised stream geometry survives a JSON round-trip bit-for-bit
    rt = plan_from_dict(plan_to_dict(ex.plan))
    assert rt.schedule == "stream" and rt.stream == ex.plan.stream
    # ...and a fresh cache handle re-reads the stored stream plan from disk
    rec = PlanCache(path=tmp + "/plans.json").lookup(res.key)
    assert plan_from_dict(rec["plan"]).stream == splan.stream
    tuned = ex(fields, scalars, coeffs)
    ref = compile_program(p, GRID, schedule="block", steps=4,
                          update=pw_advection_update(), mesh=MESH,
                          mesh_axes=AXES)(fields, scalars, coeffs)
    for k in ref:
        np.testing.assert_allclose(np.asarray(tuned[k]), np.asarray(ref[k]),
                                   atol=1e-5, rtol=1e-5, err_msg=k)
print("TUNED_STREAM_MESH_OK")
print("STREAM_MESH_OK")
"""


@pytest.mark.slow
def test_stream_under_mesh():
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src"))
    r = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                       capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, f"stdout={r.stdout}\nstderr={r.stderr}"
    assert "STREAM_MESH_OK" in r.stdout
