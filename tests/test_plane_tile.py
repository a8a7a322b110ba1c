"""Spatial unrolling (``plane_tile``) of the stream sweep.

Acceptance invariants:

* advancing P consecutive planes per sweep grid step is numerically
  invisible: 1e-5 parity against ``plane_tile=1`` for both paper kernels
  under zero AND periodic boundaries, single-step and composed with the
  ``time_tile=4`` temporal chain, sweep remainders (``n_steps % P != 0``)
  included;
* legalisation demotes an over-wide sweep (``n_steps < P``) to an
  effective width of 1 with a reason (mirroring ``chain_split_reason``)
  instead of miscompiling;
* ``vmem_cost`` prices the P-widened windows (wider sweep = more VMEM);
* the tuner enumerates ``plane_tiles=(1, 2, 4)`` in both single-step and
  fused-loop modes, and a tuned ``plane_tile`` survives the JSON
  plan-cache round trip into ``strategy="tuned"`` with zero timed runs;
* a stale v3 cache file is a clean miss rewritten at v4, never a crash;
* serving executors with different ``plane_tile`` never share a slot
  (``bucket_fingerprint``).
"""

import dataclasses
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from repro.apps import pw_advection
from repro.core import (CompileOptions, PlanCache, TuneConfig,
                        compile_program, effective_plane_tile,
                        plan_to_dict, plane_split_reason)
from repro.core.schedule import auto_plan, bucket_fingerprint, vmem_cost
from repro.core.tune import CACHE_SCHEMA_VERSION, cache_key
from test_stream import KERNELS


# ------------------------------------------------------------ parity

@pytest.mark.parametrize("boundary", ["zero", "periodic"])
@pytest.mark.parametrize("kernel", sorted(KERNELS))
@pytest.mark.parametrize("pt", [2, 4])
def test_plane_tiled_sweep_matches_plane_at_a_time(kernel, boundary, pt):
    """plane_tile=P (P in {2,4}) is numerically invisible for a single
    sweep: the unrolled step computes the same planes the one-plane sweep
    does, remainder tiles (``n_steps % P != 0``) included — the tracer
    grid's 6-plane stream axis leaves a remainder under P=4."""
    prog_fn, _update, data_fn, grid = KERNELS[kernel]
    p = prog_fn(boundary=boundary)
    fields, scalars, coeffs = data_fn(grid)
    ex1 = compile_program(p, grid, schedule="stream")
    exP = compile_program(p, grid, options=CompileOptions(
        schedule="stream", plane_tile=pt))
    assert exP.plan.plane_tile == pt          # the request is recorded
    assert exP.plan.stream.plane_tile == pt   # ...and survives legalisation
    r1 = ex1(fields, scalars, coeffs)
    rP = exP(fields, scalars, coeffs)
    for f in r1:
        np.testing.assert_allclose(np.asarray(rP[f]), np.asarray(r1[f]),
                                   atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("boundary", ["zero", "periodic"])
@pytest.mark.parametrize("kernel", sorted(KERNELS))
@pytest.mark.parametrize("pt", [2, 4])
def test_plane_tile_composes_with_temporal_chain(kernel, boundary, pt):
    """The PxT tile: plane_tile=P through a time_tile=4 fused loop matches
    the P=1 loop at the same chain depth (periodic / multi-region programs
    demote the chain, not the sweep width — parity must hold either way)."""
    prog_fn, update, data_fn, grid = KERNELS[kernel]
    p = prog_fn(boundary=boundary)
    fields, scalars, coeffs = data_fn(grid)
    steps = 8
    ex1 = compile_program(p, grid, options=CompileOptions(
        schedule="stream", steps=steps, update=update, time_tile=4))
    exP = compile_program(p, grid, options=CompileOptions(
        schedule="stream", steps=steps, update=update, time_tile=4,
        plane_tile=pt))
    assert exP.plan.stream.plane_tile == pt
    r1 = ex1(fields, scalars, coeffs)
    rP = exP(fields, scalars, coeffs)
    for f in r1:
        np.testing.assert_allclose(np.asarray(rP[f]), np.asarray(r1[f]),
                                   atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("kernel,pt", [("pw_advection", 3),
                                       ("tracer_advection", 4)])
def test_plane_tile_sweep_remainder(kernel, pt):
    """n_steps % P != 0: the final (shallower) tile stores only the planes
    that exist — pw's 8-plane axis under P=3 also leaves the output blocks
    misaligned with the sweep tiles (span % P != 0), exercising the
    staging-realignment path."""
    prog_fn, update, data_fn, grid = KERNELS[kernel]
    p = prog_fn()
    assert grid[0] % pt != 0
    fields, scalars, coeffs = data_fn(grid)
    for opts1, optsP in [
        (CompileOptions(schedule="stream"),
         CompileOptions(schedule="stream", plane_tile=pt)),
        (CompileOptions(schedule="stream", steps=5, update=update),
         CompileOptions(schedule="stream", steps=5, update=update,
                        plane_tile=pt)),
    ]:
        r1 = compile_program(p, grid, options=opts1)(fields, scalars, coeffs)
        rP = compile_program(p, grid, options=optsP)(fields, scalars, coeffs)
        for f in r1:
            np.testing.assert_allclose(np.asarray(rP[f]), np.asarray(r1[f]),
                                       atol=1e-5, rtol=1e-5)


# ------------------------------------------------------------ legalisation

@pytest.mark.parametrize("boundary", ["zero", "periodic"])
@pytest.mark.parametrize("kernel", sorted(KERNELS))
def test_plane_tile_demotes_overwide_sweep(kernel, boundary):
    """n_steps < P: a sweep step would span more planes than the domain
    holds — demoted to an effective width of 1 with a reason, mirroring
    ``chain_split_reason``; parity against plane_tile=1 still holds."""
    prog_fn, _update, data_fn, grid = KERNELS[kernel]
    p = prog_fn(boundary=boundary)
    small = (3,) + grid[1:]
    fields, scalars, coeffs = data_fn(small)
    exP = compile_program(p, small, options=CompileOptions(
        schedule="stream", plane_tile=4))
    assert exP.plan.plane_tile == 4           # the request survives
    assert exP.plan.stream.plane_tile == 1    # ...the unroll does not
    reason = plane_split_reason(p, 4, small)
    assert reason is not None and "exceeds the stream extent" in reason
    assert effective_plane_tile(p, 4, small) == 1
    # without a grid, legality is undecidable yet: the request stands
    assert plane_split_reason(p, 4) is None
    assert effective_plane_tile(p, 4) == 4
    r1 = compile_program(p, small, schedule="stream")(fields, scalars,
                                                      coeffs)
    rP = exP(fields, scalars, coeffs)
    for f in r1:
        np.testing.assert_allclose(np.asarray(rP[f]), np.asarray(r1[f]),
                                   atol=1e-5, rtol=1e-5)


def test_plane_tile_validation():
    p = pw_advection()
    grid = (8, 8, 32)
    with pytest.raises(ValueError):
        compile_program(p, grid, schedule="stream", plane_tile=0)
    # spatial unrolling widens the stream sweep: block tiles have none
    with pytest.raises(ValueError, match="stream"):
        auto_plan(p, grid, plane_tile=2)
    with pytest.raises(ValueError, match="stream"):
        dataclasses.replace(auto_plan(p, grid), plane_tile=2)
    # retargeting a plane-tiled stream plan to "block" resets the width
    ex = compile_program(p, grid, options=CompileOptions(
        backend="pallas", plan=auto_plan(p, grid, schedule="stream",
                                         plane_tile=4),
        schedule="block"))
    assert ex.plan.plane_tile == 1


def test_vmem_cost_prices_plane_width():
    """A P-wide sweep step holds P extra input planes per window and the
    P output planes (plus staging realignment) in VMEM — the cost model
    must see that, or the tuner would admit widths that cannot fit."""
    p = pw_advection()
    grid = (8, 8, 32)
    costs = [vmem_cost(p, auto_plan(p, grid, schedule="stream",
                                    plane_tile=pt, vmem_budget=1 << 40),
                       grid)
             for pt in (1, 2, 4)]
    assert costs[0] < costs[1] < costs[2]


# ------------------------------------------------------------ tuner + cache

@pytest.mark.parametrize("with_loop", [True, False])
def test_tuner_enumerates_plane_tiles(with_loop):
    """plane_tiles=(1,2,4) are distinct stream candidates in BOTH modes —
    unlike the temporal chain, a wider sweep step needs no update rule."""
    from repro.core.tune import _candidates
    cfg = TuneConfig(steps=4, timer=lambda fn: 1.0)
    cands = _candidates(pw_advection(), (8, 8, 32), "pallas",
                        "float32", cfg, with_loop=with_loop)
    eff = {c.plan.stream.plane_tile for c in cands
           if c.plan.schedule == "stream" and c.plan.stream is not None}
    assert {1, 2, 4} <= eff


def test_tuned_plane_tile_round_trips_through_plan_cache(tmp_path):
    """A tuned plane-tiled plan survives the on-disk JSON cache: the stored
    ``plane_tile`` deserialises into ``strategy="tuned"`` with zero timed
    runs and drives the unrolled lowering to the same numbers."""
    prog_fn, update, data_fn, grid = KERNELS["pw_advection"]
    p = prog_fn()
    fields, scalars, coeffs = data_fn(grid)
    plan = auto_plan(p, grid, schedule="stream", plane_tile=4)
    assert plan.stream.plane_tile == 4
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
    assert ex.plan.plane_tile == 4 and ex.plan.stream.plane_tile == 4
    ref = compile_program(p, grid, schedule="stream", steps=4,
                          update=update)(fields, scalars, coeffs)
    got = ex(fields, scalars, coeffs)
    for k in ref:
        np.testing.assert_allclose(np.asarray(got[k]), np.asarray(ref[k]),
                                   atol=1e-5, rtol=1e-5)


def test_stale_v3_cache_is_clean_miss_and_rewritten(tmp_path):
    """A v3-era cache file (pre-plane_tile) never serves entries — its
    records lack the new field, so decoding them silently would pin every
    tuned plan to an implicit width.  Lookup is a clean miss; the next
    store rewrites the file at v4."""
    assert CACHE_SCHEMA_VERSION == 4
    path = str(tmp_path / "plans.json")
    p = pw_advection()
    grid = (8, 8, 32)
    key = cache_key(p, grid, "pallas", "float32", "loop")
    plan_doc = plan_to_dict(auto_plan(p, grid, schedule="stream"))
    plan_doc.pop("plane_tile", None)          # a genuine v3 record
    with open(path, "w") as f:
        json.dump({"version": 3, "entries": {
            key: {"plan": plan_doc, "carry_write": "repad"}}}, f)
    cache = PlanCache(path=path)
    assert cache.lookup(key) is None          # stale version = miss
    cache.store(key, {"plan": plan_to_dict(auto_plan(p, grid)),
                      "carry_write": "repad"})
    doc = json.load(open(path))
    assert doc["version"] == CACHE_SCHEMA_VERSION
    assert key in doc["entries"]


def test_bucket_fingerprint_distinguishes_plane_tile():
    p = pw_advection()
    keys = {bucket_fingerprint(p, (16, 16, 16), backend="pallas",
                               schedule="stream", plane_tile=pt)
            for pt in (None, 1, 2, 4)}
    assert len(keys) == 4


# ------------------------------------------------------------ mesh

MESH_SCRIPT = r"""
import numpy as np, jax
from repro.apps import (pw_advection, pw_advection_update, tracer_advection,
                        tracer_advection_update)
from repro.core import CompileOptions, compile_program, make_auto_mesh

rng = np.random.default_rng(11)
assert jax.device_count() == 2
MESH = make_auto_mesh((1, 2), ("X", "Y"))
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

CASES = [("pw", pw_advection, pw_advection_update, pw_data, (8, 8, 32)),
         ("tracer", tracer_advection, tracer_advection_update, tracer_data,
          (6, 8, 32))]
for name, prog_fn, update_fn, data_fn, grid in CASES:
    for bnd in ("zero", "periodic"):
        p = prog_fn(boundary=bnd)
        fields, scalars, coeffs = data_fn(grid)
        upd = update_fn()
        r1 = compile_program(p, grid, options=CompileOptions(
            schedule="stream", steps=8, update=upd, time_tile=4,
            mesh=MESH, mesh_axes=AXES))(fields, scalars, coeffs)
        for pt in (2, 4):
            exP = compile_program(p, grid, options=CompileOptions(
                schedule="stream", steps=8, update=upd, time_tile=4,
                plane_tile=pt, mesh=MESH, mesh_axes=AXES))
            assert exP.plan.plane_tile == pt
            rP = exP(fields, scalars, coeffs)
            for k in r1:
                np.testing.assert_allclose(
                    np.asarray(rP[k]), np.asarray(r1[k]),
                    atol=1e-5, rtol=1e-5,
                    err_msg=f"{name}/{bnd}/P={pt}/{k}")
print("PLANE_TILE_MESH_OK")
"""


@pytest.mark.slow
def test_plane_tile_under_mesh():
    """PR acceptance: plane_tile in {2, 4} composed with time_tile=4 and a
    1x2 mesh matches plane_tile=1 to 1e-5 for both apps, both boundaries.
    Subprocess so the simulated-device override never leaks into other
    tests."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src"))
    r = subprocess.run([sys.executable, "-c", MESH_SCRIPT], env=env,
                       capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, f"stdout={r.stdout}\nstderr={r.stderr}"
    assert "PLANE_TILE_MESH_OK" in r.stdout
