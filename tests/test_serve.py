"""Stencil serving engine tests: bucketing exactness, executor caching
(zero re-traces warm), batching, the async front, and plan-record reuse."""

import queue
import time

import numpy as np
import pytest

from repro import hw
from repro.apps.advection import pw_advection, pw_advection_update
from repro.core.pipeline import compile_program
from repro.core.schedule import (PLAN_SCHEMA_VERSION, bucket_for,
                                 program_reach, quantize_extent)
from repro.core.tune import PlanCache, make_serve_record, read_serve_record
from repro.serve import (StencilEngine, StencilRequest, crop, embed_coeff,
                         embed_field, serving_program, size_scalar_names)

RNG = np.random.default_rng(7)


def make_data(p, grid, seed=0):
    rng = np.random.default_rng(seed)
    fields = {f: rng.normal(size=grid).astype(np.float32) * 0.1
              for f in p.input_fields()}
    scalars = {s: 0.05 for s in p.scalars}
    coeffs = {c: (np.abs(rng.normal(size=(grid[ax],))) + 0.5
                  ).astype(np.float32)
              for c, ax in p.coeffs.items()}
    return fields, scalars, coeffs


def make_request(p, grid, seed=0, steps=3, dt=0.01, timeout=None):
    fields, scalars, coeffs = make_data(p, grid, seed)
    return StencilRequest(program=p, fields=fields, scalars=scalars,
                          coeffs=coeffs, steps=steps,
                          update=pw_advection_update(dt),
                          update_key=f"pw/dt={dt}", timeout=timeout)


def reference(p, grid, req, backend="jnp_fused"):
    ex = compile_program(p, grid, backend=backend, steps=req.steps,
                         update=req.update)
    return ex(req.fields, req.scalars, req.coeffs)


# --------------------------------------------------------------------------
# bucketing units
# --------------------------------------------------------------------------

def test_quantize_extent_policy():
    # below the quantum: next power of two
    assert quantize_extent(3) == 4
    assert quantize_extent(17) == 32
    assert quantize_extent(100, lane_axis=True) == 128
    # at/above: align to the quantum
    assert quantize_extent(33) == 64
    assert quantize_extent(64) == 64
    assert quantize_extent(129, lane_axis=True) == 256
    assert quantize_extent(128, lane_axis=True) == 128
    with pytest.raises(ValueError):
        quantize_extent(0)


def test_bucket_for_keeps_reach_clearance():
    p = pw_advection()
    reach = program_reach(p)
    spec = bucket_for(p, (10, 12, 20))
    for a in range(3):
        lo, hi = int(reach[a, 0]), int(reach[a, 1])
        assert spec.offset[a] == lo
        assert spec.bucket[a] >= spec.grid[a] + lo + hi
    # lane axis quantised to the lane width once big enough
    big = bucket_for(p, (10, 12, hw.LANE))
    assert big.bucket[-1] % hw.LANE == 0


def test_grids_share_buckets():
    p = pw_advection()
    a = bucket_for(p, (8, 8, 16))
    b = bucket_for(p, (7, 8, 18))
    assert a.bucket == b.bucket and a.offset == b.offset
    assert a.grid != b.grid


def test_serving_program_appends_size_scalars_idempotently():
    p = pw_advection()
    sp = serving_program(p)
    assert sp.scalars == p.scalars + size_scalar_names(3)
    assert serving_program(sp) is sp            # idempotent
    assert p.scalars == ["tcx", "tcy"]          # original untouched
    sp.validate()


def test_embed_crop_roundtrip():
    p = pw_advection()
    spec = bucket_for(p, (5, 6, 9))
    x = RNG.normal(size=(5, 6, 9)).astype(np.float32)
    for bnd in ("zero", "periodic"):
        e = embed_field(x, spec, bnd)
        assert e.shape == spec.bucket
        np.testing.assert_array_equal(crop(e, spec), x)
    # zero embedding really is zero outside the interior
    ez = embed_field(x, spec, "zero")
    ez[spec.interior()] = 0
    assert not ez.any()
    # periodic embedding wraps: one cell left of the interior == last cell
    ep = embed_field(x, spec, "periodic")
    o = spec.offset
    np.testing.assert_array_equal(ep[o[0] - 1, o[1]:o[1] + 6, o[2]:o[2] + 9],
                                  x[-1])


def test_embed_coeff_modes():
    p = pw_advection()
    spec = bucket_for(p, (5, 6, 9))
    c = np.arange(9, dtype=np.float32) + 1
    z = embed_coeff(c, 2, spec, "zero")
    assert z.shape == (spec.bucket[2],)
    np.testing.assert_array_equal(z[spec.offset[2]:spec.offset[2] + 9], c)
    assert z.sum() == c.sum()
    w = embed_coeff(c, 2, spec, "periodic")
    np.testing.assert_array_equal(
        w, c[(np.arange(spec.bucket[2]) - spec.offset[2]) % 9])


# --------------------------------------------------------------------------
# exactness: bucketed execution == direct compile at the true grid
# --------------------------------------------------------------------------

@pytest.mark.parametrize("boundary", ["zero", "periodic"])
@pytest.mark.parametrize("backend", ["jnp_fused", "pallas"])
def test_bucketed_fused_loop_matches_direct(boundary, backend):
    p = pw_advection(boundary=boundary)
    grid = (6, 7, 12)
    req = make_request(p, grid, seed=3, steps=3)
    with StencilEngine(backend=backend, window_s=0.0) as eng:
        res = eng.run(req, timeout=300)
    ref = reference(p, grid, req, backend=backend)
    assert set(res.outputs) == set(ref)
    for k in ref:
        np.testing.assert_allclose(res.outputs[k], np.asarray(ref[k]),
                                   atol=1e-5, rtol=1e-5)


def test_bucketed_single_apply_matches_direct():
    p = pw_advection(boundary="periodic")
    grid = (5, 9, 14)
    fields, scalars, coeffs = make_data(p, grid, seed=11)
    req = StencilRequest(program=p, fields=fields, scalars=scalars,
                         coeffs=coeffs)
    with StencilEngine(backend="jnp_fused", window_s=0.0) as eng:
        res = eng.run(req, timeout=300)
    ref = compile_program(p, grid, backend="jnp_fused")(fields, scalars,
                                                        coeffs)
    for k in ref:
        np.testing.assert_allclose(res.outputs[k], np.asarray(ref[k]),
                                   atol=1e-5, rtol=1e-5)


def test_boundary_override_on_request():
    p = pw_advection()                       # declared zero
    grid = (6, 6, 12)
    req = make_request(p, grid, seed=5)
    req.boundary = "periodic"
    with StencilEngine(window_s=0.0) as eng:
        res = eng.run(req, timeout=300)
    ref = reference(p.with_boundary("periodic"), grid, req)
    for k in ref:
        np.testing.assert_allclose(res.outputs[k], np.asarray(ref[k]),
                                   atol=1e-5, rtol=1e-5)


# --------------------------------------------------------------------------
# executor cache: warm requests re-trace nothing
# --------------------------------------------------------------------------

def test_warm_requests_zero_retraces():
    p = pw_advection()
    with StencilEngine(window_s=0.0) as eng:
        eng.run(make_request(p, (8, 8, 16), seed=0), timeout=300)
        assert eng.stats.traces >= 1 and eng.stats.compiles == 1
        warm = eng.stats.traces
        # same grid again, and a *different* grid in the same bucket
        eng.run(make_request(p, (8, 8, 16), seed=1), timeout=300)
        eng.run(make_request(p, (7, 8, 18), seed=2), timeout=300)
        assert bucket_for(serving_program(p), (7, 8, 18)).bucket == \
            bucket_for(serving_program(p), (8, 8, 16)).bucket
        assert eng.stats.traces == warm, "warm request re-traced the update"
        assert eng.stats.compiles == 1
        assert eng.stats.exec_hits == 2 and eng.stats.exec_misses == 1
        assert eng.stats.cache_hit_rate() > 0


def test_distinct_buckets_get_distinct_executors():
    p = pw_advection()
    with StencilEngine(window_s=0.0) as eng:
        eng.run(make_request(p, (8, 8, 16), seed=0), timeout=300)
        eng.run(make_request(p, (8, 8, 40), seed=0), timeout=300)
        assert eng.stats.compiles == 2


# --------------------------------------------------------------------------
# batching + async front
# --------------------------------------------------------------------------

def test_same_bucket_requests_batch_together():
    p = pw_advection()
    reqs = [make_request(p, g, seed=i)
            for i, g in enumerate([(8, 8, 16), (7, 7, 15), (7, 8, 18)])]
    eng = StencilEngine(window_s=0.5, max_batch=4, autostart=False)
    futs = [eng.submit(r) for r in reqs]
    eng.start()
    try:
        results = [f.result(300) for f in futs]
        assert {r.batch_size for r in results} == {3}
        assert eng.stats.batches == 1
        assert eng.stats.padded_slots == 1          # 3 padded to 4
        assert 0 < eng.stats.occupancy() < 1
        # every answer still matches its own direct compile
        for req, res in zip(reqs, results):
            ref = reference(p, req.grid(), req)
            for k in ref:
                np.testing.assert_allclose(res.outputs[k],
                                           np.asarray(ref[k]),
                                           atol=1e-5, rtol=1e-5)
    finally:
        eng.close()


@pytest.mark.parametrize("error,unrolls", [
    (NotImplementedError("Batching rule for 'foo' not implemented"), True),
    (NotImplementedError("Unimplemented primitive in Pallas TPU lowering"),
     False),
    (RuntimeError("scoped vmem limit exceeded"), False)])
def test_only_a_missing_batching_rule_unrolls(error, unrolls):
    """The unrolled per-request dispatch stands in for vmap only when JAX
    has no batching rule; a compile error fails the request instead of
    quietly losing batching."""
    p = pw_advection()
    req = make_request(p, (8, 8, 16))
    with StencilEngine(window_s=0.0) as eng:
        first = eng.run(req, timeout=300)
        ex = eng._executors[first.key]

        def refuse(*args):
            raise error
        ex.batched = refuse
        if unrolls:
            res = eng.run(req, timeout=300)
            for k in first.outputs:
                np.testing.assert_allclose(res.outputs[k], first.outputs[k],
                                           atol=1e-6, rtol=1e-6)
        else:
            with pytest.raises(type(error)):
                eng.run(req, timeout=300)
        assert ex.vmap_failed is unrolls
        assert eng.stats.unrolled_batches == int(unrolls)


def test_mixed_shape_traffic_end_to_end():
    p = pw_advection(boundary="periodic")
    grids = [(8, 8, 16), (6, 7, 14), (8, 8, 24), (5, 8, 16), (8, 8, 16)]
    reqs = [make_request(p, g, seed=10 + i) for i, g in enumerate(grids)]
    with StencilEngine(window_s=0.05, max_batch=4) as eng:
        results = eng.map(reqs, timeout=300)
        for req, res in zip(reqs, results):
            ref = reference(p, req.grid(), req)
            for k in ref:
                np.testing.assert_allclose(res.outputs[k],
                                           np.asarray(ref[k]),
                                           atol=1e-5, rtol=1e-5)
        s = eng.stats
        assert s.completed == len(grids) and s.failed == 0
        assert s.cache_hit_rate() > 0
        assert s.throughput() > 0 and s.p99_ms() >= s.p50_ms() > 0


def test_bounded_queue_backpressure():
    p = pw_advection()
    eng = StencilEngine(queue_depth=2, autostart=False)
    eng.submit(make_request(p, (8, 8, 16)))
    eng.submit(make_request(p, (8, 8, 16)))
    with pytest.raises(queue.Full):
        eng.submit(make_request(p, (8, 8, 16)))
    eng.close()
    assert eng.stats.failed == 2               # drained on close


def test_request_timeout_expires_in_queue():
    p = pw_advection()
    eng = StencilEngine(autostart=False)
    fut = eng.submit(make_request(p, (8, 8, 16), timeout=0.01))
    time.sleep(0.05)
    eng.start()
    try:
        with pytest.raises(TimeoutError):
            fut.result(60)
        assert eng.stats.timeouts == 1
    finally:
        eng.close()


def test_submit_validation():
    p = pw_advection()
    eng = StencilEngine(autostart=False)
    fields, scalars, coeffs = make_data(p, (8, 8, 16))
    with pytest.raises(ValueError, match="steps and update"):
        eng.submit(StencilRequest(program=p, fields=fields, scalars=scalars,
                                  coeffs=coeffs, steps=3))
    with pytest.raises(ValueError, match="missing input fields"):
        eng.submit(StencilRequest(program=p, fields={"u": fields["u"]},
                                  scalars=scalars, coeffs=coeffs))
    with pytest.raises(ValueError, match="missing scalars"):
        eng.submit(StencilRequest(program=p, fields=fields, coeffs=coeffs))
    eng.close()


# --------------------------------------------------------------------------
# plan-record persistence
# --------------------------------------------------------------------------

def test_serve_record_reused_across_engines(tmp_path):
    cache_path = str(tmp_path / "plans.json")
    p = pw_advection()
    req = make_request(p, (8, 8, 16), seed=0)
    with StencilEngine(window_s=0.0,
                       plan_cache=PlanCache(cache_path)) as a:
        ra = a.run(req, timeout=300)
        assert a.stats.plan_misses == 1 and a.stats.plan_hits == 0
    # a fresh engine (fresh process stand-in) rebuilds from the record:
    # zero planning, and the same answer
    with StencilEngine(window_s=0.0,
                       plan_cache=PlanCache(cache_path)) as b:
        rb = b.run(make_request(p, (8, 8, 16), seed=0), timeout=300)
        assert b.stats.plan_hits == 1 and b.stats.plan_misses == 0
    for k in ra.outputs:
        np.testing.assert_array_equal(ra.outputs[k], rb.outputs[k])


def test_stale_schema_serve_record_misses_cleanly(tmp_path):
    cache_path = str(tmp_path / "plans.json")
    p = pw_advection()
    req = make_request(p, (8, 8, 16), seed=0)
    cache = PlanCache(cache_path)
    eng = StencilEngine(window_s=0.0, plan_cache=cache, autostart=False)
    _, spec, key = eng.describe(req)
    ex = compile_program(serving_program(p), spec.bucket,
                         backend="jnp_fused")
    rec = make_serve_record(ex.plan, "repad", spec.bucket, req.steps)
    assert read_serve_record(rec) is not None
    rec["schema"] = PLAN_SCHEMA_VERSION - 1          # written by an old build
    assert read_serve_record(rec) is None
    cache.store(key, rec)
    eng.start()
    try:
        res = eng.run(make_request(p, (8, 8, 16), seed=0), timeout=300)
        assert eng.stats.plan_misses == 1 and eng.stats.plan_hits == 0
        ref = reference(p, (8, 8, 16), req)
        for k in ref:
            np.testing.assert_allclose(res.outputs[k], np.asarray(ref[k]),
                                       atol=1e-5, rtol=1e-5)
        # the rebuild overwrote the stale record at the current schema
        assert read_serve_record(cache.lookup(key)) is not None
    finally:
        eng.close()


# --------------------------------------------------------------------------
# LRU executor cap + CompileOptions / mesh plumbing
# --------------------------------------------------------------------------

def test_executor_lru_evicts_coldest():
    p = pw_advection()
    grids = [(8, 8, 16), (8, 8, 40), (8, 8, 70)]      # three distinct buckets
    with StencilEngine(window_s=0.0, max_executors=2) as eng:
        eng.run(make_request(p, grids[0], seed=0), timeout=300)
        eng.run(make_request(p, grids[1], seed=0), timeout=300)
        assert eng.stats.evictions == 0 and len(eng._executors) == 2
        # touch bucket 0 so bucket 1 is the coldest, then overflow
        eng.run(make_request(p, grids[0], seed=1), timeout=300)
        eng.run(make_request(p, grids[2], seed=0), timeout=300)
        assert eng.stats.evictions == 1 and len(eng._executors) == 2
        misses = eng.stats.exec_misses
        # the refreshed bucket survived; the cold one was evicted
        eng.run(make_request(p, grids[0], seed=2), timeout=300)
        assert eng.stats.exec_misses == misses
        eng.run(make_request(p, grids[1], seed=1), timeout=300)
        assert eng.stats.exec_misses == misses + 1    # rebuilt after eviction
        assert eng.stats.evictions == 2
        assert eng.stats.snapshot()["evictions"] == 2


def test_engine_accepts_compile_options():
    from repro.core.pipeline import CompileOptions

    # options seeds every knob the caller left at its engine default
    eng = StencilEngine(options=CompileOptions(schedule="block",
                                               dtype="float32",
                                               strategy="per_field"),
                        autostart=False)
    assert eng.schedule == "block" and eng.strategy == "per_field"
    # a knob moved off its engine default that disagrees is an error
    with pytest.raises(ValueError, match="dtype"):
        StencilEngine(dtype="bfloat16",
                      options=CompileOptions(dtype="float64"),
                      autostart=False)
    # mesh= without mesh_axes= is rejected up front
    from repro.core import make_auto_mesh
    with pytest.raises(ValueError, match="mesh_axes"):
        StencilEngine(mesh=make_auto_mesh((1,), ("X",)), autostart=False)


def test_engine_mesh_topology_keys_executors():
    """The same request served under a mesh and locally must occupy
    distinct executor-table entries (and the sharded answer must agree
    with the local one — a 1x1 mesh runs on the single default device)."""
    from repro.core import make_auto_mesh
    p = pw_advection()
    req = make_request(p, (8, 8, 16), seed=0)
    mesh = make_auto_mesh((1,), ("X",))
    with StencilEngine(window_s=0.0, mesh=mesh,
                       mesh_axes=("X", None, None)) as sharded, \
            StencilEngine(window_s=0.0) as local:
        _, _, ks = sharded.describe(req)
        _, _, kl = local.describe(req)
        assert ks != kl and "mesh=X:1" in ks and "mesh=none" in kl
        rs = sharded.run(make_request(p, (8, 8, 16), seed=0), timeout=300)
        rl = local.run(make_request(p, (8, 8, 16), seed=0), timeout=300)
        for k in rl.outputs:
            np.testing.assert_allclose(rs.outputs[k], rl.outputs[k],
                                       atol=1e-5, rtol=1e-5)


def test_engine_rejects_periodic_fused_under_sharded_mesh():
    # subprocess: building an actually-sharded mesh needs >= 2 devices
    import os
    import subprocess
    import sys
    script = r"""
import numpy as np
from repro.apps.advection import pw_advection, pw_advection_update
from repro.core import make_auto_mesh
from repro.serve import StencilEngine, StencilRequest
p = pw_advection(boundary="periodic")
grid = (8, 8, 16)
rng = np.random.default_rng(0)
req = StencilRequest(
    program=p,
    fields={f: rng.normal(size=grid).astype(np.float32) for f in ("u", "v", "w")},
    scalars={s: 0.05 for s in p.scalars},
    coeffs={c: np.ones(grid[ax], np.float32) for c, ax in p.coeffs.items()},
    steps=3, update=pw_advection_update(), update_key="pw")
eng = StencilEngine(mesh=make_auto_mesh((2,), ("X",)),
                    mesh_axes=("X", None, None), autostart=False)
try:
    eng.describe(req)
    raise SystemExit("periodic fused request under a sharded mesh not rejected")
except ValueError as e:
    assert "periodic" in str(e), e
print("PERIODIC_REJECT_OK")
"""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src"))
    r = subprocess.run([sys.executable, "-c", script], env=env,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, f"stdout={r.stdout}\nstderr={r.stderr}"
    assert "PERIODIC_REJECT_OK" in r.stdout
