"""Auto-tuner (repro.core.tune): measured plan search + persistent cache.

Invariants:
* The search is deterministic in its measurements: an injected fake timer
  returning the same times yields the same winning plan.
* ``compile_program(..., strategy="tuned")`` is a pure cache hit after the
  first tune — zero timed runs, same plan.
* The cache is invalidated by program fingerprint and grid changes.
* The tuned plan is never slower than the ``auto_plan`` baseline on the
  tuner's own measurements (the baseline is always a candidate).
"""

import dataclasses
import json
import math

import pytest

from repro.apps import pw_advection, pw_advection_update
from repro.core import (PlanCache, TuneConfig, compile_program,
                        get_tuned_plan, plan_from_dict, plan_to_dict,
                        program_fingerprint, tune_plan)
from repro.core.frontend import ProgramBuilder
from repro.core.schedule import TILE_POINTS, auto_plan
from repro.core.tune import CACHE_SCHEMA_VERSION, _block_candidates, cache_key

GRID = (8, 8, 16)


def make_fake_timer():
    """Deterministic fake: time depends only on the call index, and the
    candidate order is deterministic, so measurements are reproducible.
    Never calls ``fn`` — a counted call *is* a timed run."""
    calls = {"n": 0}

    def timer(fn):
        i = calls["n"]
        calls["n"] += 1
        return 0.001 * ((i * 7) % 13 + 1)

    return timer, calls


def small_program():
    b = ProgramBuilder("tune_small", ndim=3)
    u, = b.inputs("u")
    su = b.output("su")
    b.define(su, u[-1, 0, 0] + u[1, 0, 0] - 2.0 * u[0, 0, 0])
    return b.build()


def small_update(fields, out):
    return {"u": fields["u"] + 0.1 * out["su"]}


# ----------------------------------------------------------- determinism

@pytest.mark.parametrize("backend", ["jnp_fused", "pallas"])
def test_tuner_determinism_with_fake_timer(backend):
    """Same measurements => same winning plan (and same carry_write)."""
    results = []
    for _ in range(2):
        timer, _calls = make_fake_timer()
        cfg = TuneConfig(steps=2, max_measured=4, timer=timer)
        res = tune_plan(pw_advection(), GRID, backend=backend,
                        update=pw_advection_update(0.1), config=cfg,
                        cache=PlanCache(path=None))
        results.append(res)
    a, b = results
    assert plan_to_dict(a.plan) == plan_to_dict(b.plan)
    assert a.carry_write == b.carry_write
    assert a.record["label"] == b.record["label"]


# ------------------------------------------------------------ cache hits

def test_second_tuned_compile_is_pure_cache_hit(tmp_path):
    """Acceptance: the second ``strategy="tuned"`` compile performs zero
    timed runs and reuses the stored plan — across PlanCache instances
    (i.e. through the JSON file, not just process memory).  The proof is
    the observability counters: the cache counts its own hit, and the
    process-wide ``tune.timed_runs`` counter (incremented on *every* timer
    invocation, fake or real) does not move."""
    from repro.obs import global_metrics

    p = pw_advection()
    path = str(tmp_path / "plans.json")
    update = pw_advection_update(0.1)
    timed = global_metrics().counter("tune.timed_runs")

    timer1, calls1 = make_fake_timer()
    cache1 = PlanCache(path=path)
    t0 = timed.value
    ex1 = compile_program(p, GRID, backend="jnp_fused", strategy="tuned",
                          steps=2, update=update,
                          tune_config=TuneConfig(steps=2, max_measured=3,
                                                 timer=timer1),
                          plan_cache=cache1)
    assert calls1["n"] > 0          # the first compile really tuned
    assert timed.value == t0 + calls1["n"]   # every timing was counted
    assert cache1.misses >= 1 and cache1.hits == 0

    timer2, calls2 = make_fake_timer()
    cache2 = PlanCache(path=path)
    t1 = timed.value
    ex2 = compile_program(p, GRID, backend="jnp_fused", strategy="tuned",
                          steps=2, update=update,
                          tune_config=TuneConfig(steps=2, max_measured=3,
                                                 timer=timer2),
                          plan_cache=cache2)
    assert timed.value == t1        # pure cache hit: zero timed runs
    assert calls2["n"] == 0
    assert cache2.hits == 1 and cache2.misses == 0
    assert plan_to_dict(ex1.plan) == plan_to_dict(ex2.plan)
    assert ex1.time_spec.carry_write == ex2.time_spec.carry_write


def test_cache_file_format_roundtrip(tmp_path):
    path = str(tmp_path / "plans.json")
    timer, _ = make_fake_timer()
    res = tune_plan(small_program(), GRID, backend="jnp_fused",
                    update=small_update,
                    config=TuneConfig(steps=2, timer=timer),
                    cache=PlanCache(path=path))
    doc = json.load(open(path))
    assert doc["version"] == CACHE_SCHEMA_VERSION
    rec = doc["entries"][res.key]
    assert plan_to_dict(plan_from_dict(rec["plan"])) == rec["plan"]
    assert rec["fingerprint"] == program_fingerprint(small_program())
    assert rec["measured"] >= 1 and rec["candidates"] >= rec["measured"]


# ------------------------------------------------------ cache invalidation

def test_cache_invalidated_by_program_fingerprint(tmp_path):
    """A semantically different program misses the cache and re-tunes."""
    path = str(tmp_path / "plans.json")
    timer, calls = make_fake_timer()
    cfg = TuneConfig(steps=2, timer=timer)
    get_tuned_plan(small_program(), GRID, backend="jnp_fused",
                   update=small_update, config=cfg, cache=PlanCache(path=path))
    n_first = calls["n"]
    assert n_first > 0

    b = ProgramBuilder("tune_small", ndim=3)   # same name, different stencil
    u, = b.inputs("u")
    su = b.output("su")
    b.define(su, u[0, -1, 0] + u[0, 1, 0] - 2.0 * u[0, 0, 0])
    other = b.build()
    assert program_fingerprint(other) != program_fingerprint(small_program())

    res = get_tuned_plan(other, GRID, backend="jnp_fused",
                         update=small_update, config=cfg,
                         cache=PlanCache(path=path))
    assert not res.cache_hit
    assert calls["n"] > n_first     # it measured again

    # while the original program still hits
    res2 = get_tuned_plan(small_program(), GRID, backend="jnp_fused",
                          update=small_update, config=cfg,
                          cache=PlanCache(path=path))
    assert res2.cache_hit


def test_cache_invalidated_by_grid_change(tmp_path):
    path = str(tmp_path / "plans.json")
    timer, calls = make_fake_timer()
    cfg = TuneConfig(steps=2, timer=timer)
    cache = PlanCache(path=path)
    p = small_program()
    get_tuned_plan(p, GRID, backend="jnp_fused", update=small_update,
                   config=cfg, cache=cache)
    n_first = calls["n"]
    res = get_tuned_plan(p, (16, 8, 16), backend="jnp_fused",
                         update=small_update, config=cfg, cache=cache)
    assert not res.cache_hit and calls["n"] > n_first
    assert cache_key(p, GRID, "jnp_fused") != \
        cache_key(p, (16, 8, 16), "jnp_fused")


def test_cache_keyed_by_backend_dtype_and_mode(monkeypatch):
    from repro import hw

    p = small_program()
    assert cache_key(p, GRID, "pallas") != \
        cache_key(p, GRID, "jnp_fused")
    # a winner timed in the interpreter must not serve compiled kernels
    interpreted = cache_key(p, GRID, "pallas")
    monkeypatch.setattr(hw, "pallas_interpret", lambda: False)
    assert cache_key(p, GRID, "pallas") != interpreted
    monkeypatch.undo()
    # a float32 winner must not serve a bfloat16 compile, nor a single-step
    # winner a fused steps=N compile (different pruning + ranking)
    assert cache_key(p, GRID, "pallas", "float32") != \
        cache_key(p, GRID, "pallas", "bfloat16")
    assert cache_key(p, GRID, "pallas", mode="loop") != \
        cache_key(p, GRID, "pallas", mode="single")


# ------------------------------------------- measured quality guarantee

@pytest.mark.parametrize("backend", ["jnp_fused", "pallas"])
def test_tuned_never_slower_than_auto_plan_on_measurements(backend):
    """The auto_plan seed is always measured, so argmin <= baseline."""
    cfg = TuneConfig(steps=2, repeats=1, max_measured=3)
    res = tune_plan(pw_advection(), GRID, backend=backend,
                    update=pw_advection_update(0.1), config=cfg,
                    cache=PlanCache(path=None))
    base = res.baseline
    assert base is not None and base.us_fused is not None
    assert res.record["us_fused"] <= base.us_fused


def test_tuned_plan_compiles_and_matches_auto_plan_results():
    """The tuned executable computes the same answer as the heuristic one."""
    import numpy as np
    import jax.numpy as jnp
    rng = np.random.default_rng(0)
    p = small_program()
    fields = {"u": jnp.asarray(rng.normal(size=GRID).astype(np.float32))}
    timer, _ = make_fake_timer()
    ex_t = compile_program(p, GRID, backend="pallas", strategy="tuned",
                           tune_config=TuneConfig(steps=2, timer=timer),
                           plan_cache=PlanCache(path=None))
    ex_a = compile_program(p, GRID, backend="pallas")
    got = ex_t(fields, {}, {})
    want = ex_a(fields, {}, {})
    np.testing.assert_allclose(np.asarray(got["su"]),
                               np.asarray(want["su"]), atol=1e-6)


def test_tune_without_update_measures_single_step_only():
    timer, calls = make_fake_timer()
    res = tune_plan(small_program(), GRID, backend="jnp_fused",
                    config=TuneConfig(steps=2, timer=timer),
                    cache=PlanCache(path=None))
    assert res.record["us_fused"] is None
    assert res.record["us_single"] is not None
    assert calls["n"] == res.record["measured"]  # one timing per candidate


def test_candidate_blocks_lane_quantised():
    """Every measured pallas candidate keeps a lane-quantised last axis."""
    timer, _ = make_fake_timer()
    grid = (8, 8, 256)
    res = tune_plan(pw_advection(), grid, backend="pallas",
                    update=pw_advection_update(0.1),
                    config=TuneConfig(steps=2, max_measured=6, timer=timer),
                    cache=PlanCache(path=None))
    for c in res.measured:
        last = c.plan.block[-1]
        assert last == grid[-1] or last % 128 == 0


def test_block_candidates_capped_like_auto_plan():
    """At the paper's 8M grid every block candidate keeps the last two axes
    whole and holds at most TILE_POINTS points, the cap auto_plan starts
    from, so the tuner never compiles a tile the planner would refuse."""
    grid = (256, 256, 128)
    blocks = _block_candidates(pw_advection(), grid)
    assert auto_plan(pw_advection(), grid).block in blocks
    for blk in blocks:
        assert blk[1:] == grid[1:]
        assert math.prod(blk) <= TILE_POINTS


def test_force_retune_bypasses_cache(tmp_path):
    """The key encodes the problem, not the search effort; force_retune is
    the escape hatch for re-searching with different knobs."""
    path = str(tmp_path / "plans.json")
    timer, calls = make_fake_timer()
    cfg = TuneConfig(steps=2, timer=timer)
    get_tuned_plan(small_program(), GRID, backend="jnp_fused",
                   update=small_update, config=cfg, cache=PlanCache(path=path))
    n_first = calls["n"]
    res = get_tuned_plan(small_program(), GRID, backend="jnp_fused",
                         update=small_update,
                         config=dataclasses.replace(cfg, force_retune=True),
                         cache=PlanCache(path=path))
    assert not res.cache_hit and calls["n"] > n_first


def test_corrupt_cache_file_is_ignored(tmp_path):
    path = tmp_path / "plans.json"
    path.write_text("{not json")
    timer, calls = make_fake_timer()
    res = get_tuned_plan(small_program(), GRID, backend="jnp_fused",
                         update=small_update,
                         config=TuneConfig(steps=2, timer=timer),
                         cache=PlanCache(path=str(path)))
    assert not res.cache_hit and calls["n"] > 0
    assert json.load(open(path))["entries"]    # rewritten with the record


# --------------------------------------------- mesh topology + boundaries

def test_cache_keyed_by_mesh_topology_and_boundary():
    """The key separates mesh topologies (2x2x2 vs 4x2 vs local) and
    boundary conditions — a plan tuned for one must not serve another.
    Uses lightweight mesh stand-ins: the key only reads ``.shape``."""
    class FakeMesh:
        def __init__(self, shape):
            self.shape = shape

    p = pw_advection()
    k_local = cache_key(p, GRID, "pallas")
    k_222 = cache_key(p, GRID, "pallas",
                      mesh=FakeMesh({"X": 2, "Y": 2, "Z": 2}),
                      mesh_axes=("X", "Y", "Z"))
    k_42 = cache_key(p, GRID, "pallas",
                     mesh=FakeMesh({"X": 4, "Y": 2}),
                     mesh_axes=("X", "Y", None))
    k_24 = cache_key(p, GRID, "pallas",
                     mesh=FakeMesh({"X": 2, "Y": 4}),
                     mesh_axes=("X", "Y", None))
    k_periodic = cache_key(p.with_boundary("periodic"), GRID, "pallas")
    assert len({k_local, k_222, k_42, k_24, k_periodic}) == 5


def test_tuned_plan_boundary_in_fingerprint():
    """Same program, different boundary => different tuner cache entry."""
    p = pw_advection()
    assert program_fingerprint(p) != \
        program_fingerprint(p.with_boundary("periodic"))


# --------------------------------------------------- plan copy-on-write

def test_compile_program_does_not_mutate_shared_plan():
    """Regression: ``compile_program`` used to retarget ``plan.backend`` in
    place; a plan served twice from the PlanCache (or held by the caller)
    would be silently corrupted by a second compile."""
    p = pw_advection()
    plan = auto_plan(p, GRID, backend="pallas")
    groups_before = [list(g) for g in plan.groups]
    ex = compile_program(p, GRID, backend="jnp_fused", plan=plan)
    assert plan.backend == "pallas"              # untouched
    assert ex.plan.backend == "jnp_fused"        # compiled copy retargeted
    assert plan.groups == groups_before
    ex.plan.groups[0].append(99)                 # and the copy is deep
    assert plan.groups == groups_before


# --------------------------------------------- cache schema evolution (v2)

def test_stale_cache_version_is_a_miss_and_rewritten(tmp_path):
    """A cache file written by another schema version never serves entries:
    the lookup misses (forcing a re-tune) and the next store rewrites the
    file at the current version — old records can't poison new fields."""
    path = str(tmp_path / "plans.json")
    timer, calls = make_fake_timer()
    cfg = TuneConfig(steps=2, timer=timer)
    res = tune_plan(small_program(), GRID, backend="jnp_fused",
                    update=small_update, config=cfg, cache=PlanCache(path=path))
    doc = json.load(open(path))
    assert doc["version"] == CACHE_SCHEMA_VERSION

    # forge a pre-schedule-era cache: same entries, version 1
    stale = {"version": 1, "entries": doc["entries"]}
    json.dump(stale, open(path, "w"))
    fresh = PlanCache(path=path)            # no in-memory copy
    assert fresh.lookup(res.key) is None    # stale version = miss

    calls["n"] = 0
    res2 = get_tuned_plan(small_program(), GRID, backend="jnp_fused",
                          update=small_update, config=cfg, cache=fresh)
    assert not res2.cache_hit and calls["n"] > 0    # re-tuned
    doc2 = json.load(open(path))
    assert doc2["version"] == CACHE_SCHEMA_VERSION  # rewritten current
    assert fresh.lookup(res2.key) is not None


def test_plan_from_dict_tolerates_schema_drift():
    """Unknown keys are ignored, keys a past version never wrote default."""
    plan = auto_plan(small_program(), GRID, backend="pallas")
    d = plan_to_dict(plan)

    # a future version's extra keys must not crash this one
    future = dict(d, schema=99, exotic_knob={"nested": [1, 2]})
    assert plan_to_dict(plan_from_dict(future)) == d

    # a pre-v2 record (no schema/schedule/stream) defaults to a block plan
    legacy = {k: v for k, v in d.items()
              if k not in ("schema", "schedule", "stream")}
    r = plan_from_dict(legacy)
    assert r.schedule == "block" and r.stream is None
    assert r.groups == plan.groups and r.block == plan.block

    # minimal ancient record: only the two originally-required keys
    r0 = plan_from_dict({"groups": [[0]], "block": [8, 8, 16]})
    assert r0.dtype == "float32" and r0.halo_every == 1


def test_plan_cache_roundtrips_stream_spec(tmp_path):
    """A stream-scheduled winner survives the JSON cache bit-for-bit:
    schedule, legalised regions, window depths, rings, leads."""
    p = pw_advection()
    plan = auto_plan(p, GRID, schedule="stream")
    assert plan.stream is not None and plan.stream.depths
    path = str(tmp_path / "plans.json")
    cache = PlanCache(path=path)
    cache.store("k", {"plan": plan_to_dict(plan), "carry_write": "repad"})
    rec = PlanCache(path=path).lookup("k")
    got = plan_from_dict(rec["plan"])
    assert got.schedule == "stream"
    assert got.stream == plan.stream
    assert plan_to_dict(got) == plan_to_dict(plan)


def test_tuner_enumerates_stream_and_block_schedules():
    """``strategy="tuned"`` searches both schedule values: the candidate
    set contains shift-register stream plans next to block plans, and the
    winner's schedule round-trips through the record."""
    from repro.core.tune import _candidates
    cfg = TuneConfig(steps=2, timer=lambda fn: 1.0)
    cands = _candidates(pw_advection(), GRID, "pallas", "float32",
                        cfg, with_loop=True)
    schedules = {c.plan.schedule for c in cands}
    assert schedules == {"block", "stream"}
    stream_cands = [c for c in cands if c.plan.schedule == "stream"]
    assert all(c.plan.stream is not None for c in stream_cands)
    # ...and the jnp backends never see stream candidates
    jcands = _candidates(pw_advection(), GRID, "jnp_fused", "float32",
                         cfg, with_loop=True)
    assert {c.plan.schedule for c in jcands} == {"block"}


def test_plan_cache_concurrent_writers_merge(tmp_path):
    """N threads storing distinct keys into one cache file must all
    survive: the rewrite is merge-on-write over a fresh re-read with a
    unique temp path per writer, so no store clobbers another's entries
    and no reader ever sees a torn file."""
    import threading

    path = str(tmp_path / "plans.json")
    n_threads, per_thread = 8, 10
    plan = auto_plan(pw_advection(), (8, 8, 16), backend="jnp_fused")
    rec = {"plan": plan_to_dict(plan), "carry_write": "repad"}
    caches = [PlanCache(path) for _ in range(n_threads)]
    start = threading.Barrier(n_threads)
    errs = []

    def writer(i):
        try:
            start.wait()
            for j in range(per_thread):
                caches[i].store(f"w{i}/k{j}", dict(rec, label=f"{i}/{j}"))
        except Exception as e:  # pragma: no cover - failure reporting
            errs.append(e)

    threads = [threading.Thread(target=writer, args=(i,))
               for i in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errs
    with open(path) as f:
        doc = json.load(f)
    assert doc["version"] == CACHE_SCHEMA_VERSION
    keys = {f"w{i}/k{j}" for i in range(n_threads)
            for j in range(per_thread)}
    assert keys <= set(doc["entries"])
    # and a fresh cache object reads every entry back
    fresh = PlanCache(path)
    for k in keys:
        assert fresh.lookup(k)["carry_write"] == "repad"


def test_plan_cache_shared_object_threadsafe(tmp_path):
    """One PlanCache instance shared by many threads (the serving engine's
    shape): stores and lookups interleave without losing entries."""
    import threading

    path = str(tmp_path / "plans.json")
    cache = PlanCache(path)
    plan = auto_plan(pw_advection(), (8, 8, 16), backend="jnp_fused")
    rec = {"plan": plan_to_dict(plan), "carry_write": "inplace"}
    start = threading.Barrier(4)
    errs = []

    def worker(i):
        try:
            start.wait()
            for j in range(12):
                cache.store(f"t{i}/k{j}", dict(rec))
                assert cache.lookup(f"t{i}/k{j}") is not None
        except Exception as e:  # pragma: no cover - failure reporting
            errs.append(e)

    threads = [threading.Thread(target=worker, args=(i,)) for i in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errs
    fresh = PlanCache(path)
    for i in range(4):
        for j in range(12):
            assert fresh.lookup(f"t{i}/k{j}")["carry_write"] == "inplace"
