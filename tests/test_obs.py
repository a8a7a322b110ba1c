"""Observability subsystem (repro.obs): tracing and metrics.

Invariants:
* Spans nest per thread with wall-clock timings; the Chrome export is
  schema-valid ``trace_event`` JSON (every event has ph/ts/pid/tid, and
  complete spans on one track are properly nested, never interleaved).
* Disabled tracing is the no-op singleton — zero records, shared no-op
  span, and numerics bit-identical to an untraced compile.
* MetricsRegistry snapshots are JSON-round-trippable; ServeStats keeps its
  public quantile/occupancy API on top of the registry.
* Tile demotions warn exactly once per explicit-request compile and emit
  typed ChainDemoted/PlaneDemoted events when traced.
* PlanCache counts its own hits/misses; a warm tuned compile is provably
  zero timed runs via the ``tune.timed_runs`` counter.
* A compile that plans counts how the planner cut its fuse groups
  (``compile.fuse_split.*``) and how many it made (``compile.fuse_groups``).
"""

import json
import threading
import warnings

import numpy as np
import pytest

from repro.apps import (pw_advection, pw_advection_update, tracer_advection,
                        tracer_advection_update)
from repro.core import (PlanCache, TileDemotionWarning, TuneConfig,
                        compile_program)
from repro.core.frontend import ProgramBuilder
from repro.obs import (MetricsRegistry, NullTracer, Tracer, current_tracer,
                       global_metrics, resolve_tracer, set_tracer)
from repro.obs.trace import NULL, TRACE_ENV, _reset_for_tests
from repro.serve import ServeStats, StencilEngine, StencilRequest

GRID = (8, 8, 16)


def small_program(name="obs_small"):
    b = ProgramBuilder(name, ndim=3)
    u, = b.inputs("u")
    su = b.output("su")
    b.define(su, u[-1, 0, 0] + u[1, 0, 0] - 2.0 * u[0, 0, 0])
    return b.build()


def data_for(p, grid=GRID, seed=0):
    rng = np.random.default_rng(seed)
    fields = {f: rng.normal(size=grid).astype(np.float32) * 0.1
              for f in p.input_fields()}
    scalars = {s: np.float32(0.05) for s in p.scalars}
    coeffs = {c: np.linspace(0.9, 1.1, grid[ax]).astype(np.float32)
              for c, ax in p.coeffs.items()}
    return fields, scalars, coeffs


def fake_timer():
    calls = {"n": 0}

    def timer(fn):
        i = calls["n"]
        calls["n"] += 1
        return 0.001 * ((i * 7) % 13 + 1)

    return timer, calls


# ---------------------------------------------------------------- tracer

def test_spans_nest_and_carry_attrs():
    tr = Tracer()
    with tr.span("outer", a=1) as sp:
        sp.set(b=2)
        with tr.span("inner"):
            tr.event("tick", k="v")
    spans = tr.spans()
    assert [s["name"] for s in spans] == ["inner", "outer"]  # close order
    outer = tr.spans("outer")[0]
    inner = tr.spans("inner")[0]
    assert outer["args"] == {"a": 1, "b": 2}
    assert outer["depth"] == 0 and inner["depth"] == 1
    # containment: inner lies inside outer on the same track
    assert outer["ts"] <= inner["ts"]
    assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 1e-9
    ev = tr.events("tick")[0]
    assert ev["args"] == {"k": "v"} and ev["depth"] == 2


def test_tracer_threads_get_own_stacks_and_tids():
    tr = Tracer()
    done = threading.Event()

    def worker():
        with tr.span("w"):
            done.wait(5)

    t = threading.Thread(target=worker)
    with tr.span("m"):
        t.start()
        done.set()
        t.join()
    m, w = tr.spans("m")[0], tr.spans("w")[0]
    assert m["tid"] != w["tid"]
    assert w["depth"] == 0        # not nested under the main thread's span


def test_emit_typed_event():
    from repro.obs.events import PlanChosen
    tr = Tracer()
    tr.emit(PlanChosen(program="p", backend="pallas", schedule="stream",
                       strategy="auto", measured_us=0.5))
    ev = tr.events("PlanChosen")[0]
    assert ev["args"]["schedule"] == "stream"
    assert ev["args"]["measured_us"] == 0.5


def test_jsonl_export_roundtrip(tmp_path):
    tr = Tracer()
    with tr.span("s", n=1):
        tr.event("e", m=2)
    path = str(tmp_path / "t.jsonl")
    n = tr.export_jsonl(path)
    recs = [json.loads(line) for line in open(path)]
    assert len(recs) == n == 2
    assert {r["kind"] for r in recs} == {"span", "event"}
    assert all(set(("name", "ts", "pid", "tid", "args")) <= set(r)
               for r in recs)


def _validate_chrome(doc):
    """trace_event schema: required keys everywhere, X spans per track
    properly nested (any two either disjoint or contained)."""
    evs = doc["traceEvents"]
    for ev in evs:
        assert set(("ph", "ts", "pid", "tid", "name")) <= set(ev), ev
        if ev["ph"] == "X":
            assert "dur" in ev and ev["dur"] >= 0
        else:
            assert ev["ph"] == "i" and ev["s"] == "t"
    by_track = {}
    for ev in evs:
        if ev["ph"] == "X":
            by_track.setdefault((ev["pid"], ev["tid"]), []).append(ev)
    for track in by_track.values():
        track.sort(key=lambda e: (e["ts"], -e["dur"]))
        eps = 1e-3  # us rounding slack
        for a, b in [(a, b) for i, a in enumerate(track)
                     for b in track[i + 1:]]:
            a_end = a["ts"] + a["dur"]
            disjoint = b["ts"] >= a_end - eps
            contained = b["ts"] + b["dur"] <= a_end + eps
            assert disjoint or contained, (a["name"], b["name"])


def test_chrome_export_schema_and_nesting(tmp_path):
    tr = Tracer()
    with tr.span("compile"):
        with tr.span("tune"):
            for i in range(3):
                with tr.span("tune.candidate", i=i):
                    pass
        tr.event("PlanChosen", label="x")
    with tr.span("serve.batch"):
        pass
    path = str(tmp_path / "trace.json")
    n = tr.export_chrome(path)
    doc = json.load(open(path))
    assert n == len(doc["traceEvents"]) == 7
    _validate_chrome(doc)
    # microsecond timestamps, args preserved
    cands = [e for e in doc["traceEvents"] if e["name"] == "tune.candidate"]
    assert sorted(c["args"]["i"] for c in cands) == [0, 1, 2]


def test_null_tracer_is_free_and_cannot_export(tmp_path):
    tr = NullTracer()
    assert not tr.enabled
    s1 = tr.span("a")
    s2 = tr.span("b", k=1)
    assert s1 is s2               # one shared no-op span, no allocation
    with s1 as sp:
        sp.set(x=1)
        sp.event("e")
    tr.event("e")
    tr.emit(object())             # emit never inspects when disabled
    assert tr.records() == []
    with pytest.raises(RuntimeError):
        tr.export_chrome(str(tmp_path / "x.json"))


def test_current_tracer_defaults_to_null_and_active_overrides():
    _reset_for_tests()
    try:
        assert current_tracer() is NULL
        tr = Tracer()
        with tr.active():
            assert current_tracer() is tr
            inner = Tracer()
            with inner.active():
                assert current_tracer() is inner
            assert current_tracer() is tr
        assert current_tracer() is NULL
        set_tracer(tr)
        assert current_tracer() is tr
    finally:
        _reset_for_tests()


def test_trace_env_installs_process_tracer(tmp_path, monkeypatch):
    path = str(tmp_path / "env_trace.json")
    monkeypatch.setenv(TRACE_ENV, path)
    _reset_for_tests()
    try:
        tr = current_tracer()
        assert tr.enabled and isinstance(tr, Tracer)
        assert current_tracer() is tr     # cached after the first check
    finally:
        _reset_for_tests()


def test_resolve_tracer_contract():
    _reset_for_tests()
    try:
        assert resolve_tracer(None) is NULL
        assert resolve_tracer(False) is NULL
        tr = Tracer()
        assert resolve_tracer(tr) is tr
        t = resolve_tracer(True)          # installs a fresh process tracer
        assert t.enabled and current_tracer() is t
        assert resolve_tracer(True) is t  # idempotent once installed
        with pytest.raises(TypeError):
            resolve_tracer("yes")
    finally:
        _reset_for_tests()


# --------------------------------------------------------------- metrics

def test_metrics_registry_instruments_and_snapshot_roundtrip():
    reg = MetricsRegistry()
    reg.counter("c").inc()
    reg.counter("c").inc(2)
    reg.gauge("g").set(1.5)
    reg.gauge("g").add(0.5)
    for v in (1.0, 2.0, 3.0, 4.0):
        reg.histogram("h").observe(v)
    snap = reg.snapshot()
    assert snap["c"] == 3 and snap["g"] == 2.0
    # p50 index = round(0.5 * 3) = 2 under banker's rounding => 3.0
    assert snap["h"]["count"] == 4 and snap["h"]["p50"] == 3.0
    assert json.loads(json.dumps(snap)) == snap   # JSON round-trip
    assert reg.names() == ["c", "g", "h"]
    reg.reset()
    assert reg.counter("c").value == 0
    assert len(reg.histogram("h")) == 0 and reg.histogram("h").total == 0


def test_metrics_kind_conflict_raises():
    reg = MetricsRegistry()
    reg.counter("x")
    with pytest.raises(TypeError):
        reg.gauge("x")
    with pytest.raises(TypeError):
        reg.histogram("x")


def test_histogram_window_cap_and_quantiles():
    reg = MetricsRegistry()
    h = reg.histogram("lat", maxlen=100)
    for v in range(250):
        h.observe(float(v))
    assert len(h) == 100 and h.total == 250
    # window holds 150..249
    assert h.quantile(0.0) == 150.0 and h.quantile(1.0) == 249.0


# ------------------------------------------------------------- ServeStats

def test_servestats_attribute_api_is_registry_backed():
    s = ServeStats()
    s.completed += 1
    s.completed += 2
    s.wall_s += 0.5
    assert s.completed == 3 and s.wall_s == 0.5
    assert s.registry.counter("completed").value == 3
    with pytest.raises(AttributeError):
        s.not_a_metric


def test_servestats_quantiles_on_known_sequences():
    s = ServeStats()
    for ms in [10.0, 20.0, 30.0, 40.0, 50.0, 60.0, 70.0, 80.0, 90.0, 100.0]:
        s.record_latency(ms)
    assert s.p50_ms() == 50.0       # round(0.5 * 9) = index 4 (sorted)
    assert s.p99_ms() == 100.0
    assert s.latency_quantile(0.0) == 10.0
    s.reset_latencies()
    assert s.p50_ms() == 0.0 and s.p99_ms() == 0.0


def test_servestats_latency_window_capped_at_4096():
    from repro.serve.stats import LATENCY_WINDOW
    assert LATENCY_WINDOW == 4096
    s = ServeStats()
    for i in range(LATENCY_WINDOW + 500):
        s.record_latency(float(i))
    assert s.snapshot()["latencies"] == LATENCY_WINDOW
    assert s.latency_quantile(0.0) == 500.0    # oldest 500 evicted


def test_servestats_occupancy_and_snapshot_roundtrip():
    s = ServeStats()
    s.batched_requests += 6
    s.padded_slots += 2
    s.exec_hits += 3
    s.exec_misses += 1
    s.completed += 6
    s.wall_s = 2.0
    s.record_latency(12.5)
    assert s.occupancy() == 0.75
    assert s.cache_hit_rate() == 0.75
    assert s.throughput() == 3.0
    snap = s.snapshot()
    assert snap["occupancy"] == 0.75 and snap["latencies"] == 1
    assert json.loads(json.dumps(snap)) == snap


# ----------------------------------------------- demotion warnings/events

def test_time_tile_demotion_warns_exactly_once():
    p = pw_advection(boundary="periodic")   # periodic => chain demotes
    update = pw_advection_update(0.1)
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        ex = compile_program(p, GRID, backend="pallas", schedule="stream",
                             steps=2, update=update, time_tile=4)
    demos = [x for x in w if issubclass(x.category, TileDemotionWarning)]
    assert len(demos) == 1
    msg = str(demos[0].message)
    assert "time_tile=4" in msg and "effective 1" in msg and "periodic" in msg
    assert ex.plan.stream.time_tile == 1


def test_plane_tile_demotion_warns_exactly_once():
    p = pw_advection()
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        ex = compile_program(p, GRID, backend="pallas", schedule="stream",
                             plane_tile=64)
    demos = [x for x in w if issubclass(x.category, TileDemotionWarning)]
    assert len(demos) == 1
    assert "plane_tile=64" in str(demos[0].message)
    assert ex.plan.stream.plane_tile == 1


def test_no_warning_when_tiles_legal_or_unrequested():
    p = pw_advection()
    update = pw_advection_update(0.1)
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        compile_program(p, GRID, backend="pallas", schedule="stream",
                        steps=2, update=update, time_tile=2)   # legal
        compile_program(p, GRID, backend="pallas", schedule="stream")
    assert not [x for x in w if issubclass(x.category, TileDemotionWarning)]


def test_demotions_emit_typed_events_when_traced():
    tr = Tracer()
    p = pw_advection(boundary="periodic")
    update = pw_advection_update(0.1)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", TileDemotionWarning)
        compile_program(p, GRID, backend="pallas", schedule="stream",
                        steps=2, update=update, time_tile=4, trace=tr)
        compile_program(pw_advection(), GRID, backend="pallas",
                        schedule="stream", plane_tile=64, trace=tr)
    chain = tr.events("ChainDemoted")
    plane = tr.events("PlaneDemoted")
    assert chain and chain[0]["args"]["requested"] == 4
    assert chain[0]["args"]["effective"] == 1 and chain[0]["args"]["reason"]
    assert plane and plane[0]["args"]["requested"] == 64
    assert plane[0]["args"]["effective"] == 1


# -------------------------------------------------------- compile tracing

def test_compile_span_and_plan_chosen_event():
    tr = Tracer()
    ex = compile_program(small_program(), GRID, backend="pallas", trace=tr)
    sp = tr.spans("compile")[0]
    assert sp["args"]["program"] == "obs_small"
    assert sp["args"]["backend"] == "pallas" and sp["dur"] >= 0
    assert sp["args"]["schedule"] in ("block", "stream")
    chosen = tr.events("PlanChosen")
    assert len(chosen) == 1
    assert chosen[0]["args"]["program"] == "obs_small"
    assert ex.plan is not None


def test_explicit_plan_compile_emits_no_plan_chosen():
    from repro.core.schedule import auto_plan
    p = small_program()
    plan = auto_plan(p, GRID, backend="pallas")
    tr = Tracer()
    compile_program(p, GRID, backend="pallas", plan=plan, trace=tr)
    assert tr.events("PlanChosen") == []   # nothing was chosen: plan given
    assert tr.spans("compile")             # ...but the span still records


def test_untraced_compile_records_nothing_and_matches_traced():
    _reset_for_tests()
    p = small_program()
    fields, scalars, coeffs = data_for(p)
    ex0 = compile_program(p, GRID, backend="pallas")
    tr = Tracer()
    ex1 = compile_program(p, GRID, backend="pallas", trace=tr)
    a = ex0(fields, scalars, coeffs)["su"]
    b = ex1(fields, scalars, coeffs)["su"]
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert current_tracer() is NULL        # no ambient leak from trace=


def test_compile_metrics_counters_advance():
    m = global_metrics()
    c0 = m.counter("compile.compiles").value
    s0 = m.counter("compile.stream_lowerings").value
    compile_program(small_program(), GRID, backend="pallas",
                    schedule="stream")
    assert m.counter("compile.compiles").value == c0 + 1
    assert m.counter("compile.stream_lowerings").value == s0 + 1


def _fuse_split_counts():
    m = global_metrics()
    got = {k: m.counter(f"compile.fuse_split.{k}").value
           for k in ("whole", "budget", "per_field")}
    got["groups"] = m.counter("compile.fuse_groups").value
    return got


@pytest.mark.parametrize("case,expected", [
    ("pw-fits", {"whole": 1, "groups": 1}),
    ("tracer-cell", {"budget": 1, "groups": 2}),
    ("tracer-cyclic-cell", {"budget": 1, "groups": 2}),
    ("explicit-plan", {})],
    ids=["pw-fits", "tracer-cell", "tracer-cyclic-cell", "explicit-plan"])
def test_fuse_split_counters_say_how_the_planner_cut(case, expected):
    """A compile that plans counts how it cut the fuse groups once, and
    the groups it made; one handed a plan counts nothing.  The tracer's
    24-op group overflows VMEM at its cell's 512x256x256 grid (planning
    only: nothing runs)."""
    before = _fuse_split_counts()
    if case == "pw-fits":
        compile_program(pw_advection(), GRID, steps=2,
                        update=pw_advection_update(0.1))
    elif case == "explicit-plan":
        from repro.core.schedule import auto_plan
        p = small_program()
        compile_program(p, GRID, plan=auto_plan(p, GRID))
    else:
        boundary = ("zero" if case == "tracer-cell"
                    else ["periodic", "zero", "zero"])
        compile_program(tracer_advection(boundary=boundary),
                        (512, 256, 256), steps=100,
                        update=tracer_advection_update())
    after = _fuse_split_counts()
    assert {k: after[k] - before[k] for k in after} == {
        k: expected.get(k, 0) for k in after}


# --------------------------------------------------- PlanCache + tuner obs

def test_plan_cache_counts_its_own_hits_and_misses():
    cache = PlanCache(path=None)
    assert cache.lookup("k") is None
    cache.store("k", {"v": 1})
    assert cache.lookup("k") == {"v": 1}
    assert cache.lookup("other") is None
    assert cache.hits == 1 and cache.misses == 2
    assert cache.metrics.snapshot() == {"hits": 1, "misses": 2}


def test_warm_tuned_compile_is_zero_timed_runs_by_counter(tmp_path):
    """Satellite: the zero-timed-run warm-hit guarantee is now observable
    through the ``tune.timed_runs`` counter and the cache's own hit/miss
    counters — no timer monkeypatching needed to prove it."""
    p = pw_advection()
    path = str(tmp_path / "plans.json")
    update = pw_advection_update(0.1)
    timer, _ = fake_timer()
    cfg = TuneConfig(steps=2, max_measured=3, timer=timer)
    m = global_metrics()

    cache1 = PlanCache(path=path)
    compile_program(p, GRID, backend="jnp_fused", strategy="tuned", steps=2,
                    update=update, tune_config=cfg, plan_cache=cache1)
    assert m.counter("tune.timed_runs").value > 0
    assert cache1.misses >= 1 and cache1.hits == 0

    cache2 = PlanCache(path=path)       # fresh object: through the file
    t0 = m.counter("tune.timed_runs").value
    r0 = m.counter("tune.runs").value
    compile_program(p, GRID, backend="jnp_fused", strategy="tuned", steps=2,
                    update=update, tune_config=cfg, plan_cache=cache2)
    assert m.counter("tune.timed_runs").value == t0   # zero timed runs
    assert m.counter("tune.runs").value == r0         # no search at all
    assert cache2.hits == 1 and cache2.misses == 0


def test_tuned_compile_trace_has_candidates_and_fraction():
    tr = Tracer()
    timer, _ = fake_timer()
    compile_program(pw_advection(), GRID, backend="pallas",
                    strategy="tuned", steps=2,
                    update=pw_advection_update(0.1),
                    tune_config=TuneConfig(steps=2, max_measured=3,
                                           timer=timer),
                    plan_cache=PlanCache(path=None), trace=tr)
    cands = tr.spans("tune.candidate")
    assert len(cands) >= 2
    assert all("label" in c["args"] for c in cands)
    assert tr.spans("tune")
    assert tr.events("CacheMiss")       # tuned_plan lookup missed
    chosen = tr.events("PlanChosen")
    assert chosen
    assert chosen[0]["args"]["measured_us"] > 0


# ------------------------------------------------------------ serve tracing

def test_engine_traces_batches_and_caches():
    p = pw_advection()
    fields, scalars, coeffs = data_for(p, GRID)
    tr = Tracer()
    with StencilEngine(backend="jnp_fused", tracer=tr) as eng:
        for _ in range(2):
            eng.run(StencilRequest(program=p, fields=fields,
                                   scalars=scalars, coeffs=coeffs))
    assert len(tr.spans("serve.batch")) >= 1
    assert len(tr.spans("serve.build_executor")) == 1
    names = {e["args"].get("cache") for e in tr.events("CacheMiss")}
    assert "executor" in names
    assert tr.events("CacheHit")        # the second request was warm


def test_engine_eviction_emits_event_and_counter():
    pa, pb = small_program("obs_ev_a"), small_program("obs_ev_b")
    fa, sa, ca = data_for(pa)
    tr = Tracer()
    with StencilEngine(backend="jnp_fused", max_executors=1,
                       tracer=tr) as eng:
        eng.run(StencilRequest(program=pa, fields=fa, scalars=sa, coeffs=ca))
        eng.run(StencilRequest(program=pb, fields=fa, scalars=sa, coeffs=ca))
        assert eng.stats.evictions == 1
    evs = tr.events("ExecutorEvicted")
    assert len(evs) == 1 and evs[0]["args"]["resident"] == 1


# --------------------------------------------------------------- end-to-end

def test_end_to_end_trace_compile_tune_serve(tmp_path):
    """The acceptance shape of examples/trace_compile.py: one tracer sees
    the tuned compile (>= 2 candidates), the serve batch, a PlanChosen with
    a measured time — and exports valid Chrome JSON."""
    p = pw_advection()
    fields, scalars, coeffs = data_for(p, GRID)
    tr = Tracer()
    timer, _ = fake_timer()
    compile_program(p, GRID, backend="pallas", strategy="tuned", steps=2,
                    update=pw_advection_update(0.1),
                    tune_config=TuneConfig(steps=2, max_measured=3,
                                           timer=timer),
                    plan_cache=PlanCache(path=None), trace=tr)
    with StencilEngine(backend="jnp_fused", tracer=tr) as eng:
        eng.run(StencilRequest(program=p, fields=fields, scalars=scalars,
                               coeffs=coeffs))
    assert tr.spans("compile")
    assert len(tr.spans("tune.candidate")) >= 2
    assert len(tr.spans("serve.batch")) >= 1
    assert any(e["args"]["measured_us"] for e in tr.events("PlanChosen"))
    path = str(tmp_path / "e2e.json")
    tr.export_chrome(path)
    _validate_chrome(json.load(open(path)))


# ------------------------------------------------------ profiler bridge

def _host_event_names(trace_dir) -> set:
    from jax._src.profiler import ProfileData
    (path,) = list(trace_dir.rglob("*.xplane.pb"))
    data = ProfileData.from_file(str(path))
    return {ev.name for plane in data.planes
            if plane.name.startswith("/host:")
            for line in plane.lines for ev in line.events}


@pytest.mark.parametrize("enabled", [True, False], ids=["tracer", "null"])
def test_span_shows_on_the_profiler_host_plane(tmp_path, enabled):
    """A span of an enabled tracer is also a ``TraceAnnotation``: it lands
    on the host plane of the profiler's trace, on the device ops' clock.
    The disabled tracer writes nothing there."""
    import jax
    tr = Tracer() if enabled else NULL
    jax.profiler.start_trace(str(tmp_path))
    try:
        with tr.span("obs.bridge_probe"):
            jax.block_until_ready(jax.numpy.arange(4) + 1)
    finally:
        jax.profiler.stop_trace()
    assert ("obs.bridge_probe" in _host_event_names(tmp_path)) == enabled
    assert len(tr.spans("obs.bridge_probe")) == int(enabled)
