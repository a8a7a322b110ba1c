"""Auto-tuning layer: measured search over the DataflowPlan space.

This is the loop the paper's headline rests on — the *tooling*, not the
programmer, picks the dataflow structure (§3: the transformation space is
searched automatically; the 14-100x over Vitis-style baselines comes from
that search, not from any single heuristic).  :func:`~repro.core.schedule.
auto_plan` is the one-shot heuristic seed; this module closes the loop:

1. **generate** candidates over the plan knobs — fuse strategy (``fused`` /
   ``per_field`` / ``auto``), block shape (lane-quantised on the last axis),
   ``carry_write`` style, and dtype;
2. **prune** with the static models — the steps-aware
   :func:`~repro.core.schedule.vmem_cost` drops plans whose carry-enlarged
   windows exceed the VMEM budget, and
   :func:`~repro.analysis.stencil_roofline.model_plan` ranks the rest so
   only the most promising ``max_measured`` candidates pay for a run;
3. **measure** the survivors on-device (warm-up + best-of-k with
   ``block_until_ready``), in both single-step and fused ``steps=N``
   modes when an update rule is available;
4. **persist** the winner in a JSON plan cache keyed by (program
   fingerprint, grid, backend, jax version, interpreted or compiled
   kernels), so
   ``compile_program(..., strategy="tuned")`` is a pure cache hit — zero
   measured runs — after the first tune.

The ``auto_plan`` seed is always measured as the baseline candidate, so the
tuned plan is never slower than the heuristic *on the tuner's own
measurements* — the search can only keep or beat the seed.

The measurement timer is injectable (``TuneConfig.timer``) so tests can
drive the search with fake timings: same measurements imply the same
winning plan.
"""

from __future__ import annotations

import contextlib
import dataclasses
import itertools
import json
import os
import threading
import time
import uuid
from typing import Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from .. import hw
from ..obs.events import CacheHit, CacheMiss, PlanChosen
from ..obs.metrics import MetricsRegistry, global_metrics
from ..obs.trace import current_tracer
from .ir import Program
from .schedule import (PLAN_SCHEMA_VERSION, DataflowPlan, auto_plan,
                       cap_tile, mesh_fingerprint, plan_from_dict, plan_to_dict,
                       program_fingerprint, vmem_cost)

__all__ = [
    "TuneConfig", "PlanCache", "TuneResult", "cache_key", "tune_plan",
    "get_tuned_plan", "default_cache_path", "make_serve_record",
    "read_serve_record",
]

#: Environment variable overriding the default plan-cache location.
PLAN_CACHE_ENV = "REPRO_PLAN_CACHE"

#: On-disk plan-cache schema version.  Bumped together with
#: :data:`~repro.core.schedule.PLAN_SCHEMA_VERSION` whenever serialised
#: plans gain fields whose absence would change behaviour (v2: the
#: ``schedule`` axis + ``StreamSpec``; v3: temporal blocking — ``time_tile``
#: on the plan and the effective chain depth on the stream spec; v4:
#: spatial unrolling — ``plane_tile`` on the plan and the effective sweep
#: width on the stream spec).  A cache written by another version is
#: treated as a **miss** — re-tuning is cheap, silently misreading a stale
#: record is not — and the next store rewrites the file at the current
#: version.
CACHE_SCHEMA_VERSION = 4


def default_cache_path() -> str:
    env = os.environ.get(PLAN_CACHE_ENV)
    if env:
        return env
    return os.path.join(os.path.expanduser("~"), ".cache", "stencil_hmls",
                        "plan_cache.json")


@dataclasses.dataclass
class TuneConfig:
    """Knobs of one tuning run (all defaults are CI-smoke sized)."""

    steps: int = 3              # fused-loop depth measured per candidate
    warmup: int = 1             # un-timed calls before measuring (jit compile)
    repeats: int = 3            # best-of-k timed calls
    max_measured: int = 8       # model-ranked candidates that pay for a run
    vmem_budget: int = hw.VMEM_PLAN_BUDGET
    strategies: tuple = ("auto", "fused", "per_field")
    carry_writes: tuple = ("repad", "inplace")
    # temporal-blocking depths tried for stream candidates (fused-loop mode
    # only — single-step sweeps have no update rule to chain through).
    # Depths that legalise to the same effective chain dedup to one run.
    time_tiles: tuple = (1, 2, 4)
    # spatial-unrolling widths tried for stream candidates (single-step and
    # fused-loop alike — a wider sweep step needs no update rule).  Widths
    # the legaliser demotes to the same effective P dedup to one run.
    plane_tiles: tuple = (1, 2, 4)
    dtypes: tuple | None = None   # None = the dtype compile_program asked for
    seed: int = 0               # synthetic measurement data
    # the cache key identifies the *problem*, not the search effort: a plan
    # tuned with a shallow config is served to later deeper-config compiles.
    # Set force_retune to bypass the lookup and overwrite the cached entry
    # with this config's winner.
    force_retune: bool = False
    # timer(fn) -> seconds; None = warm-up + best-of-k wall clock.  Tests
    # inject deterministic fakes here (and count invocations to prove cache
    # hits measure nothing).
    timer: Callable | None = None


class PlanCache:
    """Persistent JSON store of tuned plans.

    ``path=None`` keeps the cache in memory only (tests); the default path
    is ``$REPRO_PLAN_CACHE`` or ``~/.cache/stencil_hmls/plan_cache.json``.
    File format: ``{"version": CACHE_SCHEMA_VERSION, "entries":
    {cache_key: record}}`` where a record holds the serialised plan, its
    ``carry_write`` style, and the tuning measurements (see
    :func:`tune_plan`).  Files written by a different schema version (or
    unreadable ones) load as empty: every lookup misses, and the first
    store rewrites the file at the current version.

    Every ``lookup`` counts itself into the cache's own metrics registry
    (``cache.metrics``, counters ``hits``/``misses``) and mirrors into the
    process-wide registry as ``plan_cache.hits``/``plan_cache.misses`` —
    the *cache* owns its hit accounting, callers just read the counters.
    """

    def __init__(self, path: str | None = "auto"):
        self.path = default_cache_path() if path == "auto" else path
        self._mem: dict = {}
        self._lock = threading.Lock()
        self.metrics = MetricsRegistry()

    @property
    def hits(self) -> int:
        return self.metrics.counter("hits").value

    @property
    def misses(self) -> int:
        return self.metrics.counter("misses").value

    def _load(self) -> dict:
        if self.path and os.path.exists(self.path):
            try:
                with open(self.path) as f:
                    doc = json.load(f)
                if (doc.get("version") == CACHE_SCHEMA_VERSION
                        and isinstance(doc.get("entries"), dict)):
                    return doc
            except (json.JSONDecodeError, OSError):
                pass
        return {"version": CACHE_SCHEMA_VERSION, "entries": {}}

    def lookup(self, key: str) -> dict | None:
        with self._lock:
            rec = self._mem.get(key)
        if rec is None:
            rec = self._load()["entries"].get(key)
        name = "hits" if rec is not None else "misses"
        self.metrics.counter(name).inc()
        global_metrics().counter(f"plan_cache.{name}").inc()
        return rec

    def store(self, key: str, record: dict) -> None:
        """Persist ``record`` under ``key`` — safe under concurrent writers.

        Two tuners (or two serving engines) sharing one cache file must not
        clobber each other's entries, so the rewrite is an atomic
        read-merge-replace: an advisory ``flock`` on ``<path>.lock``
        serialises writers (across objects *and* processes), each writer
        re-reads the file under the lock, layers its own entries on top,
        writes to a per-writer unique temp file, and ``os.replace``s it in
        — readers never see a torn or truncated JSON, and no store loses
        another writer's entries.  On platforms without ``fcntl`` the lock
        degrades to best-effort merge-on-write.
        """
        with self._lock:
            self._mem[key] = record
            if not self.path:
                return
            d = os.path.dirname(self.path)
            if d:
                os.makedirs(d, exist_ok=True)
            with self._file_lock():
                # re-read under the lock so entries written by other
                # processes/threads since our last load survive the rewrite
                doc = self._load()
                doc["entries"].update(self._mem)
                tmp = (f"{self.path}.{os.getpid()}."
                       f"{uuid.uuid4().hex[:8]}.tmp")
                try:
                    with open(tmp, "w") as f:
                        json.dump(doc, f, indent=2)
                    os.replace(tmp, self.path)
                finally:
                    if os.path.exists(tmp):
                        os.unlink(tmp)

    @contextlib.contextmanager
    def _file_lock(self):
        try:
            import fcntl
        except ImportError:  # non-POSIX: best-effort merge-on-write
            yield
            return
        with open(f"{self.path}.lock", "w") as lf:
            fcntl.flock(lf, fcntl.LOCK_EX)
            try:
                yield
            finally:
                fcntl.flock(lf, fcntl.LOCK_UN)


def _mesh_tag(mesh, mesh_axes) -> str:
    """Stable encoding of the mesh topology a plan was tuned under (the
    shared :func:`~repro.core.schedule.mesh_fingerprint`): topologies of
    the same device count (2x4 vs 4x2, or different grid-axis assignments)
    shard different local blocks and measure different collectives — their
    tuned plans must not serve each other."""
    return mesh_fingerprint(mesh, mesh_axes)


def cache_key(p: Program, grid: Sequence[int], backend: str,
              dtype: str = "float32",
              mode: str = "loop", mesh=None, mesh_axes=None) -> str:
    """Tuned plans transfer only between identical search problems: same
    program semantics (boundary conditions included, via the fingerprint),
    grid, backend, jax version, whether the kernels run interpreted or
    compiled (:func:`repro.hw.pallas_interpret`), requested dtype, mesh
    topology, and tuning mode (``"loop"`` = ranked by the fused ``steps=N``
    measurement with carry-aware VMEM pruning, ``"single"`` = single-step
    only) — a single-step winner must not silently serve a fused compile,
    nor a 2x2 winner a 4x1 mesh."""
    return "|".join([
        program_fingerprint(p),
        "grid=" + "x".join(str(int(g)) for g in grid),
        f"backend={backend}",
        f"jax={jax.__version__}",
        f"interpret={int(hw.pallas_interpret())}",
        f"dtype={dtype}",
        f"mode={mode}",
        f"mesh={_mesh_tag(mesh, mesh_axes)}",
    ])


@dataclasses.dataclass
class _Candidate:
    plan: DataflowPlan
    carry_write: str
    label: str
    modeled_s: float = float("inf")
    us_single: float | None = None
    us_fused: float | None = None

    def score(self) -> float:
        if self.us_fused is not None:
            return self.us_fused
        return self.us_single if self.us_single is not None else float("inf")


@dataclasses.dataclass
class TuneResult:
    plan: DataflowPlan
    carry_write: str
    key: str
    record: dict
    cache_hit: bool
    # every measured candidate, winner-first sorted by score (empty on hit)
    measured: list = dataclasses.field(default_factory=list)

    @property
    def baseline(self) -> _Candidate | None:
        """The measured ``auto_plan`` heuristic seed itself (exact label:
        the ``auto_plan/cw=...`` variants are different candidates)."""
        for c in self.measured:
            if c.label == "auto_plan":
                return c
        return None


# --------------------------------------------------------------------------
# candidate generation
# --------------------------------------------------------------------------

def _block_candidates(p: Program, grid: Sequence[int]) -> list:
    """A coarse sweep over the leading axes, each tile capped as
    :func:`~repro.core.schedule.auto_plan` caps its own
    (:func:`~repro.core.schedule.cap_tile`: the last two axes whole, at
    most ``TILE_POINTS`` points)."""
    grid = [int(g) for g in grid]
    n_tiled = max(0, p.ndim - 2)
    per_axis = [sorted({grid[ax]} | {c for c in (8, 32) if c < grid[ax]})
                for ax in range(n_tiled)]
    return list(dict.fromkeys(cap_tile(b, grid)
                              for b in itertools.product(*per_axis)))


def _behaviour_key(plan: DataflowPlan, carry_write: str, backend: str,
                   with_loop: bool):
    """Two candidates with the same key lower to the same executable."""
    cw = carry_write if with_loop else None
    if backend != "pallas":
        # the jnp lowerings ignore groups, block shape and dtype
        return (cw,)
    if plan.schedule == "stream":
        # streams ignore block shape; the legalised regions decide the
        # kernels (two strategies whose groups legalise identically tie).
        # The *effective* chain depth matters only in fused-loop mode —
        # single-step sweeps never chain — and requested depths demoted to
        # the same effective depth lower identically.
        eff = (plan.stream.time_tile if plan.stream is not None
               else plan.time_tile)
        # the effective sweep width matters in both modes — spatial
        # unrolling needs no update rule — and requested widths demoted to
        # the same effective P lower identically.
        eff_p = (plan.stream.plane_tile if plan.stream is not None
                 else plan.plane_tile)
        regions = (plan.stream.regions if plan.stream is not None
                   else tuple(tuple(g) for g in plan.groups))
        return ("stream", regions, plan.dtype, cw,
                int(eff) if with_loop else 1, int(eff_p))
    return (tuple(tuple(g) for g in plan.groups), tuple(plan.block),
            plan.dtype, cw)


def _candidates(p: Program, grid, backend: str, dtype: str,
                cfg: TuneConfig, with_loop: bool) -> list:
    ndim = p.ndim
    out: list[_Candidate] = []
    seen: set = set()

    def add(plan, cw, label):
        k = _behaviour_key(plan, cw, backend, with_loop)
        if k in seen:
            return
        seen.add(k)
        out.append(_Candidate(plan=plan, carry_write=cw, label=label))

    carry_writes = cfg.carry_writes if with_loop else ("repad",)
    steps = cfg.steps if with_loop else None
    # the heuristic seed is always candidate 0: the tuned plan can only keep
    # or beat it on the tuner's own measurements
    base = auto_plan(p, grid, backend=backend, dtype=dtype,
                     vmem_budget=cfg.vmem_budget, steps=steps)
    add(base, "repad", "auto_plan")
    for cw in carry_writes:
        add(base, cw, f"auto_plan/cw={cw}")
    blocks = _block_candidates(p, grid)
    for strat, dt in itertools.product(cfg.strategies, cfg.dtypes or (dtype,)):
        plan0 = auto_plan(p, grid, backend=backend, dtype=dt, strategy=strat,
                          vmem_budget=cfg.vmem_budget, steps=steps)
        for blk, cw in itertools.product(blocks, carry_writes):
            plan = dataclasses.replace(plan0, block=tuple(blk),
                                       groups=[list(g) for g in plan0.groups])
            add(plan, cw, f"{strat}/block={'x'.join(map(str, blk))}/cw={cw}"
                          + (f"/dtype={dt}" if dt != "float32" else ""))
        # the stream schedule is a first-class plan dimension: one
        # shift-register candidate per fuse strategy (block shape does not
        # apply — the non-stream axes are resident whole) x temporal-chain
        # depth (fused-loop mode only; depths legalised to the same
        # effective chain dedup via the behaviour key)
        if backend == "pallas" and ndim >= 2:
            tiles = tuple(cfg.time_tiles) if with_loop else (1,)
            ptiles = tuple(cfg.plane_tiles) or (1,)
            for tt, pt in itertools.product(tiles, ptiles):
                plan_s = auto_plan(p, grid, backend=backend, dtype=dt,
                                   strategy=strat,
                                   vmem_budget=cfg.vmem_budget, steps=steps,
                                   schedule="stream", time_tile=int(tt),
                                   plane_tile=int(pt))
                tag = f"/T={int(tt)}" if int(tt) > 1 else ""
                tag += f"/P={int(pt)}" if int(pt) > 1 else ""
                for cw in carry_writes:
                    add(plan_s, cw, f"stream/{strat}{tag}/cw={cw}"
                                   + (f"/dtype={dt}" if dt != "float32"
                                      else ""))
    return out


# --------------------------------------------------------------------------
# measurement
# --------------------------------------------------------------------------

def _synth_data(p: Program, grid, seed: int = 0):
    rng = np.random.default_rng(seed)
    grid = tuple(int(g) for g in grid)
    fields = {f: jnp.asarray(rng.normal(size=grid).astype(np.float32) * 0.1)
              for f in p.input_fields()}
    scalars = {s: jnp.float32(0.05) for s in p.scalars}
    coeffs = {c: jnp.asarray(
        (np.abs(rng.normal(size=(grid[ax],))) + 0.5).astype(np.float32))
        for c, ax in p.coeffs.items()}
    return fields, scalars, coeffs


def _default_timer_factory(warmup: int, repeats: int) -> Callable:
    def timer(fn):
        out = None
        for _ in range(max(1, warmup)):
            out = fn()                      # jit compile + warm
        jax.block_until_ready(out)
        best = float("inf")
        for _ in range(max(1, repeats)):    # best-of-k (CPU noise)
            t0 = time.perf_counter()
            out = fn()
            jax.block_until_ready(out)
            best = min(best, time.perf_counter() - t0)
        return best
    return timer


def _measure(p, grid, cand: _Candidate, data, update, cfg: TuneConfig,
             timer, mesh=None, mesh_axes=None) -> None:
    # deferred: pipeline imports tune
    from .pipeline import CompileOptions, compile_program
    fields, scalars, coeffs = data
    ex = compile_program(p, grid, options=CompileOptions(
        backend=cand.plan.backend, plan=cand.plan,
        mesh=mesh, mesh_axes=mesh_axes))
    cand.us_single = timer(lambda: ex(fields, scalars, coeffs)) * 1e6
    if update is not None:
        exN = compile_program(p, grid, options=CompileOptions(
            backend=cand.plan.backend, plan=cand.plan, steps=cfg.steps,
            update=update, carry_write=cand.carry_write,
            mesh=mesh, mesh_axes=mesh_axes))
        cand.us_fused = timer(lambda: exN(fields, scalars, coeffs)) * 1e6


# --------------------------------------------------------------------------
# the tuning loop
# --------------------------------------------------------------------------

def tune_plan(p: Program, grid, *, backend: str = "pallas",
              dtype: str = "float32",
              update=None, config: TuneConfig | None = None,
              cache: PlanCache | None = None,
              mesh=None, mesh_axes=None) -> TuneResult:
    """Search the plan space by measurement and persist the winner.

    Generates candidates, prunes with the corrected VMEM cost and the
    roofline plan model, measures the survivors (single-step always; fused
    ``steps=N`` when ``update`` is given, which is also what the winner is
    ranked by), and stores the winning record under :func:`cache_key`.

    With ``mesh``/``mesh_axes`` the search tunes a *sharded* plan:
    candidate blocks are generated and VMEM-priced against the per-shard
    local grid, every measurement runs the real ``shard_map`` executable
    (halo exchange included), and the cache key carries the mesh topology.
    """
    # deferred: repro.analysis imports core IR modules, which would re-enter
    # this package's __init__ at import time
    from ..analysis.stencil_roofline import model_plan
    cfg = config or TuneConfig()
    cache = PlanCache() if cache is None else cache
    grid = tuple(int(g) for g in grid)
    plan_grid = grid
    if mesh is not None:
        from .schedule import normalize_mesh_axes, shard_local_grid
        if mesh_axes is None:
            mesh_axes = tuple(mesh.axis_names)
        mesh_axes = normalize_mesh_axes(mesh_axes, p.ndim)
        plan_grid = shard_local_grid(grid, mesh, mesh_axes)
    timer0 = cfg.timer or _default_timer_factory(cfg.warmup, cfg.repeats)

    def timer(fn):
        # every on-device timing is counted process-wide: cache-hit tests
        # assert a zero delta here instead of monkeypatching the timer
        global_metrics().counter("tune.timed_runs").inc()
        return timer0(fn)

    with_loop = update is not None
    tracer = current_tracer()
    global_metrics().counter("tune.runs").inc()

    # stream candidates compete under a mesh too: each shard sweeps its
    # local block (with exact neighbour ghost planes when the stream axis
    # itself is sharded), so ``plan_grid`` prices VMEM and the roofline
    # per shard and the measurement runs the real shard_map executable
    cands = _candidates(p, plan_grid, backend, dtype, cfg,
                        with_loop)
    baseline, rest = cands[0], cands[1:]

    # prune: VMEM feasibility on the local block (carry-aware when tuning
    # the fused loop), then modeled-time ranking; the baseline never pays
    # for either filter
    steps_for_cost = cfg.steps if with_loop else None
    feasible = []
    for c in rest:
        if (c.plan.backend == "pallas"
                and vmem_cost(p, c.plan, plan_grid, steps=steps_for_cost)
                > cfg.vmem_budget):
            continue
        feasible.append(c)
    for c in [baseline] + feasible:
        c.modeled_s = model_plan(p, c.plan, plan_grid)
    feasible.sort(key=lambda c: c.modeled_s)
    survivors = [baseline] + feasible[:max(0, cfg.max_measured - 1)]

    data = _synth_data(p, grid, seed=cfg.seed)
    with tracer.span("tune", program=p.name, backend=backend,
                     mode="loop" if with_loop else "single",
                     candidates=len(cands), measured=len(survivors)):
        for c in survivors:
            with tracer.span("tune.candidate", program=p.name,
                             label=c.label) as csp:
                _measure(p, grid, c, data, update, cfg, timer,
                         mesh=mesh, mesh_axes=mesh_axes)
                csp.set(modeled_us=c.modeled_s * 1e6,
                        us_single=c.us_single, us_fused=c.us_fused)

    order = sorted(range(len(survivors)),
                   key=lambda i: (survivors[i].score(), i))
    winner = survivors[order[0]]

    key = cache_key(p, grid, backend, dtype,
                    "loop" if with_loop else "single",
                    mesh=mesh, mesh_axes=mesh_axes)
    record = {
        "plan": plan_to_dict(winner.plan),
        "carry_write": winner.carry_write,
        "label": winner.label,
        # effective temporal-chain depth of the winner (1 = unchained)
        "time_tile": int(winner.plan.stream.time_tile
                         if winner.plan.stream is not None
                         else winner.plan.time_tile),
        # effective sweep width of the winner (1 = plane-at-a-time)
        "plane_tile": int(winner.plan.stream.plane_tile
                          if winner.plan.stream is not None
                          else winner.plan.plane_tile),
        "us_single": winner.us_single,
        "us_fused": winner.us_fused,
        "baseline_us_single": baseline.us_single,
        "baseline_us_fused": baseline.us_fused,
        "modeled_us": winner.modeled_s * 1e6,
        "mesh": _mesh_tag(mesh, mesh_axes),
        "steps": cfg.steps if with_loop else None,
        "candidates": len(cands),
        "measured": len(survivors),
        "fingerprint": program_fingerprint(p),
        "jax_version": jax.__version__,
        "tuned_at": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
    }
    cache.store(key, record)
    if tracer.enabled:
        tracer.emit(PlanChosen(
            program=p.name, backend=backend,
            schedule=winner.plan.schedule, strategy="tuned",
            label=winner.label, time_tile=record["time_tile"],
            plane_tile=record["plane_tile"], modeled_us=record["modeled_us"],
            measured_us=winner.score()))
    return TuneResult(plan=winner.plan, carry_write=winner.carry_write,
                      key=key, record=record, cache_hit=False,
                      measured=[survivors[i] for i in order])


def get_tuned_plan(p: Program, grid, *, backend: str = "pallas",
                   dtype: str = "float32",
                   update=None, config: TuneConfig | None = None,
                   cache: PlanCache | None = None,
                   mesh=None, mesh_axes=None) -> TuneResult:
    """Cache-first entry point behind ``compile_program(strategy="tuned")``.

    A hit deserialises the stored plan and performs **zero** timed runs; a
    miss runs :func:`tune_plan` and persists the winner.  The key does not
    encode the search effort, so pass a config with ``force_retune=True``
    to re-search (and overwrite the entry) with different knobs.
    """
    cache = PlanCache() if cache is None else cache
    if mesh is not None:
        from .schedule import normalize_mesh_axes
        if mesh_axes is None:
            mesh_axes = tuple(mesh.axis_names)
        mesh_axes = normalize_mesh_axes(mesh_axes, p.ndim)
    key = cache_key(p, tuple(int(g) for g in grid), backend, dtype,
                    "loop" if update is not None else "single",
                    mesh=mesh, mesh_axes=mesh_axes)
    rec = None if (config is not None and config.force_retune) \
        else cache.lookup(key)
    tracer = current_tracer()
    if rec is not None:
        if tracer.enabled:
            tracer.emit(CacheHit(cache="tuned_plan", key=key))
        return TuneResult(plan=plan_from_dict(rec["plan"]),
                          carry_write=rec.get("carry_write", "repad"),
                          key=key, record=rec, cache_hit=True)
    if tracer.enabled:
        tracer.emit(CacheMiss(cache="tuned_plan", key=key))
    return tune_plan(p, grid, backend=backend, dtype=dtype, update=update,
                     config=config, cache=cache,
                     mesh=mesh, mesh_axes=mesh_axes)


# --------------------------------------------------------------------------
# Serving-layer executor records (repro.serve's slice of the plan cache)
# --------------------------------------------------------------------------

def make_serve_record(plan: DataflowPlan, carry_write: str,
                      bucket: Sequence[int], steps: int | None) -> dict:
    """Executor record the serving engine persists per compiled bucket: the
    plan the executable was built from plus enough metadata that a *fresh
    engine process* can rebuild the identical executable without planning,
    tuning, or guessing.  Schema-stamped like tuned-plan records — see
    :func:`read_serve_record`."""
    return {
        "kind": "serve_executor",
        "schema": PLAN_SCHEMA_VERSION,
        "plan": plan_to_dict(plan),
        "carry_write": carry_write,
        "bucket": [int(b) for b in bucket],
        "steps": None if steps is None else int(steps),
        "jax_version": jax.__version__,
        "stored_at": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
    }


def read_serve_record(rec: dict | None):
    """Decode a serving executor record: ``(plan, carry_write)``, or ``None``
    when the record is absent, malformed, or written under a different
    ``PLAN_SCHEMA_VERSION`` — a stale-schema record is a clean *miss* (the
    engine replans and overwrites), never a misdecoded plan."""
    if not isinstance(rec, dict) or rec.get("kind") != "serve_executor":
        return None
    if rec.get("schema") != PLAN_SCHEMA_VERSION:
        return None
    try:
        plan = plan_from_dict(rec["plan"])
    except (KeyError, TypeError, ValueError):
        return None
    return plan, rec.get("carry_write", "repad")
