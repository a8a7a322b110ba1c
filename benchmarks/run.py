"""Benchmark driver — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (us_per_call = 0 for modeled
or dimensionless rows).  An optional LM-roofline summary is appended when
dry-run artifacts exist under experiments/dryrun/.

Run:  PYTHONPATH=src python -m benchmarks.run

``--smoke`` runs a CI-sized subset instead (tiny grid, a few steps, all
three backends incl. pallas interpret) and writes the rows to a
``BENCH_*.json`` artifact so the perf trajectory accumulates per commit.

``--tune`` runs the measured plan search (repro.core.tune) on the same
CI-sized problem and emits tuned-vs-``auto_plan`` rows per backend, so the
artifact trail records the tuner's wins per commit; the winning plans are
persisted to the JSON plan cache at ``--plan-cache``.

``--mesh AxB`` (with ``--smoke``) additionally runs the *sharded* fused
loop — ``compile_program(..., mesh=, steps=N)`` with carry-resident halo
exchange — over a simulated AxB device mesh and emits sharded steps/sec
rows into the same artifact.  On CPU hosts the required device count is
simulated automatically via ``--xla_force_host_platform_device_count``.
"""

from __future__ import annotations

import argparse
import glob
import json
import math
import os
import platform
import sys
import time


def _parse_mesh(val: str) -> tuple:
    try:
        shape = tuple(int(v) for v in val.split("x"))
    except ValueError:
        raise SystemExit(f"run.py: error: --mesh must be AxB (or AxBxC), "
                         f"got {val!r}")
    if not shape or any(s < 1 for s in shape):
        raise SystemExit(f"run.py: error: --mesh axes must be >= 1, "
                         f"got {val!r}")
    return shape


def _mesh_arg(argv) -> tuple | None:
    for i, a in enumerate(argv):
        if a == "--mesh" and i + 1 < len(argv):
            return _parse_mesh(argv[i + 1])
        if a.startswith("--mesh="):
            return _parse_mesh(a.split("=", 1)[1])
    return None


# honour --mesh before anything imports jax: simulated CPU devices can only
# be configured through XLA_FLAGS at process start (append to any existing
# flags; an explicit device-count override wins)
_MESH_SHAPE = _mesh_arg(sys.argv)
if _MESH_SHAPE and ("--xla_force_host_platform_device_count"
                    not in os.environ.get("XLA_FLAGS", "")):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "") + " "
        + "--xla_force_host_platform_device_count="
        + str(math.prod(_MESH_SHAPE))).strip()

try:
    from benchmarks import fig4_throughput, fig5_6_energy, tab1_2_resources
except ModuleNotFoundError:  # invoked as `python benchmarks/run.py`
    import fig4_throughput
    import fig5_6_energy
    import tab1_2_resources


def emit(name: str, us: float, derived: str = ""):
    print(f"{name},{us:.2f},{derived}", flush=True)


def run_smoke(out_path: str, mesh_shape: tuple | None = None,
              baseline_path: str | None = None) -> None:
    """Tiny fused-loop benchmark (16^3, 3 steps, interpret mode) -> JSON.

    With ``mesh_shape`` the sharded fused loop (one dispatch, ppermute
    halo exchange inside the carry) runs over a simulated device mesh and
    contributes ``dist/...`` steps/sec rows to the artifact.  With
    ``baseline_path`` the compute rows are gated against the committed
    baseline (see :func:`check_smoke_baseline`)."""
    rows = []

    def emit_row(name: str, us: float, derived: str = "", **extra):
        emit(name, us, derived)
        row = {"name": name, "us": round(us, 2), "derived": derived}
        row.update({k: v for k, v in extra.items() if v is not None})
        rows.append(row)

    grid, steps = (16, 16, 16), 3
    fig4_throughput.run_fused_loop(
        emit_row, grid=grid, steps=steps,
        backends=("jnp_naive", "jnp_fused", "pallas"))
    run_schedule_rows(emit_row, grid=grid, steps=steps)
    if mesh_shape:
        run_sharded_loop(emit_row, grid=grid, steps=steps,
                         mesh_shape=mesh_shape)
        run_stream_mesh_rows(emit_row, grid=grid, steps=steps,
                             mesh_shape=mesh_shape)
    doc = {
        "kind": "bench_smoke",
        "grid": list(grid),
        "steps": steps,
        "mesh": list(mesh_shape) if mesh_shape else None,
        "time": time.time(),
        "platform": platform.platform(),
        "commit": os.environ.get("GITHUB_SHA", ""),
        "rows": rows,
    }
    with open(out_path, "w") as f:
        json.dump(doc, f, indent=2)
    print(f"wrote {out_path} ({len(rows)} rows)", flush=True)
    if baseline_path:
        check_smoke_baseline(rows, baseline_path)


def check_smoke_baseline(rows: list, baseline_path: str) -> None:
    """Compute-row regression gate, mirroring the ``--serve`` one: every
    ``steps_per_sec`` row in the committed baseline must appear in the
    smoke artifact at no less than ``baseline * (1 - tolerance)`` steps/sec.
    A baseline row missing from the artifact fails too — a silently renamed
    or dropped row must not read as a pass."""
    if not os.path.exists(baseline_path):
        print(f"smoke baseline {baseline_path} missing; gate skipped",
              flush=True)
        return
    base = json.load(open(baseline_path))
    tol = float(base.get("tolerance", 0.30))
    measured = {}
    for row in rows:
        derived = row.get("derived", "")
        if derived.endswith("steps/s"):
            measured[row["name"]] = float(derived.split()[0])
    failures = []
    for name, floor_sps in base.get("steps_per_sec", {}).items():
        floor = float(floor_sps) * (1.0 - tol)
        got = measured.get(name)
        if got is None:
            failures.append(f"  {name}: row missing from artifact")
        elif got < floor:
            failures.append(f"  {name}: {got:.2f} steps/s < {floor:.2f} "
                            f"floor (baseline {float(floor_sps):.2f} "
                            f"- {tol:.0%})")
    # roofline-achieved floors WARN, never fail: the fraction on CI hosts
    # is noisy (interpret mode on shared CPU vs a TPU-priced model), so a
    # dip is a flag for a human, not a red build — ROADMAP item 3 hardens
    # this into a gate once the trend stabilises
    fractions = {row["name"]: row["roofline_fraction"] for row in rows
                 if "roofline_fraction" in row}
    for name, floor in base.get("roofline_floor", {}).items():
        got = fractions.get(name)
        if got is None:
            print(f"WARNING: {name}: no roofline_fraction in artifact "
                  f"(floor {float(floor):.2e})", flush=True)
        elif got < float(floor):
            print(f"WARNING: {name}: roofline_fraction {got:.2e} below "
                  f"floor {float(floor):.2e} (achieved share of the model "
                  "prediction dropped — not failing the build)", flush=True)
    if failures:
        raise SystemExit("smoke compute-row regression:\n"
                         + "\n".join(failures))
    print(f"smoke baseline check OK: {len(base.get('steps_per_sec', {}))} "
          f"rows within {tol:.0%} of {baseline_path}", flush=True)


def run_schedule_rows(emit_row, grid: tuple, steps: int) -> None:
    """Stream-vs-block schedule rows: fused-loop steps/sec of the pallas
    shift-register sweep (each input plane fetched once, windows in the
    kernel carry) next to the tiled block schedule, so the artifact trail
    records the dataflow layer's trajectory per commit.  Inputs come from
    ``fig4_throughput._data`` so these rows are directly comparable to the
    adjacent ``fig4/.../fused_loop`` rows in the same artifact."""
    import jax
    from repro.apps import pw_advection, pw_advection_update
    from repro.core import CompileOptions, compile_program
    from repro.obs.achieved import fraction_for

    p = pw_advection()
    update = pw_advection_update(0.1)
    tag = "x".join(str(g) for g in grid)
    fields, scalars, coeffs = fig4_throughput._data(p, grid)

    def measure(opts, nsteps):
        """Best-of-3 seconds per call plus the roofline-achieved fraction
        (measured vs model_plan prediction — tiny under CPU interpret, the
        per-commit *trend* is what the baseline floor watches)."""
        exN = compile_program(p, grid, options=opts)
        jax.block_until_ready(exN(fields, scalars, coeffs)["u"])
        dt = float("inf")
        for _ in range(3):                      # best-of-3 (CPU noise)
            t0 = time.perf_counter()
            out = exN(fields, scalars, coeffs)
            jax.block_until_ready(out["u"])
            dt = min(dt, time.perf_counter() - t0)
        return dt, fraction_for(exN, dt)

    sps = {}
    for schedule in ("block", "stream"):
        dt, rf = measure(CompileOptions(backend="pallas", steps=steps,
                                        update=update, schedule=schedule),
                         steps)
        sps[schedule] = steps / dt
        emit_row(f"sched/pw_advection/{tag}/pallas/{schedule}/fused_loop",
                 dt * 1e6, f"{steps / dt:.2f} steps/s",
                 roofline_fraction=rf)
    emit_row(f"sched/pw_advection/{tag}/pallas/stream_vs_block", 0.0,
             f"{sps['stream'] / sps['block']:.2f}x stream vs block")

    # temporal blocking through the stream sweep: T=4 chains four time
    # steps per sweep (inputs fetched from HBM once per 4 steps), T=1 is
    # the unchained baseline at the same step count
    tsteps = max(steps, 4)
    tiled = {}
    for tt in (1, 4):
        dt, rf = measure(CompileOptions(backend="pallas", steps=tsteps,
                                        update=update, schedule="stream",
                                        time_tile=tt), tsteps)
        tiled[tt] = tsteps / dt
        emit_row(f"sched/pw_advection/{tag}/pallas/stream/time_tile={tt}"
                 f"/fused_loop", dt * 1e6, f"{tsteps / dt:.2f} steps/s",
                 roofline_fraction=rf)
    emit_row(f"sched/pw_advection/{tag}/pallas/stream/t4_vs_t1", 0.0,
             f"{tiled[4] / tiled[1]:.2f}x time_tile=4 vs 1")

    # spatial x temporal tile matrix: plane_tile=P advances P planes per
    # sweep grid step (amortising per-step dispatch/window-shift overhead),
    # composing with the T-deep temporal chain into one PxT tile
    matrix = {}
    for pt in (1, 4):
        for tt in (1, 4):
            dt, rf = measure(CompileOptions(backend="pallas", steps=tsteps,
                                            update=update, schedule="stream",
                                            time_tile=tt, plane_tile=pt),
                             tsteps)
            matrix[pt, tt] = tsteps / dt
            emit_row(f"sched/pw_advection/{tag}/pallas/stream"
                     f"/plane_tile={pt}/time_tile={tt}/fused_loop",
                     dt * 1e6, f"{tsteps / dt:.2f} steps/s",
                     roofline_fraction=rf)
    emit_row(f"sched/pw_advection/{tag}/pallas/stream/p4_vs_p1", 0.0,
             f"{matrix[4, 1] / matrix[1, 1]:.2f}x plane_tile=4 vs 1")


def run_sharded_loop(emit_row, grid: tuple, steps: int,
                     mesh_shape: tuple) -> None:
    """Sharded fused-loop rows: steps/sec of N distributed steps in one
    jitted dispatch, zero and periodic boundaries."""
    import jax
    import numpy as np
    from repro.apps import pw_advection, pw_advection_update
    from repro.core import compile_program
    from repro.dist.sharding import make_auto_mesh

    names = ("X", "Y", "Z")[:len(mesh_shape)]
    mesh = make_auto_mesh(mesh_shape, names)
    update = pw_advection_update(0.1)
    tag = "x".join(str(g) for g in grid)
    mtag = "x".join(str(m) for m in mesh_shape)
    rng = np.random.default_rng(0)
    fields = {f: rng.normal(size=grid).astype(np.float32)
              for f in ("u", "v", "w")}
    scalars = {"tcx": np.float32(0.05), "tcy": np.float32(0.05)}
    coeffs = {c: np.linspace(0.9, 1.1, grid[2]).astype(np.float32)
              for c in ("tzc1", "tzc2", "tzd1", "tzd2")}
    for boundary in ("zero", "periodic"):
        p = pw_advection(boundary=boundary)
        for backend in ("jnp_fused", "pallas"):
            exN = compile_program(p, grid, backend=backend, mesh=mesh,
                                  mesh_axes=names, steps=steps,
                                  update=update)
            jax.block_until_ready(exN(fields, scalars, coeffs)["u"])
            dt = float("inf")
            for _ in range(3):                  # best-of-3 (CPU noise)
                t0 = time.perf_counter()
                out = exN(fields, scalars, coeffs)
                jax.block_until_ready(out["u"])
                dt = min(dt, time.perf_counter() - t0)
            emit_row(
                f"dist/pw_advection/{tag}/mesh{mtag}/{boundary}/{backend}"
                "/fused_loop",
                dt * 1e6, f"{steps / dt:.2f} steps/s "
                          f"local={exN.shard.local_grid}")


def run_stream_mesh_rows(emit_row, grid: tuple, steps: int,
                         mesh_shape: tuple) -> None:
    """Stream-schedule-under-mesh rows: each shard sweeps the stream axis
    over its local block with halo refresh inside the fused-loop carry.
    Emits steps/sec for time_tile 1 and 2 plus the stream-vs-block ratio
    on the same mesh (the block number is measured here, same data and
    discipline as ``run_sharded_loop``, so the ratio is apples-to-apples)."""
    import jax
    import numpy as np
    from repro.apps import pw_advection, pw_advection_update
    from repro.core import CompileOptions, compile_program
    from repro.dist.sharding import make_auto_mesh

    names = ("X", "Y", "Z")[:len(mesh_shape)]
    mesh = make_auto_mesh(mesh_shape, names)
    update = pw_advection_update(0.1)
    tag = "x".join(str(g) for g in grid)
    mtag = "x".join(str(m) for m in mesh_shape)
    p = pw_advection()
    rng = np.random.default_rng(0)
    fields = {f: rng.normal(size=grid).astype(np.float32)
              for f in ("u", "v", "w")}
    scalars = {"tcx": np.float32(0.05), "tcy": np.float32(0.05)}
    coeffs = {c: np.linspace(0.9, 1.1, grid[2]).astype(np.float32)
              for c in ("tzc1", "tzc2", "tzd1", "tzd2")}

    def measure(schedule, time_tile=None):
        exN = compile_program(p, grid, options=CompileOptions(
            backend="pallas", steps=steps, update=update, schedule=schedule,
            time_tile=time_tile, mesh=mesh, mesh_axes=names))
        jax.block_until_ready(exN(fields, scalars, coeffs)["u"])
        dt = float("inf")
        for _ in range(3):                      # best-of-3 (CPU noise)
            t0 = time.perf_counter()
            out = exN(fields, scalars, coeffs)
            jax.block_until_ready(out["u"])
            dt = min(dt, time.perf_counter() - t0)
        return dt

    sps = {}
    for schedule in ("block", "stream"):
        dt = measure(schedule)
        sps[schedule] = steps / dt
        emit_row(f"sched/pw_advection/{tag}/pallas/{schedule}/mesh={mtag}"
                 "/fused_loop", dt * 1e6, f"{steps / dt:.2f} steps/s")
    emit_row(f"sched/pw_advection/{tag}/pallas/mesh={mtag}/stream_vs_block",
             0.0, f"{sps['stream'] / sps['block']:.2f}x stream vs block "
                  "under mesh")
    dt = measure("stream", time_tile=2)
    emit_row(f"sched/pw_advection/{tag}/pallas/stream/mesh={mtag}"
             "/time_tile=2/fused_loop", dt * 1e6,
             f"{steps / dt:.2f} steps/s")


def run_tune(out_path: str, cache_path: str) -> None:
    """Measured plan search on the smoke problem (16^3 x 3 steps, all three
    backends, pruned candidate set) -> tuned-vs-auto_plan rows + plan cache."""
    from repro.apps import pw_advection, pw_advection_update
    from repro.core import tune_plan, TuneConfig, PlanCache

    grid, steps = (16, 16, 16), 3
    p = pw_advection()
    cfg = TuneConfig(steps=steps, repeats=2, max_measured=4)
    cache = PlanCache(path=cache_path)
    tag = "x".join(map(str, grid))
    rows = []

    def emit_row(name: str, us: float, derived: str = ""):
        emit(name, us, derived)
        rows.append({"name": name, "us": round(us, 2), "derived": derived})

    for backend in ("jnp_naive", "jnp_fused", "pallas"):
        res = tune_plan(p, grid, backend=backend,
                        update=pw_advection_update(0.1), config=cfg,
                        cache=cache)
        base = res.baseline
        emit_row(f"tune/{p.name}/{tag}/{backend}/auto_plan",
                 base.us_fused, f"{steps / (base.us_fused * 1e-6):.2f} steps/s")
        emit_row(f"tune/{p.name}/{tag}/{backend}/tuned",
                 res.record["us_fused"],
                 f"{steps / (res.record['us_fused'] * 1e-6):.2f} steps/s "
                 f"[{res.record['label']}]")
        emit_row(f"tune/{p.name}/{tag}/{backend}/speedup", 0.0,
                 f"{base.us_fused / res.record['us_fused']:.2f}x tuned vs "
                 f"auto_plan ({res.record['measured']} of "
                 f"{res.record['candidates']} candidates measured)")
    doc = {
        "kind": "bench_tune",
        "grid": list(grid),
        "steps": steps,
        "time": time.time(),
        "platform": platform.platform(),
        "commit": os.environ.get("GITHUB_SHA", ""),
        "plan_cache": cache_path,
        "rows": rows,
    }
    with open(out_path, "w") as f:
        json.dump(doc, f, indent=2)
    print(f"wrote {out_path} ({len(rows)} rows); plan cache -> {cache_path}",
          flush=True)


def run_serve(out_path: str, baseline_path: str | None = None) -> None:
    """Serving-layer smoke: mixed-shape request traffic through the async
    StencilEngine -> throughput + latency-quantile rows, plus a regression
    gate against the committed baseline (fail when throughput drops more
    than the baseline's tolerance, default 30%)."""
    import numpy as np
    from repro.apps import pw_advection, pw_advection_update
    from repro.serve import StencilEngine, StencilRequest

    steps, rounds = 3, 6
    p = pw_advection()
    update = pw_advection_update(0.1)
    grids = [(16, 16, 16), (12, 14, 16), (16, 16, 24), (10, 16, 16)]
    rng = np.random.default_rng(0)

    def make_req(grid):
        fields = {f: rng.normal(size=grid).astype(np.float32) * 0.1
                  for f in ("u", "v", "w")}
        scalars = {"tcx": 0.05, "tcy": 0.05}
        coeffs = {c: np.linspace(0.9, 1.1, grid[2]).astype(np.float32)
                  for c in ("tzc1", "tzc2", "tzd1", "tzd2")}
        return StencilRequest(program=p, fields=fields, scalars=scalars,
                              coeffs=coeffs, steps=steps, update=update,
                              update_key="pw/dt=0.1")

    rows = []

    def emit_row(name: str, us: float, derived: str = ""):
        emit(name, us, derived)
        rows.append({"name": name, "us": round(us, 2), "derived": derived})

    with StencilEngine(backend="jnp_fused", max_batch=4,
                       window_s=0.005) as eng:
        # warm phase: compile every bucket once
        eng.map([make_req(g) for g in grids], timeout=600)
        warm_traces = eng.stats.traces
        eng.stats.reset_latencies()   # quantiles = steady state, not compiles
        t0 = time.perf_counter()
        futs = [eng.submit(make_req(g))
                for _ in range(rounds) for g in grids]
        for f in futs:
            f.result(600)
        wall = time.perf_counter() - t0
        s = eng.stats
        tput = len(futs) / wall
        tag = f"pw_advection/jnp_fused/steps{steps}"
        emit_row(f"serve/{tag}/throughput", 0.0,
                 f"{tput:.2f} req/s ({len(futs)} reqs in {wall:.2f}s)")
        emit_row(f"serve/{tag}/p50", s.p50_ms() * 1e3,
                 f"{s.p50_ms():.1f} ms")
        emit_row(f"serve/{tag}/p99", s.p99_ms() * 1e3,
                 f"{s.p99_ms():.1f} ms")
        emit_row(f"serve/{tag}/cache", 0.0,
                 f"hit_rate={s.cache_hit_rate():.2f} "
                 f"occupancy={s.occupancy():.2f} "
                 f"warm_traces={s.traces - warm_traces} "
                 f"compiles={s.compiles}")
        summary = {"throughput_rps": tput, "p50_ms": s.p50_ms(),
                   "p99_ms": s.p99_ms(), "hit_rate": s.cache_hit_rate(),
                   "occupancy": s.occupancy(),
                   "warm_traces": s.traces - warm_traces}
    doc = {
        "kind": "bench_serve_smoke",
        "grids": [list(g) for g in grids],
        "steps": steps,
        "requests": rounds * len(grids),
        "time": time.time(),
        "platform": platform.platform(),
        "commit": os.environ.get("GITHUB_SHA", ""),
        "summary": summary,
        "rows": rows,
    }
    with open(out_path, "w") as f:
        json.dump(doc, f, indent=2)
    print(f"wrote {out_path} ({len(rows)} rows)", flush=True)
    if summary["warm_traces"]:
        raise SystemExit(f"serve smoke: {summary['warm_traces']} re-traces "
                         "on warm requests (expected 0)")
    if baseline_path and os.path.exists(baseline_path):
        base = json.load(open(baseline_path))
        tol = float(base.get("tolerance", 0.30))
        floor = float(base["throughput_rps"]) * (1.0 - tol)
        if tput < floor:
            raise SystemExit(
                f"serve throughput regression: {tput:.2f} req/s < "
                f"{floor:.2f} req/s floor (baseline "
                f"{base['throughput_rps']:.2f} req/s - {tol:.0%})")
        print(f"serve baseline check OK: {tput:.2f} req/s >= "
              f"{floor:.2f} req/s floor", flush=True)


def lm_roofline_summary(emit):
    files = sorted(glob.glob("experiments/dryrun/*.json"))
    for f in files:
        r = json.load(open(f))
        if r.get("status") != "ok":
            emit(f"dryrun/{r['arch']}/{r['shape']}/{r['mesh']}", 0.0,
                 r.get("status", "?"))
            continue
        t = r["roofline"].get("terms_primary",
                              r["roofline"]["terms_corrected"])
        emit(f"dryrun/{r['arch']}/{r['shape']}/{r['mesh']}", 0.0,
             f"dom={t['dominant']} compute={t['compute_s']:.3e}s "
             f"memory={t['memory_s']:.3e}s coll={t['collective_s']:.3e}s "
             f"mem/dev={r['memory']['per_device_total']/2**30:.2f}GiB")


def main() -> None:
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    # no prefix abbreviation: the import-time _mesh_arg scanner (which sized
    # the simulated device count before jax loaded) only matches the full
    # --mesh spelling, and the two must never diverge
    ap = argparse.ArgumentParser(description=__doc__, allow_abbrev=False)
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized fused-loop benchmark, writes a JSON "
                         "artifact instead of the full paper sweep")
    ap.add_argument("--tune", action="store_true",
                    help="CI-sized measured plan search: tuned-vs-auto_plan "
                         "rows per backend + persistent plan cache")
    ap.add_argument("--serve", action="store_true",
                    help="serving-layer smoke: mixed-shape traffic through "
                         "the async StencilEngine, throughput + p50/p99 "
                         "rows, baseline regression gate")
    ap.add_argument("--serve-baseline",
                    default="benchmarks/serve_baseline.json",
                    help="baseline JSON for the --serve regression gate "
                         "(missing file skips the gate)")
    ap.add_argument("--smoke-baseline", default=None,
                    help="baseline JSON for the --smoke compute-row "
                         "regression gate (omit to skip; simulated-mesh "
                         "runs skew timings, so the CI gate only arms the "
                         "unmeshed smoke job)")
    ap.add_argument("--out", default=None,
                    help="artifact path for --smoke / --tune / --serve "
                         "(default BENCH_smoke.json / BENCH_tune_smoke.json "
                         "/ BENCH_serve_smoke.json)")
    ap.add_argument("--plan-cache", default="PLAN_CACHE_smoke.json",
                    help="plan-cache path for --tune")
    ap.add_argument("--mesh", default=None,
                    help="AxB (or AxBxC) device mesh: adds sharded "
                         "fused-loop steps/sec rows to the --smoke "
                         "artifact (CPU devices simulated automatically)")
    args = ap.parse_args()
    # reuse the shape parsed at import time (it sized the simulated device
    # count) rather than re-parsing args.mesh — one parser, no drift
    mesh_shape = _MESH_SHAPE
    want = (tuple(int(v) for v in args.mesh.split("x"))
            if args.mesh else None)
    if want != mesh_shape:
        ap.error(f"--mesh mismatch: argparse saw {want}, the import-time "
                 f"scanner saw {mesh_shape}")
    if mesh_shape and (args.tune or args.serve or not args.smoke):
        ap.error("--mesh only applies to --smoke (the XLA device-count "
                 "override would silently skew --tune / --serve / "
                 "full-sweep timings)")

    emit("bench/header", 0.0, "name,us_per_call,derived")
    if args.tune:
        run_tune(args.out or "BENCH_tune_smoke.json", args.plan_cache)
        return
    if args.serve:
        run_serve(args.out or "BENCH_serve_smoke.json", args.serve_baseline)
        return
    if args.smoke:
        run_smoke(args.out or "BENCH_smoke.json", mesh_shape=mesh_shape,
                  baseline_path=args.smoke_baseline)
        return
    fig4_throughput.run(emit)
    fig5_6_energy.run(emit)
    tab1_2_resources.run(emit)
    if glob.glob("experiments/dryrun/*.json"):
        lm_roofline_summary(emit)


if __name__ == "__main__":
    main()
