#!/usr/bin/env python3
"""Smoke run of the stencil compiler on a TPU: the paper's two programs
through the normal entry points, each checked against the jnp reference on
the same device and grid.

    python chip_smoke.py               # one chip, the paper's grid sizes
    python chip_smoke.py --chips 4     # only the 2x2-mesh phase, four chips
    JAX_PLATFORMS=cpu python chip_smoke.py --grid 16x16x16   # CPU rehearsal

Phases, in order, in this one process:

* ``device``: platform, device kind, device count and jax version;
* ``default``: ``compile_program`` with default options (the block
  schedule) and with ``schedule="stream"``, fused loop of 10 steps:
  ``pw_advection`` at 512x256x256, ``tracer_advection`` at 256x256x128;
* ``chain``: stream ``pw_advection`` at 256x256x128 with ``time_tile=4,
  plane_tile=4``, fused 8 steps;
* ``serve``: ``StencilEngine(backend="pallas", schedule="stream")``
  answers four ``pw_advection`` requests at 256x256x128 (3 steps each),
  submitted together so they batch; it reports whether the batched (vmap)
  or the unrolled dispatch served them.  In place of compile seconds it
  prints the cold round (executor build, compile, first transfers and the
  run) less the warm round.

``--chips 4`` runs only the ``mesh`` phase: stream ``pw_advection``,
fused 10 steps, global grid 512x256x256 split 2x2 over the leading axes
(one 256x128x256 block per chip), against ``jnp_fused`` on device 0.

Each compute phase prints its compile seconds, a steps/s *smoke timing*
(one timed call after a warm-up call; not a benchmark), the max abs error
relative to ``max|ref|`` against ``backend="jnp_fused"``, and whether the
compiled program calls the generated kernels (``tpu_custom_call``).  A
phase fails on a relative error above 1e-4, a non-finite value, a missing
``tpu_custom_call`` or any exception.  The last line of standard output is
``{"ok": true, "device": {...}}``, printed only when every phase passed on
a TPU; otherwise the script exits non-zero.  ``--grid AxBxC`` replaces
every phase's grid, for a quick rehearsal on the CPU (where the kernels
run in the Pallas interpreter and the script still exits non-zero).
"""

from __future__ import annotations

import argparse
import json
import sys
import time
import traceback
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P  # noqa: E402

from repro.apps import (pw_advection, pw_advection_update,  # noqa: E402
                        tracer_advection, tracer_advection_update)
from repro.compile_cache import enable_compile_cache  # noqa: E402
from repro.core import compile_program  # noqa: E402
from repro.serve import StencilEngine, StencilRequest  # noqa: E402

TOL = 1e-4
STEPS = 10
GRID_32M = (512, 256, 256)
GRID_8M = (256, 256, 128)
KERNEL = "tpu_custom_call"

# initial fields, from standard normals, in the ranges the examples use
FIELD_INIT = {
    "pw_advection": {f: (lambda x: 0.1 * x) for f in ("u", "v", "w")},
    "tracer_advection": {
        "t": lambda x: x + 15.0,
        "un": lambda x: 0.2 * x,
        "vn": lambda x: 0.2 * x,
        "wn": lambda x: 0.05 * x,
        "e3t": lambda x: jnp.abs(x) + 1.0,
        "msk": lambda x: (x > -1.645).astype(jnp.float32),   # ~95% wet
    },
}
SCALARS = {"pw_advection": {"tcx": 0.05, "tcy": 0.05},
           "tracer_advection": {"rdt": 0.05, "zeps": 1e-6}}
COEFF_INIT = {"pw_advection": lambda n: jnp.linspace(0.9, 1.1, n),
              "tracer_advection": lambda n: jnp.full((n,), -1.8)}
UPDATES = {"pw_advection": lambda: pw_advection_update(0.1),
           "tracer_advection": tracer_advection_update}


def make_data(p, grid, seed):
    """Fields, scalars and coefficients for ``p`` on ``grid``, made on the
    default device from ``seed``."""
    keys = jax.random.split(jax.random.key(seed), len(p.input_fields()))
    fields = {f: FIELD_INIT[p.name][f](
                  jax.random.normal(k, grid, jnp.float32))
              for k, f in zip(keys, p.input_fields())}
    scalars = {s: jnp.float32(v) for s, v in SCALARS[p.name].items()}
    coeffs = {c: COEFF_INIT[p.name](grid[ax]).astype(jnp.float32)
              for c, ax in p.coeffs.items()}
    return fields, scalars, coeffs


def rel_error(out: dict, ref: dict) -> float:
    """max |out - ref| over max |ref|, across every returned field."""
    num = max(float(jnp.max(jnp.abs(jnp.asarray(out[f]) - ref[f])))
              for f in ref)
    den = max(float(jnp.max(jnp.abs(ref[f]))) for f in ref)
    return num / den


def all_finite(out: dict) -> bool:
    return all(bool(jnp.isfinite(jnp.asarray(v)).all()) for v in out.values())


def verdict(name: str, rec: dict) -> bool:
    """Print the phase line; True when the phase passed."""
    ok = (rec["rel_err"] <= TOL and rec["finite"] and rec[KERNEL]
          and rec.get("collective_permute", True))
    fields = " ".join(f"{k}={v}" for k, v in rec.items())
    print(f"phase {name}: {fields} -> {'PASS' if ok else 'FAIL'}",
          flush=True)
    return ok


def fused_phase(name, p, grid, steps=STEPS, seed=0, mesh=None,
                mesh_axes=None, **opts):
    """Compile ``p``'s fused loop of ``steps`` steps with ``opts``, run it,
    compare it with the ``jnp_fused`` loop on the default device."""
    update = UPDATES[p.name]()
    fields, scalars, coeffs = make_data(p, grid, seed)
    args = fields
    if mesh is not None:
        sharding = NamedSharding(mesh, P(*mesh_axes))
        args = {f: jax.device_put(x, sharding) for f, x in fields.items()}
    t0 = time.perf_counter()
    ex = compile_program(p, grid, steps=steps, update=update, mesh=mesh,
                         mesh_axes=mesh_axes, **opts)
    compiled = ex.lower(args, scalars, coeffs).compile()
    compile_s = time.perf_counter() - t0
    hlo = compiled.as_text()
    jax.block_until_ready(compiled(args, scalars, coeffs))      # warm-up
    t1 = time.perf_counter()
    out = jax.block_until_ready(compiled(args, scalars, coeffs))
    run_s = time.perf_counter() - t1
    rec = {"grid": "x".join(map(str, grid)), "steps": steps,
           "plan": ex.plan.describe(), "compile_s": compile_s,
           "steps_per_s_smoke_timing": steps / run_s}
    if mesh is not None:
        devices = {d for v in out.values() for d in v.sharding.device_set}
        rec["devices_holding_output"] = len(devices)
        rec["shard_shapes"] = sorted({s.data.shape for v in out.values()
                                      for s in v.addressable_shards})
        rec["collective_permute"] = "collective-permute" in hlo
        if len(devices) != mesh.devices.size:
            raise RuntimeError(f"output spans {len(devices)} devices, not "
                               f"the mesh's {mesh.devices.size}")
        out = {f: jax.device_put(v, jax.devices()[0]) for f, v in out.items()}
    ref = compile_program(p, grid, backend="jnp_fused", steps=steps,
                          update=update)(fields, scalars, coeffs)
    rec.update(rel_err=rel_error(out, ref), finite=all_finite(out))
    rec[KERNEL] = KERNEL in hlo
    return verdict(name, rec)


def serve_phase(name, grid, n_requests=4, steps=3):
    """Serve ``n_requests`` pw_advection requests submitted together, cold
    (executor build and compile) and then warm, through the batched
    stream executor."""
    p = pw_advection()
    update = pw_advection_update(0.1)
    data = [make_data(p, grid, seed=100 + i) for i in range(n_requests)]
    reqs = [StencilRequest(program=p, fields=f, scalars=s, coeffs=c,
                           steps=steps, update=update) for f, s, c in data]
    eng = StencilEngine(backend="pallas", schedule="stream",
                        max_batch=n_requests, window_s=5.0,
                        autostart=False)
    try:
        t0 = time.perf_counter()
        futs = [eng.submit(r) for r in reqs]
        eng.start()
        res = [f.result(timeout=1200) for f in futs]
        cold_s = time.perf_counter() - t0
        t1 = time.perf_counter()
        res = eng.map(reqs, timeout=1200)
        warm_s = time.perf_counter() - t1
        hlo = eng.batch_hlo(reqs)
        stats = eng.stats
        dispatch = ("unrolled" if stats.unrolled_batches
                    else "batched (vmap)")
        batches = stats.batches
    finally:
        eng.close()
    ref_ex = compile_program(p, grid, backend="jnp_fused", steps=steps,
                             update=update)
    err, finite = 0.0, True
    for (f, s, c), r in zip(data, res):
        err = max(err, rel_error(r.outputs, ref_ex(f, s, c)))
        finite = finite and all_finite(r.outputs)
    rec = {"grid": "x".join(map(str, grid)), "steps": steps,
           "requests": n_requests, "dispatch": dispatch,
           "batch_sizes": sorted({r.batch_size for r in res}),
           "batches": batches, "cold_round_s": cold_s,
           "cold_minus_warm_s": cold_s - warm_s,
           "steps_per_s_smoke_timing": n_requests * steps / warm_s,
           "rel_err": err, "finite": finite, KERNEL: KERNEL in hlo}
    return verdict(name, rec)


def run_phase(name, fn, *args, **kwargs) -> bool:
    """Run one phase; an exception fails it (traceback printed) and the
    remaining phases still run."""
    try:
        return fn(name, *args, **kwargs)
    except Exception:
        traceback.print_exc()
        print(f"phase {name}: exception -> FAIL", flush=True)
        return False


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the 2x2-mesh phase")
    ap.add_argument("--grid", default=None,
                    help="AxBxC grid for every phase (CPU rehearsal)")
    args = ap.parse_args()
    enable_compile_cache()

    devs = jax.devices()
    dev = devs[0]
    print(f"device: platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devs)} jax={jax.__version__}", flush=True)
    on_tpu = dev.platform == "tpu"
    if not on_tpu and args.grid is None:
        print("no TPU found: pass --grid for a CPU rehearsal",
              file=sys.stderr)
        return 2
    small = (None if args.grid is None
             else tuple(int(v) for v in args.grid.split("x")))

    if args.chips == 4:
        if len(devs) < 4:
            print(f"--chips 4 needs four devices, found {len(devs)}",
                  file=sys.stderr)
            return 2
        mesh = Mesh(np.array(devs[:4]).reshape(2, 2), ("X", "Y"))
        results = [run_phase("mesh/pw_advection/stream", fused_phase,
                             pw_advection(), small or GRID_32M, mesh=mesh,
                             mesh_axes=("X", "Y", None), schedule="stream")]
    else:
        results = [
            run_phase("default/pw_advection/block", fused_phase,
                      pw_advection(), small or GRID_32M),
            run_phase("default/pw_advection/stream", fused_phase,
                      pw_advection(), small or GRID_32M, schedule="stream"),
            run_phase("default/tracer_advection/block", fused_phase,
                      tracer_advection(), small or GRID_8M),
            run_phase("default/tracer_advection/stream", fused_phase,
                      tracer_advection(), small or GRID_8M,
                      schedule="stream"),
            # 8 steps: two whole chains, no shallower remainder chain to
            # compile
            run_phase("chain/pw_advection/stream_t4_p4", fused_phase,
                      pw_advection(), small or GRID_8M, steps=8,
                      schedule="stream", time_tile=4, plane_tile=4),
            run_phase("serve/pw_advection/stream", serve_phase,
                      small or GRID_8M),
        ]
    if not (on_tpu and all(results)):
        print(f"{results.count(False)} of {len(results)} phases failed"
              + ("" if on_tpu else "; not a TPU"), file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
