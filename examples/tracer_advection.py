"""NEMO tracer advection (paper benchmark 2): 24 stencil ops / 6 fields.

    PYTHONPATH=src python examples/tracer_advection.py --size 8M --steps 3
    PYTHONPATH=src python examples/tracer_advection.py --fused-loop

Demonstrates the dependency-chain handling (producer->consumer temps inside
one fused dataflow kernel with overlapped-tiling recompute) on the paper's
harder benchmark, and compares the three stage-split strategies.
``--fused-loop`` additionally compiles the whole tracer time loop into one
on-device program and reports steps/sec for both execution modes.
"""

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.apps import tracer_advection, tracer_advection_update
from repro.core import compile_program, run_time_loop
from repro.compile_cache import enable_compile_cache

SIZES = {"1M": (128, 64, 128), "8M": (256, 256, 128), "33M": (512, 256, 256)}


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--size", default="1M", choices=list(SIZES))
    ap.add_argument("--steps", type=int, default=3)
    ap.add_argument("--fused-loop", action="store_true",
                    help="compile the whole time loop on device and compare "
                         "steps/sec against the host-driven loop")
    args = ap.parse_args()

    grid = SIZES[args.size]
    p = tracer_advection()
    rng = np.random.default_rng(1)
    fields = {
        "t": jnp.asarray(rng.normal(size=grid).astype(np.float32) + 15.0),
        "un": jnp.asarray(rng.normal(size=grid).astype(np.float32) * 0.2),
        "vn": jnp.asarray(rng.normal(size=grid).astype(np.float32) * 0.2),
        "wn": jnp.asarray(rng.normal(size=grid).astype(np.float32) * 0.05),
        "e3t": jnp.asarray(np.abs(rng.normal(size=grid)).astype(np.float32) + 1.0),
        "msk": jnp.asarray((rng.uniform(size=grid) > 0.05).astype(np.float32)),
    }
    scalars = {"rdt": jnp.float32(0.05), "zeps": jnp.float32(1e-6)}
    coeffs = {"ztfreez": jnp.asarray(np.full(grid[2], -1.8, np.float32))}
    pts = float(np.prod(grid))

    if not args.fused_loop:
        for strategy in ("fused", "per_field", "auto"):
            ex = compile_program(p, grid, backend="jnp_fused"
                                 if strategy == "auto" else "pallas",
                                 strategy=strategy)
            t0 = time.perf_counter()
            out = ex(fields, scalars, coeffs)
            jax.block_until_ready(out["ta"])
            el = time.perf_counter() - t0
            print(f"strategy={strategy:9s} groups="
                  f"{len(ex.plan.groups):2d} first-call {el:6.2f}s")

    if args.fused_loop:
        update = tracer_advection_update()
        ex = compile_program(p, grid, backend="jnp_fused")
        exN = compile_program(p, grid, backend="jnp_fused",
                              steps=args.steps, update=update)
        print("time loop:", exN.time_spec.describe())
        for label, fn in (
                ("host loop ", lambda: run_time_loop(
                    ex, dict(fields), scalars, coeffs, args.steps, update)),
                ("fused loop", lambda: exN(fields, scalars, coeffs))):
            jax.block_until_ready(fn()["t"])    # warm-up (compile)
            t0 = time.perf_counter()
            out = fn()
            jax.block_until_ready(out["t"])
            el = time.perf_counter() - t0
            print(f"{label}: {args.steps} steps in {el*1e3:8.1f} ms  "
                  f"{args.steps/el:8.2f} steps/s  "
                  f"{pts*args.steps/el/1e6:8.2f} MPt/s")
            assert bool(jnp.isfinite(out["t"]).all())
        print("tracer_advection fused-loop OK")
        return

    ex = compile_program(p, grid, backend="jnp_fused")
    tr = fields["t"]
    for step in range(args.steps):
        t0 = time.perf_counter()
        out = ex(dict(fields, t=tr), scalars, coeffs)
        tr = out["ta"]
        jax.block_until_ready(tr)
        el = time.perf_counter() - t0
        print(f"step {step}: {el*1e3:8.1f} ms  {pts/el/1e6:8.2f} MPt/s  "
              f"t-mean={float(tr.mean()):.4f}")
    assert bool(jnp.isfinite(tr).all())
    print("tracer_advection OK")


if __name__ == "__main__":
    main()
