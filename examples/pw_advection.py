"""PW advection solver (paper benchmark 1): a real time-stepping run.

    PYTHONPATH=src python examples/pw_advection.py --size 8M --steps 5
    PYTHONPATH=src python examples/pw_advection.py --fused-loop --steps 20

Integrates the MONC Piacsek-Williams advection source terms over several
steps (forward Euler on the wind fields), using the generated Pallas
dataflow kernels, and reports MPt/s per application.  ``--fused-loop``
compiles the whole time loop into one on-device program (the paper's
device-resident inter-iteration dataflow) and reports steps/sec for both
execution modes.
"""

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.apps import pw_advection, pw_advection_update
from repro.core import compile_program, run_time_loop
from repro.compile_cache import enable_compile_cache

SIZES = {"1M": (128, 64, 128), "8M": (256, 256, 128), "32M": (512, 256, 256)}


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--size", default="1M", choices=list(SIZES))
    ap.add_argument("--steps", type=int, default=5)
    ap.add_argument("--backend", default="pallas",
                    choices=["pallas", "jnp_fused", "jnp_naive"])
    ap.add_argument("--fused-loop", action="store_true",
                    help="compile the whole time loop on device and compare "
                         "steps/sec against the host-driven loop")
    args = ap.parse_args()

    grid = SIZES[args.size]
    p = pw_advection()
    ex = compile_program(p, grid, backend=args.backend)
    print("plan:", ex.plan.describe())

    rng = np.random.default_rng(0)
    fields = {f: jnp.asarray(rng.normal(size=grid).astype(np.float32) * 0.1)
              for f in ("u", "v", "w")}
    scalars = {"tcx": jnp.float32(0.05), "tcy": jnp.float32(0.05)}
    coeffs = {c: jnp.asarray(np.linspace(0.9, 1.1, grid[2]).astype(np.float32))
              for c in ("tzc1", "tzc2", "tzd1", "tzd2")}
    dt = 0.1
    pts = float(np.prod(grid))
    update = pw_advection_update(dt)

    if args.fused_loop:
        exN = compile_program(p, grid, backend=args.backend,
                              steps=args.steps, update=update)
        print("time loop:", exN.time_spec.describe())
        for label, fn in (
                ("host loop ", lambda: run_time_loop(
                    ex, dict(fields), scalars, coeffs, args.steps, update)),
                ("fused loop", lambda: exN(fields, scalars, coeffs))):
            jax.block_until_ready(fn()["u"])    # warm-up (compile)
            t0 = time.perf_counter()
            out = fn()
            jax.block_until_ready(out["u"])
            el = time.perf_counter() - t0
            print(f"{label}: {args.steps} steps in {el*1e3:8.1f} ms  "
                  f"{args.steps/el:8.2f} steps/s  "
                  f"{pts*args.steps/el/1e6:8.2f} MPt/s")
            assert bool(jnp.isfinite(out["u"]).all())
        print("pw_advection fused-loop OK")
        return

    for step in range(args.steps):
        t0 = time.perf_counter()
        src = ex(fields, scalars, coeffs)
        fields = update(fields, src)
        jax.block_until_ready(fields["u"])
        el = time.perf_counter() - t0
        umax = float(jnp.abs(fields["u"]).max())
        print(f"step {step}: {el*1e3:8.1f} ms  {pts/el/1e6:8.2f} MPt/s  "
              f"|u|max={umax:.4f}")
    assert np.isfinite(umax)
    print("pw_advection OK")


if __name__ == "__main__":
    main()
