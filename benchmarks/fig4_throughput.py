"""Paper Fig. 4: stencil throughput (MPt/s) across 'frameworks'.

Framework role mapping (DESIGN.md §7):
    jnp_naive  -> unoptimised Vitis HLS / -O0 (no reuse structure)
    jnp_fused  -> DaCe (optimising, not stencil-specialised)
    pallas     -> Stencil-HMLS (this work): generated dataflow kernels

Two number sets, clearly labelled:
  * measured — wall-clock on this CPU container (jnp backends; the pallas
    interpreter is a correctness tool, not a performance proxy)
  * modeled  — TPU v5e roofline MPt/s per backend from the streaming model
    (analysis.stencil_roofline), the apples-to-apples Fig.4 analogue
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis.stencil_roofline import model_program
from repro.apps import pw_advection, pw_advection_update, tracer_advection
from repro.core import compile_program, run_time_loop
from repro.compile_cache import enable_compile_cache

# paper sizes: 8M / 32M points (134M is modeled only on this container)
SIZES = {
    "8M": (256, 256, 128),
    "32M": (512, 256, 256),
}
MODEL_ONLY_SIZES = {"134M": (1024, 512, 256)}

# fused-vs-host time-loop comparison (the PR's steps/sec headline number)
FUSED_GRID = (64, 64, 128)
FUSED_STEPS = 10


def _data(p, grid, seed=0):
    rng = np.random.default_rng(seed)
    fields = {f: jnp.asarray(rng.normal(size=grid).astype(np.float32))
              for f in p.input_fields()}
    if "e3t" in fields:
        fields["e3t"] = jnp.abs(fields["e3t"]) + 1.0
    if "msk" in fields:
        fields["msk"] = (fields["msk"] > 0).astype(jnp.float32)
    scalars = {s: jnp.float32(0.1) for s in p.scalars}
    coeffs = {c: jnp.asarray(rng.normal(size=(grid[ax],)).astype(np.float32))
              for c, ax in p.coeffs.items()}
    return fields, scalars, coeffs


def _time(fn, *args, iters=3):
    fn(*args)  # compile + warm
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters


def run(emit):
    for prog_fn in (pw_advection, tracer_advection):
        p = prog_fn()
        model = model_program(p)
        for size, grid in SIZES.items():
            pts = float(np.prod(grid))
            fields, scalars, coeffs = _data(p, grid)
            for backend in ("jnp_naive", "jnp_fused"):
                ex = compile_program(p, grid, backend=backend)
                dt = _time(ex, fields, scalars, coeffs)
                emit(f"fig4/{p.name}/{size}/{backend}/measured_cpu",
                     dt * 1e6, f"{pts / dt / 1e6:.1f} MPt/s")
            for backend in ("jnp_naive", "jnp_fused", "pallas"):
                mp = model.mpts(backend)
                emit(f"fig4/{p.name}/{size}/{backend}/modeled_v5e",
                     pts / (mp * 1e6) * 1e6, f"{mp:.1f} MPt/s")
        for size, grid in MODEL_ONLY_SIZES.items():
            pts = float(np.prod(grid))
            for backend in ("jnp_naive", "jnp_fused", "pallas"):
                mp = model.mpts(backend)
                emit(f"fig4/{p.name}/{size}/{backend}/modeled_v5e",
                     pts / (mp * 1e6) * 1e6, f"{mp:.1f} MPt/s")
        # the paper's headline ratio: ours vs next-best automated tool
        ratio = model.mpts("pallas") / model.mpts("jnp_fused")
        emit(f"fig4/{p.name}/speedup_vs_next_best", 0.0,
             f"{ratio:.1f}x modeled (paper: 14-100x vs DaCe)")


def run_fused_loop(emit, grid=FUSED_GRID, steps=FUSED_STEPS,
                   backends=("jnp_naive", "jnp_fused")):
    """Fused on-device time loop vs host-driven loop, steps/sec both ways.

    The fused path lowers all ``steps`` iterations into one jitted program
    (single dispatch, carry-resident pre-padded fields); the host path is N
    dispatches with a fresh ``jnp.pad`` round per step — the round trip the
    paper's device-resident dataflow eliminates.
    """
    p = pw_advection()
    fields, scalars, coeffs = _data(p, grid)
    update = pw_advection_update(0.1)
    pts = float(np.prod(grid))
    tag = "x".join(str(g) for g in grid)
    for backend in backends:
        ex = compile_program(p, grid, backend=backend)
        exN = compile_program(p, grid, backend=backend, steps=steps,
                              update=update)
        modes = (
            ("host_loop", lambda: run_time_loop(ex, dict(fields), scalars,
                                                coeffs, steps, update)),
            ("fused_loop", lambda: exN(fields, scalars, coeffs)),
        )
        sps = {}
        for mode, fn in modes:
            jax.block_until_ready(fn()["u"])        # compile + warm
            dt = float("inf")
            for _ in range(3):                      # best-of-3 (CPU noise)
                t0 = time.perf_counter()
                out = fn()
                jax.block_until_ready(out["u"])
                dt = min(dt, time.perf_counter() - t0)
            sps[mode] = steps / dt
            emit(f"fig4/pw_advection/fused/{tag}/{backend}/{mode}",
                 dt * 1e6, f"{steps / dt:.2f} steps/s "
                           f"{pts * steps / dt / 1e6:.1f} MPt/s")
        emit(f"fig4/pw_advection/fused/{tag}/{backend}/speedup", 0.0,
             f"{sps['fused_loop'] / sps['host_loop']:.2f}x fused vs host")


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--fused-loop", action="store_true",
                    help="run only the fused-vs-host time-loop comparison")
    ap.add_argument("--steps", type=int, default=FUSED_STEPS)
    ap.add_argument("--grid", default="x".join(map(str, FUSED_GRID)),
                    help="AxBxC grid for --fused-loop")
    ap.add_argument("--backends", default="jnp_naive,jnp_fused",
                    help="comma list; add pallas for the interpret-mode "
                         "kernels (slow on CPU)")
    args = ap.parse_args()

    def emit(name, us, derived=""):
        print(f"{name},{us:.2f},{derived}", flush=True)

    if args.fused_loop:
        grid = tuple(int(g) for g in args.grid.split("x"))
        if len(grid) != 3:
            ap.error(f"--grid must be AxBxC (3-D), got {args.grid!r}")
        run_fused_loop(emit, grid=grid, steps=args.steps,
                       backends=tuple(args.backends.split(",")))
    else:
        run(emit)


if __name__ == "__main__":
    main()
