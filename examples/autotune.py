"""Auto-tuning walkthrough: let the tooling pick the dataflow plan.

    PYTHONPATH=src python examples/autotune.py [--backend pallas]

The paper's point is that the transformation space is searched by the
*compiler*, not the programmer.  This example closes that loop end to end:

1. first ``compile_program(..., strategy="tuned")`` call — cache miss: the
   tuner prunes candidates with the VMEM + roofline models, measures the
   survivors on-device (single-step and fused ``steps=N``), and persists
   the winner in a JSON plan cache;
2. second call — pure cache hit: the stored plan compiles immediately,
   zero timed runs;
3. the tuned executable is checked against the ``auto_plan`` heuristic for
   both numerics and steps/sec.
"""

import argparse
import tempfile
import time

import jax
import numpy as np

from repro.apps import pw_advection, pw_advection_update
from repro.core import CompileOptions, PlanCache, TuneConfig, compile_program
from repro.compile_cache import enable_compile_cache

enable_compile_cache()

ap = argparse.ArgumentParser(description=__doc__)
ap.add_argument("--backend", default="jnp_fused",
                choices=("jnp_naive", "jnp_fused", "pallas"))
ap.add_argument("--steps", type=int, default=5)
args = ap.parse_args()

p = pw_advection()
grid = (32, 32, 128)
update = pw_advection_update(0.1)
rng = np.random.default_rng(0)
fields = {f: rng.normal(size=grid).astype(np.float32) * 0.1
          for f in p.input_fields()}
scalars = {s: np.float32(0.05) for s in p.scalars}
coeffs = {c: np.linspace(0.9, 1.1, grid[ax]).astype(np.float32)
          for c, ax in p.coeffs.items()}

_tmpdir = tempfile.TemporaryDirectory(prefix="stencil_hmls_")
cache = PlanCache(path=f"{_tmpdir.name}/plan_cache.json")
cfg = TuneConfig(steps=args.steps, repeats=2, max_measured=4)

# one frozen CompileOptions is shared verbatim between both tuned compiles
# (the canonical API; loose kwargs still work and normalise to the same)
opts = CompileOptions(backend=args.backend, strategy="tuned",
                      steps=args.steps, update=update,
                      tune_config=cfg, plan_cache=cache)

# -- 1. cache miss: the tuner searches the plan space by measurement --------
t0 = time.perf_counter()
ex_tuned = compile_program(p, grid, options=opts)
print(f"tuned (cache miss, measured search): {time.perf_counter()-t0:.2f}s")
print("  winning plan:", ex_tuned.plan.describe())

# -- 2. cache hit: zero timed runs ------------------------------------------
t0 = time.perf_counter()
compile_program(p, grid, options=opts)
print(f"tuned (cache hit): {time.perf_counter()-t0:.2f}s  -> {cache.path}")

# -- 3. tuned vs heuristic: same numbers, at least the same speed -----------
ex_auto = compile_program(p, grid, backend=args.backend,
                          steps=args.steps, update=update)
out_t = ex_tuned(fields, scalars, coeffs)
out_a = ex_auto(fields, scalars, coeffs)
for k in out_a:
    np.testing.assert_allclose(np.asarray(out_t[k]), np.asarray(out_a[k]),
                               atol=1e-5, rtol=1e-5)
print("tuned matches auto_plan numerics")

for name, ex in (("auto_plan", ex_auto), ("tuned", ex_tuned)):
    jax.block_until_ready(ex(fields, scalars, coeffs)["u"])   # warm
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        jax.block_until_ready(ex(fields, scalars, coeffs)["u"])
        best = min(best, time.perf_counter() - t0)
    print(f"  {name:10s} {args.steps / best:8.2f} steps/s")
print("autotune OK")
