"""Quickstart: write a stencil, let the compiler structure it for TPU.

    PYTHONPATH=src python examples/quickstart.py

Builds a 3-D 7-point diffusion stencil through the frontend (the PSyclone
role), lowers it through all three backends, checks they agree, and prints
the plan the auto-scheduler derived (the HLS-dialect analogue).
"""

import numpy as np

from repro.core import ProgramBuilder, compile_program
from repro.core.schedule import auto_plan, vmem_cost
from repro.compile_cache import enable_compile_cache

enable_compile_cache()

# -- 1. write the maths (stencil dialect analogue) --------------------------
b = ProgramBuilder("diffusion", ndim=3)
u = b.input("u")
alpha = b.scalar("alpha")
out = b.output("u_next")
b.define(out, u[0, 0, 0] + alpha * (
    u[1, 0, 0] + u[-1, 0, 0] + u[0, 1, 0] + u[0, -1, 0]
    + u[0, 0, 1] + u[0, 0, -1] - 6.0 * u[0, 0, 0]))
prog = b.build()
print(prog.to_text())

# -- 2. auto-plan (HLS dialect analogue) ------------------------------------
grid = (64, 64, 256)
plan = auto_plan(prog, grid)
print("\nplan:", plan.describe())
print(f"VMEM claim: {vmem_cost(prog, plan, grid)/2**20:.2f} MiB")

# -- 3. run all three backends ----------------------------------------------
rng = np.random.default_rng(0)
fields = {"u": rng.normal(size=grid).astype(np.float32)}
scalars = {"alpha": np.float32(0.1)}

results = {}
for backend in ("jnp_naive", "jnp_fused", "pallas"):
    ex = compile_program(prog, grid, backend=backend)
    results[backend] = np.asarray(ex(fields, scalars)["u_next"])

for k in ("jnp_fused", "pallas"):
    ok = np.allclose(results["jnp_naive"], results[k], atol=1e-5)
    print(f"{k} matches oracle: {ok}")
print("quickstart OK")
