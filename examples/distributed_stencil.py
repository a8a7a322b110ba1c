"""Distributed stencil run through the unified compile pipeline.

``compile_program`` is the single entry point for local AND SPMD
execution: pass ``mesh=``/``mesh_axes=`` to domain-decompose the grid over
a device mesh, add ``steps=N`` to fuse the whole time loop into one
dispatch with the halo exchange *inside* the loop carry (ppermute-refresh-
then-compute, no host round trips), and ``boundary="periodic"`` to run the
same program on a torus.

    PYTHONPATH=src python examples/distributed_stencil.py

(Sets the XLA host-device override itself; run as a standalone script.
The old ``make_sharded_executor`` entry point is deprecated — it now
forwards here.)
"""

import os
import time

if __name__ == "__main__" and "XLA_FLAGS" not in os.environ:
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import jax                                              # noqa: E402
import numpy as np                                      # noqa: E402

from repro.apps import pw_advection, pw_advection_update  # noqa: E402
from repro.core import (compile_program, make_auto_mesh,  # noqa: E402
                        run_time_loop)
from repro.compile_cache import enable_compile_cache  # noqa: E402


def main():
    enable_compile_cache()
    mesh = make_auto_mesh((2, 2, 2), ("X", "Y", "Z"))
    grid = (64, 64, 128)
    steps = 8
    rng = np.random.default_rng(0)
    # modest amplitudes: the PW scheme is quadratic, and forward Euler on
    # O(1) winds amplifies rounding noise across steps
    fields = {f: rng.normal(size=grid).astype(np.float32) * 0.1
              for f in ("u", "v", "w")}
    scalars = {"tcx": np.float32(0.05), "tcy": np.float32(0.05)}
    coeffs = {c: np.linspace(0.9, 1.1, grid[2]).astype(np.float32)
              for c in ("tzc1", "tzc2", "tzd1", "tzd2")}
    update = pw_advection_update(0.05)

    # --- one sharded step: same API as a local compile, plus mesh= -------
    p = pw_advection()
    dist = compile_program(p, grid, backend="pallas", mesh=mesh,
                           mesh_axes=("X", "Y", "Z"))
    print(f"{dist.shard.describe()}\nplan {dist.plan.describe()}")
    out = dist(fields, scalars, coeffs)
    ref = compile_program(p, grid, backend="jnp_naive")(fields, scalars,
                                                        coeffs)
    for k in ref:
        err = float(np.abs(np.asarray(out[k]) - np.asarray(ref[k])).max())
        print(f"single-step {k}: sharded-vs-local max err = {err:.2e}")
        assert err < 1e-4

    # --- the fused distributed time loop: N steps, ONE dispatch ----------
    for boundary in ("zero", "periodic"):
        pb = pw_advection(boundary=boundary)
        exN = compile_program(pb, grid, backend="jnp_fused", mesh=mesh,
                              mesh_axes=("X", "Y", "Z"), steps=steps,
                              update=update)
        jax.block_until_ready(exN(fields, scalars, coeffs)["u"])  # warm
        t0 = time.perf_counter()
        got = exN(fields, scalars, coeffs)
        jax.block_until_ready(got["u"])
        dt = time.perf_counter() - t0
        want = run_time_loop(compile_program(pb, grid, backend="jnp_fused"),
                             dict(fields), scalars, coeffs, steps, update)
        err = max(float(np.abs(np.asarray(got[k])
                               - np.asarray(want[k])).max()) for k in want)
        print(f"fused loop ({boundary}): {steps} distributed steps in one "
              f"dispatch, {steps / dt:.1f} steps/s, max err = {err:.2e}")
        assert err < 1e-4
    print("distributed_stencil OK")


if __name__ == "__main__":
    main()
