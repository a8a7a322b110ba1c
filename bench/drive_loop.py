"""Traffic of kind ``loop``: a long run of the fused time loop.

The cell's program is compiled once through ``compile_program`` with what
a user of the deployment states (grid, boundary, ``steps``, update rule,
and the mesh where the traffic names one); every other option keeps its
default. The window then calls the returned executor back to back, each
dispatch taking the state the previous one returned and ending at
``block_until_ready``; it closes on the first dispatch boundary after
``--seconds``. One dispatch of the window, drawn from the seed, is
compared with the plain reference run from the same state.
"""

from __future__ import annotations

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

import common
import tracing
from repro import apps
from repro.core import compile_program


def run(r) -> None:
    cell, cfg, traffic = r.cell, r.cell.config, r.cell.traffic
    grid = r.grid(cfg["grid"])
    steps = r.steps(traffic["steps"])
    program = getattr(apps, cfg["program"])(boundary=cfg["boundary"])
    update = getattr(apps, cfg["update"])(*cfg["update_args"])
    opts, sharding = {}, None
    if "mesh" in traffic:
        mesh_spec = traffic["mesh"]
        mesh = Mesh(np.array(r.devices).reshape(mesh_spec["shape"]),
                    tuple(mesh_spec["axes"]))
        grid_axes = tuple(mesh_spec["grid_axes"])
        opts = {"mesh": mesh, "mesh_axes": grid_axes}
        sharding = NamedSharding(mesh, P(*grid_axes))

    with r.phase("data"):
        state = common.make_fields(cfg, grid, r.seed, sharding=sharding)
        scalars = common.make_scalars(cfg)
        coeffs = common.make_coeffs(cfg, grid)
        jax.block_until_ready((state, scalars, coeffs))
    with r.phase("warmup"):         # compiles, or reads the cache
        ex = compile_program(program, grid, steps=steps, update=update,
                             **opts)
        state = jax.block_until_ready(ex(state, scalars, coeffs))
    r.setup_done()

    # one dispatch of the window, uniform over all of them and drawn from
    # the seed (a reservoir of one), keeps its start and its answer
    rng = np.random.default_rng(r.seed)
    kept, n = None, 0
    with r.window() as w:
        while True:
            with tracing.span("dispatch"):
                nxt = ex(state, scalars, coeffs)
            with tracing.span("wait"):
                nxt = jax.block_until_ready(nxt)
            if rng.integers(n + 1) == 0:
                kept = (state, nxt)
            state, n = nxt, n + 1
            if w.elapsed() >= r.seconds:
                w.close()
                break
    r.read_memory()
    del state, nxt, ex

    point_steps = n * steps * int(np.prod(grid))
    r.counters.update(dispatches=n, steps=n * steps, point_steps=point_steps)
    r.attempted, r.failed = n, 0
    r.metrics["gpts_per_s"] = point_steps / w.seconds / 1e9

    start, got = (jax.device_put(x, r.devices[0]) for x in kept)
    del kept
    with r.phase("reference"):
        ref = jax.jit(lambda s, sc, co: cell.reference.run(
            s, sc, co, steps, cfg["update_args"]))(start, scalars, coeffs)
        r.readings = common.compare(cfg, start, got, ref)
