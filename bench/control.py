#!/usr/bin/env python3
"""Readings that a cell's limits are set from, in one process.

    python bench/control.py --workload pw.134m.loop --seconds 1 \\
        --seeds 11 12 13 ... --control-seeds 21 22 23

For each of ``--seeds`` it runs the cell as ``run.py`` does, with a short
window, and prints the numbers its check compares: the program's readings
(the lower ones). For each of ``--control-seeds`` it runs the cell again
with the plain reference, computed in bfloat16, in the program's place:
the control, whose readings (the upper ones) have to fail the cell's
limits. The last line is a JSON object with each number's largest program
reading and smallest control reading. The benchmark's own runs never run
the control. ``--rehearse`` runs all of it on the CPU at the rehearsal
size.
"""

import argparse
import concurrent.futures as cf
import json
import sys
import types

import run  # noqa: E402  (puts bench/ and src/ on the path first)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

import common  # noqa: E402
import drive_loop  # noqa: E402
import drive_serve  # noqa: E402

LOWER = jnp.bfloat16


def _lower(cell: common.Cell, steps: int):
    """The reference in the precision below the configuration's: f32 in,
    bfloat16 throughout, f32 out."""
    cfg = cell.config

    @jax.jit
    def answer(state, scalars, coeffs):
        out = cell.reference.run(state, scalars, coeffs, steps,
                                 cfg["update_args"], dtype=LOWER)
        return {f: x.astype(jnp.float32) for f, x in out.items()}
    return answer


class LowerEngine:
    """Stands in for ``StencilEngine``: answers each request with the
    lower-precision reference, one at a time on a worker thread."""

    max_batch = 8

    def __init__(self, cell):
        self.cell = cell
        self.answers = {}
        self.pool = cf.ThreadPoolExecutor(max_workers=1)

    def submit(self, req):
        if req.steps not in self.answers:
            self.answers[req.steps] = _lower(self.cell, req.steps)
        answer = self.answers[req.steps]

        def serve():
            out = answer(req.fields, req.scalars, req.coeffs)
            return types.SimpleNamespace(
                outputs=jax.tree.map(jax.device_get, out), batch_size=1)
        return self.pool.submit(serve)

    def close(self):
        self.pool.shutdown()


def put_lower_in_place(cell: common.Cell):
    """Make the drivers' program entry points answer with the control."""
    def compile_program(program, grid, *, steps, update, **opts):
        del program, grid, update, opts
        return _lower(cell, steps)
    drive_loop.compile_program = compile_program
    drive_serve.StencilEngine = lambda: LowerEngine(cell)


def readings(cell, devices, seeds, seconds, rehearse, label) -> list:
    out = []
    for seed in seeds:
        args = types.SimpleNamespace(seed=seed, seconds=seconds, trace=0,
                                     rehearse=rehearse)
        r = run.run_cell(cell, devices, args)
        print(f"{label} seed={seed} " + " ".join(
            f"{k}={v!r}" for k, v in r.readings.items()), flush=True)
        out.append(r.readings)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=1.0)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, nargs="+", required=True)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    cell = common.load_cell(args.workload)
    devices = run.find_devices(cell, args.rehearse)
    if devices is None:
        return 2
    run.use_compile_cache()
    program = readings(cell, devices, args.seeds, args.seconds,
                       args.rehearse, "program")
    put_lower_in_place(cell)
    control = readings(cell, devices, args.control_seeds, args.seconds,
                       args.rehearse, "control")
    keys = sorted({k for r in program + control for k in r})
    print(json.dumps({
        "workload": cell.name, "limits": cell.limits,
        "lower": {k: max(r[k] for r in program) for k in keys},
        "upper": {k: min(r[k] for r in control) for k in keys}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
