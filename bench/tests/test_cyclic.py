"""The east-west cyclic cell, ``tracer.32m.periodic``, rehearsed on the
CPU: its control fails the limits while the program meets them with
``steady_err`` at 0, and the all-zero program fails them against the
cyclic reference, so the cell tests the wrap. ``test_faults.py`` and
``test_control.py`` pick the cell up from ``BENCHMARK.json`` as well."""

import json
import os
import subprocess
import sys

import jax

import common

CELL = "tracer.32m.periodic"


def test_control_fails_and_steady_fields_stay(tmp_path):
    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    proc = subprocess.run(
        [sys.executable, str(common.BENCH / "control.py"), "--rehearse",
         "--workload", CELL, "--seconds", "1",
         "--seeds", str(2**33 + 5), "--control-seeds", str(2**33 + 6)],
        capture_output=True, text=True, timeout=900, env=env)
    assert proc.returncode == 0, proc.stderr[-4000:]
    summary = json.loads(proc.stdout.splitlines()[-1])
    limits = summary["limits"]
    assert summary["lower"]["steady_err"] == 0.0
    assert summary["lower"]["change_err"] <= limits["change_err"]
    assert summary["upper"]["change_err"] > limits["change_err"]


def test_zero_program_fails_the_cyclic_reference():
    from repro import apps
    from repro.core import compile_program

    cell = common.load_cell(CELL)
    cfg = cell.config
    grid, steps = tuple(max(8, g // 16) for g in cfg["grid"]), 3
    start = common.make_fields(cfg, grid, 2**31 + 11)
    scalars, coeffs = common.make_scalars(cfg), common.make_coeffs(cfg, grid)
    ref = jax.jit(lambda s, sc, co: cell.reference.run(
        s, sc, co, steps, cfg["update_args"]))(start, scalars, coeffs)
    update = getattr(apps, cfg["update"])(*cfg["update_args"])
    readings = {}
    for boundary in (cfg["boundary"], "zero"):
        program = getattr(apps, cfg["program"])(boundary=boundary)
        got = compile_program(program, grid, steps=steps, update=update)(
            start, scalars, coeffs)
        readings[str(boundary)] = common.compare(cfg, start, got, ref)
    ok, _ = common.judge(readings[str(cfg["boundary"])], cell.limits)
    assert ok, readings
    ok, _ = common.judge(readings["zero"], cell.limits)
    assert not ok, readings
    assert readings["zero"]["change_err"] > 100 * cell.limits["change_err"]
