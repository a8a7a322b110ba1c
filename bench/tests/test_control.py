"""The control of every cell, rehearsed on the CPU: ``control.py`` runs
the cell with the program and then with the bfloat16 reference in the
program's place. The program's readings stay within the cell's limits and
the control's fail them. On the chip the same script, at the cell's own
size and on a dozen seeds, gives the readings the limits are set from."""

import json
import os
import subprocess
import sys

import pytest

import common

CELLS = [w["name"] for w in common.load_json(
    common.ROOT / "BENCHMARK.json")["workloads"]]


@pytest.mark.parametrize("cell", CELLS)
def test_control_fails_limits_that_the_program_meets(cell, tmp_path):
    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    proc = subprocess.run(
        [sys.executable, str(common.BENCH / "control.py"), "--rehearse",
         "--workload", cell, "--seconds", "1",
         "--seeds", str(2**31 + 1), str(2**31 + 2),
         "--control-seeds", str(2**31 + 3), str(2**31 + 4)],
        capture_output=True, text=True, timeout=900, env=env)
    assert proc.returncode == 0, proc.stderr[-4000:]
    summary = json.loads(proc.stdout.splitlines()[-1])
    limits = summary["limits"]
    assert all(summary["lower"][k] <= lim for k, lim in limits.items())
    assert any(summary["upper"][k] > lim for k, lim in limits.items())
