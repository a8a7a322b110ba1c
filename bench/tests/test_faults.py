"""A whole rehearsed run of each cell (``run.py --rehearse``: the CPU in
place of the chip, every step after that as on the chip) with the timed
path broken underneath, once for each fault the cell can have; each must
come out ``correct: false``. A run with nothing broken comes out true.

Run as a script, this file plants one fault and runs one cell:

    python bench/tests/test_faults.py <fault> --rehearse --workload ... \\
        --seed ... --seconds ...
"""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve()
BENCH = HERE.parents[1]


def _cases() -> list:
    """Each cell of ``BENCHMARK.json`` with no fault, and with each fault
    its traffic kind can have."""
    sys.path.insert(0, str(BENCH))
    import common
    cases = []
    for w in common.load_json(common.ROOT / "BENCHMARK.json")["workloads"]:
        traffic = common.load_json(
            common.BENCH / "traffic" / f"{w['traffic']}.json")
        faults = ["none", "unchanged", "altered"]
        if traffic["kind"] == "serve":
            faults.append("half_batch")
        if "mesh" in traffic:
            faults.append("no_exchange")
        cases += [(w["name"], f) for f in faults]
    return cases


CASES = _cases()


def _altered(out: dict) -> dict:
    """One value of the first field moved by a thousandth of the field's
    largest magnitude."""
    import jax.numpy as jnp
    f = sorted(out)[0]
    x = out[f]
    mid = tuple(n // 2 for n in x.shape)
    return dict(out, **{f: x.at[mid].add(1e-3 * jnp.max(jnp.abs(x)))})


def plant(fault: str):
    """Break the timed path: the executor the loop driver compiles, the
    batched executable the serving engine dispatches, or the halo exchange
    between chips."""
    import jax
    import jax.numpy as jnp

    import drive_loop
    from repro.serve import engine

    def wrap(answer):
        def broken(fields, scalars, coeffs):
            out = answer(fields, scalars, coeffs)
            if fault == "unchanged":
                return {f: fields[f] for f in out}
            if fault == "altered":
                return _altered(out)
            if fault == "half_batch":
                n = next(iter(out.values())).shape[0]
                h = max(1, n // 2)
                return {f: jnp.concatenate([x[:h]] + [x[:1]] * (n - h))
                        for f, x in out.items()}
            raise ValueError(fault)
        return broken

    if fault == "no_exchange":
        jax.lax.ppermute = lambda x, axis_name, perm: jnp.zeros_like(x)
        return
    real_compile = drive_loop.compile_program

    def compile_program(*args, **kwargs):
        return wrap(real_compile(*args, **kwargs))
    drive_loop.compile_program = compile_program

    real_init = engine._BucketExecutor.__init__

    def init(self, *args, **kwargs):
        real_init(self, *args, **kwargs)
        self.batched = wrap(self.batched)
    engine._BucketExecutor.__init__ = init


@pytest.mark.parametrize("cell,fault", CASES)
def test_fault_makes_run_incorrect(cell, fault, tmp_path):
    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    proc = subprocess.run(
        [sys.executable, str(HERE), fault, "--rehearse", "--workload", cell,
         "--seed", str(2**31 + 99), "--seconds", "1"],
        capture_output=True, text=True, timeout=600, env=env)
    lines = [ln for ln in proc.stdout.splitlines() if ln.startswith("{")]
    assert lines, proc.stderr[-4000:]
    result = json.loads(lines[-1])
    assert result["correct"] is (fault == "none"), (result, proc.stderr[-2000:])
    assert proc.returncode == (0 if fault == "none" else 1)


if __name__ == "__main__":
    fault = sys.argv.pop(1)
    sys.path.insert(0, str(BENCH))
    import run  # noqa: E402  (sets up the rehearsal before JAX loads)
    if fault != "none":
        plant(fault)
    sys.exit(run.main(sys.argv[1:]))
