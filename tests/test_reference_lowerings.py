"""Every lowering of the fused loop against the benchmark's plain
references (``bench/configs/<name>.py``), which never import ``repro``.

Three configurations, three lowerings (the default block plan, the stream
schedule, ``jnp_fused``) and an odd and an even step count: the odd count
runs the step after the block loop's two-step body.  Each answer is judged
by the benchmark's own comparison and its cell's limits.  The data, the
reference and the limits are loaded as ``bench/tests/test_reference.py``
loads them, which already runs the default lowering of the two zero
configurations at 6 steps.
"""

import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1] / "bench"
sys.path.insert(0, str(BENCH))

import common  # noqa: E402
from repro import apps  # noqa: E402
from repro.core import compile_program  # noqa: E402

GRID = (16, 16, 128)
SEED = 2**31 + 17
CONFIGS = ["pw_advection", "tracer_advection", "tracer_advection_cyclic"]
LOWERINGS = {"default": {}, "stream": {"schedule": "stream"},
             "jnp_fused": {"backend": "jnp_fused"}}
#: the cases ``bench/tests/test_reference.py`` runs
COVERED = {("pw_advection", "default", 6), ("tracer_advection", "default", 6)}
CASES = [(name, lowering, steps) for name in CONFIGS for lowering in LOWERINGS
         for steps in (5, 6) if (name, lowering, steps) not in COVERED]


def _limits(config: str) -> dict:
    """The limits of the benchmark cell that runs ``config``."""
    bench = common.load_json(common.ROOT / "BENCHMARK.json")
    cell = next(w["name"] for w in bench["workloads"]
                if w["config"] == config)
    return common.load_json(BENCH / "limits" / f"{cell}.json")


@pytest.mark.parametrize("name,lowering,steps", CASES)
def test_lowering_meets_the_plain_reference(name, lowering, steps):
    cfg = common.load_json(BENCH / "configs" / f"{name}.json")
    ref = common.load_module(BENCH / "configs" / f"{name}.py",
                             f"reference_{name}")
    start = common.make_fields(cfg, GRID, seed=SEED)
    scalars = common.make_scalars(cfg)
    coeffs = common.make_coeffs(cfg, GRID)
    program = getattr(apps, cfg["program"])(boundary=cfg["boundary"])
    update = getattr(apps, cfg["update"])(*cfg["update_args"])
    ex = compile_program(program, GRID, steps=steps, update=update,
                         **LOWERINGS[lowering])
    got = ex(start, scalars, coeffs)
    want = ref.run(start, scalars, coeffs, steps, cfg["update_args"])
    ok, lines = common.judge(common.compare(cfg, start, got, want),
                             _limits(name))
    assert ok, lines
