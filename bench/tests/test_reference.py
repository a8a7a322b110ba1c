"""The plain references against the system's fused loop, on the CPU at a
small grid: the float32 reference agrees with ``compile_program``, and
the same reference computed in bfloat16 does not, by far."""

import jax
import jax.numpy as jnp
import pytest

import common
from repro import apps
from repro.core import compile_program

GRID = (16, 16, 128)
STEPS = 6
# the float32 runs agree to rounding; bfloat16 misses by a large share of
# the change a step makes (bounds chosen with room from the CPU readings)
AGREE, DIFFER = 1e-4, 1e-2


def _cell(name):
    cfg = common.load_json(common.BENCH / "configs" / f"{name}.json")
    ref = common.load_module(common.BENCH / "configs" / f"{name}.py",
                             f"reference_{name}")
    return cfg, ref


@pytest.mark.parametrize("name", ["pw_advection", "tracer_advection"])
def test_reference_matches_fused_loop_and_bf16_does_not(name):
    cfg, ref = _cell(name)
    start = common.make_fields(cfg, GRID, seed=2**31 + 17)
    scalars = common.make_scalars(cfg)
    coeffs = common.make_coeffs(cfg, GRID)
    program = getattr(apps, cfg["program"])(boundary=cfg["boundary"])
    update = getattr(apps, cfg["update"])(*cfg["update_args"])
    ex = compile_program(program, GRID, steps=STEPS, update=update)
    got = ex(start, scalars, coeffs)

    want = ref.run(start, scalars, coeffs, STEPS, cfg["update_args"])
    low = ref.run(start, scalars, coeffs, STEPS, cfg["update_args"],
                  dtype=jnp.bfloat16)
    low = jax.tree.map(lambda x: x.astype(jnp.float32), low)

    sound = common.compare(cfg, start, got, want)
    control = common.compare(cfg, start, low, want)
    assert sound["change_err"] <= AGREE
    assert control["change_err"] >= DIFFER
    if "steady_err" in sound:
        assert sound["steady_err"] == 0.0
        assert control["steady_err"] > 0.0


def test_compulsory_bytes():
    assert common.compulsory_bytes_per_point_step(
        _cell("pw_advection")[0]) == 24
    assert common.compulsory_bytes_per_point_step(
        _cell("tracer_advection")[0]) == 28


def test_unchanged_state_reads_one():
    cfg, ref = _cell("pw_advection")
    start = common.make_fields(cfg, GRID, seed=5)
    want = ref.run(start, common.make_scalars(cfg),
                   common.make_coeffs(cfg, GRID), 2, cfg["update_args"])
    assert common.compare(cfg, start, start, want)["change_err"] == 1.0


def test_large_seeds_make_distinct_data():
    cfg, _ = _cell("pw_advection")
    a = common.make_fields(cfg, (8, 8, 8), seed=2**31 + 1)
    b = common.make_fields(cfg, (8, 8, 8), seed=2**32 + 2**31 + 1)
    c = common.make_fields(cfg, (8, 8, 8), seed=2**31 + 1)
    assert not jnp.array_equal(a["u"], b["u"])
    assert jnp.array_equal(a["u"], c["u"])
