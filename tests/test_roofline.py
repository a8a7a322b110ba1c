"""Unit tests for the stencil plan model (``repro.analysis.stencil_roofline``):
reuse-aware bytes per point."""

import dataclasses

from repro.analysis.stencil_roofline import (model_plan, model_program,
                                             plan_bytes_per_point)
from repro.apps import pw_advection
from repro.core.schedule import auto_plan


def test_stream_schedule_models_fewer_bytes_than_block():
    """The stream schedule charges each input cell once per sweep; the
    block schedule re-reads window overlaps every tile.  On the paper's
    advection kernel with a deliberately small block, the modeled
    bytes/point must separate — and the stream number must sit at the
    read-once floor (inputs + outputs, plus only the halo-ring fraction)."""
    p = pw_advection()
    grid = (32, 32, 128)
    block = auto_plan(p, grid)
    small = dataclasses.replace(block, block=(4, 4, 128),
                                groups=[list(g) for g in block.groups])
    stream = auto_plan(p, grid, schedule="stream")

    b_small = plan_bytes_per_point(p, small, grid)
    b_stream = plan_bytes_per_point(p, stream, grid)
    assert b_stream < b_small

    # read-once floor: 3 inputs fetched once + 3 outputs written once,
    # times 4 bytes, inflated only by the padded halo ring (< 25% here)
    floor = (3 + 3) * 4
    assert floor <= b_stream < floor * 1.25
    # the 4x4 block re-reads its 6x6 overlap ring: strictly above the floor
    assert b_small > floor * 1.5

    # and the time model ranks accordingly for this memory-bound stencil
    assert model_plan(p, stream, grid) < model_plan(p, small, grid)


def test_model_plan_block_schedule_unchanged_for_jnp_backends():
    """Non-pallas candidates still collapse to the backend-level model —
    the schedule axis is a pallas-only dimension."""
    p = pw_advection()
    grid = (16, 16, 128)
    plan = dataclasses.replace(auto_plan(p, grid, backend="jnp_naive"),
                               backend="jnp_naive")
    assert plan_bytes_per_point(p, plan, grid) == \
        model_program(p).bytes_per_point["jnp_naive"]
