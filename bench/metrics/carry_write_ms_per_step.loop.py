"""Device time per time step of the fused loop's ops tagged
``carry_write``: the new state written into the carry, by a re-pad or a
scatter, in ms, averaged over the devices."""

import phases


def read(ctx):
    return phases.ms_per_step(ctx, {"carry_write"})
