"""Device time per time step of the fused loop's ops tagged
``group_pad``: intermediate fields padded for the next fuse group, in ms,
averaged over the devices."""

import phases


def read(ctx):
    return phases.ms_per_step(ctx, {"group_pad"})
