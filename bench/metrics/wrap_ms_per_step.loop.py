"""Device time per time step of the fused loop's ops tagged ``wrap``: the
slices and concatenations that fill a periodic axis's halo (the carry
refill, a group's pad or the entry's), in ms, averaged over the devices.
None where no op carries a phase tag."""

import phases


def read(ctx):
    return phases.ms_per_step(ctx, {"wrap"})
