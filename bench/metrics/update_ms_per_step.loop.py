"""Device time per time step of the fused loop's ops tagged ``update``:
the update rule and the interior slices of the carry it reads, in ms,
averaged over the devices."""

import phases


def read(ctx):
    return phases.ms_per_step(ctx, {"update"})
