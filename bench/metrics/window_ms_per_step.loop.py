"""Device time per time step of the fused loop's ops tagged ``window``:
a kernel's operands sliced out of an oversized carry and its outputs
cropped, in ms, averaged over the devices."""

import phases


def read(ctx):
    return phases.ms_per_step(ctx, {"window"})
