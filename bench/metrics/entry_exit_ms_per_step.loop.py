"""Device time per time step of the fused loop's ops tagged ``entry`` or
``exit``: the carry and coefficient pads before the loop and the interior
slices after it, once per dispatch, spread over the window's steps, in ms,
averaged over the devices."""

import phases


def read(ctx):
    return phases.ms_per_step(ctx, {"entry", "exit"})
