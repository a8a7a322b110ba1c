"""Device time per time step of the ops that are neither kernels nor
collectives and carry no phase, in ms, averaged over the devices: what the
phase tags leave out of ``glue_ms_per_step.loop``."""

import phases


def read(ctx):
    return phases.ms_per_step(ctx, {None})
