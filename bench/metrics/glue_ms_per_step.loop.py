"""Device time per time step of the ops that are neither generated kernels
nor collectives (the update rule, re-pad and carry writes around the
kernels), in ms, averaged over the devices."""


def read(ctx):
    t, c = ctx["trace"], ctx["counters"]
    if t is None or not t.devices or not c.get("steps"):
        return None
    return 1e3 * t.class_s("other") / c["steps"]
