"""Device time per time step in which a collective (the ppermute halo
refresh) ran and no other op did, in ms, averaged over the devices."""


def read(ctx):
    t, c = ctx["trace"], ctx["counters"]
    if t is None or ctx["chips"] < 2 or not c.get("steps"):
        return None
    return 1e3 * t.exposed_s("collective") / c["steps"]
