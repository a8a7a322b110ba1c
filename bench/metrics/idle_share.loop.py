"""Share of the traced window in which the device ran no op, in %,
averaged over the devices."""


def read(ctx):
    t = ctx["trace"]
    if t is None or not t.devices:
        return None
    return 100.0 * (1.0 - t.busy_s() / t.window_s)
