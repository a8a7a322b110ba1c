"""Share of the HBM roofline the fused loop reaches, in %: the least time
the chips could take for the traced window's grid-point updates (their
compulsory bytes over the peak bandwidth of the cell's chips) over the
device-busy time of that window. The bytes come from the configuration,
not the plan, so the share prices the same work whatever implements it."""

import common


def read(ctx):
    t, c = ctx["trace"], ctx["counters"]
    if t is None or not t.devices or not c.get("point_steps"):
        return None
    least = (common.compulsory_bytes_per_point_step(ctx["config"])
             * c["point_steps"]
             / (ctx["peaks"]["hbm_bytes_per_s"] * ctx["chips"]))
    return 100.0 * least / t.busy_s()
