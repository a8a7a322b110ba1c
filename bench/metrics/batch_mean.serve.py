"""Mean ``ServeResult.batch_size`` over the requests the window completed:
how many requests the engine ran in one dispatch."""


def read(ctx):
    return ctx["counters"].get("batch_mean")
