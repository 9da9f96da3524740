"""Store operations dispatched per solver step of the producer: the
server's exact ``op_count`` over the traced window, divided by the
lockstep steps the ranks completed in it."""


def read(ctx):
    c = ctx.counters
    if not c.get("steps"):
        return None
    return c["store_ops"] / c["steps"]
