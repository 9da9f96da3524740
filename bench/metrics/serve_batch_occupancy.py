"""Requests per fused serving dispatch in the traced window: the serving
loop's exact ``served`` over ``batches`` (at most ``max_batch``)."""


def read(ctx):
    c = ctx.counters
    if not c.get("batches"):
        return None
    return c["served"] / c["batches"]
