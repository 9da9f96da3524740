"""Share of the traced window in which the serving loop's thread slept in
its backoff after a step found no request: the union of its
``repro.serve.idle`` spans, clipped to the window, over the window."""

from bench import spans as S


def read(ctx):
    sp = S.of(ctx)
    loop = S.line_of(sp, "serve.dispatch")
    if loop is None:
        return None
    w = ctx.trace.window
    idle = S.named(sp, "serve.idle", w, loop)
    return 100.0 * S.union_ns(idle, w) / (w[1] - w[0])
