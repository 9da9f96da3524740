"""Mean host time of one serving dispatch in the traced window: the
``repro.serve.dispatch`` span around ``Client.serve_batch`` (table-lock
wait, jit dispatch, and the block on the result that the ``model_eval``
timer adds)."""

from bench import spans as S


def read(ctx):
    d = S.started(S.named(S.of(ctx), "serve.dispatch", ctx.trace.window),
                  ctx.trace.window)
    if not d:
        return None
    return sum(s.end_ns - s.start_ns for s in d) / len(d) * 1e-6
