"""Response polls per request served in the traced window: the clients'
``repro.retrieve`` spans (one per ``Client.get_kv``, each a device
program and a block on its result), on every thread but the serving
loop's, over the loop's exact ``served``."""

from bench import spans as S


def read(ctx):
    sp = S.of(ctx)
    loop = S.line_of(sp, "serve.dispatch")
    w = ctx.trace.window
    polls = [s for s in S.started(S.named(sp, "retrieve", w), w)
             if s.line != loop]
    served = ctx.counters.get("served", 0)
    if not polls or not served:
        return None
    return len(polls) / served
