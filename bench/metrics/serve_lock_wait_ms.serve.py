"""Host time spent waiting for the store's table locks per request
served: the ``repro.store.lock`` spans (each the wait of one acquisition)
of every thread, clipped to the traced window and summed, over the
serving loop's exact ``served``."""

from bench import spans as S


def read(ctx):
    w = ctx.trace.window
    waits = S.named(S.of(ctx), "store.lock", w)
    served = ctx.counters.get("served", 0)
    if not waits or not served:
        return None
    return S.clipped_ns(waits, w) * 1e-6 / served
