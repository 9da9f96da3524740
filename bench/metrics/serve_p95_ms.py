"""The 95th percentile of request latency in the traced window, from when
a request was due to when its client held the response (host clock).  In
a closed loop the ranks wait for their answers, so the tail shows the
serving loop's stalls beside the rate they cost."""


def read(ctx):
    return ctx.counters.get("p95_ms")
