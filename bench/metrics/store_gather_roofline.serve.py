"""The store's probe and gather kernels inside the fused serving
dispatch, as a share of their bandwidth roofline: the least bytes the
window's batches need (per batch the request ring's slot keys and
versions and the queries; per request served one image row read and
written; padding slots repeat one row, which the kernel does not fetch
again) over HBM bandwidth times the kernels' summed device time."""

from bench.trace import kernel_ops


def read(ctx):
    tr, c = ctx.trace, ctx.counters
    ops = kernel_ops(tr, "probe", "serve_batch") \
        + kernel_ops(tr, "gather", "serve_batch")
    if not ops or not c.get("batches"):
        return None
    f = ctx.flops
    image = 4
    for d in c["image"]:
        image *= d
    least = (c["batches"] * f.probe_bytes(c["request_slots"], c["max_batch"])
             + f.gather_rows_bytes(c["served"], image)) \
        / ctx.peaks["hbm_bytes_per_s"]
    return 100.0 * least / (sum(o.dur_ns for o in ops) * 1e-9)
