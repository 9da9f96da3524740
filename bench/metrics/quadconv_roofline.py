"""The QuadConv contraction kernel's share of its roofline: for every
call the window made (``bench/flops.py``, unpadded shapes: 4 layers in
each of the epoch's SGD microsteps and in its validation forward) the
least time the chip could take, max(FLOPs / bf16 peak, bytes / HBM
bandwidth), summed, over the kernel's summed device time in the trace."""

from bench.trace import kernel_ops


def read(ctx):
    ops = kernel_ops(ctx.trace, "quadconv_matmul")
    c = ctx.counters
    if not ops or not c.get("epochs"):
        return None
    calls = ctx.flops.ae_epoch_contractions(ctx.cell.cfg, c["gather"],
                                            c["batch"])
    least = c["epochs"] * sum(max(k["flops"] / ctx.peaks["bf16_flops_per_s"],
                                  k["bytes"] / ctx.peaks["hbm_bytes_per_s"])
                              for k in calls)
    return 100.0 * least / (sum(o.dur_ns for o in ops) * 1e-9)
