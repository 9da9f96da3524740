"""The trainer step's share of the chips' peak: the forward and backward
FLOPs one fused epoch requires (``bench/flops.py``: both SGD microsteps
and the validation forward) times the epochs completed in the traced
window, over the window, the chips and the bf16 peak."""


def read(ctx):
    c = ctx.counters
    if not c.get("epochs") or not ctx.trace.busy_ns:
        return None
    work = c["epochs"] * ctx.flops.ae_epoch_flops(ctx.cell.cfg, c["gather"],
                                                  c["batch"])
    return 100.0 * work / (c["elapsed_s"] * ctx.chips
                           * ctx.peaks["bf16_flops_per_s"])
