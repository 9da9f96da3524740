"""The serving model step's share of the chip's peak: ResNet-50's
forward FLOPs (``bench/flops.py``, 2 x 4.09 GMAC per 224x224 image) for
every request served in the traced window, over the bf16 peak times the
device time of the fused serving programs (``store.serve_batch``: probe,
gather, forward over all ``max_batch`` slots, masked put)."""


def read(ctx):
    secs, n = ctx.trace.module_seconds(lambda m: "serve_batch" in m.text)
    served = ctx.counters.get("served", 0)
    if not n or not served:
        return None
    cfg = ctx.cell.cfg
    work = served * ctx.flops.resnet50_flops(cfg["image"][1], cfg["classes"])
    return 100.0 * work / (secs * ctx.peaks["bf16_flops_per_s"])
