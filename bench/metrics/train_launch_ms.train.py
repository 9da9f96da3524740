"""Mean time from the trainer's call for a fused epoch to the epoch's
start on the chip: from each ``repro.capture_epoch`` span's start in the
traced window to the start of the next execution of the fused-epoch
program (``jit_epoch``, ``XLA Modules``) on the first device, one clock
for both."""

import bisect

from bench import spans as S


def read(ctx):
    tr = ctx.trace
    if not tr.devices:
        return None
    pat = S.program_pattern("epoch")
    starts = sorted(m.start_ns for m in tr.modules
                    if m.device == tr.devices[0] and pat.search(m.text))
    gaps = []
    for s in S.started(S.named(S.of(ctx), "capture_epoch", tr.window),
                       tr.window):
        k = bisect.bisect_left(starts, s.start_ns)
        if k < len(starts) and starts[k] < tr.window[1]:
            gaps.append(starts[k] - s.start_ns)
    if not gaps:
        return None
    return sum(gaps) / len(gaps) * 1e-6
