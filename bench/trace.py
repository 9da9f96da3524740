"""Reduction of a JAX profiler trace to the benchmark's device numbers.

The profiler writes ``<dir>/plugins/profile/<time>/<host>.xplane.pb``.
``jax.profiler.ProfileData`` reads it: planes (one per device and one for
the host), lines, and events with a start and a duration in nanoseconds on
one clock.  This module takes from it

* the traced window: the host span the harness opened around the measured
  loop (``WINDOW_SPAN``);
* per device, the union of the intervals in which an operation ran (busy),
  clipped to the window, and the gaps between them (idle);
* every device operation with its duration and the text the profiler
  attached to it (HLO name, program, source scope), so that a metric's
  reader can pick out a kernel or a program by name;
* the harness's host spans (names starting ``bench.``), so that each idle
  gap is put down to what the host was doing in it.
"""

from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field

WINDOW_SPAN = "bench.window"
HOST_PREFIX = "bench."
#: Lines of a TPU plane that hold one event per executed HLO operation.
OP_LINES = ("XLA Ops",)
#: Lines of a TPU plane that hold one event per executed program.
MODULE_LINES = ("XLA Modules",)


@dataclass
class Op:
    name: str            # short HLO name: ``fusion.12``, ``quadconv_matmul.1``
    start_ns: float
    dur_ns: float
    device: int
    text: str            # full event name and every string stat, lower case


def short_name(event_name: str) -> str:
    """The TPU profiler names an operation by its HLO text,
    ``%fusion.12 = bf16[8,112,112,64]{...} fusion(...), ...``; keep the
    instruction's name.  Other names pass through."""
    if event_name.startswith("%") and " = " in event_name:
        return event_name[1:event_name.index(" = ")]
    return event_name


def op_label(op: Op) -> str:
    """A short readable label: name, result shape and HLO opcode."""
    text = op.text
    if " = " not in text:
        return op.name
    rhs = text.split(" = ", 1)[1]
    shape = rhs.split("{", 1)[0].split(" ", 1)[0]
    opcode = rhs.split("(", 1)[0].split(" ")[-1]
    return f"{op.name} {shape} {opcode}"


@dataclass
class Trace:
    window: tuple[float, float]
    ops: list[Op]
    modules: list[Op]
    busy_ns: dict[int, float]
    gaps: list[tuple[float, float]]           # idle gaps of device 0
    spans: list[tuple[str, float, float]]     # host spans in the window
    devices: list[int] = field(default_factory=list)

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    @property
    def busy_s(self) -> float:
        """Busy seconds averaged over the devices that ran anything."""
        if not self.busy_ns:
            return 0.0
        return sum(self.busy_ns.values()) / len(self.busy_ns) * 1e-9

    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def module_seconds(self, match) -> tuple[float, int]:
        """Total device seconds and count of the program executions
        ``match`` accepts (a predicate on :class:`Op`)."""
        sel = [m for m in self.modules if match(m)]
        return sum(m.dur_ns for m in sel) * 1e-9, len(sel)

    def top_ops(self, n: int = 10) -> list[list]:
        """The ``n`` operations that took most device time, by label."""
        agg: dict[str, float] = {}
        for o in self.ops:
            k = op_label(o)
            agg[k] = agg.get(k, 0.0) + o.dur_ns * 1e-9
        return [[k, v] for k, v in sorted(agg.items(), key=lambda kv: -kv[1])[:n]]

    def idle_gaps(self, n: int = 10) -> list[list]:
        """The ``n`` longest idle gaps of device 0, each named by the
        innermost host span that covers its midpoint."""
        out = []
        for a, b in sorted(self.gaps, key=lambda g: g[0] - g[1])[:n]:
            mid = 0.5 * (a + b)
            cover = [s for s in self.spans if s[1] <= mid <= s[2]]
            name = min(cover, key=lambda s: s[2] - s[1])[0] if cover \
                else "no host span"
            out.append([name, (b - a) * 1e-9])
        return out


def find_xplane(log_dir: str) -> str:
    files = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return max(files, key=os.path.getmtime)


def _device_index(plane_name: str) -> int | None:
    """``/device:TPU:3`` -> 3; other planes -> None."""
    prefix = "/device:TPU:"
    if not plane_name.startswith(prefix):
        return None
    rest = plane_name[len(prefix):]
    return int(rest) if rest.isdigit() else None


def _stat_text(event) -> str:
    parts = [event.name]
    try:
        for key, value in event.stats:
            if isinstance(value, str):
                parts.append(f"{key}={value}")
    except (TypeError, ValueError):
        pass
    return " ".join(parts).lower()


def _union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    merged: list[list[float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def reduce_planes(planes, window_span: str = WINDOW_SPAN) -> Trace:
    """Reduce profiler planes (``ProfileData.planes`` or objects of the
    same shape) to a :class:`Trace`."""
    spans, ops, modules = [], [], []
    window = None
    for plane in planes:
        dev = _device_index(plane.name)
        for line in plane.lines:
            if dev is None:
                for e in line.events:
                    if e.name.startswith(HOST_PREFIX):
                        end = e.start_ns + e.duration_ns
                        spans.append((e.name, e.start_ns, end))
                        if e.name == window_span and window is None:
                            window = (e.start_ns, end)
                continue
            target = ops if line.name in OP_LINES else \
                modules if line.name in MODULE_LINES else None
            if target is None:
                continue
            for e in line.events:
                target.append(Op(short_name(e.name), e.start_ns,
                                 e.duration_ns, dev, _stat_text(e)))
    if window is None:
        raise ValueError(f"trace has no host span {window_span!r}")
    w0, w1 = window

    def clip(o: Op):
        return max(o.start_ns, w0), min(o.start_ns + o.dur_ns, w1)

    ops = [o for o in ops if clip(o)[1] > clip(o)[0]]
    modules = [m for m in modules if clip(m)[1] > clip(m)[0]]
    devices = sorted({o.device for o in ops})
    busy, gaps = {}, []
    for d in devices:
        merged = _union([clip(o) for o in ops if o.device == d])
        busy[d] = sum(b - a for a, b in merged)
        if d == devices[0]:
            edges = [w0] + [x for ab in merged for x in ab] + [w1]
            gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                    if edges[i + 1] > edges[i]]
    spans = [s for s in spans if s[2] > w0 and s[1] < w1]
    return Trace(window, ops, modules, busy, gaps, spans, devices)


def load(log_dir: str, window_span: str = WINDOW_SPAN) -> Trace:
    import jax
    data = jax.profiler.ProfileData.from_file(find_xplane(log_dir))
    return reduce_planes(data.planes, window_span)


def kernel_ops(tr: Trace, prefix: str, module: str | None = None) -> list[Op]:
    """Executions of the Pallas kernel whose jitted wrapper is named
    ``prefix``: its HLO custom call is ``<prefix>.<n>``, or
    ``jvp_jit_<prefix>__.<n>`` where it runs under differentiation.  A
    name alone could be one of XLA's own operations (``gather.3``), so an
    operation counts only where the profiler's text marks it a custom
    call, or, if ``module`` is given and the text says nothing, where it
    ran inside an execution of a program whose name holds ``module``."""
    pat = re.compile(r"(?:^|_)" + re.escape(prefix) + r"(?:_*\.\d+|_*$)")
    named = [o for o in tr.ops if pat.search(o.name)]
    marked = [o for o in named if "custom" in o.text or "pallas" in o.text]
    if marked or module is None:
        return marked
    spans = [(m.device, m.start_ns, m.start_ns + m.dur_ns)
             for m in tr.modules if module in m.text]
    return [o for o in named if any(d == o.device and a <= o.start_ns < b
                                    for d, a, b in spans)]
