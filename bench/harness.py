"""The benchmark's general machinery, driven by ``BENCHMARK.json``.

A cell names a configuration and a traffic mix.  Everything that belongs
to one of them is found by name:

* ``BENCHMARK.json`` ``configs[].file``: the configuration's sizes;
  ``bench/configs/<name>_ref.py`` beside it: its plain reference;
* ``bench/traffic/<name>.json``: the mix's parameters; its ``kind`` names
  the runner ``bench/runners/<kind>.py`` that runs it;
* ``bench/metrics/<metric>.py``: the reader of one per-layer metric, a
  function ``read(ctx) -> float | None``.

A runner's ``run(ctx)`` sets up, warms up, measures for ``ctx.seconds``
inside ``ctx.window()`` and checks its output against the reference, and
returns an :class:`Outcome`.  This module turns the outcome into the
result line.
"""

from __future__ import annotations

import contextlib
import importlib
import importlib.util
import json
import math
import time
from dataclasses import dataclass, field
from pathlib import Path
from typing import Any

from . import flops as flops_mod
from . import trace as trace_mod

BENCH_DIR = Path(__file__).resolve().parent


class BenchError(Exception):
    """The benchmark is misconfigured or a run cannot produce a result."""


@dataclass
class Cell:
    name: str
    config: str
    traffic_name: str
    chips: int
    cfg: dict
    traffic: dict

    @property
    def kind(self) -> str:
        return self.traffic["kind"]


@dataclass
class Check:
    """One number compared with the reference, beside its limit."""
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return math.isfinite(self.value) and self.value <= self.limit


@dataclass
class Outcome:
    setup_s: float
    e2e: dict[str, float]
    attempted: int
    failed: int
    checks: list[Check]
    counters: dict[str, Any] = field(default_factory=dict)
    memory_peak_bytes: int = 0


@dataclass
class Result:
    outcome: Outcome
    metrics: dict[str, dict]
    device: dict
    breakdown: dict | None

    @property
    def correct(self) -> bool:
        return bool(self.outcome.checks) and all(c.ok for c in self.outcome.checks)


# ---------------------------------------------------------------------------
# loading by name
# ---------------------------------------------------------------------------

def load_benchmark(root: Path) -> dict:
    path = Path(root) / "BENCHMARK.json"
    if not path.is_file():
        raise BenchError(f"no BENCHMARK.json at {root}")
    return json.loads(path.read_text())


def _by_name(items: list[dict], name: str, what: str) -> dict:
    for item in items:
        if item["name"] == name:
            return item
    raise BenchError(f"no {what} named {name!r} in BENCHMARK.json")


def load_cell(root: Path, bench: dict, name: str) -> Cell:
    w = _by_name(bench["workloads"], name, "workload")
    c = _by_name(bench["configs"], w["config"], "configuration")
    cfg_path = Path(root) / c["file"]
    traffic_path = BENCH_DIR / "traffic" / f"{w['traffic']}.json"
    for p in (cfg_path, traffic_path):
        if not p.is_file():
            raise BenchError(f"missing {p.relative_to(root)}")
    return Cell(name, w["config"], w["traffic"], int(w["chips"]),
                json.loads(cfg_path.read_text()),
                json.loads(traffic_path.read_text()))


def load_module(path: Path, name: str):
    """Import a file whose name may hold dots (a metric's reader)."""
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reference(cell: Cell):
    """The configuration's plain reference module."""
    return importlib.import_module(f"bench.configs.{cell.config}_ref")


def runner(cell: Cell):
    return importlib.import_module(f"bench.runners.{cell.kind}")


def peaks(device_kind: str) -> dict:
    table = json.loads((BENCH_DIR / "peaks.json").read_text())
    if device_kind not in table["kinds"]:
        raise BenchError(f"no peaks for device kind {device_kind!r} in "
                         f"bench/peaks.json")
    return table["kinds"][device_kind]


def limits(cell: Cell) -> dict[str, float]:
    """The limits of the cell's compared numbers, ``bench/limits/<cell>.json``
    (each set from readings of the program and of its control)."""
    path = BENCH_DIR / "limits" / f"{cell.name}.json"
    if not path.is_file():
        raise BenchError(f"no limits file bench/limits/{cell.name}.json")
    return {k: float(v["limit"]) for k, v in
            json.loads(path.read_text())["limits"].items()}


def trace_dir(root: Path, workload: str) -> Path:
    return Path(root) / "bench_out" / "trace" / workload


# ---------------------------------------------------------------------------
# what a runner is given
# ---------------------------------------------------------------------------

@dataclass
class Context:
    cell: Cell
    seed: int
    seconds: float
    trace_dir: Path | None
    t_start: float

    def setup_seconds(self) -> float:
        return time.perf_counter() - self.t_start

    @contextlib.contextmanager
    def window(self):
        """The measured window; under the profiler when tracing."""
        import jax
        if self.trace_dir is not None:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(str(self.trace_dir),
                                     profiler_options=opts)
        try:
            with jax.profiler.TraceAnnotation(trace_mod.WINDOW_SPAN):
                yield
        finally:
            if self.trace_dir is not None:
                jax.profiler.stop_trace()


def span(name: str):
    """A host span in the profiler's trace, named ``bench.<name>``."""
    import jax
    return jax.profiler.TraceAnnotation(trace_mod.HOST_PREFIX + name)


def memory_peak_bytes(chips: int) -> int:
    """Peak device memory on the fullest chip.  On the TPU the allocator's
    ``peak_bytes_in_use`` counts buffers only; the compiled programs'
    temporaries are reserved apart (``peak_bytes_reserved``, which reads
    the fused training epoch's 13.5 GB), so the peak is their sum."""
    import jax
    peak = 0
    for d in jax.devices()[:chips]:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0))
                   + int(stats.get("peak_bytes_reserved", 0)))
    return peak


# ---------------------------------------------------------------------------
# per-layer metrics
# ---------------------------------------------------------------------------

@dataclass
class MetricContext:
    cell: Cell
    trace: trace_mod.Trace
    counters: dict
    peaks: dict
    chips: int
    flops: Any = flops_mod


def cell_e2e(bench: dict, cell: str) -> list[dict]:
    return [m for m in bench["end_to_end"]
            if cell in m.get("workloads", [cell])]


def cell_per_layer(bench: dict, cell: str) -> list[dict]:
    moved = {m["name"] for m in cell_e2e(bench, cell)}
    return [m for m in bench["per_layer"]
            if (cell in m["workloads"] if "workloads" in m
                else m["moves"] in moved)]


def read_per_layer(bench: dict, cell: Cell, mctx: MetricContext) -> dict:
    out = {}
    for m in cell_per_layer(bench, cell.name):
        path = BENCH_DIR / "metrics" / f"{m['name']}.py"
        if not path.is_file():
            raise BenchError(f"no reader bench/metrics/{m['name']}.py")
        value = load_module(path, f"bench_metric_{m['name']}").read(mctx)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------

def run_cell(root: Path, bench: dict, cell: Cell, *, seed: int,
             seconds: float, trace: bool, device: dict,
             t_start: float) -> Result:
    peak_table = peaks(device["kind"])
    tdir = trace_dir(root, cell.name) if trace else None
    if trace:
        # the profiler keeps a bounded number of device events: a mix
        # whose scan emits many per second traces a shorter window
        seconds = min(seconds, cell.traffic.get("trace_seconds", seconds))
    ctx = Context(cell, seed, seconds, tdir, t_start)
    out = runner(cell).run(ctx)
    device = dict(device, memory_peak_bytes=out.memory_peak_bytes)
    breakdown = None
    if not trace:
        metrics = {}
        for m in cell_e2e(bench, cell.name):
            value = out.setup_s if m["name"] == "setup_s" \
                else out.e2e.get(m["name"])
            if value is None:
                raise BenchError(f"the {cell.kind} runner gave no "
                                 f"{m['name']}")
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    else:
        tr = trace_mod.load(str(tdir))
        mctx = MetricContext(cell, tr, out.counters, peak_table, cell.chips)
        metrics = read_per_layer(bench, cell, mctx)
        device["busy_s"] = tr.busy_s
        device["window_s"] = tr.window_s
        breakdown = {"device_ops": tr.top_ops(10),
                     "idle_gaps": tr.idle_gaps(10)}
    return Result(out, metrics, device, breakdown)


def check_lines(result: Result) -> list[str]:
    lines = [f"check {c.name}: {c.value!r} limit {c.limit!r} "
             f"{'ok' if c.ok else 'FAIL'}" for c in result.outcome.checks]
    lines.append(f"correct: {str(result.correct).lower()}")
    return lines


def result_line(result: Result) -> dict:
    line = {"correct": result.correct,
            "attempted": int(result.outcome.attempted),
            "failed": int(result.outcome.failed),
            "metrics": result.metrics,
            "device": result.device}
    if result.breakdown is not None:
        line["breakdown"] = result.breakdown
    line["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                      for c in result.outcome.checks}
    return line
