"""The program's own spans and named scopes in a traced window.

The program opens host spans (``jax.profiler.TraceAnnotation``) named
``repro.<name>`` at its layer boundaries, and names device scopes
(``jax.named_scope``) around some of its operations.  ``bench/trace.py``
keeps only the harness's ``bench.`` spans; this module reads the
``repro.`` ones, each with the host thread (line of the host plane) it
ran on, from the same ``.xplane.pb``, parsed once per process.  A reader
takes the window, operations and programs from ``ctx.trace``.

A named scope (``jax.named_scope``) is not in the device events: an
``XLA Ops`` event carries the instruction's HLO text and its times only.
It is in the ``op_name`` metadata of the instruction in the optimized HLO
of each program, which the profiler keeps in its ``/host:metadata``
plane (stat ``Hlo Proto``, one event per program, named as the program's
``XLA Modules`` events are).  ``ProfileData`` does not expose that plane's
metadata, so ``program_scopes`` reads it from the file's protobuf wire
format.  A fusion has the ``op_name`` of its root instruction.  The
operations of control flow (``while``, ``conditional``, ``call``) enclose
their bodies' operations, so device time under a scope leaves them out.
"""

from __future__ import annotations

import bisect
import functools
import re
from dataclasses import dataclass

from . import harness
from . import trace as trace_mod

PREFIX = "repro."
#: Operations whose events enclose the events of their bodies' operations.
CONTAINERS = ("while", "conditional", "call")


@dataclass(frozen=True)
class Span:
    name: str            # without the ``repro.`` prefix
    start_ns: float
    end_ns: float
    line: tuple          # (host plane, line index): one host thread


def host_spans(planes) -> list[Span]:
    """Every ``repro.`` span of the host planes of ``planes``
    (``ProfileData.planes`` or objects of the same shape)."""
    out = []
    for plane in planes:
        if trace_mod._device_index(plane.name) is not None:
            continue
        for i, line in enumerate(plane.lines):
            for e in line.events:
                if e.name.startswith(PREFIX):
                    out.append(Span(e.name[len(PREFIX):], e.start_ns,
                                    e.start_ns + e.duration_ns,
                                    (plane.name, i)))
    return out


@functools.lru_cache(maxsize=1)
def _load(path: str) -> tuple[Span, ...]:
    import jax
    return tuple(host_spans(jax.profiler.ProfileData.from_file(path).planes))


def _xplane(ctx) -> str:
    """The trace file of the cell's traced run."""
    d = harness.trace_dir(harness.BENCH_DIR.parent, ctx.cell.name)
    return trace_mod.find_xplane(str(d))


def of(ctx) -> tuple[Span, ...]:
    """The ``repro.`` spans of the cell's traced run."""
    return _load(_xplane(ctx))


def named(spans, name: str, window, line=None) -> list[Span]:
    """The spans called ``name`` (on ``line`` if given) that overlap
    ``window``."""
    w0, w1 = window
    return [s for s in spans if s.name == name and s.end_ns > w0
            and s.start_ns < w1 and (line is None or s.line == line)]


def started(spans, window) -> list[Span]:
    """Those of ``spans`` that start inside ``window``."""
    return [s for s in spans if window[0] <= s.start_ns < window[1]]


def clipped_ns(spans, window) -> float:
    """Summed duration of ``spans`` inside ``window``."""
    w0, w1 = window
    return sum(min(s.end_ns, w1) - max(s.start_ns, w0) for s in spans)


def union_ns(spans, window) -> float:
    """Length of the union of ``spans`` inside ``window``."""
    w0, w1 = window
    merged = trace_mod._union([(max(s.start_ns, w0), min(s.end_ns, w1))
                               for s in spans])
    return sum(b - a for a, b in merged if b > a)


def line_of(spans, name: str):
    """The host thread that opened most spans called ``name``, or None."""
    count: dict = {}
    for s in spans:
        if s.name == name:
            count[s.line] = count.get(s.line, 0) + 1
    return max(count, key=count.get) if count else None


# Field numbers of the protobuf messages read (tsl/profiler/protobuf/
# xplane.proto, xla/service/hlo.proto, xla/xla_data.proto).
_SPACE_PLANES = 1                      # XSpace.planes
_PLANE_NAME, _PLANE_EVENT_MD, _PLANE_STAT_MD = 2, 4, 5
_MAP_VALUE = 2                         # value of a map entry
_MD_NAME, _EVENT_MD_STATS = 2, 5       # X{Event,Stat}Metadata.name; .stats
_STAT_MD_ID, _STAT_BYTES = 1, 6        # XStat.metadata_id, .bytes_value
_HLO_MODULE = 1                        # HloProto.hlo_module
_MODULE_COMPUTATIONS = 3               # HloModuleProto.computations
_COMPUTATION_INSTRUCTIONS = 2          # HloComputationProto.instructions
_INSTR_NAME, _INSTR_METADATA = 1, 7    # HloInstructionProto
_OP_NAME = 2                           # OpMetadata.op_name
METADATA_PLANE = "/host:metadata"
HLO_PROTO_STAT = "Hlo Proto"


def _fields(buf):
    """(field number, value) of each field of a protobuf message (a
    ``memoryview``), in order: varints as ints, the rest as views."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            value, i = _varint(buf, i)
        elif kind == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif kind in (1, 5):
            width = 8 if kind == 1 else 4
            value, i = buf[i:i + width], i + width
        else:
            raise ValueError(f"protobuf wire type {kind} not read")
        yield key >> 3, value


def _varint(buf, i: int) -> tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        shift += 7
        if b < 0x80:
            return out, i


def _first(buf, field: int, default=b""):
    return next((v for f, v in _fields(buf) if f == field), default)


def _text(buf, field: int) -> str:
    return bytes(_first(buf, field)).decode()


def _op_names(hlo_proto) -> dict[str, str]:
    """Instruction name -> ``op_name`` of one serialized ``HloProto``."""
    out = {}
    for f, comp in _fields(_first(hlo_proto, _HLO_MODULE)):
        if f != _MODULE_COMPUTATIONS:
            continue
        for g, ins in _fields(comp):
            if g == _COMPUTATION_INSTRUCTIONS:
                md = _first(ins, _INSTR_METADATA, None)
                if md is not None:
                    out[_text(ins, _INSTR_NAME)] = _text(md, _OP_NAME)
    return out


def program_scopes(xspace: bytes) -> dict[str, dict[str, str]]:
    """For each program in a serialized ``XSpace`` (an ``.xplane.pb``),
    by its ``XLA Modules`` name, the ``op_name`` (scope path) of each
    instruction of its optimized HLO."""
    out: dict[str, dict[str, str]] = {}
    for f, plane in _fields(memoryview(xspace)):
        if f != _SPACE_PLANES or _text(plane, _PLANE_NAME) != METADATA_PLANE:
            continue
        stat_ids = set()
        for g, entry in _fields(plane):
            if g == _PLANE_STAT_MD:
                md = _first(entry, _MAP_VALUE)
                if _text(md, _MD_NAME) == HLO_PROTO_STAT:
                    stat_ids.add(_first(md, _STAT_MD_ID, 0))
        for g, entry in _fields(plane):
            if g != _PLANE_EVENT_MD:
                continue
            md = _first(entry, _MAP_VALUE)
            for h, stat in _fields(md):
                if h == _EVENT_MD_STATS and \
                        _first(stat, _STAT_MD_ID, 0) in stat_ids:
                    out[_text(md, _MD_NAME)] = _op_names(
                        _first(stat, _STAT_BYTES))
    return out


@functools.lru_cache(maxsize=1)
def _load_scopes(path: str) -> dict[str, dict[str, str]]:
    with open(path, "rb") as f:
        return program_scopes(f.read())


def scopes_of(ctx) -> dict[str, dict[str, str]]:
    """``program_scopes`` of the cell's traced run."""
    return _load_scopes(_xplane(ctx))


def is_container(op: trace_mod.Op) -> bool:
    return op.name.split(".", 1)[0] in CONTAINERS


def program_pattern(name: str) -> re.Pattern:
    """Matches the text of the executions of the jitted function ``name``
    (``jit_<name>``)."""
    return re.compile(r"(?<![a-z0-9_])jit_" + re.escape(name)
                      + r"(?![a-z0-9_])")


def program_ops(tr: trace_mod.Trace, program: str):
    """The operations, control flow left out, that ran inside an
    execution of the jitted function ``program`` on their device, each
    with the name of the program that execution ran."""
    pat = program_pattern(program)
    runs: dict[int, list[tuple[float, float, str]]] = {}
    for m in tr.modules:
        if pat.search(m.text):
            runs.setdefault(m.device, []).append(
                (m.start_ns, m.start_ns + m.dur_ns, m.name))
    for v in runs.values():
        v.sort()
    starts = {d: [r[0] for r in v] for d, v in runs.items()}
    out = []
    for o in tr.ops:
        if is_container(o) or o.device not in runs:
            continue
        k = bisect.bisect_right(starts[o.device], o.start_ns) - 1
        if k >= 0 and o.start_ns < runs[o.device][k][1]:
            out.append((o, runs[o.device][k][2]))
    return out


def scope_share(tr: trace_mod.Trace, scopes: dict, scope: str,
                program: str):
    """Percent of the device time of ``program``'s operations whose
    instruction's ``op_name`` lies under the named scope ``scope``
    (``scopes``: ``program_scopes``); None without such operations or
    without any under the scope."""
    ops = program_ops(tr, program)
    total = sum(o.dur_ns for o, _ in ops)
    under = sum(o.dur_ns for o, m in ops
                if scope in scopes.get(m, {}).get(o.name, ""))
    if not total or not under:
        return None
    return 100.0 * under / total
