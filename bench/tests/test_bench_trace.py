"""The trace reduction on a small constructed trace: busy union, idle
gaps and their host spans, kernel and program selection, the window."""

from types import SimpleNamespace as NS

import pytest

import _paths  # noqa: F401
from bench import trace as T


def ev(name, start, dur, stats=()):
    return NS(name=name, start_ns=start, duration_ns=dur, stats=list(stats))


def plane(name, lines):
    return NS(name=name, lines=[NS(name=k, events=v) for k, v in lines.items()])


def planes():
    host = plane("/host:CPU", {"python": [
        ev("bench.window", 1000, 9000),
        ev("bench.producer", 1000, 2000),
        ev("bench.sync", 6000, 4000),
        ev("unrelated", 0, 50000)]})
    dev0 = plane("/device:TPU:0", {
        "XLA Ops": [ev("fusion.1", 500, 1500),          # clipped to 1000..2000
                    ev("%quadconv_matmul.1 = f32[4,4096]{1,0} custom-call("
                       "%a, %b), custom_call_target=\"tpu_custom_call\"",
                       2500, 1000),
                    ev("gather.2", 3000, 1000),        # overlaps: union
                    ev("%jvp_jit_quadconv_matmul__.40 = f32[4,16] custom-call"
                       "(%a), custom_call_target=\"tpu_custom_call\"",
                       3200, 100),
                    ev("%not_quadconv_matmulx.2 = f32[4] custom-call(%a)",
                       3300, 100),
                    ev("fusion.1", 9500, 1000)],       # clipped to ..10000
        "XLA Modules": [ev("jit_serve_batch_impl(1)", 2500, 2000)],
        "Steps": [ev("0", 0, 100000)]})
    dev1 = plane("/device:TPU:1", {"XLA Ops": [ev("fusion.1", 4000, 2000)]})
    return [host, dev0, dev1]


def test_busy_union_and_window():
    tr = T.reduce_planes(planes())
    assert tr.window == (1000, 10000)
    assert tr.window_s == pytest.approx(9e-6)
    # device 0: [1000,2000] + [2500,4000] + [9500,10000] = 3000 ns
    assert tr.busy_ns[0] == pytest.approx(3000)
    assert tr.busy_ns[1] == pytest.approx(2000)
    assert tr.busy_s == pytest.approx(2.5e-6)
    assert tr.idle_share() == pytest.approx(1 - 2.5 / 9)
    assert tr.devices == [0, 1]


def test_idle_gaps_named_by_innermost_span():
    tr = T.reduce_planes(planes())
    assert sorted(tr.gaps) == [(2000, 2500), (4000, 9500)]
    gaps = tr.idle_gaps(10)
    assert gaps[0] == ["bench.sync", pytest.approx(5.5e-6)]
    assert gaps[1] == ["bench.producer", pytest.approx(0.5e-6)]


def test_kernel_and_program_selection():
    tr = T.reduce_planes(planes())
    qc = T.kernel_ops(tr, "quadconv_matmul")
    assert [o.name for o in qc] == ["quadconv_matmul.1",
                                    "jvp_jit_quadconv_matmul__.40"]
    # unmarked gather: counted only inside a serve_batch program
    assert T.kernel_ops(tr, "gather") == []
    assert [o.name for o in T.kernel_ops(tr, "gather", "serve_batch")] == \
        ["gather.2"]
    secs, n = tr.module_seconds(lambda m: "serve_batch" in m.text)
    assert (n, secs) == (1, pytest.approx(2e-6))
    top = tr.top_ops(2)
    assert top[0][0] == "fusion.1"
    assert top[0][1] == pytest.approx(4.5e-6)  # 1500 + 1000 + 2000 ns
    labels = [k for k, _ in tr.top_ops(10)]
    assert "quadconv_matmul.1 f32[4,4096] custom-call" in labels


def test_short_names_of_hlo_text():
    assert T.short_name("%fusion.12 = bf16[8,4]{1,0} fusion(%x)") == "fusion.12"
    assert T.short_name("while") == "while"


def test_missing_window_is_an_error():
    p = planes()
    p[0].lines[0].events = p[0].lines[0].events[1:]
    with pytest.raises(ValueError, match="bench.window"):
        T.reduce_planes(p)
