"""The readers of the program's spans and scopes on small constructed
traces: the split by host thread, clipping to the window, control flow
left out of device time, and no number where a span or scope is
missing."""

import threading
import time
from types import SimpleNamespace as NS

import pytest

import _paths
from bench import harness
from bench import spans as S
from bench import trace as T

METRICS = harness.BENCH_DIR / "metrics"


def ev(name, start, dur, stats=()):
    return NS(name=name, start_ns=start, duration_ns=dur, stats=list(stats))


def plane(name, lines):
    return NS(name=name, lines=[NS(name=k, events=v) for k, v in lines])


def serve_planes():
    """Window 1000..11000.  Thread 1 is the serving loop, threads 2 and 3
    clients; device 0 busy 2000..3000 only."""
    host = plane("/host:CPU", [
        ("python", [ev("bench.window", 1000, 10000),
                    ev("bench.clients", 1000, 10000)]),
        ("python", [ev("repro.serve.dispatch", 500, 400),     # before
                    ev("repro.serve.idle", 800, 400),         # 1000..1200
                    ev("repro.serve.dispatch", 1500, 500),
                    ev("repro.model_eval", 1500, 490),
                    ev("repro.store.lock", 1510, 40),
                    ev("repro.serve.idle", 2100, 300),
                    ev("repro.serve.idle", 2200, 100),        # nested: union
                    ev("repro.serve.dispatch", 3000, 1500),
                    ev("repro.retrieve", 3100, 10),           # the loop's own
                    ev("repro.serve.idle", 10500, 1000)]),    # ..11000
        ("python", [ev("repro.retrieve", 900, 50),            # before
                    ev("repro.retrieve", 4000, 100),
                    ev("repro.store.lock", 4000, 60),
                    ev("repro.retrieve", 5000, 100),
                    ev("repro.store.lock", 10950, 100)]),     # clipped: 50
        ("python", [ev("repro.retrieve", 6000, 100),
                    ev("repro.store.lock", 6000, 200),
                    ev("repro.send", 7000, 100)])])
    dev = plane("/device:TPU:0", [
        ("XLA Ops", [ev("fusion.1", 2000, 1000)]),
        ("XLA Modules", [ev("jit_serve_batch_impl(1)", 2000, 1000)])])
    return [host, dev]


def train_planes():
    """Two fused epochs in the window, launched 300 and 500 ns after
    their ``capture_epoch`` spans; the producer runs between."""
    host = plane("/host:CPU", [
        ("python", [ev("bench.window", 0, 10000),
                    ev("repro.capture_epoch", 1000, 200),
                    ev("repro.capture_epoch", 5000, 200),
                    ev("repro.capture_epoch", 9800, 100)])])  # none after
    ops = [
        ev("%while.6 = (s32[]) while(%t), body=%body", 1300, 3000),
        ev("%fusion.3 = f32[4096]{0} fusion(%a), kind=kLoop", 1300, 1000),
        ev("fusion.4", 2300, 1000),
        ev("fusion.5", 3300, 1000),
        ev("fusion.9", 4500, 400),                  # another program
        ev("%call.2 = f32[4] call(%x), to_apply=%f", 5500, 1000),
        ev("fusion.3", 5500, 1000),
        ev("fusion.6", 6500, 2000)]
    dev = plane("/device:TPU:0", [
        ("XLA Ops", ops),
        ("XLA Modules", [ev("jit_epoch(7)", 1300, 3000),
                         ev("jit_capture_scan_impl(3)", 4500, 400),
                         ev("jit_epoch(7)", 5500, 3000)])])
    return [host, dev]


#: The op names of the programs of ``train_planes``, as
#: ``spans.program_scopes`` reads them from the trace's HLO.
TRAIN_SCOPES = {
    "jit_epoch(7)": {
        "fusion.3": "jit(epoch)/while/body/quadconv.kernel_tensor/mul",
        "fusion.4": "jit(epoch)/while/body/transpose(jvp(quadconv."
                    "kernel_tensor))/dot_general",
        "fusion.5": "jit(epoch)/while/body/quadconv_matmul",
        "fusion.6": "jit(epoch)/adam/add",
        "call.2": "jit(epoch)/quadconv.kernel_tensor"},
    "jit_capture_scan_impl(3)": {
        "fusion.9": "jit(capture_scan_impl)/quadconv.kernel_tensor/mul"}}


def ctx_of(planes, monkeypatch, counters=None, scopes=None):
    sp = tuple(S.host_spans(planes))
    monkeypatch.setattr(S, "of", lambda ctx: sp)
    monkeypatch.setattr(S, "scopes_of", lambda ctx: scopes or {})
    return NS(cell=NS(name="cell"), trace=T.reduce_planes(planes),
              counters=counters or {})


def read(metric, ctx):
    path = METRICS / f"{metric}.py"
    return harness.load_module(path, "m_" + metric.replace(".", "_")).read(ctx)


def test_spans_keep_their_thread_line_and_drop_the_prefix():
    sp = S.host_spans(serve_planes())
    assert {s.name for s in sp} >= {"serve.dispatch", "serve.idle",
                                    "retrieve", "store.lock"}
    assert S.line_of(sp, "serve.dispatch") == ("/host:CPU", 1)
    assert {s.line for s in sp if s.name == "retrieve"} == {
        ("/host:CPU", 1), ("/host:CPU", 2), ("/host:CPU", 3)}
    assert S.line_of(sp, "missing") is None
    assert all(not s.name.startswith("bench.") for s in sp)


def test_serve_readers(monkeypatch):
    ctx = ctx_of(serve_planes(), monkeypatch, {"served": 4})
    # idle on the loop: 1000..1200, 2100..2400, 10500..11000 = 1000 ns
    assert read("serve_loop_wait.serve", ctx) == pytest.approx(10.0)
    # dispatches starting in the window: 500 and 1500 ns
    assert read("serve_dispatch_ms.serve", ctx) == pytest.approx(1e-3)
    # client polls in the window: 3, the loop's own left out
    assert read("serve_polls_per_response.serve", ctx) == pytest.approx(0.75)
    # lock waits clipped: 40 + 60 + 50 + 200 ns over 4 requests
    assert read("serve_lock_wait_ms.serve", ctx) == pytest.approx(
        350e-6 / 4)


def test_serve_readers_without_spans_read_nothing(monkeypatch):
    planes = serve_planes()
    planes[0].lines = planes[0].lines[:1]
    ctx = ctx_of(planes, monkeypatch, {"served": 4})
    for m in ("serve_loop_wait.serve", "serve_dispatch_ms.serve",
              "serve_polls_per_response.serve", "serve_lock_wait_ms.serve"):
        assert read(m, ctx) is None, m
    ctx = ctx_of(serve_planes(), monkeypatch, {"served": 0})
    assert read("serve_polls_per_response.serve", ctx) is None
    assert read("serve_lock_wait_ms.serve", ctx) is None


def test_loop_wait_reads_zero_when_the_loop_never_slept(monkeypatch):
    planes = serve_planes()
    loop = planes[0].lines[1]
    loop.events = [e for e in loop.events if e.name != "repro.serve.idle"]
    assert read("serve_loop_wait.serve", ctx_of(planes, monkeypatch)) == 0.0


def test_train_launch(monkeypatch):
    ctx = ctx_of(train_planes(), monkeypatch)
    # 1000 -> 1300 and 5000 -> 5500; the last call has no epoch after it
    assert read("train_launch_ms.train", ctx) == pytest.approx(400e-6)
    planes = train_planes()
    planes[0].lines[0].events = planes[0].lines[0].events[:1]
    assert read("train_launch_ms.train", ctx_of(planes, monkeypatch)) is None


def test_scope_share_leaves_out_containers_and_other_programs(monkeypatch):
    ctx = ctx_of(train_planes(), monkeypatch, scopes=TRAIN_SCOPES)
    ops = S.program_ops(ctx.trace, "epoch")
    assert [(o.name, m) for o, m in ops] == [
        ("fusion.3", "jit_epoch(7)"), ("fusion.4", "jit_epoch(7)"),
        ("fusion.5", "jit_epoch(7)"), ("fusion.3", "jit_epoch(7)"),
        ("fusion.6", "jit_epoch(7)")]
    # under the scope 3 x 1000 ns of 4 x 1000 + 2000 (fusion.6 ends at
    # 8500, with the second epoch); the while and the call left out
    assert read("train_kernel_tensor_share.train", ctx) == \
        pytest.approx(50.0)
    tr = ctx.trace
    assert S.scope_share(tr, TRAIN_SCOPES, "store.put", "epoch") is None
    assert S.scope_share(tr, TRAIN_SCOPES, "quadconv.kernel_tensor",
                         "capture_scan_impl") == pytest.approx(100.0)
    assert S.scope_share(tr, TRAIN_SCOPES, "quadconv.kernel_tensor",
                         "capture_scan") is None
    # no HLO for the program: nothing is under any scope
    assert read("train_kernel_tensor_share.train",
                ctx_of(train_planes(), monkeypatch)) is None


def test_store_put_share(monkeypatch):
    host = plane("/host:CPU", [("python", [ev("bench.window", 0, 5000)])])
    dev = plane("/device:TPU:0", [
        ("XLA Ops", [
            ev("%while.7 = (s32[]) while(%t)", 100, 4000),
            ev("%conditional.2 = () conditional(%p)", 200, 300),
            ev("fusion.377", 200, 900),
            ev("fusion.379", 1100, 100),
            ev("fusion.1", 4600, 300)]),            # outside the program
        ("XLA Modules", [ev("jit_capture_scan_multi_impl(2)", 100, 4000)])])
    scopes = {"jit_capture_scan_multi_impl(2)": {
        "fusion.377": "jit(capture_scan_multi_impl)/while/body/vmap(sin)",
        "fusion.379": "jit(capture_scan_multi_impl)/while/body/cond/"
                      "branch_1_fun/store.put/scatter",
        "conditional.2": "jit(capture_scan_multi_impl)/while/body/cond",
        "fusion.1": "store.put"}}
    ctx = ctx_of([host, dev], monkeypatch, scopes=scopes)
    assert read("store_put_share.capture", ctx) == pytest.approx(10.0)
    del scopes["jit_capture_scan_multi_impl(2)"]["fusion.379"]
    assert read("store_put_share.capture", ctx) is None


def test_program_scopes_read_from_a_real_trace(tmp_path, monkeypatch):
    """The profiler's HLO of a jitted function: each instruction's
    ``op_name``, the named scope in it, by the program's name."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def scoped(x):
        with jax.named_scope("store.put"):
            y = jnp.sin(x) * 2.0
        return jnp.cos(y)

    x = jnp.ones((8, 8))
    jax.profiler.start_trace(str(tmp_path / "cell"))
    try:
        scoped(x).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    monkeypatch.setattr(harness, "trace_dir",
                        lambda root, workload: tmp_path / workload)
    S._load_scopes.cache_clear()
    try:
        scopes = S.scopes_of(NS(cell=NS(name="cell")))
    finally:
        S._load_scopes.cache_clear()
    names = [v for k, v in scopes.items() if k.startswith("jit_scoped(")]
    assert len(names) == 1
    paths = set(names[0].values())
    assert any("store.put/sin" in p for p in paths)
    assert any(p.endswith("/cos") and "store.put" not in p for p in paths)


def test_program_pattern_matches_whole_names():
    pat = S.program_pattern("epoch")
    assert pat.search("jit_epoch(7)")
    assert not pat.search("jit_epoch_sharded(7)")
    assert not pat.search("jit_per_epoch(7)")


def test_of_reads_a_real_trace_once_per_process(tmp_path, monkeypatch):
    """Spans of two threads in a real CPU trace, found under the cell's
    trace directory and parsed once."""
    import jax
    monkeypatch.setattr(harness, "trace_dir",
                        lambda root, workload: tmp_path / workload)
    S._load.cache_clear()

    def other():
        with jax.profiler.TraceAnnotation("repro.retrieve"):
            time.sleep(0.002)

    jax.profiler.start_trace(str(tmp_path / "cell"))
    try:
        with jax.profiler.TraceAnnotation("repro.serve.dispatch"):
            t = threading.Thread(target=other)
            t.start()
            t.join(5)
    finally:
        jax.profiler.stop_trace()
    ctx = NS(cell=NS(name="cell"))
    sp = S.of(ctx)
    assert sorted(s.name for s in sp) == ["retrieve", "serve.dispatch"]
    assert len({s.line for s in sp}) == 2
    assert S.of(ctx) is sp
    S._load.cache_clear()


def test_a_traced_serving_run_reads_the_host_span_metrics():
    """A whole traced run of the serving cell, cut to the CPU: the
    readers find the program's spans in the trace the harness wrote.
    (The CPU trace has no TPU plane: device time is not measured.)"""
    import shutil
    import _tiny
    c = _tiny.cell("resnet50.serve")
    bench = harness.load_benchmark(_paths.ROOT)
    S._load.cache_clear()
    try:
        res = harness.run_cell(_paths.ROOT, bench, c, seed=2 ** 31 + 11,
                               seconds=1.0, trace=True, device=_tiny.DEVICE,
                               t_start=time.perf_counter())
    finally:
        shutil.rmtree(harness.trace_dir(_paths.ROOT, c.name),
                      ignore_errors=True)
        S._load.cache_clear()
    assert res.correct
    m = res.metrics
    for name in ("serve_loop_wait.serve", "serve_dispatch_ms.serve",
                 "serve_polls_per_response.serve",
                 "serve_lock_wait_ms.serve"):
        assert name in m, name
        assert m[name]["value"] >= 0
    assert 0 <= m["serve_loop_wait.serve"]["value"] <= 100
    # every response takes at least one poll
    assert m["serve_polls_per_response.serve"]["value"] >= 1
