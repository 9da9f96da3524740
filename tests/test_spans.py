"""The program's spans and named scopes, on the profiler's clock.

* ``Timers.time`` opens ``repro.<name>`` over exactly the interval it
  records, read back from a real ``jax.profiler`` trace on the CPU;
* a table lock's span covers the wait for it, on the waiting thread;
* ``poll_backoff`` spans its sleeps only; the serving loop spans its
  dispatches and idle sleeps; it keeps no retired requests;
* the fused epoch (its gradient too), ``capture_scan_multi`` and
  ``serve_batch`` carry the named scopes ``quadconv.kernel_tensor`` and
  ``store.put`` in their lowered programs.
"""

import glob
import os
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import Client, StoreServer, TableSpec
from repro.core import store as S
from repro.core.telemetry import SpanLock, Timers, poll_backoff, span
from repro.serve.engine import ServeLoop, request_key, submitted_meta


def _traced(tmp_path, fn):
    """Run ``fn`` under the profiler; return the host spans named
    ``repro.*`` as (name, start_ns, end_ns, line index)."""
    jax.profiler.start_trace(str(tmp_path))
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(tmp_path, "**", "*.xplane.pb"),
                      recursive=True)
    out = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:"):
            continue
        for i, line in enumerate(plane.lines):
            for e in line.events:
                if e.name.startswith("repro."):
                    out.append((e.name, e.start_ns, e.start_ns
                                + e.duration_ns, (plane.name, i)))
    return out


def test_timers_time_is_a_span_over_what_it_records(tmp_path):
    timers = Timers()
    x = jnp.ones((64, 64))

    def work():
        with timers.time("retrieve") as box:
            time.sleep(0.02)
            box[0] = x @ x            # the payload block is inside too
        with span("other", n=3):
            pass

    spans = _traced(tmp_path, work)
    got = [s for s in spans if s[0] == "repro.retrieve"]
    assert len(got) == 1
    dur = (got[0][2] - got[0][1]) * 1e-9
    recorded = timers.stats("retrieve").total
    assert recorded >= 0.02
    # the span encloses the two clock reads and nothing else
    assert recorded <= dur < recorded + 2e-3
    assert [s[0] for s in spans].count("repro.other") == 1


def test_span_lock_spans_the_wait_on_the_waiting_thread(tmp_path):
    lock = SpanLock("store.lock")
    held = threading.Event()

    def holder():
        with lock:
            held.set()
            time.sleep(0.05)

    def work():
        t = threading.Thread(target=holder)
        t.start()
        held.wait(5)
        with lock:
            pass
        t.join(5)
        assert not t.is_alive()

    spans = _traced(tmp_path, work)
    waits = sorted(spans, key=lambda s: s[2] - s[1])
    assert [s[0] for s in waits] == ["repro.store.lock"] * 2
    quick, slow = waits
    assert quick[3] != slow[3]                   # two threads
    assert (slow[2] - slow[1]) * 1e-9 > 0.03     # waited for the holder
    assert (quick[2] - quick[1]) * 1e-9 < 0.01   # took a free lock
    assert lock.acquire(blocking=False)
    lock.release()


def test_poll_backoff_spans_each_sleep_only(tmp_path):
    probes = []

    def work():
        for _ in poll_backoff(0.03, 1e-3, 4e-3, sleep_span="wait"):
            probes.append(time.perf_counter())

    spans = _traced(tmp_path, work)
    assert len(probes) >= 3
    assert [s[0] for s in spans] == ["repro.wait"] * (len(probes) - 1)
    assert list(poll_backoff(0.0, 1e-3, 1e-3)) == [None]


def _serving(clients=2, requests=3):
    server = StoreServer()
    for name in ("req", "res"):
        server.create_table(TableSpec(name, shape=(2, 4), capacity=16,
                                      engine="ring"))
    server.set_model("m", lambda p, x: p * x + 1.0, jnp.asarray(2.0))
    client = Client(server)
    loop = ServeLoop(client, model_key="m", request_table="req",
                     response_table="res", clients=clients,
                     requests=requests, max_batch=2)
    return server, client, loop


def test_serving_loop_keeps_no_retired_requests():
    server, client, loop = _serving(clients=2, requests=4)
    for s in range(4):
        for c in range(2):
            client.put_kv("req", request_key(c, s), jnp.ones((2, 4)))
            server.put_meta(submitted_meta("req", c), s + 1)
    loop.run(timeout=30.0)
    assert (loop.served, loop.batches) == (8, 4)
    assert loop.batcher.completed == []
    loop.recover()
    assert loop.batcher.completed == [] and loop.batcher.idle


def test_serving_loop_spans_its_dispatches_and_idle_sleeps(tmp_path):
    server, client, loop = _serving()

    def submit_later():
        for s in range(3):
            time.sleep(0.02)
            for c in range(2):
                client.put_kv("req", request_key(c, s),
                              jnp.full((2, 4), float(10 * c + s)))
                server.put_meta(submitted_meta("req", c), s + 1)

    def work():
        t = threading.Thread(target=submit_later)
        t.start()
        loop.run(timeout=30.0)
        t.join(5)
        assert not t.is_alive()

    spans = _traced(tmp_path, work)
    assert loop.served == 6
    names = [s[0] for s in spans]
    dispatch = [s for s in spans if s[0] == "repro.serve.dispatch"]
    assert len(dispatch) == loop.batches
    idle = [s for s in spans if s[0] == "repro.serve.idle"]
    assert idle, "the loop found nothing at first, so it slept"
    loop_line = {s[3] for s in dispatch}
    assert len(loop_line) == 1 and {s[3] for s in idle} == loop_line
    # dispatch and sleeps never overlap on the loop's thread
    for d in dispatch:
        assert all(i[2] <= d[1] or i[1] >= d[2] for i in idle)
    assert "repro.model_eval" in names and "repro.store.lock" in names
    y, found = client.get_kv("res", request_key(1, 2))
    assert bool(found)
    np.testing.assert_array_equal(np.asarray(y), 2.0 * 12.0 + 1.0)


def test_put_and_serve_batch_carry_the_store_put_scope():
    spec = TableSpec("t", shape=(2, 4), capacity=8, engine="ring")
    st = S.init_table(spec)

    def step(carry, rank, t):
        return carry, S.make_key(rank, t), jnp.full((2, 4), 1.0) * t

    text = S.capture_scan_multi.lower(
        spec, st, step, jnp.zeros((3,)), 4, 3, 1).as_text(debug_info=True)
    assert "store.put" in text
    res = TableSpec("r", shape=(2, 4), capacity=8, engine="ring")
    text = S.serve_batch.lower(
        spec, res, lambda p, x: p * x, st, S.init_table(res),
        jnp.asarray(2.0), jnp.zeros((2,), jnp.uint32),
        jnp.ones((2,), bool)).as_text(debug_info=True)
    assert "store.put" in text
    # the scope is debug metadata: the program text without it has none
    assert "store.put" not in S.serve_batch.lower(
        spec, res, lambda p, x: p * x, st, S.init_table(res),
        jnp.asarray(2.0), jnp.zeros((2,), jnp.uint32),
        jnp.ones((2,), bool)).as_text()


def test_fused_epoch_and_its_gradient_carry_the_kernel_tensor_scope():
    from repro.ml import autoencoder as ae
    from repro.ml import trainer as tr
    from repro.sim import flatplate as fp
    from repro.train import optimizer as opt

    fcfg = fp.FlatPlateConfig(nx=8, ny=8, nz=4)
    n = fcfg.n_points
    spec = TableSpec("field", shape=(4, n), capacity=8, engine="ring")
    st = S.init_table(spec)
    aecfg = ae.AEConfig(n_points=n, mode="ref", latent=16, mlp_width=16)
    levels = ae.coords_pyramid(aecfg, fp.grid_coords(fcfg))
    tx = opt.adam(1e-3)
    cfg = tr.TrainerConfig(ae=aecfg, gather=4, batch_size=2, lr=1e-3)
    state0 = tr.init_state(cfg, jax.random.key(0), tx)
    epoch = tr.make_fused_epoch(cfg, levels, tx, spec)
    text = epoch.lower(st, state0, jax.random.key(1), jnp.zeros((4,)),
                       jnp.ones((4,))).as_text(debug_info=True)
    assert "quadconv.kernel_tensor" in text
    assert "transpose(jvp(quadconv.kernel_tensor))" in text


def _cos_of_scaled_sin(scope: str | None):
    def f(x):
        if scope is None:
            y = jnp.sin(x) * 2.0
        else:
            with jax.named_scope(scope):
                y = jnp.sin(x) * 2.0
        return jnp.cos(y)
    return f


def test_compile_cache_keys_on_named_scopes(tmp_path, monkeypatch):
    """A program that differs from a cached one only in a named scope
    compiles anew, so the profile names the scope; by default JAX would
    load the cached executable and its metadata."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    from repro.launch.cache import configure_compile_cache
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    flags = ("jax_enable_compilation_cache", "jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs",
             "jax_persistent_cache_min_entry_size_bytes",
             "jax_hlo_source_file_canonicalization_regex",
             "jax_compilation_cache_include_metadata_in_key")
    was = {k: getattr(jax.config, k) for k in flags}
    try:
        jax.config.update("jax_enable_compilation_cache", True)
        assert configure_compile_cache(tmp_path) == str(tmp_path /
                                                        ".jax_cache")
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
        cc.reset_cache()
        x = jnp.ones((8, 8))
        plain = jax.jit(_cos_of_scaled_sin(None)).lower(x).compile()
        assert "store.put" not in plain.as_text()
        assert os.listdir(tmp_path / ".jax_cache")
        scoped = jax.jit(_cos_of_scaled_sin("store.put")).lower(x).compile()
        assert "store.put" in scoped.as_text()
    finally:
        for k, v in was.items():
            jax.config.update(k, v)
        cc.reset_cache()
