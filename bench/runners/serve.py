"""Runner of the ``serve`` kind: simulation ranks asking the store for
inference.

``clients`` host threads stand for simulation ranks.  A rank waits for
its answer before it sends again: each client sends its next request
``think_s`` after its previous response is in hand.  A
request is one image ``put_kv`` into the request ring plus the client's
``submitted`` watermark; the client then polls the results ring for its
own key with the program's backoff.  One ``ServeLoop`` thread drains the
request table in continuous batches of up to ``max_batch``
(``store.serve_batch``: probe and gather kernels, a vmapped forward, a
masked put).

Latency runs from when a request was due to when its client holds the
response.  The window's requests are those due in it; the ones still open
at its close are waited for.

Check: a sample of the window's requests, drawn from the seed, against a
plain ResNet-50 forward, at the precision the configuration states, of
the image each client sent; and every request of the window answered,
within ``poll_timeout_s`` of being sent.
"""

from __future__ import annotations

import threading
import time

import numpy as np

from .. import compare
from .. import precision as P
from .. import generator as gen
from .. import harness
from ..harness import Check, Outcome, span

REQUESTS = "requests"
RESPONSES = "responses"
MODEL = "resnet50"


def _apply(params, x):
    """The registry's single-image model: [3, H, W] -> logits."""
    from repro.ml.resnet import apply_resnet50
    return apply_resnet50(params, x[None])[0]


class Client:
    """One simulation rank: a send, its own response polled, the next send."""

    def __init__(self, cell, c: int):
        from repro.core.client import Client as StoreClient
        self.cell, self.c = cell, c
        self.store = StoreClient(cell.server, rank=c)
        self.seq = 0
        self.records: list[tuple[int, float, float, float]] = []
        self.responses: dict[int, object] = {}
        self.unanswered: list[int] = []
        self.error: BaseException | None = None

    def request(self, s: int, due: float) -> None:
        from repro.core.telemetry import poll_backoff
        from repro.serve.engine import request_key, submitted_meta
        cell = self.cell
        key = request_key(self.c, s)
        x = cell.pool[self.c][s % len(cell.pool[self.c])]
        self.store.put_kv(REQUESTS, key, x)
        cell.server.put_meta(submitted_meta(REQUESTS, self.c), s + 1)
        for _ in poll_backoff(cell.poll_timeout, 1e-4, 0.01):
            y, found = self.store.get_kv(RESPONSES, key)
            if bool(found):
                y.block_until_ready()
                done = time.perf_counter()
                self.records.append((s, due, done, done - due))
                if cell.keep_responses:
                    self.responses[s] = y
                return
        self.unanswered.append(s)

    def run(self, start: float, close: float) -> None:
        """Send every request due in ``[start, close)`` (sequence ids go
        on from the previous round)."""
        ready = start
        try:
            while True:
                due = gen.send_time(self.cell.tf, ready)
                if due >= close:
                    return
                wait = due - time.perf_counter()
                if wait > 0:
                    time.sleep(wait)
                self.request(self.seq, due)
                ready = time.perf_counter()
                self.seq += 1
        except BaseException as exc:      # reported by the runner
            self.error = exc


class ServeCell:
    def __init__(self, ctx: harness.Context):
        import jax
        from repro.core import TableSpec
        from repro.core.client import Client as StoreClient
        from repro.core.server import StoreServer
        from repro.serve.engine import ServeLoop

        cfg, tf = ctx.cell.cfg, ctx.cell.traffic
        self.ctx, self.cfg, self.tf = ctx, cfg, tf
        self.clients = tf["clients"]
        self.poll_timeout = tf["poll_timeout_s"]
        self.keep_responses = True
        self.server = StoreServer()
        self.server.create_table(TableSpec(
            REQUESTS, shape=tuple(cfg["image"]),
            capacity=tf["request_slots"], engine="ring"))
        self.server.create_table(TableSpec(
            RESPONSES, shape=(cfg["classes"],),
            capacity=tf["response_slots"], engine="ring"))
        self.params = harness.reference(ctx.cell).init_params(
            cfg, gen.weights_key(ctx.seed))
        self.server.set_model(MODEL, _apply, self.params)
        pool = gen.images(ctx.seed, tf, cfg["image"])
        # one device array per image, so that a send slices nothing
        self.pool = [[pool[c, i] for i in range(pool.shape[1])]
                     for c in range(self.clients)]
        jax.block_until_ready(self.pool)
        self.loop = ServeLoop(
            StoreClient(self.server, rank=self.clients), model_key=MODEL,
            request_table=REQUESTS, response_table=RESPONSES,
            clients=self.clients, requests=tf["max_requests_per_client"],
            max_batch=tf["max_batch"])
        self.client = [Client(self, c) for c in range(self.clients)]
        self._stop = threading.Event()
        self.loop_error: BaseException | None = None
        self._thread = threading.Thread(target=self._serve, daemon=True)
        self._thread.start()

    def _serve(self) -> None:
        try:
            self.loop.run(stop_event=self._stop, timeout=self.poll_timeout)
        except BaseException as exc:      # reported by the runner
            self.loop_error = exc

    def round(self, seconds: float, name: str):
        """All clients send what is due in the next ``seconds``; returns
        (start, close) after every request of the round is answered."""
        start = time.perf_counter() + 0.01
        close = start + seconds
        threads = [threading.Thread(target=c.run, args=(start, close),
                                    daemon=True) for c in self.client]
        with span(name):
            for t in threads:
                t.start()
            for t in threads:
                t.join(close - time.perf_counter() + self.poll_timeout)
        if any(t.is_alive() for t in threads):
            raise harness.BenchError("a client did not finish its round")
        for c in self.client:
            if c.error is not None:
                raise harness.BenchError(f"client {c.c}: {c.error!r}")
        if self.loop_error is not None:
            raise harness.BenchError(f"serving loop: {self.loop_error!r}")
        return start, close

    def stop(self) -> None:
        self._stop.set()
        self._thread.join(self.poll_timeout)
        if self._thread.is_alive():
            raise harness.BenchError("the serving loop did not stop")


def readings(got: list[np.ndarray], ref: list[np.ndarray]) -> dict[str, float]:
    """The worst sampled response's gap from the reference logits."""
    return {"logit_gap": compare.max_rel_row_gap(np.stack(got),
                                                 np.stack(ref))}


def sample(ctx: harness.Context, done: list[tuple[int, int]], n: int):
    """``n`` of the window's answered (client, seq), drawn from the seed."""
    rng = np.random.default_rng([ctx.seed % 2 ** 32, ctx.seed // 2 ** 32, 7])
    pick = rng.choice(len(done), size=min(n, len(done)), replace=False)
    return [done[i] for i in sorted(pick)]


def reference_logits(ctx: harness.Context, picked,
                     arith: str | None = None) -> list[np.ndarray]:
    """The reference's logits of the picked requests' images, in
    ``arith`` (by default the precision the configuration states)."""
    import jax.numpy as jnp
    cfg, tf = ctx.cell.cfg, ctx.cell.traffic
    ref = harness.reference(ctx.cell)
    pool = gen.images(ctx.seed, tf, cfg["image"])
    x = jnp.stack([pool[c, s % tf["images_per_client"]] for c, s in picked])
    params = ref.init_params(cfg, gen.weights_key(ctx.seed))
    out = np.asarray(ref.make_forward(cfg, arith or P.stated(cfg))(params, x))
    return list(out)


def run(ctx: harness.Context) -> Outcome:
    tf = ctx.cell.traffic
    cell = ServeCell(ctx)
    cell.round(tf["warmup_s"], "warmup")
    setup_s = ctx.setup_seconds()
    for c in cell.client:
        c.records.clear()
        c.responses.clear()
        c.unanswered.clear()

    served0, batches0 = cell.loop.served, cell.loop.batches
    with ctx.window():
        start, close = cell.round(ctx.seconds, "clients")
    served, batches = cell.loop.served - served0, cell.loop.batches - batches0
    cell.stop()
    peak = harness.memory_peak_bytes(ctx.cell.chips)

    lat = np.array([r[3] for c in cell.client for r in c.records])
    in_window = sum(1 for c in cell.client for r in c.records if r[2] <= close)
    done = [(c.c, r[0]) for c in cell.client for r in c.records]
    picked = sample(ctx, done, tf["check_sample"])
    got = [np.asarray(cell.client[c].responses[s]) for c, s in picked]
    unanswered = sum(len(c.unanswered) for c in cell.client)
    attempted = len(done) + unanswered
    cell = None

    ref = reference_logits(ctx, picked)
    limits = harness.limits(ctx.cell)
    checks = [Check(k, v, limits[k]) for k, v in readings(got, ref).items()]
    checks.append(Check("unanswered", float(unanswered), limits["unanswered"]))
    q = np.percentile(lat, [50, 95]) * 1e3
    return Outcome(
        setup_s=setup_s,
        e2e={"serve_req_per_s": in_window / (close - start)},
        attempted=attempted, failed=unanswered, checks=checks,
        counters={"served": served, "batches": batches,
                  "elapsed_s": close - start, "p50_ms": float(q[0]),
                  "p95_ms": float(q[1]),
                  "max_batch": tf["max_batch"],
                  "image": ctx.cell.cfg["image"],
                  "classes": ctx.cell.cfg["classes"],
                  "request_slots": tf["request_slots"]},
        memory_peak_bytes=peak)


def _program_responses(ctx: harness.Context):
    tf = ctx.cell.traffic
    cell = ServeCell(ctx)
    cell.round(ctx.seconds, "clients")
    cell.stop()
    done = [(c.c, r[0]) for c in cell.client for r in c.records]
    picked = sample(ctx, done, tf["check_sample"])
    return picked, [np.asarray(cell.client[c].responses[s])
                    for c, s in picked]


def calibrate(ctx: harness.Context, full: bool = True) -> dict[str, dict]:
    """Readings of the program after a short round against the reference
    at the stated precision, and of the reference logits put in its place
    in bfloat16 (the control).  ``full`` adds a second witness, the program
    with every convolution at precision HIGHEST against the float32
    reference, and the program against that reference."""
    import jax
    picked, got = _program_responses(ctx)
    ref = reference_logits(ctx, picked)
    out = {"program": readings(got, ref),
           "control_bfloat16": readings(
               reference_logits(ctx, picked, "bfloat16"), ref)}
    if full:
        # the serving loop runs in its own thread: set the precision for
        # every thread, not in a context that only this one sees
        jax.config.update("jax_default_matmul_precision", "highest")
        try:
            high_picked, high = _program_responses(ctx)
        finally:
            jax.config.update("jax_default_matmul_precision", None)
        out["program_highest_vs_float32"] = readings(
            high, reference_logits(ctx, high_picked, "float32"))
        out["program_vs_float32"] = readings(
            got, reference_logits(ctx, picked, "float32"))
    return out
