"""Client: the SmartRedis-verb API (paper §2.2).

One ``Client`` per producer/consumer rank.  Mirrors the SmartRedis surface
the paper leans on ("a single call … each requiring a single line of code"):

    client = Client(server, rank=3)
    client.put_tensor("x.3.120", x)                     # named put
    client.send_step("field", step=120, value=x)        # rank/step-keyed put
    y, ok = client.get_tensor("x.3.120")
    client.poll_tensor("x.3.120", timeout=10.0)
    client.set_model("encoder", apply_fn, params)
    client.run_model("encoder", inputs=["x.3.120"], outputs=["z.3.120"])
    z, _ = client.get_tensor("z.3.120")

plus the fused ``infer`` fast path (beyond-paper: one dispatch instead of the
paper's three-step send/run/retrieve) and the consumer-side batch loaders.

Every verb is timed into the paper's component buckets:
``client_init`` / ``metadata`` / ``send`` / ``retrieve`` / ``model_eval``.
"""

from __future__ import annotations

import contextlib
import time
from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp

from . import store as S
from .deployment import StagingPipeline
from .faults import StoreTimeout, TransferDropped, call_with_retry
from .server import StoreServer
from .telemetry import Timers, poll_backoff, span

__all__ = ["Client"]


class Client:
    def __init__(self, server: StoreServer, rank: int = 0,
                 timers: Timers | None = None):
        t0 = time.perf_counter()
        self.server = server
        self.rank = int(rank)
        self.timers = timers or Timers()
        #: fault-tolerance telemetry, surfaced through ComponentResult:
        #: verb retries absorbed, restarts survived, straggler events seen.
        self.retries = 0
        self.restarts = 0
        self.straggler_events = 0
        self._seq = 0            # next fused-chunk sequence number
        # two-slot overlap pipelines, one per table (clustered fused tier)
        self._staging: dict[str, StagingPipeline] = {}
        # "Client initialization" = establishing the connection in the paper;
        # here: binding the server reference and warming the key hasher.
        S.name_key("__warmup__")
        self.timers.record("client_init", time.perf_counter() - t0)

    # -- fault boundary --------------------------------------------------------

    def _count_retry(self) -> None:
        self.retries += 1
        self.server._bump_retry()

    def _call_verb(self, verb: str, table: str | None, call):
        """Route one store verb through the fault boundary: the server's
        injector (if armed) sees one attempt per call, transient
        ``StoreUnavailable`` windows are absorbed by the plan's
        ``RetryPolicy`` (bounded, jittered, deadline-clamped backoff), and
        every absorbed retry is counted on both the client and the server.
        Without a ``FaultPlan`` this is a plain call — zero overhead."""
        inj = self.server.faults
        if inj is None:
            return call()

        def attempt():
            inj.on_verb(verb, table)
            return call()

        return call_with_retry(attempt, inj.retry, self._count_retry)

    def fault_point(self, component: str, idx: int) -> None:
        """A declared crash point: raises
        :class:`~repro.core.faults.InjectedCrash` exactly once if the plan
        says ``component`` dies at ``idx`` (the caller's restart loop
        catches it and resumes from the watermark / checkpoint)."""
        inj = self.server.faults
        if inj is not None:
            inj.maybe_crash(component, idx)

    # -- named tensors ---------------------------------------------------------

    def put_tensor(self, name: str, value, table: str = "default") -> None:
        with self.timers.time("send", payload=value):
            self._call_verb("put", table,
                            lambda: self.server.put(table, S.name_key(name),
                                                    value))

    def get_tensor(self, name: str, table: str = "default"):
        with self.timers.time("retrieve") as box:
            value, found = self.server.get(table, S.name_key(name))
            box[0] = value
        return value, found

    def delete_tensor(self, name: str, table: str = "default") -> None:
        self.server.delete(table, S.name_key(name))

    def poll_tensor(self, name: str, table: str = "default",
                    timeout: float = 10.0, interval: float = 0.001,
                    max_interval: float = 0.05, strict: bool = True) -> bool:
        """Poll until the key exists (SmartRedis ``poll_tensor``).

        Each probe dispatches one device op, so the spin uses exponential
        backoff (``interval`` doubling up to ``max_interval``) instead of a
        fixed-rate busy loop hammering the dispatch queue.  On timeout
        raises :class:`~repro.core.faults.StoreTimeout` naming the tensor
        and the deadline; ``strict=False`` restores the old silent-False
        contract for callers probing optional keys.
        """
        key = S.name_key(name)
        with self.timers.time("metadata"):
            for _ in poll_backoff(timeout, interval, max_interval):
                if self.server.poll(table, key):
                    return True
            if strict:
                raise StoreTimeout("tensor", name, timeout,
                                   f"table {table!r}")
            return False

    # -- rank/step-keyed streaming (the simulation path) ------------------------

    def send_step(self, table: str, step: int, value) -> None:
        """Send this rank's contribution of one time step (unique key per
        rank and step, exactly the paper's keying scheme)."""
        with self.timers.time("send", payload=value):
            self._call_verb(
                "put", table,
                lambda: self.server.put(table, S.make_key(self.rank, step),
                                        value))

    def put_kv(self, table: str, key, value) -> None:
        """Pre-made-key put through the fault boundary (the session's
        per-verb producer path — retried on transient unavailability)."""
        with self.timers.time("send", payload=value):
            self._call_verb("put", table,
                            lambda: self.server.put(table, key, value))

    def get_kv(self, table: str, key):
        """Pre-made-key get through the fault boundary (the serving
        clients' response poll — retried on transient unavailability).
        Returns ``(value, found)``."""
        with self.timers.time("retrieve") as box:
            value, found = self._call_verb(
                "get", table, lambda: self.server.get(table, key))
            box[0] = value
        return value, found

    def serve_batch(self, req_table: str, res_table: str, keys, mask,
                    apply_fn, params):
        """One continuous-batching drain through the fault boundary: the
        fused gather → model → scatter dispatch
        (``StoreServer.serve_batch``) under a stable chunk id, so a
        dropped response transfer is retried under the SAME id and the
        server's ack set keeps the insert exactly-once.  Returns the
        per-slot served flags."""
        inj = self.server.faults
        chunk_id = None
        if self.server.wal_enabled:
            chunk_id = (self.rank, self._seq)
            self._seq += 1
        with self.timers.time("model_eval") as box:
            def attempt():
                if inj is not None:
                    inj.on_verb("serve", res_table)
                return self.server.serve_batch(req_table, res_table, keys,
                                               mask, apply_fn, params,
                                               chunk_id=chunk_id)

            if inj is None:
                ok = attempt()
            else:
                ok = call_with_retry(attempt, inj.retry, self._count_retry)
            box[0] = ok
        return ok

    def retrieve_step(self, table: str, rank: int, step: int):
        with self.timers.time("retrieve") as box:
            value, found = self.server.get(table, S.make_key(rank, step))
            box[0] = value
        return value, found

    def send_batch(self, table: str, step: int, values, ranks=None) -> None:
        """Vectorized send of many ranks' contributions in one dispatch."""
        n = values.shape[0]
        ranks = jnp.arange(n) if ranks is None else jnp.asarray(ranks)
        keys = S.make_key(ranks, jnp.full((n,), step))
        with self.timers.time("send", payload=values):
            self.server.put_many(table, keys, values)

    # -- fused-capture fast path --------------------------------------------------

    @contextlib.contextmanager
    def capture(self, table: str = "default"):
        """Fused in-situ capture transaction (beyond-paper fast path).

        Yields the server's :class:`~repro.core.server.CaptureTxn` under
        the table's lock: dispatch ONE fused op (``store.capture_scan`` /
        ``store.sample_and_step`` / a fused epoch) against ``txn.state``,
        assign the result back, set ``txn.puts`` — then block on outputs
        after the ``with`` exits.  Replaces O(steps) per-verb calls with
        one dispatch and one lock round-trip.
        """
        with self.server.capture(table) as txn:
            yield txn

    def capture_scan(self, table: str, step_fn, carry, length: int,
                     emit_every: int = 1, t0=0, n_ranks: int | None = None,
                     bucket: bool = False, elem_sharding=None):
        """Fold ``length`` producer steps + their ring puts into ONE
        dispatch under one table-lock round-trip (the fused producer tier).

        ``n_ranks=None``: the single-producer form —
        ``step_fn(carry, t) -> (carry, key, value)``.  With ``n_ranks=R``
        the multi-producer form: ``step_fn(carry_r, rank, t)`` is vmapped
        over the leading ``[R]`` axis of ``carry`` and every emitting step
        interleaves all R snapshots into the ring (see
        ``store.capture_scan_multi``).  ``t0`` is an int or (multi-
        producer) a *concrete* per-rank ``[R]`` array of clock offsets —
        the put count is computed on the host from rank 0's clock, so a
        non-int ``t0`` costs one blocking read here; the cached watermark
        is bumped by the exact static put count.  Returns the new carry
        (the dispatch is async — block on it or on a later read when
        ordering matters).

        ``bucket=True`` pads the chunk to its power-of-two bucket
        (``store.bucket_length``) with traced-masked no-op steps, so a
        driver whose tail chunk is shorter than its body chunk reuses one
        executable per (table, bucket) instead of compiling every distinct
        tail length (the scan runs ``bucket_length(length)`` iterations;
        only the first ``length`` advance the carry or the table).

        Under a *clustered* deployment the whole chunk still costs ONE
        interconnect hop: the steps run collect-only on the client side
        (``store.capture_scan_collect[_multi]``), the stacked chunk is
        staged onto the store mesh in one batched reshard
        (``StoreServer.stage_chunk`` — counted in
        ``stats()["staged_transfers"]``), and one ``store.put_masked``
        dispatch inserts it — instead of the per-element ``device_put``
        the per-verb tier pays.

        ``elem_sharding`` (a ``NamedSharding`` over the element dims, or
        ``None``) pins every emitted value to the producer's own layout —
        a domain-decomposed solver's snapshot is put **shard-local**, the
        ``capture_scan_sharded`` tier of ``insitu.plan``.
        """
        spec = self.server.spec(table)
        t0_gate = int(jnp.reshape(jnp.asarray(t0), (-1,))[0]) \
            if not isinstance(t0, int) else t0
        padded, valid = length, None
        if bucket:
            padded = S.bucket_length(length)
            valid = jnp.asarray(length, jnp.int32)
        dep = self.server.deployment
        staged = dep is not None and dep.crosses_mesh
        # The put-count accounting is deployment-independent — one source,
        # whichever branch dispatches below.
        puts = S.capture_emit_count(length, emit_every, t0_gate) \
            if n_ranks is None else S.capture_emit_count_multi(
                n_ranks, length, emit_every, t0_gate)
        # Crossing deployments must go collect → stage → masked-insert; an
        # armed FaultPlan routes every deployment through the same logged
        # path, because exactly-once needs the chunk boundary: the chunk
        # gets a stable (rank, seq) id — the SAME id on every retry, a NEW
        # id per chunk — that the server's ack set deduplicates, and the
        # applied chunk lands in the WAL for replay after a store restart.
        logged = staged or self.server.wal_enabled
        with self.timers.time("send"):
            if logged:
                chunk_id = (self.rank, self._seq)
                self._seq += 1
                inj = self.server.faults
                # Two-slot overlap: stage this chunk's reshard async, then
                # insert the PREVIOUS chunk (whose transfer has had a full
                # collect-duration to land).  Serial order is preserved —
                # inserts happen in collect order, one capture late — so
                # the ring's last-writer-wins contents are byte-identical.
                overlap = staged and getattr(dep, "overlap", False)

                def attempt():
                    if inj is not None:
                        inj.on_verb("capture", table)
                    try:
                        with self.server.capture(table) as txn:
                            if n_ranks is None:
                                new_carry, keys, vals, mask = \
                                    S.capture_scan_collect(
                                        spec, step_fn, carry, padded,
                                        emit_every, t0=t0, valid=valid,
                                        elem_sharding=elem_sharding)
                            else:
                                new_carry, keys, vals, mask = \
                                    S.capture_scan_collect_multi(
                                        spec, step_fn, carry, padded,
                                        n_ranks, emit_every, t0=t0,
                                        valid=valid,
                                        elem_sharding=elem_sharding)
                            if overlap:
                                pending = self.server.stage_chunk_logged(
                                    table, chunk_id, keys, vals, mask,
                                    puts)
                                prev = self._pipeline(table).swap(pending)
                                if prev is not None:
                                    self.server.insert_chunk(table, txn,
                                                             prev)
                            else:
                                self.server.apply_chunk(table, chunk_id,
                                                        txn, keys, vals,
                                                        mask, puts)
                    except TransferDropped:
                        # drain-on-restage: flush the surviving in-flight
                        # slot before the retry re-collects and re-stages,
                        # so the pipeline never holds a stale slot across
                        # a fault boundary
                        self.drain_captures(table)
                        raise
                    return new_carry

                # collect never donates the carry, so a dropped transfer
                # retries the whole attempt against the original carry
                if inj is None:
                    return attempt()
                return call_with_retry(attempt, inj.retry,
                                       self._count_retry)
            with self.capture(table) as txn:
                txn.puts = puts
                if n_ranks is None:
                    txn.state, carry = S.capture_scan(
                        spec, txn.state, step_fn, carry, padded, emit_every,
                        t0=t0, valid=valid, elem_sharding=elem_sharding)
                else:
                    txn.state, carry = S.capture_scan_multi(
                        spec, txn.state, step_fn, carry, padded, n_ranks,
                        emit_every, t0=t0, valid=valid,
                        elem_sharding=elem_sharding)
        return carry

    def _pipeline(self, table: str) -> StagingPipeline:
        pipe = self._staging.get(table)
        if pipe is None:
            pipe = self._staging[table] = StagingPipeline()
        return pipe

    def drain_captures(self, table: str) -> None:
        """Flush the two-slot staging pipeline: insert the in-flight
        staged chunk in one capture dispatch.  Called at capture end
        (every overlapped producer run ends with exactly one in-flight
        chunk, so the plan predicts this as ONE ``drain`` dispatch) and
        on fault-injected restage (where its dispatch is recovery
        overhead, mirrored by ``faults.simulate_overhead``).  A no-op —
        no dispatch, nothing counted — when nothing is pending."""
        pipe = self._staging.get(table)
        prev = pipe.drain() if pipe is not None else None
        if prev is None:
            return
        with self.timers.time("send"):
            with self.server.capture(table) as txn:
                self.server.insert_chunk(table, txn, prev)

    # -- consumer-side loaders ---------------------------------------------------

    def sample_batch(self, table: str, n: int, rng):
        """Random gather of ``n`` stored tensors (the paper's data loader)."""
        with self.timers.time("retrieve") as box:
            values, keys, ok = self._call_verb(
                "sample", table, lambda: self.server.sample(table, rng, n))
            box[0] = values
        return values, keys, ok

    def sample_staged(self, table: str, n: int, rng):
        """Clustered random gather: sample on the store mesh, bring the
        assembled batch back across the interconnect in ONE counted
        staged transfer (``StoreServer.sample_staged``).  Returns
        ``(values [n,*shape], ok)``."""
        with self.timers.time("retrieve") as box:
            values, ok = self._call_verb(
                "sample_staged", table,
                lambda: self.server.sample_staged(table, rng, n))
            box[0] = values
        return values, ok

    def capture_epoch(self, table: str, body):
        """One fused read-only capture through the fault boundary: a
        transient ``StoreUnavailable`` window on the "capture" verb is
        absorbed *before* the table lock is taken, so a failed attempt
        dispatches nothing and bumps no counters — the retried capture is
        the one that counts.  ``body(txn)``'s return value is passed
        through (the fused trainer's ``(state, metrics)``).  The call,
        from entry to the dispatch's return, is the span
        ``repro.capture_epoch``."""
        inj = self.server.faults

        def attempt():
            if inj is not None:
                inj.on_verb("capture", table)
            with self.server.capture(table) as txn:
                return body(txn)

        with span("capture_epoch"):
            if inj is None:
                return attempt()
            return call_with_retry(attempt, inj.retry, self._count_retry)

    def latest_batch(self, table: str, n: int):
        with self.timers.time("retrieve") as box:
            values, keys, valid = self.server.latest(table, n)
            box[0] = values
        return values, keys, valid

    def wait_for_data(self, table: str, minimum: int = 1,
                      timeout: float = 60.0) -> bool:
        """Paper: "the ML workload must query the database multiple times
        while waiting for the first training snapshot".  Keeps the bool
        contract (``strict=False``): on timeout the trainer proceeds with
        whatever data exists — the straggler mitigation path."""
        with self.timers.time("metadata"):
            return self.server.wait_watermark(table, minimum, timeout,
                                              strict=False)

    def watermark(self, table: str) -> int:
        with self.timers.time("metadata"):
            return self.server.watermark(table)

    # -- metadata ------------------------------------------------------------------

    def put_metadata(self, name: str, value) -> None:
        with self.timers.time("metadata"):
            self.server.put_meta(name, value)

    def get_metadata(self, name: str, timeout: float | None = None,
                     strict: bool = False):
        """Non-strict by default (None on a missed ``timeout`` wait) — the
        inference consumer polls this in a loop; pass ``strict=True`` to
        get a typed :class:`~repro.core.faults.StoreTimeout` instead."""
        with self.timers.time("metadata"):
            if timeout is None:
                return self.server.get_meta(name)
            return self.server.wait_meta(name, timeout=timeout,
                                         strict=strict)

    # -- models (RedisAI verbs) -------------------------------------------------------

    def set_model(self, key: str, apply_fn: Callable, params) -> None:
        with self.timers.time("model_load"):
            self.server.set_model(key, apply_fn, params)

    def run_model(self, key: str, inputs: Sequence[str],
                  outputs: Sequence[str], table: str = "default",
                  out_table: str | None = None) -> None:
        """Evaluate a stored model on stored tensors, store the predictions.

        The three-step paper protocol is: (1) ``put_tensor`` the inference
        data, (2) ``run_model`` by key, (3) ``get_tensor`` the predictions —
        this verb is step (2) alone, so callers measure each step just like
        paper Fig. 7.
        """
        out_table = out_table or table
        ins = []
        for nm in inputs:
            v, found = self.server.get(table, S.name_key(nm))
            ins.append(v)
        with self.timers.time("model_eval") as box:
            outs = self.server.run_model(key, *ins)
            box[0] = outs
        if not isinstance(outs, (tuple, list)):
            outs = (outs,)
        if len(outs) != len(outputs):
            raise ValueError(f"model {key!r} returned {len(outs)} outputs, "
                             f"expected {len(outputs)}")
        for nm, o in zip(outputs, outs):
            self.server.put(out_table, S.name_key(nm), o)

    def infer(self, key: str, *xs):
        """Fused fast path: one dispatch, no store round-trip (beyond-paper;
        the tightly-coupled LibTorch baseline of Fig. 7, but still going
        through the registry so the producer stays model-agnostic)."""
        with self.timers.time("model_eval") as box:
            out = self.server.run_model(key, *xs)
            box[0] = out
        return out
