"""StoreServer: the host-side owner of TensorStore state.

The Redis process of the paper becomes a lock-guarded holder of immutable
JAX store state.  Host threads (producer / consumer / driver) call the
server's verbs; each verb dispatches a jitted pure store op and swaps the
state reference.  JAX's async dispatch gives the loose coupling: a ``put``
returns as soon as the update is enqueued on the device stream, so the
producer (like the paper's PHASTA ranks) is blocked only for the enqueue,
not for the ML consumer.

Concurrency model (fused-pipeline rework):

* **Per-table locks.** Every table owns its own re-entrant lock; a
  producer streaming into one table never serializes against a consumer
  reading a different table.  Each wait for a table lock is the profiler
  span ``repro.store.lock`` (``telemetry.SpanLock``).  The server-wide
  lock only guards the registries (table/model/metadata maps), taken
  briefly and never while dispatching table ops.
* **Lock-free cached watermark.** A host-side monotonic counter per table
  is bumped at *dispatch* time (put +1, put_many +n, commit +puts), so
  ``watermark()`` / ``wait_watermark()`` read a Python int instead of
  dispatching a device reduction per poll — the consumer's 5 ms spin loop
  becomes a free memory read with exponential backoff.
* **Capture transactions.** ``capture(table)`` hands the caller the live
  ``TableState`` under the table lock; the caller dispatches one *fused*
  op (``store.capture_scan`` / a fused training epoch) and commits the
  updated state + put count.  One lock round-trip and one dispatch replace
  O(steps) verb calls.

Donation safety: ``put``/``put_many``/fused captures donate the previous
table state, which marks its buffers deleted *at dispatch time*.  Every
read of the same table therefore dispatches while holding that table's
lock — the lock orders dispatches, and the device stream executes them in
dispatch order, so a read enqueued before a donating put always sees live
buffers.  (Blocking host-side ``.item()``/print on results happens outside
the lock; returned arrays are fresh outputs, not aliases.)
"""

from __future__ import annotations

import contextlib
import threading
import time
from typing import Any, Callable, Iterable

import jax
import jax.numpy as jnp

from . import store as S
from .deployment import Colocated, Deployment
from .faults import (FaultInjector, FaultPlan, StoreTimeout,
                     WatermarkTimeout)
from .telemetry import SpanLock, Timers, poll_backoff

__all__ = ["StoreServer", "CaptureTxn", "PendingChunk"]


class PendingChunk:
    """An in-flight slot of the overlap staging pipeline.

    The chunk's cross-mesh ``stage_chunk`` transfer has been dispatched
    (and its wire crossing counted), but its masked insert has not run
    yet — ``keys``/``values``/``mask`` are the *staged* (db-placed)
    arrays, so the deferred :meth:`StoreServer.insert_chunk` is a pure
    db-mesh dispatch with no further interconnect traffic.
    """

    __slots__ = ("chunk_id", "keys", "values", "mask", "puts")

    def __init__(self, chunk_id: tuple, keys, values, mask, puts: int):
        self.chunk_id = chunk_id
        self.keys = keys
        self.values = values
        self.mask = mask
        self.puts = puts


class CaptureTxn:
    """One fused-capture transaction on a single table.

    ``state`` holds the checked-out ``TableState``; assign the updated
    state back to commit.  Set ``puts`` to the number of put operations
    the fused dispatch performed so the cached watermark stays exact
    (``store.capture_emit_count`` computes it for ``capture_scan``).
    Read-only captures (consumers) simply leave ``state`` untouched.
    """

    __slots__ = ("spec", "state", "puts", "_orig")

    def __init__(self, spec: S.TableSpec, state: S.TableState):
        self.spec = spec
        self.state = state
        self.puts = 0
        self._orig = state


class StoreServer:
    """Thread-safe owner of a set of store tables plus the model registry."""

    def __init__(self, deployment: Deployment | None = None,
                 timers: Timers | None = None,
                 faults: FaultPlan | None = None):
        self.deployment = deployment
        self.timers = timers or Timers()
        self._lock = threading.RLock()           # registries + metadata only
        self._table_locks: dict[str, SpanLock] = {}
        self._specs: dict[str, S.TableSpec] = {}
        self._state: dict[str, S.TableState] = {}
        self._counts: dict[str, int] = {}        # cached watermarks
        self._placements: dict[str, Any] = {}    # slab shardings (recovery)
        self._models: dict[str, tuple[Callable, Any]] = {}
        self._model_raw: dict[str, Callable] = {}  # unjitted apply fns
        self._model_versions: dict[str, int] = {}  # hot-swap generations
        self.model_swaps = 0                     # serving weight adoptions
        self._meta: dict[str, Any] = {}          # tiny host-side metadata KV
        self._meta_event = threading.Condition(self._lock)
        self._ops_lock = threading.Lock()
        self.op_count = 0                        # dispatched store ops
        self.staged_transfers = 0                # cross-mesh staging hops
        self._gathers: dict[tuple, Callable] = {}  # clustered gather cache
        # -- fault/recovery machinery (armed by a declared FaultPlan, even
        # an empty one — the fault-free chaos baseline takes this path too)
        plan = faults if faults is not None \
            else getattr(deployment, "faults", None)
        self.faults = FaultInjector(plan) if plan is not None else None
        self.wal_enabled = plan is not None
        self.retries = 0                         # verb retries (clients')
        self.recoveries = 0                      # completed store restarts
        self._wal: dict[str, list] = {}          # per-table write-ahead log
        self._wal_base: dict[str, int] = {}      # replay floor (snapshot)
        self._acked: set = set()                 # applied chunk ids
        self._recovery: dict[str, S.TableState] | None = None

    def _bump_ops(self, n: int = 1) -> None:
        with self._ops_lock:
            self.op_count += n

    def _bump_staged(self, n: int = 1) -> None:
        with self._ops_lock:
            self.staged_transfers += n

    def _bump_retry(self, n: int = 1) -> None:
        with self._ops_lock:
            self.retries += n

    # -- table management ---------------------------------------------------

    def create_table(self, spec: S.TableSpec,
                     deployment: Deployment | None = None,
                     slab_sharding=None) -> S.TableSpec:
        """Register + allocate a table.  ``slab_sharding`` explicitly
        places the slab (e.g. the slab-sharded trainer tier partitioning
        the slot axis over its data mesh via
        ``parallel.sharding.slab_sharding``); when ``None`` the
        deployment's placement rule applies."""
        dep = deployment or self.deployment
        if slab_sharding is None and dep is not None:
            slab_sharding = dep.slab_sharding(spec)
        with self._lock:
            if spec.name in self._specs:
                raise ValueError(f"table {spec.name!r} already exists")
            self._specs[spec.name] = spec
            self._state[spec.name] = S.init_table(spec, slab_sharding)
            self._table_locks[spec.name] = SpanLock("store.lock")
            self._counts[spec.name] = 0
            self._placements[spec.name] = slab_sharding
            self._wal[spec.name] = []
            self._wal_base[spec.name] = 0
        return spec

    def placement(self, table: str) -> Any:
        """The slab sharding ``table`` was created with (``None`` = default
        placement) — what a recovering restart re-allocates against."""
        return self._placements[table]

    def spec(self, table: str) -> S.TableSpec:
        return self._specs[table]

    def tables(self) -> list[str]:
        return list(self._specs)

    def hbm_bytes(self) -> int:
        return sum(S.table_bytes(sp) for sp in self._specs.values())

    def table_lock(self, table: str) -> SpanLock:
        """The per-table lock (dispatch ordering for fused captures)."""
        return self._table_locks[table]

    # -- fused-capture fast path ---------------------------------------------

    def checkout(self, table: str) -> S.TableState:
        with self._table_locks[table]:
            return self._state[table]

    def commit(self, table: str, new_state: S.TableState,
               puts: int = 0) -> None:
        """Swap in a state produced by a fused dispatch.

        ``puts``: how many put ops the dispatch performed — keeps the
        cached watermark exact without a device read.
        """
        with self._table_locks[table]:
            self._state[table] = new_state
            self._counts[table] += puts
        self._bump_ops()

    @contextlib.contextmanager
    def capture(self, table: str):
        """Checkout → fused dispatch → commit, atomically under the table
        lock.  Yields a :class:`CaptureTxn`; the body must only *dispatch*
        (async) device work — block on results after the ``with`` exits.

        An assigned ``txn.state`` commits even if the body then raises:
        fused ops donate the checked-out state at dispatch time, so
        rolling back to it would leave the table pointing at deleted
        buffers.  A body that raises *without* assigning leaves the table
        untouched.  (Assign the fused op's result to ``txn.state`` in the
        same statement as the dispatch.)
        """
        committed = False
        with self._table_locks[table]:
            txn = CaptureTxn(self._specs[table], self._state[table])
            try:
                yield txn
            finally:
                if txn.state is not txn._orig:
                    self._state[table] = txn.state
                    self._counts[table] += txn.puts
                    committed = True
        # One capture == one fused dispatch (read-only captures included).
        self._bump_ops()
        if committed:
            self._after_commit(table)

    # -- verbs ---------------------------------------------------------------

    def _staged(self, value, spec: S.TableSpec | None = None):
        """Stage one element onto the store placement (per-verb path).

        Threads the table's real ``TableSpec`` through to the deployment
        so spec-dependent element layouts hold, and counts one staged
        transfer whenever the deployment actually crosses meshes."""
        dep = self.deployment
        if dep is None:
            return value
        if dep.crosses_mesh:
            self._bump_staged()
        return dep.stage(value, spec)

    def _staged_batch(self, values, spec: S.TableSpec | None = None):
        """Stage a ``[n, *shape]`` batch in ONE transfer (batched verbs)."""
        dep = self.deployment
        if dep is None:
            return values
        if dep.crosses_mesh:
            self._bump_staged()
        return dep.stage_batch(values, spec)

    def stage_chunk(self, table: str, keys, values, mask):
        """Stage a whole fused-capture chunk (keys + values + emit mask)
        onto the store placement as ONE cross-mesh transfer — the
        clustered fused put's only interconnect hop per dispatch.  A
        no-op (and not counted) for deployments that never cross meshes.
        """
        dep = self.deployment
        if dep is None or not dep.crosses_mesh:
            return keys, values, mask
        self._bump_staged()
        return dep.stage_chunk(keys, values, mask, self._specs[table])

    # lint: holds-lock — runs inside the caller's capture txn (table lock)
    def apply_chunk(self, table: str, chunk_id: tuple, txn: CaptureTxn,
                    keys, values, mask, puts: int) -> None:
        """Exactly-once insert of one collected chunk (the WAL-logged form
        of ``stage_chunk`` + ``put_masked``, used whenever a ``FaultPlan``
        is armed).

        ``chunk_id`` is the client's stable ``(rank, seq)`` — the SAME id
        on every retry of the same chunk, a NEW id per new chunk.  The
        acknowledged-id set gives exactly-once semantics on an at-least-
        once transport: ``store.put_masked`` is last-writer-wins but not
        idempotent (ring pointer and count advance per apply), so a
        duplicated delivery is *deduplicated* here rather than re-applied,
        and a dropped delivery is retried by the client under the same id.
        The staging hop is counted (and the injector consulted) *before*
        the transfer: a dropped chunk still paid its interconnect hop, a
        duplicated chunk pays one extra.
        """
        spec = self._specs[table]
        dep = self.deployment
        crossing = dep is not None and dep.crosses_mesh
        if crossing:
            self._bump_staged()
        # may raise TransferDropped (hop already paid, nothing applied);
        # dup=True means a second copy of this chunk arrives right after
        dup = self.faults.on_stage(table) if self.faults is not None \
            else False
        if chunk_id not in self._acked:
            if crossing:
                keys, values, mask = dep.stage_chunk(keys, values, mask,
                                                     spec)
            txn.state = S.put_masked(spec, txn.state, keys, values, mask)
            txn.puts = puts
            self._acked.add(chunk_id)
            if self.wal_enabled:
                self._wal[table].append(("chunk", (keys, values, mask),
                                         puts))
        if dup:
            # the duplicate delivery: one more hop, then the ack set makes
            # it a no-op — the table state never sees the second apply
            if crossing:
                self._bump_staged()
            assert chunk_id in self._acked

    def stage_chunk_logged(self, table: str, chunk_id: tuple,
                           keys, values, mask, puts: int) -> PendingChunk:
        """First half of the overlapped exactly-once apply —
        :meth:`apply_chunk` split at the wire: pay the crossing, consult
        the injector, start the async cross-mesh transfer (donating the
        client-side collect buffers), and hand back the in-flight
        :class:`PendingChunk` for the client's two-slot pipeline.

        Staged-transfer accounting is identical to the serial path and
        counts once per *wire crossing*, at stage time: a dropped
        transfer already paid its hop (the restage after the drain-on-
        restage flush pays again, because the chunk crosses again), a
        duplicated delivery pays one extra, and the deferred insert —
        however many capture dispatches later it lands — never counts.
        That is what keeps ``predicted == stats()`` exact with two slots
        in flight.
        """
        spec = self._specs[table]
        dep = self.deployment
        crossing = dep is not None and dep.crosses_mesh
        if crossing:
            self._bump_staged()
        # may raise TransferDropped (hop already paid, nothing in flight)
        dup = self.faults.on_stage(table) if self.faults is not None \
            else False
        if crossing:
            keys, values, mask = dep.stage_chunk(keys, values, mask, spec,
                                                 donate=True)
        if dup and crossing:
            self._bump_staged()
        return PendingChunk(chunk_id, keys, values, mask, puts)

    # lint: holds-lock — runs inside the caller's capture txn (table lock)
    def insert_chunk(self, table: str, txn: CaptureTxn,
                     pending: PendingChunk) -> None:
        """Second half of the overlapped apply: the masked insert of an
        in-flight staged chunk, inside the caller's capture txn.
        Deduplicated by the ack set exactly like :meth:`apply_chunk`
        (``put_masked`` is last-writer-wins but not idempotent), and
        WAL-logged with the staged arrays so a restart replays it
        byte-identically."""
        if pending.chunk_id in self._acked:
            return
        spec = self._specs[table]
        txn.state = S.put_masked(spec, txn.state, pending.keys,
                                 pending.values, pending.mask)
        txn.puts += pending.puts
        self._acked.add(pending.chunk_id)
        if self.wal_enabled:
            self._wal[table].append(("chunk", (pending.keys, pending.values,
                                               pending.mask), pending.puts))

    def _after_commit(self, table: str) -> None:
        """Injected-operator actions at a commit boundary: a declared
        ``snapshot`` parks a recovery image (and truncates the replay
        tail), a declared ``restart`` kills and rebuilds the store."""
        if self.faults is None:
            return
        for act in self.faults.on_commit(table):
            if act == "snapshot":
                self._take_recovery_snapshot()
            else:
                self._restart_and_recover()

    def put(self, table: str, key, value) -> None:
        spec = self._specs[table]
        value = self._staged(value, spec)
        key = jax.numpy.asarray(key, S.KEY_DTYPE)
        with self._table_locks[table]:
            self._state[table] = S.put(spec, self._state[table], key, value)
            self._counts[table] += 1
            if self.wal_enabled:
                self._wal[table].append(("put", (key, value), 1))
        self._bump_ops()
        self._after_commit(table)

    def put_many(self, table: str, keys, values) -> None:
        spec = self._specs[table]
        values = self._staged_batch(values, spec)
        keys = jax.numpy.asarray(keys, S.KEY_DTYPE)
        with self._table_locks[table]:
            self._state[table] = S.put_many(spec, self._state[table], keys,
                                            values)
            self._counts[table] += int(keys.shape[0])
            if self.wal_enabled:
                self._wal[table].append(("put_many", (keys, values),
                                         int(keys.shape[0])))
        self._bump_ops()
        self._after_commit(table)

    def put_stream(self, table: str, keys, values) -> None:
        """One dispatch for a whole trajectory of sends (fused pipeline)."""
        spec = self._specs[table]
        values = self._staged_batch(values, spec)
        keys = jax.numpy.asarray(keys, S.KEY_DTYPE)
        n = int(keys.shape[0]) * (int(keys.shape[1]) if keys.ndim == 2 else 1)
        with self._table_locks[table]:
            self._state[table] = S.put_stream(spec, self._state[table], keys,
                                              values)
            self._counts[table] += n
            if self.wal_enabled:
                self._wal[table].append(("put_stream", (keys, values), n))
        self._bump_ops()
        self._after_commit(table)

    def get(self, table: str, key):
        spec = self._specs[table]
        key = jax.numpy.asarray(key, S.KEY_DTYPE)
        with self._table_locks[table]:
            out = S.get(spec, self._state[table], key)
        self._bump_ops()
        return out

    def get_many(self, table: str, keys):
        spec = self._specs[table]
        with self._table_locks[table]:
            out = S.get_many(spec, self._state[table], keys)
        self._bump_ops()
        return out

    def serve_batch(self, req_table: str, res_table: str, keys, mask,
                    apply_fn, params, chunk_id: tuple | None = None):
        """Drain one continuous-batching batch in ONE fused dispatch:
        gather the active requests from ``req_table``, apply the bound
        model, scatter the responses into ``res_table``
        (``store.serve_batch``).

        Requests, model params and responses all live on the store
        placement, so the dispatch never crosses the interconnect — no
        staged transfers are counted — but the injector's stage hook on
        ``res_table`` is still consulted so drop/dup chaos events exercise
        the serving path.  Under an armed ``FaultPlan`` the batch is
        WAL-logged as a ``put_masked`` chunk (host-known ``mask``, so a
        restart replays the insert byte-identically) and deduplicated by
        ``chunk_id`` exactly like :meth:`apply_chunk`.  Returns the
        per-slot found-and-served flags.
        """
        req_spec = self._specs[req_table]
        res_spec = self._specs[res_table]
        keys = jnp.asarray(keys, S.KEY_DTYPE)
        mask_dev = jnp.asarray(mask, bool)
        puts = int(mask_dev.sum())
        first, second = sorted((req_table, res_table))
        with self._table_locks[first], self._table_locks[second]:
            dup = self.faults.on_stage(res_table) \
                if self.faults is not None else False
            if chunk_id is None or chunk_id not in self._acked:
                new_res, ok, ys = S.serve_batch(
                    req_spec, res_spec, apply_fn,
                    self._state[req_table], self._state[res_table],
                    params, keys, mask_dev)
                self._state[res_table] = new_res
                self._counts[res_table] += puts
                if chunk_id is not None:
                    self._acked.add(chunk_id)
                if self.wal_enabled:
                    self._wal[res_table].append(
                        ("chunk", (keys, ys, mask_dev), puts))
            else:
                ok = mask_dev
        self._bump_ops()
        self._after_commit(res_table)
        return ok

    def sample(self, table: str, rng, n: int):
        spec = self._specs[table]
        with self._table_locks[table]:
            out = S.sample(spec, self._state[table], rng, n)
        self._bump_ops()
        return out

    def _clustered_gather(self, table: str, n: int):
        """Cached db-mesh gather executable for ``sample_staged`` (one per
        (table, batch size); see ``store.make_clustered_gather``)."""
        key = (table, n)
        fn = self._gathers.get(key)
        if fn is None:
            spec = self._specs[table]
            dep = self.deployment
            db_mesh = getattr(dep, "db_mesh", None)
            axis = getattr(dep, "slab_axis", None)
            shards = dep.gather_shards(spec) \
                if hasattr(dep, "gather_shards") else 1
            fn = S.make_clustered_gather(spec, n, db_mesh=db_mesh,
                                         axis=axis, shards=shards)
            with self._lock:
                self._gathers[key] = fn
        return fn

    def sample_staged(self, table: str, rng, n: int):
        """Clustered read verb: sample ``n`` elements ON the store mesh
        (shard-local gather + explicit psum when the slab is
        slot-partitioned), then move the assembled batch back onto the
        clients in ONE counted cross-mesh transfer.

        One store dispatch (like ``sample``) plus one staged transfer —
        the read-side mirror of the fused clustered put.  Degrades to a
        plain sample (no staging, nothing counted) under co-located /
        local deployments.  Returns ``(values [n, *shape], ok)``.
        """
        gather = self._clustered_gather(table, n)
        with self._table_locks[table]:
            values, ok = gather(self._state[table], rng)
        dep = self.deployment
        if dep is not None and dep.crosses_mesh:
            values, ok = dep.stage_to_clients((values, ok))
            self._bump_staged()
        self._bump_ops()
        return values, ok

    def latest(self, table: str, n: int):
        spec = self._specs[table]
        with self._table_locks[table]:
            out = S.latest(spec, self._state[table], n)
        self._bump_ops()
        return out

    def poll(self, table: str, key) -> bool:
        spec = self._specs[table]
        key = jax.numpy.asarray(key, S.KEY_DTYPE)
        with self._table_locks[table]:
            hit = S.poll(spec, self._state[table], key)
        self._bump_ops()
        return bool(hit)

    def delete(self, table: str, key) -> None:
        spec = self._specs[table]
        key = jax.numpy.asarray(key, S.KEY_DTYPE)
        with self._table_locks[table]:
            self._state[table] = S.delete(spec, self._state[table], key)
            if self.wal_enabled:
                # Tombstones must replay too: a restart that re-runs the
                # put log but skips deletes resurrects dead keys.
                self._wal[table].append(("delete", (key,), 0))
        self._bump_ops()
        self._after_commit(table)

    def stats(self) -> dict:
        """Telemetry snapshot: dispatched-op count, cross-mesh staged
        transfers, plus every table's cached watermark.  ``op_count``
        counts host→device dispatches (one per verb, one per fused
        capture) — the benchmarks' O(k)-vs-O(1) dispatch claims are
        measured from deltas of this dict.  ``staged_transfers`` counts
        interconnect hops of a clustered deployment (one per staged verb
        element/batch, one per fused chunk, one per staged gather) — the
        Fig.-5 clustered traffic, measured."""
        with self._lock:
            marks = dict(self._counts)
        return {"op_count": self.op_count,
                "staged_transfers": self.staged_transfers,
                "faults_injected": self.faults.faults_injected
                if self.faults is not None else 0,
                "retries": self.retries,
                "recoveries": self.recoveries,
                "model_swaps": self.model_swaps,
                "watermarks": marks}

    def watermark(self, table: str) -> int:
        """Total writes so far — the consumer's freshness signal.

        Lock-free: reads the host-side cached counter (updated at dispatch
        time), so polling never dispatches a device op and never contends
        with the producer.
        """
        return self._counts[table]

    def watermark_device(self, table: str) -> int:
        """Ground-truth watermark from device state (blocking read; tests
        assert it always equals the cached ``watermark``)."""
        with self._table_locks[table]:
            count = jax.numpy.asarray(self._state[table].count).copy()
        return int(count)

    def valid_count(self, table: str) -> int:
        spec = self._specs[table]
        with self._table_locks[table]:
            n = S.valid_count(spec, self._state[table])
        self._bump_ops()
        return int(n)

    def wait_watermark(self, table: str, minimum: int, timeout: float = 60.0,
                       interval: float = 0.001,
                       max_interval: float = 0.05,
                       strict: bool = True) -> bool:
        """Block until ``watermark >= minimum`` (paper: ML ranks poll the DB
        while waiting for the first snapshot).  On timeout raises
        :class:`~repro.core.faults.WatermarkTimeout` carrying the table,
        the wanted/actual watermarks and the deadline — or, with
        ``strict=False`` (straggler mitigation: proceed on stale data),
        returns False instead.

        Polls the lock-free cached watermark with deadline-clamped
        exponential backoff (``telemetry.poll_backoff``) — zero device
        dispatches and zero producer contention while spinning, and the
        call never overshoots ``timeout`` by a backoff step.
        """
        for _ in poll_backoff(timeout, interval, max_interval):
            if self._counts[table] >= minimum:
                return True
        if self._counts[table] >= minimum:
            return True
        if strict:
            raise WatermarkTimeout(table, minimum, self._counts[table],
                                   timeout)
        return False

    # -- metadata (host KV, paper's "useful metadata") ------------------------

    def put_meta(self, name: str, value) -> None:
        with self._meta_event:
            self._meta[name] = value
            self._meta_event.notify_all()

    def get_meta(self, name: str, default=None):
        with self._lock:
            return self._meta.get(name, default)

    def wait_meta(self, name: str, timeout: float = 60.0,
                  strict: bool = True):
        """Block until metadata ``name`` exists.  On timeout raises
        :class:`~repro.core.faults.StoreTimeout` (``strict=False``: returns
        None — the polling form inference consumers loop on)."""
        with self._meta_event:
            ok = self._meta_event.wait_for(lambda: name in self._meta,
                                           timeout=timeout)
            if ok:
                return self._meta.get(name)
        if strict:
            raise StoreTimeout("metadata", name, timeout)
        return None

    # -- model registry (RedisAI analogue) ------------------------------------

    def set_model(self, key: str, apply_fn: Callable, params,
                  jit_compile: bool = True) -> None:
        """Store a model "in the database": params pinned to the store
        placement, apply jitted.  The producer only ever sees ``key``."""
        dep = self.deployment
        if dep is not None and not isinstance(dep, Colocated):
            params = jax.tree.map(dep.stage, params)
        fn = jax.jit(apply_fn) if jit_compile else apply_fn
        with self._lock:
            self._models[key] = (fn, params)
            # keep the UNJITTED fn too: the fused serving dispatch takes
            # it as a static jit arg, and a fresh jax.jit wrapper per
            # publish would miss its compile cache on every hot-swap
            self._model_raw[key] = apply_fn
            self._model_versions[key] = \
                self._model_versions.get(key, 0) + 1

    def has_model(self, key: str) -> bool:
        with self._lock:
            return key in self._models

    def run_model(self, key: str, *inputs):
        with self._lock:
            fn, params = self._models[key]
        return fn(params, *inputs)

    def model_keys(self) -> list[str]:
        with self._lock:
            return list(self._models)

    def model_version(self, key: str) -> int:
        """Monotonic publication counter for ``key`` (0 = never published).
        Each ``set_model`` bumps it — the serving consumer's hot-swap
        watermark, polled for free like the table watermarks."""
        with self._lock:
            return self._model_versions.get(key, 0)

    def bind_model(self, key: str, have: int | None = None):
        """Atomically adopt the current weights for ``key`` if they are
        newer than generation ``have``.

        Returns ``(apply_fn, params, version)`` on adoption — including the
        very first bind (``have=None``) — or ``None`` when nothing newer is
        published.  ``apply_fn`` is the publisher's raw (unjitted)
        function, identity-stable across re-publishes of the same
        callable, so the fused serving dispatch's compile cache survives
        hot-swaps.  Version read and registry read happen under one lock,
        so a concurrent ``set_model`` can never hand out torn
        (old-params, new-version) pairs; every adoption bumps
        ``model_swaps`` in :meth:`stats`.
        """
        with self._lock:
            version = self._model_versions.get(key, 0)
            if version == 0 or version == have:
                return None
            fn = self._model_raw[key]
            params = self._models[key][1]
        with self._ops_lock:
            self.model_swaps += 1
        return fn, params, version

    # -- in-memory checkpointing hook -----------------------------------------

    def snapshot(self) -> dict[str, S.TableState]:
        """Deep snapshot of all table state.  Copies the buffers: later
        ``put``s donate (invalidate) the live state, so a zero-copy
        snapshot would dangle.  Tables are snapshotted one at a time under
        their own locks (per-table consistency)."""
        snap = {}
        with self._lock:
            names = list(self._specs)
        for name in names:
            with self._table_locks[name]:
                snap[name] = jax.tree.map(jax.numpy.copy, self._state[name])
        return snap

    def restore(self, snap: dict[str, S.TableState]) -> None:
        for name, st in snap.items():
            if name in self._specs:
                with self._table_locks[name]:
                    self._state[name] = st
                    # Re-derive the cached watermark from device truth.
                    self._counts[name] = int(jax.numpy.asarray(st.count))

    # -- injected store restart + recovery -------------------------------------

    def _take_recovery_snapshot(self) -> None:
        """Park a recovery image (a declared ``snapshot`` fault event):
        deep-copies every table and marks the current WAL length as the
        replay floor — commits before this point never replay again (the
        snapshot truncates the log, which is also what keeps the WAL from
        growing without bound in a long-running session)."""
        snap = self.snapshot()
        # The image and the replay floor are registry state: publish them
        # under the registry lock so a concurrent restart never sees the
        # new snapshot paired with the old floor (or vice versa).
        with self._lock:
            self._recovery = snap
            for t in self._wal:
                self._wal_base[t] = len(self._wal[t])

    def _replay_entry(self, spec: S.TableSpec, state: S.TableState,
                      kind: str, payload) -> S.TableState:
        if kind == "put":
            return S.put(spec, state, *payload)
        if kind == "put_many":
            return S.put_many(spec, state, *payload)
        if kind == "put_stream":
            return S.put_stream(spec, state, *payload)
        if kind == "delete":
            return S.delete(spec, state, *payload)
        return S.put_masked(spec, state, *payload)       # "chunk"

    def _restart_and_recover(self) -> None:
        """A declared ``restart`` fault: the store process dies and comes
        back.  The device slab is lost; each table is rebuilt from the
        last recovery snapshot (or re-initialised empty if none was taken)
        and the WAL tail since that snapshot is replayed — the same puts,
        in the same commit order, against the same base state, so the
        recovered table is byte-identical to the pre-crash one (the store
        ops are pure functions of (state, chunk): determinism carries the
        exactly-once argument through a restart).  The snapshot is
        restored as a *copy* — later puts donate the live state, and the
        parked image must survive a second restart.  Each replayed entry
        is one real dispatch, counted in ``op_count`` (and predicted by
        ``faults.simulate_overhead``)."""
        with self._lock:
            names = list(self._specs)
        for name in names:
            spec = self._specs[name]
            with self._table_locks[name]:
                if self._recovery is not None and name in self._recovery:
                    st = jax.tree.map(jax.numpy.copy, self._recovery[name])
                else:
                    st = S.init_table(spec, self._placements[name])
                for kind, payload, _puts in \
                        self._wal[name][self._wal_base[name]:]:
                    st = self._replay_entry(spec, st, kind, payload)
                    self._bump_ops()
                self._state[name] = st
                self._counts[name] = int(jax.numpy.asarray(st.count))
        with self._ops_lock:
            self.recoveries += 1
