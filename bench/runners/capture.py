"""Runner of the ``capture`` kind: the producer alone.

``ranks`` flat-plate producers advance in lockstep inside
``Client.capture_scan(n_ranks=ranks)`` (``store.capture_scan_multi``),
``chunk_steps`` solver steps per dispatch, and every ``emit_every``-th
step puts all ranks' snapshots into the ring.  No trainer reads the
table, so the store's write path and the fused scan are the work.  At
most ``in_flight`` chunks are queued on the device.

Check: after the window, the table's keys and versions must equal a
sequential replay of every put (ring wrap, last writer wins), and every
row the snapshot the reference computes for that slot's rank and step, at
the precision the configuration states.
"""

from __future__ import annotations

import time
from collections import deque

import numpy as np

from .. import compare
from .. import precision as P
from .. import generator as gen
from .. import harness, producer
from ..harness import Check, Outcome, span

EMPTY_KEY = 0xFFFFFFFF


def packed_key(rank: int, step: int) -> int:
    """The paper's (rank, step) tensor key packed into 32 bits: top bit
    set, 19 bits of step, 12 bits of rank; the all-ones pattern is kept
    free for empty slots."""
    key = (1 << 31) | ((step & 0x7FFFF) << 12) | (rank & 0xFFF)
    return 0x7FFFFFFF if key == EMPTY_KEY else key


def ring_replay(capacity: int, n_puts: int, put_at):
    """Slot contents after ``n_puts`` puts into an empty ring, put ``n``
    being ``put_at(n) -> (rank, step)``: (keys, versions, (rank, step) per
    slot or None).  Put ``n`` lands in slot ``n % capacity`` with version
    ``n + 1``; only the last ``capacity`` puts survive."""
    keys = np.full(capacity, EMPTY_KEY, np.uint32)
    version = np.zeros(capacity, np.int64)
    owner: list = [None] * capacity
    for n in range(max(0, n_puts - capacity), n_puts):
        rank, step = put_at(n)
        slot = n % capacity
        keys[slot] = packed_key(rank, step)
        version[slot] = n + 1
        owner[slot] = (rank, step)
    return keys, version, owner


class CaptureCell:
    def __init__(self, ctx: harness.Context):
        import jax.numpy as jnp
        from repro.core.client import Client
        from repro.core.server import StoreServer

        self.ctx = ctx
        cfg, tf = ctx.cell.cfg, ctx.cell.traffic
        self.cfg, self.tf = cfg, tf
        self.server = StoreServer()
        producer.field_table(self.server, cfg)
        self.client = Client(self.server)
        self._step_fn = producer.step_fn(cfg, tf["solver_period"], ranked=True)
        self.ranks = tf["ranks"]
        self.rank_keys = jnp.stack([gen.producer_key(ctx.seed, r)
                                    for r in range(self.ranks)])
        self._carry = self.rank_keys
        self.steps = 0
        self._pending: deque = deque()

    def chunk(self) -> None:
        n = self.tf["chunk_steps"]
        self._carry = self.client.capture_scan(
            producer.TABLE, self._step_fn, self._carry, n,
            emit_every=self.tf["emit_every"], t0=self.steps,
            n_ranks=self.ranks)
        self.steps += n
        self._pending.append(self._carry)
        while len(self._pending) > self.tf["in_flight"]:
            with span("wait"):
                self._pending.popleft().block_until_ready()

    def drain(self) -> None:
        while self._pending:
            self._pending.popleft().block_until_ready()

    def n_puts(self) -> int:
        every = self.tf["emit_every"]
        return self.ranks * -(-self.steps // every)

    def put_at(self, n: int) -> tuple[int, int]:
        """Rank and step of the ``n``-th put: emitting steps in order, all
        ranks of one step rank-major."""
        return n % self.ranks, (n // self.ranks) * self.tf["emit_every"]

    def table(self) -> dict:
        st = self.server.checkout(producer.TABLE)
        return {"keys": np.asarray(st.keys), "version": np.asarray(st.version),
                "slab": np.asarray(st.slab)}


def reference_rows(ctx: harness.Context, owner: list,
                   arith: str | None = None) -> np.ndarray:
    """The snapshot each slot should hold, computed by the reference in
    ``arith`` (by default the precision the configuration states)."""
    import jax
    import jax.numpy as jnp
    ref = harness.reference(ctx.cell)
    arith = arith or P.stated(ctx.cell.cfg)
    period = ctx.cell.traffic["solver_period"]
    live = [o for o in owner if o is not None]
    keys = jnp.stack([gen.producer_key(ctx.seed, r) for r, _ in live])
    steps = jnp.asarray([t % period for _, t in live], jnp.int32)
    rows = np.asarray(ref.make_snapshots(ctx.cell.cfg, arith)(keys, steps),
                      np.float64)
    out = np.zeros((len(owner),) + rows.shape[1:])
    out[[i for i, o in enumerate(owner) if o is not None]] = rows
    return out


def readings(table: dict, replay, ref_rows: np.ndarray) -> dict[str, float]:
    """Mismatched slot keys, mismatched versions (both exact), and the
    worst row's gap from the reference snapshot."""
    keys, version, owner = replay
    live = [i for i, o in enumerate(owner) if o is not None]
    return {"key_mismatch": float(np.sum(table["keys"] != keys)),
            "version_mismatch": float(np.sum(table["version"] != version)),
            "row_gap": compare.max_rel_row_gap(table["slab"][live],
                                               ref_rows[live])}


def run(ctx: harness.Context) -> Outcome:
    cell = CaptureCell(ctx)
    cell.chunk()
    cell.drain()
    setup_s = ctx.setup_seconds()

    steps0 = cell.steps
    ops0 = cell.server.stats()["op_count"]
    with ctx.window():
        t0 = time.perf_counter()
        deadline = t0 + ctx.seconds
        while time.perf_counter() < deadline:
            cell.chunk()
        cell.drain()
        elapsed = time.perf_counter() - t0
    steps = cell.steps - steps0
    ops = cell.server.stats()["op_count"] - ops0
    peak = harness.memory_peak_bytes(ctx.cell.chips)
    table = cell.table()
    replay = ring_replay(ctx.cell.cfg["table"]["capacity"], cell.n_puts(),
                         cell.put_at)
    cell = None

    rows = reference_rows(ctx, replay[2])
    limits = harness.limits(ctx.cell)
    checks = [Check(k, v, limits[k])
              for k, v in readings(table, replay, rows).items()]
    return Outcome(
        setup_s=setup_s, e2e={"sim_steps_per_s": steps / elapsed},
        attempted=steps, failed=0, checks=checks,
        counters={"steps": steps, "store_ops": ops, "elapsed_s": elapsed},
        memory_peak_bytes=peak)


def _program_readings(ctx: harness.Context):
    cell = CaptureCell(ctx)
    deadline = time.perf_counter() + ctx.seconds
    while True:
        cell.chunk()
        if time.perf_counter() >= deadline:
            break
    cell.drain()
    table = cell.table()
    replay = ring_replay(ctx.cell.cfg["table"]["capacity"], cell.n_puts(),
                         cell.put_at)
    return table, replay


def calibrate(ctx: harness.Context, full: bool = True) -> dict[str, dict]:
    """Readings of the program after a short window against the reference
    at the stated precision, and of the reference rows put in the table's
    place in bfloat16 (the control).  ``full`` adds a second witness, the
    program with its solver's matmuls at precision HIGHEST against the
    float32 reference, and the program against that reference."""
    import jax
    table, replay = _program_readings(ctx)
    rows = reference_rows(ctx, replay[2])
    out = {"program": readings(table, replay, rows),
           "control_bfloat16": readings(
               dict(table, slab=reference_rows(ctx, replay[2], "bfloat16")),
               replay, rows)}
    if full:
        with jax.default_matmul_precision("highest"):
            high, high_replay = _program_readings(ctx)
        out["program_highest_vs_float32"] = readings(
            high, high_replay, reference_rows(ctx, high_replay[2], "float32"))
        out["program_vs_float32"] = readings(
            table, replay, reference_rows(ctx, replay[2], "float32"))
    return out
