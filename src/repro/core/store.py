"""TensorStore: a device-resident, sharded, in-memory key-value tensor store.

This is the TPU-native analogue of the SmartSim-deployed Redis/KeyDB database
of Balin et al. (2023).  On Polaris the database is an OS process holding
tensors in node-local DRAM, addressed by string keys over TCP.  On a TPU pod
there is no node-local service to talk to; instead the store is *state*:

  * each **table** is a fixed-capacity slab ``[capacity, *elem_shape]`` living
    in device HBM, plus per-slot metadata (``keys``, ``version``) and scalar
    cursors (``ptr``, ``count``);
  * all operations (``put`` / ``get`` / ``sample`` / ``poll`` / ``delete``)
    are pure jit-compatible functions ``state -> state`` so they can run
    standalone (the loosely-coupled paper path, dispatched by host threads)
    **or fused into a producer/consumer step** (in-situ capture with zero
    dispatch overhead — a beyond-paper optimization);
  * the slab is sharded across the mesh.  With the **co-located** deployment
    the element dims carry the *same* PartitionSpec as the producer's output,
    so a put lowers to a pure local dynamic-update-slice: **zero collective
    bytes**, the structural equivalent of the paper's "all data transfer is
    contained within each node".  (Asserted from compiled HLO in tests and
    reported in the roofline.)

Two storage **engines** mirror the paper's Redis-vs-KeyDB comparison:

  * ``ring``  — slots assigned by a monotone write pointer, oldest snapshot
    overwritten first.  Natural for streaming solution states ("unique key
    per rank and step" in the paper, with an explicit finite-memory window).
  * ``hash``  — slot = key mod capacity; idempotent same-key overwrite.
    Natural for named tensors, metadata and model buffers.

Versions are strictly increasing per-table write stamps (``count``+1), giving
consumers a total order: ``latest``/``sample`` implement the paper's
data-loader that "gathers tensors at random" or takes the freshest ones, and
the scalar ``count`` doubles as the watermark used for epoch gating.

Fused in-situ pipeline (the hot path)
-------------------------------------

Two access tiers share these ops:

* **Per-verb** (paper-fidelity): every client verb is one host dispatch —
  flexible, measurable component-by-component, but the driver pays one
  dispatch plus one lock round-trip per verb.  Use it for control-plane
  traffic, irregular access, and paper-comparison benchmarks.
* **Fused** (beyond-paper): ``capture_scan`` folds ``k`` producer steps and
  their ring puts into a single ``jax.lax.scan`` dispatch
  (``capture_scan_multi`` is the R-rank form: per-rank ``t0`` clocks, all
  ranks' snapshots interleaved into the ring each emitting step);
  ``put_stream`` batches a whole trajectory of sends into one ``put_many``;
  ``sample_and_step`` runs the consumer's gather *and* its training
  microstep inside one jit.  One epoch of ``ml.trainer.insitu_train``
  costs O(1) dispatches instead of O(gather·batches).  Use it whenever the
  producer/consumer step is itself jit-traceable (the common case).

Everywhere a fused op batches writes, slot collisions keep the per-verb
semantics: **last-writer-wins** in trace order, with every overwrite still
bumping ``count`` — a fused trajectory is byte-identical to replaying its
verbs one dispatch at a time.

The gather-side verbs (``get_many`` / ``sample``) route through the Pallas
package ``repro.kernels.store`` (probe / sample / gather kernels on TPU,
pure-jnp oracle elsewhere); neither tier materializes an ``[n, capacity]``
match matrix.
"""

from __future__ import annotations

import zlib
from dataclasses import dataclass, replace
from functools import partial, wraps
from typing import Any, Callable, NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..kernels.store import ops as _kops

__all__ = [
    "TableSpec",
    "TableState",
    "make_key",
    "name_key",
    "init_table",
    "put",
    "put_many",
    "put_masked",
    "put_stream",
    "get",
    "get_many",
    "serve_batch",
    "sample",
    "sample_sharded_impl",
    "latest",
    "poll",
    "delete",
    "valid_count",
    "table_bytes",
    "capture_scan",
    "capture_scan_multi",
    "capture_scan_collect",
    "capture_scan_collect_multi",
    "capture_rows",
    "capture_emit_count",
    "capture_emit_count_multi",
    "bucket_length",
    "MIN_BUCKET",
    "sample_and_step",
    "make_clustered_gather",
]

KEY_DTYPE = jnp.uint32
EMPTY_KEY = np.uint32(0xFFFFFFFF)
#: Named scope of every put's operations in the compiled programs (and so
#: in the profiler's device trace), wherever a put is traced.
PUT_SCOPE = "store.put"


def _put_scope(fn: Callable) -> Callable:
    """Trace ``fn`` under ``jax.named_scope(PUT_SCOPE)``, a scope object of
    its own per call (a scope object keeps state while entered)."""
    @wraps(fn)
    def scoped(*args, **kwargs):
        with jax.named_scope(PUT_SCOPE):
            return fn(*args, **kwargs)
    return scoped


# ---------------------------------------------------------------------------
# Keys.  SmartRedis addresses tensors with strings like "x.rank_3.step_120";
# device-side we need integers.  Host code hashes names (crc32) or packs
# (rank, step) into the 32-bit key space.
# ---------------------------------------------------------------------------

def name_key(name: str) -> int:
    """Stable 32-bit key for a string tensor name (crc32, never EMPTY_KEY)."""
    k = zlib.crc32(name.encode()) & 0xFFFFFFFE  # keep EMPTY_KEY reserved
    return int(k)


def make_key(rank, step) -> Any:
    """Pack (rank, step) into a uint32 key; works on ints or traced arrays.

    rank in [0, 2^12), step in [0, 2^19) -> key = 1<<31 | step<<12 | rank.
    The top bit keeps packed keys disjoint from crc32 name keys' typical
    range and away from EMPTY_KEY (which has all bits set).
    """
    rank = jnp.asarray(rank, dtype=KEY_DTYPE)
    step = jnp.asarray(step, dtype=KEY_DTYPE)
    key = (jnp.uint32(1) << 31) | ((step & jnp.uint32(0x7FFFF)) << 12) | (
        rank & jnp.uint32(0xFFF)
    )
    # Avoid the reserved EMPTY_KEY bit pattern.
    return jnp.where(key == EMPTY_KEY, jnp.uint32(0x7FFFFFFF), key)


# ---------------------------------------------------------------------------
# Table spec + state
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class TableSpec:
    """Static description of one store table."""

    name: str
    shape: tuple[int, ...]          # element shape
    dtype: Any = jnp.float32
    capacity: int = 16
    engine: str = "ring"            # "ring" | "hash"

    def __post_init__(self):
        if self.engine not in ("ring", "hash"):
            raise ValueError(f"unknown engine {self.engine!r}")
        if self.capacity < 1:
            raise ValueError("capacity must be >= 1")

    @property
    def elem_bytes(self) -> int:
        return int(np.prod(self.shape, dtype=np.int64)) * jnp.dtype(self.dtype).itemsize

    @property
    def slab_bytes(self) -> int:
        return self.capacity * self.elem_bytes


class TableState(NamedTuple):
    """Device-resident state of one table (a pytree)."""

    slab: jax.Array      # [capacity, *shape]
    keys: jax.Array      # uint32[capacity]; EMPTY_KEY where never written
    version: jax.Array   # int32[capacity]; 0 where empty, else write stamp
    ptr: jax.Array       # int32 scalar: next ring slot
    count: jax.Array     # int32 scalar: total successful puts (watermark)


def init_table(spec: TableSpec, slab_sharding=None) -> TableState:
    """Allocate an empty table, optionally with an explicit slab sharding.

    When the slab lives on a mesh, the per-slot metadata (keys/version) and
    cursors are replicated on the *same* mesh so every store op is a single
    SPMD computation."""
    slab = jnp.zeros((spec.capacity, *spec.shape), dtype=spec.dtype)
    meta_sharding = None
    if slab_sharding is not None:
        slab = jax.device_put(slab, slab_sharding)
        from jax.sharding import NamedSharding, PartitionSpec
        if hasattr(slab_sharding, "mesh"):
            meta_sharding = NamedSharding(slab_sharding.mesh,
                                          PartitionSpec())

    def _meta(x):
        return jax.device_put(x, meta_sharding) if meta_sharding is not None \
            else x

    return TableState(
        slab=slab,
        keys=_meta(jnp.full((spec.capacity,), EMPTY_KEY, dtype=KEY_DTYPE)),
        version=_meta(jnp.zeros((spec.capacity,), dtype=jnp.int32)),
        ptr=_meta(jnp.zeros((), dtype=jnp.int32)),
        count=_meta(jnp.zeros((), dtype=jnp.int32)),
    )


def table_bytes(spec: TableSpec) -> int:
    """HBM footprint of the table (slab + metadata)."""
    return spec.slab_bytes + spec.capacity * (4 + 4) + 8


# ---------------------------------------------------------------------------
# Slot resolution
# ---------------------------------------------------------------------------

def _slot_for_put(spec: TableSpec, state: TableState, key) -> jax.Array:
    if spec.engine == "ring":
        return state.ptr
    # hash engine: reuse an existing slot holding this key (idempotent
    # overwrite), else key mod capacity.
    homed = jnp.asarray(key, KEY_DTYPE) % jnp.uint32(spec.capacity)
    match = (state.keys == jnp.asarray(key, KEY_DTYPE)) & (state.version > 0)
    existing = jnp.argmax(match).astype(jnp.int32)
    return jnp.where(jnp.any(match), existing, homed.astype(jnp.int32))


# ---------------------------------------------------------------------------
# Core ops.  Each op has a raw ``*_impl`` (traceable inside larger fused
# computations — capture_scan, the trainer's fused epoch) and a jitted
# public wrapper (the per-verb dispatch path).  ``spec`` is always static.
# ---------------------------------------------------------------------------

@_put_scope
def put_impl(spec: TableSpec, state: TableState, key, value) -> TableState:
    """Insert/overwrite one element.  O(1) slab dynamic-update-slice."""
    value = jnp.asarray(value, dtype=spec.dtype)
    if value.shape != spec.shape:
        raise ValueError(
            f"put into table {spec.name!r}: value shape {value.shape} != "
            f"element shape {spec.shape}"
        )
    slot = _slot_for_put(spec, state, key)
    stamp = state.count + 1
    new_ptr = (state.ptr + 1) % spec.capacity if spec.engine == "ring" else state.ptr
    return TableState(
        slab=jax.lax.dynamic_update_index_in_dim(state.slab, value, slot, 0),
        keys=state.keys.at[slot].set(jnp.asarray(key, KEY_DTYPE)),
        version=state.version.at[slot].set(stamp),
        ptr=new_ptr,
        count=stamp,
    )


put = partial(jax.jit, static_argnums=0, donate_argnums=1)(put_impl)


@_put_scope
def put_many_impl(spec: TableSpec, state: TableState, keys, values) -> TableState:
    """Vectorized put of n elements (one producer step sending all ranks).

    ``ring``: consecutive slots from the write pointer.
    ``hash``: slot = key mod capacity (the batched path probes the homed
    slot only — unlike single ``put`` it does not relocate onto an existing
    slot holding the same key elsewhere).

    Slot collisions within one batch (hash keys equal mod capacity, or a
    ring batch longer than ``capacity``) resolve deterministically
    **last-writer-wins**, exactly matching a sequence of single ``put``s;
    every element still bumps ``count`` (a collision is an overwrite, not a
    dropped write).
    """
    keys = jnp.asarray(keys, KEY_DTYPE)
    values = jnp.asarray(values, dtype=spec.dtype)
    n = keys.shape[0]
    if values.shape != (n, *spec.shape):
        raise ValueError(
            f"put_many into {spec.name!r}: values {values.shape} != "
            f"({n}, *{spec.shape})"
        )
    if spec.engine == "ring":
        slots = (state.ptr + jnp.arange(n, dtype=jnp.int32)) % spec.capacity
        new_ptr = (state.ptr + n) % spec.capacity
    else:
        slots = (keys % jnp.uint32(spec.capacity)).astype(jnp.int32)
        new_ptr = state.ptr
    stamps = state.count + 1 + jnp.arange(n, dtype=jnp.int32)
    if n > 1:
        # Deterministic last-writer-wins: redirect all but the last write to
        # each slot out of bounds (mode="drop").
        i = jnp.arange(n, dtype=jnp.int32)
        if spec.engine == "ring":
            # Ring slots are consecutive mod capacity: element i collides
            # only with i + capacity, i + 2·capacity, …  → O(n).
            is_last = i + spec.capacity >= n
        else:
            # Hash batches are per-step rank sends (small n); the [n, n]
            # mask is over the *batch*, never over capacity.
            later_dup = (slots[None, :] == slots[:, None]) \
                & (i[None, :] > i[:, None])
            is_last = ~jnp.any(later_dup, axis=1)
        slots = jnp.where(is_last, slots, spec.capacity)
    return TableState(
        slab=state.slab.at[slots].set(values, mode="drop"),
        keys=state.keys.at[slots].set(keys, mode="drop"),
        version=state.version.at[slots].set(stamps, mode="drop"),
        ptr=new_ptr,
        count=state.count + n,
    )


put_many = partial(jax.jit, static_argnums=0, donate_argnums=1)(put_many_impl)


@_put_scope
def put_masked_impl(spec: TableSpec, state: TableState, keys, values,
                    mask) -> TableState:
    """Vectorized put of the *masked subset* of a chunk, in chunk order.

    ``keys [n]`` / ``values [n, *shape]`` / ``mask [n]`` — exactly the
    elements with ``mask`` set are inserted, equivalent to replaying their
    single ``put`` verbs in order (ring slot assignment, version stamps,
    ``count`` bumps and **last-writer-wins** collisions all match the
    sequential reference; unmasked elements advance nothing).

    This is the db-mesh half of the clustered fused put: a
    :func:`capture_scan_collect` chunk — whose emit mask may be traced
    (bucketed tails, ``emit_every`` gating against a traced ``t0``) — is
    staged across the interconnect once and inserted in ONE dispatch.

    Replay safety (``core.faults``): last-writer-wins does NOT make this
    op idempotent — ``ptr``/``count`` advance on every apply, so applying
    the same chunk twice corrupts the ring bookkeeping.  Exactly-once
    delivery therefore lives a level up: the server deduplicates repeated
    chunk ids (``StoreServer.apply_chunk``) and its restart recovery
    *replays* the write-ahead log — the same chunks, in the same order,
    against the same snapshot base.  Because this op is a pure function of
    ``(state, chunk)``, that replay reproduces the pre-crash table
    byte-identically: determinism, not idempotence, carries the proof.
    """
    keys = jnp.asarray(keys, KEY_DTYPE)
    values = jnp.asarray(values, dtype=spec.dtype)
    mask = jnp.asarray(mask, bool)
    n = keys.shape[0]
    if values.shape != (n, *spec.shape):
        raise ValueError(
            f"put_masked into {spec.name!r}: values {values.shape} != "
            f"({n}, *{spec.shape})"
        )
    r = jnp.cumsum(mask.astype(jnp.int32)) - 1   # emission rank (masked)
    total = jnp.sum(mask.astype(jnp.int32))
    if spec.engine == "ring":
        slots = (state.ptr + r) % spec.capacity
        new_ptr = (state.ptr + total) % spec.capacity
        # Masked elements occupy consecutive ring positions: rank r is
        # overwritten only by rank r + capacity, r + 2·capacity, … → O(n).
        is_last = r + spec.capacity >= total
    else:
        slots = (keys % jnp.uint32(spec.capacity)).astype(jnp.int32)
        new_ptr = state.ptr
        i = jnp.arange(n, dtype=jnp.int32)
        # Last masked writer per slot via scatter-max — O(n + capacity),
        # not the [n, n] pairwise mask (n here is a whole fused chunk,
        # not one step's rank batch).  Unmasked elements dump into the
        # extra bucket at index `capacity`.
        dump = jnp.where(mask, slots, spec.capacity)
        last = jnp.full((spec.capacity + 1,), -1, jnp.int32).at[dump].max(i)
        is_last = last[dump] == i
    stamps = state.count + 1 + r
    slots = jnp.where(mask & is_last, slots, spec.capacity)
    return TableState(
        slab=state.slab.at[slots].set(values, mode="drop"),
        keys=state.keys.at[slots].set(keys, mode="drop"),
        version=state.version.at[slots].set(stamps, mode="drop"),
        ptr=new_ptr,
        count=state.count + total,
    )


put_masked = partial(jax.jit, static_argnums=0, donate_argnums=1)(
    put_masked_impl)


@_put_scope
def put_stream_impl(spec: TableSpec, state: TableState, keys, values
                    ) -> TableState:
    """Fold a whole trajectory of sends into one dispatch.

    ``keys [T]`` / ``values [T, *shape]`` — T single-element steps — or
    ``keys [T, R]`` / ``values [T, R, *shape]`` — T steps of R ranks each.
    Equivalent to the corresponding sequence of ``put``/``put_many`` calls
    (time-major order; last-writer-wins on slot collisions), in a single
    device dispatch instead of T.
    """
    keys = jnp.asarray(keys, KEY_DTYPE)
    values = jnp.asarray(values, dtype=spec.dtype)
    if keys.ndim == 2:
        t, r = keys.shape
        keys = keys.reshape(t * r)
        values = values.reshape(t * r, *values.shape[2:])
    return put_many_impl(spec, state, keys, values)


put_stream = partial(jax.jit, static_argnums=0, donate_argnums=1)(
    put_stream_impl)


@partial(jax.jit, static_argnums=0)
def get(spec: TableSpec, state: TableState, key):
    """Fetch by key.  Returns ``(value, found)``; value is zeros if absent.

    ``EMPTY_KEY`` is reserved (never found) — same contract as the
    batched probe path.
    """
    key = jnp.asarray(key, KEY_DTYPE)
    match = (state.keys == key) & (state.version > 0)
    found = jnp.any(match) & (key != EMPTY_KEY)
    idx = jnp.argmax(match).astype(jnp.int32)
    value = jax.lax.dynamic_index_in_dim(state.slab, idx, 0, keepdims=False)
    value = jnp.where(found, value, jnp.zeros_like(value))
    return value, found


def get_many_impl(spec: TableSpec, state: TableState, keys,
                  mode: str | None = None):
    """Vectorized get.  Returns ``(values [n,*shape], founds [n])``.

    Routed through the fused probe+gather kernels (``repro.kernels.store``):
    a blocked pass over slot metadata resolves each key to its first valid
    slot, then a row gather fetches the slab — no ``[n, capacity]`` match
    matrix is ever materialized.  Duplicate keys resolve to the lowest slot
    (the historical behavior).
    """
    keys = jnp.asarray(keys, KEY_DTYPE)
    idx, founds = _kops.probe_slots(state.keys, state.version, keys, mode)
    safe = jnp.minimum(idx, spec.capacity - 1)
    values = _kops.gather_rows(state.slab, safe, mode)
    values = jnp.where(
        founds.reshape((-1,) + (1,) * len(spec.shape)), values, 0
    ).astype(spec.dtype)
    return values, founds


get_many = partial(jax.jit, static_argnums=(0, 3))(get_many_impl)


def serve_batch_impl(req_spec: TableSpec, res_spec: TableSpec, apply_fn,
                     req_state: TableState, res_state: TableState,
                     params, keys, mask):
    """Fused serving dispatch: gather requests → model → scatter results.

    One traced program covers a whole drained serving batch — the batched
    probe+gather over the request table, a ``vmap`` of the single-element
    ``apply_fn(params, x)`` registry function, and the masked insert into
    the results table — so each batch costs O(1) host dispatches regardless
    of how many ring slots are active.

    ``mask`` is the host-known active-slot mask; insertion uses it directly
    (not ``found & mask``) so a WAL replay of ``(keys, ys, mask)`` via the
    ``put_masked`` path reproduces the insert byte-identically.  Returns
    ``(new_res_state, found & mask, ys)`` — the second element flags slots
    whose request key was actually present.
    """
    keys = jnp.asarray(keys, KEY_DTYPE)
    mask = jnp.asarray(mask, bool)
    xs, found = get_many_impl(req_spec, req_state, keys)
    ys = jnp.asarray(
        jax.vmap(lambda x: apply_fn(params, x))(xs), res_spec.dtype)
    new_res = put_masked_impl(res_spec, res_state, keys, ys, mask)
    return new_res, found & mask, ys


serve_batch = partial(jax.jit, static_argnums=(0, 1, 2),
                      donate_argnums=4)(serve_batch_impl)


def sample_impl(spec: TableSpec, state: TableState, rng, n: int,
                mode: str | None = None):
    """Uniformly sample ``n`` valid elements (with replacement).

    This is the in-situ data loader: the paper's ML ranks "retrieve multiple
    tensors from the database at random" before each epoch.
    Returns ``(values [n,*shape], keys [n], ok)`` where ``ok`` is False if
    the table is empty (values are zeros then).

    A single pass over slot metadata (cumulative valid count + blocked
    rank-to-slot search in ``repro.kernels.store``) replaces the former
    ``-inf``-logits ``categorical``, which materialized an
    ``[n, capacity]`` Gumbel matrix.
    """
    nvalid = jnp.sum((state.version > 0).astype(jnp.int32))
    ok = nvalid > 0
    ranks = jax.random.randint(rng, (n,), 0, jnp.maximum(nvalid, 1))
    slots = _kops.sample_slots(state.version, ranks, mode)
    slots = jnp.minimum(slots, spec.capacity - 1)
    values = _kops.gather_rows(state.slab, slots, mode)
    values = jnp.where(ok, values,
                       jnp.zeros((n, *spec.shape), spec.dtype))
    return values.astype(spec.dtype), state.keys[slots], ok


sample = partial(jax.jit, static_argnums=(0, 3, 4))(sample_impl)


def sample_sharded_impl(spec: TableSpec, state: TableState, rng, n: int,
                        axis: str, mode: str | None = None):
    """Slab-sharded form of :func:`sample_impl`, for use *inside* a
    ``shard_map`` whose in-spec partitions the slab's slot axis over mesh
    axis ``axis`` (``parallel.sharding.slab_sharding`` placement).

    ``state.slab`` here is the rank's LOCAL shard ``[capacity/D, *shape]``
    while the per-slot metadata (``keys``/``version``) and cursors stay
    replicated, so slot selection is identical replicated compute on every
    rank.  Each rank then gathers only the slots it owns
    (``kernels.store.gather_rows_sharded`` — zeros elsewhere) and one
    ``lax.psum`` over ``axis`` reassembles the batch: the cross-rank
    mini-batch assembly becomes an explicit, HLO-countable collective
    instead of an implicit replicated slab read, and per-device slab
    memory drops from O(capacity) to O(capacity/D).  Every slot has
    exactly one owner, so the psum adds zeros to the owned row —
    bit-identical to the replicated gather.

    Returns ``(values [n,*shape], keys [n], ok)`` like ``sample_impl``.
    """
    local_cap = state.slab.shape[0]
    nvalid = jnp.sum((state.version > 0).astype(jnp.int32))
    ok = nvalid > 0
    ranks = jax.random.randint(rng, (n,), 0, jnp.maximum(nvalid, 1))
    slots = _kops.sample_slots(state.version, ranks, mode)
    slots = jnp.minimum(slots, spec.capacity - 1)
    offset = jax.lax.axis_index(axis) * local_cap
    local = _kops.gather_rows_sharded(state.slab, slots, offset, mode)
    values = jax.lax.psum(local, axis)
    values = jnp.where(ok, values,
                       jnp.zeros((n, *spec.shape), spec.dtype))
    return values.astype(spec.dtype), state.keys[slots], ok


@partial(jax.jit, static_argnums=(0, 2))
def latest(spec: TableSpec, state: TableState, n: int):
    """The ``n`` most recently written elements (newest first).

    Returns ``(values [n,*shape], keys [n], valid [n])``.
    """
    _, slots = jax.lax.top_k(state.version, n)
    vals = state.slab[slots]
    return vals, state.keys[slots], state.version[slots] > 0


@partial(jax.jit, static_argnums=0)
def poll(spec: TableSpec, state: TableState, key) -> jax.Array:
    """Does ``key`` exist?  (SmartRedis ``poll_tensor`` single check.)
    ``EMPTY_KEY`` is reserved — never reported present."""
    key = jnp.asarray(key, KEY_DTYPE)
    return jnp.any((state.keys == key) & (state.version > 0)) \
        & (key != EMPTY_KEY)


@partial(jax.jit, static_argnums=0, donate_argnums=1)
def delete(spec: TableSpec, state: TableState, key) -> TableState:
    """Tombstone every slot holding ``key`` (slab data left in place)."""
    match = (state.keys == jnp.asarray(key, KEY_DTYPE))
    return state._replace(
        version=jnp.where(match, 0, state.version),
        keys=jnp.where(match, EMPTY_KEY, state.keys),
    )


@partial(jax.jit, static_argnums=0)
def valid_count(spec: TableSpec, state: TableState) -> jax.Array:
    return jnp.sum(state.version > 0)


# ---------------------------------------------------------------------------
# Fused producer/consumer steps (the in-situ capture fast path)
# ---------------------------------------------------------------------------

#: The data plane's bucket floor: the smallest power-of-two bucket a fused
#: chunk pads to.  THE single source — the plan's ``default_chunk`` /
#: autotuner derive their floors from this constant instead of re-deriving
#: an ``8`` of their own, so predicted compile-cache hits cannot drift
#: from actual bucketing.
MIN_BUCKET = 8


def bucket_length(length: int, min_bucket: int = MIN_BUCKET) -> int:
    """Round a chunk length up to the next power-of-two bucket.

    Chunked ``capture_scan`` drivers compile one executable per distinct
    static ``length``; a run whose tail chunk differs from the body chunk
    therefore compiles twice (and sweeps over ``sim_steps`` compile once per
    distinct tail).  Bucketing pads the tail to the nearest power of two
    ``>= min_bucket`` and masks the padded steps with a traced ``valid``
    count, so each (table, bucket) pair compiles exactly once.
    """
    if length < 1:
        raise ValueError("length must be >= 1")
    n = max(length, min_bucket)
    return 1 << (n - 1).bit_length()


def _constrain_elem(value, elem_sharding, lead: int = 0):
    """Pin an emitted element (with ``lead`` stacked leading axes) to the
    producer's element sharding, so a sharded solver's put stays a
    shard-local slab update instead of funneling through one device.
    ``elem_sharding`` is a ``NamedSharding`` over the element dims only;
    ``None`` is the un-sharded fast path (no constraint inserted)."""
    if elem_sharding is None:
        return value
    from jax.sharding import NamedSharding, PartitionSpec
    ns = NamedSharding(elem_sharding.mesh,
                       PartitionSpec(*([None] * lead), *elem_sharding.spec))
    return jax.lax.with_sharding_constraint(value, ns)


def capture_scan_impl(spec: TableSpec, state: TableState,
                      step_fn: Callable, carry, length: int,
                      emit_every: int = 1, t0=0, valid=None,
                      elem_sharding=None):
    """Fold ``length`` producer steps and their puts into ONE dispatch.

    ``step_fn(carry, t) -> (carry, key, value)`` is the producer's
    jit-traceable step (solver advance + snapshot).  Steps where
    ``t % emit_every == 0`` put their value into the table; ``t`` runs over
    ``t0 .. t0+length-1`` (``t0`` may be a traced array, so chunked drivers
    reuse one compiled executable across chunks).

    ``valid`` (traced, defaults to ``length``) gates chunk-length bucketing:
    scan iterations ``i >= valid`` are complete no-ops — neither the carry
    nor the table advances — so a tail of any length can run under the
    executable compiled for its power-of-two bucket (``bucket_length``).

    Emitted puts land in ring order exactly as the equivalent sequence of
    single ``put`` verbs would; if more than ``capacity`` steps emit within
    one call, slot collisions resolve **last-writer-wins** (the overwrite
    still bumps ``count``), identical to the sequential reference.

    The multi-rank form is :func:`capture_scan_multi`.

    Returns ``(state, carry)``.  The number of puts is static given the
    *valid* length — use ``capture_emit_count`` to bump the server's cached
    watermark on commit.
    """
    def step(sc, t):
        st, c = sc
        c, key, value = step_fn(c, t)
        value = _constrain_elem(value, elem_sharding)
        st = jax.lax.cond(
            t % emit_every == 0,
            lambda s: put_impl(spec, s, key, value),
            lambda s: s,
            st,
        )
        return st, c

    ts = jnp.asarray(t0, jnp.int32) + jnp.arange(length, dtype=jnp.int32)
    if valid is None:
        def body(sc, t):
            return step(sc, t), None
        xs = ts
    else:
        valid = jnp.asarray(valid, jnp.int32)

        def body(sc, it):
            i, t = it
            return jax.lax.cond(i < valid, step, lambda sc, _t: sc, sc, t), \
                None
        xs = (jnp.arange(length, dtype=jnp.int32), ts)
    (state, carry), _ = jax.lax.scan(body, (state, carry), xs)
    return state, carry


capture_scan = partial(jax.jit, static_argnums=(0, 2, 4, 5),
                       static_argnames=("elem_sharding",),
                       donate_argnums=1)(capture_scan_impl)


def capture_emit_count(length: int, emit_every: int = 1, t0: int = 0) -> int:
    """Host-side count of puts a ``capture_scan`` call will perform."""
    return sum(1 for t in range(t0, t0 + length) if t % emit_every == 0)


def capture_scan_multi_impl(spec: TableSpec, state: TableState,
                            step_fn: Callable, carry, length: int,
                            n_ranks: int, emit_every: int = 1, t0=0,
                            valid=None, elem_sharding=None):
    """Multi-producer :func:`capture_scan`: ``n_ranks`` producers advance in
    lockstep for ``length`` steps inside ONE dispatch.

    ``step_fn(carry_r, rank, t) -> (carry_r, key, value)`` is a *single
    rank's* jit-traceable step; it is ``vmap``-ped over the leading ``[R]``
    axis of ``carry`` (every leaf of the carry pytree stacks the per-rank
    solver states).

    ``t0`` may be a scalar or a per-rank ``[R]`` array: each rank's clock
    runs over ``t0_r .. t0_r+length-1``, so restarted or staggered ranks
    interleave their keys into the same ring.  Emission is gated on rank
    0's clock (``(t0_0 + i) % emit_every == 0``): the paper's simulation
    ranks send each sampled step together, so staggered ``t0`` offsets
    shift the *keys*, never the cadence.

    Each emitting step writes all ``n_ranks`` snapshots with one
    ``put_many`` — rank-major within the step, byte-identical to ``R``
    sequential per-verb ``put`` calls (including ring wrap-around and
    last-writer-wins slot collisions when ``R`` exceeds ``capacity``).

    ``valid`` gates chunk-length bucketing exactly as in
    :func:`capture_scan_impl`: iterations ``i >= valid`` advance nothing.

    Returns ``(state, carry)``.  The put count is static given the valid
    length — commit with ``puts=capture_emit_count_multi(...)`` to keep the
    server's cached watermark exact.
    """
    ranks = jnp.arange(n_ranks, dtype=jnp.int32)
    t0_arr = jnp.broadcast_to(jnp.asarray(t0, jnp.int32), (n_ranks,))

    def step(sc, i):
        st, c = sc
        ts = t0_arr + i
        c, keys, values = jax.vmap(step_fn, in_axes=(0, 0, 0))(c, ranks, ts)
        values = _constrain_elem(values, elem_sharding, lead=1)
        st = jax.lax.cond(
            ts[0] % emit_every == 0,
            lambda s: put_many_impl(spec, s, keys, values),
            lambda s: s,
            st,
        )
        return st, c

    steps = jnp.arange(length, dtype=jnp.int32)
    if valid is None:
        def body(sc, i):
            return step(sc, i), None
    else:
        valid = jnp.asarray(valid, jnp.int32)

        def body(sc, i):
            return jax.lax.cond(i < valid, step, lambda sc, _i: sc, sc, i), \
                None
    (state, carry), _ = jax.lax.scan(body, (state, carry), steps)
    return state, carry


capture_scan_multi = partial(jax.jit, static_argnums=(0, 2, 4, 5, 6),
                             static_argnames=("elem_sharding",),
                             donate_argnums=1)(capture_scan_multi_impl)


def capture_emit_count_multi(n_ranks: int, length: int, emit_every: int = 1,
                             t0: int = 0) -> int:
    """Host-side count of puts a ``capture_scan_multi`` call will perform.

    ``t0`` is rank 0's start offset (the emission gate's clock)."""
    return n_ranks * capture_emit_count(length, emit_every, t0)


def capture_rows(length: int, emit_every: int = 1) -> int:
    """Static bound on the emissions of one collect chunk: the most
    multiples of ``emit_every`` any ``length``-step window can contain
    (the ``t0`` phase decides floor vs ceil; the buffer takes the ceil)."""
    return -(-length // emit_every)


def capture_scan_collect_impl(spec: TableSpec, step_fn: Callable, carry,
                              length: int, emit_every: int = 1, t0=0,
                              valid=None, elem_sharding=None):
    """Producer half of the *clustered* fused put: run ``length`` steps in
    ONE dispatch and **collect** the would-be puts instead of applying
    them.

    Same step/emission/bucketing semantics as :func:`capture_scan_impl`,
    but no table state is touched — emitting steps accumulate their
    ``(key, value)`` into a compact ``rows = capture_rows(length,
    emit_every)`` buffer rides in the scan carry, so the staged payload
    scales with the *emissions*, not the raw step count (a sparse
    ``emit_every`` never ships zero rows across the interconnect).  The
    caller then moves the chunk across in ONE staged transfer
    (``Deployment.stage_chunk``) and inserts it with ONE
    :func:`put_masked` dispatch on the store mesh — so a clustered fused
    producer costs one cross-mesh hop per chunk, not one per element.

    Returns ``(carry, keys [rows], values [rows, *shape], mask [rows])``
    — ``mask`` is the filled prefix; replaying the masked elements in
    order is byte-identical to the equivalent :func:`capture_scan`.
    """
    rows = capture_rows(length, emit_every)

    def live(st, i, t):
        c, keys_buf, vals_buf, cursor = st
        c, key, value = step_fn(c, t)
        value = _constrain_elem(jnp.asarray(value, spec.dtype),
                                elem_sharding)
        if value.shape != spec.shape:
            raise ValueError(
                f"capture into table {spec.name!r}: value shape "
                f"{value.shape} != element shape {spec.shape}")
        emit = t % emit_every == 0
        idx = jnp.where(emit, cursor, rows)      # non-emitting: dropped
        keys_buf = keys_buf.at[idx].set(jnp.asarray(key, KEY_DTYPE),
                                        mode="drop")
        vals_buf = vals_buf.at[idx].set(value, mode="drop")
        return c, keys_buf, vals_buf, cursor + emit.astype(jnp.int32)

    def dead(st, i, t):
        return st

    ts = jnp.asarray(t0, jnp.int32) + jnp.arange(length, dtype=jnp.int32)
    its = (jnp.arange(length, dtype=jnp.int32), ts)
    if valid is None:
        def body(st, it):
            return live(st, *it), None
    else:
        valid = jnp.asarray(valid, jnp.int32)

        def body(st, it):
            i, t = it
            return jax.lax.cond(i < valid, live, dead, st, i, t), None
    st0 = (carry, jnp.zeros((rows,), KEY_DTYPE),
           _constrain_elem(jnp.zeros((rows, *spec.shape), spec.dtype),
                           elem_sharding, lead=1),
           jnp.zeros((), jnp.int32))
    (carry, keys, values, cursor), _ = jax.lax.scan(body, st0, its)
    return carry, keys, values, jnp.arange(rows, dtype=jnp.int32) < cursor


capture_scan_collect = partial(jax.jit, static_argnums=(0, 1, 3, 4),
                               static_argnames=("elem_sharding",))(
    capture_scan_collect_impl)


def capture_scan_collect_multi_impl(spec: TableSpec, step_fn: Callable,
                                    carry, length: int, n_ranks: int,
                                    emit_every: int = 1, t0=0, valid=None,
                                    elem_sharding=None):
    """Multi-producer :func:`capture_scan_collect`: ``n_ranks`` producers
    advance in lockstep, collecting instead of putting (the clustered
    form of :func:`capture_scan_multi_impl` — same vmapped step, per-rank
    ``t0`` clocks, rank-0-gated emission, same compact
    ``rows = capture_rows(length, emit_every)`` buffering).

    Returns ``(carry, keys [rows·R], values [rows·R, *shape],
    mask [rows·R])`` flattened **rank-major within each emitting step**,
    so the masked replay is byte-identical to the in-scan ``put_many``
    path.
    """
    rows = capture_rows(length, emit_every)
    ranks = jnp.arange(n_ranks, dtype=jnp.int32)
    t0_arr = jnp.broadcast_to(jnp.asarray(t0, jnp.int32), (n_ranks,))

    def live(st, i):
        c, keys_buf, vals_buf, cursor = st
        ts = t0_arr + i
        c, keys, values = jax.vmap(step_fn, in_axes=(0, 0, 0))(c, ranks, ts)
        values = _constrain_elem(jnp.asarray(values, spec.dtype),
                                 elem_sharding, lead=1)
        if values.shape != (n_ranks, *spec.shape):
            raise ValueError(
                f"capture into table {spec.name!r}: rank values "
                f"{values.shape} != ({n_ranks}, *{spec.shape})")
        emit = ts[0] % emit_every == 0
        idx = jnp.where(emit, cursor, rows)      # non-emitting: dropped
        keys_buf = keys_buf.at[idx].set(jnp.asarray(keys, KEY_DTYPE),
                                        mode="drop")
        vals_buf = vals_buf.at[idx].set(values, mode="drop")
        return c, keys_buf, vals_buf, cursor + emit.astype(jnp.int32)

    def dead(st, i):
        return st

    steps = jnp.arange(length, dtype=jnp.int32)
    if valid is None:
        def body(st, i):
            return live(st, i), None
    else:
        valid = jnp.asarray(valid, jnp.int32)

        def body(st, i):
            return jax.lax.cond(i < valid, live, dead, st, i), None
    st0 = (carry, jnp.zeros((rows, n_ranks), KEY_DTYPE),
           _constrain_elem(jnp.zeros((rows, n_ranks, *spec.shape),
                                     spec.dtype), elem_sharding, lead=2),
           jnp.zeros((), jnp.int32))
    (carry, keys, values, cursor), _ = jax.lax.scan(body, st0, steps)
    mask = jnp.arange(rows, dtype=jnp.int32) < cursor
    return (carry, keys.reshape(rows * n_ranks),
            values.reshape(rows * n_ranks, *spec.shape),
            jnp.repeat(mask, n_ranks))


capture_scan_collect_multi = partial(jax.jit, static_argnums=(0, 1, 3, 4, 5),
                                     static_argnames=("elem_sharding",))(
    capture_scan_collect_multi_impl)


def make_clustered_gather(spec: TableSpec, n: int, db_mesh=None,
                          axis: str | None = None, shards: int = 1,
                          mode: str | None = None):
    """The db-mesh half of the clustered read path: ONE dispatch sampling
    ``n`` elements from the table on its own mesh.

    With ``shards > 1`` the slab is slot-partitioned over db-mesh axis
    ``axis`` and the gather runs shard-local with one explicit ``psum``
    (:func:`sample_sharded_impl` inside a ``shard_map`` over the db mesh
    — the same structure as the co-located slab-sharded tier, except the
    psum's reassembled batch then leaves the mesh: the cross-mesh staged
    transfer the caller performs and counts).  Otherwise the plain
    :func:`sample_impl` against the (possibly element-sharded) slab.

    Returns a jitted ``fn(state, rng) -> (values [n,*shape], ok)``.
    """
    if shards > 1:
        from jax import shard_map
        from jax.sharding import PartitionSpec as P
        specs = TableState(slab=P(axis), keys=P(), version=P(),
                           ptr=P(), count=P())

        def sharded_body(state, rng):
            vals, _, ok = sample_sharded_impl(spec, state, rng, n, axis,
                                              mode)
            return vals, ok

        return jax.jit(shard_map(sharded_body, mesh=db_mesh,
                                 in_specs=(specs, P()),
                                 out_specs=(P(), P()),
                                 check_vma=False))

    def body(state, rng):
        vals, _, ok = sample_impl(spec, state, rng, n, mode)
        return vals, ok

    return jax.jit(body)


def sample_and_step_impl(spec: TableSpec, state: TableState, rng, n: int,
                         step_fn: Callable, carry, mode: str | None = None):
    """Fused consumer step: gather ``n`` random elements AND run the
    training microstep ``step_fn(carry, values) -> (carry, aux)`` in one
    dispatch.  Returns ``(carry, aux, ok)``.

    The table state is only read — call under the table's capture/lock so
    the dispatch is ordered against donating producer puts.
    """
    values, _, ok = sample_impl(spec, state, rng, n, mode)
    carry, aux = step_fn(carry, values)
    return carry, aux, ok


sample_and_step = partial(jax.jit, static_argnums=(0, 3, 4, 6))(
    sample_and_step_impl)


# Non-jit convenience: functional update preserving NamedTuple type.
def _replace_state(state: TableState, **kw) -> TableState:
    return state._replace(**kw)
