"""Store deployment policies: co-located vs clustered (paper §2.3).

On Polaris the *co-located* deployment runs one database shard per compute
node (sharing the node with the simulation and ML ranks) so that every
send/retrieve stays on-node; the *clustered* deployment gives the database
dedicated nodes and pushes every transfer across the interconnect.

TPU-native translation:

* **Colocated(mesh, elem_spec)** — the store slab's element dims carry the
  *same PartitionSpec as the producer's output*.  A ``put`` of a
  producer-sharded tensor is then a per-device local slab update: the
  compiled HLO contains **zero collective ops** ("all data transfer is
  contained within each node").  The resource the store consumes is HBM
  (slots per chip) rather than CPU cores; ``hbm_budget`` mirrors the
  paper's Fig-3 core-count sweep.

* **Clustered(client_mesh, db_mesh, elem_spec)** — the store lives on a
  *dedicated* device subset (its own mesh).  ``stage`` moves a
  producer-mesh array onto the store mesh (``jax.device_put`` across
  meshes = the TCP transfer of the paper), and the many-clients-per-shard
  contention that wrecks the paper's clustered weak scaling shows up as a
  producer:db fan-in ratio.  ``slab_axis`` optionally partitions the
  slot axis over the db mesh — the slab-sharded *clustered* data plane
  (each db shard owns ``capacity/D`` slots, like the paper's sharded
  KeyDB run).

Both policies expose the same small interface consumed by the
``StoreServer``/``Client``:

    slab_sharding(spec)      -> sharding for the [capacity, *shape] slab
    elem_sharding(spec)      -> sharding of one element (``stage``'s target)
    stage(x, spec)           -> move one element onto the store placement
                                (identity when co-located and aligned)
    stage_batch(xs, spec)    -> move a [n, *shape] batch in ONE transfer
    stage_chunk(k, v, m, spec) -> move a whole fused-capture chunk
                                (keys + values + mask) in ONE transfer
    stage_to_clients(x)      -> the read-side hop back onto the clients
    crosses_mesh             -> does ``stage`` actually move bytes across
                                the interconnect? (drives the server's
                                staged-transfer telemetry)
    fan_in                   -> clients per store shard (1 for co-located)
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..parallel.sharding import data_mesh
from .faults import FaultPlan
from .store import TableSpec

__all__ = ["Deployment", "Colocated", "Clustered", "split_devices",
           "fan_in_ratio", "StagingPipeline",
           "make_colocated_1d", "make_clustered_1d", "make_clustered_2d"]


def fan_in_ratio(n_clients: int, n_db: int) -> int:
    """Clients per db shard — the paper's Fig.-5 contention knob.

    Ceiling division: 3 clients over 2 db shards load the busiest shard
    with 2, not 1 — the contention model cares about the *hottest* shard.
    This is THE single source both ``Clustered.fan_in`` and the plan's
    ``ComponentPlan.fan_in`` consult; floors at 1 when clients < shards.
    """
    return max(1, -(-int(n_clients) // max(1, int(n_db))))


def split_devices(devices=None, db_fraction: float = 0.25):
    """Split the available devices into (client, db) sets for Clustered.

    Mirrors the paper's node split (e.g. 448 sim + 16 DB nodes).  At least
    one device lands on each side; with a single device both sides share it
    (degenerate but keeps laptop-scale runs working).
    """
    devices = list(devices if devices is not None else jax.devices())
    if len(devices) == 1:
        return devices, devices
    n_db = max(1, int(round(len(devices) * db_fraction)))
    n_db = min(n_db, len(devices) - 1)
    return devices[:-n_db], devices[-n_db:]


class Deployment:
    """Interface; see module docstring."""

    #: clients per store shard — drives the clustered contention model.
    fan_in: int = 1
    #: does ``stage`` move bytes across the interconnect?  The server
    #: counts one staged transfer per stage call only when this is set.
    crosses_mesh: bool = False
    #: declared fault plan (``core.faults.FaultPlan``) — a server built on
    #: this deployment arms its injector + exactly-once machinery with it.
    faults: FaultPlan | None = None

    def slab_sharding(self, spec: TableSpec):
        raise NotImplementedError

    def elem_sharding(self, spec: TableSpec):
        raise NotImplementedError

    def stage(self, x, spec: TableSpec | None = None):
        raise NotImplementedError

    def stage_batch(self, values, spec: TableSpec | None = None):
        """Move a ``[n, *shape]`` batch onto the store placement in one
        transfer (leading batch axis never sharded by ``elem_spec``)."""
        raise NotImplementedError

    def stage_chunk(self, keys, values, mask, spec: TableSpec | None = None):
        """Move a whole fused-capture chunk (keys ``[n]``, values
        ``[n, *shape]``, emit mask ``[n]``) onto the store placement as
        ONE batched transfer."""
        raise NotImplementedError

    def stage_to_clients(self, x):
        """The read-side hop: move a gathered batch from the store
        placement back onto the consumers (identity when co-located)."""
        raise NotImplementedError

    def describe(self) -> str:
        raise NotImplementedError


@dataclass
class Colocated(Deployment):
    """Store sharded exactly like the producer output (on-node DB analogue).

    ``elem_spec`` is the PartitionSpec of one stored element; it must match
    the sharding the producer emits so that put/get are collective-free.
    ``capacity_axis`` optionally shards the slot axis too (spreading the
    ring across an unused mesh axis — beyond-paper, trades capacity for
    per-chip HBM).
    """

    mesh: Mesh
    elem_spec: P = P()
    capacity_axis: str | None = None

    fan_in: int = 1
    crosses_mesh: bool = False
    faults: FaultPlan | None = None

    def slab_sharding(self, spec: TableSpec) -> NamedSharding:
        return NamedSharding(self.mesh, P(self.capacity_axis, *self.elem_spec))

    def elem_sharding(self, spec: TableSpec) -> NamedSharding:
        return NamedSharding(self.mesh, self.elem_spec)

    def stage(self, x, spec: TableSpec | None = None):
        # Producer output is already placed correctly: zero-copy.  We do not
        # device_put here on purpose — a sharding mismatch should surface as
        # a collective in the compiled put (tests assert it does not).
        return x

    def stage_batch(self, values, spec: TableSpec | None = None):
        return values

    def stage_chunk(self, keys, values, mask, spec: TableSpec | None = None):
        return keys, values, mask

    def stage_to_clients(self, x):
        return x

    def describe(self) -> str:
        return (f"colocated(mesh={tuple(self.mesh.shape.items())}, "
                f"elem_spec={self.elem_spec})")


def _fit_spec(parts: Sequence, shape: Sequence[int], mesh: Mesh) -> P:
    """Drop mesh axes that do not divide their dim (device_put targets
    must divide exactly; GSPMD padding only applies to intermediates).
    An elem_spec LONGER than the element rank is a misconfiguration, not
    a fitting problem — keep it loud instead of silently truncating."""
    parts = tuple(parts)
    if len(parts) > len(shape):
        raise ValueError(
            f"elem_spec {parts} has more entries than the element rank "
            f"{len(shape)} (shape {tuple(shape)})")
    fitted = []
    for dim, entry in zip(shape, parts + (None,) * (len(shape) -
                                                    len(parts))):
        if entry is not None:
            axes = (entry,) if isinstance(entry, str) else tuple(entry)
            n = int(np.prod([mesh.shape[a] for a in axes]))
            if dim % n != 0:
                entry = None
        fitted.append(entry)
    return P(*fitted)


@dataclass
class Clustered(Deployment):
    """Store on dedicated devices; every transfer crosses the interconnect.

    ``elem_spec`` lays one element out across the db mesh; it is *fitted*
    per table — axes that do not divide the element dims fall back to
    replicated instead of silently mis-placing (``elem_sharding(spec)``).
    ``slab_axis`` names a db-mesh axis to partition the slot axis over:
    the slab-sharded clustered data plane (``capacity/D`` slots per db
    shard; falls back to an unpartitioned slab when capacity does not
    divide).  ``overlap`` enables the two-slot staging pipeline on the
    fused put path: chunk N's cross-mesh reshard rides the async dispatch
    queue while chunk N+1's collect-scan runs, and the masked insert of
    chunk N happens one capture later (drained explicitly at capture end
    and on fault-injected restage).
    """

    client_mesh: Mesh
    db_mesh: Mesh
    elem_spec: P = P()          # layout of an element across the db mesh
    slab_axis: str | None = None  # slot-partition the slab over this axis
    overlap: bool = True        # double-buffer the fused staging hop
    #: a fitted ``insitu.plan.ContentionModel`` (kept untyped — core must
    #: not import the plan layer).  When set, the session's plan autotunes
    #: the fused chunk from it and predicts producer steps/s per entry.
    cost_model: object | None = None

    crosses_mesh: bool = True
    faults: FaultPlan | None = None

    def __post_init__(self):
        n_clients = int(np.prod(list(self.client_mesh.shape.values())))
        n_db = int(np.prod(list(self.db_mesh.shape.values())))
        self.fan_in = fan_in_ratio(n_clients, n_db)
        if self.slab_axis is not None:
            used = {a for entry in self.elem_spec if entry is not None
                    for a in ((entry,) if isinstance(entry, str)
                              else entry)}
            if self.slab_axis in used:
                raise ValueError(
                    f"slab_axis {self.slab_axis!r} also appears in "
                    f"elem_spec {self.elem_spec}: a slot-partitioned "
                    f"slab keeps each element whole on its owning shard "
                    f"— use disjoint mesh axes")

    def _elem_spec_for(self, spec: TableSpec | None) -> P:
        if spec is None:
            return self.elem_spec
        return _fit_spec(self.elem_spec, spec.shape, self.db_mesh)

    def slab_shards(self, spec: TableSpec) -> int:
        """How many slot partitions the slab actually splits into (1 when
        ``slab_axis`` is unset or capacity does not divide)."""
        if self.slab_axis is None:
            return 1
        d = int(self.db_mesh.shape[self.slab_axis])
        return d if spec.capacity % d == 0 else 1

    def gather_shards(self, spec: TableSpec) -> int:
        """Shard count usable by the shard-local staged gather
        (``store.make_clustered_gather``): the slot-partition factor,
        but ONLY when the element dims are replicated on the db mesh —
        the sharded gather assumes local ``[capacity/D, *shape]`` rows.
        An element-sharded slab falls back to the plain gather (GSPMD
        handles any layout) rather than silently resharding the slab.
        This is THE rule both the server's runtime gather and the plan's
        ``plan(hlo=True)`` compile consult — keep it single-sourced."""
        if any(e is not None for e in self._elem_spec_for(spec)):
            return 1
        return self.slab_shards(spec)

    def slab_sharding(self, spec: TableSpec) -> NamedSharding:
        cap_axis = self.slab_axis if self.slab_shards(spec) > 1 else None
        return NamedSharding(self.db_mesh,
                             P(cap_axis, *self._elem_spec_for(spec)))

    def elem_sharding(self, spec: TableSpec) -> NamedSharding:
        return NamedSharding(self.db_mesh, self._elem_spec_for(spec))

    def stage(self, x, spec: TableSpec | None = None):
        """The cross-network hop: reshard from client mesh onto the db
        mesh, honoring the table's fitted element layout."""
        return jax.device_put(x, self.elem_sharding(spec))

    def stage_batch(self, values, spec: TableSpec | None = None):
        values = jnp.asarray(values)
        es = self._elem_spec_for(spec)
        # however many leading batch dims ride ahead of the element dims
        # (put_many sends [n, *shape]; put_stream may send [T, R, *shape]).
        # Without a spec the element rank is unknown — assume the
        # documented one-batch-dim contract rather than guessing from
        # elem_spec's length (which may be shorter than the element rank).
        lead = max(1, values.ndim - len(spec.shape)) if spec is not None \
            else 1
        sh = NamedSharding(self.db_mesh, P(*([None] * lead), *es))
        return jax.device_put(values, sh)

    def stage_chunk(self, keys, values, mask, spec: TableSpec | None = None,
                    donate: bool = False):
        """ONE batched cross-mesh reshard for a whole fused-capture chunk:
        the stacked values ride with their keys and emit mask in a single
        ``jax.device_put`` — this is the clustered fused put's only
        interconnect hop per dispatch.  ``device_put`` dispatches async;
        the transfer overlaps whatever the host enqueues next.
        ``donate=True`` (the overlap pipeline) releases the client-side
        collect buffers to the transfer — they are never read again (a
        fault-injected restage re-collects from the original carry)."""
        meta = NamedSharding(self.db_mesh, P())
        vsh = NamedSharding(self.db_mesh, P(None, *self._elem_spec_for(spec)))
        return jax.device_put((keys, values, mask), (meta, vsh, meta),
                              donate=donate)

    def stage_to_clients(self, x):
        """The read-side hop: a gathered batch (any pytree) leaves the db
        mesh for the consumers (replicated over the client mesh) in one
        batched ``device_put`` call."""
        sh = NamedSharding(self.client_mesh, P())
        return jax.device_put(x, jax.tree.map(lambda _: sh, x))

    def describe(self) -> str:
        return (f"clustered(clients={tuple(self.client_mesh.shape.items())}, "
                f"db={tuple(self.db_mesh.shape.items())}, "
                f"fan_in={self.fan_in}"
                + (", overlap" if self.overlap else "")
                + (f", slab_axis={self.slab_axis!r}"
                   if self.slab_axis else "") + ")")


class StagingPipeline:
    """Two-slot staging pipeline for the overlapped clustered put path.

    Slot A (held here) is the *in-flight* chunk: its cross-mesh
    ``stage_chunk`` transfer has been dispatched but its masked insert
    has not.  Slot B is the chunk currently being collected on the
    client mesh — it lives in the caller's hands until its own stage
    dispatch, at which point ``swap`` retires slot A for insertion and
    the freshly staged chunk becomes the new in-flight slot.  ``drain``
    empties slot A without refilling it (capture end, or the
    drain-on-restage flush after a fault-injected ``TransferDropped``).
    Insert order is therefore exactly the collect order — the ring's
    last-writer-wins semantics cannot observe the pipelining.
    """

    __slots__ = ("_in_flight",)

    def __init__(self):
        self._in_flight = None

    @property
    def pending(self) -> bool:
        return self._in_flight is not None

    def swap(self, staged):
        """Retire the in-flight slot (returning it for insertion, or
        ``None`` on the first chunk) and park ``staged`` in its place."""
        prev = self._in_flight
        self._in_flight = staged
        return prev

    def drain(self):
        """Empty the in-flight slot without refilling it."""
        prev = self._in_flight
        self._in_flight = None
        return prev


def make_colocated_1d(axis: str = "data", mesh: Mesh | None = None,
                      shard_dim: int = 0, ndim: int = 1,
                      faults: FaultPlan | None = None) -> Colocated:
    """Convenience: co-located deployment sharding element dim 0 over `axis`."""
    if mesh is None:
        mesh = data_mesh(axis=axis)
    spec = [None] * ndim
    spec[shard_dim] = axis
    return Colocated(mesh=mesh, elem_spec=P(*spec), faults=faults)


def make_clustered_1d(db_fraction: float = 0.25, axis: str = "data",
                      devices=None, elem_spec: P = P(),
                      slab_axis: str | None = None, overlap: bool = True,
                      faults: FaultPlan | None = None) -> Clustered:
    """Convenience: split the visible devices into client/db 1-D meshes
    (``split_devices``) and build the ``Clustered`` deployment over them.
    ``overlap=False`` restores the serial stage-then-insert put path
    (the pre-pipeline baseline the parity tests and benches compare
    against)."""
    client_devs, db_devs = split_devices(devices, db_fraction)
    return Clustered(
        client_mesh=Mesh(np.asarray(client_devs), (axis,)),
        db_mesh=Mesh(np.asarray(db_devs), (axis,)),
        elem_spec=elem_spec, slab_axis=slab_axis, overlap=overlap,
        faults=faults)


def make_clustered_2d(elem_spec: P, db_fraction: float = 0.5,
                      slab_axis: str = "slab", elem_axis: str = "space",
                      client_axis: str = "space", devices=None,
                      slab_shards: int | None = None, overlap: bool = True,
                      faults: FaultPlan | None = None) -> Clustered:
    """Clustered deployment over a 2-D **(slab, element)** db mesh.

    ``Clustered`` requires the slot partition and the element partition to
    live on *disjoint mesh axes* — on a 1-D db mesh that forces a choice
    between them.  This factory lifts that to both-at-once by reshaping
    the db devices into a ``(slab_shards, elem_shards)`` grid: the slot
    axis partitions over ``slab_axis`` (rows), each stored element lays
    out over ``elem_axis`` (columns) with ``elem_spec``, so a
    domain-decomposed producer's shard-local put stays shard-local *and*
    the slab still scales with capacity.  The client mesh is 1-D over
    ``client_axis`` — name it after the producer's mesh axis (default
    ``"space"``) so one ``elem_spec`` reads the same on both sides.

    ``slab_shards=None`` picks the largest split ≤ 2 that divides the db
    device count (1 when the pool is odd or a single device).
    """
    used = {a for entry in elem_spec if entry is not None
            for a in ((entry,) if isinstance(entry, str) else entry)}
    if slab_axis in used:
        raise ValueError(
            f"slab_axis {slab_axis!r} also appears in elem_spec "
            f"{elem_spec}: the 2-D db mesh gives the slot and element "
            f"partitions their own axes — put the element layout on "
            f"{elem_axis!r}")
    client_devs, db_devs = split_devices(devices, db_fraction)
    n_db = len(db_devs)
    if slab_shards is None:
        slab_shards = 2 if n_db % 2 == 0 and n_db >= 2 else 1
    if slab_shards < 1 or n_db % slab_shards != 0:
        raise ValueError(
            f"slab_shards={slab_shards} does not divide the {n_db}-device "
            f"db pool: the (slab, element) grid needs equal rows")
    db_grid = np.asarray(db_devs).reshape(slab_shards, n_db // slab_shards)
    return Clustered(
        client_mesh=Mesh(np.asarray(client_devs), (client_axis,)),
        db_mesh=Mesh(db_grid, (slab_axis, elem_axis)),
        elem_spec=elem_spec, slab_axis=slab_axis, overlap=overlap,
        faults=faults)
