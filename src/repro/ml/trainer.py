"""Distributed in-situ trainer (the paper's data-consumer component, §4).

Mirrors the paper's PyTorch-DDP training workload with the store-backed
data loader swapped in ("the distributed training application … gathers the
data before each epoch by simply modifying the existing dataloaders"):

* at the start of each epoch every ML rank gathers ``gather`` tensors from
  the store (paper: 6 = 24 sim ranks / 4 ML ranks per node), concatenates
  them, holds one out at random for validation (paper §4), and runs
  mini-batch SGD on the rest;
* Adam + MSE, lr = 1e-4 × n_ranks (paper's linear scaling rule);
* per-channel standardization statistics are computed from the first
  gathered snapshots and broadcast via store *metadata* (the paper's
  metadata transfers);
* component timers land in the same buckets as paper Table 2
  (client_init / metadata / retrieve / train).

Two execution tiers (``TrainerConfig.fused``):

* **fused** (default, beyond-paper): the whole epoch — store gather,
  normalization, held-out split, the mini-batch SGD scan, and validation —
  is ONE jitted dispatch against the checked-out table state
  (``Client.capture``).  O(1) dispatches per epoch instead of
  O(gather·batches), and the consumer holds the table lock only for the
  enqueue.
* **per-verb** (paper-fidelity): one client verb per gather + one dispatch
  per mini-batch, matching the paper's component-measurable loop.

DDP (``TrainerConfig.mesh``): the **sharded fused epoch** runs the whole
fused epoch — store gather, normalization, the mini-batch SGD scan with an
explicit gradient all-reduce, and validation — inside ONE ``shard_map``
over the mesh's ``data`` axis, so a multi-device epoch is still a single
dispatch.  Every rank derives the identical gather/permutation from the
shared epoch rng (replicated compute, cheap), takes its slice of each
mini-batch, and the per-rank gradients are combined with either an exact
fp32 ``psum`` (``ddp="psum"``, default) or the int8-compressed wire format
from ``parallel/compress.py`` (``ddp="int8"``, ≈¼ the interconnect bytes,
biased per step).  The paper's perfect train-scaling claim becomes a
structural property: dispatches/epoch stays O(1) at any mesh size.
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field
from functools import partial
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from jax import shard_map

from ..core import store as S
from ..core.client import Client
from ..parallel.compress import compressed_psum_mean, compressed_psum_mean_ef
from ..train import optimizer as opt
from . import autoencoder as ae

__all__ = ["TrainState", "TrainerConfig", "make_train_step",
           "make_fused_epoch", "make_sharded_fused_epoch",
           "make_clustered_sharded_epoch", "make_per_verb_epoch",
           "EPOCH_BUILDERS", "insitu_train", "EpochResult"]


class TrainState(NamedTuple):
    params: Any
    opt_state: Any
    step: jax.Array


@dataclass(frozen=True)
class TrainerConfig:
    """Consumer-loop configuration (paper §4 values as defaults).

    Fused-epoch knobs:

    * ``fused`` — run each epoch as ONE jitted dispatch against the
      checked-out table state (``Client.capture``): gather, normalization,
      held-out split, the mini-batch SGD scan and validation all fuse.
      ``False`` keeps the paper-fidelity per-verb loop (one dispatch per
      gather and per mini-batch) for component-level measurement.  The
      gather reads the table under the capture transaction, so producer
      puts racing the epoch keep per-verb semantics — batched ring writes
      resolve **last-writer-wins** (see ``core.store.put_many``), and the
      epoch sees either the pre- or post-chunk table, never a torn one.
    * ``mesh`` / ``mesh_axis`` — a device mesh turns the fused epoch into
      the *sharded* fused epoch: the same one-dispatch epoch inside a
      single ``shard_map`` over ``mesh_axis``, mini-batches sharded across
      ranks and gradients all-reduced every SGD microstep (DDP).
      ``batch_size`` must divide by the mesh-axis size.  Requires
      ``fused=True``.
    * ``ddp`` — gradient wire format on the mesh: ``"psum"`` (exact fp32
      all-reduce, bit-deterministic given fixed mesh) or ``"int8"``
      (``parallel.compress`` compressed all-reduce, ≈¼ the bytes, biased
      per step — validated to track the exact path in tests).
    * ``ddp_error_feedback`` — for ``ddp="int8"``: thread the quantization
      residual through the epoch scan's carry
      (``parallel.compress.compressed_psum_mean_ef``) so the compressed
      wire stops silently dropping what int8 rounded away.  Resets at each
      epoch boundary (the carry is per-dispatch state).
    * ``slab_sharded`` — slab-sharded *data plane*: the table slab enters
      the sharded fused epoch's ``shard_map`` already partitioned along
      the mesh axis (slot axis split ``capacity/D`` per rank,
      ``parallel.sharding.slab_sharding`` placement) instead of
      replicated.  The store gather becomes shard-local
      (``core.store.sample_sharded_impl``) with one explicit ``psum``
      reassembling each batch — no table all-gather on entry, per-device
      table memory O(capacity/D), results bit-identical to the
      replicated-entry tier.  Requires ``mesh`` and a table capacity
      divisible by the mesh-axis size.
    * ``db_mesh`` / ``db_axis`` — the slab-sharded *clustered* data
      plane (tier ``slab_sharded_clustered``): the table lives
      slot-partitioned on a *dedicated* db mesh (a ``Clustered``
      deployment's store devices; the session wires these from the
      deployment) while the trainer's ``shard_map`` runs on ``mesh``
      (the client devices).  Each epoch gathers ON the db mesh
      (shard-local rows + one explicit psum), moves the assembled batch
      across the interconnect in ONE counted staged transfer, and
      trains on the client mesh — the gather psum becomes an explicit
      cross-mesh hop instead of an implicit replication.  Requires
      ``slab_sharded=True`` and ``mesh``.
    """

    ae: ae.AEConfig
    epochs: int = 50
    gather: int = 6              # tensors gathered per rank per epoch (paper)
    batch_size: int = 4
    lr: float = 1e-4             # paper base lr, scaled by n_ranks
    n_ranks: int = 1
    min_snapshots: int = 1
    wait_timeout_s: float = 60.0
    table: str = "field"
    seed: int = 0
    fused: bool = True           # one-dispatch epochs via Client.capture
    mesh: Any = None             # device mesh -> sharded fused epoch (DDP)
    mesh_axis: str = "data"      # mesh axis the batch shards over
    ddp: str = "psum"            # "psum" (exact) | "int8" (compressed wire)
    ddp_error_feedback: bool = True   # int8: residual rides the scan carry
    slab_sharded: bool = False   # table enters the shard_map pre-sharded
    db_mesh: Any = None          # clustered: the store's dedicated mesh
    db_axis: str | None = None   # clustered: slot-partition axis on db_mesh

    def __post_init__(self):
        if self.ddp not in ("psum", "int8"):
            raise ValueError(f"unknown ddp mode {self.ddp!r}")
        if self.mesh is not None and not self.fused:
            raise ValueError("mesh-sharded training requires fused=True")
        if self.slab_sharded and self.mesh is None:
            raise ValueError("slab_sharded needs a mesh (the slab shards "
                             "over cfg.mesh_axis)")
        if self.db_mesh is not None and not self.slab_sharded:
            raise ValueError("db_mesh is the slab-sharded clustered data "
                             "plane; it needs slab_sharded=True")

    @property
    def scaled_lr(self) -> float:
        return self.lr * self.n_ranks   # paper's linear scaling rule


@dataclass
class EpochResult:
    epoch: int
    train_loss: float
    val_loss: float
    val_rel_error: float
    watermark: int


def make_train_step(cfg: TrainerConfig, levels, tx: opt.GradientTransformation):
    """jit'd (state, batch[B,N,C]) → (state, loss)."""

    return jax.jit(_microstep_fn(cfg, levels, tx))


def _microstep_fn(cfg: TrainerConfig, levels, tx: opt.GradientTransformation):
    """Raw (unjitted) SGD microstep, traceable inside the fused epoch."""

    def loss_fn(params, batch):
        return ae.loss_fn(params, cfg.ae, levels, batch)

    def step(state: TrainState, batch):
        loss, grads = jax.value_and_grad(loss_fn)(state.params, batch)
        updates, opt_state = tx.update(grads, state.opt_state, state.params)
        params = opt.apply_updates(state.params, updates)
        return TrainState(params, opt_state, state.step + 1), loss

    return step


def _epoch_data(cfg: TrainerConfig, spec: S.TableSpec, table_state, rng,
                mu, sd, sample: Callable | None = None):
    """The shared per-epoch data pipeline (traceable): random store gather,
    standardization, random held-out validation tensor, shuffled train set.

    Both the single-device fused epoch and the sharded fused epoch consume
    the epoch rng identically here, so a mesh run trains on exactly the
    same data stream as the single-device tier — the basis of the
    parity tests.  ``sample`` overrides the gather primitive (the
    slab-sharded tier passes ``store.sample_sharded_impl`` bound to its
    mesh axis; slot selection stays replicated compute, so the rng stream
    is untouched).  Returns ``(train [n_train,N,C], val [1,N,C], ok)``.
    """
    n_train = max(cfg.gather - 1, 1)
    k_samp, k_val, k_perm = jax.random.split(rng, 3)
    if sample is None:
        vals, _, ok = S.sample_impl(spec, table_state, k_samp, cfg.gather)
    else:
        vals, _, ok = sample(table_state, k_samp, cfg.gather)
    data = (vals.transpose(0, 2, 1) - mu) / sd              # [G, N, C]
    # hold one tensor out at random (paper §4); train on the rest
    val_idx = jax.random.randint(k_val, (), 0, cfg.gather)
    val = jax.lax.dynamic_index_in_dim(data, val_idx, 0, keepdims=True)
    if cfg.gather > 1:
        tr_idx = (val_idx + 1 + jnp.arange(cfg.gather - 1)) % cfg.gather
    else:
        tr_idx = jnp.zeros((1,), jnp.int32)
    train = data[tr_idx]
    train = train[jax.random.permutation(k_perm, n_train)]
    return train, val, ok


def make_fused_epoch(cfg: TrainerConfig, levels,
                     tx: opt.GradientTransformation, spec: S.TableSpec):
    """One-dispatch training epoch over the checked-out table state.

    Fuses the paper's per-epoch consumer sequence — random store gather,
    standardization, random held-out validation tensor, shuffled mini-batch
    SGD, validation metrics — into a single jitted function

        (table_state, train_state, rng, mu, sd)
            -> (train_state, (train_loss, val_loss, val_rel, ok))

    Mini-batches are equal-sized clipped windows over the shuffled train
    set (the final window is shifted back to full size when
    ``gather-1 % batch_size != 0``), so the SGD loop is a ``lax.scan``.
    """
    n_train = max(cfg.gather - 1, 1)
    bs = min(cfg.batch_size, n_train)
    n_batches = -(-n_train // bs)
    micro = _microstep_fn(cfg, levels, tx)

    @jax.jit
    def epoch(table_state: S.TableState, state: TrainState, rng, mu, sd):
        train, val, ok = _epoch_data(cfg, spec, table_state, rng, mu, sd)
        starts = jnp.clip(jnp.arange(n_batches) * bs, 0, n_train - bs)

        def body(ts, s):
            batch = jax.lax.dynamic_slice_in_dim(train, s, bs, 0)
            return micro(ts, batch)

        state, losses = jax.lax.scan(body, state, starts)
        rec = ae.reconstruct(state.params, cfg.ae, levels, val)
        val_loss = jnp.mean(jnp.square(rec - val))
        val_rel = ae.rel_frobenius(val, rec)
        return state, (jnp.mean(losses), val_loss, val_rel, ok)

    return epoch


def make_per_verb_epoch(cfg: TrainerConfig, levels,
                        tx: opt.GradientTransformation, spec: S.TableSpec):
    """The paper-fidelity epoch: identical math to :func:`make_fused_epoch`
    dispatched verb by verb.

    One client ``sample_batch`` (a store dispatch), one jitted data-prep
    dispatch, one jitted SGD dispatch per mini-batch, one validation
    dispatch — each component measurable in its own paper Table-2 bucket.
    The rng splits and the clipped equal-size mini-batch windows mirror
    ``_epoch_data`` and the fused scan exactly, so the per-verb tier and
    the fused tier train on bit-identical data in bit-identical order
    (the plan/tier parity suite asserts the resulting ``TrainState``
    matches bitwise).

    Returns ``epoch(client, state, rng, mu, sd) ->
    (state, (train_loss, val_loss, val_rel, ok))`` — the same metrics
    tuple as the fused builders, but driven through a live ``Client``
    instead of a checked-out table state.
    """
    n_train = max(cfg.gather - 1, 1)
    bs = min(cfg.batch_size, n_train)
    n_batches = -(-n_train // bs)
    micro = jax.jit(_microstep_fn(cfg, levels, tx))

    @jax.jit
    def prep(vals, k_val, k_perm, mu, sd):
        data = (vals.transpose(0, 2, 1) - mu) / sd          # [G, N, C]
        val_idx = jax.random.randint(k_val, (), 0, cfg.gather)
        val = jax.lax.dynamic_index_in_dim(data, val_idx, 0, keepdims=True)
        if cfg.gather > 1:
            tr_idx = (val_idx + 1 + jnp.arange(cfg.gather - 1)) % cfg.gather
        else:
            tr_idx = jnp.zeros((1,), jnp.int32)
        train = data[tr_idx]
        return train[jax.random.permutation(k_perm, n_train)], val

    @jax.jit
    def take_batch(train, s):
        return jax.lax.dynamic_slice_in_dim(train, s, bs, 0)

    @jax.jit
    def validate(params, val):
        rec = ae.reconstruct(params, cfg.ae, levels, val)
        return jnp.mean(jnp.square(rec - val)), ae.rel_frobenius(val, rec)

    starts = [min(i * bs, n_train - bs) for i in range(n_batches)]

    def epoch(client: Client, state: TrainState, rng, mu, sd):
        k_samp, k_val, k_perm = jax.random.split(rng, 3)
        vals, _, ok = client.sample_batch(cfg.table, cfg.gather, k_samp)
        train, val = prep(vals, k_val, k_perm, mu, sd)
        losses = []
        with client.timers.time("train"):
            for s in starts:
                state, loss = micro(state, take_batch(train, s))
                losses.append(loss)
            jax.block_until_ready(state.params)
        val_loss, val_rel = validate(state.params, val)
        return state, (jnp.mean(jnp.stack(losses)), val_loss, val_rel, ok)

    def warmup(state, mu, sd):
        """Pre-compile the per-verb dispatches on dummy data (no client,
        no store ops) so the timed loop measures dispatch, not compile —
        the same off-clock treatment the fused tiers get."""
        vals = jnp.zeros((cfg.gather, *spec.shape), spec.dtype)
        k = jax.random.key(0)
        train, val = prep(vals, k, k, mu, sd)
        s2, _ = micro(state, take_batch(train, starts[0]))
        jax.block_until_ready(validate(s2.params, val))

    epoch.warmup = warmup
    return epoch


def make_sharded_fused_epoch(cfg: TrainerConfig, levels,
                             tx: opt.GradientTransformation,
                             spec: S.TableSpec):
    """The fused epoch *and* DDP inside ONE ``shard_map`` over the mesh.

    Same signature and semantics as :func:`make_fused_epoch`, but the whole
    epoch body runs as a single SPMD program over ``cfg.mesh``'s
    ``cfg.mesh_axis`` (size D):

    * the gather / holdout / shuffle pipeline is computed redundantly on
      every rank from the shared epoch rng (replicated compute — it is a
      few permutations, while the gradient work dominates), so the global
      data order matches the single-device tier exactly;
    * each SGD microstep slices the rank's ``batch_size/D`` mini-batch
      shard, takes the local mean-loss gradient, and all-reduces it —
      exact fp32 ``psum`` or the int8-compressed wire
      (``parallel.compress.compressed_psum_mean``) per ``cfg.ddp``; with
      ``cfg.ddp_error_feedback`` the int8 quantization residual rides the
      scan carry (``compressed_psum_mean_ef``) instead of being dropped;
    * optimizer state stays replicated: every rank applies the identical
      synced gradient, so no post-hoc parameter broadcast is needed.

    One host dispatch per epoch regardless of mesh size — the paper's
    "perfect scaling of training" claim made structural.

    Data-plane entry (``cfg.slab_sharded``, tier ``"slab_sharded"``):

    * **replicated entry** (default, tier ``"sharded_fused"``): every
      operand — table state included — enters the ``shard_map``
      replicated, so each device holds the whole ``[capacity, *elem]``
      slab and a slab-sharded table is all-gathered on entry;
    * **slab-sharded entry**: the slab's in-spec partitions the slot axis
      over ``cfg.mesh_axis`` (matching the
      ``parallel.sharding.slab_sharding`` placement), metadata stays
      replicated, and the gather runs shard-local
      (``store.sample_sharded_impl``) with ONE explicit ``psum``
      reassembling each batch.  No table all-gather, per-device slab
      memory O(capacity/D), bit-identical results (each slot has exactly
      one owner, so the psum adds zeros to the owned row).
    """
    mesh = cfg.mesh
    if mesh is None:
        raise ValueError("make_sharded_fused_epoch needs cfg.mesh")
    axis = cfg.mesh_axis
    ndev = int(mesh.shape[axis])
    n_train = max(cfg.gather - 1, 1)
    bs = min(cfg.batch_size, n_train)
    if bs % ndev:
        raise ValueError(
            f"batch_size {bs} must divide by mesh axis {axis!r} size {ndev}")
    bl = bs // ndev
    n_batches = -(-n_train // bs)

    if cfg.slab_sharded:
        if spec.capacity % ndev:
            raise ValueError(
                f"slab-sharded entry needs capacity {spec.capacity} "
                f"divisible by mesh axis {axis!r} size {ndev}")
        sample = partial(S.sample_sharded_impl, spec, axis=axis)
        slab_spec = P(axis)
    else:
        sample = None
        slab_spec = P()

    run = _make_ddp_scan(cfg, levels, tx, axis, ndev, bs, n_train,
                         n_batches)

    def epoch_body(table_state: S.TableState, state: TrainState, rng,
                   mu, sd):
        train, val, ok = _epoch_data(cfg, spec, table_state, rng, mu, sd,
                                     sample=sample)
        return run(state, train, val, ok)

    table_specs = S.TableState(slab=slab_spec, keys=P(), version=P(),
                               ptr=P(), count=P())
    sharded = shard_map(epoch_body, mesh=mesh,
                        in_specs=(table_specs, P(), P(), P(), P()),
                        out_specs=(P(), P()),
                        check_vma=False)
    return jax.jit(sharded)


def _make_ddp_scan(cfg: TrainerConfig, levels, tx, axis: str, ndev: int,
                   bs: int, n_train: int, n_batches: int):
    """The DDP mini-batch SGD scan + validation, traceable inside a
    ``shard_map`` over mesh axis ``axis`` — the epoch half shared by
    :func:`make_sharded_fused_epoch` (gather in-dispatch) and
    :func:`make_clustered_sharded_epoch` (batch staged across meshes).

    Returns ``run(state, train, val, ok) -> (state, metrics)``.
    """
    bl = bs // ndev
    use_ef = cfg.ddp == "int8" and cfg.ddp_error_feedback

    def loss_fn(params, batch):
        return ae.loss_fn(params, cfg.ae, levels, batch)

    def run(state: TrainState, train, val, ok):
        starts = jnp.clip(jnp.arange(n_batches) * bs, 0, n_train - bs)
        ridx = jax.lax.axis_index(axis)

        def body(carry, s):
            ts, resid = carry
            batch = jax.lax.dynamic_slice_in_dim(train, s, bs, 0)
            local = jax.lax.dynamic_slice_in_dim(batch, ridx * bl, bl, 0)
            loss_l, grads_l = jax.value_and_grad(loss_fn)(ts.params, local)
            if use_ef:
                grads, resid = compressed_psum_mean_ef(grads_l, resid,
                                                       axis, ndev)
            elif cfg.ddp == "int8":
                grads = compressed_psum_mean(grads_l, axis, ndev)
            else:
                grads = jax.tree.map(
                    lambda g: jax.lax.psum(g, axis) / ndev, grads_l)
            loss = jax.lax.psum(loss_l, axis) / ndev
            updates, opt_state = tx.update(grads, ts.opt_state, ts.params)
            params = opt.apply_updates(ts.params, updates)
            return (TrainState(params, opt_state, ts.step + 1), resid), loss

        # Error feedback is per-dispatch state: the residual starts at zero
        # each epoch and lives only inside the scan carry.
        resid0 = jax.tree.map(jnp.zeros_like, state.params) if use_ef \
            else jnp.zeros(())
        (state, _), losses = jax.lax.scan(body, (state, resid0), starts)
        # validation is replicated compute (identical on every rank)
        rec = ae.reconstruct(state.params, cfg.ae, levels, val)
        val_loss = jnp.mean(jnp.square(rec - val))
        val_rel = ae.rel_frobenius(val, rec)
        return state, (jnp.mean(losses), val_loss, val_rel, ok)

    return run


def make_clustered_sharded_epoch(cfg: TrainerConfig, levels,
                                 tx: opt.GradientTransformation,
                                 spec: S.TableSpec):
    """The slab-sharded *clustered* tier: db mesh ≠ trainer mesh.

    The table slab lives slot-partitioned on the deployment's dedicated
    ``cfg.db_mesh`` (``Clustered(slab_axis=...)`` placement) while the
    trainer's DDP ``shard_map`` runs on ``cfg.mesh`` (the client
    devices), so one jitted program cannot span both.  Each epoch is
    therefore:

    1. ONE staged-gather store verb (``Client.sample_staged``): slot
       selection + shard-local row gather + the explicit batch-assembly
       ``psum`` run on the db mesh (``store.make_clustered_gather``), and
       the assembled ``[gather, *shape]`` batch crosses the interconnect
       in ONE counted staged transfer — the co-located tier's gather psum
       made an explicit cross-mesh hop;
    2. ONE client-mesh ``shard_map`` dispatch running the identical DDP
       epoch body (:func:`_make_ddp_scan`) on the staged batch.

    Client-driven signature like :func:`make_per_verb_epoch`:
    ``epoch(client, state, rng, mu, sd)``.  The epoch rng stream matches
    every other tier exactly — the staged gather consumes the same
    ``k_samp`` the fused tiers split off in ``_epoch_data``, so slot
    selection (and hence training data) is tier-independent.  One store
    dispatch per epoch; the db-side gather executable compiles lazily on
    the first epoch (server-side cache), charged to its retrieve bucket.
    """
    mesh = cfg.mesh
    if mesh is None:
        raise ValueError("make_clustered_sharded_epoch needs cfg.mesh")
    axis = cfg.mesh_axis
    ndev = int(mesh.shape[axis])
    n_train = max(cfg.gather - 1, 1)
    bs = min(cfg.batch_size, n_train)
    if bs % ndev:
        raise ValueError(
            f"batch_size {bs} must divide by mesh axis {axis!r} size {ndev}")
    n_batches = -(-n_train // bs)
    run = _make_ddp_scan(cfg, levels, tx, axis, ndev, bs, n_train,
                         n_batches)

    def train_body(vals, ok_in, state: TrainState, rng, mu, sd):
        # _epoch_data splits the same epoch rng; its k_samp was already
        # consumed by the staged gather, so the sample override just
        # injects the staged batch — identical stream to the fused tiers.
        train, val, ok = _epoch_data(
            cfg, spec, None, rng, mu, sd,
            sample=lambda _ts, _k, _n: (vals, None, ok_in))
        return run(state, train, val, ok)

    train_fn = jax.jit(shard_map(train_body, mesh=mesh,
                                 in_specs=(P(),) * 6,
                                 out_specs=(P(), P()),
                                 check_vma=False))

    def epoch(client: Client, state: TrainState, rng, mu, sd):
        k_samp = jax.random.split(rng, 3)[0]
        vals, ok = client.sample_staged(cfg.table, cfg.gather, k_samp)
        with client.timers.time("train"):
            state, metrics = train_fn(vals, ok, state, rng, mu, sd)
            jax.block_until_ready(state.params)
        return state, metrics

    def warmup(state, mu, sd):
        """Pre-compile the client-mesh half on a zero batch placed like
        the staged one (no store ops — dispatch accounting stays exact;
        the db-side gather compiles on the first real epoch)."""
        vals = jax.device_put(
            jnp.zeros((cfg.gather, *spec.shape), spec.dtype),
            NamedSharding(mesh, P()))
        jax.block_until_ready(
            train_fn(vals, jnp.asarray(True), state, jax.random.key(0),
                     mu, sd)[1])

    epoch.warmup = warmup
    epoch.train_fn = train_fn      # HLO accounting (plan(hlo=True))
    return epoch


#: Consumer tier -> epoch builder.  Tier *selection* is plan data
#: (``repro.insitu.plan.trainer_tier``); this table is the only place the
#: names meet code, so adding a tier is one entry, not another if-chain.
#: ``sharded_fused`` and ``slab_sharded`` share one builder — the entry
#: layout is read from ``cfg.slab_sharded``, which the tier rules keep
#: consistent with the tier name.
EPOCH_BUILDERS: dict[str, Callable] = {
    "fused": make_fused_epoch,
    "sharded_fused": make_sharded_fused_epoch,
    "slab_sharded": make_sharded_fused_epoch,
    "slab_sharded_clustered": make_clustered_sharded_epoch,
    "per_verb": make_per_verb_epoch,
}

#: tiers whose epoch is driven through a live ``Client`` (one verb per
#: component) instead of a fused capture against checked-out table state.
CLIENT_DRIVEN_TIERS = ("per_verb", "slab_sharded_clustered")


def _strong(x):
    """Drop weak types so the step-N state has the same avals as init
    (a weak-typed init leaf forces a silent recompile on the 2nd step)."""
    x = jnp.asarray(x)
    return jax.lax.convert_element_type(x, x.dtype)


def init_state(cfg: TrainerConfig, key, tx) -> TrainState:
    params = jax.tree.map(_strong, ae.init_autoencoder(key, cfg.ae))
    return TrainState(params=params,
                      opt_state=jax.tree.map(_strong, tx.init(params)),
                      step=jnp.zeros((), jnp.int32))


def _standardize_stats(batch: jax.Array):
    """Per-channel mean/std over [B,N,C] → ([C],[C])."""
    mu = jnp.mean(batch, axis=(0, 1))
    sd = jnp.std(batch, axis=(0, 1)) + 1e-6
    return mu, sd


def insitu_train(client: Client, coords: jax.Array, cfg: TrainerConfig,
                 stop_event=None,
                 on_epoch: Callable[[EpochResult], None] | None = None,
                 state: TrainState | None = None, tier: str | None = None,
                 memckpt=None, component: str | None = None,
                 on_checkpoint: Callable[[int, TrainState], None]
                 | None = None):
    """The consumer loop.  Returns (state, [EpochResult...], levels, stats).

    This is the runtime behind ``repro.insitu.InSituSession``'s
    ``TrainerConsumer`` (and the legacy direct entry point).  ``tier``
    names the execution tier — ``"fused"`` / ``"sharded_fused"`` /
    ``"per_verb"``, one key of :data:`EPOCH_BUILDERS`; when ``None`` it is
    resolved from ``cfg`` by ``repro.insitu.plan.trainer_tier`` (the same
    data-driven rule a session ``Plan`` records).  Every tier consumes the
    epoch rng identically and trains on the identical data stream, so tier
    choice is a deployment decision, not a numerics decision.

    The loop never blocks on the producer beyond ``wait_timeout_s``
    (straggler mitigation): it trains on whatever the store already holds.

    Fault tolerance: ``memckpt`` (a ``train.checkpoint.MemoryCheckpoint``)
    parks ``(state, rng, history)`` in store metadata after every epoch —
    and once before epoch 0, right after the norm-stats bootstrap — so a
    crashed trainer re-entering this function resumes at the first
    unfinished epoch with the identical rng stream (bit-identical final
    state vs an uncrashed run).  ``component`` names this consumer to the
    deployment's ``FaultPlan``: each epoch opens with a crash point the
    injector may fire exactly once.  Checkpoint traffic is host-side
    metadata — zero store dispatches, so crash/recovery never perturbs the
    plan's op-count predictions.

    ``on_checkpoint(epoch, state)`` fires at the end of every completed
    epoch, after its checkpoint save — the hot-swap publication hook (the
    session publishes versioned model generations from it).  Because the
    crash point opens an epoch and this hook closes one, a resumed run
    skips completed epochs and never re-fires their publications.
    """
    if tier is None:
        from ..insitu.plan import trainer_tier
        tier = trainer_tier(cfg)
    if tier not in EPOCH_BUILDERS:
        raise ValueError(f"unknown trainer tier {tier!r} "
                         f"(have {sorted(EPOCH_BUILDERS)})")
    levels = ae.coords_pyramid(cfg.ae, coords)
    tx = opt.adam(cfg.scaled_lr)
    resumed = memckpt.restore() if memckpt is not None else None
    if state is None and resumed is None:
        state = init_state(cfg, jax.random.key(cfg.seed), tx)
    epoch_fn = EPOCH_BUILDERS[tier](cfg, levels, tx,
                                    client.server.spec(cfg.table))
    # capture-driven tiers dispatch one fused epoch against checked-out
    # table state; client-driven tiers (per-verb, the clustered staged
    # gather) run their epoch through live store verbs instead.
    fused = tier not in CLIENT_DRIVEN_TIERS
    rng = jax.random.key(cfg.seed + 1)
    history: list[EpochResult] = []
    start_epoch = 0

    if resumed is not None:
        # --- crash recovery: pick up at the first unfinished epoch -------
        # The checkpoint was written after the bootstrap published the
        # norm stats, so the metadata read below always hits; no store
        # verbs are issued on this path (warmup reuses the in-process jit
        # cache, the wait/bootstrap already happened before the crash).
        saved_epoch, payload = resumed
        state = payload["state"]
        rng = payload["rng"]
        history = list(payload["history"])
        start_epoch = saved_epoch + 1
        mu, sd = client.get_metadata("norm_stats")
        if tier == "slab_sharded_clustered":
            sh = NamedSharding(cfg.mesh, P())
            mu, sd = jax.device_put(mu, sh), jax.device_put(sd, sh)
    else:
        # Paper: "the ML workload must query the database multiple times
        # while waiting for the first training snapshot".
        client.wait_for_data(cfg.table, minimum=cfg.min_snapshots,
                             timeout=cfg.wait_timeout_s)

        # Standardization stats from the first gather, published as
        # metadata.
        mu_sd = client.get_metadata("norm_stats")
        if mu_sd is None:
            rng, k = jax.random.split(rng)
            first, _, ok = client.sample_batch(cfg.table, cfg.gather, k)
            batch = first.transpose(0, 2, 1)        # [G, N, C]
            mu, sd = _standardize_stats(batch)
            client.put_metadata("norm_stats", (mu, sd))
            mu_sd = (mu, sd)
        mu, sd = mu_sd
        if tier == "slab_sharded_clustered":
            # The bootstrap stats were computed from a sample living on the
            # store's db mesh; pin them onto the trainer's client mesh so
            # the staged epoch stays a pure client-mesh program (one jitted
            # computation cannot span both device sets).
            sh = NamedSharding(cfg.mesh, P())
            mu, sd = jax.device_put(mu, sh), jax.device_put(sd, sh)

        if fused:
            # Warm the fused-epoch executable on a throwaway empty table so
            # the timed loop measures dispatch, not compilation (charged to
            # its own component bucket, like the paper's one-off model-load
            # cost).  The slab-sharded tier places the dummy like the live
            # table — jit caches on input shardings, so a replicated dummy
            # would compile a second executable the timed loop never uses.
            # (Every other tier keeps the dummy uncommitted: jit re-places
            # it freely, which is what the epoch does to the live
            # single-device state too.)
            with client.timers.time("jit_compile"):
                dummy_sharding = None
                if tier == "slab_sharded":
                    from ..parallel.sharding import slab_sharding
                    dummy_sharding = slab_sharding(
                        client.server.spec(cfg.table), cfg.mesh,
                        cfg.mesh_axis)
                dummy = S.init_table(client.server.spec(cfg.table),
                                     dummy_sharding)
                jax.block_until_ready(
                    epoch_fn(dummy, state, jax.random.key(0), mu, sd)[1])
        else:
            # The per-verb tier gets the same off-clock compile treatment.
            with client.timers.time("jit_compile"):
                epoch_fn.warmup(state, mu, sd)

        if memckpt is not None:
            # Anchor checkpoint: a crash at epoch 0 resumes here instead of
            # re-running the bootstrap (which would burn an extra sample
            # verb and fork the rng stream).
            memckpt.save(-1, {"state": state, "rng": rng, "history": []})

    epoch_timer_start = time.perf_counter()
    for epoch in range(start_epoch, cfg.epochs):
        if stop_event is not None and stop_event.is_set():
            break
        if component is not None:
            # Crash point: before the rng split, so a restarted epoch
            # re-derives the identical per-epoch key from the checkpoint.
            client.fault_point(component, epoch)
        rng, k_ep = jax.random.split(rng)
        if fused:
            # --- fused: ONE dispatch for gather + SGD + validation --------
            with client.timers.time("retrieve"):
                # Enqueue-only under the table lock (orders the read against
                # donating producer puts); blocking happens below.  Routed
                # through ``capture_epoch`` so a transient store-unavailable
                # window retries the read-only capture.
                prev = state
                state, metrics = client.capture_epoch(
                    cfg.table,
                    lambda txn: epoch_fn(txn.state, prev, k_ep, mu, sd))
            with client.timers.time("train"):
                jax.block_until_ready(state.params)
        else:
            # --- per-verb: same math, one dispatch per component ----------
            state, metrics = epoch_fn(client, state, k_ep, mu, sd)
        train_loss_t, val_loss_t, val_err_t, _ok = metrics
        res = EpochResult(epoch=epoch, train_loss=float(train_loss_t),
                          val_loss=float(val_loss_t),
                          val_rel_error=float(val_err_t),
                          watermark=client.watermark(cfg.table))
        history.append(res)
        if on_epoch is not None:
            on_epoch(res)
        if memckpt is not None:
            memckpt.save(epoch, {"state": state, "rng": rng,
                                 "history": list(history)})
        if on_checkpoint is not None:
            on_checkpoint(epoch, state)
    client.timers.record("total_training",
                         time.perf_counter() - epoch_timer_start)
    return state, history, levels, (mu, sd)
