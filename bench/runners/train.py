"""Runner of the ``train`` kind: in-situ training on one table.

One host thread interleaves a flat-plate producer chunk
(``Client.capture_scan``, ``chunk_snapshots`` solver steps, every one put
into the ring) with one fused training epoch read from the same table
(``Client.capture_epoch`` over ``trainer.make_fused_epoch``), and reads
each epoch's loss on the host, as the program's trainer loop does.  So the
data stream and the device schedule are the same on every run.

Set-up fills the ring with ``fill_snapshots`` and drives the first
``check_steps`` epochs through the window's own call; the reference
follows those epochs from the same seed (``reference_steps``).
"""

from __future__ import annotations

import math
import time

import numpy as np

from .. import compare
from .. import precision as P
from .. import generator as gen
from .. import harness, producer
from ..harness import Check, Outcome, span


class TrainCell:
    """The program's in-situ trainer and producer, built from the cell."""

    def __init__(self, ctx: harness.Context):
        import jax
        import jax.numpy as jnp
        from repro.core.client import Client
        from repro.core.server import StoreServer
        from repro.ml import autoencoder as ae
        from repro.ml import trainer as tr
        from repro.sim import flatplate as fp
        from repro.train import optimizer as opt

        self.ctx = ctx
        cfg, tf = ctx.cell.cfg, ctx.cell.traffic
        self.cfg, self.tf = cfg, tf
        self.ref = harness.reference(ctx.cell)
        aecfg = ae.AEConfig(
            n_points=cfg["n_points"], channels=cfg["channels"],
            internal=cfg["internal"], latent=cfg["latent"],
            blocks=cfg["blocks"], pool=cfg["pool"],
            mlp_width=cfg["mlp_width"], mlp_depth=cfg["mlp_depth"],
            support=cfg["support"])
        self.tcfg = tr.TrainerConfig(ae=aecfg, gather=tf["gather"],
                                     batch_size=tf["batch"], lr=tf["lr"])
        self.server = StoreServer()
        spec = producer.field_table(self.server, cfg)
        self.client = Client(self.server)
        self._step_fn = producer.step_fn(cfg, tf["solver_period"],
                                         ranked=False)
        self.producer_key = gen.producer_key(ctx.seed)
        self._carry = self.producer_key
        self.t = 0

        # inputs made by the benchmark: weights and standardisation
        self.params0 = self.ref.init_params(cfg, gen.weights_key(ctx.seed))
        g = tf["gather"]
        snaps = self.ref.make_snapshots(cfg)(
            _repeat_key(self.producer_key, g), jnp.arange(g, dtype=jnp.int32))
        data = snaps.transpose(0, 2, 1)
        self.mu = jnp.mean(data, axis=(0, 1))
        self.sd = jnp.std(data, axis=(0, 1)) + 1e-6

        self.tx = opt.adam(self.tcfg.scaled_lr)
        self.state = tr.TrainState(self.params0, self.tx.init(self.params0),
                                   jnp.zeros((), jnp.int32))
        levels = ae.coords_pyramid(aecfg, fp.grid_coords(
            producer.flatplate(cfg)))
        self._epoch_fn = tr.make_fused_epoch(self.tcfg, levels, self.tx, spec)
        n_train = max(tf["gather"] - 1, 1)
        bs = min(tf["batch"], n_train)
        self.samples_per_epoch = -(-n_train // bs) * bs
        self.epochs = 0

    def produce(self) -> None:
        n = self.tf["chunk_snapshots"]
        self._carry = self.client.capture_scan(producer.TABLE, self._step_fn,
                                               self._carry, n, t0=self.t)
        self.t += n

    def epoch(self):
        """One window iteration: a producer chunk, one fused epoch, the
        loss read on the host.  Returns the loss."""
        with span("producer"):
            self.produce()
        k = gen.epoch_key(self.ctx.seed, self.epochs)
        state = self.state
        with span("epoch"):
            self.state, metrics = self.client.capture_epoch(
                producer.TABLE, lambda txn: self._epoch_fn(
                    txn.state, state, k, self.mu, self.sd))
        with span("sync"):
            loss = float(metrics[0])
        self.epochs += 1
        return loss

    def fill(self) -> None:
        chunk = self.tf["chunk_snapshots"]
        for _ in range(self.tf["fill_snapshots"] // chunk):
            self.produce()

    def first_steps(self) -> dict:
        """Drive the first ``check_steps`` epochs and keep what the
        reference is compared with, on the host."""
        losses, mu1 = [], None
        for i in range(self.tf["check_steps"]):
            losses.append(self.epoch())
            if i == 0:
                mu1 = compare.leaves(self.state.opt_state.mu)
        return {"losses": losses, "mu1": mu1,
                "p0": compare.leaves(self.params0),
                "p3": compare.leaves(self.state.params)}

    def close(self) -> None:
        """Drop the program's state so that the reference can run."""
        self.state = self._epoch_fn = self.server = self.client = None
        self._carry = self.params0 = None


def _repeat_key(key, n: int):
    import jax.numpy as jnp
    return jnp.broadcast_to(key, (n,))


def reference_steps(ctx: harness.Context, arith: str = "float32",
                    keep: float = 1.0, data: str | None = None) -> dict:
    """The reference's first ``check_steps`` epochs from the same seed:
    the ring replayed (slot s holds solver step s), the program's rng
    contract for the draw, the reference arithmetic ``arith``.
    ``keep < 1`` plants a fault: each mini-batch's loss is the mean over
    its first ``keep`` share of samples only.  ``data``: the arithmetic of
    the replayed snapshots, by default the precision the configuration
    states for its producer (the ``capture`` cell holds the producer to
    it); the standardisation is the float32 one handed to the program."""
    import jax
    import jax.numpy as jnp
    cfg, tf = ctx.cell.cfg, ctx.cell.traffic
    ref = harness.reference(ctx.cell)
    pkey = gen.producer_key(ctx.seed)
    g, period = tf["gather"], tf["solver_period"]
    snap = ref.make_snapshots(cfg, data or P.stated(cfg))
    first = ref.make_snapshots(cfg)(_repeat_key(pkey, g),
                                    jnp.arange(g, dtype=jnp.int32))
    data0 = first.transpose(0, 2, 1)
    mu_n = jnp.mean(data0, axis=(0, 1))
    sd_n = jnp.std(data0, axis=(0, 1)) + 1e-6
    trainer = ref.Trainer(cfg, arith, keep)
    params = ref.init_params(cfg, gen.weights_key(ctx.seed))
    p0 = compare.leaves(params)
    mu = jax.tree.map(jnp.zeros_like, params)
    nu = jax.tree.map(jnp.zeros_like, params)
    count, losses, mu1 = 0, [], None
    for i in range(tf["check_steps"]):
        nvalid = tf["fill_snapshots"] + tf["chunk_snapshots"] * (i + 1)
        if nvalid > cfg["table"]["capacity"]:
            raise harness.BenchError("the checked steps must not wrap the ring")
        ranks, val_idx, perm = ref.epoch_batches(g, tf["batch"],
                                                 gen.epoch_key(ctx.seed, i),
                                                 nvalid)
        steps = jnp.asarray(ranks % period, jnp.int32)
        vals = snap(_repeat_key(pkey, g), steps)
        data = (vals.transpose(0, 2, 1) - mu_n) / sd_n
        params, mu, nu, loss, n = trainer.epoch(params, mu, nu, count, data,
                                                val_idx, perm, tf["batch"],
                                                tf["lr"])
        count += n
        losses.append(loss)
        if i == 0:
            mu1 = compare.leaves(mu)
    return {"losses": losses, "mu1": mu1, "p0": p0,
            "p3": compare.leaves(params)}


def readings(prog: dict, ref: dict) -> dict[str, float]:
    """The three numbers compared: worst step's relative loss gap; worst
    leaf's gap of the first gradient as Adam holds it (its first moment
    after step 1); worst moving leaf's gap of the change after the
    checked steps."""
    keep = compare.moving_leaves(ref["mu1"])
    d_prog = [a - b for a, b in zip(prog["p3"], prog["p0"])]
    d_ref = [a - b for a, b in zip(ref["p3"], ref["p0"])]
    return {"loss": compare.worst_rel_gap(prog["losses"], ref["losses"]),
            "grad": compare.leaf_norm_gap(prog["mu1"], ref["mu1"]),
            "change": compare.leaf_norm_gap(d_prog, d_ref, keep)}


def run(ctx: harness.Context) -> Outcome:
    cell = TrainCell(ctx)
    cell.fill()
    prog = cell.first_steps()
    setup_s = ctx.setup_seconds()

    failed = 0
    start_epochs = cell.epochs
    with ctx.window():
        t0 = time.perf_counter()
        deadline = t0 + ctx.seconds
        while True:
            if not math.isfinite(cell.epoch()):
                failed += 1
            if time.perf_counter() >= deadline:
                break
        elapsed = time.perf_counter() - t0
    epochs = cell.epochs - start_epochs
    peak = harness.memory_peak_bytes(ctx.cell.chips)
    samples = cell.samples_per_epoch
    cell.close()

    ref = reference_steps(ctx)
    limits = harness.limits(ctx.cell)
    checks = [Check(k, v, limits[k]) for k, v in readings(prog, ref).items()]
    return Outcome(
        setup_s=setup_s,
        e2e={"train_samples_per_s": epochs * samples / elapsed},
        attempted=epochs, failed=failed, checks=checks,
        counters={"epochs": epochs, "elapsed_s": elapsed,
                  "samples_per_epoch": samples,
                  "gather": ctx.cell.traffic["gather"],
                  "batch": ctx.cell.traffic["batch"]},
        memory_peak_bytes=peak)


def _worst_leaves(prog: dict, ref: dict) -> dict:
    """Which leaf reads worst in each norm gap, with both norms."""
    out = {}
    keep = compare.moving_leaves(ref["mu1"])
    d_prog = [a - b for a, b in zip(prog["p3"], prog["p0"])]
    d_ref = [a - b for a, b in zip(ref["p3"], ref["p0"])]
    for name, a, b, k in (("grad", prog["mu1"], ref["mu1"], None),
                          ("change", d_prog, d_ref, keep)):
        gaps = compare.leaf_norm_gaps(a, b, k)
        i = int(np.argmax(gaps))
        out[name] = {"leaf": i, "gap": float(gaps[i]),
                     "prog_norm": float(np.linalg.norm(a[i])),
                     "ref_norm": float(np.linalg.norm(b[i])),
                     "median_ref_norm": float(np.median(
                         [np.linalg.norm(x) for x in b])),
                     "gaps_sorted": sorted(map(float, gaps))[-5:]}
    return out


def calibrate(ctx: harness.Context, full: bool = True) -> dict[str, dict]:
    """Readings of the program (its first steps) and of the reference put
    in its place in bfloat16 (the control).  ``full`` adds the worst
    leaves, the reference with half of each batch left out (a fault), a
    second witness (the program with every matmul at precision HIGHEST,
    its producer too, against the reference on float32 snapshots) and the
    program against that reference."""
    import jax
    prog = _program_steps(ctx)
    ref = reference_steps(ctx)
    out = {"program": readings(prog, ref),
           "control_bfloat16": readings(reference_steps(ctx, "bfloat16"),
                                        ref)}
    if full:
        with jax.default_matmul_precision("highest"):
            high = _program_steps(ctx)
        ref32 = reference_steps(ctx, data="float32")
        out.update(
            program_leaves=_worst_leaves(prog, ref),
            fault_half_batch=readings(reference_steps(ctx, keep=0.5), ref),
            program_highest_vs_float32=readings(high, ref32),
            program_vs_float32=readings(prog, ref32))
    return out


def _program_steps(ctx: harness.Context) -> dict:
    cell = TrainCell(ctx)
    cell.fill()
    prog = cell.first_steps()
    cell.close()
    return prog
