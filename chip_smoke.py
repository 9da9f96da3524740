"""Chip smoke test: the co-located in-situ path on TPU, end to end.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # the multi-chip deployments only

One chip: one ``InSituSession`` on the co-located deployment — a
flat-plate producer capturing snapshots into a ring table, a trainer
fitting the QuadConv autoencoder at the paper's published widths through
the Pallas kernels (``mode=None`` picks them on TPU), and an inference
consumer encoding new snapshots.  Then it checks, on the live table and
the trained model:

* every component finished and the training loss is finite and falling;
* the compiled store reads (``get_many``/``sample``) and the train step
  contain ``tpu_custom_call`` — the Pallas kernels, not the references;
* the ``pallas`` and ``ref`` probe/sample/gather slots agree exactly, and
  one batch's autoencoder loss agrees within ``LOSS_RTOL``.

``--chips 4`` runs the same declaration three times, sequentially so the
data stream is deterministic: co-located on a 4-chip mesh with the
slab-sharded trainer, clustered (3 client chips : 1 store chip), and on
device 0 alone.  It checks that the table bytes match the one-chip run,
the trained parameters agree within ``STATE_RTOL``, and the co-located
slab's shards sit on 4 distinct devices.

Measurements go to earlier lines; the last line of stdout is one JSON
object ``{"ok": true, "device": {...}}``.  Without a TPU the script exits
non-zero before printing a result.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import math
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

#: flat-plate grid per rank (nx, ny, nz).  The paper's partition is
#: 48x25x32 = 38,400 points; the dense QuadConv kernel tensor
#: G[J, O*C, I] grows as N^2: the fused train epoch compiles to 3.5 GiB
#: of temporaries at 16x16x4 = 1,024 points and 14.1 GiB at 16x16x8 =
#: 2,048 points, near a v5e's 16 GiB.
GRID = (16, 16, 4)
CAPACITY = 256          # ring slots: two 128-lane blocks for probe/sample
SIM_STEPS = 128         # snapshots the producer captures (emit every step)
EPOCHS = 6
GATHER = 6              # snapshots per epoch (paper)
BATCH = 4
LR = 1e-3
INFER_STEPS = 3
SEED = 0
#: Relative tolerance of the pallas-vs-reference AE loss.  The reference
#: runs at precision=HIGHEST; the kernel's f32 MXU dot may round its
#: operands to bf16 (relative error 2^-8 per product), which a random-sign
#: sum over I*C terms and four QuadConv layers keeps well under 1e-2.
LOSS_RTOL = 1e-2
#: Relative L2 distance allowed between parameters trained on 4 chips
#: (DDP mean of per-chip gradients) and on one: only the summation order
#: of the batch gradient differs, amplified by Adam's normalisation.
STATE_RTOL = 1e-3


class SmokeFailure(Exception):
    """A check failed; the message says which."""


def _check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def _device_info() -> dict:
    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices())}


def _require_tpu(chips: int) -> None:
    info = _device_info()
    if info["platform"] != "tpu":
        raise SmokeFailure(f"no TPU: JAX sees platform "
                           f"{info['platform']!r}")
    if info["count"] < chips:
        raise SmokeFailure(f"--chips {chips} needs {chips} TPU devices, "
                           f"JAX sees {info['count']}")


# ---------------------------------------------------------------------------
# the session declaration
# ---------------------------------------------------------------------------

def declare(deployment, *, trainer_mesh=None, slab_sharded=False):
    """The in-situ session every phase runs: flat-plate producer →
    ring table → QuadConv-AE trainer → inference.  Returns
    ``(session, trainer_cfg, flat_plate_cfg)``."""
    from repro.configs import quadconv_ae
    from repro.core import TableSpec
    from repro.core import store as S
    from repro.insitu import (InferenceConsumer, InSituSession, Producer,
                              TrainerConsumer)
    from repro.ml import trainer as tr
    from repro.sim import flatplate as fp

    fcfg = fp.FlatPlateConfig(nx=GRID[0], ny=GRID[1], nz=GRID[2])
    n_points = fcfg.n_points
    # The flat-plate trajectory is generated in bulk on the device at
    # set-up and the producer replays it step by step, so every
    # deployment stores the same bytes (an in-loop solve compiles into a
    # different program per mesh and may round differently).
    traj = fp.snapshot_batch(fcfg, jax.random.key(SEED), 0,
                             SIM_STEPS + INFER_STEPS)     # [T, 4, N]

    def step_fn(carry, rank, t):
        snap = jax.lax.dynamic_index_in_dim(carry, t, keepdims=False)
        return carry, S.make_key(rank, t), snap

    aecfg = dataclasses.replace(quadconv_ae.config(), n_points=n_points)
    cfg = tr.TrainerConfig(ae=aecfg, epochs=EPOCHS, gather=GATHER,
                           batch_size=BATCH, lr=LR, seed=SEED,
                           mesh=trainer_mesh, slab_sharded=slab_sharded)

    def feed(client, step):
        """Encode snapshots the trainer never saw (the inference phase)."""
        mu, sd = client.get_metadata("norm_stats")
        return (traj[SIM_STEPS + step].T[None] - mu) / sd

    session = InSituSession(
        tables=[TableSpec("field", shape=(4, n_points), capacity=CAPACITY,
                          engine="ring")],
        components=[
            Producer(step_fn, table="field", steps=SIM_STEPS, carry=traj),
            TrainerConsumer(cfg, fp.grid_coords(fcfg), model_key="encoder"),
            InferenceConsumer("encoder", feed, steps=INFER_STEPS),
        ],
        deployment=deployment)
    return session, cfg, fcfg


def run_session(session, sequential: bool, label: str):
    plan = session.plan()
    tiers = ", ".join(f"{c.name}={c.tier}" for c in plan.components)
    print(f"[{label}] plan: {tiers}")
    t0 = time.perf_counter()
    res = session.run(plan=plan, max_wall_s=900.0, sequential=sequential)
    wall = time.perf_counter() - t0
    for comp in res.run.components.values():
        if not comp.ok:
            print(f"[{label}] component {comp.name!r} failed "
                  f"({comp.error_type}):\n{comp.error}", file=sys.stderr)
    _check(res.ok, f"[{label}] a component failed")
    print(f"[{label}] session wall {wall:.3f} s")
    return res


def check_training(res, label: str):
    hist = res.output("trainer").history
    losses = [h.train_loss for h in hist]
    print(f"[{label}] train loss per epoch: {losses}")
    print(f"[{label}] val rel. Frobenius error per epoch: "
          f"{[h.val_rel_error for h in hist]}")
    _check(len(losses) >= 2, f"[{label}] fewer than 2 epochs ran")
    _check(all(math.isfinite(x) for x in losses),
           f"[{label}] non-finite training loss")
    _check(losses[-1] < losses[0],
           f"[{label}] training loss did not fall: {losses}")
    z = res.output("inference").last
    _check(z is not None and bool(jnp.all(jnp.isfinite(z))),
           f"[{label}] inference produced no finite latent")
    print(f"[{label}] inference latent {tuple(z.shape)}")


# ---------------------------------------------------------------------------
# one chip
# ---------------------------------------------------------------------------

def _custom_calls(compiled) -> int:
    return compiled.as_text().count('custom_call_target="tpu_custom_call"')


def check_kernels_compiled(res, cfg, coords) -> None:
    """The Pallas kernels, not the references, are in the compiled store
    reads and in the compiled train step."""
    from repro.core import store as S
    from repro.ml import autoencoder as ae
    from repro.ml import trainer as tr
    from repro.train import optimizer as opt

    spec = res.server.spec("field")
    st = res.server.checkout("field")
    keys = S.make_key(jnp.zeros((8,), jnp.int32), jnp.arange(8))
    n_get = _custom_calls(S.get_many.lower(spec, st, keys).compile())
    n_samp = _custom_calls(
        S.sample.lower(spec, st, jax.random.key(0), GATHER).compile())
    levels = ae.coords_pyramid(cfg.ae, coords)
    tx = opt.adam(cfg.scaled_lr)
    step = tr.make_train_step(cfg, levels, tx)
    state = res.output("trainer").state
    batch = jnp.zeros((BATCH, cfg.ae.n_points, cfg.ae.channels))
    t0 = time.perf_counter()
    n_step = _custom_calls(step.lower(state, batch).compile())
    print(f"tpu_custom_call: get_many {n_get}, sample {n_samp}, "
          f"train step {n_step} (compile {time.perf_counter() - t0:.3f} s)")
    _check(n_get > 0, "get_many compiled without a Pallas kernel")
    _check(n_samp > 0, "sample compiled without a Pallas kernel")
    _check(n_step > 0, "train step compiled without a Pallas kernel")


def check_store_parity(res) -> None:
    """pallas ≡ ref on the live table: probe, sample and gather slots."""
    from repro.core import store as S
    from repro.kernels.store import ops as kops

    st = res.server.checkout("field")
    capacity = st.keys.shape[0]
    present = S.make_key(jnp.zeros((SIM_STEPS,), jnp.int32),
                         jnp.arange(SIM_STEPS))
    absent = S.make_key(jnp.ones((8,), jnp.int32), jnp.arange(8))
    query = jnp.concatenate([present, absent,
                             jnp.array([S.EMPTY_KEY], jnp.uint32)])
    idx = {m: kops.probe_slots(st.keys, st.version, query, m)
           for m in ("pallas", "ref")}
    for a, b in zip(idx["pallas"], idx["ref"]):
        _check(np.array_equal(np.asarray(a), np.asarray(b)),
               "probe: pallas and ref slots differ")
    found = int(np.asarray(idx["ref"][1]).sum())
    _check(found == SIM_STEPS, f"probe found {found} of {SIM_STEPS} keys")
    nvalid = int(jnp.sum(st.version > 0))
    ranks = jnp.concatenate([jnp.arange(nvalid, dtype=jnp.int32),
                             jnp.array([nvalid, capacity + 5], jnp.int32)])
    slots = {m: kops.sample_slots(st.version, ranks, m)
             for m in ("pallas", "ref")}
    _check(np.array_equal(np.asarray(slots["pallas"]),
                          np.asarray(slots["ref"])),
           "sample: pallas and ref slots differ")
    safe = jnp.minimum(slots["ref"], capacity - 1)
    rows = {m: kops.gather_rows(st.slab, safe, m) for m in ("pallas", "ref")}
    _check(np.array_equal(np.asarray(rows["pallas"]),
                          np.asarray(rows["ref"])),
           "gather: pallas and ref rows differ")
    print(f"store parity on the live table: probe {query.shape[0]} keys, "
          f"sample {ranks.shape[0]} ranks over {nvalid} valid of "
          f"{capacity} slots, gather {safe.shape[0]} rows: pallas == ref")


def check_loss_parity(res, cfg, coords) -> None:
    """One batch's AE loss through the Pallas QuadConv vs the reference
    contraction, both with every XLA matmul at precision=HIGHEST."""
    from repro.core import store as S
    from repro.ml import autoencoder as ae

    spec = res.server.spec("field")
    st = res.server.checkout("field")
    out = res.output("trainer")
    mu, sd = out.norm_stats
    vals, _, _ = S.sample(spec, st, jax.random.key(SEED + 7), BATCH, "ref")
    batch = (vals.transpose(0, 2, 1) - mu) / sd
    levels = ae.coords_pyramid(cfg.ae, coords)
    loss = {}
    with jax.default_matmul_precision("highest"):
        for mode in ("pallas", "ref"):
            acfg = dataclasses.replace(cfg.ae, mode=mode)
            fn = jax.jit(lambda p, b, acfg=acfg: ae.loss_fn(p, acfg,
                                                            levels, b))
            loss[mode] = float(fn(out.state.params, batch))
    rel = abs(loss["pallas"] - loss["ref"]) / abs(loss["ref"])
    print(f"AE loss on one batch: pallas {loss['pallas']!r}, "
          f"ref(HIGHEST) {loss['ref']!r}, rel. diff {rel!r} "
          f"(tolerance {LOSS_RTOL})")
    _check(math.isfinite(loss["pallas"]) and rel <= LOSS_RTOL,
           "AE loss: pallas and ref disagree")


def report_timers(res, label: str) -> None:
    t = res.timers
    sol = t.total("equation_solution")
    if sol:
        print(f"[{label}] producer {SIM_STEPS} steps in {sol:.3f} s "
              f"({SIM_STEPS / sol:.1f} steps/s with the store in the loop)")
    n_ep = len(res.output("trainer").history)
    train = t.total("total_training")
    if train and n_ep:
        print(f"[{label}] trainer {n_ep} fused epochs in {train:.3f} s "
              f"({train / n_ep:.4f} s/epoch), compile "
              f"{t.total('jit_compile'):.3f} s")
    stats = jax.devices()[0].memory_stats() or {}
    if "peak_bytes_in_use" in stats:
        print(f"[{label}] device 0 peak HBM "
              f"{stats['peak_bytes_in_use'] / 2**30:.3f} GiB")


def one_chip() -> None:
    from repro.core.deployment import make_colocated_1d
    from repro.configs import quadconv_ae

    paper = quadconv_ae.config()
    print(f"AE widths (published): channels {paper.channels}, internal "
          f"{paper.internal}, latent {paper.latent}, blocks "
          f"{paper.blocks}, pool {paper.pool}, filter MLP "
          f"{paper.mlp_width}x{paper.mlp_depth}")
    n = GRID[0] * GRID[1] * GRID[2]
    print(f"points per rank: {n} ({GRID[0]}x{GRID[1]}x{GRID[2]}), cut "
          f"from the paper's {paper.n_points}: the dense kernel tensor "
          f"G[N,256,N] grows as N^2 (14.1 GiB of temporaries at 2,048 "
          f"points)")
    session, cfg, fcfg = declare(make_colocated_1d())
    res = run_session(session, sequential=False, label="colocated-1")
    report_timers(res, "colocated-1")
    check_training(res, "colocated-1")
    from repro.sim import flatplate as fp
    coords = fp.grid_coords(fcfg)
    check_store_parity(res)
    check_loss_parity(res, cfg, coords)
    check_kernels_compiled(res, cfg, coords)


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------

def _table_bytes(res) -> dict:
    st = res.server.checkout("field")
    return {f: np.asarray(getattr(st, f)) for f in ("slab", "keys",
                                                    "version", "count")}


def _param_vector(res) -> np.ndarray:
    leaves = jax.tree.leaves(res.output("trainer").state.params)
    return np.concatenate([np.asarray(x, np.float64).ravel()
                           for x in leaves])


def four_chips() -> None:
    from jax.sharding import Mesh
    from repro.core.deployment import make_clustered_1d, make_colocated_1d
    from repro.parallel.sharding import data_mesh

    runs = {}
    mesh4 = data_mesh(4)
    session, _, _ = declare(make_colocated_1d(mesh=mesh4),
                            trainer_mesh=mesh4, slab_sharded=True)
    runs["colocated-4"] = run_session(session, True, "colocated-4")
    session, _, _ = declare(make_clustered_1d(devices=jax.devices()[:4]))
    runs["clustered-3:1"] = run_session(session, True, "clustered-3:1")
    one = Mesh(np.asarray(jax.devices()[:1]), ("data",))
    session, _, _ = declare(make_colocated_1d(mesh=one))
    runs["device0"] = run_session(session, True, "device0")

    base_tab = _table_bytes(runs["device0"])
    base_par = _param_vector(runs["device0"])
    for label, res in runs.items():
        report_timers(res, label)
        check_training(res, label)
        if label == "device0":
            continue
        tab = _table_bytes(res)
        for f, arr in tab.items():
            _check(np.array_equal(arr, base_tab[f]),
                   f"[{label}] table {f} differs from the one-chip run")
        par = _param_vector(res)
        rel = float(np.linalg.norm(par - base_par)
                    / np.linalg.norm(base_par))
        print(f"[{label}] table bytes equal to device0; params rel. L2 "
              f"distance {rel!r}, max abs diff "
              f"{float(np.max(np.abs(par - base_par)))!r} "
              f"(tolerance {STATE_RTOL})")
        _check(rel <= STATE_RTOL,
               f"[{label}] trained params differ from the one-chip run")
    slab = runs["colocated-4"].server.checkout("field").slab
    devs = {s.device for s in slab.addressable_shards}
    shapes = sorted({tuple(s.data.shape) for s in slab.addressable_shards})
    print(f"[colocated-4] slab {tuple(slab.shape)} in shards {shapes} on "
          f"{len(devs)} distinct devices")
    _check(len(devs) == 4, "slab shards are not on 4 distinct devices")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the multi-chip deployments")
    args = ap.parse_args(argv)
    try:
        _require_tpu(args.chips)
        from repro.launch.cache import configure_compile_cache
        print(f"compile cache: {configure_compile_cache(ROOT)}")
        t0 = time.perf_counter()
        if args.chips == 4:
            four_chips()
        else:
            one_chip()
        print(f"smoke wall {time.perf_counter() - t0:.3f} s")
    except SmokeFailure as exc:
        print(f"chip smoke FAILED: {exc}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": _device_info()}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
