"""The program's flat-plate producer and its ring table, built from a
configuration: the set-up the ``train`` and ``capture`` runners share."""

from __future__ import annotations

from . import harness

TABLE = "field"


def field_table(server, cfg: dict):
    """Create the ring the producer puts its snapshots into; returns its
    spec."""
    from repro.core import TableSpec
    return server.create_table(TableSpec(
        TABLE, shape=(cfg["channels"], cfg["n_points"]),
        capacity=cfg["table"]["capacity"], engine=cfg["table"]["engine"]))


def flatplate(cfg: dict):
    """The program's ``FlatPlateConfig`` on the configuration's grid."""
    from repro.sim import flatplate as fp
    nx, ny, nz = cfg["grid"]
    fcfg = fp.FlatPlateConfig(nx=nx, ny=ny, nz=nz, **cfg["producer"])
    if fcfg.n_points != cfg["n_points"]:
        raise harness.BenchError("grid and n_points disagree")
    return fcfg


def step_fn(cfg: dict, period: int, ranked: bool):
    """The producer's scan step: the snapshot of solver step ``t`` (modulo
    ``period``) under the key of (rank, t).  ``ranked``: the multi-rank
    form ``(carry, rank, t)``, else ``(carry, t)`` for rank 0."""
    from repro.core import store as S
    from repro.sim import flatplate as fp
    fcfg = flatplate(cfg)

    def ranked_step(carry, rank, t):
        return carry, S.make_key(rank, t), fp.snapshot(fcfg, carry, t % period)

    def single_step(carry, t):
        return ranked_step(carry, 0, t)

    return ranked_step if ranked else single_step
