"""Cells of the benchmark cut to a size a CPU test run holds: the same
runners, references and limits, with small grids, widths and images.  The
CPU multiplies float32 in float32 whatever the precision asked for, so a
cut cell states precision ``highest``, which is what it computes."""

import time

import _paths
from bench import harness

DEVICE = {"platform": "cpu", "kind": "TPU v5 lite", "count": 1}


def cell(name: str) -> harness.Cell:
    bench = harness.load_benchmark(_paths.ROOT)
    c = harness.load_cell(_paths.ROOT, bench, name)
    c.cfg["matmul_precision"] = "highest"
    if c.config == "quadconv_ae":
        c.cfg.update(grid=[4, 4, 4], n_points=64, internal=4, latent=8,
                     mlp_width=8, mlp_depth=3)
        c.cfg["table"] = dict(c.cfg["table"], capacity=64)
        c.traffic.update(fill_snapshots=12)
    elif c.config == "flatplate":
        c.cfg.update(grid=[4, 4, 4], n_points=64)
        c.cfg["table"] = dict(c.cfg["table"], capacity=64)
        c.traffic.update(chunk_steps=16)
    else:
        c.cfg.update(image=[3, 32, 32])
        c.traffic.update(warmup_s=0.5, check_sample=4)
    return c


def context(c: harness.Cell, seed: int, seconds: float = 1.0):
    return harness.Context(c, seed, seconds, None, time.perf_counter())


def run(name: str, seed: int, seconds: float = 1.0) -> harness.Result:
    """A whole run of the cell, past the harness's look for a chip."""
    c = cell(name)
    bench = harness.load_benchmark(_paths.ROOT)
    return harness.run_cell(_paths.ROOT, bench, c, seed=seed,
                            seconds=seconds, trace=False, device=DEVICE,
                            t_start=time.perf_counter())
