"""The main-path Pallas kernels compile for a TPU v5e — without a chip.

Each test lowers one kernel at the chip smoke's real shapes
(``chip_smoke.py``: ring capacity, points per rank, the published
autoencoder widths) against a *described* v5e topology and compiles it
with the TPU compiler, with interpret mode off.  The compiler refuses
what interpret mode accepts — blocks that break the (8, 128) tiling
rule, layouts Mosaic cannot cast — so these guard the chip path at no
chip time.  Each asserts the kernel is in the compiled program
(``tpu_custom_call``), i.e. no reference took its place.  One more
compiles a whole QuadConv layer's forward and backward and asserts that
nothing in it relays out the kernel tensor ``G``.

The topology is described only inside the module fixture: the TPU
library may be loaded by one process at a time, and every test worker
imports this file.
"""

from __future__ import annotations

import importlib.util
import math
import re
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import quadconv_ae
from repro.kernels.quadconv import quadconv_contract
from repro.kernels.store import kernel as K
from repro.ml.quadconv import QuadConv

_SMOKE = Path(__file__).resolve().parents[1] / "chip_smoke.py"


def _smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", _SMOKE)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


SMOKE = _smoke()
N_POINTS = SMOKE.GRID[0] * SMOKE.GRID[1] * SMOKE.GRID[2]
CAPACITY = SMOKE.CAPACITY
N_QUERY = 300           # more than one 128-row query block


def _layer_shapes():
    """Distinct (I=J points, C_in, C_out) of the autoencoder's QuadConvs."""
    cfg = quadconv_ae.config()
    pts = [N_POINTS // cfg.pool ** lvl for lvl in range(cfg.blocks + 1)]
    shapes = set()
    c = cfg.channels
    for b in range(cfg.blocks):
        shapes.add((pts[b], c, cfg.internal))
        shapes.add((pts[cfg.blocks - b - 1], cfg.internal, cfg.internal))
        c = cfg.internal
    return sorted(shapes)


@pytest.fixture(scope="module")
def chip():
    """One described v5e chip; the compile cache is off meanwhile (an
    entry compiled for an absent chip cannot be read back here)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as exc:  # noqa: BLE001 — any failure means no TPU lib
            pytest.skip(f"no v5e:2x2 topology can be described here: {exc}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compiles_to_kernel(fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert 'custom_call_target="tpu_custom_call"' in text


def _arg(chip, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)


def test_probe_compiles(chip):
    _compiles_to_kernel(
        lambda k, v, q: K.probe(k, v, q),
        _arg(chip, (CAPACITY,), jnp.uint32),
        _arg(chip, (CAPACITY,), jnp.int32),
        _arg(chip, (N_QUERY,), jnp.uint32))


def test_sample_compiles(chip):
    _compiles_to_kernel(
        lambda v, r: K.sample(v, r),
        _arg(chip, (CAPACITY,), jnp.int32),
        _arg(chip, (N_QUERY,), jnp.int32))


def test_gather_compiles(chip):
    _compiles_to_kernel(
        lambda s, i: K.gather(s, i),
        _arg(chip, (CAPACITY, 4, N_POINTS), jnp.float32),
        _arg(chip, (SMOKE.GATHER,), jnp.int32))


def test_gather_sharded_compiles(chip):
    _compiles_to_kernel(
        lambda s, i, o: K.gather_sharded(s, i, o),
        _arg(chip, (CAPACITY // 4, 4, N_POINTS), jnp.float32),
        _arg(chip, (SMOKE.GATHER,), jnp.int32),
        _arg(chip, (), jnp.int32))


@pytest.mark.parametrize("points,c_in,c_out", _layer_shapes())
def test_quadconv_compiles(chip, points, c_in, c_out):
    _compiles_to_kernel(
        lambda f, w, g: quadconv_contract(f, w, g, "pallas"),
        _arg(chip, (SMOKE.BATCH, points, c_in), jnp.float32),
        _arg(chip, (points,), jnp.float32),
        _arg(chip, (points, c_out * c_in, points), jnp.float32))


_RELAYOUT = re.compile(r"=\s*\w+\[([\d,]*)\]\S*\s+(copy|transpose|reshape|"
                       r"copy-start)\(")


def test_quadconv_layer_never_relays_out_g(chip):
    """Forward and backward of one 1,024-point 16->16 QuadConv layer, the
    kernel tensor built by its filter MLP included: the optimised program
    holds no copy, transpose or reshape of as many elements as ``G``
    (``bitcast`` is free and allowed), and the three kernels are in it."""
    cfg = quadconv_ae.config()
    points, c = N_POINTS, cfg.internal
    conv = QuadConv(c_in=c, c_out=c, mlp_width=cfg.mlp_width,
                    mlp_depth=cfg.mlp_depth, mode="pallas")
    params = jax.tree.map(
        lambda x: _arg(chip, x.shape, x.dtype),
        jax.eval_shape(lambda: conv.init(jax.random.key(0), points)))

    def loss(p, f, x):
        return jnp.sum(jnp.square(conv.apply(p, f, x, x)))

    text = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
        params, _arg(chip, (SMOKE.BATCH, points, c), jnp.float32),
        _arg(chip, (points, 3), jnp.float32)).compile().as_text()
    g_elems = points * points * c * c
    relayouts = [line.strip()[:160] for line in text.splitlines()
                 if (m := _RELAYOUT.search(line)) and math.prod(
                     int(d) for d in m.group(1).split(",") if d) == g_elems]
    assert not relayouts, relayouts
    assert text.count('custom_call_target="tpu_custom_call"') == 3
