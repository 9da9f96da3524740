"""Plain reference of the ``flatplate`` configuration: the flat-plate
boundary-layer snapshot the producer computes.

A law-of-the-wall mean profile plus random Fourier-mode fluctuations
(frozen turbulence convected downstream) on a wall-stretched structured
grid, written in straightforward ``jax.numpy``; it imports nothing of the
program under test.  ``arith`` selects the arithmetic
(``bench/precision.py``): ``default``, the precision the configuration
states, is what a producer's snapshot is compared with; ``bfloat16`` is
its control.  The ``quadconv_ae`` reference takes its snapshots from here.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from bench import precision as P

KAPPA = 0.41
B_LOG = 5.2


def grid_coords(cfg: dict) -> jax.Array:
    """Structured grid, wall-normal tanh stretching, [N, 3] in (x, y, z)."""
    nx, ny, nz = cfg["grid"]
    p = cfg["producer"]
    x = jnp.arange(nx, dtype=jnp.float32) * (p["lx"] / nx)
    eta = jnp.linspace(0.0, 1.0, ny, dtype=jnp.float32)
    y = 1.0 - jnp.tanh(p["stretch"] * (1.0 - eta)) / math.tanh(p["stretch"])
    z = jnp.arange(nz, dtype=jnp.float32) * (p["lz"] / nz)
    xx, yy, zz = jnp.meshgrid(x, y, z, indexing="ij")
    return jnp.stack([xx.ravel(), yy.ravel(), zz.ravel()], axis=-1)


def _modes(cfg: dict, key):
    """Random wavevectors, phases, amplitudes and polarisations."""
    m = cfg["producer"]["n_modes"]
    k0, k1, k2, _ = jax.random.split(key, 4)
    kvec = jax.random.normal(k0, (m, 3)) * jnp.array([4.0, 8.0, 4.0])
    phase0 = jax.random.uniform(k1, (m,), maxval=2 * jnp.pi)
    raw = jax.random.normal(k2, (m, 3))
    return kvec, phase0, raw


def snapshot(cfg: dict, key, step, arith: str = "float32") -> jax.Array:
    """(p, u, v, w) at every grid point, [4, N], computed in ``arith``."""
    p = cfg["producer"]
    dtype = P.dtype(arith)
    coords = grid_coords(cfg).astype(dtype)
    kvec, phase0, raw = (a.astype(dtype) for a in _modes(cfg, key))
    y = coords[:, 1]
    kmag = jnp.sqrt(jnp.sum(kvec * kvec, -1)) + 1e-3
    amp = kmag ** (-5.0 / 6.0)
    amp = amp / jnp.sqrt(jnp.sum(amp * amp))
    pol = raw - kvec * jnp.sum(raw * kvec, -1, keepdims=True) / kmag[:, None] ** 2
    pol = pol / (jnp.sqrt(jnp.sum(pol * pol, -1, keepdims=True)) + 1e-8)
    t = jnp.asarray(step, dtype)
    phases = (jnp.einsum("nd,md->nm", P.operand(coords, arith),
                         P.operand(kvec, arith))
              + phase0[None, :] - p["u_conv"] * t * kvec[None, :, 0])
    fluct = jnp.einsum("nm,md->nd", P.operand(jnp.sin(phases) * amp[None, :],
                                              arith), P.operand(pol, arith))
    yplus = jnp.maximum(y * p["re_tau"], 0.0)
    intensity = (yplus / 15.0) * jnp.exp(1.0 - yplus / 15.0) * 2.0 \
        + 0.1 * jnp.exp(-y)
    fluct = fluct * intensity[:, None]
    yp = jnp.maximum(y * p["re_tau"], 1e-6)
    blend = 1.0 - jnp.exp(-yp / 11.0)
    mean = (1 - blend) * yp + blend * jnp.minimum(jnp.log(yp) / KAPPA + B_LOG,
                                                  yp + 20.0)
    u = mean + 2.0 * fluct[:, 0]
    pres = jnp.sum(jnp.cos(phases) * (amp * kmag ** (-1.0 / 3.0))[None, :],
                   -1) * intensity
    return jnp.stack([pres, u, fluct[:, 1], fluct[:, 2]])


def make_snapshots(cfg: dict, arith: str = "float32"):
    """Jitted ``(keys [n], steps [n]) -> [n, 4, N]`` at precision HIGHEST."""
    def fn(keys, steps):
        with jax.default_matmul_precision("highest"):
            return jax.vmap(lambda k, s: snapshot(cfg, k, s, arith))(keys,
                                                                      steps)
    return jax.jit(fn)
