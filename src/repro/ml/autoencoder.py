"""QuadConv autoencoder for flow-state compression (paper §4, Fig. 9).

Structure (paper Fig. 9, adapted hyper-parameters as the paper itself did):

  encoder:  B=2 blocks of [QuadConv → GELU → LayerNorm → 4× point pool]
            then flatten → linear → latent (dim 100)
  decoder:  linear → unflatten → B blocks of [4× point unpool → QuadConv →
            GELU → LayerNorm] → linear channel head back to 4 channels

* 16 internal data channels, five-layer filter MLPs mapping R³ → R^{16×16}
  (paper §4) — both via ``ml.quadconv``.
* Point sets: level-l coords are a stride-4ˡ subset of the level-0 grid
  (the paper pools on its structured-but-stretched grid the same way);
  pooling takes the max over each group of 4 consecutive points, unpooling
  broadcasts (paper: max-pool / un-pool).
* Latent 100 → the paper's headline "1700× spatial compression" ratio
  ``(C·N)/latent`` is reported by ``compression_factor``.
* Loss: MSE; validation metric: relative Frobenius reconstruction error
  (paper Eq. 1), in ``rel_frobenius``.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp

from .quadconv import QuadConv

__all__ = ["AEConfig", "init_autoencoder", "encode", "decode", "reconstruct",
           "loss_fn", "rel_frobenius", "coords_pyramid", "compression_factor"]


@dataclass(frozen=True)
class AEConfig:
    n_points: int               # level-0 point count (per rank partition)
    channels: int = 4           # (p, u, v, w)
    internal: int = 16          # paper: 16 internal data channels
    latent: int = 100           # paper: latent dimension 100
    blocks: int = 2             # paper: two blocks in encoder and decoder
    pool: int = 4               # point-pool factor per block
    mlp_width: int = 32
    mlp_depth: int = 5          # paper: five-layer filter MLPs
    support: float = 0.75
    mode: str | None = None     # quadconv kernel dispatch

    def level_points(self, level: int) -> int:
        return self.n_points // (self.pool ** level)

    @property
    def bottleneck(self) -> int:
        return self.level_points(self.blocks) * self.internal


def compression_factor(cfg: AEConfig) -> float:
    """Paper: size of the per-rank simulation data / latent dimension."""
    return (cfg.n_points * cfg.channels) / cfg.latent


def coords_pyramid(cfg: AEConfig, coords: jax.Array) -> list[jax.Array]:
    """Strided point subsets per level: [N], [N/4], [N/16], ..."""
    out = [coords]
    for level in range(1, cfg.blocks + 1):
        out.append(coords[:: cfg.pool ** level])
    return out


def _conv(cfg: AEConfig, c_in: int, c_out: int) -> QuadConv:
    return QuadConv(c_in=c_in, c_out=c_out, mlp_width=cfg.mlp_width,
                    mlp_depth=cfg.mlp_depth, support=cfg.support,
                    mode=cfg.mode)


def init_autoencoder(key, cfg: AEConfig) -> dict:
    keys = jax.random.split(key, 2 * cfg.blocks + 3)
    params: dict[str, Any] = {"enc": [], "dec": []}
    c = cfg.channels
    for b in range(cfg.blocks):
        conv = _conv(cfg, c, cfg.internal)
        p = conv.init(keys[b], cfg.level_points(b))
        p["ln_scale"] = jnp.ones((cfg.internal,))
        p["ln_bias"] = jnp.zeros((cfg.internal,))
        params["enc"].append(p)
        c = cfg.internal
    params["enc_head"] = {
        "w": jax.random.normal(keys[cfg.blocks], (cfg.bottleneck, cfg.latent))
        * jnp.sqrt(1.0 / cfg.bottleneck),
        "b": jnp.zeros((cfg.latent,)),
    }
    params["dec_head"] = {
        "w": jax.random.normal(keys[cfg.blocks + 1],
                               (cfg.latent, cfg.bottleneck))
        * jnp.sqrt(1.0 / cfg.latent),
        "b": jnp.zeros((cfg.bottleneck,)),
    }
    for b in range(cfg.blocks):
        conv = _conv(cfg, cfg.internal, cfg.internal)
        p = conv.init(keys[cfg.blocks + 2 + b],
                      cfg.level_points(cfg.blocks - b - 1))
        p["ln_scale"] = jnp.ones((cfg.internal,))
        p["ln_bias"] = jnp.zeros((cfg.internal,))
        params["dec"].append(p)
    params["out_head"] = {
        "w": jax.random.normal(keys[-1], (cfg.internal, cfg.channels))
        * jnp.sqrt(1.0 / cfg.internal),
        "b": jnp.zeros((cfg.channels,)),
    }
    return params


def _layernorm(x, scale, bias, eps=1e-5):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.var(x, axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * scale + bias


def _pool_max(x: jax.Array, k: int) -> jax.Array:
    b, n, c = x.shape
    return jnp.max(x.reshape(b, n // k, k, c), axis=2)


def _unpool(x: jax.Array, k: int) -> jax.Array:
    b, n, c = x.shape
    return jnp.broadcast_to(x[:, :, None, :], (b, n, k, c)).reshape(b, n * k, c)


def encode(params: dict, cfg: AEConfig, levels: list[jax.Array],
           f: jax.Array) -> jax.Array:
    """f: [B, N, C] → z: [B, latent]."""
    x = f
    c = cfg.channels
    for b in range(cfg.blocks):
        conv = _conv(cfg, c, cfg.internal)
        p = params["enc"][b]
        x = conv.apply(p, x, levels[b], levels[b])
        x = jax.nn.gelu(x)
        x = _layernorm(x, p["ln_scale"], p["ln_bias"])
        x = _pool_max(x, cfg.pool)
        c = cfg.internal
    x = x.reshape(x.shape[0], -1)
    return x @ params["enc_head"]["w"] + params["enc_head"]["b"]


def decode(params: dict, cfg: AEConfig, levels: list[jax.Array],
           z: jax.Array) -> jax.Array:
    """z: [B, latent] → f̂: [B, N, C]."""
    x = z @ params["dec_head"]["w"] + params["dec_head"]["b"]
    x = x.reshape(z.shape[0], cfg.level_points(cfg.blocks), cfg.internal)
    for b in range(cfg.blocks):
        lvl = cfg.blocks - b - 1
        x = _unpool(x, cfg.pool)
        conv = _conv(cfg, cfg.internal, cfg.internal)
        p = params["dec"][b]
        x = conv.apply(p, x, levels[lvl], levels[lvl])
        x = jax.nn.gelu(x)
        x = _layernorm(x, p["ln_scale"], p["ln_bias"])
    return x @ params["out_head"]["w"] + params["out_head"]["b"]


def reconstruct(params: dict, cfg: AEConfig, levels: list[jax.Array],
                f: jax.Array) -> jax.Array:
    return decode(params, cfg, levels, encode(params, cfg, levels, f))


def loss_fn(params: dict, cfg: AEConfig, levels: list[jax.Array],
            f: jax.Array) -> jax.Array:
    """Mean-squared reconstruction error (paper: MSE loss)."""
    rec = reconstruct(params, cfg, levels, f)
    return jnp.mean(jnp.square(rec - f))


def rel_frobenius(f: jax.Array, rec: jax.Array) -> jax.Array:
    """Paper Eq. 1: mean over samples of ‖F−F̂‖_F / ‖F‖_F."""
    num = jnp.sqrt(jnp.sum(jnp.square(f - rec), axis=(-2, -1)))
    den = jnp.sqrt(jnp.sum(jnp.square(f), axis=(-2, -1)))
    return jnp.mean(num / jnp.maximum(den, 1e-12))
