"""Plain reference of the ``quadconv_ae`` configuration.

Written from the paper (arXiv:2306.12900 section 4) and the QuadConv paper
(Doherty et al. 2023) in straightforward ``jax.numpy``; it imports nothing
of the program under test.  Three parts:

* the flat-plate snapshot the producer computes, from
  ``flatplate_ref.py``;
* the QuadConv autoencoder: per layer a filter MLP over every output-input
  offset, windowed by a compact bump, contracted against the quadrature
  weights and the input field; GELU (tanh form), LayerNorm, max-pool by 4;
* one in-situ training epoch: a uniform draw of ``gather`` snapshots from
  the ring's valid slots, per-channel standardisation, one held-out
  validation snapshot, shuffled mini-batches and Adam.

``arith`` selects the arithmetic (``bench/precision.py``): float32 at
matmul precision HIGHEST is the training reference; bfloat16 throughout
is the control that a correct program must beat.
The initial weights are made here from the seed, in one jitted call, and
handed to the program as its initial state.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from bench import precision as P
from bench.configs.flatplate_ref import grid_coords, make_snapshots  # noqa: F401


# ---------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------

def _mlp_sizes(cfg: dict, c_in: int, c_out: int) -> tuple[int, ...]:
    return (3,) + (cfg["mlp_width"],) * (cfg["mlp_depth"] - 1) + (c_in * c_out,)


def _layer_plan(cfg: dict) -> list[tuple[str, int, int, int]]:
    """(group, points, c_in, c_out) of each QuadConv layer, in order."""
    n, pool, blocks = cfg["n_points"], cfg["pool"], cfg["blocks"]
    plan, c = [], cfg["channels"]
    for b in range(blocks):
        plan.append(("enc", n // pool ** b, c, cfg["internal"]))
        c = cfg["internal"]
    for b in range(blocks):
        plan.append(("dec", n // pool ** (blocks - b - 1), cfg["internal"],
                     cfg["internal"]))
    return plan


def _bottleneck(cfg: dict) -> int:
    return cfg["n_points"] // cfg["pool"] ** cfg["blocks"] * cfg["internal"]


def _init(cfg: dict, key) -> dict:
    plan = _layer_plan(cfg)
    keys = iter(jax.random.split(key, 8 * len(plan) + 8))
    params: dict = {"enc": [], "dec": []}
    for group, pts, c_in, c_out in plan:
        sizes = _mlp_sizes(cfg, c_in, c_out)
        mlp = []
        for i, (a, b) in enumerate(zip(sizes[:-1], sizes[1:])):
            std = math.sqrt(2.0 / a) * (0.3 if i == len(sizes) - 2 else 1.0)
            mlp.append({"w": jax.random.normal(next(keys), (a, b)) * std,
                        "b": jnp.zeros((b,))})
        params[group].append({
            "quad_w": jnp.full((pts,), 1.0 / pts), "mlp": mlp,
            "bias": jnp.zeros((c_out,)),
            "ln_scale": jnp.ones((c_out,)), "ln_bias": jnp.zeros((c_out,))})
    bott, lat = _bottleneck(cfg), cfg["latent"]
    params["enc_head"] = {"w": jax.random.normal(next(keys), (bott, lat))
                          * math.sqrt(1.0 / bott), "b": jnp.zeros((lat,))}
    params["dec_head"] = {"w": jax.random.normal(next(keys), (lat, bott))
                          * math.sqrt(1.0 / lat), "b": jnp.zeros((bott,))}
    ci, ch = cfg["internal"], cfg["channels"]
    params["out_head"] = {"w": jax.random.normal(next(keys), (ci, ch))
                          * math.sqrt(1.0 / ci), "b": jnp.zeros((ch,))}
    return params


def init_params(cfg: dict, key) -> dict:
    """Initial weights from the seed, on the device, in one jitted call."""
    fn = jax.jit(partial(_init, cfg))
    return fn(key)


# ---------------------------------------------------------------------------
# the autoencoder
# ---------------------------------------------------------------------------

def _gelu(x):
    c = math.sqrt(2.0 / math.pi)
    return 0.5 * x * (1.0 + jnp.tanh(c * (x + 0.044715 * x ** 3)))


def _layernorm(x, scale, bias):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + 1e-5) * scale + bias


def quadconv(p: dict, cfg: dict, coords, f, c_in: int, c_out: int,
             arith: str = "float32"):
    """out[b,j,o] = sum_i w_i K(x_j - y_i)[o,c] f[b,i,c] + bias[o]."""
    d = coords[:, None, :] - coords[None, :, :]                # [J, I, 3]
    j, i, _ = d.shape
    h = d.reshape(j * i, 3)
    for n, layer in enumerate(p["mlp"]):
        h = P.operand(h, arith) @ P.operand(layer["w"], arith) + layer["b"]
        if n < len(p["mlp"]) - 1:
            h = _gelu(h)
    r2 = cfg["support"] ** 2
    bump = jnp.square(jnp.maximum(0.0, 1.0 - jnp.sum(d * d, -1) / r2))
    g = h.reshape(j, i, c_out, c_in) * bump[:, :, None, None]
    fw = P.operand(f * p["quad_w"][:, None], arith)
    return jnp.einsum("jioc,bic->bjo", P.operand(g, arith), fw) + p["bias"]


def reconstruct(params: dict, cfg: dict, coords, f, arith: str = "float32"):
    """f [B, N, C] -> reconstruction [B, N, C]."""
    def mm(a, b):
        return P.operand(a, arith) @ P.operand(b, arith)

    pool, blocks = cfg["pool"], cfg["blocks"]
    plan = _layer_plan(cfg)
    x = f
    for b in range(blocks):
        _, _, c_in, c_out = plan[b]
        p = params["enc"][b]
        x = quadconv(p, cfg, coords[:: pool ** b], x, c_in, c_out, arith)
        x = _layernorm(_gelu(x), p["ln_scale"], p["ln_bias"])
        bsz, n, c = x.shape
        x = jnp.max(x.reshape(bsz, n // pool, pool, c), axis=2)
    z = mm(x.reshape(x.shape[0], -1), params["enc_head"]["w"]) \
        + params["enc_head"]["b"]
    x = mm(z, params["dec_head"]["w"]) + params["dec_head"]["b"]
    x = x.reshape(z.shape[0], cfg["n_points"] // pool ** blocks, cfg["internal"])
    for b in range(blocks):
        _, _, c_in, c_out = plan[blocks + b]
        p = params["dec"][b]
        x = jnp.repeat(x, pool, axis=1)
        x = quadconv(p, cfg, coords[:: pool ** (blocks - b - 1)], x, c_in,
                     c_out, arith)
        x = _layernorm(_gelu(x), p["ln_scale"], p["ln_bias"])
    return mm(x, params["out_head"]["w"]) + params["out_head"]["b"]


def loss(params, cfg, coords, f, arith: str = "float32"):
    return jnp.mean(jnp.square(reconstruct(params, cfg, coords, f, arith) - f))


# ---------------------------------------------------------------------------
# one training epoch
# ---------------------------------------------------------------------------

def adam_step(params, mu, nu, count, grads, lr, b1=0.9, b2=0.999, eps=1e-8):
    """Adam with bias correction; ``count`` steps were taken before."""
    t = count + 1
    mu = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, mu, grads)
    nu = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, nu, grads)
    bc1, bc2 = 1 - b1 ** t, 1 - b2 ** t
    params = jax.tree.map(
        lambda p, m, v: p - lr * (m / bc1) / (jnp.sqrt(v / bc2) + eps),
        params, mu, nu)
    return params, mu, nu


def epoch_batches(gather: int, batch: int, rng, nvalid: int):
    """The epoch's draw from the ring: slot ranks, the held-out index and
    the order of the training snapshots (the program's rng contract)."""
    k_samp, k_val, k_perm = jax.random.split(rng, 3)
    ranks = jax.random.randint(k_samp, (gather,), 0, max(nvalid, 1))
    val_idx = jax.random.randint(k_val, (), 0, gather)
    n_train = max(gather - 1, 1)
    perm = jax.random.permutation(k_perm, n_train)
    return np.asarray(ranks), int(val_idx), np.asarray(perm)


class Trainer:
    """The reference's training epoch in one arithmetic (``arith``).
    Adam's moments and the weights stay float32; only the forward and
    backward passes run in ``arith``.  ``keep < 1`` plants a fault: each
    mini-batch's loss is the mean over its first ``keep`` share."""

    def __init__(self, cfg: dict, arith: str = "float32", keep: float = 1.0):
        self.cfg, self.arith, self.keep = cfg, arith, keep
        self.dtype = P.dtype(arith)
        self.coords = grid_coords(cfg).astype(self.dtype)
        self._grad = jax.jit(jax.value_and_grad(self._loss))

    def _loss(self, params, batch):
        n = max(1, int(batch.shape[0] * self.keep))
        with jax.default_matmul_precision("highest"):
            return loss(params, self.cfg, self.coords, batch[:n], self.arith)

    def epoch(self, params, mu, nu, count: int, data, val_idx: int, perm,
              batch: int, lr: float):
        """One epoch on ``data`` [G, N, C] (already standardised): the
        snapshots other than ``val_idx`` in ``perm`` order, in
        equal-sized clipped windows of ``batch``.  Returns
        ``(params, mu, nu, mean_train_loss, steps)``."""
        gather = data.shape[0]
        n_train = max(gather - 1, 1)
        bs = min(batch, n_train)
        n_batches = -(-n_train // bs)
        tr_idx = [(val_idx + 1 + k) % gather for k in range(gather - 1)] \
            if gather > 1 else [0]
        train = data[np.asarray(tr_idx)][np.asarray(perm)]
        losses = []
        for s in range(n_batches):
            start = min(s * bs, n_train - bs)
            b = train[start:start + bs].astype(self.dtype)
            p = jax.tree.map(lambda a: a.astype(self.dtype), params)
            val, grads = self._grad(p, b)
            grads = jax.tree.map(lambda g: g.astype(jnp.float32), grads)
            params, mu, nu = adam_step(params, mu, nu, count + s, grads, lr)
            losses.append(float(val))
        return params, mu, nu, float(np.mean(losses)), n_batches
