"""Gradient / payload compression (distributed-optimization tricks).

* ``quantize_int8`` / ``dequantize_int8`` — per-tensor-block symmetric int8
  with fp32 scales: 4× wire-size reduction for DP gradient all-reduce or
  store transfers (the in-situ framework's send path can compress solution
  snapshots the same way — the paper's autoencoder is the learned version
  of this lever).
* ``ErrorFeedback`` — residual accumulation (1-bit-Adam style): the
  quantization error of step *t* is added back to the gradient of step
  *t+1*, which keeps SGD convergence unbiased.
* ``compressed_psum_mean`` — the *inside-shard_map* form: int8-quantize the
  local gradient, ``psum`` the int32 accumulator over a named mesh axis,
  dequantize with the rank-mean scale.  This is the DDP gradient sync the
  sharded fused epoch (``ml.trainer.make_sharded_fused_epoch``) embeds in
  its one-dispatch ``shard_map``.
* ``compressed_allreduce`` — standalone shard_map DP all-reduce built on
  ``compressed_psum_mean`` (wire bytes ≈ ¼ of fp32), used by the
  explicit-DP in-situ trainer.
"""

from __future__ import annotations

from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P
from jax import shard_map

__all__ = ["quantize_int8", "dequantize_int8", "ErrorFeedback",
           "compressed_psum_mean", "compressed_psum_mean_ef",
           "compressed_allreduce", "compression_ratio"]


class QTensor(NamedTuple):
    q: jax.Array        # int8 payload
    scale: jax.Array    # fp32 per-block scale


def quantize_int8(x: jax.Array, block: int = 256) -> QTensor:
    flat = x.reshape(-1).astype(jnp.float32)
    pad = (-flat.size) % block
    flat = jnp.pad(flat, (0, pad))
    blocks = flat.reshape(-1, block)
    scale = jnp.max(jnp.abs(blocks), axis=1, keepdims=True) / 127.0
    scale = jnp.maximum(scale, 1e-12)
    q = jnp.clip(jnp.round(blocks / scale), -127, 127).astype(jnp.int8)
    return QTensor(q=q, scale=scale)


def dequantize_int8(qt: QTensor, shape, dtype=jnp.float32) -> jax.Array:
    flat = (qt.q.astype(jnp.float32) * qt.scale).reshape(-1)
    n = 1
    for s in shape:
        n *= s
    return flat[:n].reshape(shape).astype(dtype)


def compression_ratio(x: jax.Array, block: int = 256) -> float:
    raw = x.size * jnp.dtype(jnp.float32).itemsize
    comp = x.size * 1 + (x.size // block + 1) * 4
    return raw / comp


class ErrorFeedback:
    """Residual error feedback for biased compressors (host-side state)."""

    def __init__(self):
        self.residual: Any = None

    def compress(self, grads: Any, block: int = 256):
        if self.residual is not None:
            grads = jax.tree.map(lambda g, r: g + r.astype(g.dtype),
                                 grads, self.residual)
        qts = jax.tree.map(lambda g: quantize_int8(g, block), grads,
                           is_leaf=lambda x: isinstance(x, jax.Array))
        deq = jax.tree.map(
            lambda g, qt: dequantize_int8(qt, g.shape, g.dtype),
            grads, qts, is_leaf=lambda x: isinstance(x, jax.Array))
        self.residual = jax.tree.map(lambda g, d: (g - d), grads, deq)
        return qts, deq


def _wire_psum_mean(g: jax.Array, axis: str, n_ranks: int, block: int
                    ) -> tuple[jax.Array, QTensor]:
    """The int8 wire for one leaf: quantize the local value, ``psum`` the
    int8 payload in int32 (no overflow for ≤2^23 ranks), dequantize with
    the rank-mean scale.  Returns ``(mean, local QTensor)`` so callers
    can also reconstruct their own contribution (error feedback)."""
    qt = quantize_int8(g, block)
    qsum = jax.lax.psum(qt.q.astype(jnp.int32), axis)
    # per-rank scales differ; dequantize with the mean scale and let
    # error feedback absorb the residual bias.
    smean = jax.lax.psum(qt.scale, axis) / n_ranks
    mean = (qsum.astype(jnp.float32) * smean) / n_ranks
    return mean.reshape(-1)[: g.size].reshape(g.shape).astype(g.dtype), qt


def compressed_psum_mean(grads: Any, axis: str, n_ranks: int,
                         block: int = 256) -> Any:
    """int8-wire mean-all-reduce of a *local* gradient pytree.

    Call inside a ``shard_map``/``pmap`` body over the named mesh axis
    ``axis`` (of size ``n_ranks``): each rank quantizes its local
    gradient and the payloads meet on the wire (see
    :func:`_wire_psum_mean`) — the traffic is ≈ ¼ of an fp32 all-reduce.
    Per-step bias from the shared scale is absorbed by
    :class:`ErrorFeedback` / :func:`compressed_psum_mean_ef` when
    convergence parity matters; the sharded fused epoch exposes it as the
    ``ddp="int8"`` knob.
    """
    return jax.tree.map(
        lambda g: _wire_psum_mean(g, axis, n_ranks, block)[0], grads)


def compressed_psum_mean_ef(grads: Any, residuals: Any, axis: str,
                            n_ranks: int, block: int = 256
                            ) -> tuple[Any, Any]:
    """:func:`compressed_psum_mean` with error feedback in the carry.

    The host-side :class:`ErrorFeedback` cannot ride a fused epoch — its
    residual lives outside the jit.  This is the traceable form: the
    caller threads ``residuals`` (same pytree as ``grads``, zeros at epoch
    start) through its ``lax.scan`` carry.  Each rank adds its residual to
    the local gradient *before* quantizing, and the new residual is the
    part of the compensated gradient its own int8 contribution dropped —
    so the compressed wire no longer silently discards quantization error
    step after step.  Returns ``(mean_grads, new_residuals)``.
    """
    def _one(g, r):
        comp = g + r.astype(g.dtype)
        mean, qt = _wire_psum_mean(comp, axis, n_ranks, block)
        return mean, comp - dequantize_int8(qt, g.shape, g.dtype)

    leaves_g, tdef = jax.tree.flatten(grads)
    leaves_r = jax.tree.leaves(residuals)
    outs = [_one(g, r) for g, r in zip(leaves_g, leaves_r)]
    return (tdef.unflatten([m for m, _ in outs]),
            tdef.unflatten([r for _, r in outs]))


def compressed_allreduce(grad_stack: Any, mesh: Mesh, axis: str = "data",
                         block: int = 256) -> Any:
    """Mean-all-reduce of per-rank gradients with an int8 wire format.

    ``grad_stack`` leaves are [n_ranks, ...] (rank axis sharded over
    ``axis``): the standalone ``shard_map`` wrapper around
    :func:`compressed_psum_mean`.  Biased per step — pair with
    ErrorFeedback.  Returns the mean gradient, replicated (leaves [...]).
    """
    n = mesh.shape[axis]

    def _one(g_stack):
        def _worker(gl):
            return compressed_psum_mean(gl[0], axis, n, block)

        fn = shard_map(_worker, mesh=mesh,
                       in_specs=(P(axis),), out_specs=P(),
                       check_vma=False)
        return fn(g_stack)

    return jax.tree.map(_one, grad_stack)
