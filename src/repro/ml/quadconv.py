"""QuadConv: quadrature-based continuous convolution (Doherty et al. 2023).

The operator behind the paper's autoencoder (§4).  A continuous convolution
over a *non-uniform* point cloud is approximated with one quadrature sum,

    (K ∗ f)(x_j) ≈ Σ_i  w_i · K_θ(x_j − y_i) · f(y_i),

where both the quadrature weights ``w_i`` and the kernel ``K_θ`` (a 5-layer
MLP mapping 3-D offsets to an O×C matrix, paper: R³ → R^{16×16}) are learned.
Compact support is enforced with a smooth bump window so kernels stay local
on the stretched boundary-layer grid.

The pairwise contraction (the FLOPs hot spot) is delegated to
``repro.kernels.quadconv``: Pallas kernels on TPU for the forward
(``quadconv_matmul``) and the backward (``quadconv_bwd_q`` reads ``G``,
``quadconv_bwd_dg`` writes ``dG``), the einsum oracle on CPU.  The kernel
tensor's evaluation over J×I offsets is a plain MLP left to XLA.  It runs
feature-major, hidden states ``[width, J, I]``, which XLA lays out
``[J][width][I]``; so ``G`` is born as ``[J, O·C, I]`` (input points
minor), the layout every pass of the contraction reads and writes.  No
pass relays out the 1-GB-class ``G`` or its gradient, and no 16-wide
channel axis is padded to 128 lanes (``kernels/quadconv/kernel.py``).

Spectral normalization from the original QuadConv MLPs is omitted — the
paper removes it "to ensure traceability for online inference"; we keep
LayerNorm between autoencoder blocks instead (see ``autoencoder.py``).
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp

from ..kernels.quadconv import quadconv_contract

__all__ = ["QuadConv", "mlp_init", "mlp_apply"]


def mlp_init(key, sizes: tuple[int, ...], scale: float = 1.0) -> list[dict]:
    """Plain MLP params: list of {w,b}; he-style init, small final layer."""
    params = []
    keys = jax.random.split(key, len(sizes) - 1)
    for i, (din, dout) in enumerate(zip(sizes[:-1], sizes[1:])):
        std = jnp.sqrt(2.0 / din)
        if i == len(sizes) - 2:
            std = std * scale
        params.append({
            "w": jax.random.normal(keys[i], (din, dout)) * std,
            "b": jnp.zeros((dout,)),
        })
    return params


def mlp_apply(params: list[dict], x: jax.Array) -> jax.Array:
    """The MLP feature-major: ``x`` [fan_in, ...] -> [fan_out, ...].  Same
    params as row-major (``w`` [fan_in, fan_out]), contracted on fan_in."""
    bcast = (slice(None),) + (None,) * (x.ndim - 1)
    for i, layer in enumerate(params):
        x = jax.lax.dot_general(layer["w"], x, (((0,), (0,)), ((), ()))) \
            + layer["b"][bcast]
        if i < len(params) - 1:
            x = jax.nn.gelu(x)
    return x


def _bump(d2: jax.Array, r: float) -> jax.Array:
    """C¹ compact-support window: (max(0, 1 − (d/r)²))²."""
    return jnp.square(jnp.maximum(0.0, 1.0 - d2 / (r * r)))


@dataclass(frozen=True)
class QuadConv:
    """One QuadConv layer: I input points/C channels → J output points/O.

    Static hyper-parameters only; learned state lives in the params dict so
    the layer is a pure function (jit/pjit friendly).
    """

    c_in: int
    c_out: int
    mlp_width: int = 32
    mlp_depth: int = 5          # paper: five-layer filter MLPs
    support: float = 0.75       # compact-support radius (domain units)
    mode: str | None = None     # kernel dispatch: None=auto|"ref"|"interpret"

    def init(self, key, n_in_points: int) -> dict:
        km, kw = jax.random.split(key)
        sizes = (3,) + (self.mlp_width,) * (self.mlp_depth - 1) \
            + (self.c_out * self.c_in,)
        return {
            # learned quadrature weights, init to uniform rule 1/I
            "quad_w": jnp.full((n_in_points,), 1.0 / n_in_points),
            "mlp": mlp_init(km, sizes, scale=0.3),
            "bias": jnp.zeros((self.c_out,)),
        }

    def kernel_tensor(self, params: dict, coords_out: jax.Array,
                      coords_in: jax.Array) -> jax.Array:
        """G[j, o·C + c, i] = MLP(x_j − y_i)[o,c] ⊙ bump(|x_j − y_i|),
        ``[J, O·C, I]``, traced under the named scope
        ``quadconv.kernel_tensor``.  The MLP runs feature-major over
        ``[width, J, I]``; XLA lays that out ``[J][width][I]``, so the
        final transpose is free and ``G`` is born in the contraction's
        layout."""
        with jax.named_scope("quadconv.kernel_tensor"):
            # [3, J, I]
            deltas = coords_out.T[:, :, None] - coords_in.T[:, None, :]
            g = mlp_apply(params["mlp"], deltas)                     # [OC,J,I]
            win = _bump(jnp.sum(deltas * deltas, 0), self.support)  # [J,I]
            return (g * win).transpose(1, 0, 2)

    def apply(self, params: dict, f: jax.Array, coords_in: jax.Array,
              coords_out: jax.Array) -> jax.Array:
        """f: [B, I, C_in] → [B, J, C_out]."""
        g = self.kernel_tensor(params, coords_out, coords_in)
        out = quadconv_contract(f, params["quad_w"], g, self.mode)
        return out + params["bias"]
