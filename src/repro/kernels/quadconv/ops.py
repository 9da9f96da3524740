"""Public entry point for the QuadConv contraction.

``quadconv_contract(f, w, g)`` computes

    out[b,j,o] = Σ_{i,c} w[i] · G[j,i,o,c] · f[b,i,c]

dispatching to:
* the Pallas kernel (compiled) on TPU backends;
* the Pallas kernel under ``interpret=True`` when ``mode="interpret"``
  (kernel-correctness tests on CPU);
* the pure-jnp oracle otherwise (CPU training runs — XLA's native GEMM is
  the right tool off-TPU).

The wrapper performs the layout work the kernel expects:
  f [B,I,C]   -> fm [B, I·C]           (row-major flatten)
  w [I]       -> wk [1, I·C]           (repeat each weight C times)
  g [J,I,O,C] -> gm [I·C, J·O]         (transpose to (I,C,J,O), flatten)
and pads every GEMM dim up to the block size (zero padding is exact for a
sum contraction).  The custom VJP computes the three gradient
contractions as XLA einsums: only the forward pass runs the kernel.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import ref as _ref
from .kernel import quadconv_matmul

__all__ = ["quadconv_contract", "preferred_mode"]


def preferred_mode() -> str:
    return "pallas" if jax.default_backend() == "tpu" else "ref"


def _pad_to(x: jax.Array, axis: int, mult: int) -> jax.Array:
    size = x.shape[axis]
    pad = (-size) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def _contract_gemm(fm, wk, gm, mode, bm, bn, bk):
    m, k = fm.shape
    n = gm.shape[1]
    bm_, bn_, bk_ = min(bm, m), min(bn, n), min(bk, k)
    fm_p = _pad_to(_pad_to(fm, 0, bm_), 1, bk_)
    wk_p = _pad_to(wk, 1, bk_)
    gm_p = _pad_to(_pad_to(gm, 0, bk_), 1, bn_)
    out = quadconv_matmul(fm_p, wk_p, gm_p, bm=bm_, bn=bn_, bk=bk_,
                          interpret=(mode == "interpret"))
    return out[:m, :n]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def quadconv_contract(f: jax.Array, w: jax.Array, g: jax.Array,
                      mode: str | None = None, bm: int = 128, bn: int = 128,
                      bk: int = 512) -> jax.Array:
    """out[b,j,o] = Σ_{i,c} w[i] G[j,i,o,c] f[b,i,c].  See module docstring."""
    return _fwd(f, w, g, mode, bm, bn, bk)[0]


def _fwd(f, w, g, mode, bm, bn, bk):
    mode = mode or preferred_mode()
    b, i, c = f.shape
    j, i2, o, c2 = g.shape
    assert (i, c) == (i2, c2) and w.shape == (i,), (f.shape, w.shape, g.shape)
    if mode == "ref":
        return _ref.quadconv_contract(f, w, g), (f, w, g)
    fm = f.reshape(b, i * c)
    wk = jnp.repeat(w, c)[None, :]
    gm = g.transpose(1, 3, 0, 2).reshape(i * c, j * o)
    out = _contract_gemm(fm, wk, gm, mode, bm, bn, bk)
    return out.reshape(b, j, o), (f, w, g)


def _bwd(mode, bm, bn, bk, res, ct):
    f, w, g = res
    # ct: [B,J,O]
    # df[b,i,c] = w[i] Σ_{j,o} G[j,i,o,c] ct[b,j,o]
    # dw[i]     = Σ_{b,j,o,c} G[j,i,o,c] f[b,i,c] ct[b,j,o]
    # dG[j,i,o,c] = w[i] f[b,i,c] ct[b,j,o] summed over b
    df = jnp.einsum("bjo,jioc,i->bic", ct, g, w).astype(f.dtype)
    dw = jnp.einsum("bjo,jioc,bic->i", ct, g, f).astype(w.dtype)
    dg = jnp.einsum("bjo,bic,i->jioc", ct, f, w).astype(g.dtype)
    return df, dw, dg


quadconv_contract.defvjp(_fwd, _bwd)
