"""Public entry point for the QuadConv contraction.

``quadconv_contract(f, w, g)`` computes

    out[b,j,o] = Σ_{c,i} G[j, o·C + c, i] · w[i] f[b,i,c]

with the kernel tensor in the layout it is built in, ``G[J, O·C, I]``
(input points minor; ``kernel.py`` says why), dispatching to

* the Pallas kernels (compiled) on TPU backends, forward and backward;
* the same kernels under ``interpret=True`` when ``mode="interpret"``
  (kernel-correctness tests on CPU);
* the pure-jnp oracle otherwise (``mode="ref"``, CPU training runs), which
  autodiff differentiates: XLA's native GEMM is the right tool off-TPU.

``G`` and ``dG`` are never relaid out.  Only the small operands are:
``f`` [B,I,C] and ``w`` [I] become ``w ⊙ f`` with rows or lanes
``(c, b)``, the cotangent [B,J,O] becomes ``ctr[b, (j, r)]``, and the
kernels' outputs are relaid back (the forward's is also summed over the
``C`` rows of each output channel).  The batch pads to ``Bp``; a
configuration's layers need no other padding, but rows ``O·C`` that are
not a multiple of 8, or a points axis too long for one block with no
aligned divisor, pad ``G`` (zero padding is exact for a sum contraction).

The custom VJP makes one pass over each 1-GB-class tensor:
``quadconv_bwd_q`` reads ``G`` once for ``Q[b,i,c] = Σ_{j,o}
G[j, o·C + c, i] ct[b,j,o]``, whence ``df = w ⊙ Q`` and ``dw = Σ_{b,c}
Q ⊙ f``; ``quadconv_bwd_dg`` writes ``dG`` once, in ``G``'s layout.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from . import ref as _ref
from .kernel import quadconv_bwd_dg, quadconv_bwd_q, quadconv_matmul

__all__ = ["quadconv_contract", "preferred_mode", "blocks"]

#: Elements of ``G`` per block: 4 MiB of float32.
BLOCK_ELEMS = 1 << 20
_LANE, _SUBLANE = 128, 8


def preferred_mode() -> str:
    return "pallas" if jax.default_backend() == "tpu" else "ref"


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def _batch_pad(b: int) -> int:
    """Bp: 8, 16, 32, 64 or a multiple of 128, so that it divides the
    128-lane side ``W`` of the small operands."""
    bp = _SUBLANE
    while bp < b and bp < _LANE:
        bp *= 2
    return _round_up(b, _LANE) if b > _LANE else bp


def blocks(j: int, r: int, i: int) -> tuple[int, int]:
    """``(bj, bi)``: output and input points of ``G[J, R, I]`` per block.
    Whole rows of input points if they fit (``bi``), else the largest
    multiple of 128 that divides ``I`` (``I`` pads to it if none does);
    then as many output points (``bj``, a divisor of ``J`` with ``bj·R`` a
    multiple of 128, or all of ``J``) as fit ``BLOCK_ELEMS``."""
    cap = max(BLOCK_ELEMS // r // _LANE * _LANE, _LANE)
    if i <= cap:
        bi = i
    else:
        bi = next((b for b in range(cap, 0, -_LANE) if i % b == 0), cap)
    fits = [d for d in range(1, j + 1) if j % d == 0
            and d * r * bi <= BLOCK_ELEMS and (d * r) % _LANE == 0]
    return (max(fits) if fits else j), bi


def _pad_to(x: jax.Array, axis: int, mult: int) -> jax.Array:
    pad = (-x.shape[axis]) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def quadconv_contract(f: jax.Array, w: jax.Array, g: jax.Array,
                      mode: str | None = None) -> jax.Array:
    """out[b,j,o] = Σ_{c,i} w[i] G[j, o·C + c, i] f[b,i,c].  See the module
    docstring.

    Args:
      f: [B, I, C] features on the I input points.
      w: [I] quadrature weights.
      g: [J, O·C, I] kernel tensor.
    Returns:
      [B, J, O], in ``f``'s dtype.
    """
    b, i, c = f.shape
    j, r, i2 = g.shape
    assert i == i2 and r % c == 0 and w.shape == (i,), \
        (f.shape, w.shape, g.shape)
    mode = mode or preferred_mode()
    if mode == "ref":
        return _ref.quadconv_contract(f, w, g)
    return _contract(f, w, g, mode == "interpret")


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _contract(f, w, g, interpret):
    return _fwd(f, w, g, interpret)[0]


def _plan(f, g):
    """Block sizes and the padded ``G``: rows to a multiple of 8, points
    to whole blocks (zero padding is exact for a sum contraction)."""
    b, _, c = f.shape
    j, r, i = g.shape
    rp = _round_up(r, math.lcm(_SUBLANE, c))
    bj, bi = blocks(j, rp, i)
    gp = _pad_to(_pad_to(_pad_to(g, 1, rp), 0, bj), 2, bi)
    bp = _batch_pad(b)
    return gp, bj, bi, bp, _round_up(c * bp, _LANE)


def _weighted(f, w, bp, width, bi):
    """fw[c·Bp + b, i] = w[i] f[b,i,c], float32, ``[W, Ip]``."""
    b, i, c = f.shape
    fw = f.astype(jnp.float32) * w.astype(jnp.float32)[:, None]
    fw = _pad_to(fw.transpose(2, 0, 1), 1, bp).reshape(c * bp, i)
    return _pad_to(_pad_to(fw, 0, width), 1, bi)


def _fwd(f, w, g, interpret):
    b, i, c = f.shape
    j, r, _ = g.shape
    gp, bj, bi, bp, width = _plan(f, g)
    z = quadconv_matmul(gp, _weighted(f, w, bp, width, bi).T, c=c, bp=bp,
                        bj=bj, bi=bi, interpret=interpret)
    z = z.reshape(bp, gp.shape[0], gp.shape[1])[:b, :j, :r]
    out = z.reshape(b, j, r // c, c).sum(-1).astype(f.dtype)
    return out, (f, w, g)


def _bwd(interpret, res, ct):
    f, w, g = res
    b, i, c = f.shape
    j, r, _ = g.shape
    gp, bj, bi, bp, width = _plan(f, g)
    jp, rp, _ = gp.shape
    ctr = jnp.repeat(ct.astype(jnp.float32), c, axis=-1)       # [B, J, R]
    ctr = _pad_to(_pad_to(_pad_to(ctr, 0, bp), 1, bj), 2, rp)
    ctr = ctr.reshape(bp, jp * rp)
    q = quadconv_bwd_q(gp, ctr, c=c, w=width, bj=bj, bi=bi,
                       interpret=interpret)
    q = q[:c * bp].reshape(c, bp, -1)[:, :b, :i].transpose(1, 2, 0)
    df = (q * w.astype(jnp.float32)[:, None]).astype(f.dtype)
    dw = jnp.sum(q * f.astype(jnp.float32), axis=(0, 2)).astype(w.dtype)
    dg = quadconv_bwd_dg(ctr, _weighted(f, w, bp, width, bi), c=c,
                         shape=gp.shape, dtype=g.dtype, bj=bj, bi=bi,
                         interpret=interpret)
    return df, dw, dg[:j, :r, :i]


_contract.defvjp(_fwd, _bwd)
