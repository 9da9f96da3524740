"""Pallas TPU kernels for the QuadConv quadrature contraction and its VJP.

Layout.  The kernel tensor is ``G[J, R, I]`` with ``R = O·C`` (row
``r = o·C + c``): output points major, the filter's features in the
middle, input points minor.  It is the layout XLA gives the filter MLP
when ``ml.quadconv.QuadConv.kernel_tensor`` evaluates it feature-major
(a dot over ``[width, J, I]`` hidden states lands as ``[J][R][I]``), so
``G`` is born in it, and every pass here reads or writes it in it:

* the input points fill the 128 lanes and the features (64 or 256) the
  sublanes, so a block ``G[jb, :, ib]`` is dense; a layout with a 16-wide
  channel axis last is padded to 128 lanes in HBM, 8× the bytes;
* a block is one matrix ``G2[(j, r), i]`` of ``bj·R`` rows, so each pass
  is one matmul per block with ``G2`` never transposed.

The channel ``c`` of a row pairs with the channel of the features, so the
small operand of each matmul carries all channels along its 128-wide
side, ``(c', b)`` with the batch padded to ``Bp`` (a multiple of 8), and a
mask keeps ``c' == c``:

* ``quadconv_matmul``, the forward: ``P[(j,r), (c',b)] = Σ_i G2 ·
  fwx[i, (c',b)]`` with ``fwx = w ⊙ f``, masked to ``c' = c(r)`` and
  summed over ``c'``: ``z[b, (j, r)] = Σ_i G[j,r,i] · w[i] f[b,i,c(r)]``.
  ``ops.py`` sums ``z`` over the ``C`` rows of each output channel.
  Grid ``(j-block | i-block)``.
* ``quadconv_bwd_q``, the backward pass's one read of ``G``:
  ``Q[(c,b), i] = Σ_{j,r} ct[b, j, o(r)] [c(r) = c] · G[j,r,i]``.
  Grid ``(i-block | j-block)``.  ``ops.py`` takes ``df = w ⊙ Q`` and
  ``dw = Σ_{b,c} Q ⊙ f`` from it.
* ``quadconv_bwd_dg``, the backward pass's one write of ``dG``, in
  ``G``'s layout: ``dG[j,r,i] = Σ_b ct[b, j, o(r)] · w[i] f[b,i,c(r)]``.
  Every grid axis parallel.

The cotangent comes in as ``ctr[b, (j, r)] = ct[b, j, o(r)]``, repeated
over ``c`` (``ops.py``).  A block holds ``bj`` output points × ``R`` ×
``bi`` input points; ``bj·R`` is a multiple of 128 and ``bi`` a multiple
of 128 or all of ``I`` (``ops.blocks``).  Dots take float32 operands at
the default precision and accumulate in float32; masks, transposes and
the sums over ``c'`` are exact.  On CPU the kernels run under
``interpret=True`` (tests).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["quadconv_matmul", "quadconv_bwd_q", "quadconv_bwd_dg"]

#: Scoped VMEM for every kernel: two 4 MiB blocks of ``G`` in flight plus
#: the small operands and temporaries, within v5e's 128 MiB.
VMEM_BYTES = 48 << 20


def _params(*semantics: str) -> pltpu.CompilerParams:
    return pltpu.CompilerParams(dimension_semantics=semantics,
                                vmem_limit_bytes=VMEM_BYTES)


def _check(shape, bj, bi, c):
    j, r, i = shape
    if j % bj or i % bi or r % c or r % 8:
        raise ValueError(f"G {shape} must divide into blocks ({bj},{r},{bi}) "
                         f"of {c} channels; ops.py pads before calling")


def _rows(g_ref):
    """The block ``G[jb, :, ib]`` as one matrix ``[bj·R, bi]``, float32."""
    bj, r, bi = g_ref.shape
    return g_ref[...].reshape(bj * r, bi).astype(jnp.float32)


def _spread(ctr, c: int, w: int):
    """``[Bp, L]`` -> ``[W, L]``: row ``(c', b)`` holds ``ctr[b]`` where the
    lane's channel ``c(r)`` is ``c'``, else 0."""
    bp, length = ctr.shape
    x = jnp.concatenate([ctr] * (w // bp), axis=0)
    row = jax.lax.broadcasted_iota(jnp.int32, (w, length), 0) // bp
    col = jax.lax.broadcasted_iota(jnp.int32, (w, length), 1) % c
    return jnp.where(row == col, x, 0.0)


def _fwd_kernel(g_ref, fwx_ref, z_ref, acc_ref, *, c: int, bp: int):
    ib = pl.program_id(1)

    @pl.when(ib == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(_rows(g_ref), fwx_ref[...],
                            preferred_element_type=jnp.float32)

    @pl.when(ib == pl.num_programs(1) - 1)
    def _store():
        p = acc_ref[...]                                   # [L, W]
        length, w = p.shape
        row = jax.lax.broadcasted_iota(jnp.int32, p.shape, 0) % c
        col = jax.lax.broadcasted_iota(jnp.int32, p.shape, 1) // bp
        y = jnp.where(row == col, p, 0.0).T                # [W, L]
        z_ref[...] = y.reshape(w // bp, bp, length).sum(0).astype(z_ref.dtype)


@functools.partial(jax.jit, static_argnames=("c", "bp", "bj", "bi",
                                             "interpret"))
def quadconv_matmul(g: jax.Array, fwx: jax.Array, *, c: int, bp: int,
                    bj: int, bi: int, interpret: bool = False) -> jax.Array:
    """Forward contraction in ``G``'s own layout.

    Args:
      g:   [J, R, I] kernel tensor, ``R = O·C``.
      fwx: [I, W] weighted features, ``fwx[i, c·Bp + b] = w[i] f[b,i,c]``,
           float32, zero beyond ``C·Bp``.
    Returns:
      [Bp, J·R] float32:
      ``z[b, j·R + r] = Σ_i g[j,r,i] fwx[i, c(r)·Bp + b]``.
    """
    j, r, i = g.shape
    w = fwx.shape[1]
    assert fwx.shape == (i, w) and w % bp == 0, (g.shape, fwx.shape, bp)
    _check(g.shape, bj, bi, c)
    length = bj * r
    return pl.pallas_call(
        functools.partial(_fwd_kernel, c=c, bp=bp),
        grid=(j // bj, i // bi),
        in_specs=[pl.BlockSpec((bj, r, bi), lambda jb, ib: (jb, 0, ib)),
                  pl.BlockSpec((bi, w), lambda jb, ib: (ib, 0))],
        out_specs=pl.BlockSpec((bp, length), lambda jb, ib: (0, jb)),
        out_shape=jax.ShapeDtypeStruct((bp, j * r), jnp.float32),
        scratch_shapes=[pltpu.VMEM((length, w), jnp.float32)],
        compiler_params=_params("parallel", "arbitrary"),
        interpret=interpret,
    )(g, fwx)


def _bwd_q_kernel(ctr_ref, g_ref, q_ref, acc_ref, *, c: int):
    jb = pl.program_id(1)

    @pl.when(jb == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = _spread(ctr_ref[...], c, acc_ref.shape[0])         # [W, L]
    acc_ref[...] += jnp.dot(x, _rows(g_ref),
                            preferred_element_type=jnp.float32)

    @pl.when(jb == pl.num_programs(1) - 1)
    def _store():
        q_ref[...] = acc_ref[...].astype(q_ref.dtype)


@functools.partial(jax.jit, static_argnames=("c", "w", "bj", "bi",
                                             "interpret"))
def quadconv_bwd_q(g: jax.Array, ctr: jax.Array, *, c: int, w: int,
                   bj: int, bi: int, interpret: bool = False) -> jax.Array:
    """The backward pass's read of ``G``.

    Args:
      g:   [J, R, I] kernel tensor.
      ctr: [Bp, J·R] cotangent repeated over channels,
           ``ctr[b, j·R + r] = ct[b, j, o(r)]``, float32.
      w:   rows of the result, a multiple of 8 at least ``C·Bp``.
    Returns:
      [W, I] float32: ``Q[c·Bp + b, i] = Σ_{j, r: c(r)=c} ctr[b, j·R + r]
      g[j,r,i]``, zero beyond ``C·Bp``.
    """
    j, r, i = g.shape
    bp = ctr.shape[0]
    assert ctr.shape == (bp, j * r), (g.shape, ctr.shape)
    _check(g.shape, bj, bi, c)
    return pl.pallas_call(
        functools.partial(_bwd_q_kernel, c=c),
        grid=(i // bi, j // bj),
        in_specs=[pl.BlockSpec((bp, bj * r), lambda ib, jb: (0, jb)),
                  pl.BlockSpec((bj, r, bi), lambda ib, jb: (jb, 0, ib))],
        out_specs=pl.BlockSpec((w, bi), lambda ib, jb: (0, ib)),
        out_shape=jax.ShapeDtypeStruct((w, i), jnp.float32),
        scratch_shapes=[pltpu.VMEM((w, bi), jnp.float32)],
        compiler_params=_params("parallel", "arbitrary"),
        interpret=interpret,
    )(ctr, g)


def _bwd_dg_kernel(ctr_ref, fwr_ref, dg_ref, *, c: int):
    bj, r, bi = dg_ref.shape
    x = _spread(ctr_ref[...], c, fwr_ref.shape[0]).T       # [L, W]
    dg = jnp.dot(x, fwr_ref[...], preferred_element_type=jnp.float32)
    dg_ref[...] = dg.reshape(bj, r, bi).astype(dg_ref.dtype)


@functools.partial(jax.jit, static_argnames=("c", "shape", "dtype", "bj",
                                             "bi", "interpret"))
def quadconv_bwd_dg(ctr: jax.Array, fwr: jax.Array, *, c: int, shape: tuple,
                    dtype, bj: int, bi: int,
                    interpret: bool = False) -> jax.Array:
    """The backward pass's write of ``dG``, in ``G``'s layout.

    Args:
      ctr: [Bp, J·R] cotangent repeated over channels (``quadconv_bwd_q``).
      fwr: [W, I] weighted features, ``fwr[c·Bp + b, i] = w[i] f[b,i,c]``,
           float32, zero beyond ``C·Bp``.
      shape, dtype: ``G``'s, ``(J, R, I)``.
    Returns:
      [J, R, I]: ``dG[j,r,i] = Σ_b ctr[b, j·R + r] fwr[c(r)·Bp + b, i]``.
    """
    j, r, i = shape
    bp = ctr.shape[0]
    w = fwr.shape[0]
    assert ctr.shape == (bp, j * r) and fwr.shape == (w, i), \
        (shape, ctr.shape, fwr.shape)
    _check(shape, bj, bi, c)
    return pl.pallas_call(
        functools.partial(_bwd_dg_kernel, c=c),
        grid=(j // bj, i // bi),
        in_specs=[pl.BlockSpec((bp, bj * r), lambda jb, ib: (0, jb)),
                  pl.BlockSpec((w, bi), lambda jb, ib: (0, ib))],
        out_specs=pl.BlockSpec((bj, r, bi), lambda jb, ib: (jb, 0, ib)),
        out_shape=jax.ShapeDtypeStruct(shape, dtype),
        compiler_params=_params("parallel", "parallel"),
        interpret=interpret,
    )(ctr, fwr)
