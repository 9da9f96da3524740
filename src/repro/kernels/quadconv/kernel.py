"""Pallas TPU kernel for the QuadConv quadrature contraction.

TPU adaptation (vs the paper's CUDA/PyTorch path): the contraction

    out[b, j, o] = Σ_{i,c} w[i] · G[j,i,o,c] · f[b,i,c]

is reshaped into a single GEMM  ``out[B, J·O] = F'[B, I·C] @ Gm[I·C, J·O]``
with the quadrature weighting ``F' = f ⊙ w`` **fused into the LHS load** —
so the weighted field is never materialized in HBM.  The kernel is a
classic MXU-tiled matmul:

* grid = (B/bm, J·O/bn, I·C/bk); the K axis is innermost so each (m, n)
  output tile stays resident in VMEM across the K loop (accumulate in
  fp32), written once on the last K step.
* block shapes default to (128, 128, 512): MXU-aligned 128-lane tiles;
  VMEM footprint = bm·bk (F) + bk·bn (G) + bm·bn (acc) floats
  = (128·512 + 512·128 + 128·128)·4B ≈ 0.6 MB ≪ 16 MB v5e VMEM,
  leaving room for double buffering of the streamed G tiles.
* ``w`` is pre-expanded to the flattened I·C axis by the ops wrapper and
  passed as a ``[1, K]`` row (a ``[1, bk]`` block per K tile — a legal TPU
  block whose layout matches XLA's), broadcast-multiplied into the F tile
  on load — one VPU multiply per element, free next to the MXU work.

On CPU the kernel runs under ``interpret=True`` (tests); ``ops.py`` picks
the execution mode.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["quadconv_matmul"]


def _kernel(f_ref, w_ref, g_ref, out_ref, acc_ref, *, n_k: int):
    """One (m, n, k) grid step: acc += (F ⊙ w)[m, k] @ G[k, n]."""
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    f_blk = f_ref[...].astype(jnp.float32) * w_ref[...].astype(jnp.float32)
    g_blk = g_ref[...].astype(jnp.float32)
    acc_ref[...] += jax.lax.dot_general(
        f_blk, g_blk, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    @pl.when(k == n_k - 1)
    def _store():
        out_ref[...] = acc_ref[...].astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret"))
def quadconv_matmul(fm: jax.Array, wk: jax.Array, gm: jax.Array,
                    bm: int = 128, bn: int = 128, bk: int = 512,
                    interpret: bool = False) -> jax.Array:
    """Fused quadrature-weighted GEMM.

    Args:
      fm: [M, K]  flattened features (M = batch, K = I·C).
      wk: [1, K]  quadrature weights pre-broadcast to the K axis.
      gm: [K, N]  flattened kernel tensor (N = J·O).
    Returns:
      [M, N] = (fm ⊙ wk) @ gm
    """
    m, k = fm.shape
    k2, n = gm.shape
    assert k == k2 and wk.shape == (1, k), (fm.shape, wk.shape, gm.shape)
    bm_, bn_, bk_ = min(bm, m), min(bn, n), min(bk, k)
    if m % bm_ or n % bn_ or k % bk_:
        raise ValueError(
            f"shapes ({m},{n},{k}) must divide block ({bm_},{bn_},{bk_}); "
            "ops.py pads before calling")
    n_k = k // bk_
    grid = (m // bm_, n // bn_, n_k)
    return pl.pallas_call(
        functools.partial(_kernel, n_k=n_k),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm_, bk_), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((1, bk_), lambda i, j, kk: (0, kk)),
            pl.BlockSpec((bk_, bn_), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((bm_, bn_), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), fm.dtype),
        scratch_shapes=[pltpu.VMEM((bm_, bn_), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(fm, wk, gm)
