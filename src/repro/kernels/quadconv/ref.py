"""Pure-jnp oracle for the QuadConv quadrature contraction.

QuadConv (Doherty et al. 2023, arXiv:2211.05151) approximates a continuous
convolution with a single quadrature sum over non-uniform points:

    out[b, j, o] = sum_i sum_c  w[i] * G[j, o*C + c, i] * f[b, i, c]

where ``w`` are learned quadrature weights over the I input points, ``G`` is
the MLP-parameterized kernel evaluated at point-pair offsets, f has C input
channels, and the output lives on J (possibly different) points with O
channels.  This contraction is the FLOPs hot spot of the paper's autoencoder
(everything else is small MLPs), hence the Pallas kernels next door.

``G`` is ``[J, O·C, I]``: output points, the filter's features, input
points minor; the layout ``ml.quadconv.QuadConv.kernel_tensor`` builds it
in and the kernels read and write it in (``kernel.py``).  This oracle is
one ``einsum`` on that layout, and autodiff of it is the gradient oracle.
"""

from __future__ import annotations

import jax.numpy as jnp

__all__ = ["quadconv_contract"]


def quadconv_contract(f: jnp.ndarray, w: jnp.ndarray, g: jnp.ndarray
                      ) -> jnp.ndarray:
    """out[b,j,o] = Σ_{c,i} w[i] G[j, o·C + c, i] f[b,i,c].

    Args:
      f: [B, I, C] input features on I quadrature points.
      w: [I] quadrature weights.
      g: [J, O·C, I] kernel tensor (MLP(x_j - y_i), compact-support masked).
    Returns:
      [B, J, O]
    """
    j, r, i = g.shape
    g = g.reshape(j, r // f.shape[-1], f.shape[-1], i)
    return jnp.einsum("i,joci,bic->bjo", w, g, f,
                      preferred_element_type=jnp.float32).astype(f.dtype)
