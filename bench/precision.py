"""The arithmetic a plain reference computes in.

* ``float32``: float32 throughout, every matmul and convolution at
  precision HIGHEST.
* ``default``: what the TPU computes for a float32 matmul or convolution
  at its default precision: operands rounded to bfloat16, products
  accumulated in float32, everything else float32.  Emulated exactly at
  precision HIGHEST (a product of two bfloat16 values is exact in
  float32).
* ``bfloat16``: everything in bfloat16, operands and elementwise work
  alike: the control, the step below the float32 that each configuration
  states.
"""

from __future__ import annotations

import jax.numpy as jnp

ARITHS = ("float32", "default", "bfloat16")


def dtype(arith: str):
    """The dtype values and weights are held in."""
    if arith not in ARITHS:
        raise ValueError(f"unknown arithmetic {arith!r}")
    return jnp.bfloat16 if arith == "bfloat16" else jnp.float32


def operand(x, arith: str):
    """A matmul or convolution operand as the arithmetic rounds it."""
    if arith == "default":
        return x.astype(jnp.bfloat16).astype(x.dtype)
    return x


def stated(cfg: dict) -> str:
    """The arithmetic a configuration states: float32 values with its
    ``matmul_precision`` (``default`` or ``highest``)."""
    return {"default": "default", "highest": "float32"}[cfg["matmul_precision"]]
