"""Pipeline parallelism: SPMD GPipe over a mesh axis (default: ``pod``).

The multi-pod mesh can spend its ``pod`` axis as pipeline stages instead of
extra data parallelism: layer periods are split across stages, microbatches
flow stage-to-stage over ``lax.ppermute`` (on hardware: the inter-pod DCN
hop happens once per microbatch per stage boundary instead of once per
gradient all-reduce).

SPMD formulation (single program, all stages): over ``T = M + n_stages − 1``
iterations every stage runs its block on whatever activation it holds,
masked to zero outside its active window; activations hop one stage per
iteration via ppermute; stage ``n−1``'s outputs are collected and
``psum``-broadcast at the end.  ``jax.grad`` differentiates straight
through (ppermute transposes to the reverse permutation), giving the
backward pipeline for free.

``pipeline_forward`` is generic over ``stage_fn``; correctness is asserted
against the plain scanned forward in tests (same params, same batch,
2-stage mesh).  The bubble fraction is the usual (n−1)/(M+n−1) — pick
M ≫ n_stages.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax import shard_map

__all__ = ["pipeline_forward", "split_stages", "bubble_fraction"]


def bubble_fraction(n_stages: int, n_micro: int) -> float:
    return (n_stages - 1) / (n_micro + n_stages - 1)


def split_stages(stacked_params: Any, n_stages: int) -> Any:
    """Reshape leaves [P, ...] → [n_stages, P/n_stages, ...] for stage
    sharding.  P must divide evenly (pad periods upstream otherwise)."""
    def _split(a):
        p = a.shape[0]
        if p % n_stages:
            raise ValueError(f"{p} periods not divisible by {n_stages} stages")
        return a.reshape(n_stages, p // n_stages, *a.shape[1:])
    return jax.tree.map(_split, stacked_params)


def pipeline_forward(stage_fn: Callable[[Any, jax.Array], jax.Array],
                     stage_params: Any, x_micro: jax.Array, mesh: Mesh,
                     stage_axis: str = "pod") -> jax.Array:
    """Run ``x_micro [M, ...mb]`` through ``n_stages`` of ``stage_fn``.

    ``stage_params`` leaves are [n_stages, ...] (see ``split_stages``) and
    will be sharded over ``stage_axis``; every other mesh axis can keep
    sharding the microbatch dims as usual.  Returns [M, ...mb] outputs.
    """
    n_stages = mesh.shape[stage_axis]
    M = x_micro.shape[0]

    param_specs = jax.tree.map(lambda _: P(stage_axis), stage_params)

    def _worker(params_local, x_all):
        params_local = jax.tree.map(lambda a: a[0], params_local)
        sid = jax.lax.axis_index(stage_axis)
        T = M + n_stages - 1
        h0 = jnp.zeros_like(x_all[0])
        outs0 = jnp.zeros_like(x_all)
        fwd_perm = [(i, i + 1) for i in range(n_stages - 1)]

        def step(carry, t):
            h_prev, outs = carry
            mb_in = jnp.clip(t, 0, M - 1)
            x_in = jnp.where(sid == 0, x_all[mb_in], h_prev)
            active = (sid <= t) & (t < sid + M)
            h = stage_fn(params_local, x_in)
            h = jnp.where(active, h, jnp.zeros_like(h))
            out_idx = jnp.clip(t - (n_stages - 1), 0, M - 1)
            is_out = (sid == n_stages - 1) & (t >= n_stages - 1)
            outs = outs.at[out_idx].set(
                jnp.where(is_out, h, outs[out_idx]))
            h_next = jax.lax.ppermute(h, stage_axis, fwd_perm)
            return (h_next, outs), None

        (_, outs), _ = jax.lax.scan(step, (h0, outs0), jnp.arange(T))
        # outputs are nonzero only on the last stage: broadcast to all
        return jax.lax.psum(outs, stage_axis)

    fn = shard_map(
        _worker, mesh=mesh,
        in_specs=(param_specs, P()),
        out_specs=P(),
        check_vma=False)
    return fn(stage_params, x_micro)
