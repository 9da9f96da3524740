"""Pallas TPU kernels for fused TensorStore access.

Three kernels, all bounded-memory (no ``[n, capacity]`` materialization):

* ``probe`` — key lookup on a ``(query block, capacity block)`` grid.
  Queries ride the sublanes as a ``[blk_q, 1]`` column, slot keys and
  versions the lanes as ``[1, blk_c]`` rows, so the match tile
  ``[blk_q, blk_c]`` is a plain 2-D broadcast; the output block stays
  resident across the capacity axis and keeps a running min-slot.
* ``sample`` — valid-slot selection.  The cumulative valid count is one
  XLA ``cumsum`` over slot metadata (O(capacity)); the kernel then counts
  ``Σ_j [cum_j <= r]`` over the same blocked grid — a branch-free
  binary-search equivalent.
* ``gather`` — the slab row fetch: scalar-prefetched slot indices drive
  the input ``BlockSpec`` index map, so each grid step DMAs exactly one
  slab row HBM→VMEM→out.  The row is blocked as the element's own
  trailing 2-D shape (``[*lead, last]`` folded to ``[a, b]``), which is
  always a legal TPU block and needs no relayout of the slab.

Every block obeys the TPU tiling rule: its last two dims are multiples of
(8, 128) or equal to the array's.  On CPU the kernels run under
``interpret=True`` (parity tests); ``ops.py`` selects the execution mode.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["probe", "sample", "gather", "gather_sharded"]

# EMPTY_KEY (0xFFFFFFFF) bitcast to int32: keys are compared as int32 so
# the kernel needs no unsigned vector ops.
_EMPTY_I32 = np.int32(-1)
_I32_MAX = np.int32(np.iinfo(np.int32).max)
# (query block, capacity block) grid: queries are independent, the
# capacity axis accumulates into the resident output block.
_REDUCE_GRID = pltpu.CompilerParams(
    dimension_semantics=("parallel", "arbitrary"))


def _round_up(x: int, mult: int) -> int:
    return -(-x // mult) * mult


def _pad1(x, size, fill):
    pad = size - x.shape[0]
    if pad:
        x = jnp.concatenate([x, jnp.full((pad,), fill, x.dtype)])
    return x


def _blocks(n: int, capacity: int, blk_q: int, blk_c: int):
    """Effective (blk_q, n_pad, blk_c, cap_pad): query blocks are a
    multiple of 8 sublanes, capacity blocks a multiple of 128 lanes."""
    bq = min(blk_q, _round_up(max(n, 1), 8))
    bc = min(blk_c, _round_up(max(capacity, 1), 128))
    return bq, _round_up(max(n, 1), bq), bc, _round_up(max(capacity, 1), bc)


def _as_i32(x):
    return jax.lax.bitcast_convert_type(x.astype(jnp.uint32), jnp.int32)


# ---------------------------------------------------------------------------
# probe: first valid slot per query key
# ---------------------------------------------------------------------------

def _probe_kernel(keys_ref, ver_ref, query_ref, idx_ref, *, blk_c: int,
                  capacity: int):
    c = pl.program_id(1)

    @pl.when(c == 0)
    def _init():
        idx_ref[...] = jnp.full(idx_ref.shape, capacity, jnp.int32)

    q = query_ref[...]                                   # [blk_q, 1]
    match = (q == keys_ref[...]) & (ver_ref[...] > 0) \
        & (q != _EMPTY_I32)                              # [blk_q, blk_c]
    slot = c * blk_c + jax.lax.broadcasted_iota(jnp.int32, match.shape, 1)
    cand = jnp.where(match, slot, capacity)
    idx_ref[...] = jnp.minimum(idx_ref[...],
                               jnp.min(cand, axis=1, keepdims=True))


@functools.partial(jax.jit, static_argnames=("blk_q", "blk_c", "interpret"))
def probe(table_keys: jax.Array, version: jax.Array, query: jax.Array,
          blk_q: int = 128, blk_c: int = 128, interpret: bool = False):
    """keys u32[C], version i32[C], query u32[n] → idx i32[n] (C = absent)."""
    capacity = table_keys.shape[0]
    n = query.shape[0]
    bq, n_p, bc, c_p = _blocks(n, capacity, blk_q, blk_c)
    keys_p = _pad1(_as_i32(table_keys), c_p, _EMPTY_I32)[None, :]
    ver_p = _pad1(version.astype(jnp.int32), c_p, 0)[None, :]
    q_p = _pad1(_as_i32(query), n_p, _EMPTY_I32)[:, None]
    idx = pl.pallas_call(
        functools.partial(_probe_kernel, blk_c=bc, capacity=capacity),
        grid=(n_p // bq, c_p // bc),
        in_specs=[
            pl.BlockSpec((1, bc), lambda i, c: (0, c)),
            pl.BlockSpec((1, bc), lambda i, c: (0, c)),
            pl.BlockSpec((bq, 1), lambda i, c: (i, 0)),
        ],
        out_specs=pl.BlockSpec((bq, 1), lambda i, c: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n_p, 1), jnp.int32),
        compiler_params=_REDUCE_GRID,
        interpret=interpret,
    )(keys_p, ver_p, q_p)
    return idx[:n, 0]


# ---------------------------------------------------------------------------
# sample: slot of the r-th valid entry
# ---------------------------------------------------------------------------

def _sample_kernel(cum_ref, r_ref, out_ref):
    @pl.when(pl.program_id(1) == 0)
    def _init():
        out_ref[...] = jnp.zeros(out_ref.shape, jnp.int32)

    tile = (cum_ref[...] <= r_ref[...]).astype(jnp.int32)  # [blk_q, blk_c]
    out_ref[...] += jnp.sum(tile, axis=1, keepdims=True)


@functools.partial(jax.jit, static_argnames=("blk_q", "blk_c", "interpret"))
def sample(version: jax.Array, ranks: jax.Array, blk_q: int = 128,
           blk_c: int = 128, interpret: bool = False):
    """version i32[C], ranks i32[n] → slots i32[n] (r-th valid slot)."""
    capacity = version.shape[0]
    n = ranks.shape[0]
    bq, n_p, bc, c_p = _blocks(n, capacity, blk_q, blk_c)
    cum = jnp.cumsum((version > 0).astype(jnp.int32))
    # Padded slots never count (cum = INT32_MAX > any rank); padded rank
    # lanes get -1 → slot 0 and are sliced off below.
    cum_p = _pad1(cum, c_p, _I32_MAX)[None, :]
    r_p = _pad1(ranks.astype(jnp.int32), n_p, -1)[:, None]
    slots = pl.pallas_call(
        _sample_kernel,
        grid=(n_p // bq, c_p // bc),
        in_specs=[
            pl.BlockSpec((1, bc), lambda i, c: (0, c)),
            pl.BlockSpec((bq, 1), lambda i, c: (i, 0)),
        ],
        out_specs=pl.BlockSpec((bq, 1), lambda i, c: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n_p, 1), jnp.int32),
        compiler_params=_REDUCE_GRID,
        interpret=interpret,
    )(cum_p, r_p)
    return slots[:n, 0]


# ---------------------------------------------------------------------------
# gather: slab row fetch via scalar-prefetched indices
# ---------------------------------------------------------------------------

def _row_view(slab: jax.Array):
    """``[C, *elem]`` → ``[C, a, b]``: the element's leading dims fold
    into ``a`` (a free reshape of major dims), its last dim is ``b``.  A
    ``(None, a, b)`` block then equals the array in its last two dims."""
    elem = slab.shape[1:]
    b = elem[-1] if elem else 1
    a = 1
    for d in elem[:-1]:
        a *= d
    return slab.reshape(slab.shape[0], a, b)


def _gather_kernel(idx_ref, slab_ref, out_ref):
    del idx_ref  # consumed by the BlockSpec index maps
    out_ref[...] = slab_ref[...]


def _gather_sharded_kernel(meta_ref, slab_ref, out_ref, *, local_cap: int):
    # meta = [shard_offset, slot_0, ..., slot_{n-1}] (scalar-prefetched).
    i = pl.program_id(0)
    off = meta_ref[0]
    slot = meta_ref[i + 1]
    owned = (slot >= off) & (slot < off + local_cap)
    row = slab_ref[...]
    out_ref[...] = jnp.where(owned, row, jnp.zeros_like(row))


@functools.partial(jax.jit, static_argnames=("interpret",))
def gather(slab: jax.Array, slots: jax.Array, interpret: bool = False):
    """slab [C, *elem], slots i32[n] (in-range) → rows [n, *elem]."""
    elem = slab.shape[1:]
    n = slots.shape[0]
    rows3 = _row_view(slab)
    blk = (None, *rows3.shape[1:])
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n,),
        in_specs=[pl.BlockSpec(blk, lambda i, idx_ref: (idx_ref[i], 0, 0))],
        out_specs=pl.BlockSpec(blk, lambda i, idx_ref: (i, 0, 0)),
    )
    rows = pl.pallas_call(
        _gather_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n, *rows3.shape[1:]), slab.dtype),
        interpret=interpret,
    )(slots.astype(jnp.int32), rows3)
    return rows.reshape((n, *elem))


@functools.partial(jax.jit, static_argnames=("interpret",))
def gather_sharded(local_slab: jax.Array, slots: jax.Array, offset,
                   interpret: bool = False):
    """Shard-local row gather for a slot-axis-sharded slab.

    ``local_slab [Cl, *elem]`` is THIS shard's slice of the global
    ``[capacity, *elem]`` slab; ``slots i32[n]`` are *global* slot indices
    (already clamped in ``[0, capacity)``); ``offset`` (traced scalar) is
    the shard's first global slot.  Rows whose slot lives on this shard
    are DMA'd out of the local slab (same scalar-prefetch indexing as
    :func:`gather`, clamped into the local range); rows owned elsewhere
    come out as zeros — the caller ``psum``s across shards to assemble
    the full batch, which is the explicit collective that replaces the
    replicated slab read.
    """
    local_cap = local_slab.shape[0]
    elem = local_slab.shape[1:]
    n = slots.shape[0]
    rows3 = _row_view(local_slab)
    blk = (None, *rows3.shape[1:])
    meta = jnp.concatenate([
        jnp.asarray(offset, jnp.int32).reshape(1),
        slots.astype(jnp.int32)])
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n,),
        in_specs=[pl.BlockSpec(
            blk,
            lambda i, m: (jnp.clip(m[i + 1] - m[0], 0, local_cap - 1), 0, 0))],
        out_specs=pl.BlockSpec(blk, lambda i, m: (i, 0, 0)),
    )
    rows = pl.pallas_call(
        functools.partial(_gather_sharded_kernel, local_cap=local_cap),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n, *rows3.shape[1:]),
                                       local_slab.dtype),
        interpret=interpret,
    )(meta, rows3)
    return rows.reshape((n, *elem))
