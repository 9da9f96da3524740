"""The numbers that decide ``correct``: gaps between what the timed path
produced and what the plain reference computes.

Each function returns one number; the harness holds it against the limit
that ``bench/limits/<cell>.json`` gives it.
"""

from __future__ import annotations

import numpy as np


def leaves(tree) -> list[np.ndarray]:
    """Leaves of a pytree as float64 host arrays, in pytree order."""
    import jax
    return [np.asarray(x, np.float64) for x in jax.tree.leaves(tree)]


def worst_rel_gap(values, refs) -> float:
    """Largest ``|value - ref| / |ref|`` over matching pairs."""
    return max(abs(v - r) / max(abs(r), 1e-30) for v, r in zip(values, refs))


def leaf_norm_gaps(prog: list[np.ndarray], ref: list[np.ndarray],
                   keep: list[bool] | None = None) -> np.ndarray:
    """Per leaf, ``| |prog| - |ref| |`` measured against the reference's
    norm of that leaf or of the median leaf, whichever is larger (some
    gradients are all but zero); leaves not kept read 0."""
    pn = np.array([np.linalg.norm(x) for x in prog])
    rn = np.array([np.linalg.norm(x) for x in ref])
    keep = np.ones(len(rn), bool) if keep is None else np.asarray(keep)
    scale = np.maximum(rn, np.median(rn[keep]))
    return np.where(keep, np.abs(pn - rn) / np.maximum(scale, 1e-30), 0.0)


def leaf_norm_gap(prog: list[np.ndarray], ref: list[np.ndarray],
                  keep: list[bool] | None = None) -> float:
    """The worst leaf of :func:`leaf_norm_gaps`."""
    return float(np.max(leaf_norm_gaps(prog, ref, keep)))


def moving_leaves(grads: list[np.ndarray], floor: float = 1e-3) -> list[bool]:
    """Leaves whose reference gradient is not nought to rounding: norm at
    least ``floor`` times the median leaf's.  A leaf below moves under Adam
    by round-off alone (a key bias under softmax, say)."""
    n = np.array([np.linalg.norm(g) for g in grads])
    return list(n >= floor * np.median(n))


def max_rel_row_gap(rows: np.ndarray, ref: np.ndarray) -> float:
    """Worst row of ``max |row - ref| / max |ref|`` (leading axis = rows)."""
    rows = np.asarray(rows, np.float64).reshape(len(rows), -1)
    ref = np.asarray(ref, np.float64).reshape(len(ref), -1)
    scale = np.maximum(np.max(np.abs(ref), axis=1), 1e-30)
    return float(np.max(np.max(np.abs(rows - ref), axis=1) / scale))
