"""Logical-axis sharding rules (MaxText-style) for the production meshes.

Models annotate every tensor with *logical* axes ("batch", "embed", "heads",
…); this module maps them onto the physical mesh axes of the assignment:

    single-pod: (16, 16)      = ("data", "model")
    multi-pod:  (2, 16, 16)   = ("pod", "data", "model")

Default rules:

| logical axis | mesh axes        | role                                  |
|--------------|------------------|---------------------------------------|
| batch        | ("pod", "data")  | DP                                    |
| embed        | "data"           | FSDP / ZeRO-3 param shard             |
| heads/kv_heads/mlp/vocab | "model" | TP                               |
| expert       | "model"          | EP                                    |
| kv_length    | "data"           | SP for long-context KV caches         |
| length       | (replicated)     | activation sequence axis              |
| stage        | "pod"            | pipeline stages (parallel/pipeline)   |

Non-divisible dims (e.g. 40 heads over 16-way "model", vocab 50280) rely on
GSPMD's implicit padding — verified to compile; the padding waste is called
out per-arch in the roofline notes.

``use_mesh`` installs a mesh for the annotation helpers; outside any mesh
(unit tests, laptop runs) ``shard`` is a no-op so the same model code runs
anywhere.
"""

from __future__ import annotations

import threading
from contextlib import contextmanager
from typing import Any, Sequence

import jax
import numpy as np
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

__all__ = ["DEFAULT_RULES", "use_mesh", "current_mesh", "spec_for", "shard",
           "sharding_for", "fitted_sharding", "logical_sharding", "ParamSpec",
           "init_params", "param_specs_to_shardings", "param_axes",
           "auto_mesh", "data_mesh", "space_mesh", "disjoint_data_meshes",
           "slab_sharding"]

# logical axis -> mesh axis name(s)
DEFAULT_RULES: dict[str, Any] = {
    "batch": ("pod", "data"),
    "embed": "data",
    "embed2": None,            # second embed axis of square weights
    "heads": "model",
    "kv_heads": "model",
    "mlp": "model",
    "vocab": "model",
    "expert": "model",
    "length": None,
    "kv_length": "data",
    "layers": None,
    "d_head": None,
    "state": None,
    "conv": None,
    "stage": "pod",
    None: None,
}

_local = threading.local()


@contextmanager
def use_mesh(mesh: Mesh | None, rules: dict | None = None):
    prev = getattr(_local, "ctx", (None, None))
    _local.ctx = (mesh, rules or DEFAULT_RULES)
    try:
        yield mesh
    finally:
        _local.ctx = prev


def current_mesh() -> Mesh | None:
    return getattr(_local, "ctx", (None, None))[0]


def _current_rules() -> dict:
    return getattr(_local, "ctx", (None, DEFAULT_RULES))[1] or DEFAULT_RULES


def spec_for(axes: Sequence[str | None], mesh: Mesh | None = None,
             rules: dict | None = None) -> P:
    """Map logical axes to a PartitionSpec valid on ``mesh``."""
    mesh = mesh or current_mesh()
    rules = rules or _current_rules()
    names = set(mesh.shape) if mesh is not None else set()
    parts = []
    used: set[str] = set()
    for ax in axes:
        target = rules.get(ax, None)
        if target is None:
            parts.append(None)
            continue
        if isinstance(target, str):
            target = (target,)
        chosen = tuple(t for t in target if t in names and t not in used)
        used.update(chosen)
        if not chosen:
            parts.append(None)
        elif len(chosen) == 1:
            parts.append(chosen[0])
        else:
            parts.append(chosen)
    return P(*parts)


def _axis_size(mesh: Mesh, entry) -> int:
    if entry is None:
        return 1
    if isinstance(entry, str):
        return mesh.shape[entry]
    n = 1
    for e in entry:
        n *= mesh.shape[e]
    return n


def fitted_sharding(mesh: Mesh | None, shape: Sequence[int],
                    axes: Sequence[str | None], rules: dict | None = None
                    ) -> NamedSharding | None:
    """Sharding for a jit *input*: non-divisible dims fall back to
    replicated (GSPMD pads intermediates, but input shardings must divide
    the shape exactly)."""
    if mesh is None:
        return None
    spec = spec_for(axes, mesh, rules)
    parts = []
    for dim, entry in zip(shape, tuple(spec) + (None,) * (len(shape) - len(spec))):
        if entry is not None and dim % _axis_size(mesh, entry) != 0:
            entry = None
        parts.append(entry)
    return NamedSharding(mesh, P(*parts))


def sharding_for(axes: Sequence[str | None], mesh: Mesh | None = None,
                 rules: dict | None = None) -> NamedSharding | None:
    mesh = mesh or current_mesh()
    if mesh is None:
        return None
    return NamedSharding(mesh, spec_for(axes, mesh, rules))


# Back-compat alias
logical_sharding = sharding_for


def shard(x: jax.Array, *axes: str | None) -> jax.Array:
    """Annotate ``x`` with logical axes (no-op when no mesh installed)."""
    mesh = current_mesh()
    if mesh is None:
        return x
    if len(axes) != x.ndim:
        raise ValueError(f"shard: {len(axes)} axes for rank-{x.ndim} tensor")
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, spec_for(axes, mesh)))


def shard_fit(x: jax.Array, *axes: str | None) -> jax.Array:
    """Like ``shard`` but drops mesh axes that do not divide the dim —
    used for tensors where GSPMD padding causes pathological reshards
    (e.g. 2 KV heads over a 16-way model axis: replicate instead)."""
    mesh = current_mesh()
    if mesh is None:
        return x
    sh = fitted_sharding(mesh, x.shape, axes, _current_rules())
    return jax.lax.with_sharding_constraint(x, sh)


# ---------------------------------------------------------------------------
# DDP helpers (the sharded fused epoch's mesh plumbing)
# ---------------------------------------------------------------------------

def auto_mesh(shape: Sequence[int], axes: Sequence[str]) -> Mesh:
    """``jax.make_mesh`` with every axis ``AxisType.Auto``.

    ``jax.make_mesh`` defaults to Explicit axes, under which a store put
    mixing a mesh-sharded slab with an unsharded update raises
    ``ShardingTypeError``.  Every mesh in the repo is built here (or with
    ``jax.sharding.Mesh``, which defaults to Auto) so GSPMD decides the
    layouts.
    """
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))


def data_mesh(n_devices: int | None = None, axis: str = "data") -> Mesh:
    """A 1-D mesh over ``axis`` for pure data parallelism.

    ``n_devices`` defaults to every visible device.  This is the mesh the
    sharded fused epoch (``ml.trainer.make_sharded_fused_epoch``) runs its
    single ``shard_map`` over; on CPU, force multiple devices with
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` before the
    first jax call.
    """
    n = len(jax.devices()) if n_devices is None else int(n_devices)
    return auto_mesh((n,), (axis,))


def space_mesh(n_devices: int | None = None, axis: str = "space") -> Mesh:
    """A 1-D mesh over ``axis`` for domain decomposition.

    The producer-side twin of :func:`data_mesh`: the axis a
    halo-exchanged solver (``sim.distributed``) partitions its grid rows
    over inside one ``shard_map``, and the axis its ``elem_sharding``
    carries into the store so puts stay shard-local.  Name it to match
    the db mesh's element axis (``core.deployment.make_clustered_2d``)
    when staging across meshes.
    """
    n = len(jax.devices()) if n_devices is None else int(n_devices)
    return auto_mesh((n,), (axis,))


def slab_sharding(spec, mesh: Mesh | None, axis: str = "data"
                  ) -> NamedSharding | None:
    """Placement rule of the slab-sharded data plane: partition a store
    table's ``[capacity, *elem]`` slab along its *slot* axis over mesh
    axis ``axis``, so each rank owns ``capacity/D`` slots and per-device
    table memory stops growing with total capacity (the co-located
    scaling property of the paper's Fig. 5).

    ``spec`` is a ``core.store.TableSpec`` (duck-typed: anything with
    ``capacity`` and ``shape``).  The per-slot metadata stays replicated —
    ``core.store.init_table`` handles that when given this sharding.
    Falls back to a replicated slab when ``capacity`` does not divide the
    axis size (jit input shardings must divide exactly).
    """
    if mesh is None:
        return None
    part = axis if spec.capacity % int(mesh.shape[axis]) == 0 else None
    return NamedSharding(mesh, P(part, *([None] * len(spec.shape))))


def disjoint_data_meshes(count: int, axis: str = "data", devices=None
                         ) -> list[Mesh | None]:
    """Split the visible devices into ``count`` disjoint 1-D data meshes.

    The multi-consumer deployment: each trainer replica runs its sharded
    fused epoch on its own device slice, all sharing one store.  Devices
    are divided evenly (``len(devices) // count`` each; the remainder is
    left idle so every replica sees the same shape).  A slice of fewer
    than 2 devices returns ``None`` — that replica falls back to the
    single-device fused tier, which keeps the same session declaration
    runnable on a 1-device laptop and on a real mesh.
    """
    if count < 1:
        raise ValueError("count must be >= 1")
    devices = list(devices if devices is not None else jax.devices())
    per = len(devices) // count
    if per < 2:
        return [None] * count
    return [Mesh(np.asarray(devices[i * per:(i + 1) * per]), (axis,))
            for i in range(count)]


# ---------------------------------------------------------------------------
# Parameter specs: one source of truth for shape + logical axes + init
# ---------------------------------------------------------------------------

class ParamSpec:
    """Declares one parameter: shape, logical axes, initializer."""

    __slots__ = ("shape", "axes", "init", "scale")

    def __init__(self, shape: Sequence[int], axes: Sequence[str | None],
                 init: str = "normal", scale: float | None = None):
        if len(shape) != len(axes):
            raise ValueError(f"ParamSpec rank mismatch: {shape} vs {axes}")
        self.shape = tuple(int(s) for s in shape)
        self.axes = tuple(axes)
        self.init = init
        self.scale = scale

    def __repr__(self):
        return f"ParamSpec({self.shape}, {self.axes}, {self.init})"


def _is_spec(x):
    return isinstance(x, ParamSpec)


def _init_leaf(key, spec: ParamSpec, dtype):
    if spec.init == "zeros":
        return jax.numpy.zeros(spec.shape, dtype)
    if spec.init == "ones":
        return jax.numpy.ones(spec.shape, dtype)
    if spec.init == "normal":
        # fan-in over the trailing input dim (stacked-layer dims excluded)
        if spec.scale is not None:
            std = spec.scale
        else:
            fan_in = spec.shape[-2] if len(spec.shape) >= 2 else spec.shape[-1]
            std = float(np.sqrt(1.0 / max(1, fan_in)))
        return (jax.random.normal(key, spec.shape) * std).astype(dtype)
    if spec.init == "embed":
        std = spec.scale if spec.scale is not None else 0.02
        return (jax.random.normal(key, spec.shape) * std).astype(dtype)
    if spec.init == "const":
        return jax.numpy.full(spec.shape, spec.scale or 0.0, dtype)
    raise ValueError(f"unknown init {spec.init!r}")


def init_params(key, specs, dtype=jax.numpy.float32):
    """Materialize a specs pytree into a params pytree (same structure)."""
    leaves, tree = jax.tree.flatten(specs, is_leaf=_is_spec)
    keys = jax.random.split(key, len(leaves))
    vals = [_init_leaf(k, s, dtype) for k, s in zip(keys, leaves)]
    return jax.tree.unflatten(tree, vals)


def abstract_params(specs, dtype=jax.numpy.bfloat16):
    """ShapeDtypeStruct pytree for dry-run lowering (no allocation)."""
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, dtype), specs,
        is_leaf=_is_spec)


def param_axes(specs):
    return jax.tree.map(lambda s: s.axes, specs, is_leaf=_is_spec)


def param_specs_to_shardings(specs, mesh: Mesh, rules: dict | None = None):
    """NamedSharding pytree for the params described by ``specs``."""
    return jax.tree.map(
        lambda s: NamedSharding(mesh, spec_for(s.axes, mesh, rules)),
        specs, is_leaf=_is_spec)
