"""Production meshes for the assigned TPU v5e pods.

``make_production_mesh`` is a FUNCTION (not a module-level constant) so that
importing this module never touches jax device state — the dry-run sets its
placeholder-device XLA flag before the first jax call, and smoke
tests/benches must keep seeing the single real device.
"""

from __future__ import annotations

from ..parallel.sharding import auto_mesh

__all__ = ["make_production_mesh", "make_mesh_for", "HW"]


#: TPU v5e hardware constants used by the roofline (per chip).
HW = {
    "name": "TPU v5e",
    "peak_flops_bf16": 197e12,     # FLOP/s
    "hbm_bytes_per_s": 819e9,      # HBM bandwidth
    "ici_bytes_per_s_per_link": 50e9,
    "ici_links": 4,                # 2D torus: 4 links/chip (x±, y±)
    "hbm_bytes": 16 * 2**30,       # 16 GiB HBM per chip
    "vmem_bytes": 128 * 2**20,
}


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return auto_mesh(shape, axes)


def make_mesh_for(n_devices: int, model_axis: int = 1, name_data: str = "data",
                  name_model: str = "model"):
    """Small helper for laptop-scale runs/tests: (n/model, model) mesh."""
    if n_devices % model_axis:
        raise ValueError(f"{n_devices} devices, model axis {model_axis}")
    return auto_mesh((n_devices // model_axis, model_axis),
                     (name_data, name_model))
