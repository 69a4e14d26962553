"""Production meshes. Defined as FUNCTIONS so importing this module never
touches jax device state (the dry-run sets XLA_FLAGS before first init)."""
from __future__ import annotations

import jax


def make_auto_mesh(shape, names):
    """``jax.make_mesh`` with every axis explicitly Auto (GSPMD-propagated),
    which is what the exchange's sharding constraints and shard_maps ask
    for."""
    return jax.make_mesh(tuple(shape), tuple(names),
                         axis_types=(jax.sharding.AxisType.Auto,) * len(names))


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 single-pod (256 chips) or 2×16×16 two-pod (512 chips).

    Axes: ``pod`` (DCN, slow — the Jetson-WiFi analogue), ``data`` (batch /
    FSDP), ``model`` (TP in LOCAL mode; the paper's P=16 position-wise
    sequence partitions in PRISM/VOLTAGE modes).
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_auto_mesh(shape, axes)


def make_debug_mesh(n_data: int = 4, n_model: int = 2):
    """Small host-device mesh for tests (requires
    --xla_force_host_platform_device_count ≥ n_data·n_model)."""
    return make_auto_mesh((n_data, n_model), ("data", "model"))
