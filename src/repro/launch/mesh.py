"""Production mesh definitions.

``make_production_mesh`` is a FUNCTION (not a module-level constant) so that
importing this module never touches jax device state.  The dry-run entry
point sets ``XLA_FLAGS=--xla_force_host_platform_device_count=512`` *before*
any jax import; everything else sees the real device count.

Target hardware (constants used by §Roofline): TPU v5e,
197 TFLOP/s bf16 per chip, 819 GB/s HBM, ~50 GB/s/link ICI.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType

__all__ = ["make_production_mesh", "make_host_mesh", "make_clients_mesh",
           "make_fl_mesh", "PEAK_FLOPS", "HBM_BW", "ICI_BW",
           "mesh_axes"]

PEAK_FLOPS = 197e12       # bf16 FLOP/s per chip
HBM_BW = 819e9            # bytes/s per chip
ICI_BW = 50e9             # bytes/s per link


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 single-pod (256 chips) or 2×16×16 multi-pod (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_host_mesh(data: int = 1, model: int = 1):
    """Tiny mesh over the real host devices (CI / smoke tests)."""
    n = len(jax.devices())
    data = min(data, n)
    model = max(1, min(model, n // max(data, 1)))
    return jax.make_mesh((data, model), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)


def make_clients_mesh(num_clients: int, max_devices: int | None = None):
    """1-D ``("clients",)`` mesh for client-sharded FL data planes.

    Uses the largest available device count that divides ``num_clients`` so
    every shard carries an equal block of client slots (the sharded executor
    requires an even split).  On a single-device host this degenerates to a
    1-device mesh — same program, no collectives on the wire.  Drive CPU
    multi-device runs with ``XLA_FLAGS=--xla_force_host_platform_device_count=K``
    set before the first jax import.
    """
    n = len(jax.devices())
    if max_devices is not None:
        n = max(1, min(n, max_devices))
    k = max(d for d in range(1, n + 1) if num_clients % d == 0)
    return jax.make_mesh((k,), ("clients",), axis_types=(AxisType.Auto,))


def make_fl_mesh(num_clients: int, model: int = 1,
                 max_devices: int | None = None):
    """2-D ``("clients", "model")`` mesh for the overlapped FL data plane.

    ``model`` is the requested model-axis size: during diffusion hops the
    flattened per-client parameter block is split feature-wise over
    ``"model"`` (each ring-shift ``ppermute`` then moves only ``F/model``
    bytes per link) while client slots shard over ``"clients"``; outside the
    hops the leading client axis is sharded over the *combined*
    ``("clients", "model")`` axis, so every device trains an equal block of
    clients.  Unlike :func:`make_clients_mesh` there is no divisibility
    requirement on ``num_clients`` — the executor pads the slot axis
    (zero-weighted padding slots) to the mesh size.

    The model axis is clamped to a divisor of the device count; remaining
    devices land on ``"clients"``.  On one device this degenerates to a
    ``(1, 1)`` mesh — same program, no collectives on the wire.
    """
    n = len(jax.devices())
    if max_devices is not None:
        n = max(1, min(n, max_devices))
    km = max(d for d in range(1, max(1, min(model, n)) + 1) if n % d == 0)
    # Never more client shards than clients — padding a 4096-device mesh to
    # N=20 would be absurd; excess devices simply sit out of the mesh.
    kc = min(n // km, max(1, num_clients))
    return jax.make_mesh((kc, km), ("clients", "model"),
                         axis_types=(AxisType.Auto,) * 2)


def mesh_axes(mesh) -> tuple[str, ...]:
    return tuple(mesh.axis_names)
