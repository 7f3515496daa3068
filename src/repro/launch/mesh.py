"""Production meshes (TPU v5e). 256-chip pod = 16x16 (data, model);
2-pod cluster = 2x16x16 (pod, data, model).

``make_production_mesh`` is a function (not a module constant) so importing
this module never touches jax device state — the dry-run sets
XLA_FLAGS=--xla_force_host_platform_device_count=512 before first jax use,
while tests and benches see the real single CPU device.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_host_mesh():
    """Whatever this host actually has (1 CPU device in this container):
    the degenerate (1, 1) mesh used by the real train/serve drivers and tests."""
    n = len(jax.devices())
    return jax.make_mesh((n, 1), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)


# TPU v5e hardware constants (per chip) — the roofline denominators.
PEAK_FLOPS_BF16 = 197e12      # FLOP/s
HBM_BW = 819e9                # B/s
ICI_BW = 50e9                 # B/s per link
