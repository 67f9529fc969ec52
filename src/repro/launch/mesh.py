"""Production meshes (assignment spec).

``make_production_mesh`` is a FUNCTION so importing this module never
touches jax device state.  The single-pod mesh is (data=16, model=16) = 256
chips; the multi-pod mesh is (pod=2, data=16, model=16) = 512 chips (the
"pod" axis is the paper's RDMA domain; "model" is the intra-pod ICI/NVLink
domain).
"""
from __future__ import annotations

import jax

from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_bench_mesh(n_devices: int, model: int = 4):
    """(data, model) mesh over the first ``n_devices`` local devices, for
    benchmarks, integration tests and ``--mesh local`` launches."""
    if model < 1 or n_devices % model:
        raise ValueError(f"{n_devices} devices do not split into a model "
                         f"axis of {model}")
    return jax.make_mesh((n_devices // model, model), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2,
                         devices=jax.devices()[:n_devices])


# TPU v5e hardware constants for the roofline (assignment spec)
PEAK_FLOPS_BF16 = 197e12        # per chip
HBM_BW = 819e9                  # bytes/s per chip
ICI_BW = 50e9                   # bytes/s per link
