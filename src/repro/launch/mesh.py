"""Production mesh construction.

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state. Single pod: 16x16 = 256 chips, axes
(data, model). Multi-pod: 2x16x16 = 512 chips with a leading "pod" axis
(data parallelism across pods — the lowest-bandwidth boundary gets the
least-frequent collective, the gradient all-reduce).
"""
from __future__ import annotations

import jax


def _auto_axes(n_axes: int) -> tuple:
    return (jax.sharding.AxisType.Auto,) * n_axes


def _require_devices(n: int, what: str) -> None:
    """Fail BEFORE ``Mesh`` construction with an actionable message —
    jax's own error ("len(devices) < prod(shape)") names neither the
    mesh being built nor the CPU workaround."""
    have = len(jax.devices())
    if have < n:
        raise ValueError(
            f"{what} needs {n} devices but jax sees only {have}. On a "
            f"CPU host, set XLA_FLAGS=--xla_force_host_platform_device_"
            f"count={n} BEFORE importing jax (tests do this by "
            f"launching a subprocess; see tests/test_sharding_dryrun.py)")


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = 512 if multi_pod else 256
    _require_devices(n, f"make_production_mesh(multi_pod={multi_pod})")
    return jax.make_mesh(shape, axes, axis_types=_auto_axes(len(axes)))


def make_debug_mesh(n_data: int = 2, n_model: int = 2):
    """Small mesh for tests (requires >= n_data*n_model host devices)."""
    _require_devices(n_data * n_model,
                     f"make_debug_mesh({n_data}, {n_model})")
    return jax.make_mesh((n_data, n_model), ("data", "model"),
                         axis_types=_auto_axes(2))


def make_tp_mesh(tp_degree: int, devices=None):
    """1D ``("model",)``-only mesh for one tensor-parallel serving
    instance, carved from an explicit device subset — the instance
    pool hands each engine its slice of the shared device set, so
    co-resident instances at different TP degrees partition the same
    hardware. ``devices=None`` takes the first ``tp_degree`` of
    ``jax.devices()`` (single-engine use and tests)."""
    import numpy as np
    if tp_degree < 1:
        raise ValueError(f"tp_degree must be >= 1, got {tp_degree}")
    if devices is None:
        _require_devices(tp_degree, f"make_tp_mesh({tp_degree})")
        devices = jax.devices()[:tp_degree]
    if len(devices) != tp_degree:
        raise ValueError(
            f"make_tp_mesh({tp_degree}) given {len(devices)} devices")
    return jax.sharding.Mesh(np.asarray(devices), ("model",),
                             axis_types=_auto_axes(1))


def batch_axes(mesh) -> tuple:
    """The axes the global batch is sharded over."""
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)
