"""Production mesh construction.

Defined as FUNCTIONS (never module-level constants) so importing this
module never touches jax device state. The dry-run entry point sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any jax
import; smoke tests and benches see the real (1-device) CPU.
"""

from __future__ import annotations

import jax


def make_mesh(shape, axes, devices=None):
    """``jax.make_mesh`` with Auto axis types (sharding by propagation)."""
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes),
        devices=devices,
    )


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips/pod; multi-pod doubles along a leading "pod" axis."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(model: int = 1):
    """Degenerate 1-device mesh for CPU integration tests of the
    distributed code path (same axis names as production)."""
    return make_mesh((1, model), ("data", "model"))


def make_silo_mesh(devices=None):
    """Cross-silo mesh: one silo per device on the "pod" axis.

    ``("pod", "data", "model") = (n_devices, 1, 1)`` over ``devices``
    (default: every local device), so ``fl_step`` trains one client per
    device at each scan step and sums int32 vote counts across pods."""
    devices = jax.devices() if devices is None else list(devices)
    return make_mesh((len(devices), 1, 1), ("pod", "data", "model"), devices)


def make_campaign_mesh(n_devices: int | None = None):
    """1-D data mesh for campaign batch sharding.

    The campaign executor (``repro.sim.campaign``) lays each plan group's
    (cell, seed) batch axis on this mesh — cells are embarrassingly
    parallel, so a pure data mesh over all local devices is the right
    placement. Cross-host campaigns would swap this for a slice of
    :func:`make_production_mesh`'s "data" axis (ROADMAP follow-on).
    """
    n = len(jax.devices()) if n_devices is None else n_devices
    return make_mesh((n,), ("data",))
