"""Production mesh builders (multi-pod dry-run spec).

A FUNCTION, not a module-level constant — importing this module never
touches jax device state (jax locks the device count on first use, and the
dry-run must set XLA_FLAGS before that happens).

Single pod: 16 x 16 = 256 chips, axes ("data", "model").
Multi-pod:  2 x 16 x 16 = 512 chips, axes ("pod", "data", "model") — the
"pod" axis composes with data parallelism (batch sharded over pod x data)
and carries the cross-pod (DCN-ish) collectives the dry-run must prove out.
"""

from __future__ import annotations

import jax


def _make_mesh(shape, axes):
    # Auto axes: sharding propagates through GSPMD, as every caller expects
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_debug_mesh(data: int = 1, model: int = 1):
    """Tiny mesh over however many local devices exist (tests)."""
    return _make_mesh((data, model), ("data", "model"))


def batch_axes(mesh) -> tuple:
    """Mesh axes that carry the batch dimension."""
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)


def fsdp_axes(mesh) -> tuple:
    """Mesh axes weights are FSDP-sharded over in training."""
    return ("data",)  # pod stays pure-DP: weights replicated across pods


def axis_size(mesh, *names) -> int:
    n = 1
    for nm in names:
        if nm in mesh.axis_names:
            n *= mesh.shape[nm]
    return n


def replica_devices(index: int, n_replicas: int, devices=None) -> tuple:
    """Devices backing data-parallel serving replica ``index`` (0-based)
    of ``n_replicas``: an even partition of the local device list in
    enumeration order, so replicas never contend for a chip. On hosts
    with fewer devices than replicas (CPU / single-chip dev boxes) the
    replicas share round-robin — the serving router's correctness
    depends only on the Transport boundary, never on physical isolation,
    so the degenerate placement is still a faithful fleet."""
    if not 0 <= index < n_replicas:
        raise ValueError(
            f"replica index {index} out of range [0, {n_replicas})")
    devs = list(devices) if devices is not None else jax.devices()
    if len(devs) < n_replicas:
        return (devs[index % len(devs)],)
    per = len(devs) // n_replicas
    return tuple(devs[index * per:(index + 1) * per])
