"""Production mesh construction.

Single pod: 16×16 = 256 chips, axes ("data", "model") — ``model`` maps to the
fast ICI ring for tensor/expert parallelism, ``data`` carries FSDP + batch.
Multi-pod: 2×16×16 = 512 chips with a leading ("pod",) axis over DCI; only
gradient all-reduce (optionally int8-compressed) crosses it.

Defined as functions (never module-level constants) so importing this module
never touches jax device state; the dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` *before* any jax
import to fabricate the placeholder devices.
"""

from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_host_mesh(data: int | None = None, model: int = 1):
    """Small mesh over whatever devices exist (tests / smoke runs)."""
    n = jax.device_count()
    data = data or max(1, n // model)
    return jax.make_mesh((data, model), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
