"""Production mesh definitions.

Defined as functions (never module-level constants) so importing this
module never touches jax device state.
"""

from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; multi_pod adds a leading 2-pod axis."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    # Auto axes: the GSPMD sharding rules in common/sharding.py assume
    # the compiler propagates shardings (make_mesh defaults to Explicit)
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def mesh_tag(multi_pod: bool) -> str:
    return "multipod2x16x16" if multi_pod else "pod16x16"


def require_devices(n: int):
    have = len(jax.devices())
    if have < n:
        raise RuntimeError(
            f"need {n} devices but have {have}; the dry-run entrypoint must "
            "set XLA_FLAGS=--xla_force_host_platform_device_count=512 before "
            "importing jax (see launch/dryrun.py)")
