"""Production mesh construction (TPU v5e pods).

Defined as FUNCTIONS so importing this module never touches jax device
state (the dry-run must set XLA_FLAGS before any jax initialization).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType

#: ``device_kind`` of the chips the production meshes stand for; the dry
#: runs compile on host placeholder devices and take peaks from this kind
PRODUCTION_DEVICE_KIND = "TPU v5 lite"


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single-pod (256 chips) or 2x16x16 two-pod (512 chips) mesh.

    Axes: ``data`` carries the batch (and is the PE axis for the paper's
    cooperative minibatching), ``model`` carries tensor parallelism,
    ``pod`` is the outer data-parallel axis across ICI islands (the
    paper's cooperation domain is one fast-interconnect island — see
    DESIGN.md §6 and paper §A.11).
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def make_host_mesh(num_devices: int | None = None, axis: str = "data"):
    """Small 1-D mesh over available devices (tests, single-host runs)."""
    n = num_devices or len(jax.devices())
    return jax.make_mesh((n,), (axis,))


def make_coop_mesh(num_pes: int, axis_name: str = "data"):
    """1-D mesh carrying the cooperative PE axis (one PE per device).

    This is the mesh :class:`repro.engine.shard.ShardRunner` runs
    ``shard_map`` over.  On CPU, force a multi-device platform *before*
    importing jax::

        XLA_FLAGS=--xla_force_host_platform_device_count=P

    which is how CI exercises the real all-to-all path without TPUs.

    The axis is ``Auto``: ``jax.make_mesh`` defaults to ``Explicit`` axes,
    under which plain indexing of a stacked ``(P, ...)`` output that is
    sharded along the axis is a sharding type error.
    """
    avail = len(jax.devices())
    if avail < num_pes:
        raise ValueError(
            f"cooperative shard execution needs num_pes={num_pes} devices, "
            f"but jax sees {avail}; on CPU set "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={num_pes} "
            f"before importing jax"
        )
    return jax.make_mesh(
        (num_pes,), (axis_name,), axis_types=(AxisType.Auto,)
    )


def batch_axes(mesh) -> tuple[str, ...]:
    """Mesh axes that shard the batch dimension."""
    names = mesh.axis_names
    return tuple(a for a in ("pod", "data") if a in names)
