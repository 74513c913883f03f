"""The one mesh constructor, plus host-device forcing for CPU runs.

Every mesh in the repo — the streaming engine's ``("data", "model")``
mesh, test meshes, benchmark meshes, the production dry-run — comes from
:func:`make_mesh`, which states its axis types: ``AxisType.Auto``, so
GSPMD propagates shardings through gathers and scatters. (JAX's own
``jax.make_mesh`` defaults to Explicit axes, on which the engine's
gathers raise ``ShardingTypeError``.)

Production shapes:

Single pod : (16, 16)    -> ("data", "model")        = 256 chips
Multi-pod  : (2, 16, 16) -> ("pod", "data", "model") = 512 chips

Defined as FUNCTIONS so importing this module never touches jax device
state (callers force host devices before the first jax init).
"""
from __future__ import annotations

import os
import re

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes=("data", "model")):
    """A mesh of ``shape`` over ``axes`` with Auto axis types."""
    shape, axes = tuple(shape), tuple(axes)
    assert len(shape) == len(axes), (shape, axes)
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def data_axes(mesh) -> tuple[str, ...]:
    """The batch/data-parallel axes of a mesh (pod is an outer data axis)."""
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def axis_sizes(mesh) -> dict[str, int]:
    return dict(zip(mesh.axis_names, mesh.devices.shape))


def cpu_requested() -> bool:
    """True when the run is held to the CPU (``JAX_PLATFORMS=cpu``) —
    readable before jax initializes, unlike ``jax.default_backend()``."""
    return os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu"


def force_host_devices(n: int):
    """Give a CPU run ``n`` host-platform devices for a debug mesh.

    Call only when ``cpu_requested()``: on an accelerator host a mesh is
    built over the real devices, never over CPU stand-ins. Appends the
    XLA flag, which only takes effect if jax backends are not yet
    initialized — so callers must parse CLI flags and call this before
    their first device query. Raises if it is already too late."""
    if n <= 1:
        return
    flag = "--xla_force_host_platform_device_count"
    flags = os.environ.get("XLA_FLAGS", "")
    m = re.search(rf"{flag}=(\d+)", flags)
    if m is None:
        flags = f"{flags} {flag}={n}".strip()
    elif int(m.group(1)) < n:  # raise an existing lower setting
        flags = flags[: m.start()] + f"{flag}={n}" + flags[m.end():]
    os.environ["XLA_FLAGS"] = flags
    if len(jax.devices()) < n:
        raise RuntimeError(
            f"need {n} devices but jax already initialized with "
            f"{len(jax.devices())} (XLA_FLAGS was applied too late — "
            f"export {flag}={n} before startup)")
