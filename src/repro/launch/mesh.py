"""Production meshes.  Functions, not module constants, so importing this
module never touches jax device state (the dry-run sets the 512-device
XLA flag before any jax initialization).

Mesh axes are GSPMD-style (``AxisType.Auto``): the model stack's sharding
rules constrain and the compiler places, which ``jax.make_mesh``'s default
Explicit axes do not allow."""
from __future__ import annotations

import jax
import numpy as np
from jax.sharding import AxisType, Mesh


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    """Single pod: 16x16 = 256 chips (data, model).
    Multi-pod: 2 pods x 256 = 512 chips (pod, data, model)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_local_mesh(*, data: int = 0, model: int = 1) -> Mesh:
    """Mesh over whatever devices exist (CPU tests: 1 device -> 1x1).

    Degenerate shapes are rejected eagerly with a clear error instead of
    letting ``make_mesh`` fail opaquely: ``model`` (or an explicit
    ``data``) larger than the device count would floor-divide ``data`` to
    zero, and an explicit ``data * model`` that does not match the device
    population cannot tile it."""
    n = len(jax.devices())
    if model < 1 or data < 0:
        raise ValueError(f"mesh axes must be positive, got data={data}, "
                         f"model={model}")
    if model > n:
        raise ValueError(
            f"model={model} exceeds the {n} available device(s); "
            f"a local ({n // model if model else 0}, {model}) mesh would "
            f"have a zero-sized data axis")
    if data == 0:
        data = n // model
    if data * model > n:
        raise ValueError(
            f"mesh shape ({data}, {model}) needs {data * model} devices "
            f"but only {n} are available")
    return jax.make_mesh((data, model), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)


def make_lanes_mesh(shards: int = 0) -> Mesh:
    """1-D ``lanes`` mesh over the first ``shards`` local devices (0 = all
    of them) — the mesh the lane-sharded fused dispatch plane
    (``repro.core.partition``) runs batched simulator programs under.
    Uses the same degeneracy guard as ``make_local_mesh``: asking for more
    shards than devices is an eager ``ValueError``."""
    devs = jax.devices()
    if shards == 0:
        shards = len(devs)
    if shards < 1 or shards > len(devs):
        raise ValueError(
            f"lanes mesh needs 1..{len(devs)} shards, got {shards}")
    return Mesh(np.asarray(devs[:shards]), ("lanes",))
