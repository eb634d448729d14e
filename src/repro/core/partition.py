"""Lane-sharded fused dispatch: run fused batches data-parallel on a mesh.

Every fused evaluation in the repo — ``qn_sim.response_time_batch``,
``dag.response_time_batch``, and the Pallas ``qn_event``/``amva`` kernel
paths — is a vmap over a flat *lane* axis of fully independent programs
(lane = candidate x replication, or one AMVA fixed point).  That axis is
embarrassingly parallel, so this module executes it under
``jax.shard_map`` over a 1-D ``lanes`` mesh (``launch.mesh.make_lanes_mesh``)
and turns the service's throughput ceiling from one device into the mesh.

Bit-parity contract
-------------------
Sharding changes *placement*, never values.  Each lane's result depends
only on its own parameters and its own RNG fold offsets (padded lanes
replicate a real lane and are dropped on the way out), so splitting the
lane axis into D contiguous shards executes the exact same per-lane
programs on D devices; the sharded result is required — and tested
(``tests/test_partition.py``) — to be bit-identical to the single-device
program for every workload kind, impl, and bucket grid.

Device-aware lane bucketing
---------------------------
The flat lane axis must divide evenly across shards AND each shard must
keep a bucketed shape (so compiled executables are shared across nearby
sweep widths, per shard):

    bucket_lanes(C, D) = D * shapes.bucket_lanes(ceil(C / D))

``D=1`` degenerates exactly to the single-device ``shapes.bucket_lanes``.
The extra padding sharding induces beyond the single-device bucket is
accounted separately (``qn_sim.padding_stats``: ``shard_padded_lanes`` /
``shard_padded_events``) so a scale-out run cannot hide bucketing
regressions — and vice versa.

Configuration
-------------
``REPRO_SHARD`` selects the shard count:

  * ``auto`` (default) — one shard per local device, capped at the real
    candidate count (a 3-candidate sweep on 8 devices uses 3 shards, not
    8x the padding);
  * ``off`` — always 1 shard: bit- and accounting-identical to the
    pre-sharding plane;
  * ``<D>``  — exactly D shards (must not exceed the device count).

``set_shard_spec``/``shard_spec`` flip it at runtime (benchmarks and
tests); everything above this layer — ``fused_qn_call``,
``fused_eval_call``, ``BatchedQNEvaluator.evaluate_many``,
``FusionScheduler.flush`` and the deferred ``PendingBatch`` pipeline —
inherits sharding transparently, including the one-coalesced-fetch-per-
round resolution (``jax.device_get`` gathers sharded buffers directly).
"""
from __future__ import annotations

import os
import threading
from functools import partial
from typing import Callable, Dict, Tuple

import jax
from jax.sharding import PartitionSpec

from repro.core import shapes as _shapes

__all__ = [
    "shard_spec", "set_shard_spec", "shard_count", "device_count",
    "bucket_lanes", "lanes_mesh", "shard_call", "shard_info",
]

_LANES = PartitionSpec("lanes")
_REPL = PartitionSpec()


def _parse_spec(spec: str) -> str:
    spec = str(spec).strip().lower()
    if spec in ("auto", "off"):
        return spec
    try:
        d = int(spec)
    except ValueError:
        raise ValueError(
            f"REPRO_SHARD must be 'auto', 'off', or a positive shard "
            f"count, got {spec!r}") from None
    if d < 1:
        raise ValueError(f"REPRO_SHARD shard count must be >= 1, got {d}")
    return str(d)


_DEFAULT_SPEC = _parse_spec(os.environ.get("REPRO_SHARD", "auto"))


def shard_spec() -> str:
    """The active sharding spec: ``"auto"``, ``"off"``, or a digit string."""
    return _DEFAULT_SPEC


def set_shard_spec(spec) -> None:
    """Select the lane-sharding policy for subsequent fused dispatches
    (``"auto"`` | ``"off"`` | an explicit shard count).  Tests and
    benchmarks use this; production code should prefer ``$REPRO_SHARD``."""
    global _DEFAULT_SPEC
    _DEFAULT_SPEC = _parse_spec(spec)


def device_count() -> int:
    return len(jax.devices())


def shard_count(lanes: int = None) -> int:
    """Resolve the spec to a concrete shard count for a dispatch of
    ``lanes`` real candidates (``None``: the configured maximum).  ``auto``
    never uses more shards than real candidates — padding a 1-candidate
    probe to 8 devices would multiply its cost, not split it."""
    spec = _DEFAULT_SPEC
    if spec == "off":
        return 1
    n = device_count()
    if spec == "auto":
        d = n
        if lanes is not None:
            d = min(d, max(int(lanes), 1))
        return d
    d = int(spec)
    if d > n:
        raise ValueError(
            f"REPRO_SHARD={d} exceeds the {n} available device(s)")
    return d


def bucket_lanes(n: int, shards: int, *, grid: str = None) -> int:
    """Device-aware candidate-axis bucket: ``shards`` equal shards, each a
    ``shapes.bucket_lanes`` grid point wide — so the flat lane axis splits
    evenly across the mesh and every shard keeps a bucketed compiled
    shape.  ``shards=1`` degenerates exactly to ``shapes.bucket_lanes``."""
    if shards <= 1:
        return _shapes.bucket_lanes(n, grid=grid)
    per = _shapes.bucket_lanes(-(-int(n) // shards), grid=grid)
    return shards * per


_MESHES: Dict[int, "jax.sharding.Mesh"] = {}
_CALLS: Dict[tuple, Callable] = {}
_LOCK = threading.Lock()


def lanes_mesh(shards: int):
    """The (cached) 1-D ``lanes`` mesh over the first ``shards`` devices."""
    with _LOCK:
        mesh = _MESHES.get(shards)
        if mesh is None:
            from repro.launch.mesh import make_lanes_mesh
            mesh = _MESHES[shards] = make_lanes_mesh(shards)
        return mesh


def _sharded(fn: Callable, shards: int, n_lane: int, n_shared: int,
             static_kw: tuple) -> Callable:
    """The jitted ``shard_map`` wrapper for one (inner fn, shard count,
    arity, static config) combination — cached, so repeat dispatches reuse
    the compiled executable exactly like the unsharded jit entry points."""
    key = (fn, shards, n_lane, n_shared, static_kw)
    with _LOCK:
        got = _CALLS.get(key)
    if got is not None:
        return got
    mesh = lanes_mesh(shards)
    inner = partial(fn, **dict(static_kw))
    wrapped = jax.jit(jax.shard_map(
        inner, mesh=mesh,
        in_specs=(_LANES,) * n_lane + (_REPL,) * n_shared,
        out_specs=_LANES, check_vma=False))
    with _LOCK:
        got = _CALLS.setdefault(key, wrapped)
    return got


def shard_call(fn: Callable, lane_args: Tuple, shared_args: Tuple = (),
               *, shards: int, **static_kw):
    """Run ``fn(*lane_args, *shared_args, **static_kw)`` with the leading
    axis of every ``lane_args`` entry sharded over ``shards`` devices
    (``shared_args`` — e.g. replay sample tables — are replicated; entries
    may be ``None``).  ``shards=1`` calls ``fn`` directly: the sharded
    plane is byte-for-byte the old plane when it degenerates.

    Every lane-arg leading axis must be divisible by ``shards`` — callers
    guarantee that by padding the candidate axis with ``bucket_lanes``.
    Outputs are lane-sharded arrays (or pytrees of them); ``device_get``
    and ``qn_sim.resolve_batches`` gather them in one coalesced fetch."""
    if shards <= 1:
        return fn(*lane_args, *shared_args, **static_kw)
    for a in lane_args:
        if a.shape[0] % shards:
            raise ValueError(
                f"lane axis {a.shape[0]} not divisible by {shards} shards "
                f"(pad with partition.bucket_lanes first)")
    wrapped = _sharded(fn, shards, len(lane_args), len(shared_args),
                       tuple(sorted(static_kw.items())))
    return wrapped(*lane_args, *shared_args)


def shard_info() -> dict:
    """Provenance stamp of the sharding plane: the active spec, the local
    device population, and the mesh the next full-width dispatch would
    use (``benchmarks.common.emit`` attaches this to every BENCH file)."""
    try:
        n = device_count()
        shards = shard_count()
    except Exception:                      # pragma: no cover - no backend
        return {"spec": _DEFAULT_SPEC, "devices": None, "shards": None,
                "mesh": None}
    return {"spec": _DEFAULT_SPEC, "devices": n, "shards": shards,
            "mesh": [shards]}
