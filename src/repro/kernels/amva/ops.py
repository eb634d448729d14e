"""jit'd public wrappers for the batched-AMVA kernels (interpreted on CPU,
compiled on TPU: ``kernels.interpret_mode()``).  ``ps_fixed_point`` backs
``evaluators.amva_frontier`` — the one-launch fast tier of the optimizer;
``mva_response`` is the degenerate-case exact-MVA oracle at kernel speed.

Both wrappers open ``kernel:amva*`` telemetry spans around the jitted
launch and label the region with ``jax.named_scope`` for XLA profiles.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.core import partition as _partition
from repro.kernels import interpret_mode
from repro.kernels.amva import kernel
from repro.obs import trace as _obs_trace


def _bucket_args(n: int, shards: int, args):
    """Pad every (N,) operand to the (device-aware) lane bucket by
    replicating its last element.  Lanes are independent fixed points, so
    the replicas converge to the same value as the original and are sliced
    off on the way out — nearby frontier widths then share one compiled
    executable, per shard when the lane axis is device-sharded."""
    n_pad = _partition.bucket_lanes(n, shards) - n
    if n_pad == 0:
        return args
    return tuple(jnp.concatenate(
        [x, jnp.broadcast_to(x[-1:], (n_pad,) + x.shape[1:])]) for x in args)


@partial(jax.jit, static_argnames=("iters",))
def _ps_fixed_point_jit(a_over_c, b, think, h_users,
                        iters: int = kernel.PS_ITERS):
    with jax.named_scope("amva_ps_fixed_point"):
        return kernel.amva_fwd(a_over_c, b, think, h_users, iters=iters,
                               interpret=interpret_mode())


def ps_fixed_point(a_over_c, b, think, h_users, iters: int = kernel.PS_ITERS):
    n = int(getattr(a_over_c, "shape", (1,))[0]
            if getattr(a_over_c, "ndim", 0) else 1)
    shards = _partition.shard_count(n)
    with _obs_trace.span("kernel:amva", cat="kernel",
                         points=n, iters=int(iters), devices=shards):
        if getattr(a_over_c, "ndim", 0):
            args = tuple(jnp.broadcast_to(jnp.asarray(x, jnp.float32), (n,))
                         for x in (a_over_c, b, think, h_users))
            args = _bucket_args(n, shards, args)
            if shards > 1:
                return _partition.shard_call(
                    _ps_fixed_point_jit, args, shards=shards,
                    iters=iters)[:n]
            return _ps_fixed_point_jit(*args, iters=iters)[:n]
        return _ps_fixed_point_jit(a_over_c, b, think, h_users, iters=iters)


@partial(jax.jit, static_argnames=("h_users",))
def _mva_response_jit(demand, think, h_users: int):
    with jax.named_scope("amva_exact_mva"):
        return kernel.mva_fwd(demand, think, h_users=h_users,
                              interpret=interpret_mode())


def mva_response(demand, think, h_users: int):
    with _obs_trace.span("kernel:amva_exact", cat="kernel",
                         h_users=int(h_users)):
        return _mva_response_jit(demand, think, h_users=h_users)
