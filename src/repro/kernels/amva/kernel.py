"""Batched AMVA (interactive PS fixed point + exact MVA) as Pallas kernels.

This accelerates the PAPER's compute hotspot: D-SPACE4Cloud spends hours in
performance-model evaluations inside the hill climber (JMT runs).  The
batched fast tier evaluates thousands of candidate configurations — whole
(class x vm-type x nu) decision frontiers — in one kernel launch.

Production layout (vs the original flat-1D stub): candidates are tiled
into VPU-shaped ``(8, 128)`` f32 blocks — sublane x lane — and the grid
walks row-blocks of the padded ``(rows, 128)`` candidate matrix.  The
fixed-point / MVA iteration count is a *grid-resident* ``fori_loop``: each
block loads its operands into VMEM once, iterates entirely on-chip
(``PS_ITERS`` = 128 rounds, no HBM round trips), and stores one result
tile.  Arithmetic intensity is ~4 flops x iters per 20 operand bytes
(≈ 26 flop/byte at 128 iters) — compute-bound on TPU.

Two kernels share the tiling:

  * ``amva_fwd`` — the interactive processor-sharing fixed point
        T <- (A/c) * max(1, H*T/(T+Z)) + B
    (elementwise in the candidate; oracle ``mva.ps_response_batch``);
  * ``mva_fwd``  — textbook exact MVA for a single-server closed network,
    carrying (Q, R) over the static population recursion h = 1..H
    (oracle ``mva.mva_response_batch``).

The pure-jnp oracles in ``repro.core.mva`` remain the parity references
(tests/test_kernels.py); interpret mode on CPU is the tier-1 CI path.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.mva import PS_ITERS
SUBLANE, LANE = 8, 128          # f32 VPU tile
TILE = SUBLANE * LANE


def _ps_kernel(a_ref, b_ref, z_ref, h_ref, t_ref, *, iters: int):
    a = a_ref[...].astype(jnp.float32)
    b = b_ref[...].astype(jnp.float32)
    z = z_ref[...].astype(jnp.float32)
    h = h_ref[...].astype(jnp.float32)

    def body(_, t):
        m = h * t / (t + z)
        return a * jnp.maximum(1.0, m) + b

    t = jax.lax.fori_loop(0, iters, body, a + b)
    t_ref[...] = t.astype(t_ref.dtype)


def _mva_kernel(d_ref, z_ref, r_ref, *, h_users: int):
    d = d_ref[...].astype(jnp.float32)
    z = z_ref[...].astype(jnp.float32)

    def body(h, carry):
        q, _ = carry
        r = d * (1.0 + q)
        x = h.astype(jnp.float32) / (r + z)
        return x * r, r

    _, r = jax.lax.fori_loop(1, h_users + 1, body,
                             (jnp.zeros_like(d), d))
    r_ref[...] = r.astype(r_ref.dtype)


def _tiled_call(kernel, args, n: int, interpret: bool):
    """Pad ``(N,)`` operands to a ``(rows, LANE)`` f32 matrix (rows a
    multiple of SUBLANE), launch over row-blocks, unpad."""
    pad = (-n) % TILE
    rows = (n + pad) // LANE

    def shaped(x):
        x = jnp.pad(x.astype(jnp.float32), (0, pad), constant_values=1.0)
        return x.reshape(rows, LANE)

    grid = (rows // SUBLANE,)
    spec = pl.BlockSpec((SUBLANE, LANE), lambda i: (i, 0))
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[spec] * len(args),
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((rows, LANE), jnp.float32),
        interpret=interpret,
    )(*map(shaped, args))
    return out.reshape(-1)[:n]


def amva_fwd(a_over_c: jax.Array, b: jax.Array, think: jax.Array,
             h_users: jax.Array, *, iters: int = PS_ITERS,
             interpret: bool = True) -> jax.Array:
    """All inputs (N,) float32; returns the PS fixed point T (N,)."""
    kernel = functools.partial(_ps_kernel, iters=iters)
    return _tiled_call(kernel, (a_over_c, b, think, h_users),
                       a_over_c.shape[0], interpret)


def mva_fwd(demand: jax.Array, think: jax.Array, *, h_users: int,
            interpret: bool = True) -> jax.Array:
    """Exact single-station MVA response R(H) per candidate (N,)."""
    kernel = functools.partial(_mva_kernel, h_users=h_users)
    return _tiled_call(kernel, (demand, think), demand.shape[0], interpret)
