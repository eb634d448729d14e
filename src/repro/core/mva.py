"""Analytic performance models — the *fast* tier of D-SPACE4Cloud.

Three layers:

1. ``aria_demand``: ARIA-style job demand bounds (Verma et al. [41], the
   paper's profile-based estimate):
       T_low(c) = (n_M M_avg + n_R R_avg) / c
       T_up(c)  = (n_M-1)M_avg/c + M_max + (n_R-1)R_avg/c + R_max
   giving T_est(c) = A/c + B with
       A = ((n_M-0.5) M_avg + (n_R-0.5) R_avg),  B = (M_max+R_max+S1_max)/2.

2. ``ps_response``: the closed interactive model.  The YARN Capacity
   Scheduler interleaves tasks of concurrent jobs, so at job level the
   cluster behaves as a processor-sharing resource:
       T = (A / c) * max(1, m) + B         (a job present shares c with m)
       m = H * T / (T + Z)                 (interactive/response-time law)
   solved by fixed point (monotone, converges geometrically).  T is
   decreasing in c and cost increasing, so the KKT point of the convex
   inner problem is "deadline binds" — found by bisection
   (``min_slots_for_deadline``).  This is the MINLP-tier model handed to
   the Initial Solution Builder.

3. ``mva_response``: textbook exact MVA for a single-server closed network
   (used by degenerate-case tests that cross-validate the QN simulator).

``ps_response_batch`` evaluates many candidates at once and is the oracle
for the batched AMVA Pallas kernel (repro.kernels.amva).
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from repro.core.problem import JobProfile
from repro.core.workload import DAG, workload_kind

# Iterations of the PS fixed point (here and in the AMVA kernel, which
# imports it): at 128 the iterate is converged to f32 resolution for the
# planner's inputs (the contraction factor is below Z / (T* + Z)).
PS_ITERS = 128


def aria_demand(p: JobProfile, slots: int = 1) -> Tuple[float, float]:
    """Returns (A, B) such that T_est(c) = A/c + B."""
    a = (p.n_map - 1.0) * p.m_avg + (p.n_reduce - 1.0) * p.r_avg
    a = 0.5 * (a + p.n_map * p.m_avg + p.n_reduce * p.r_avg)
    b = 0.5 * (p.m_max + p.r_max + p.s1_max)
    return a, b


def workload_demand(w) -> Tuple[float, float]:
    """Generic ARIA-style (A, B) demand of any workload kind, such that
    T_est(c) = A/c + B.

    For MapReduce profiles this IS ``aria_demand`` (bit-identical — the
    paper-faithful path does not change); for DAG chains the same
    average/max aggregation is summed over the stage sequence (each stage
    is one fork-join, so A accumulates (n_k - 0.5) t_k and B half the
    per-stage maxima).  Every analytic consumer — the KKT bisection of
    ``milp.py``, ``job_response``, the batched AMVA frontier and its Pallas
    kernel — prices workloads through this one function."""
    if workload_kind(w) == DAG:
        a = sum((s.n_tasks - 0.5) * s.t_avg for s in w.stages)
        b = 0.5 * sum(s.max_or_est for s in w.stages)
        return a, b
    return aria_demand(w)


def aria_bounds(p: JobProfile, slots: int) -> Tuple[float, float]:
    low = (p.n_map * p.m_avg + p.n_reduce * p.r_avg) / slots
    up = ((p.n_map - 1) * p.m_avg / slots + p.m_max
          + (p.n_reduce - 1) * p.r_avg / slots + p.r_max + p.s1_max)
    return low, up


def ps_response(a_over_c: float, b: float, think: float,
                h_users: int, iters: int = PS_ITERS) -> float:
    """Interactive processor-sharing fixed point (see module docstring)."""
    t = a_over_c + b
    for _ in range(iters):
        m = h_users * t / (t + think)
        t = a_over_c * max(1.0, m) + b
    return t


def mva_response(demand: float, think: float, h_users: int) -> float:
    """Exact MVA, single queueing station + delay; returns R(H)."""
    q = 0.0
    r = demand
    for h in range(1, h_users + 1):
        r = demand * (1.0 + q)
        x = h / (r + think)
        q = x * r
    return r


def job_response(p, slots: int, think: float, h_users: int) -> float:
    """Analytic response time of class jobs on ``slots`` containers
    (``p`` is any workload kind — see ``workload_demand``)."""
    a, b = workload_demand(p)
    return ps_response(a / slots, b, think, h_users)


# --------------------------------------------------------------------------
# Batched JAX versions (oracles for kernels/amva)
# --------------------------------------------------------------------------

def ps_response_batch(a_over_c: jax.Array, b: jax.Array, think: jax.Array,
                      h_users: jax.Array, iters: int = PS_ITERS) -> jax.Array:
    """Vectorized PS fixed point over candidate configurations (all (N,))."""
    t = a_over_c + b

    def body(t, _):
        m = h_users * t / (t + think)
        t = a_over_c * jnp.maximum(1.0, m) + b
        return t, None

    t, _ = jax.lax.scan(body, t, None, length=iters)
    return t


def mva_response_batch(demand: jax.Array, think: jax.Array,
                       h_users: int) -> jax.Array:
    """Vectorized exact single-station MVA (degenerate-case oracle)."""
    def body(carry, h):
        q = carry
        r = demand * (1.0 + q)
        x = h / (r + think)
        q = x * r
        return q, r

    _, rs = jax.lax.scan(body, jnp.zeros_like(demand),
                         jnp.arange(1, h_users + 1, dtype=jnp.float32))
    return rs[-1]


def min_slots_for_deadline(p, think: float, h_users: int,
                           deadline: float, max_slots: int = 1 << 16) -> int:
    """Smallest slot count meeting the deadline under the PS model
    (= the KKT point: deadline binds at the optimum).  Workload-generic:
    ``p`` may be a MapReduce profile or a DAG chain."""
    lo, hi = 1, max_slots
    if job_response(p, hi, think, h_users) > deadline:
        return -1
    while lo < hi:
        mid = (lo + hi) // 2
        if job_response(p, mid, think, h_users) <= deadline:
            hi = mid
        else:
            lo = mid + 1
    return lo
