"""jit'd public wrapper for the batched QN event-step kernel.

``sim_batch`` is signature-compatible with ``qn_sim._sim_batch_jit`` (the
``lax.scan`` oracle), plus the static ``seed_period`` of
``kernel.qn_event_fwd``, and is what ``qn_sim.response_time_batch`` dispatches
to under ``impl="pallas"``.  ``kernels.interpret_mode()`` decides how it
runs: interpreted on CPU (the tier-1 path, bit-exact vs the oracle),
compiled on TPU.

The public wrapper opens a ``kernel:qn_event`` telemetry span around the
jitted launch (counted once per dispatch, not per trace) and names the
region with ``jax.named_scope`` inside the jitted function so the launch
is labeled in XLA/Pallas profiles too.
"""
from __future__ import annotations

from functools import partial

import jax

from repro.kernels import interpret_mode
from repro.kernels.qn_event import kernel
from repro.obs import trace as _obs_trace


@partial(jax.jit, static_argnames=("h_users", "max_slots", "n_events",
                                   "warmup_jobs", "seed_period"))
def _sim_batch_jit(n_map, n_reduce, m_avg, r_avg, think_ms, slots_cap, seed,
                   n_events_active, m_samples, r_samples, *,
                   h_users, max_slots, n_events, warmup_jobs,
                   seed_period=None):
    with jax.named_scope("qn_event_kernel"):
        return kernel.qn_event_fwd(
            n_map, n_reduce, m_avg, r_avg, think_ms, slots_cap, seed,
            n_events_active, m_samples, r_samples,
            h_users=h_users, max_slots=max_slots, n_events=n_events,
            warmup_jobs=warmup_jobs, interpret=interpret_mode(),
            seed_period=seed_period)


def sim_batch(n_map, n_reduce, m_avg, r_avg, think_ms, slots_cap, seed,
              n_events_active, m_samples, r_samples, *,
              h_users, max_slots, n_events, warmup_jobs, seed_period=None):
    with _obs_trace.span("kernel:qn_event", cat="kernel",
                         lanes=int(n_map.shape[0]), n_events=int(n_events),
                         max_slots=int(max_slots),
                         backend=jax.default_backend()):
        return _sim_batch_jit(
            n_map, n_reduce, m_avg, r_avg, think_ms, slots_cap, seed,
            n_events_active, m_samples, r_samples,
            h_users=h_users, max_slots=max_slots, n_events=n_events,
            warmup_jobs=warmup_jobs, seed_period=seed_period)
