"""Closed fork-join queueing-network simulator (paper §3.1, Figure 2) in JAX.

Faithful structure:
  * H_i users cycle through a delay station (think time Z_i, exponential);
  * a job forks into n^M Map task requests that enter the finite-capacity
    region (FCR): at most ``slots`` tasks are in service at once;
  * Map and Reduce stages are multi-server queues inside the FCR; the class
    switch gives Reduce tasks priority over queued Map tasks (YARN Capacity
    Scheduler FIFO semantics);
  * joins are OUTSIDE the FCR: a completing task releases its container
    immediately; the Reduce fork is outside too (n_R may exceed slots).

Implementation: event-driven ``lax.scan`` with a fixed event budget.  Each
iteration performs exactly one action — dispatch one task / complete one
task / end one think — selected with masked ``jnp.where`` updates so the
whole simulator is one fused XLA program, ``vmap``-able over replications
and candidate configurations (the paper runs JMT for hours; this batched
simulator is the same abstraction at ~10^5 events/s/config on CPU).

Service times are exponential with the profile means (the QN abstraction
that the paper validates within ~12-30% against real systems; we validate
against the detailed trace-replay simulator in ``cluster_sim.py``).
"""
from __future__ import annotations

import os
import time
from dataclasses import dataclass
from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import partition as _partition
from repro.core import shapes as _shapes
from repro.obs import compile as _obs_compile
from repro.obs import metrics as _obs_metrics
from repro.obs import trace as _obs_trace

# Compile observability (qn.compiles / qn.compile_ms) + the env-gated
# persistent compilation cache must be live before the first jit of any
# entry point that simulates — importing this module is that point.
_obs_compile.install()

INF = jnp.float32(1e30)
_PRIO = jnp.float32(1e15)       # added to map-stage keys: reduce dispatches first


@dataclass(frozen=True)
class QNParams:
    n_map: int
    n_reduce: int
    m_avg: float                 # mean map-task service [ms]
    r_avg: float                 # mean reduce-task service [ms]
    think_ms: float              # Z_i
    h_users: int
    slots: int                   # FCR capacity = total containers
    n_events: int = 200_000
    warmup_jobs: int = 10
    seed: int = 0


def _init_state(key, think_ms, h_users: int, max_slots: int):
    H = h_users
    k0, _ = jax.random.split(key)
    return dict(
        now=jnp.float32(0),
        slot_end=jnp.full((max_slots,), INF),
        slot_user=jnp.full((max_slots,), -1, jnp.int32),
        think_end=jax.random.exponential(k0, (H,)) * think_ms,
        phase=jnp.zeros((H,), jnp.int32),         # 0 think, 1 map, 2 reduce
        pending=jnp.zeros((H,), jnp.int32),
        inflight=jnp.zeros((H,), jnp.int32),
        arrival=jnp.full((H,), INF),
        job_start=jnp.zeros((H,)),
        resp_sum=jnp.float32(0), resp_cnt=jnp.float32(0),
        done_jobs=jnp.int32(0))


def _rng_tables(key, n_events: int, fold_base,
                m_samples=None, r_samples=None):
    """Hoist the per-event RNG out of the scan: every draw is a pure
    function of ``(key, i)``, so precomputing the whole (n_events,) stream
    in one vectorized pass produces bit-for-bit the values the old
    in-loop ``fold_in`` calls drew — while removing two threefry hashes
    from every scan step (the dominant per-step cost on CPU).

    Returns ``(st_m, st_r, td)``: the map/reduce service draw per event
    (replay mode gathers the sampled durations; exponential mode returns
    the unit-exponential draw in both, scaled by the profile mean inside
    the step) and the unit-exponential think redraw (fold offset
    ``i + fold_base`` — the *logical* budget, part of the values)."""
    idx = jnp.arange(n_events)

    def service(i):
        key_i = jax.random.fold_in(key, i)
        if m_samples is not None:
            idx_m = jax.random.randint(key_i, (), 0, m_samples.shape[0])
            idx_r = jax.random.randint(key_i, (), 0, r_samples.shape[0])
            return m_samples[idx_m], r_samples[idx_r]
        e = jax.random.exponential(key_i)
        return e, e

    def think(i):
        return jax.random.exponential(jax.random.fold_in(key, i + fold_base))

    st_m, st_r = jax.vmap(service)(idx)
    return st_m, st_r, jax.vmap(think)(idx)


def _make_step(n_map, n_reduce, m_avg, r_avg, think_ms, slots_cap,
               max_slots: int, warmup_jobs: int,
               replay: bool = False, n_events_active=None):
    """One event per step — dispatch one task / complete one task / end one
    think.  The step consumes ``xs = (i, st_m, st_r, td)`` from the
    precomputed RNG tables (``_rng_tables``) and applies every state change
    as a single *guarded scatter* per array (branch-selected index +
    branch-selected value, identity when no branch fires) instead of
    materializing three full candidate states and ``where``-chaining them —
    same values, roughly half the per-step op count.

    ``n_events_active``: optional traced per-config event budget.  The scan
    length stays static (padded across a batch), but steps with
    ``i >= n_events_active`` become no-ops and the think-redraw fold offset
    uses the *logical* budget — so a config padded inside a batch produces
    bit-for-bit the random stream of a scalar run with ``n_events`` equal to
    its own logical budget."""
    slot_enabled = jnp.arange(max_slots) < slots_cap
    i32 = jnp.int32

    def step(s, xs):
        i, st_m, st_r, td = xs

        # ---------------- choose the event ---------------------------------
        avail = (s["slot_user"] < 0) & slot_enabled
        slot = jnp.argmax(avail)           # first free slot (if any)
        free_slot = avail[slot]
        b_dispatch = free_slot & jnp.any(s["pending"] > 0)

        # Reduce priority, FIFO-by-wave-arrival within a priority level.
        # Two-level lexicographic selection (NOT arrival+BIG in one float:
        # f32 resolution at 1e15 collapses all arrivals and starves users).
        red_key = jnp.where((s["pending"] > 0) & (s["phase"] == 2),
                            s["arrival"], INF)
        map_key = jnp.where((s["pending"] > 0) & (s["phase"] == 1),
                            s["arrival"], INF)
        has_red = jnp.min(red_key) < INF
        u = jnp.where(has_red, jnp.argmin(red_key), jnp.argmin(map_key))
        if replay:
            st = jnp.where(s["phase"][u] == 1, st_m, st_r)
        else:
            st = st_m * jnp.where(s["phase"][u] == 1, m_avg, r_avg)

        cslot = jnp.argmin(s["slot_end"])  # next completion (if any)
        t_slot = s["slot_end"][cslot]
        tu = jnp.argmin(s["think_end"])    # next think end (if any)
        t_think = s["think_end"][tu]
        b_complete = (~b_dispatch) & (t_slot <= t_think) & (t_slot < INF)
        b_think = (~b_dispatch) & (~b_complete) & (t_think < INF)
        if n_events_active is not None:          # padded batch: mask tail
            active = i < n_events_active
            b_dispatch = b_dispatch & active
            b_complete = b_complete & active
            b_think = b_think & active

        # ---------------- completion bookkeeping ---------------------------
        cu = s["slot_user"][cslot]
        infl_cu = s["inflight"][cu] - 1
        stage_done = (s["pending"][cu] == 0) & (infl_cu == 0)
        was_map = s["phase"][cu] == 1
        job_done = stage_done & (~was_map)      # reduce done -> job done
        resp = t_slot - s["job_start"][cu]
        new_think = t_slot + td * think_ms
        counted = job_done & (s["done_jobs"] >= warmup_jobs)

        # ---------------- guarded scatters ---------------------------------
        # slot arrays: dispatch writes (now+st, u) at the free slot,
        # completion writes (INF, -1) at the completing slot
        sidx = jnp.where(b_dispatch, slot, cslot)
        do_slot = b_dispatch | b_complete
        se_val = jnp.where(b_dispatch, s["now"] + st, INF)
        su_val = jnp.where(b_dispatch, u.astype(i32), i32(-1))
        slot_end = s["slot_end"].at[sidx].set(
            jnp.where(do_slot, se_val, s["slot_end"][sidx]))
        slot_user = s["slot_user"].at[sidx].set(
            jnp.where(do_slot, su_val, s["slot_user"][sidx]))

        # user arrays: dispatch touches u, completion touches cu (map stage
        # done -> fork reduce outside the FCR; reduce done -> back to think),
        # think end touches tu (submit job: fork maps)
        uidx = jnp.where(b_dispatch, u,
                         jnp.where(b_complete, cu.astype(u.dtype),
                                   tu.astype(u.dtype)))
        do_any = b_dispatch | b_complete | b_think
        pending_val = jnp.where(
            b_dispatch, s["pending"][u] - 1,
            jnp.where(b_complete,
                      jnp.where(stage_done & was_map, n_reduce,
                                s["pending"][cu]),
                      n_map))
        pending = s["pending"].at[uidx].set(
            jnp.where(do_any, pending_val, s["pending"][uidx]))
        inflight_val = jnp.where(b_dispatch, s["inflight"][u] + 1, infl_cu)
        inflight = s["inflight"].at[uidx].set(
            jnp.where(b_dispatch | b_complete, inflight_val,
                      s["inflight"][uidx]))
        phase_val = jnp.where(
            b_complete,
            jnp.where(stage_done, jnp.where(was_map, i32(2), i32(0)),
                      s["phase"][cu]),
            i32(1))
        phase = s["phase"].at[uidx].set(
            jnp.where(b_complete | b_think, phase_val, s["phase"][uidx]))
        arrival_val = jnp.where(
            b_complete,
            jnp.where(job_done, INF,
                      jnp.where(stage_done & was_map, t_slot,
                                s["arrival"][cu])),
            t_think)
        arrival = s["arrival"].at[uidx].set(
            jnp.where(b_complete | b_think, arrival_val, s["arrival"][uidx]))
        think_val = jnp.where(
            b_complete, jnp.where(job_done, new_think, s["think_end"][cu]),
            INF)
        think_end = s["think_end"].at[uidx].set(
            jnp.where(b_complete | b_think, think_val, s["think_end"][uidx]))
        job_start = s["job_start"].at[tu].set(
            jnp.where(b_think, t_think, s["job_start"][tu]))

        now = jnp.where(b_complete, t_slot,
                        jnp.where(b_think, t_think, s["now"]))
        resp_sum = s["resp_sum"] + jnp.where(b_complete & counted, resp, 0.0)
        resp_cnt = s["resp_cnt"] + jnp.where(b_complete & counted, 1.0, 0.0)
        done_jobs = s["done_jobs"] + jnp.where(b_complete & job_done, 1, 0)

        return dict(now=now, slot_end=slot_end, slot_user=slot_user,
                    think_end=think_end, phase=phase, pending=pending,
                    inflight=inflight, arrival=arrival, job_start=job_start,
                    resp_sum=resp_sum, resp_cnt=resp_cnt,
                    done_jobs=done_jobs), None

    return step


def _sim(n_map, n_reduce, m_avg, r_avg, think_ms, slots_cap,
         h_users: int, max_slots: int, n_events: int, warmup_jobs: int,
         seed, m_samples=None, r_samples=None, n_events_active=None):
    """Core simulator.  Static: h_users, max_slots, n_events, warmup_jobs.
    Traced: everything else (so configs can be vmapped).

    ``m_samples``/``r_samples``: optional empirical task-duration lists —
    the JMT *replayer* mode the paper uses (service times drawn from logged
    durations instead of exponentials)."""
    key = jax.random.key(seed)
    state = _init_state(key, think_ms, h_users, max_slots)
    fold_base = n_events if n_events_active is None else n_events_active
    tables = _rng_tables(key, n_events, fold_base,
                         m_samples=m_samples, r_samples=r_samples)
    step = _make_step(n_map, n_reduce, m_avg, r_avg, think_ms,
                      slots_cap, max_slots, warmup_jobs,
                      replay=m_samples is not None,
                      n_events_active=n_events_active)
    state, _ = jax.lax.scan(step, state, (jnp.arange(n_events),) + tables)
    mean_resp = state["resp_sum"] / jnp.maximum(state["resp_cnt"], 1.0)
    return mean_resp, state["resp_cnt"]


@partial(jax.jit, static_argnames=("h_users", "max_slots", "n_events",
                                   "warmup_jobs"))
def _sim_jit(n_map, n_reduce, m_avg, r_avg, think_ms, slots_cap, seed, *,
             h_users, max_slots, n_events, warmup_jobs):
    return _sim(n_map, n_reduce, m_avg, r_avg, think_ms, slots_cap,
                h_users, max_slots, n_events, warmup_jobs, seed)


@partial(jax.jit, static_argnames=("h_users", "max_slots", "n_events",
                                   "warmup_jobs"))
def _sim_replay_jit(n_map, n_reduce, think_ms, slots_cap, seed,
                    m_samples, r_samples, *,
                    h_users, max_slots, n_events, warmup_jobs):
    return _sim(n_map, n_reduce, jnp.float32(0), jnp.float32(0), think_ms,
                slots_cap, h_users, max_slots, n_events, warmup_jobs, seed,
                m_samples=m_samples, r_samples=r_samples)


@partial(jax.jit, static_argnames=("h_users", "max_slots", "n_events",
                                   "warmup_jobs"))
def _sim_batch_jit(n_map, n_reduce, m_avg, r_avg, think_ms, slots_cap, seed,
                   n_events_active, m_samples, r_samples, *,
                   h_users, max_slots, n_events, warmup_jobs):
    """One fused device program over a flat (candidate x replication) batch.
    All per-config parameters are (B,) arrays; replay sample lists (when
    given) are shared across the batch (in_axes=None)."""
    def one(nm, nr, ma, ra, tm, sc, sd, nea):
        return _sim(nm, nr, ma, ra, tm, sc, h_users, max_slots, n_events,
                    warmup_jobs, sd, m_samples=m_samples,
                    r_samples=r_samples, n_events_active=nea)
    return jax.vmap(one)(n_map, n_reduce, m_avg, r_avg, think_ms, slots_cap,
                         seed, n_events_active)


# ---------------------------------------------------------------------------
# Batch-simulator implementation switch.  ``impl="jnp"`` is the lax.scan
# oracle above; ``impl="pallas"`` dispatches the SAME padded batch to the
# fused Pallas event-step kernel (repro.kernels.qn_event), whose contract
# is bit-exact parity in interpret mode (tests/test_qn_event_kernel.py).
# The process default follows the platform — the compiled kernel on TPU,
# the scan on CPU, where the kernel only runs interpreted — unless
# $REPRO_QN_IMPL names one, so racing, coordination and windowed planning
# switch transparently; ``set_default_impl`` flips it at runtime (dispatch
# accounting is impl-independent by construction).
# ---------------------------------------------------------------------------

QN_IMPLS = ("jnp", "pallas")
_DEFAULT_IMPL = os.environ.get("REPRO_QN_IMPL")     # None: by platform


def set_default_impl(impl: str) -> None:
    """Select the batch simulator backend for calls that don't pass one."""
    global _DEFAULT_IMPL
    if impl not in QN_IMPLS:
        raise ValueError(f"impl must be one of {QN_IMPLS}, got {impl!r}")
    _DEFAULT_IMPL = impl


def default_impl() -> str:
    if _DEFAULT_IMPL is not None:
        return _DEFAULT_IMPL
    from repro.kernels import interpret_mode
    return "jnp" if interpret_mode() else "pallas"


def _batch_sim_fn(impl):
    return _batch_sim_fns(impl)[0]


def _batch_sim_fns(impl):
    """(outer, inner) batch simulators for ``impl``: ``outer`` is the
    public single-device entry point (spans included), ``inner`` the bare
    jitted program ``partition.shard_call`` wraps in ``shard_map`` — the
    sharded path opens its span at the dispatch site instead."""
    impl = default_impl() if impl is None else impl
    if impl == "jnp":
        return _sim_batch_jit, _sim_batch_jit
    if impl == "pallas":
        from repro.kernels.qn_event import ops as qn_event_ops
        return qn_event_ops.sim_batch, qn_event_ops._sim_batch_jit
    raise ValueError(f"impl must be one of {QN_IMPLS}, got {impl!r}")


# ---------------------------------------------------------------------------
# Device-dispatch accounting (benchmarks/batched_qn.py measures the batched
# path's dispatch reduction against the scalar path with these).  Beyond raw
# dispatches the counters track vmap lanes and simulated events — including
# the padding overhead (pow2 candidate axis, scan length padded to the batch
# maximum) that the service's admission control exists to keep profitable.
# The DAG simulator (``repro.core.dag``) reports into the SAME counters, so
# ``dispatch_count()``/``sim_stats()`` are the process-wide accounting for
# every workload kind (run reports, benchmarks, and the service's
# zero-dispatch warm-cache guarantees all rely on that).
# The hill climber probes classes from a thread pool, so updates take a lock.
# ---------------------------------------------------------------------------

# Counters live in the process-global metrics registry (repro.obs.metrics)
# under the ``qn.`` prefix; the names below are the historical sim_stats
# keys.  All of them update atomically under the shared registry lock — the
# same guarantee the old private _DISPATCH_LOCK gave — so sim_stats() is
# always a consistent snapshot of one-or-more whole dispatches.
_SIM_STAT_KEYS = ("dispatches", "lanes", "padded_lanes",
                  "events_total", "events_useful", "draw_columns")
_REG = _obs_metrics.registry()
_QN_COUNTERS = {k: _REG.counter(f"qn.{k}") for k in _SIM_STAT_KEYS}
# Bucket-induced padding, tracked SEPARATELY from batch padding: a padded
# lane exists because the lane-count grid rounded the candidate axis up
# (shapes.bucket_lanes), while events_total - events_useful additionally
# contains real lanes scanned past their own logical budget (batch
# padding).  ``padding_stats()`` splits the two so efficiency reports
# don't conflate them.
_QN_BUCKET = {k: _REG.counter(f"qn.bucket_{k}") for k in
              ("padded_lanes", "padded_events")}
# Shard-induced padding, tracked separately again: rounding the candidate
# axis to a multiple of the shard count (partition.bucket_lanes) can pad
# beyond the single-device bucket would have.  ``qn.devices`` records the
# shard count of the most recent fused dispatch (1 for scalar paths).
_QN_SHARD = {k: _REG.counter(f"qn.shard_{k}") for k in
             ("padded_lanes", "padded_events")}
_QN_DEVICES = _REG.gauge(
    "qn.devices", help="lane shards (devices) of the last fused dispatch")
_QN_SYNC_US = _REG.counter(
    "qn.sync_wait_us",
    help="host time blocked fetching batch results from the device [us]")


def _count_dispatch(n: int = 1, *, lanes: int = None, padded_lanes: int = 0,
                    events_total: int = 0, events_useful: int = 0,
                    bucket_padded_lanes: int = 0,
                    bucket_padded_events: int = 0,
                    shard_padded_lanes: int = 0,
                    shard_padded_events: int = 0,
                    devices: int = 1,
                    draw_columns: int = None,
                    kind: str = "mapreduce",
                    impl: str = None) -> None:
    lanes = n if lanes is None else lanes
    with _REG.lock:
        _QN_COUNTERS["dispatches"].inc(n)
        # Labeled attribution rides beside (never instead of) the flat
        # totals: sim_stats()/dispatch_count() read the bare counters and
        # stay bit-identical whether or not anyone looks at labels.
        _QN_COUNTERS["dispatches"].labels(
            kind=kind, impl=impl if impl is not None else default_impl(),
        ).inc(n)
        _QN_COUNTERS["lanes"].inc(lanes)
        _QN_COUNTERS["padded_lanes"].inc(padded_lanes)
        _QN_COUNTERS["events_total"].inc(events_total)
        _QN_COUNTERS["events_useful"].inc(events_useful)
        _QN_COUNTERS["draw_columns"].inc(
            lanes if draw_columns is None else draw_columns)
        _QN_BUCKET["padded_lanes"].inc(bucket_padded_lanes)
        _QN_BUCKET["padded_events"].inc(bucket_padded_events)
        _QN_SHARD["padded_lanes"].inc(shard_padded_lanes)
        _QN_SHARD["padded_events"].inc(shard_padded_events)
        _QN_DEVICES.set(devices)


def padding_stats() -> dict:
    """Split of the padding overhead: ``bucket_padded_lanes`` /
    ``bucket_padded_events`` are the lanes (and their scan events) that
    exist only because of lane-grid rounding; ``shard_padded_lanes`` /
    ``shard_padded_events`` the *additional* lanes sharding's
    round-up-to-the-mesh padding created beyond the single-device bucket
    (0 whenever ``REPRO_SHARD=off`` or one shard is used);
    ``batch_padded_events`` is the remainder of ``events_total -
    events_useful`` — real lanes scanned past their own logical budget to
    the batch maximum.  All counters cover every workload kind (the DAG
    batch reports here too) and reset with ``reset_sim_stats``."""
    with _REG.lock:
        total = _QN_COUNTERS["events_total"].value
        useful = _QN_COUNTERS["events_useful"].value
        b_lanes = _QN_BUCKET["padded_lanes"].value
        b_events = _QN_BUCKET["padded_events"].value
        s_lanes = _QN_SHARD["padded_lanes"].value
        s_events = _QN_SHARD["padded_events"].value
        return {"bucket_padded_lanes": b_lanes,
                "bucket_padded_events": b_events,
                "shard_padded_lanes": s_lanes,
                "shard_padded_events": s_events,
                "batch_padded_events": total - useful - b_events - s_events,
                "events_total": total, "events_useful": useful}


def dispatch_count() -> int:
    """Total simulator device dispatches issued by this process so far."""
    return _QN_COUNTERS["dispatches"].value


def sim_stats() -> dict:
    """Process-wide simulator counters: ``dispatches`` (device calls),
    ``lanes`` (vmapped candidate x replication programs, incl. pow2
    padding), ``padded_lanes`` (lanes that were pure padding), and the
    scan-step totals ``events_total`` vs ``events_useful`` (logical budgets
    only) — their ratio is the batch-padding efficiency — and
    ``draw_columns``, the columns of seed-only draw tables the dispatches
    build: one per lane, or one per replication seed and shard where
    ``response_time_batch`` passes the lanes' seed period to the
    ``qn_event`` kernel (the pallas impl).  It is the one key that differs
    between impls: the scan oracle draws per lane.

    Backed by the ``qn.*`` counters of ``repro.obs.registry()``: the dict
    holds the registry's values, not a copy (asserted in
    tests/test_impl_dispatch.py)."""
    with _REG.lock:
        return {k: _QN_COUNTERS[k].value for k in _SIM_STAT_KEYS}


def reset_sim_stats() -> None:
    """Zero ALL simulator counters (dispatches, lanes, padded_lanes,
    events_total, events_useful, draw_columns, and the bucket and shard
    padding).  This is the one reset for per-run accounting;
    ``reset_dispatch_count`` is a back-compat alias."""
    with _REG.lock:
        for c in _QN_COUNTERS.values():
            c.reset()
        for c in _QN_BUCKET.values():
            c.reset()
        for c in _QN_SHARD.values():
            c.reset()


reset_dispatch_count = reset_sim_stats


_pow2 = _shapes.pow2


def _combine(means, cnts) -> Tuple[float, float]:
    """Count-weighted mean across replications, in host float64.

    Shared by the scalar and batched paths of BOTH workload simulators
    (this module and ``repro.core.dag``) — each kind's bit-exact parity
    contract requires one combination rule, and cross-kind consistency
    keeps mixed-workload reports comparable.  Returns (inf, 0.0) when no
    replication completed a job."""
    good = [(float(m), float(c)) for m, c in zip(means, cnts) if c > 0]
    if not good:
        return float("inf"), 0.0
    tot = sum(c for _, c in good)
    return sum(m * c for m, c in good) / tot, tot


def simulate(p: QNParams, replications: int = 3) -> Tuple[float, float]:
    """Returns (mean response [ms], total completed jobs counted).

    ``max_slots`` is bucketed to the geometric shape grid and ``n_events``
    to its pow2 logical-budget grid (``repro.core.shapes``) so the hill
    climber's slot sweeps hit the jit cache instead of recompiling."""
    outs = []
    cnts = []
    for r in range(replications):
        ne = _shapes.bucket_events(p.n_events)
        _count_dispatch(events_total=ne, events_useful=ne, impl="jnp")
        with _obs_trace.span("kernel:scalar", cat="kernel", events=ne):
            m, c = _sim_jit(
                jnp.int32(p.n_map), jnp.int32(p.n_reduce),
                jnp.float32(p.m_avg), jnp.float32(p.r_avg),
                jnp.float32(p.think_ms), jnp.int32(p.slots),
                p.seed + 1000 * r,
                h_users=p.h_users, max_slots=_shapes.bucket_slots(p.slots),
                n_events=ne, warmup_jobs=p.warmup_jobs)
        outs.append(float(m))
        cnts.append(float(c))
    return _combine(outs, cnts)


def events_needed(p: QNParams, min_jobs: int = 40) -> int:
    """Event budget heuristic: ~2 events per task (dispatch+completion) + 2
    per job, times jobs; padded 1.5x."""
    per_job = 2 * (p.n_map + p.n_reduce) + 4
    return int(1.5 * per_job * (min_jobs + p.warmup_jobs))


def padded_event_budget(n_map: int, n_reduce: int, *, min_jobs: int = 40,
                        warmup_jobs: int = 10) -> int:
    """The pow2-bucketed logical event budget one (candidate, replication)
    lane costs — what ``response_time``/``response_time_batch`` will actually
    scan for this profile.  The budget depends only on the task counts and
    the job quota, so admission control can price a request without knowing
    the candidate nu yet."""
    p = QNParams(n_map=int(n_map), n_reduce=int(n_reduce), m_avg=0.0,
                 r_avg=0.0, think_ms=0.0, h_users=1, slots=1,
                 warmup_jobs=warmup_jobs)
    return _pow2(events_needed(p, min_jobs))


def response_time(n_map: int, n_reduce: int, m_avg: float, r_avg: float,
                  think_ms: float, h_users: int, slots: int,
                  min_jobs: int = 40, warmup_jobs: int = 10,
                  seed: int = 0, replications: int = 2,
                  m_samples=None, r_samples=None) -> float:
    """Mean response time of the closed QN.  When ``m_samples``/``r_samples``
    are given, service times replay the empirical lists (JMT replayer mode,
    the paper's validation setup); otherwise exponential with the profile
    means."""
    p = QNParams(n_map=n_map, n_reduce=n_reduce, m_avg=m_avg, r_avg=r_avg,
                 think_ms=think_ms, h_users=h_users, slots=slots,
                 warmup_jobs=warmup_jobs, seed=seed)
    p = QNParams(**{**p.__dict__, "n_events": events_needed(p, min_jobs)})
    if m_samples is None:
        mean, cnt = simulate(p, replications)
        return mean
    ms = jnp.asarray(np.asarray(m_samples, np.float32))
    rs = jnp.asarray(np.asarray(r_samples, np.float32))
    outs, cnts = [], []
    for r in range(replications):
        ne = _shapes.bucket_events(p.n_events)
        _count_dispatch(events_total=ne, events_useful=ne, impl="jnp")
        with _obs_trace.span("kernel:scalar", cat="kernel", events=ne,
                             replay=True):
            m, c = _sim_replay_jit(
                jnp.int32(p.n_map), jnp.int32(p.n_reduce),
                jnp.float32(p.think_ms), jnp.int32(p.slots),
                p.seed + 1000 * r,
                ms, rs, h_users=p.h_users,
                max_slots=_shapes.bucket_slots(p.slots),
                n_events=ne, warmup_jobs=p.warmup_jobs)
        outs.append(float(m)); cnts.append(float(c))
    return _combine(outs, cnts)[0]


class PendingBatch:
    """Handle to an in-flight batched dispatch (JAX async dispatch): the
    device arrays are captured un-synced, so the caller can issue further
    dispatches — or do host-side bookkeeping — while the device executes.
    ``resolve()`` performs the one host sync (``jax.device_get``) and the
    float64 per-candidate combination; ``resolve_batches`` syncs MANY
    handles in a single ``device_get`` (the per-round coalescing point of
    ``scheduler.flush`` and ``BatchedQNEvaluator.evaluate_many``).
    Resolution is memoized, and the resolved values are identical to what
    the blocking call would have returned."""

    def __init__(self, mean, cnt, C: int, R: int):
        self._mean, self._cnt = mean, cnt
        self._C, self._R = C, R
        self._out: "np.ndarray | None" = None

    def _finish(self, mean, cnt) -> np.ndarray:
        if self._out is None:
            C, R = self._C, self._R
            mean = np.asarray(mean, np.float64).reshape(-1, R)[:C]
            cnt = np.asarray(cnt, np.float64).reshape(-1, R)[:C]
            out = np.full((C,), np.inf)
            for c in range(C):   # same float64 combination as the scalar path
                out[c] = _combine(mean[c], cnt[c])[0]
            self._out = out
            self._mean = self._cnt = None      # free the device buffers
        return self._out

    def resolve(self) -> np.ndarray:
        if self._out is None:
            return self._finish(*_device_get((self._mean, self._cnt), 1))
        return self._out

    @classmethod
    def resolved(cls, out) -> "PendingBatch":
        """A pre-resolved handle (empty batches, cache hits)."""
        pb = cls(None, None, 0, 1)
        pb._out = np.asarray(out, np.float64)
        return pb


def _device_get(tree, batches: int):
    """``jax.device_get`` of ``batches`` handles' arrays: the host waits
    here for the device, so the wait is spanned (``resolve``) and counted
    (``qn.sync_wait_us``)."""
    with _obs_trace.span("resolve", cat="qn", batches=batches):
        t0 = time.perf_counter_ns()
        out = jax.device_get(tree)
        _QN_SYNC_US.inc((time.perf_counter_ns() - t0) // 1000)
    return out


def resolve_batches(batches) -> list:
    """Resolve many ``PendingBatch`` handles with ONE ``jax.device_get``
    (one host sync per scheduling round instead of one per fusion group).
    Already-resolved handles are passed through."""
    batches = list(batches)
    todo = [b for b in batches if b._out is None]
    if todo:
        fetched = _device_get([(b._mean, b._cnt) for b in todo], len(todo))
        for b, (m, c) in zip(todo, fetched):
            b._finish(m, c)
    return [b._out for b in batches]


def _check_seed_period(seeds, period: int) -> None:
    """Raise unless lane ``l`` carries the seed of lane ``l % period``: the
    layout under which the ``qn_event`` kernel builds its seed-only draw
    tables for one period of lanes and broadcasts them to the rest."""
    seeds = np.asarray(seeds)
    if seeds.size % period or not np.array_equal(
            seeds, np.tile(seeds[:period], seeds.size // period)):
        raise ValueError(f"lane seeds do not repeat with period {period}")


def response_time_batch(n_map, n_reduce, m_avg, r_avg, think_ms,
                        h_users: int, slots, min_jobs: int = 40,
                        warmup_jobs: int = 10, seed: int = 0,
                        replications: int = 2,
                        m_samples=None, r_samples=None,
                        impl: str = None, defer: bool = False):
    """Batched ``response_time``: one fused device dispatch for a whole
    candidate sweep.

    ``n_map``/``n_reduce``/``m_avg``/``r_avg``/``think_ms``/``slots`` are
    scalars or broadcastable 1-D arrays over C candidates (so a call can mix
    a nu frontier with several VM types' profiles at once); ``h_users`` is a
    single static int — the batch is per concurrency level, which is fixed
    within an application class.  The simulator is vmapped over the flat
    (candidate x replication) axis with ``max_slots`` and the event budget
    padded to the batch maximum; each candidate still runs with its *own*
    logical event budget (masked tail + matching RNG fold offset), so the
    result for every candidate is numerically identical to a scalar
    ``response_time`` call with the same seed.

    When ``m_samples``/``r_samples`` are given the whole batch runs in JMT
    replayer mode with the shared empirical duration lists.

    ``impl`` selects the batch simulator backend (``"jnp"`` — the lax.scan
    oracle — or ``"pallas"`` — the fused event-step kernel, bit-exact in
    interpret mode); ``None`` uses the process default (``default_impl``).
    Dispatch/lane accounting is identical for every impl.

    Static jit axes (``max_slots``, the candidate axis) are quantized to
    the geometric shape grid (``repro.core.shapes``), so nearby sweeps
    share one compiled executable; bucket-induced padding is counted
    separately from batch padding (``padding_stats``).

    Under ``REPRO_SHARD`` (``repro.core.partition``) the padded lane axis
    additionally executes data-parallel over a 1-D ``lanes`` device mesh:
    the candidate axis is rounded to ``shards`` equal bucketed shards and
    the same program runs under ``jax.shard_map`` — per-lane results are
    bit-identical to the single-device dispatch (sharding changes
    placement, never values), and the shard-induced extra padding is
    accounted under ``shard_padded_*`` in ``padding_stats``.

    Returns a float64 array of shape (C,) of mean response times [ms]
    (``inf`` where no replication completed a job) — or, with
    ``defer=True``, a ``PendingBatch`` handle that resolves to exactly
    that array without blocking the caller on the device.
    """
    impl = default_impl() if impl is None else impl
    outer_fn, inner_fn = _batch_sim_fns(impl)
    shape = np.broadcast_shapes(*(np.shape(np.asarray(x)) for x in
                                  (n_map, n_reduce, m_avg, r_avg,
                                   think_ms, slots)))
    C = int(np.prod(shape, dtype=np.int64)) if shape else 1

    def _b(x, dt):
        return np.broadcast_to(np.asarray(x, dt), (C,)).copy()

    nm = _b(n_map, np.int64)
    nr = _b(n_reduce, np.int64)
    ma = _b(m_avg, np.float32)
    ra = _b(r_avg, np.float32)
    tk = _b(think_ms, np.float32)
    sl = _b(slots, np.int64)

    # Per-candidate logical event budget — identical to the scalar path's
    # events_needed + pow2 bucketing, so padded runs reproduce scalar runs.
    n_ev = np.empty((C,), np.int64)
    for c in range(C):
        n_ev[c] = padded_event_budget(int(nm[c]), int(nr[c]),
                                      min_jobs=min_jobs,
                                      warmup_jobs=warmup_jobs)
    scan_len = int(n_ev.max())
    max_slots = _shapes.bucket_slots(int(sl.max()))

    # Pad the candidate axis to the lane grid (replicating the last
    # candidate) so sweeps of nearby widths share one compiled program —
    # vmap lanes are independent, so results for real candidates are
    # unchanged; padded lanes are dropped below.  With lane sharding the
    # grid becomes device-aware: `shards` equal shards, each a bucketed
    # shape, so the flat lane axis splits evenly across the mesh.
    shards = _partition.shard_count(C)
    C_single = _shapes.bucket_lanes(C)
    C_pad = _partition.bucket_lanes(C, shards)
    if C_pad > C:
        pad = lambda x: np.concatenate(
            [x, np.repeat(x[-1:], C_pad - C, axis=0)])
        nm, nr, ma, ra, tk, sl, n_ev = map(
            pad, (nm, nr, ma, ra, tk, sl, n_ev))

    R = replications
    seeds = seed + 1000 * np.tile(np.arange(R, dtype=np.int64), C_pad)
    # lane c*R + r runs replication r of every candidate: its seed-only
    # draw tables are built once per replication seed (and shard)
    _check_seed_period(seeds, R)
    rep = lambda x: np.repeat(x, R)

    if m_samples is not None:
        ms = jnp.asarray(np.asarray(m_samples, np.float32))
        rs = jnp.asarray(np.asarray(r_samples, np.float32))
        ma = np.zeros_like(ma)      # replay mode ignores the profile means
        ra = np.zeros_like(ra)
    else:
        ms = rs = None

    # Shard-induced lane padding = rounding past what the single-device
    # bucket would pad; pure grid rounding is whatever remains.
    shard_pad = max(C_pad - C_single, 0)
    bucket_pad = (C_pad - C) - shard_pad
    statics = dict(h_users=int(h_users), max_slots=max_slots,
                   n_events=scan_len, warmup_jobs=warmup_jobs)
    if impl == "pallas":
        statics["seed_period"] = R
    _count_dispatch(
        lanes=C_pad * R, padded_lanes=(C_pad - C) * R,
        events_total=scan_len * C_pad * R,
        events_useful=int(n_ev[:C].sum()) * R,
        bucket_padded_lanes=bucket_pad * R,
        bucket_padded_events=scan_len * bucket_pad * R,
        shard_padded_lanes=shard_pad * R,
        shard_padded_events=scan_len * shard_pad * R,
        devices=shards,
        draw_columns=shards * R if "seed_period" in statics else None,
        kind="mapreduce", impl=impl)
    lane_args = (
        jnp.asarray(rep(nm), jnp.int32), jnp.asarray(rep(nr), jnp.int32),
        jnp.asarray(rep(ma)), jnp.asarray(rep(ra)), jnp.asarray(rep(tk)),
        jnp.asarray(rep(sl), jnp.int32), jnp.asarray(seeds, jnp.int32),
        jnp.asarray(rep(n_ev), jnp.int32))
    with _obs_trace.span(f"kernel:{impl}", cat="kernel",
                         lanes=C_pad * R, candidates=C,
                         scan_len=scan_len, max_slots=max_slots,
                         replay=ms is not None, devices=shards,
                         shard_lanes=C_pad * R // shards):
        if shards > 1:
            mean, cnt = _partition.shard_call(
                inner_fn, lane_args, (ms, rs), shards=shards, **statics)
        else:
            mean, cnt = outer_fn(*lane_args, ms, rs, **statics)
    pending = PendingBatch(mean, cnt, C, R)
    return pending if defer else pending.resolve()
