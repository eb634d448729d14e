"""Plain reference of a MapReduce class's QN point estimate and plan.

The semantics are the paper's closed fork-join queueing network (§3.1,
Figure 2) in JMT replayer mode, as the planner states them:

* ``h_users`` users alternate between thinking (exponential, mean
  ``think_ms``) and one job: ``n_map`` map tasks, then ``n_reduce`` reduce
  tasks, each taking one of ``slots`` containers while it runs;
* a free container takes a waiting reduce task before any map task, and
  among tasks of one kind the earliest stage arrival (lowest user index on
  ties); the next event is a dispatch whenever one is possible, else the
  earliest completion, else the earliest think end (completion first on a
  tie);
* service times replay the profiling run's task-duration lists;
* the estimate of one replication is the mean response of the jobs that
  finish after the first ``warmup_jobs``, over a fixed budget of events;
  the point estimate is the count-weighted mean over replications.

Random draws are part of the point's definition: replication ``r`` uses
the key ``seed + 1000 r``; event ``i`` draws its map and reduce list
indices with ``fold_in(key, i)``, and a think time that starts at event
``i`` draws ``fold_in(key, i + budget)``; the first think times come from
the first half of ``split(key)``.  The event budget is
``pow2(1.5 (2 (n_map + n_reduce) + 4) (min_jobs + warmup_jobs))``.

This module imports nothing of the planner.  It runs every lane as one
step per event in a ``lax.scan`` over ``(rows, lanes)`` arrays, in the
floating type it is given: float32 is the stated precision, bfloat16 the
control.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

BIG = 1e30                 # "never": an empty container, a user not waiting
REPLICATION_STRIDE = 1000  # replication r runs with key seed + 1000 r
# container counts that share a scan, so that a few large fleets do not
# widen every lane: up to 256, up to 2048, and beyond
ROW_CLASSES = (256, 2048, None)


def event_budget(n_map: int, n_reduce: int, min_jobs: int,
                 warmup_jobs: int) -> int:
    """Events one replication of a point simulates."""
    need = int(1.5 * (2 * (n_map + n_reduce) + 4) * (min_jobs + warmup_jobs))
    return 1 << max(need - 1, 0).bit_length()


def spot_mix_cost(nu: int, eta: float, sigma: float, pi: float) -> float:
    """Hourly cost of ``nu`` VMs with the cheapest admissible spot share
    (at most ``eta`` of the fleet when spot is the cheaper price)."""
    if nu <= 0:
        return 0.0
    spot = int(math.floor(eta * nu)) if sigma < pi else 0
    return sigma * spot + pi * (nu - spot)


def _first_thinks(seed, think_ms, h_users):
    """One lane's first think ends ``(H,)``."""
    key = jax.random.key(seed)
    return jax.random.exponential(jax.random.split(key)[0],
                                  (h_users,)) * think_ms


def _event_draws(seed, budget, n_m, n_r, i):
    """One lane's draws at event ``i``: map and reduce list indices and
    the unit think time."""
    key = jax.random.key(seed)
    k_i = jax.random.fold_in(key, i)
    return (jax.random.randint(k_i, (), 0, n_m),
            jax.random.randint(k_i, (), 0, n_r),
            jax.random.exponential(jax.random.fold_in(key, i + budget)))


@partial(jax.jit, static_argnames=("h_users", "rows", "n_events",
                                   "warmup_jobs", "dtype"))
def _simulate(n_map, n_reduce, slots, think_ms, seed, budget, m_lists,
              m_len, r_lists, r_len, *, h_users, rows, n_events, warmup_jobs,
              dtype):
    """Mean response and counted jobs of each lane, all lanes at once;
    lane ``l`` replays the first ``m_len[l]`` / ``r_len[l]`` durations of
    its rows of ``m_lists`` / ``r_lists``."""
    lanes = n_map.shape[0]
    first = jax.vmap(partial(_first_thinks, h_users=h_users))(seed, think_ms)
    m_lists = m_lists.astype(dtype)
    r_lists = r_lists.astype(dtype)
    draw = jax.vmap(_event_draws, in_axes=(0, 0, 0, 0, None))
    lane_ix = jnp.arange(lanes)
    zt = think_ms.astype(dtype)[None, :]
    big = jnp.asarray(BIG, dtype)
    slot_row = jnp.arange(rows)[:, None]
    user_row = jnp.arange(h_users)[:, None]
    usable = slot_row < slots[None, :]

    def lowest(mask, n):
        """Lowest row index where ``mask`` holds, per lane (``n`` if none)."""
        return jnp.min(jnp.where(mask, jnp.arange(n)[:, None], n), axis=0)

    def step(st, i):
        i_m, i_r, zd = draw(seed, budget, m_len, r_len, i)
        dm = m_lists[lane_ix, i_m]
        dr = r_lists[lane_ix, i_r]
        zd = zd.astype(dtype)
        (now, busy_until, owner, wake, stage, left, running, since, start,
         total, counted, finished) = st
        live = i < budget

        # -- can a task start? a free container and a waiting task
        free = (owner < 0) & usable
        c_free = lowest(free, rows)
        waiting = left > 0
        red_t = jnp.where(waiting & (stage == 2), since, big)
        map_t = jnp.where(waiting & (stage == 1), since, big)
        has_red = jnp.min(red_t, axis=0) < big
        key_t = jnp.where(has_red[None, :], red_t, map_t)
        u = lowest(key_t == jnp.min(key_t, axis=0)[None, :], h_users)
        start_task = (c_free < rows) & jnp.any(waiting, axis=0) & live
        is_u = user_row == u[None, :]
        u_stage = jnp.sum(jnp.where(is_u, stage, 0), axis=0)
        dur = jnp.where(u_stage == 1, dm, dr)

        # -- otherwise the earliest completion or think end
        t_done = jnp.min(busy_until, axis=0)
        t_wake = jnp.min(wake, axis=0)
        complete = (~start_task) & live & (t_done < big) & (t_done <= t_wake)
        wakes = (~start_task) & live & (~complete) & (t_wake < big)
        c_done = lowest(busy_until == t_done[None, :], rows)
        v = jnp.sum(jnp.where(slot_row == c_done[None, :], owner, 0), axis=0)
        v = jnp.where(complete, v, -1)
        w = lowest(wake == t_wake[None, :], h_users)
        w = jnp.where(wakes, w, -1)
        is_v = user_row == v[None, :]
        is_w = user_row == w[None, :]

        # completion of user v's task
        v_run = jnp.sum(jnp.where(is_v, running, 0), axis=0) - 1
        v_left = jnp.sum(jnp.where(is_v, left, 0), axis=0)
        v_stage = jnp.sum(jnp.where(is_v, stage, 0), axis=0)
        stage_over = complete & (v_left == 0) & (v_run == 0)
        maps_over = stage_over & (v_stage == 1)
        job_over = stage_over & (v_stage == 2)
        v_start = jnp.sum(jnp.where(is_v, start, 0), axis=0)
        keep = job_over & (finished >= warmup_jobs)

        c_start = jnp.where(start_task, c_free, -1)
        at_c = slot_row == c_start[None, :]
        at_done = slot_row == jnp.where(complete, c_done, -1)[None, :]
        busy_until = jnp.where(at_c, (now + dur)[None, :],
                               jnp.where(at_done, big, busy_until))
        owner = jnp.where(at_c, u[None, :], jnp.where(at_done, -1, owner))
        u_hit = is_u & start_task[None, :]
        left = jnp.where(u_hit, left - 1, left)
        running = jnp.where(u_hit, running + 1, running)
        running = jnp.where(is_v, running - 1, running)
        left = jnp.where(is_v & maps_over[None, :], n_reduce[None, :], left)
        stage = jnp.where(is_v & maps_over[None, :], 2, stage)
        stage = jnp.where(is_v & job_over[None, :], 0, stage)
        since = jnp.where(is_v & maps_over[None, :], t_done[None, :], since)
        since = jnp.where(is_v & job_over[None, :], big, since)
        wake = jnp.where(is_v & job_over[None, :],
                         (t_done + zd * zt[0])[None, :], wake)
        # think end of user w: the job arrives, its maps wait
        stage = jnp.where(is_w, 1, stage)
        left = jnp.where(is_w, n_map[None, :], left)
        since = jnp.where(is_w, t_wake[None, :], since)
        start = jnp.where(is_w, t_wake[None, :], start)
        wake = jnp.where(is_w, big, wake)

        now = jnp.where(complete, t_done, jnp.where(wakes, t_wake, now))
        total = total + jnp.where(keep, t_done - v_start, 0)
        counted = counted + jnp.where(keep, 1, 0).astype(dtype)
        finished = finished + jnp.where(job_over, 1, 0)
        return (now, busy_until, owner, wake, stage, left, running, since,
                start, total, counted, finished), None

    zero = jnp.zeros((lanes,), dtype)
    users = (h_users, lanes)
    st0 = (zero, jnp.full((rows, lanes), big), jnp.full((rows, lanes), -1),
           first.T.astype(dtype), jnp.zeros(users, jnp.int32),
           jnp.zeros(users, jnp.int32), jnp.zeros(users, jnp.int32),
           jnp.full(users, big), jnp.zeros(users, dtype), zero, zero,
           jnp.zeros((lanes,), jnp.int32))
    st, _ = jax.lax.scan(step, st0, jnp.arange(n_events))
    total, counted = st[9], st[10]
    return total / jnp.maximum(counted, 1), counted


def _bucket(n: int) -> int:
    """Powers of two and their midpoints, so nearby sizes share a program."""
    p = 1 << max(int(n) - 1, 0).bit_length()
    return 3 * p // 4 if 3 * p // 4 >= n else p


def point_estimates(points, *, min_jobs: int, warmup_jobs: int,
                    replications: int, seed: int = 0,
                    dtype=jnp.float32) -> np.ndarray:
    """Reference estimate of each point, in float64 on the host.

    A point is a dict with ``h_users``, ``think_ms``, ``n_map``,
    ``n_reduce``, ``slots`` (containers) and the replay lists ``m_list``
    and ``r_list``.  Points with the same user count and container class
    (``ROW_CLASSES``) run in one scan."""
    out = np.full((len(points),), np.inf)
    groups = {}
    for k, p in enumerate(points):
        size = next(r for r in ROW_CLASSES if r is None or r >= p["slots"])
        groups.setdefault((int(p["h_users"]), size or 0), []).append(k)
    for (h_users, _), ks in sorted(groups.items()):
        lanes = [(k, seed + REPLICATION_STRIDE * r) for k in ks
                 for r in range(replications)]
        pts = [points[k] for k, _ in lanes]
        n = _bucket(len(lanes))
        pad = [pts[-1]] * (n - len(lanes))
        pts = pts + pad
        seeds = [s for _, s in lanes] + [lanes[-1][1]] * len(pad)
        budget = [event_budget(p["n_map"], p["n_reduce"], min_jobs,
                               warmup_jobs) for p in pts]
        k_m = max(len(p["m_list"]) for p in pts)
        k_r = max(len(p["r_list"]) for p in pts)

        def lists(key, width):
            a = np.zeros((len(pts), width), np.float32)
            for i, p in enumerate(pts):
                a[i, :len(p[key])] = p[key]
            return jnp.asarray(a)

        i32 = partial(jnp.asarray, dtype=jnp.int32)
        mean, cnt = _simulate(
            i32([p["n_map"] for p in pts]), i32([p["n_reduce"] for p in pts]),
            i32([p["slots"] for p in pts]),
            jnp.asarray([p["think_ms"] for p in pts], jnp.float32),
            i32(seeds), i32(budget), lists("m_list", _bucket(k_m)),
            i32([len(p["m_list"]) for p in pts]),
            lists("r_list", _bucket(k_r)),
            i32([len(p["r_list"]) for p in pts]),
            h_users=h_users,
            rows=_bucket(max(8, max(p["slots"] for p in pts))),
            n_events=max(budget), warmup_jobs=int(warmup_jobs), dtype=dtype)
        mean, cnt = (np.asarray(x, np.float64).reshape(-1)[:len(lanes)]
                     .reshape(-1, replications)
                     for x in jax.device_get((mean, cnt)))
        for row, k in enumerate(ks):
            ok = cnt[row] > 0
            if ok.any():
                out[k] = float(np.sum(mean[row][ok] * cnt[row][ok])
                               / np.sum(cnt[row][ok]))
    return out
