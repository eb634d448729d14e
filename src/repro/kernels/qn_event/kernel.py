"""Batched QN event-step as a Pallas kernel (the repo's hottest loop).

``qn_sim`` simulates the paper's closed fork-join queueing network with an
event-driven ``lax.scan``: every optimizer axis — catalog racing, dual-price
coordination, 24-window day plans — multiplies calls into that scan, so the
per-event step (slot selection + clock advance + accumulator update) is the
single biggest raw-speed lever in the repo (ROADMAP item 2).

This kernel fuses the whole event loop for a *block of lanes* (lane =
candidate x replication) into one Pallas program: the per-lane state
(slot clocks, user phases, accumulators) lives in VMEM across all
``n_events`` steps — no HBM round trips between events — and every step's
masked selection runs vectorized across the lane block.

TPU layout
----------
Lanes sit on the 128-wide minor axis.  A lane block is ``LANE_TILE`` lanes
(or the whole batch when it is narrower), and every per-lane state array
is ``(rows, lanes)``: ``(max_slots, L)`` slot clocks, ``(H, L)`` user
state, ``(1, L)`` per-lane scalars.  Every selection is a reduction over
the row axis: a min over f32 plus an iota select, because the TPU
compiler has no arg-reduction over integers or booleans.

The draw tables are ``(n_events, lanes)``.  At the paper's largest event
budgets (524,288 events per lane) they cannot sit whole in VMEM, so a
second, sequential grid axis streams them through in ``EVENT_CHUNK``-event
blocks.  The lane state is carried in VMEM scratch across those blocks:
initialised at the first, written out after the last.  Inside a block,
event ``j`` reads its draw row by ref indexing (``ref[pl.ds(j, 1), :]``).

Bit-parity strategy
-------------------
The ``lax.scan`` path (``qn_sim._sim_batch_jit``) is the ORACLE and the
kernel must match it bit for bit in interpret mode.  Two observations make
that tractable:

  * Every random draw of the oracle is a pure function of ``(key, i)`` —
    the event index — never of simulation state (the *mean* is selected by
    state, the unit-exponential draw is not).  So the streams (unit
    service/think exponentials, or replay samples) are precomputed
    OUTSIDE the kernel with exactly the oracle's calls (``fold_in``/
    ``exponential``/``randint`` in the same order, same fold offsets) and
    passed in as ``(n_events, lanes)`` tables; the kernel itself is
    RNG-free.  Replay samples are copied out of the lists at the oracle's
    indices by a second, small kernel (``replay_lookup``), never
    computed.  The service tables depend on the lane's seed alone, so
    lanes that share a seed share them: with a ``seed_period`` they are
    built once per seed and broadcast to the lanes (``qn_event_fwd``).
  * The draw-consuming arithmetic (``now + e*mean``, ``t_slot +
    e*think``) keeps the oracle's exact op structure IN-KERNEL — XLA
    contracts ``add(x, mul(a, b))`` chains into FMAs inside loop bodies,
    so hoisting the multiply out of the loop would round differently by
    1 ulp.  Everything else in the step is f32 adds/compares/min/max/
    where — nothing else contractible — so the elementwise translation of
    the oracle step (scalar-per-lane -> lane-vectorized) is bitwise exact.

State updates use gather-free one-hot ``where`` masks (the oracle's
``.at[u].set`` on a scalar lane places exactly one element, the one-hot
mask places the same element with the same value).

Degenerate lanes are honored exactly like the oracle: a pure-padding lane
(``n_events_active == 0``) never steps and reports ``resp_cnt == 0``; a
single-slot lane serializes through ``slot_enabled``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# plain Python float (not a jnp constant: Pallas kernels may not capture
# array constants); weak-typed to the oracle's exact f32 1e30 in every op
INF = 1e30
LANE_TILE = 128         # lanes per grid block: the TPU's minor tile
EVENT_CHUNK = 1024      # events per block of the streamed draw tables
LOOKUP_ROWS = 256       # 128-wide index rows per block of the replay lookup


# ---------------------------------------------------------------------------
# RNG streams — bit-identical to the oracle's in-scan draws
# ---------------------------------------------------------------------------

def seed_streams(seed, *, h_users: int, n_events: int, list_lens=None):
    """One seed's draws: unit initial think clocks ``(H,)`` plus the
    per-event service draws ``(E,)``.  They depend on the seed alone, never
    on a lane's budget, slots or state, so lanes that share a seed share
    these tables.

    Must mirror ``qn_sim._init_state`` / ``qn_sim._rng_tables`` exactly:
      * init:     ``k0, _ = split(key);  exponential(k0, (H,))`` — the
        oracle's ``* think_ms`` is applied per lane by the caller (outside
        the oracle's scan, so the multiply is safe out there);
      * event i:  ``key_i = fold_in(key, i)`` drives ONE unit exponential
        — returned UNSCALED (the ``e * mean`` multiply must stay in-kernel
        next to its consuming add, see module docstring) — or, in replay
        mode (``list_lens`` given), the oracle's ``randint(key_i, (), 0,
        n)`` index into a sample list of each length ``n`` in
        ``list_lens``: a tuple of ``(E,)`` int32 tables, whose entries
        ``replay_lookup`` copies out of the lists.  The oracle draws the
        map and the reduce index with the same key, so lists of equal
        length share one index table.
    """
    key = jax.random.key(seed)
    k0, _ = jax.random.split(key)
    think0 = jax.random.exponential(k0, (h_users,))

    def service(i):
        key_i = jax.random.fold_in(key, i)
        if list_lens is not None:
            return tuple(jax.random.randint(key_i, (), 0, n)
                         for n in list_lens)
        return jax.random.exponential(key_i)

    return think0, jax.vmap(service)(jnp.arange(n_events))


def _copy_entries(idx, rows_ref):
    """``list[idx]`` for a list held as ``(rows, 128)`` lane rows: for each
    row, an in-vreg lane gather of every index's low 7 bits, kept where
    the high bits name that row.  Each value is a copy of a list entry."""
    hi, lo = idx >> 7, idx & 127

    def row(h, out):
        vals = jnp.broadcast_to(rows_ref[pl.ds(h, 1), :], idx.shape)
        got = jnp.take_along_axis(vals, lo, axis=1,
                                  mode="promise_in_bounds")
        return jnp.where(hi == h, got, out)

    return jax.lax.fori_loop(0, rows_ref.shape[0], row,
                             jnp.zeros(idx.shape, rows_ref.dtype))


def _lookup_kernel(*refs):
    *idx_refs, m_ref, r_ref, stm_ref, str_ref = refs
    stm_ref[...] = _copy_entries(idx_refs[0][...], m_ref)
    str_ref[...] = _copy_entries(idx_refs[-1][...], r_ref)


def replay_lookup(idx, m_samples, r_samples, *, interpret: bool):
    """``(m_samples[idx_m], r_samples[idx_r])``, each shaped like the index
    tables, as a Pallas kernel (``qn_replay_lookup``) that holds both lists
    in VMEM.  ``idx`` is ``(idx_m, idx_r)``, or ``(idx,)`` when both lists
    share it.  The tables are flattened into 128-wide rows and padded with
    index 0 to whole blocks of ``LOOKUP_ROWS`` rows."""
    shape, n = idx[0].shape, idx[0].size
    block = LOOKUP_ROWS * 128
    rows = [jnp.pad(i.reshape(-1), (0, -n % block)).reshape(-1, 128)
            for i in idx]
    lists = [jnp.pad(s, (0, -s.shape[0] % 128)).reshape(-1, 128)
             for s in (m_samples, r_samples)]
    grid = rows[0].shape[0] // LOOKUP_ROWS
    blocks = pl.BlockSpec((LOOKUP_ROWS, 128), lambda b: (b, 0))
    whole = [pl.BlockSpec(x.shape, lambda b: (0, 0)) for x in lists]
    out = pl.pallas_call(
        _lookup_kernel,
        grid=(grid,),
        in_specs=[blocks] * len(rows) + whole,
        out_specs=[blocks, blocks],
        out_shape=[jax.ShapeDtypeStruct(rows[0].shape, jnp.float32)] * 2,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
        name="qn_replay_lookup",
    )(*rows, *lists)
    return tuple(t.reshape(-1)[:n].reshape(shape) for t in out)


def think_stream(seed, n_events_active, *, n_events: int):
    """One lane's unit think redraws ``(E,)``: ``kq = fold_in(key, i +
    n_events_active)`` (the logical budget is the fold offset — that is
    what makes a padded lane reproduce its scalar run), so this table stays
    per lane."""
    key = jax.random.key(seed)

    def think(i):
        kq = jax.random.fold_in(key, i + n_events_active)
        return jax.random.exponential(kq)

    return jax.vmap(think)(jnp.arange(n_events))


def _tile_lanes(table, lanes: int):
    """``(rows, P)`` -> ``(rows, lanes)``, column ``l`` holding column
    ``l % P``: a broadcast and a reshape, so the expansion stays a memory
    pass (no gather)."""
    rows, period = table.shape
    return jnp.broadcast_to(table[:, None, :],
                            (rows, lanes // period, period)) \
        .reshape(rows, lanes)


# ---------------------------------------------------------------------------
# kernel body
# ---------------------------------------------------------------------------

def _iota(shape):
    return jax.lax.broadcasted_iota(jnp.int32, shape, 0)


def _first_index(mask):
    """Row of the first True per lane, as f32 ``(1, L)``; ``rows`` where a
    lane has none."""
    rows = mask.shape[0]
    io = _iota(mask.shape).astype(jnp.float32)
    return jnp.min(jnp.where(mask, io, float(rows)), axis=0, keepdims=True)


def _argmin(vals):
    """``jnp.argmin(vals, axis=0)`` per lane — the first row holding the
    minimum — as a min plus an iota select."""
    low = jnp.min(vals, axis=0, keepdims=True)
    return _first_index(vals == low).astype(jnp.int32)


def _pick(vals, idx):
    """``vals[idx[l], l]`` per lane, gather-free (one-hot mask + max).
    Exact: one element survives, the rest are the dtype's lowest value."""
    info = jnp.finfo if jnp.issubdtype(vals.dtype, jnp.floating) \
        else jnp.iinfo
    low = info(vals.dtype).min
    return jnp.max(jnp.where(_iota(vals.shape) == idx, vals, low),
                   axis=0, keepdims=True)


def _place(vals, idx, new):
    """``vals.at[idx[l], l].set(new[l])`` per lane via one-hot ``where``."""
    return jnp.where(_iota(vals.shape) == idx, new, vals)


def _state_layout(lanes: int, max_slots: int, h_users: int):
    """``(shape, dtype, initial fill)`` of each carried state array, in
    carry order; the ``None`` fill is the initial think clocks."""
    f32, i32 = jnp.float32, jnp.int32
    one, slots, users = (1, lanes), (max_slots, lanes), (h_users, lanes)
    return ((one, f32, 0.0),            # now
            (slots, f32, INF),          # slot_end
            (slots, i32, -1),           # slot_user
            (users, f32, None),         # think_end
            (users, i32, 0),            # phase: 0 think, 1 map, 2 reduce
            (users, i32, 0),            # pending
            (users, i32, 0),            # inflight
            (users, f32, INF),          # arrival
            (users, f32, 0.0),          # job_start
            (one, f32, 0.0),            # resp_sum
            (one, f32, 0.0),            # resp_cnt
            (one, i32, 0))              # done_jobs


def _event_kernel(ip_ref, fp_ref, think0_ref, stm_ref, str_ref, td_ref,
                  out_ref, *state_refs, layout, max_slots: int, chunk: int,
                  warmup_jobs: int, replay: bool):
    c = pl.program_id(1)                      # event block (sequential)
    L = ip_ref.shape[1]
    nm, nr, cap, nea = (ip_ref[k:k + 1, :] for k in range(4))
    ma, ra, tm = (fp_ref[k:k + 1, :] for k in range(3))
    slot_enabled = _iota((max_slots, L)) < cap

    @pl.when(c == 0)
    def _init():
        for ref, (shape, dt, fill) in zip(state_refs, layout):
            ref[...] = think0_ref[...] if fill is None \
                else jnp.full(shape, fill, dt)

    def step(j, s):
        (now, slot_end, slot_user, think_end, phase, pending, inflight,
         arrival, job_start, resp_sum, resp_cnt, done_jobs) = s
        i = c * chunk + j                     # global event index
        free_idx = _first_index((slot_user < 0) & slot_enabled)
        any_pending = jnp.max(pending, axis=0, keepdims=True) > 0
        b_dispatch = (free_idx < max_slots) & any_pending

        # ------------- dispatch one task (reduce priority, FIFO) ----------
        red_key = jnp.where((pending > 0) & (phase == 2), arrival, INF)
        map_key = jnp.where((pending > 0) & (phase == 1), arrival, INF)
        has_red = jnp.min(red_key, axis=0, keepdims=True) < INF
        u = jnp.where(has_red, _argmin(red_key), _argmin(map_key))
        stm_i = stm_ref[pl.ds(j, 1), :]
        str_i = str_ref[pl.ds(j, 1), :]
        if replay:
            st = jnp.where(_pick(phase, u) == 1, stm_i, str_i)
        else:
            # mirror the oracle's op order (select mean, then multiply the
            # unit draw IN the loop body — FMA-contraction parity)
            mean = jnp.where(_pick(phase, u) == 1, ma, ra)
            st = stm_i * mean
        # first free slot; slot 0 when none is free (jnp.argmax's answer)
        slot = jnp.where(free_idx < max_slots, free_idx, 0.0) \
            .astype(jnp.int32)
        d_slot_end = _place(slot_end, slot, now + st)
        d_slot_user = _place(slot_user, slot, u)
        d_pending = _place(pending, u, _pick(pending, u) - 1)
        d_inflight = _place(inflight, u, _pick(inflight, u) + 1)

        # ------------- or advance time ------------------------------------
        t_slot = jnp.min(slot_end, axis=0, keepdims=True)
        t_think = jnp.min(think_end, axis=0, keepdims=True)
        b_complete = (~b_dispatch) & (t_slot <= t_think) & (t_slot < INF)
        b_think = (~b_dispatch) & (~b_complete) & (t_think < INF)
        active = i < nea                       # padded tail: no-op steps
        b_dispatch &= active
        b_complete &= active
        b_think &= active

        # completion
        cslot = _argmin(slot_end)
        cu = _pick(slot_user, cslot)
        infl_cu = _pick(inflight, cu) - 1
        stage_done = (_pick(pending, cu) == 0) & (infl_cu == 0)
        was_map = _pick(phase, cu) == 1
        c_inflight = _place(inflight, cu, infl_cu)
        c_phase = _place(phase, cu, jnp.where(
            stage_done, jnp.where(was_map, 2, 0), _pick(phase, cu)))
        c_pending = _place(pending, cu, jnp.where(
            stage_done & was_map, nr, _pick(pending, cu)))
        job_done = stage_done & (~was_map)
        arr_cu = jnp.where(stage_done & was_map, t_slot,
                           _pick(arrival, cu))
        c_arrival = _place(arrival, cu, jnp.where(job_done, INF, arr_cu))
        resp = t_slot - _pick(job_start, cu)
        td_i = td_ref[pl.ds(j, 1), :]
        new_think = t_slot + td_i * tm        # oracle: t_slot + e*think_ms
        c_think = _place(think_end, cu, jnp.where(
            job_done, new_think, _pick(think_end, cu)))
        counted = job_done & (done_jobs >= warmup_jobs)
        c_resp_sum = resp_sum + jnp.where(counted, resp, 0.0)
        c_resp_cnt = resp_cnt + jnp.where(counted, 1.0, 0.0)
        c_done = done_jobs + jnp.where(job_done, 1, 0)
        c_slot_end = _place(slot_end, cslot, INF)
        c_slot_user = _place(slot_user, cslot, -1)

        # think end -> submit job (fork maps)
        tu = _argmin(think_end)
        t_phase = _place(phase, tu, 1)
        t_pending = _place(pending, tu, nm)
        t_arrival = _place(arrival, tu, t_think)
        t_jobstart = _place(job_start, tu, t_think)
        t_think_end = _place(think_end, tu, INF)

        def sel(cur, on_dispatch, on_complete, on_think):
            return jnp.where(b_dispatch, on_dispatch, jnp.where(
                b_complete, on_complete, jnp.where(b_think, on_think, cur)))

        return (sel(now, now, t_slot, t_think),
                sel(slot_end, d_slot_end, c_slot_end, slot_end),
                sel(slot_user, d_slot_user, c_slot_user, slot_user),
                sel(think_end, think_end, c_think, t_think_end),
                sel(phase, phase, c_phase, t_phase),
                sel(pending, d_pending, c_pending, t_pending),
                sel(inflight, d_inflight, c_inflight, inflight),
                sel(arrival, arrival, c_arrival, t_arrival),
                sel(job_start, job_start, job_start, t_jobstart),
                sel(resp_sum, resp_sum, c_resp_sum, resp_sum),
                sel(resp_cnt, resp_cnt, c_resp_cnt, resp_cnt),
                sel(done_jobs, done_jobs, c_done, done_jobs))

    out = jax.lax.fori_loop(0, chunk, step,
                            tuple(ref[...] for ref in state_refs))
    for ref, val in zip(state_refs, out):
        ref[...] = val

    @pl.when(c == pl.num_programs(1) - 1)
    def _emit():
        out_ref[0:1, :] = out[9]
        out_ref[1:2, :] = out[10]


# ---------------------------------------------------------------------------
# host-side wrapper
# ---------------------------------------------------------------------------

def qn_event_fwd(n_map, n_reduce, m_avg, r_avg, think_ms, slots_cap, seed,
                 n_events_active, m_samples=None, r_samples=None, *,
                 h_users: int, max_slots: int, n_events: int,
                 warmup_jobs: int, interpret: bool, seed_period: int = None):
    """Drop-in for ``qn_sim._sim_batch_jit``: all per-lane parameters are
    ``(B,)`` arrays, replay sample lists (when given) are shared across the
    batch.  Returns ``(mean_resp, resp_cnt)`` per lane, bit-identical (in
    interpret mode) to the ``lax.scan`` oracle.

    ``seed_period``: the caller's guarantee that lane ``l`` carries the
    seed of lane ``l % seed_period`` (``qn_sim.response_time_batch`` gives
    lane ``c*R + r`` the seed ``seed + 1000*r`` and checks it).  The
    seed-only tables (``seed_streams``) are then built for the first
    ``seed_period`` lanes and broadcast to the rest: the same values for
    ``seed_period / B`` of the draws.  ``None`` builds them per lane (a
    period of ``B``, for which the broadcast is the identity)."""
    B = n_map.shape[0]
    if seed_period is not None and B % seed_period:
        raise ValueError(f"{B} lanes are not a whole number of seed "
                         f"periods of {seed_period}")
    L = min(B, LANE_TILE)
    lane_pad = (-B) % L
    chunk = min(EVENT_CHUNK, -(-n_events // 8) * 8)
    event_pad = (-n_events) % chunk

    replay = m_samples is not None
    # one index table per distinct list length (static shapes)
    list_lens = tuple(dict.fromkeys(
        (m_samples.shape[0], r_samples.shape[0]))) if replay else None
    streams = functools.partial(seed_streams, h_users=h_users,
                                n_events=n_events, list_lens=list_lens)
    # the draw tables are built outside the event kernel; the scope names
    # their device ops apart from the kernel's in a profile
    with jax.named_scope("qn_event_draws"):
        P = B if seed_period is None else seed_period
        think0, draws = jax.vmap(streams)(seed[:P])
        st_m, st_r = replay_lookup(draws, m_samples, r_samples,
                                   interpret=interpret) if replay \
            else (draws, draws)
        think0, st_m, st_r = (_tile_lanes(t.T, B)
                              for t in (think0, st_m, st_r))
        think0 = think0 * think_ms       # the oracle's init multiply
        td = jax.vmap(functools.partial(think_stream, n_events=n_events),
                      out_axes=1)(seed, n_events_active)

    ip = jnp.stack([n_map, n_reduce, slots_cap, n_events_active]) \
        .astype(jnp.int32)
    fp = jnp.stack([m_avg, r_avg, think_ms]).astype(jnp.float32)
    if lane_pad:
        # pure-padding lanes: zero active events -> untouched state,
        # resp_cnt == 0; dropped below.  slots_cap 1 keeps the slot mask
        # well-formed.
        ip, fp, think0 = (jnp.pad(x, ((0, 0), (0, lane_pad)))
                          for x in (ip, fp, think0))
        ip = ip.at[2, B:].set(1)
    if lane_pad or event_pad:
        # padded events lie past every lane's n_events_active: no-op steps
        st_m, st_r, td = (jnp.pad(x, ((0, event_pad), (0, lane_pad)))
                          for x in (st_m, st_r, td))

    layout = _state_layout(L, max_slots, h_users)
    kernel = functools.partial(
        _event_kernel, layout=layout, max_slots=max_slots, chunk=chunk,
        warmup_jobs=warmup_jobs, replay=replay)
    rows = lambda n: pl.BlockSpec((n, L), lambda b, c: (0, b))
    table = pl.BlockSpec((chunk, L), lambda b, c: (c, b))
    out = pl.pallas_call(
        kernel,
        grid=((B + lane_pad) // L, (n_events + event_pad) // chunk),
        in_specs=[rows(4), rows(3), rows(h_users), table, table, table],
        out_specs=rows(2),
        out_shape=jax.ShapeDtypeStruct((2, B + lane_pad), jnp.float32),
        scratch_shapes=[pltpu.VMEM(shape, dt) for shape, dt, _ in layout],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(ip, fp, think0, st_m, st_r, td)
    resp_sum, resp_cnt = out[0, :B], out[1, :B]
    return resp_sum / jnp.maximum(resp_cnt, 1.0), resp_cnt
