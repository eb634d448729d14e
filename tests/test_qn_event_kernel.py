"""Bit-parity of the fused Pallas QN event-step kernel vs the lax.scan
oracle (interpret mode on CPU — the tier-1 contract of docs/kernels.md).

Every grid point asserts *bitwise* equality of the full
``response_time_batch`` pipeline under ``impl="jnp"`` vs ``impl="pallas"``:
the kernel hoists the oracle's RNG streams but must reproduce its
arithmetic exactly (including the FMA structure XLA gives loop bodies —
see kernels/qn_event/kernel.py).  Degenerate shapes ride along: all-padding
lanes (zero logical event budget), single-slot lanes, non-pow2 candidate
counts that force padded vmap lanes.
"""
import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import partition, qn_sim, shapes
from repro.kernels.qn_event import kernel as qn_kernel
from repro.kernels.qn_event import ops as qn_event_ops
from repro.kernels.qn_event import ref as qn_event_ref

try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False

BASE = dict(n_map=8, n_reduce=2, m_avg=40.0, r_avg=60.0, think_ms=1000.0)
FAST = dict(min_jobs=8, warmup_jobs=2, replications=2, seed=0)


def _pair(slots, h_users, **over):
    kw = {**BASE, **FAST, **over, "h_users": h_users, "slots": slots}
    return (qn_sim.response_time_batch(impl="jnp", **kw),
            qn_sim.response_time_batch(impl="pallas", **kw))


# slot lists chosen to exercise: single candidate, non-pow2 counts (3 -> 4
# and 5 -> 8 lanes of vmap padding), single-slot lanes, wide slot spread
SLOT_GRIDS = [[1], [4], [2, 3, 5], [1, 2, 3, 4, 6, 9, 17], [8, 8, 8]]


@pytest.mark.parametrize("h_users", [1, 3, 8])
@pytest.mark.parametrize("slots", SLOT_GRIDS)
def test_parity_slots_h_users(slots, h_users):
    a, b = _pair(slots, h_users)
    assert np.array_equal(a, b), (a, b)


@pytest.mark.parametrize("min_jobs,warmup_jobs", [(6, 0), (12, 4), (20, 8)])
def test_parity_event_budgets(min_jobs, warmup_jobs):
    a, b = _pair([2, 5, 11], 4, min_jobs=min_jobs, warmup_jobs=warmup_jobs)
    assert np.array_equal(a, b)


@pytest.mark.parametrize("n_map,n_reduce", [(1, 1), (3, 0), (16, 4)])
def test_parity_task_counts(n_map, n_reduce):
    a, b = _pair([3, 7], 2, n_map=n_map, n_reduce=n_reduce)
    assert np.array_equal(a, b)


REPLAY = dict(m_samples=[30.0, 45.0, 55.0, 38.0, 61.0],
              r_samples=[80.0, 95.0, 70.0])


# the pallas path builds the replay tables once per replication seed and
# broadcasts them to the candidates; 3 and 5 candidates pad the lane axis
@pytest.mark.parametrize("replications", [1, 2, 3])
@pytest.mark.parametrize("slots", [[3, 6, 12], [2, 4, 5, 9, 13]])
def test_parity_replay_mode(slots, replications):
    a, b = _pair(slots, 2, replications=replications, **REPLAY)
    assert np.array_equal(a, b)


def test_parity_across_seeds_and_replications():
    for seed in (0, 7, 123):
        a, b = _pair([2, 9], 3, seed=seed, replications=3)
        assert np.array_equal(a, b), seed


def _direct_args(budgets, slots, seed=0, seed_period=None, samples=None):
    """Hand-built fused-batch arguments with per-lane budgets (including
    zero = pure-padding lanes); every lane has its own seed, or lane ``l``
    the seed of lane ``l % seed_period``."""
    B = len(budgets)
    n_events = max(budgets)
    full = lambda v, dt: jnp.full((B,), v, dt)
    lane = np.arange(B) if seed_period is None \
        else np.arange(B) % seed_period
    shared = (None, None) if samples is None else \
        tuple(jnp.asarray(samples[k], jnp.float32)
              for k in ("m_samples", "r_samples"))
    args = (full(BASE["n_map"], jnp.int32), full(BASE["n_reduce"], jnp.int32),
            full(BASE["m_avg"], jnp.float32), full(BASE["r_avg"], jnp.float32),
            full(BASE["think_ms"], jnp.float32),
            jnp.asarray(slots, jnp.int32),
            jnp.asarray(seed + 1000 * lane, jnp.int32),
            jnp.asarray(budgets, jnp.int32)) + shared
    statics = dict(h_users=3, max_slots=int(max(slots)),
                   n_events=n_events, warmup_jobs=2)
    return args, statics


def test_direct_sim_batch_bitwise_with_zero_budget_lanes():
    """ops.sim_batch vs the scan oracle on a raw fused batch whose lanes
    carry distinct logical budgets — including all-padding (0) lanes."""
    budget = qn_sim.padded_event_budget(BASE["n_map"], BASE["n_reduce"],
                                        min_jobs=8, warmup_jobs=2)
    budgets = [0, budget, budget // 2, budget, 0, budget // 4]
    slots = [1, 3, 5, 2, 4, 1]
    args, statics = _direct_args(budgets, slots)
    mean_k, cnt_k = qn_event_ops.sim_batch(*args, **statics)
    mean_o, cnt_o = qn_event_ref.sim_batch(*args, **statics)
    assert np.array_equal(np.asarray(cnt_k), np.asarray(cnt_o))
    assert np.array_equal(np.asarray(mean_k), np.asarray(mean_o))
    assert float(cnt_k[0]) == 0.0 and float(cnt_k[4]) == 0.0


def test_streamed_event_blocks_and_lane_blocks_bitwise(monkeypatch):
    """Small tiles force several lane blocks and several streamed event
    blocks, each axis with a padded tail: the lane state carried in
    scratch across event blocks must reproduce the oracle's one scan."""
    monkeypatch.setattr(qn_kernel, "LANE_TILE", 8)
    monkeypatch.setattr(qn_kernel, "EVENT_CHUNK", 24)
    budget = qn_sim.padded_event_budget(BASE["n_map"], BASE["n_reduce"],
                                        min_jobs=8, warmup_jobs=2)
    assert budget % 24 and budget > 3 * 24
    budgets = [budget, budget // 3, 0, budget // 2, budget] * 4 \
        + [budget // 5]                          # 21 lanes: 3 blocks of 8
    slots = [1 + i % 5 for i in range(len(budgets))]
    args, statics = _direct_args(budgets, slots)
    fwd = jax.jit(functools.partial(qn_kernel.qn_event_fwd, **statics,
                                    interpret=True))
    mean_k, cnt_k = fwd(*args)
    mean_o, cnt_o = qn_event_ref.sim_batch(*args, **statics)
    assert np.array_equal(np.asarray(cnt_k), np.asarray(cnt_o))
    assert np.array_equal(np.asarray(mean_k), np.asarray(mean_o))
    assert float(cnt_k[2]) == 0.0 and float(cnt_k[0]) > 0.0


@pytest.mark.parametrize("samples", [None, REPLAY], ids=["exp", "replay"])
@pytest.mark.parametrize("seed_period", [2, 3])
def test_seed_period_tables_bitwise_equal_per_lane_tables(
        monkeypatch, seed_period, samples):
    """Tables built once per seed and broadcast to the lanes give the same
    output as tables built per lane, and as the oracle — across several
    lane blocks and streamed event blocks, each with a padded tail."""
    monkeypatch.setattr(qn_kernel, "LANE_TILE", 8)
    monkeypatch.setattr(qn_kernel, "EVENT_CHUNK", 24)
    budget = qn_sim.padded_event_budget(BASE["n_map"], BASE["n_reduce"],
                                        min_jobs=8, warmup_jobs=2)
    budgets = [budget, budget // 3, 0, budget // 2, budget, budget // 5] * 3
    slots = [1 + i % 5 for i in range(len(budgets))]       # 18 lanes
    args, statics = _direct_args(budgets, slots, seed=11,
                                 seed_period=seed_period, samples=samples)
    fwd = functools.partial(qn_kernel.qn_event_fwd, **statics,
                            interpret=True)
    mean_p, cnt_p = jax.jit(functools.partial(
        fwd, seed_period=seed_period))(*args)
    mean_l, cnt_l = jax.jit(fwd)(*args)
    mean_o, cnt_o = qn_event_ref.sim_batch(*args, **statics)
    assert np.array_equal(np.asarray(cnt_p), np.asarray(cnt_l))
    assert np.array_equal(np.asarray(mean_p), np.asarray(mean_l))
    assert np.array_equal(np.asarray(mean_p), np.asarray(mean_o))
    assert float(cnt_p[0]) > 0.0 and float(cnt_p[2]) == 0.0


@pytest.mark.parametrize("seeds,period", [
    ([0, 1000, 0, 1001], 2),       # a later lane breaks the period
    ([0, 1000, 0], 2),             # not a whole number of periods
    ([5, 5, 5, 6], 1),
])
def test_seed_period_check_raises_on_a_broken_period(seeds, period):
    with pytest.raises(ValueError, match="period"):
        qn_sim._check_seed_period(np.asarray(seeds), period)
    qn_sim._check_seed_period(np.tile(np.asarray(seeds[:period]), 3),
                              period)


def test_kernel_refuses_lanes_that_are_not_whole_seed_periods():
    args, statics = _direct_args([16] * 5, [2] * 5)
    with pytest.raises(ValueError, match="seed periods of 2"):
        qn_kernel.qn_event_fwd(*args, **statics, interpret=True,
                               seed_period=2)


@pytest.mark.parametrize("replications", [1, 2, 3])
def test_draw_columns_count_one_per_seed_with_the_period(replications):
    """A batched dispatch builds its seed-only tables once per replication
    seed; a scalar dispatch (no period) once per lane."""
    old = partition.shard_spec()
    partition.set_shard_spec("off")
    try:
        s0 = qn_sim.sim_stats()
        kw = {**BASE, **FAST, "replications": replications, "h_users": 2}
        qn_sim.response_time_batch(**kw, slots=[2, 3, 5, 7, 9],
                                   impl="pallas", **REPLAY)
        s1 = qn_sim.sim_stats()
        qn_sim.response_time(**kw, slots=3)
        s2 = qn_sim.sim_stats()
    finally:
        partition.set_shard_spec(old)
    assert s1["dispatches"] - s0["dispatches"] == 1
    assert s1["draw_columns"] - s0["draw_columns"] == replications
    assert s1["lanes"] - s0["lanes"] \
        == shapes.bucket_lanes(5) * replications
    assert s2["dispatches"] - s1["dispatches"] == replications
    assert s2["draw_columns"] - s1["draw_columns"] \
        == s2["lanes"] - s1["lanes"] == replications


def test_single_slot_single_user_degenerate():
    a, b = _pair([1], 1, min_jobs=6, warmup_jobs=0)
    assert np.array_equal(a, b)
    assert np.isfinite(a).all()


def test_impl_switch_default():
    old = qn_sim.default_impl()
    try:
        qn_sim.set_default_impl("pallas")
        assert qn_sim.default_impl() == "pallas"
        kw = {**BASE, **FAST, "h_users": 2, "slots": [2, 3]}
        a = qn_sim.response_time_batch(**kw)           # default = pallas
        b = qn_sim.response_time_batch(impl="jnp", **kw)
        assert np.array_equal(a, b)
    finally:
        qn_sim.set_default_impl(old)
    with pytest.raises(ValueError):
        qn_sim.set_default_impl("cuda")
    with pytest.raises(ValueError):
        qn_sim.response_time_batch(impl="nope",
                                   **{**BASE, **FAST, "h_users": 1,
                                      "slots": [1]})


if HAVE_HYPOTHESIS:
    @settings(max_examples=10, deadline=None)
    @given(h_users=st.integers(1, 6), n_map=st.integers(1, 12),
           n_reduce=st.integers(0, 4), seed=st.integers(0, 1 << 16),
           slots=st.lists(st.integers(1, 9), min_size=1, max_size=5))
    def test_parity_property(h_users, n_map, n_reduce, seed, slots):
        a, b = _pair(slots, h_users, n_map=n_map, n_reduce=n_reduce,
                     seed=seed, min_jobs=6, warmup_jobs=1, replications=1)
        assert np.array_equal(a, b)
