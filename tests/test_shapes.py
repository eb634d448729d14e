"""Shape-bucketing invariants and the dispatch-plane perf-layer contracts:
grid properties of ``core/shapes``, bit-parity of bucketed batches against
exact-padded scalar runs for every simulator backend, and the
warm-path 0-compiles regression (``qn.compiles``)."""
import numpy as np
import pytest

from repro.core import dag as dag_mod
from repro.core import qn_sim
from repro.core import shapes
from repro.core.workload import DagJob, Stage
from repro.obs import compile as obs_compile


@pytest.fixture
def restore_grid():
    g = shapes.default_grid()
    yield
    shapes.set_default_grid(g)


# ----------------------------------------------------------- grid properties
def test_bucket_properties_exhaustive():
    for grid in shapes.GRIDS:
        prev = 0
        for n in range(1, 4097):
            b = shapes.bucket(n, grid=grid)
            assert b >= n                              # never truncates
            assert b >= prev                           # monotone
            assert shapes.bucket(b, grid=grid) == b    # idempotent
            prev = b
    for n in range(1, 4097):
        assert shapes.bucket(n, grid="pow2") == shapes.pow2(n)


def test_geo_grid_is_pow2_plus_midpoints():
    pts = sorted({shapes.bucket(n, grid="geo") for n in range(1, 2049)})
    for p in pts:
        assert p == shapes.pow2(p) or (p % 3 == 0
                                       and shapes.pow2(p // 3) == p // 3)
    # worst-case padding waste on geo is 1.5x (vs 2x for pow2)
    assert max(shapes.bucket(n, grid="geo") / n for n in range(1, 4097)) <= 1.5


def test_bucket_events_pinned_pow2(restore_grid):
    # logical event budgets are RNG fold offsets: the grid must not move
    # with the default, or simulated values would change
    for g in shapes.GRIDS:
        shapes.set_default_grid(g)
        for n in (5, 100, 1500, 4096):
            assert shapes.bucket_events(n) == shapes.pow2(n)


def test_hypothesis_bucket_properties():
    pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    @given(n=st.integers(1, 10**9), m=st.integers(1, 10**9),
           grid=st.sampled_from(shapes.GRIDS))
    @settings(max_examples=300, deadline=None)
    def prop(n, m, grid):
        bn, bm = (shapes.bucket(x, grid=grid) for x in (n, m))
        assert bn >= n
        if n <= m:
            assert bn <= bm                            # monotone
        assert shapes.bucket(bn, grid=grid) == bn      # idempotent

    prop()


# ------------------------------------------------------ bit-parity: bucketed
# batch == exact-padded scalar runs (the parity contract bucketing must not
# bend), across both grids and every batch backend.
QN = dict(n_map=12, n_reduce=4, m_avg=900.0, r_avg=1200.0, think_ms=5000.0,
          h_users=3, min_jobs=6, warmup_jobs=2, replications=2, seed=7)


def _qn_scalar(slots):
    return qn_sim.response_time(
        slots=slots, **{k: v for k, v in QN.items()})


@pytest.mark.parametrize("impl", ["jnp", "pallas"])
def test_qn_batch_bucketed_parity(impl, restore_grid):
    slots = [6, 8, 10, 12, 14]          # C=5 -> geo bucket 6, pow2 bucket 8
    want = [_qn_scalar(s) for s in slots]
    for grid in shapes.GRIDS:
        shapes.set_default_grid(grid)
        got = qn_sim.response_time_batch(
            QN["n_map"], QN["n_reduce"], QN["m_avg"], QN["r_avg"],
            QN["think_ms"], QN["h_users"], np.asarray(slots),
            min_jobs=QN["min_jobs"], warmup_jobs=QN["warmup_jobs"],
            seed=QN["seed"], replications=QN["replications"], impl=impl)
        assert got.tolist() == want     # bit-identical, not approx


def test_qn_replay_batch_bucketed_parity(restore_grid):
    ms = [700.0, 900.0, 1100.0, 800.0]
    rs = [1000.0, 1400.0, 1200.0]
    slots = [6, 9, 12]
    want = [qn_sim.response_time(
        slots=s, m_samples=ms, r_samples=rs, **QN) for s in slots]
    for grid in shapes.GRIDS:
        shapes.set_default_grid(grid)
        got = qn_sim.response_time_batch(
            QN["n_map"], QN["n_reduce"], QN["m_avg"], QN["r_avg"],
            QN["think_ms"], QN["h_users"], np.asarray(slots),
            min_jobs=QN["min_jobs"], warmup_jobs=QN["warmup_jobs"],
            seed=QN["seed"], replications=QN["replications"],
            m_samples=ms, r_samples=rs)
        assert got.tolist() == want


def _chain(k, base=600.0):
    return DagJob(name=f"c{k}", stages=tuple(
        Stage(n_tasks=3 + i, t_avg=base + 100 * i, cv=0.4)
        for i in range(k)))


def test_dag_batch_bucketed_parity(restore_grid):
    jobs = [_chain(3), _chain(5), _chain(4)]   # K=5 -> geo 6, pow2 8
    kw = dict(think_ms=4000.0, slots=[6, 8, 10], h_users=3,
              min_jobs=5, warmup_jobs=2, seed=3, replications=2)
    want = [dag_mod.dag_response_time(
        j, slots=s, think_ms=4000.0, h_users=3, min_jobs=5,
        warmup_jobs=2, seed=3, replications=2)
        for j, s in zip(jobs, [6, 8, 10])]
    for grid in shapes.GRIDS:
        shapes.set_default_grid(grid)
        got = dag_mod.response_time_batch(jobs, **kw)
        assert got.tolist() == want


def test_amva_kernel_bucketed_parity():
    pytest.importorskip("jax")
    import jax.numpy as jnp
    from repro.kernels.amva import ops as amva_ops
    a = np.linspace(0.2, 2.0, 5).astype(np.float32)     # N=5 -> bucket 6
    b = np.full(5, 800.0, np.float32)
    think = np.full(5, 5000.0, np.float32)
    h = np.full(5, 4.0, np.float32)
    got = np.asarray(amva_ops.ps_fixed_point(
        jnp.asarray(a), jnp.asarray(b), jnp.asarray(think), jnp.asarray(h)))
    assert got.shape == (5,)
    # exact-width call (one lane at a time, N=1 buckets to 1) must agree
    singles = [float(np.asarray(amva_ops.ps_fixed_point(
        jnp.asarray(a[i:i + 1]), jnp.asarray(b[:1]),
        jnp.asarray(think[:1]), jnp.asarray(h[:1])))[0]) for i in range(5)]
    np.testing.assert_allclose(got, singles, rtol=1e-6)


# ----------------------------------------------------- deferred-resolution
def test_defer_returns_pending_and_matches_blocking():
    slots = [6, 8, 10]
    blocking = qn_sim.response_time_batch(
        QN["n_map"], QN["n_reduce"], QN["m_avg"], QN["r_avg"],
        QN["think_ms"], QN["h_users"], np.asarray(slots),
        min_jobs=QN["min_jobs"], warmup_jobs=QN["warmup_jobs"],
        seed=QN["seed"], replications=QN["replications"])
    pend = qn_sim.response_time_batch(
        QN["n_map"], QN["n_reduce"], QN["m_avg"], QN["r_avg"],
        QN["think_ms"], QN["h_users"], np.asarray(slots),
        min_jobs=QN["min_jobs"], warmup_jobs=QN["warmup_jobs"],
        seed=QN["seed"], replications=QN["replications"], defer=True)
    assert isinstance(pend, qn_sim.PendingBatch)
    (resolved,) = qn_sim.resolve_batches([pend])
    assert resolved.tolist() == blocking.tolist()
    assert pend.resolve().tolist() == blocking.tolist()   # memoized


# ------------------------------------------------------- padding accounting
def test_bucket_padding_counted_separately(restore_grid):
    shapes.set_default_grid("geo")
    qn_sim.reset_sim_stats()
    slots = [6, 8, 10, 12, 14]          # C=5 -> C_pad=6: 1 bucket lane
    qn_sim.response_time_batch(
        QN["n_map"], QN["n_reduce"], QN["m_avg"], QN["r_avg"],
        QN["think_ms"], QN["h_users"], np.asarray(slots),
        min_jobs=QN["min_jobs"], warmup_jobs=QN["warmup_jobs"],
        seed=QN["seed"], replications=QN["replications"])
    pad = qn_sim.padding_stats()
    R = QN["replications"]
    assert pad["bucket_padded_lanes"] == 1 * R
    assert pad["bucket_padded_events"] > 0
    assert pad["batch_padded_events"] >= 0
    s = qn_sim.sim_stats()
    assert (pad["bucket_padded_events"] + pad["batch_padded_events"]
            == s["events_total"] - s["events_useful"])


# --------------------------------------------------- warm path: 0 compiles
def test_warm_resubmission_zero_compiles():
    obs_compile.install()                    # listeners on (idempotent)

    def solve(slots):
        return qn_sim.response_time_batch(
            QN["n_map"], QN["n_reduce"], QN["m_avg"], QN["r_avg"],
            QN["think_ms"], QN["h_users"], np.asarray(slots),
            min_jobs=QN["min_jobs"], warmup_jobs=QN["warmup_jobs"],
            seed=QN["seed"], replications=QN["replications"])

    solve([6, 8, 10, 12, 14])                     # cold: compiles
    c0 = obs_compile.compile_stats()
    solve([6, 8, 10, 12, 14])                     # warm resubmission
    # a DIFFERENT width in the same bucket reuses the same executable:
    # C=5 and C=6 both land in the 6-lane bucket under the geo grid, and
    # max slots 14 and 16 both land in the 16-slot bucket
    if shapes.default_grid() == "geo":
        solve([7, 9, 11, 13, 15, 16])
    c1 = obs_compile.compile_stats()
    assert c1["compiles"] == c0["compiles"], \
        f"warm path recompiled: {c1['compiles'] - c0['compiles']}"


def test_persistent_cache_dir_is_the_env_or_the_checkout():
    import os

    import jax
    obs_compile.install()
    want = os.environ.get("JAX_COMPILATION_CACHE_DIR") \
        or obs_compile.DEFAULT_CACHE_DIR
    assert jax.config.jax_compilation_cache_dir == want
    assert obs_compile.DEFAULT_CACHE_DIR.endswith(os.sep + ".jax-cache")
    assert os.path.isdir(os.path.join(
        os.path.dirname(obs_compile.DEFAULT_CACHE_DIR), "src", "repro"))
