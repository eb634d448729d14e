"""Lane-sharded fused dispatch (``repro.core.partition``).

In-process: shard-spec parsing, device-aware lane bucketing, the
``make_local_mesh``/``make_lanes_mesh`` degeneracy guards, and the
``REPRO_SHARD=off`` / one-shard degenerate path (bit- and
accounting-identical to the pre-sharding plane).

Subprocess (4 virtual host devices, the ``test_multidevice`` idiom):
bit-parity of sharded fused rounds vs the single-device program across
workload kinds (MapReduce + DAG replay), impls (``jnp`` + ``pallas``),
bucket grids, and D in {1, 2, 4}; service-level parity plus the
one-coalesced-fetch-per-round contract and scheduler digest eviction.
"""
import os
import subprocess
import sys

import numpy as np
import pytest

import jax

from repro.core import partition, qn_sim, shapes
from repro.launch import mesh as mesh_mod


@pytest.fixture
def restore_shard():
    s = partition.shard_spec()
    yield
    partition.set_shard_spec(s)


@pytest.fixture
def restore_grid():
    g = shapes.default_grid()
    yield
    shapes.set_default_grid(g)


# ------------------------------------------------------------- spec parsing
def test_shard_spec_roundtrip(restore_shard):
    for spec, want in (("auto", "auto"), ("off", "off"), ("3", "3"),
                       (2, "2"), (" AUTO ", "auto")):
        partition.set_shard_spec(spec)
        assert partition.shard_spec() == want


def test_shard_spec_rejects_garbage(restore_shard):
    for bad in ("fast", "", "0", "-2", "1.5"):
        with pytest.raises(ValueError):
            partition.set_shard_spec(bad)


def test_shard_count_resolution(restore_shard):
    n = partition.device_count()
    partition.set_shard_spec("off")
    assert partition.shard_count() == 1
    assert partition.shard_count(100) == 1
    partition.set_shard_spec("auto")
    assert partition.shard_count() == n
    assert partition.shard_count(1) == 1          # capped at real candidates
    assert partition.shard_count(10 ** 9) == n
    partition.set_shard_spec(str(n + 1))          # parses fine...
    with pytest.raises(ValueError):               # ...but cannot resolve
        partition.shard_count()


# ----------------------------------------------------- device-aware buckets
def test_bucket_lanes_sharded_properties():
    for grid in shapes.GRIDS:
        for d in range(1, 9):
            for c in range(1, 131):
                b = partition.bucket_lanes(c, d, grid=grid)
                assert b >= c
                assert b % d == 0
                per = b // d
                assert shapes.bucket_lanes(per, grid=grid) == per
    for c in range(1, 131):
        assert partition.bucket_lanes(c, 1) == shapes.bucket_lanes(c)


# -------------------------------------------------------------- mesh guards
def test_make_local_mesh_degenerate_raises():
    n = len(jax.devices())
    with pytest.raises(ValueError, match="zero-sized data axis"):
        mesh_mod.make_local_mesh(model=n + 1)
    with pytest.raises(ValueError, match="devices"):
        mesh_mod.make_local_mesh(data=n + 1, model=1)
    with pytest.raises(ValueError, match="positive"):
        mesh_mod.make_local_mesh(model=0)
    m = mesh_mod.make_local_mesh()                # full population works
    assert m.devices.size == n


def test_make_lanes_mesh():
    n = len(jax.devices())
    m = mesh_mod.make_lanes_mesh()
    assert m.axis_names == ("lanes",) and m.devices.size == n
    assert mesh_mod.make_lanes_mesh(1).devices.size == 1
    with pytest.raises(ValueError, match="shards"):
        mesh_mod.make_lanes_mesh(n + 1)


def test_shard_call_rejects_indivisible_lane_axis():
    if partition.device_count() < 2:
        pytest.skip("needs >= 2 devices")
    import jax.numpy as jnp
    with pytest.raises(ValueError, match="not divisible"):
        partition.shard_call(lambda x: x, (jnp.zeros(3),), shards=2)


# --------------------------------------------------- one-shard degeneracy
def test_one_shard_bit_and_accounting_identical(restore_shard):
    """An explicit single shard must reproduce REPRO_SHARD=off exactly:
    same result bits, same counter deltas, zero shard padding."""
    kw = dict(n_map=16, n_reduce=4, m_avg=900.0, r_avg=600.0,
              think_ms=8000.0, h_users=3, slots=[6, 8, 10],
              min_jobs=5, replications=2)

    def run():
        s0, p0 = qn_sim.sim_stats(), qn_sim.padding_stats()
        out = qn_sim.response_time_batch(**kw)
        ds = {k: v - s0[k] for k, v in qn_sim.sim_stats().items()}
        dp = {k: v - p0[k] for k, v in qn_sim.padding_stats().items()}
        return out, ds, dp

    partition.set_shard_spec("off")
    base, ds_off, dp_off = run()
    partition.set_shard_spec(1)
    one, ds_one, dp_one = run()
    assert np.array_equal(base, one)
    assert ds_off == ds_one
    assert dp_off == dp_one
    assert dp_one["shard_padded_lanes"] == 0
    assert dp_one["shard_padded_events"] == 0


def test_padding_split_sum_identity(restore_shard):
    partition.set_shard_spec("off")
    p0 = qn_sim.padding_stats()
    qn_sim.response_time_batch(16, 4, 900.0, 600.0, 8000.0, 3,
                               [6, 8, 10, 12, 14], min_jobs=5,
                               replications=1)
    p = {k: v - p0[k] for k, v in qn_sim.padding_stats().items()}
    assert (p["events_total"] - p["events_useful"]
            == p["bucket_padded_events"] + p["shard_padded_events"]
            + p["batch_padded_events"])
    assert p["shard_padded_events"] == 0


# ------------------------------------------------------ subprocess harness
def _run_subprocess(code: str) -> str:
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    env.pop("XLA_FLAGS", None)               # the scripts set their own
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, cwd=os.path.dirname(
                           os.path.dirname(os.path.abspath(__file__))),
                       timeout=540)
    assert r.returncode == 0, r.stderr[-3000:]
    return r.stdout


PARITY_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import numpy as np
from repro.core import dag as dag_mod
from repro.core import partition, qn_sim, shapes
from repro.core.workload import DagJob, Stage

assert partition.device_count() == 4
job = DagJob(name="j", stages=(Stage(12, 800.0), Stage(4, 500.0)))
smp = dag_mod.dag_replayer_lists(job, cap=64)
ms = np.random.default_rng(0).lognormal(6.8, 0.3, 128).astype(np.float32)
rs = np.random.default_rng(1).lognormal(6.3, 0.3, 128).astype(np.float32)
SLOTS = [6, 8, 10, 12, 14, 16]

for grid in shapes.GRIDS:
    shapes.set_default_grid(grid)
    for impl in qn_sim.QN_IMPLS:
        partition.set_shard_spec("off")
        base = qn_sim.response_time_batch(
            16, 4, 900.0, 600.0, 8000.0, 3, SLOTS, min_jobs=5,
            replications=2, impl=impl)
        base_r = qn_sim.response_time_batch(
            16, 4, 0.0, 0.0, 8000.0, 3, SLOTS[:3], min_jobs=5,
            replications=2, m_samples=ms, r_samples=rs, impl=impl)
        for D in (1, 2, 4):
            partition.set_shard_spec(D)
            d0 = qn_sim.dispatch_count()
            got = qn_sim.response_time_batch(
                16, 4, 900.0, 600.0, 8000.0, 3, SLOTS, min_jobs=5,
                replications=2, impl=impl)
            assert qn_sim.dispatch_count() - d0 == 1   # still ONE dispatch
            assert np.array_equal(base, got), (grid, impl, D)
            s0 = qn_sim.sim_stats()
            got_r = qn_sim.response_time_batch(
                16, 4, 0.0, 0.0, 8000.0, 3, SLOTS[:3], min_jobs=5,
                replications=2, m_samples=ms, r_samples=rs, impl=impl)
            assert np.array_equal(base_r, got_r), (grid, impl, D, "replay")
            s1 = qn_sim.sim_stats()
            # each shard builds its seed-only tables for the 2 seeds
            assert s1["draw_columns"] - s0["draw_columns"] == (
                2 * D if impl == "pallas" else s1["lanes"] - s0["lanes"])
    partition.set_shard_spec("off")
    dbase = dag_mod.response_time_batch([job] * 5, 8000.0, SLOTS[:5], 3,
                                        min_jobs=5, replications=2)
    dbase_r = dag_mod.response_time_batch([job] * 5, 8000.0, SLOTS[:5], 3,
                                          min_jobs=5, replications=1,
                                          samples=smp)
    for D in (1, 2, 4):
        partition.set_shard_spec(D)
        dg = dag_mod.response_time_batch([job] * 5, 8000.0, SLOTS[:5], 3,
                                         min_jobs=5, replications=2)
        assert np.array_equal(dbase, dg), (grid, D, "dag")
        dg_r = dag_mod.response_time_batch([job] * 5, 8000.0, SLOTS[:5], 3,
                                           min_jobs=5, replications=1,
                                           samples=smp)
        assert np.array_equal(dbase_r, dg_r), (grid, D, "dag replay")

# shard padding is accounted separately: 6 candidates over 4 shards pad to
# 4 * bucket(ceil(6/4)) = 8 lanes where the geo grid alone would use 6
shapes.set_default_grid("geo")
partition.set_shard_spec(4)
p0 = qn_sim.padding_stats()
qn_sim.response_time_batch(16, 4, 900.0, 600.0, 8000.0, 3, SLOTS,
                           min_jobs=5, replications=1)
p = {k: v - p0[k] for k, v in qn_sim.padding_stats().items()}
assert p["shard_padded_lanes"] == 2, p
assert p["bucket_padded_lanes"] == 0, p
assert (p["events_total"] - p["events_useful"]
        == p["bucket_padded_events"] + p["shard_padded_events"]
        + p["batch_padded_events"])
from repro.obs import metrics
assert metrics.registry().get("qn.devices").value == 4

# AMVA kernel lanes shard too
from repro.kernels.amva import ops as amva_ops
import jax.numpy as jnp
a = jnp.linspace(100.0, 400.0, 7); b = jnp.full((7,), 30.0)
tk = jnp.full((7,), 8000.0); h = jnp.full((7,), 5.0)
partition.set_shard_spec("off")
b0 = np.asarray(amva_ops.ps_fixed_point(a, b, tk, h))
for D in (2, 4):
    partition.set_shard_spec(D)
    assert np.array_equal(b0, np.asarray(amva_ops.ps_fixed_point(a, b, tk, h)))
print("PARITY=OK")
"""


SERVICE_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax
import numpy as np
from repro.core import partition, qn_sim
from repro.core.optimizer import DSpace4Cloud
from repro.core.problem import ApplicationClass, JobProfile, Problem, VMType
from repro.service import SolverService

vm = VMType(name="m4.xlarge", cores=4, sigma=0.07, pi=0.22,
            containers_per_core=2)
def prob(i):
    p = JobProfile(n_map=24, n_reduce=6, m_avg=1000.0 + 120.0 * i,
                   m_max=2400.0, r_avg=500.0 + 50.0 * i, r_max=1300.0)
    c = ApplicationClass(name=f"t{i}", h_users=3, think_ms=8000.0,
                         deadline_ms=36000.0 + 4000.0 * i, eta=0.3,
                         profiles={vm.name: p})
    return Problem(classes=[c], vm_types=[vm])

kw = dict(min_jobs=6, replications=1, seed=0)
partition.set_shard_spec("off")
solo = [DSpace4Cloud(prob(i), batched=True, window=8, **kw).run()
        for i in range(3)]

partition.set_shard_spec(2)
svc = SolverService(window=8)
jids = [svc.submit(prob(i), **kw) for i in range(3)]
jobs = svc.run_until_complete()
for jid, rep in zip(jids, solo):
    assert jobs[jid].report.solutions == rep.solutions, jid
assert svc.scheduler._digests == {}, svc.scheduler._digests  # evicted
assert svc.stats()["shard"]["devices"] == 4

# deferred pipeline: one coalesced device_get per evaluate_many round,
# regardless of shard count, even for a mixed two-group batch
from repro.core import dag as dag_mod
from repro.core.evaluators import make_batched_qn_evaluator
from repro.core.workload import DagJob, Stage
dj = DagJob(name="dag", stages=(Stage(12, 800.0), Stage(4, 500.0)))
mixed = ApplicationClass(name="mix", h_users=3, think_ms=8000.0,
                         deadline_ms=40000.0, eta=0.3,
                         profiles={vm.name: dj})
mr = prob(0).classes[0]
ev = make_batched_qn_evaluator(min_jobs=6, replications=1)
calls = {"n": 0}
orig = jax.device_get
def counting(x):
    calls["n"] += 1
    return orig(x)
jax.device_get = counting
try:
    ev.evaluate_many([(mr, vm, 4), (mr, vm, 6), (mixed, vm, 4),
                      (mixed, vm, 6)])
finally:
    jax.device_get = orig
assert ev.device_calls == 2, ev.device_calls        # one per workload kind
assert calls["n"] == 1, calls                       # ONE coalesced fetch
print("SERVICE=OK")
"""


def test_sharded_parity_across_kinds_impls_grids():
    out = _run_subprocess(PARITY_SCRIPT)
    assert "PARITY=OK" in out, out[-500:]


def test_sharded_service_parity_and_coalesced_fetch():
    out = _run_subprocess(SERVICE_SCRIPT)
    assert "SERVICE=OK" in out, out[-500:]
