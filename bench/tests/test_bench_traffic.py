"""The traffic generator: the same seed gives the same jobs, every seed the
same set of sizes in another order, and set-up knows every window a job
can dispatch."""
import json
from collections import Counter
from pathlib import Path

import numpy as np
import pytest

from bench import harness
from bench.traffic import Traffic

ROOT = Path(__file__).resolve().parents[2]
SEEDS = (7, 2**31 + 99, 2**32 + 12345)


@pytest.fixture(scope="module")
def cell():
    return harness.load_cell("t3large.fresh")


def sizes(traffic, jobs_per_tenant):
    """(class, deadline stratum) of every tenant's first jobs."""
    out = Counter()
    for t in range(traffic.tenants):
        for j in range(jobs_per_tenant):
            job = traffic.job(t, j)
            lo, hi = traffic.classes[job.cls]["deadline_ms"]
            frac = (job.deadline_ms - lo) / (hi - lo)
            assert 0.0 <= frac < 1.0
            out[(job.cls, int(frac * traffic.strata))] += 1
    return out


def test_same_seed_same_jobs(cell):
    a = Traffic(cell.config, cell.mix, SEEDS[1]).job(3, 17)
    b = Traffic(cell.config, cell.mix, SEEDS[1]).job(3, 17)
    assert (a.cls, a.deadline_ms) == (b.cls, b.deadline_ms)
    for vm in a.profiles:
        assert a.profiles[vm] == b.profiles[vm]
        assert np.array_equal(a.samples[vm][0], b.samples[vm][0])


def test_every_seed_same_sizes_in_another_order(cell):
    n = len(cell.mix["cycle"]) * cell.mix["deadline_strata"]
    got = [sizes(Traffic(cell.config, cell.mix, s), n) for s in SEEDS]
    tenants = cell.mix["tenants"]
    for g in got:
        assert set(g.values()) == {tenants}
    orders = {tuple(Traffic(cell.config, cell.mix, s).job(t, 0).cls
                    for t in range(tenants)) for s in SEEDS}
    assert len(orders) > 1


def test_every_profile_is_new(cell):
    traffic = Traffic(cell.config, cell.mix, SEEDS[0])
    jobs = [traffic.job(t, j) for t in range(2) for j in range(12)]
    jobs += traffic.setup_jobs()
    means = {job.profiles["m4.xlarge"]["m_avg"] for job in jobs}
    assert len(means) == len(jobs)


@pytest.mark.parametrize("nu0,want", [
    (5, [(5, 5), (21, 16), (37, 16)]),
    (16, [(16, 16), (32, 16), (48, 16)]),
    (40, [(40, 16), (24, 16), (8, 8), (56, 16), (72, 16)]),
])
def test_sweep_windows(nu0, want):
    assert harness.sweep_windows(nu0, 16, 2) == want


def test_mix_names_only_classes_of_its_config():
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    for w in spec["workloads"]:
        c = harness.load_cell(w["name"])
        names = {x["name"] for x in c.config["classes"]}
        assert sorted(c.mix["cycle"]) == sorted(names)


def test_setup_jobs_same_for_every_seed(cell):
    a, b = (Traffic(cell.config, cell.mix, s).setup_jobs() for s in SEEDS[:2])
    assert [(x.cls, x.deadline_ms) for x in a] == \
        [(x.cls, x.deadline_ms) for x in b]
    assert [x.profiles for x in a] == [x.profiles for x in b]
