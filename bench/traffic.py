"""The one traffic generator: planning jobs from a configuration and a mix.

A configuration (``bench/configs/<name>.json``) holds the deployment: the
VM catalog, the application classes with their published task counts,
user counts, task-duration shapes and deadline ranges, the profiling
method and the solver settings.  A mix (``bench/traffic/<name>.json``) holds
the traffic: how many tenants, how many jobs each may send, the cyclic
order in which a tenant visits the classes and how many strata its
deadlines are drawn from.

Every tenant plans in a closed loop: it submits its next job as soon as
its last one settles.  Tenant ``t``'s ``j``-th job is a pure function of
``(seed, t, j)``, so the same seed gives the same jobs whatever the speed
of the system.  Every job brings a new profiling run, so no two jobs share
a profile: nothing is served from the planner's cache and no two jobs fuse.

Every seed gets the same set of sizes, in another order.  A tenant visits
the classes in the mix's fixed ``cycle`` (heavy rows between light ones),
starting at an offset that the seed rotates; its successive visits to one
class walk through the class's deadline strata from a seeded start, and
the seed draws the deadline within the stratum and the profiling run.

A profiling run is the paper's §4.1 method as the job builders of the
planner's Table-3 scenarios call it: ``runs`` single-user jobs of the
class's calibrated task durations (lognormal, stragglers, container
start-up, first-wave shuffle) on a cluster of ``slots`` containers, per
VM type scaled by its core speed; the profile is the runs' task-duration
means and maxima, the replay lists a subsample of at most ``replay_cap``
durations of each kind.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Dict, List, Tuple

import numpy as np

SEED_MOD = 2**31 - 1


@dataclass(frozen=True)
class PlannedJob:
    tenant: int                      # -1 for a set-up job
    index: int                       # position in the tenant's sequence
    cls: str                         # configuration class name
    deadline_ms: float
    # per VM type: profile stats and (map list, reduce list) to replay
    profiles: Dict[str, Dict[str, float]]
    samples: Dict[str, Tuple[np.ndarray, np.ndarray]]


def _lognormal(rng, median, cv, size):
    sigma = math.sqrt(math.log(1.0 + cv * cv))
    return rng.lognormal(math.log(max(median, 1e-9)), sigma, size)


def _task_durations(w: dict, rng, speed: float):
    """One job's map and reduce task durations [ms] on cores ``speed``x
    faster than the reference (the detailed simulator's ground truth)."""
    m = _lognormal(rng, w["map_ms"] / speed, w["cv"], w["n_map"])
    r = _lognormal(rng, w["reduce_ms"] / speed, w["cv"], w["n_reduce"])
    strag_m = rng.random(w["n_map"]) < w["straggler_p"]
    strag_r = rng.random(w["n_reduce"]) < w["straggler_p"]
    m = np.where(strag_m, m * w["straggler_mult"], m)
    r = np.where(strag_r, r * w["straggler_mult"], r)
    return m + w["startup_ms"] / speed, r + w["startup_ms"] / speed


def profiling_run(w: dict, speed: float, seed: int, *, runs: int,
                  slots: int, replay_cap: int):
    """(profile stats, map list, reduce list) of one profiling run."""
    rng = np.random.default_rng(seed)
    m_all, r_all = [], []
    for _ in range(runs):
        m, r = _task_durations(w, rng, speed)
        r = r.copy()
        r[:min(slots, w["n_reduce"])] += w["shuffle_first_ms"] / speed
        m_all.append(m)
        r_all.append(r)
    m_cat, r_cat = np.concatenate(m_all), np.concatenate(r_all)
    prof = dict(n_map=int(w["n_map"]), n_reduce=int(w["n_reduce"]),
                m_avg=float(m_cat.mean()), m_max=float(m_cat.max()),
                r_avg=float(r_cat.mean()), r_max=float(r_cat.max()))
    sub = np.random.default_rng(seed + 1)
    if len(m_cat) > replay_cap:
        m_cat = sub.choice(m_cat, replay_cap, replace=False)
    if len(r_cat) > replay_cap:
        r_cat = sub.choice(r_cat, replay_cap, replace=False)
    return prof, m_cat.astype(np.float32), r_cat.astype(np.float32)


class Traffic:
    """Jobs of one cell: ``config`` and ``mix`` are the parsed JSON files."""

    def __init__(self, config: dict, mix: dict, seed: int):
        self.config = config
        self.mix = mix
        self.seed = int(seed)
        self.classes = {c["name"]: c for c in config["classes"]}
        self.cycle = list(mix["cycle"])
        self.tenants = int(mix["tenants"])
        self.strata = int(mix["deadline_strata"])
        rng = self._rng(0)
        n = len(self.cycle)
        self.offset = (np.arange(self.tenants) + rng.integers(n)) % n
        self.stratum0 = rng.integers(self.strata, size=(self.tenants, n))

    def _rng(self, *path: int) -> np.random.Generator:
        return np.random.default_rng([self.seed % SEED_MOD,
                                      self.seed // SEED_MOD, *path])

    def profile(self, cls: str, seed: int):
        """Profiles and replay lists of ``cls`` per VM type, from the
        profiling run with ``seed``."""
        c = self.classes[cls]
        p = self.config["profiling"]
        profiles, samples = {}, {}
        for vm in self.config["vm_types"]:
            prof, ms, rs = profiling_run(
                c["workload"], vm["speed"], int(seed), runs=p["runs"],
                slots=p["slots"], replay_cap=p["replay_cap"])
            profiles[vm["name"]] = prof
            samples[vm["name"]] = (ms, rs)
        return profiles, samples

    def _job(self, tenant: int, index: int, cls: str, frac: float,
             rng: np.random.Generator) -> PlannedJob:
        lo, hi = self.classes[cls]["deadline_ms"]
        profiles, samples = self.profile(cls, int(rng.integers(1, SEED_MOD)))
        return PlannedJob(tenant=tenant, index=index, cls=cls,
                          deadline_ms=float(lo + frac * (hi - lo)),
                          profiles=profiles, samples=samples)

    def job(self, tenant: int, index: int) -> PlannedJob:
        """Tenant ``tenant``'s ``index``-th job."""
        visit, k = divmod(int(self.offset[tenant]) + index, len(self.cycle))
        stratum = (visit + int(self.stratum0[tenant, k])) % self.strata
        rng = self._rng(1, tenant, index)
        return self._job(tenant, index, self.cycle[k],
                         (stratum + rng.random()) / self.strata, rng)

    def pool(self) -> List[List[PlannedJob]]:
        """Every tenant's job sequence, made before the window."""
        per = int(self.mix["jobs_per_tenant"])
        return [[self.job(t, j) for j in range(per)]
                for t in range(self.tenants)]

    def setup_jobs(self) -> List[PlannedJob]:
        """One job per class, mid-range deadline, on profiling runs of a
        stream that no seed changes: set-up plans them before the window,
        the same work for every seed."""
        return [self._job(-1, i, name, 0.5, np.random.default_rng([2, i]))
                for i, name in enumerate(self.classes)]
