"""The comparison that decides ``correct``.

Once the window has closed, a sample of the jobs that settled in it, drawn
from the seed and always holding the job with the most simulated events,
is held to the configuration's plain reference
(``bench/reference/<name>.py``, which imports nothing of the planner):

* ``point_gap``: the widest relative gap between a QN point estimate the
  job received (from the ``qn_event`` kernel or from the shared cache) and
  the reference's estimate of the same point;
* ``plan_faults``: plans that fail their certificate under the reference's
  estimates.  A feasible plan ``(vm, nu)`` meets its deadline, and no
  cheaper fleet does: for every VM type, the largest fleet that costs less
  than the plan misses the deadline (response times fall as fleets grow).
  An infeasible plan misses the deadline at the fleet it reports;
* ``failed_jobs``: jobs of the window that ended FAILED or SHED;
* ``missing_jobs``: jobs still unsettled a grace period after the close.

Each number has its limit in the configuration's ``correct`` section.
"""
from __future__ import annotations

import importlib
import math
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

import numpy as np


@dataclass
class Answer:
    """What the service answered for one job, in plain values."""
    cls: dict                      # configuration class entry
    deadline_ms: float
    profiles: Dict[str, dict]
    samples: Dict[str, Tuple[np.ndarray, np.ndarray]]
    plan_vm: Optional[str]
    plan_nu: int
    plan_cost: float
    feasible: bool
    probes: List[Tuple[str, int, float]] = field(default_factory=list)

    @property
    def events(self) -> int:
        p = next(iter(self.profiles.values()))
        return int(p["n_map"] + p["n_reduce"])


def sample(answers: List[Answer], k: int, rng: np.random.Generator
           ) -> List[Answer]:
    """``k`` answers drawn by ``rng``, the one with the most simulated
    events (and then the most probes) always among them."""
    if len(answers) <= k:
        return list(answers)
    big = max(range(len(answers)),
              key=lambda i: (answers[i].events, len(answers[i].probes)))
    rest = [i for i in range(len(answers)) if i != big]
    pick = rng.choice(len(rest), size=k - 1, replace=False)
    return [answers[big]] + [answers[rest[int(i)]] for i in sorted(pick)]


def _gap(got: float, ref: float) -> float:
    if math.isinf(got) and math.isinf(ref):
        return 0.0
    if math.isinf(got) or math.isinf(ref) or ref == 0.0:
        return math.inf
    return abs(got - ref) / abs(ref)


def reference(config: dict):
    return importlib.import_module(f"bench.reference.{config['reference']}")


def certificate_points(a: Answer, config: dict, ref) -> List[Tuple[str, int]]:
    """The fleets whose reference estimates decide the plan's certificate."""
    pts = []
    if a.plan_vm is not None:
        pts.append((a.plan_vm, a.plan_nu))
    if a.feasible:
        for vm in config["vm_types"]:
            n = cheaper_fleet(a.plan_cost, vm, config["eta"], ref)
            if n >= 1:
                pts.append((vm["name"], n))
    return pts


def cheaper_fleet(cost: float, vm: dict, eta: float, ref) -> int:
    """Largest fleet of ``vm`` that costs strictly less than ``cost``."""
    n = 0
    while ref.spot_mix_cost(n + 1, eta, vm["sigma"], vm["pi"]) < cost - 1e-9:
        n += 1
    return n


def evaluate(answers: List[Answer], config: dict, *, dtype=None,
             ref=None) -> Dict[str, float]:
    """Reference estimates for every probe and certificate point, and the
    numbers ``point_gap`` and ``plan_faults``; ``dtype`` overrides the
    configuration's precision (the control)."""
    ref = ref or reference(config)
    vms = {vm["name"]: vm for vm in config["vm_types"]}
    solver = config["solver"]
    keys: Dict[tuple, int] = {}
    points = []
    for j, a in enumerate(answers):
        wanted = [(vm, nu) for vm, nu, _ in a.probes]
        wanted += certificate_points(a, config, ref)
        for vm, nu in wanted:
            k = (j, vm, int(nu))
            if k in keys:
                continue
            keys[k] = len(points)
            prof = a.profiles[vm]
            m_list, r_list = a.samples[vm]
            points.append(dict(
                h_users=a.cls["users"], think_ms=config["think_ms"],
                n_map=prof["n_map"], n_reduce=prof["n_reduce"],
                slots=int(nu) * vms[vm]["cores"]
                * vms[vm]["containers_per_core"],
                m_list=m_list, r_list=r_list))
    kw = dict(min_jobs=solver["min_jobs"], warmup_jobs=solver["warmup_jobs"],
              replications=solver["replications"], seed=solver["seed"])
    if dtype is not None:
        kw["dtype"] = dtype
    est = ref.point_estimates(points, **kw)
    gap, faults = 0.0, 0
    for j, a in enumerate(answers):
        t = {(vm, nu): est[keys[(j, vm, nu)]]
             for (jj, vm, nu) in keys if jj == j}
        for vm, nu, got in a.probes:
            gap = max(gap, _gap(got, t[(vm, int(nu))]))
        faults += 0 if plan_holds(a, t, config, ref) else 1
    return {"point_gap": gap, "plan_faults": float(faults)}


def plan_holds(a: Answer, t: Dict[tuple, float], config: dict, ref) -> bool:
    if a.plan_vm is None:
        return False
    vm = {v["name"]: v for v in config["vm_types"]}[a.plan_vm]
    cost = ref.spot_mix_cost(a.plan_nu, config["eta"], vm["sigma"], vm["pi"])
    if abs(cost - a.plan_cost) > 1e-9 * max(1.0, cost):
        return False
    here = t[(a.plan_vm, a.plan_nu)]
    if not a.feasible:
        return here > a.deadline_ms
    if here > a.deadline_ms:
        return False
    for v in config["vm_types"]:
        n = cheaper_fleet(a.plan_cost, v, config["eta"], ref)
        if n >= 1 and t[(v["name"], n)] <= a.deadline_ms:
            return False
    return True


def judge(numbers: Dict[str, float], limits: Dict[str, float]
          ) -> Tuple[bool, Dict[str, dict]]:
    """(every number within its limit, ``{name: {value, limit}}``)."""
    checks = {k: {"value": numbers[k], "limit": limits[k]} for k in limits}
    ok = all(c["value"] <= c["limit"] for c in checks.values())
    return ok, checks
