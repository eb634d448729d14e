"""Response-time evaluators at the four fidelity tiers.

  * "mva"      — analytic closed MVA (the MINLP-tier model; instant).
  * "amva"     — batched MVA frontier, Pallas-kernel-backed when available
                 (beyond-paper fast tier; evaluates whole nu ranges at once).
  * "qn"       — JAX event-driven QN simulation (the paper's accurate tier).
                 ``make_qn_evaluator`` dispatches one point per call;
                 ``make_batched_qn_evaluator`` sweeps whole nu frontiers
                 (and several VM types) in one fused device call with
                 cache-aware gather of already-known points.
  * "detailed" — trace-replay cluster simulator (ground truth; used for
                 validation only, never inside the optimizer — mirroring the
                 paper, where the real cluster is not in the loop).

Every tier is *workload-generic*: a class's per-VM profile may be the
paper's MapReduce ``JobProfile`` or a Tez/Spark ``DagJob`` stage chain
(``repro.core.workload``).  The analytic tiers price both through
``mva.workload_demand``; the accurate tier routes each fusion group by
workload kind — MapReduce windows to ``qn_sim.response_time_batch``, DAG
windows to ``dag.response_time_batch`` (``fused_eval_call``) — and both
batched simulators honor the same bit-exact-vs-scalar parity contract.
Caches are content-addressed (``workload.profile_hash``): two classes
sharing a name but not a profile can never exchange results, and DAG and
MapReduce entries can never collide.

See docs/evaluators.md and docs/workloads.md for the accuracy-vs-cost
trade-offs and the dispatch points a new workload kind must cover.
"""
from __future__ import annotations

import threading
from typing import Callable, Dict, Iterable, List, Optional, Sequence, Tuple

import numpy as np

from repro.core import dag as dag_mod
from repro.core import partition as _partition
from repro.core import qn_sim
from repro.core.mva import job_response, ps_response_batch, workload_demand
from repro.obs import trace as _obs_trace
from repro.core.problem import ApplicationClass, VMType
from repro.core.workload import (
    DAG,
    profile_hash,
    samples_digest,
    workload_kind,
)


def mva_evaluator(cls: ApplicationClass, vm: VMType, nu: int) -> float:
    prof = cls.profile_for(vm)
    return job_response(prof, nu * vm.slots, cls.think_ms, cls.h_users)


class _ContextDigests:
    """Per-(class, vm) evaluation-context digests, memoizing the replay
    sample digest (the expensive part — lists can be thousands of floats).
    Replay lists are looked up by (class_name, vm_name), so memoizing the
    sample digest by name is sound even across same-named classes; the
    profile part is rehashed per call (a few µs) precisely so same-named
    classes with different profiles get different keys."""

    def __init__(self, samples: Optional[Dict], *, min_jobs: int,
                 warmup_jobs: int, replications: int):
        self.samples = samples or {}
        self.sim = dict(min_jobs=min_jobs, warmup_jobs=warmup_jobs,
                        replications=replications)
        self._sdig: Dict[tuple, str] = {}

    def replay_for(self, cls: ApplicationClass, vm: VMType):
        return self.samples.get((cls.name, vm.name))

    def sample_digest(self, cls: ApplicationClass, vm: VMType) -> str:
        k = (cls.name, vm.name)
        if k not in self._sdig:
            self._sdig[k] = samples_digest(self.samples.get(k))
        return self._sdig[k]

    def digest(self, prof, cls: ApplicationClass, vm: VMType) -> str:
        return profile_hash(prof, cls.think_ms, cls.h_users, vm.slots,
                            samples_dig=self.sample_digest(cls, vm),
                            **self.sim)


def make_qn_evaluator(min_jobs: int = 40, warmup_jobs: int = 8,
                      replications: int = 2, seed: int = 0,
                      cache: Optional[dict] = None,
                      samples: Optional[Dict] = None) -> Callable:
    """``samples``: optional {(class_name, vm_name): replay lists} —
    ``(m_list, r_list)`` for MapReduce classes, a per-stage ``(K, NS)``
    array for DAG classes — switches the QN to JMT-replayer mode (§4.1).

    The cache is keyed ``(profile_hash, vm_name, nu, seed)`` — the same
    content-addressed scheme as the service's ``EvalCache`` — so two
    problems that reuse a class/VM *name* against one shared dict can
    never exchange results (names are labels, content is identity)."""
    cache = cache if cache is not None else {}
    ctx = _ContextDigests(samples, min_jobs=min_jobs,
                          warmup_jobs=warmup_jobs, replications=replications)

    def evaluate(cls: ApplicationClass, vm: VMType, nu: int) -> float:
        prof = cls.profile_for(vm)
        key = (ctx.digest(prof, cls, vm), vm.name, int(nu), seed)
        if key in cache:
            return cache[key]
        smp = ctx.replay_for(cls, vm)
        if workload_kind(prof) == DAG:
            t = dag_mod.dag_response_time(
                prof, slots=nu * vm.slots, think_ms=cls.think_ms,
                h_users=cls.h_users, min_jobs=min_jobs,
                warmup_jobs=warmup_jobs, seed=seed,
                replications=replications, samples=smp)
        else:
            ms, rs = smp if smp is not None else (None, None)
            t = qn_sim.response_time(
                n_map=prof.n_map, n_reduce=prof.n_reduce,
                m_avg=prof.m_avg, r_avg=prof.r_avg,
                think_ms=cls.think_ms, h_users=cls.h_users,
                slots=nu * vm.slots, min_jobs=min_jobs,
                warmup_jobs=warmup_jobs, seed=seed,
                replications=replications, m_samples=ms, r_samples=rs)
        cache[key] = t
        return t
    return evaluate


def fused_qn_call(profs: Sequence["object"], think_ms: Sequence[float],
                  h_users: int, slots: Sequence[int], *,
                  min_jobs: int = 40, warmup_jobs: int = 8,
                  replications: int = 2, seed: int = 0,
                  m_samples=None, r_samples=None,
                  impl: Optional[str] = None, defer: bool = False):
    """ONE fused simulator dispatch over heterogeneous points of a fusion
    group (shared ``h_users``, replay lists, and simulation parameters).

    ``impl`` selects the simulator backend — ``"jnp"`` (lax.scan) or
    ``"pallas"`` (fused event-step kernel, bit-identical; see
    docs/kernels.md) — and defaults to ``qn_sim.default_impl()``.

    ``profs``/``think_ms``/``slots`` are aligned per-point sequences; the
    points may come from different classes, VM types — or, in the service,
    different tenants' jobs.  Each vmap lane runs with its own logical event
    budget and seed, so every returned estimate is bit-identical to a scalar
    ``qn_sim.response_time`` call for the same point (the parity contract of
    ``response_time_batch``).  This is the single marshaling point both
    ``BatchedQNEvaluator`` and ``repro.service.scheduler`` dispatch through.

    ``defer=True`` returns a ``qn_sim.PendingBatch`` right after the async
    device dispatch; callers coalesce many groups into one
    ``qn_sim.resolve_batches`` host sync.
    """
    return qn_sim.response_time_batch(
        n_map=np.asarray([p.n_map for p in profs], np.int64),
        n_reduce=np.asarray([p.n_reduce for p in profs], np.int64),
        m_avg=np.asarray([p.m_avg for p in profs], np.float32),
        r_avg=np.asarray([p.r_avg for p in profs], np.float32),
        think_ms=np.asarray(think_ms, np.float32),
        h_users=int(h_users),
        slots=np.asarray(slots, np.int64),
        min_jobs=min_jobs, warmup_jobs=warmup_jobs,
        seed=seed, replications=replications,
        m_samples=m_samples, r_samples=r_samples, impl=impl, defer=defer)


def fused_dag_call(jobs: Sequence["object"], think_ms: Sequence[float],
                   h_users: int, slots: Sequence[int], *,
                   min_jobs: int = 40, warmup_jobs: int = 8,
                   replications: int = 2, seed: int = 0,
                   samples=None, defer: bool = False):
    """DAG counterpart of ``fused_qn_call``: one fused dispatch of
    ``dag.response_time_batch`` over heterogeneous chain configurations
    (chains of different length pad to the batch-maximum stage count).
    Each lane is bit-identical to a scalar ``dag_response_time`` call.
    ``defer`` as in ``fused_qn_call``."""
    return dag_mod.response_time_batch(
        jobs, think_ms=np.asarray(think_ms, np.float32),
        slots=np.asarray(slots, np.int64), h_users=int(h_users),
        min_jobs=min_jobs, warmup_jobs=warmup_jobs,
        seed=seed, replications=replications, samples=samples, defer=defer)


def fused_eval_call(kind: str, profs: Sequence["object"],
                    think_ms: Sequence[float], h_users: int,
                    slots: Sequence[int], *, min_jobs: int = 40,
                    warmup_jobs: int = 8, replications: int = 2,
                    seed: int = 0, samples=None,
                    impl: Optional[str] = None, defer: bool = False):
    """Workload dispatch of a fusion group: route MapReduce windows to
    ``fused_qn_call`` and DAG windows to ``fused_dag_call``.  ``samples``
    is the group-shared replay payload in the kind's native form (an
    ``(m_list, r_list)`` pair, or a ``(K, NS)`` array).  This is the single
    marshaling point both ``BatchedQNEvaluator`` and the service's
    ``FusionScheduler`` dispatch through.  ``impl`` selects the MapReduce
    simulator backend (see ``fused_qn_call``); the DAG route has a single
    implementation and ignores it.  With ``defer=True`` the span covers
    the (async) dispatch only, and a ``qn_sim.PendingBatch`` is returned
    for a later coalesced ``resolve_batches``."""
    kw = dict(min_jobs=min_jobs, warmup_jobs=warmup_jobs,
              replications=replications, seed=seed, defer=defer)
    with _obs_trace.span("fused_dispatch", cat="fusion", kind=kind,
                         points=len(profs), h_users=int(h_users),
                         replay=samples is not None,
                         devices=_partition.shard_count(len(profs))):
        if kind == DAG:
            return fused_dag_call(profs, think_ms, h_users, slots,
                                  samples=samples, **kw)
        ms, rs = samples if samples is not None else (None, None)
        return fused_qn_call(profs, think_ms, h_users, slots,
                             m_samples=ms, r_samples=rs, impl=impl, **kw)


class BatchedQNEvaluator:
    """QN-tier evaluator that amortizes device dispatches over candidate
    sweeps.

    Where the point-wise evaluator pays ``replications`` XLA dispatches per
    probed (class, vm, nu), this one evaluates a whole frontier in ONE fused
    call of the kind's batched simulator (``qn_sim.response_time_batch`` or
    ``dag.response_time_batch``): cached points are gathered from the
    shared dict cache, only the misses go to the device, and every result
    lands back in the cache under the same content-addressed
    ``(profile_hash, vm, nu, seed)`` keys the scalar evaluator uses — so
    the two are drop-in interchangeable and numerically identical for the
    same seed.

    Counters (for benchmarks): ``device_calls`` fused dispatches issued,
    ``points_evaluated`` simulator configurations they covered.
    """

    def __init__(self, min_jobs: int = 40, warmup_jobs: int = 8,
                 replications: int = 2, seed: int = 0,
                 cache: Optional[dict] = None,
                 samples: Optional[Dict] = None,
                 impl: Optional[str] = None):
        self.impl = impl
        self.min_jobs = min_jobs
        self.warmup_jobs = warmup_jobs
        self.replications = replications
        self.seed = seed
        self.cache = cache if cache is not None else {}
        self.samples = samples or {}
        self._ctx = _ContextDigests(self.samples, min_jobs=min_jobs,
                                    warmup_jobs=warmup_jobs,
                                    replications=replications)
        self.device_calls = 0
        self.points_evaluated = 0
        self._counter_lock = threading.Lock()   # hill_climb probes from a
        #                                         thread pool (per class)

    # ------------------------------------------------------------ frontier
    def evaluate_frontier(self, cls: ApplicationClass, vm: VMType,
                          nus: Sequence[int]) -> np.ndarray:
        """Response time for every nu in ``nus`` (one device call for all
        cache misses).  Returns a float array aligned with ``nus``."""
        return np.asarray(
            self.evaluate_many((cls, vm, int(n)) for n in nus))

    # ------------------------------------------------- multi-VM fused call
    def evaluate_many(
        self, items: Iterable[Tuple[ApplicationClass, VMType, int]],
    ) -> List[float]:
        """Evaluate arbitrary (class, vm, nu) points, fusing everything that
        can share a device program: one dispatch per (workload kind,
        h_users, replay-list) group — so a sweep across several VM types of
        one class is a single call, and a mixed MapReduce + DAG item list
        costs one dispatch per kind.  Cached points never reach the device.
        Returns times aligned with ``items``."""
        items = list(items)
        keys: List[tuple] = []
        profs: List[object] = []
        todo: Dict[tuple, list] = {}
        seen = set()
        for idx, (cls, vm, nu) in enumerate(items):
            prof = cls.profile_for(vm)
            profs.append(prof)
            key = (self._ctx.digest(prof, cls, vm), vm.name, int(nu),
                   self.seed)
            keys.append(key)
            if key in self.cache or key in seen:
                continue
            seen.add(key)
            replay = (cls.name, vm.name) if (cls.name, vm.name) \
                in self.samples else None
            kind = workload_kind(prof)
            group_key = (kind, cls.h_users, replay)
            if kind == DAG and replay is not None:
                # replay lanes share one (K, NS) sample array, so a replay
                # group must agree on the stage count (non-replay DAG lanes
                # pad freely and fuse across chain lengths)
                group_key += (len(prof.stages),)
            todo.setdefault(group_key, []).append(idx)
        # Two-phase round: dispatch every group's device program first
        # (JAX async dispatch — marshaling group k+1 overlaps the device
        # executing group k), then resolve ALL results with one host sync.
        inflight: List[Tuple[list, "qn_sim.PendingBatch"]] = []
        for group_key, idxs in todo.items():
            kind, h_users, replay = group_key[:3]
            smp = self.samples[replay] if replay is not None else None
            pending = fused_eval_call(
                kind, [profs[i] for i in idxs],
                [items[i][0].think_ms for i in idxs],
                h_users,
                [int(items[i][2]) * items[i][1].slots for i in idxs],
                min_jobs=self.min_jobs, warmup_jobs=self.warmup_jobs,
                seed=self.seed, replications=self.replications,
                samples=smp, impl=self.impl, defer=True)
            inflight.append((idxs, pending))
            with self._counter_lock:
                self.device_calls += 1
                self.points_evaluated += len(idxs)
        if inflight:
            results = qn_sim.resolve_batches(p for _, p in inflight)
            for (idxs, _), ts in zip(inflight, results):
                for i, t in zip(idxs, ts):
                    self.cache[keys[i]] = float(t)
        return [self.cache[k] for k in keys]

    # --------------------------------------------------- scalar-compatible
    def __call__(self, cls: ApplicationClass, vm: VMType, nu: int) -> float:
        return float(self.evaluate_frontier(cls, vm, [nu])[0])


def make_batched_qn_evaluator(min_jobs: int = 40, warmup_jobs: int = 8,
                              replications: int = 2, seed: int = 0,
                              cache: Optional[dict] = None,
                              samples: Optional[Dict] = None,
                              impl: Optional[str] = None,
                              ) -> BatchedQNEvaluator:
    """Batched counterpart of ``make_qn_evaluator`` — same cache keys, same
    per-point numbers for the same seed, but whole frontiers per dispatch."""
    return BatchedQNEvaluator(min_jobs=min_jobs, warmup_jobs=warmup_jobs,
                              replications=replications, seed=seed,
                              cache=cache, samples=samples, impl=impl)


def make_detailed_evaluator(spec_by_class: Dict[str, "object"],
                            max_jobs: int = 40, seed: int = 0) -> Callable:
    from repro.core.cluster_sim import simulate_cluster

    def evaluate(cls: ApplicationClass, vm: VMType, nu: int) -> float:
        spec = spec_by_class[cls.name]
        mean, _ = simulate_cluster(
            spec, slots=nu * vm.slots, h_users=cls.h_users,
            think_ms=cls.think_ms, speed=vm.speed,
            max_jobs=max_jobs, seed=seed)
        return mean
    return evaluate


def workload_event_budget(prof, *, min_jobs: int,
                          warmup_jobs: int) -> int:
    """Pow2-bucketed logical event budget of one (candidate, replication)
    simulator lane for any workload kind — the unit admission control
    prices jobs in (``service/admission.py``).  Budgets depend only on the
    task counts and job quota, never on the candidate nu."""
    if workload_kind(prof) == DAG:
        return dag_mod.padded_event_budget(prof, min_jobs=min_jobs,
                                           warmup_jobs=warmup_jobs)
    return qn_sim.padded_event_budget(prof.n_map, prof.n_reduce,
                                      min_jobs=min_jobs,
                                      warmup_jobs=warmup_jobs)


def amva_frontier(cls: ApplicationClass, vm: VMType, nu_lo: int, nu_hi: int,
                  use_kernel: bool = True) -> np.ndarray:
    """Evaluate T for every nu in [nu_lo, nu_hi] in ONE batched call.

    This is the beyond-paper optimization of the paper's bottleneck: instead
    of one simulator run per hill-climbing move (~minutes each in the
    original JMT setup), the whole decision frontier is evaluated at once;
    the QN simulator then verifies only the chosen point.  The frontier is
    priced from the generic ``workload_demand`` (A, B), so DAG classes get
    the same one-launch fast tier (and the same Pallas kernel) as
    MapReduce classes.
    """
    import jax.numpy as jnp
    prof = cls.profile_for(vm)
    nus = np.arange(nu_lo, nu_hi + 1)
    slots = nus * vm.slots
    a, b = workload_demand(prof)
    a_over_c = jnp.asarray(a / slots, jnp.float32)
    bb = jnp.full((len(nus),), b, jnp.float32)
    think = jnp.full((len(nus),), cls.think_ms, jnp.float32)
    h = jnp.full((len(nus),), float(cls.h_users), jnp.float32)
    if use_kernel:
        from repro.kernels.amva import ops as amva_ops
        return np.asarray(amva_ops.ps_fixed_point(a_over_c, bb, think, h))
    return np.asarray(ps_response_batch(a_over_c, bb, think, h))


def amva_nu_seed(cls: ApplicationClass, vm: VMType, nu0: int,
                 span: int, *, max_nu: int = 8192,
                 use_kernel: bool = True) -> int:
    """AMVA-frontier seed for one QN search lane: the smallest nu in a
    window around the analytic proposal ``nu0`` whose frontier response
    time meets the deadline.

    The window starts asymmetric — ``[nu0 - span//2, nu0 + span]`` —
    because the analytic proposal usually *under*shoots (the smooth model
    is optimistic) and the sweep above recovers cheaply.  When the proposal
    *over*shoots instead, the whole window can sit above the true frontier
    and its feasible minimum lands on the lower edge; in that case the
    window is re-anchored downward (keeping the known-feasible edge) until
    the minimum is interior or nu hits 1, so a pessimistic seed can no
    longer hide the frontier below the window.  Frontier calls are
    analytic (one batched AMVA evaluation each) — no simulator dispatches.
    """
    span = max(2, span)
    lo = max(1, int(nu0) - span // 2)
    hi = min(max_nu, int(nu0) + span)
    while True:
        ts = amva_frontier(cls, vm, lo, hi, use_kernel=use_kernel)
        feas = np.where(ts <= cls.deadline_ms)[0]
        if len(feas) == 0:
            return hi                       # infeasible window: sweep climbs
        nu_star = lo + int(feas[0])
        if nu_star > lo or lo == 1:
            return nu_star                  # interior (or floor) minimum
        hi = nu_star                        # feasible on the lower edge:
        lo = max(1, hi - span)              # look below, keep the edge
