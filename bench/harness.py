"""One run of one cell: set-up, the measured window, the traced reading and
the comparison with the reference.

The system under test is ``repro.service.SolverService``, driven in this
process.  Tenants plan in a closed loop with no think time: each submits
its next job as soon as its last one settles.  A job's time-to-plan runs
from its ``submit`` to the end of the ``step()`` round in which it
settled.  The window opens when the tenants submit their first jobs and
closes at the end of the first round that ends ``seconds`` or more later;
no job is submitted after that.
"""
from __future__ import annotations

import gc
import importlib
import json
import os
import shutil
import sys
import tempfile
import time
from dataclasses import dataclass
from pathlib import Path
from typing import Dict, List, Optional

import numpy as np

from bench import correct, stats
from bench.traffic import PlannedJob, Traffic

ROOT = Path(__file__).resolve().parents[1]
SETTLED = ("done", "infeasible", "failed", "shed")
LOST = ("failed", "shed")
SLIDES_UP = 2               # window slides above the seed set-up warms
GRACE_S = 60.0              # how long past the close a late answer may come


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


class PoolExhausted(RuntimeError):
    """A tenant used up its jobs: the run would have to recycle them."""


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    mix: dict
    end_to_end: List[dict]
    per_layer: List[dict]


def load_cell(name: str, root: Path = ROOT) -> Cell:
    spec = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    w = cells[name]
    cfg = {c["name"]: c for c in spec["configs"]}[w["config"]]
    config = json.loads((root / cfg["file"]).read_text())
    mix = json.loads((root / "bench" / "traffic"
                      / f"{w['traffic']}.json").read_text())

    def here(m):
        return name in m.get("workloads", [name])

    return Cell(name=name, chips=int(w["chips"]), config=config, mix=mix,
                end_to_end=[m for m in spec["end_to_end"] if here(m)],
                per_layer=[m for m in spec["per_layer"] if here(m)])


def device_info(chips: int, require_chip: bool = True) -> dict:
    import jax
    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    if require_chip and (info["platform"] == "cpu" or len(devs) < chips):
        raise NoChip(f"JAX finds {len(devs)} {info['platform']} device(s); "
                     f"the cell needs {chips} accelerator chip(s)")
    return info


def memory_peak_bytes() -> Optional[int]:
    import jax
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.devices()]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


# ------------------------------------------------------------ the system
def problem_of(job: PlannedJob, config: dict):
    """The job as the planner's API takes it: ``(problem, samples)``."""
    from repro.core.problem import (ApplicationClass, JobProfile, Problem,
                                    VMType)
    c = {x["name"]: x for x in config["classes"]}[job.cls]
    vms = [VMType(**v) for v in config["vm_types"]]
    cls = ApplicationClass(
        name=job.cls, h_users=int(c["users"]), think_ms=config["think_ms"],
        deadline_ms=job.deadline_ms, eta=config["eta"],
        profiles={vm: JobProfile(**p) for vm, p in job.profiles.items()})
    samples = {(job.cls, vm): job.samples[vm] for vm in job.profiles}
    return Problem(classes=[cls], vm_types=vms), samples


def submit(svc, job: PlannedJob, config: dict, tag: str) -> str:
    problem, samples = problem_of(job, config)
    s = config["solver"]
    return svc.submit(problem, samples=samples, tag=tag,
                      window=s["window"], min_jobs=s["min_jobs"],
                      warmup_jobs=s["warmup_jobs"],
                      replications=s["replications"], seed=s["seed"])


def drain(svc, ids) -> None:
    for _ in range(10_000):
        if all(svc.job(j).state in SETTLED for j in ids):
            return
        svc.step()
    raise RuntimeError("set-up jobs did not settle")


def sweep_windows(nu0: int, window: int, up: int):
    """``(top, lanes)`` of every window the planner's sweep from the
    analytic seed ``nu0`` can dispatch: the seed's own window
    ``[nu0 - window + 1, nu0]``, every slide below it, and ``up`` slides
    above it."""
    out = []
    top = nu0
    while top >= 1:
        out.append((top, min(window, top)))
        top -= window
    out += [(nu0 + k * window, window) for k in range(1, up + 1)]
    return out


def warm_up(svc, traffic: Traffic, config: dict,
            pool: List[List[PlannedJob]]) -> dict:
    """Plan the set-up jobs, then compile and run once every simulator
    program that a job of ``pool`` can dispatch.  Returns what it did.

    A job dispatches one window of consecutive fleet sizes per VM type and
    round, from the planner's analytic seed (``rank_vm_types`` on the job's
    own profile and deadline) down, or up while no size meets the
    deadline.  The program's shape of a window is its event budget, users,
    replay-list lengths, lane and slot buckets; each shape is dispatched
    once here, so that nothing compiles in the window.
    """
    from repro.core import partition, qn_sim, shapes
    from repro.core.milp import rank_vm_types

    t0 = time.perf_counter()
    jobs = traffic.setup_jobs()
    drain(svc, [submit(svc, j, config, "setup") for j in jobs])
    t1 = time.perf_counter()

    s = config["solver"]
    vms = {v["name"]: v for v in config["vm_types"]}
    users = {c["name"]: int(c["users"]) for c in config["classes"]}
    todo = {}
    for job in (j for tenant in pool for j in tenant):
        problem, _ = problem_of(job, config)
        for sol in rank_vm_types(problem)[job.cls]:
            vm = vms[sol.vm_type]
            per_vm = vm["cores"] * vm["containers_per_core"]
            prof = job.profiles[sol.vm_type]
            ms, rs = job.samples[sol.vm_type]
            budget = qn_sim.padded_event_budget(
                prof["n_map"], prof["n_reduce"], min_jobs=s["min_jobs"],
                warmup_jobs=s["warmup_jobs"])
            for top, lanes in sweep_windows(sol.nu, s["window"], SLIDES_UP):
                shards = partition.shard_count(lanes)
                key = (budget, users[job.cls], len(ms), len(rs), shards,
                       partition.bucket_lanes(lanes, shards),
                       shapes.bucket_slots(top * per_vm))
                todo.setdefault(key, (job, prof, ms, rs, per_vm, top, lanes))
    t2 = time.perf_counter()
    pending = []
    for job, prof, ms, rs, per_vm, top, lanes in todo.values():
        pending.append(qn_sim.response_time_batch(
            prof["n_map"], prof["n_reduce"], prof["m_avg"], prof["r_avg"],
            config["think_ms"], users[job.cls],
            [n * per_vm for n in range(top - lanes + 1, top + 1)],
            min_jobs=s["min_jobs"], warmup_jobs=s["warmup_jobs"],
            seed=s["seed"], replications=s["replications"], m_samples=ms,
            r_samples=rs, defer=True))
    qn_sim.resolve_batches(pending)
    t3 = time.perf_counter()
    return {"setup_jobs": len(jobs), "setup_jobs_s": t1 - t0,
            "seeds_s": t2 - t1, "shapes": len(todo), "shapes_s": t3 - t2}


def reader(metric: str, root: Path = ROOT):
    """The per-layer metric's reader, ``bench/metrics/<metric>.py``."""
    import importlib.util
    path = root / "bench" / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{metric.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def kernel_events(root: Path = ROOT) -> Dict[str, tuple]:
    """Device event names of every kernel with a roofline file."""
    out = {}
    for path in sorted((root / "bench" / "roofline").glob("*.py")):
        if path.stem != "__init__":
            mod = importlib.import_module(f"bench.roofline.{path.stem}")
            out[path.stem] = tuple(mod.EVENT_NAMES)
    return out


def peak(kind: str, root: Path = ROOT) -> dict:
    table = json.loads((root / "bench" / "peaks.json").read_text())
    if kind not in table["devices"]:
        raise KeyError(f"no peaks for device kind {kind!r} in peaks.json")
    return table["devices"][kind]


# ---------------------------------------------------------------- window
@dataclass
class Settled:
    job_id: str
    job: PlannedJob
    submitted: float
    settled: float
    state: str


def closed_loop(svc, pool: List[List[PlannedJob]], config: dict,
                seconds: float):
    """Drive the window.  Returns ``(settled jobs, attempted, t0, t1,
    rounds, live)``: ``live`` maps the jobs still in flight at the close
    to ``(job, submit time)``."""
    import jax.profiler

    nxt = [0] * len(pool)
    live: Dict[str, tuple] = {}

    def send(t: int) -> None:
        if nxt[t] >= len(pool[t]):
            raise PoolExhausted(f"tenant {t} used all {len(pool[t])} jobs")
        job = pool[t][nxt[t]]
        nxt[t] += 1
        t_sub = time.perf_counter()
        live[submit(svc, job, config, f"tenant-{t}")] = (job, t_sub)

    done: List[Settled] = []
    t0 = time.perf_counter()
    t_end = t0 + seconds
    for t in range(len(pool)):
        send(t)
    attempted = len(pool)
    rounds = 0
    while True:
        with jax.profiler.TraceAnnotation("bench.round"):
            svc.step()
        now = time.perf_counter()
        rounds += 1
        for jid in list(live):
            state = svc.job(jid).state
            if state in SETTLED:
                job, t_sub = live.pop(jid)
                done.append(Settled(jid, job, t_sub, now, str(state)))
                if now < t_end:
                    send(job.tenant)
                    attempted += 1
        if now >= t_end:
            return done, attempted, t0, now, rounds, live


def settle_late(svc, live: Dict[str, tuple], grace_s: float
                ) -> List[Settled]:
    """After the close: step until every job still in flight has settled,
    for at most ``grace_s`` seconds.  Jobs left in ``live`` never came."""
    late = []
    t_stop = time.perf_counter() + grace_s
    while live and time.perf_counter() < t_stop:
        svc.step()
        now = time.perf_counter()
        for jid in list(live):
            state = svc.job(jid).state
            if state in SETTLED:
                job, t_sub = live.pop(jid)
                late.append(Settled(jid, job, t_sub, now, str(state)))
    return late


def answers_of(svc, done: List[Settled], config: dict
               ) -> List[correct.Answer]:
    classes = {c["name"]: c for c in config["classes"]}
    out = []
    for d in done:
        if d.state in LOST:
            continue
        rep = svc.job(d.job_id).report
        (sol,) = rep.solutions.values()
        probes = [(tr.vm, int(nu), float(t)) for tr in rep.traces.values()
                  for nu, t, _ in tr.moves]
        out.append(correct.Answer(
            cls=classes[d.job.cls], deadline_ms=d.job.deadline_ms,
            profiles=d.job.profiles, samples=d.job.samples,
            plan_vm=sol.vm_type, plan_nu=int(sol.nu),
            plan_cost=float(sol.cost_per_h),
            feasible=bool(sol.feasible), probes=probes))
    return out


# ------------------------------------------------------------------- run
def run(cell, seed: int, seconds: float, trace: bool, *,
        t_start: float, require_chip: bool = True, root: Path = ROOT,
        log=print) -> dict:
    """One run of ``cell`` (a ``Cell``, or its name in BENCHMARK.json)."""
    if isinstance(cell, str):
        cell = load_cell(cell, root)
    device = device_info(cell.chips, require_chip)
    from repro.obs import compile as obs_compile
    from repro.obs import metrics as obs_metrics
    from repro.service import SolverService

    t_pool = time.perf_counter()
    traffic = Traffic(cell.config, cell.mix, seed)
    pool = traffic.pool()
    t_pool = time.perf_counter() - t_pool
    svc = SolverService()
    warm = warm_up(svc, traffic, cell.config, pool)
    reg = obs_metrics.registry()
    built = obs_compile.compile_stats()
    compiles0 = built["compiles"]
    before = reg.snapshot()
    setup_s = time.perf_counter() - t_start
    log(f"set-up {setup_s:.3f} s: pool of {sum(map(len, pool))} jobs "
        f"{t_pool:.3f} s, {warm['setup_jobs']} set-up jobs "
        f"{warm['setup_jobs_s']:.3f} s, analytic seeds {warm['seeds_s']:.3f}"
        f" s, {warm['shapes']} simulator shapes {warm['shapes_s']:.3f} s; "
        f"{built['compiles']} compiles ({built['compile_ms'] / 1e3:.1f} s), "
        f"{built['cache_hits']} from the cache")

    tracer = trace_dir = None
    if trace:
        import jax.profiler
        from repro import obs
        trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
        tracer = obs.install(obs.Tracer())
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    try:
        done, attempted, t0, t1, rounds, live = closed_loop(
            svc, pool, cell.config, seconds)
    finally:
        if trace:
            import jax.profiler
            from repro import obs
            jax.profiler.stop_trace()
            obs.uninstall()
    window_s = t1 - t0
    counters = obs_metrics.counter_delta(before, reg.snapshot())
    compiles = obs_compile.compile_stats()["compiles"] - compiles0
    device["memory_peak_bytes"] = memory_peak_bytes()
    lat = [d.settled - d.submitted for d in done]
    failed = sum(d.state in LOST for d in done)
    log(f"window {window_s:.3f} s: {len(done)} jobs settled in {rounds} "
        f"rounds, {attempted} submitted, {failed} failed, "
        f"{compiles} compiles")

    result = {"correct": False, "attempted": attempted, "failed": failed}
    if not done:
        result["metrics"] = {}
    elif trace:
        from bench import trace_reduce
        red = trace_reduce.reduce_dir(trace_dir, kernel_events())
        shutil.rmtree(trace_dir, ignore_errors=True)
        ctx = dict(cell=cell, done=done, window_s=window_s, rounds=rounds,
                   counters=counters, compiles=compiles, trace=red,
                   spans=list(tracer.spans), device=device,
                   peak=peak(device["kind"], root))
        metrics = {}
        for m in cell.per_layer:
            v = reader(m["name"], root).read(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        for name, note in ctx.get("notes", {}).items():
            log(f"{name}: {note}")
            if name in metrics:
                metrics[name]["note"] = note
        device["busy_s"] = red.busy_s
        device["window_s"] = red.window_s
        result["metrics"] = metrics
        result["breakdown"] = red.breakdown
    else:
        e2e = {"plan_p50_s": stats.percentile(lat, 50),
               "plan_p95_s": stats.percentile(lat, 95),
               "jobs_per_s": stats.rate(len(done), window_s),
               "setup_s": setup_s}
        log("end to end: " + ", ".join(f"{k} {v!r}" for k, v in e2e.items()))
        result["metrics"] = {m["name"]: {"value": e2e[m["name"]],
                                         "unit": m["unit"]}
                             for m in cell.end_to_end}
    result["device"] = device

    # the comparison: after the window, with the program's state freed
    late = settle_late(svc, live, GRACE_S)
    answers = answers_of(svc, done + late, cell.config)
    del svc, pool, traffic
    gc.collect()
    check = cell.config["correct"]
    rng = np.random.default_rng([seed % (2**31 - 1), seed // (2**31 - 1),
                                 99])
    picked = correct.sample(answers, int(check["sample_jobs"]), rng)
    t_ref = time.perf_counter()
    numbers = correct.evaluate(picked, cell.config)
    numbers["failed_jobs"] = float(
        failed + sum(d.state in LOST for d in late))
    numbers["missing_jobs"] = float(len(live))
    if not answers:
        numbers["point_gap"] = numbers["plan_faults"] = float("inf")
    ok, checks = correct.judge(numbers, check["limits"])
    log(f"reference: {len(picked)} jobs, "
        f"{sum(len(a.probes) for a in picked)} probes, "
        f"{time.perf_counter() - t_ref:.3f} s")
    result["correct"] = ok
    result["checks"] = checks
    return result


def finite(obj):
    """The result with every non-finite number written as a string."""
    if isinstance(obj, dict):
        return {k: finite(v) for k, v in obj.items()}
    if isinstance(obj, (list, tuple)):
        return [finite(v) for v in obj]
    if isinstance(obj, float) and not np.isfinite(obj):
        return str(obj)
    return obj


def main(argv=None, *, t_start: float) -> int:
    import argparse
    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    def log(msg):
        print(msg, file=sys.stderr, flush=True)

    try:
        result = run(args.workload, args.seed, args.seconds,
                     bool(args.trace), t_start=t_start, log=log)
    except NoChip as e:
        log(f"no result: {e}")
        return 3
    for name, c in result["checks"].items():
        log(f"check {name}: {c['value']!r} (limit {c['limit']!r})")
    print(json.dumps(finite(result), allow_nan=False), flush=True)
    return 0


def cache_dir() -> str:
    """JAX's persistent compilation cache: the directory that
    ``JAX_COMPILATION_CACHE_DIR`` names where it is set, else a fixed
    directory in the checkout, handed to the program through that
    variable."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") \
        or str(ROOT / ".bench-jax-cache")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = path
    os.makedirs(path, exist_ok=True)
    return path
