#!/usr/bin/env python3
"""Chip smoke test: the served capacity planner, once, on a TPU.

Drives the planner through the entry points its users call — planning jobs
submitted to one ``SolverService``, and one ``DSpace4Cloud.run_fast`` solve
— at the paper's published TPC-DS sizes (Table 3, ``core/tpcds.py``), then
holds the two kernels the chip runs to their plain references:

  * service: Q3 at 1000 GB (1560 maps, 1009 reduces: 524,288-event
    simulator lanes), Q1 at 250 GB with 5 users (144/151) and Q5 at
    1000 GB (64/68), each racing the two-type ``VM_CATALOG`` against a
    deadline of ``DEADLINE_FACTOR`` times its published response time, in
    the paper's replayer mode; every job must return a feasible plan;
  * run_fast: the Q1 5-user class through the AMVA frontier tier;
  * qn_event: one fused QN window at the Q3 1000 GB event budget under
    ``impl="jnp"`` (the ``lax.scan`` reference) and ``impl="pallas"`` (the
    compiled kernel), equal per candidate within ``QN_RTOL``;
  * amva: the compiled PS fixed point against ``mva.ps_response_batch``
    on 4096 points, equal within ``AMVA_RTOL``.

It exits 1 when JAX finds no TPU, when a job fails, is shed or comes back
infeasible, or when a comparison misses its tolerance.  On success the
last line of its output is one JSON object,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.

``--chips 4`` runs the multi-chip path and nothing else: the same service
round with its lanes sharded over four chips (``partition.set_shard_spec``)
and unsharded, which must give identical plans and point estimates, with
the sharded outputs spread over all four devices.

Everything runs in this one process, which holds the chip.  Every time it
prints is the host's wall clock, compilation included — none is a device
metric.

    python chip_smoke.py [--chips 4]
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.core import mva, partition, qn_sim  # noqa: E402
from repro.core.optimizer import DSpace4Cloud  # noqa: E402
from repro.core.tpcds import TABLE3, table3_problem  # noqa: E402
from repro.kernels import interpret_mode  # noqa: E402
from repro.kernels.qn_event import ops as qn_event_ops  # noqa: E402
from repro.kernels.amva import ops as amva_ops  # noqa: E402
from repro.service import SolverService  # noqa: E402
from repro.service.jobs import JobState  # noqa: E402

# Table-3 rows (indices into tpcds.TABLE3)
Q1_250G_5U, Q3_1000G, Q5_1000G = 1, 10, 11
SERVICE_ROWS = (Q3_1000G, Q1_250G_5U, Q5_1000G)
DEADLINE_FACTOR = 1.5       # deadline = factor x the published T
QN_RTOL = 1e-4              # compiled kernel vs scan, per candidate
AMVA_RTOL = 1e-5            # compiled PS fixed point vs jnp, per point


class SmokeFailure(Exception):
    pass


def say(phase: str, msg: str) -> None:
    print(f"[{phase}] {msg}", flush=True)


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def table3_job(row: int):
    """(problem, replayer samples) of one Table-3 row at its deadline."""
    deadline = DEADLINE_FACTOR * TABLE3[row].t_published_ms
    problem, samples, _ = table3_problem(row, deadline)
    return problem, samples


def serve(rows, phase: str):
    """Submit one planning job per Table-3 row to one ``SolverService``,
    wait for every plan and check it.  Returns ``(plans, estimates)``:
    the class solutions by class name and the service's cached point
    estimates."""
    t0 = time.perf_counter()
    svc = SolverService()
    deadlines = {}
    ids = []
    for row in rows:
        problem, samples = table3_job(row)
        deadlines.update({c.name: c.deadline_ms for c in problem.classes})
        ids.append(svc.submit(problem, samples=samples))
    jobs = svc.run_until_complete()
    plans = {}
    for jid in ids:
        job = jobs[jid]
        check(job.state == JobState.DONE,
              f"{jid} ended {job.state}: {job.error}")
        for name, sol in job.report.solutions.items():
            check(sol.feasible and sol.nu >= 1
                  and sol.predicted_ms <= deadlines[name]
                  and np.isfinite(sol.cost_per_h),
                  f"{name}: plan {sol} misses its deadline "
                  f"{deadlines[name]:.0f} ms")
            say(phase, f"{name}: {sol.nu} x {sol.vm_type}, predicted T "
                f"{sol.predicted_ms / 1e3:.1f} s <= deadline "
                f"{deadlines[name] / 1e3:.1f} s, {sol.cost_per_h:.2f}/h")
            plans[name] = sol
    sched = svc.scheduler.stats()
    say(phase, f"{len(ids)} jobs in {svc.rounds} rounds, "
        f"{sched['fused_dispatches']} fused dispatches, "
        f"{partition.shard_count()} lane shard(s); host wall "
        f"{time.perf_counter() - t0:.1f} s")
    return plans, svc.cache.snapshot()


def run_fast(row: int) -> None:
    t0 = time.perf_counter()
    problem, samples = table3_job(row)
    report = DSpace4Cloud(problem, samples=samples).run_fast()
    for name, sol in report.solutions.items():
        check(sol.feasible and np.isfinite(sol.predicted_ms),
              f"run_fast {name}: infeasible plan {sol}")
        say("run_fast", f"{name}: {sol.nu} x {sol.vm_type}, predicted T "
            f"{sol.predicted_ms / 1e3:.1f} s, {sol.cost_per_h:.2f}/h; "
            f"{report.qn_dispatches} QN dispatches; host wall "
            f"{time.perf_counter() - t0:.1f} s")


def qn_window_kwargs(row: int) -> dict:
    """One QN window of the row's class on its first VM type: eight
    candidate cluster sizes around the published container count."""
    problem, _ = table3_job(row)
    cls, vm = problem.classes[0], problem.vm_types[0]
    prof = cls.profile_for(vm)
    centre = max(TABLE3[row].containers // vm.slots, 5)
    nus = np.arange(centre - 4, centre + 4)
    return dict(n_map=prof.n_map, n_reduce=prof.n_reduce,
                m_avg=prof.m_avg, r_avg=prof.r_avg, think_ms=cls.think_ms,
                h_users=cls.h_users, slots=[int(n) * vm.slots for n in nus])


def kernel_is_compiled() -> bool:
    """Whether the qn_event launch lowers to a Mosaic custom call (and not
    to interpreted XLA ops) on this backend."""
    ints = jnp.ones((8,), jnp.int32)
    floats = jnp.ones((8,), jnp.float32)
    hlo = qn_event_ops._sim_batch_jit.lower(
        ints, ints, floats, floats, floats, ints, ints, ints, None, None,
        h_users=1, max_slots=8, n_events=64, warmup_jobs=0).compile()
    return "tpu_custom_call" in hlo.as_text()


def qn_parity(row: int) -> None:
    check(not interpret_mode() and kernel_is_compiled(),
          "the qn_event kernel does not run compiled")
    kw = qn_window_kwargs(row)
    times = {}
    out = {}
    for impl in ("jnp", "pallas"):
        t0 = time.perf_counter()
        out[impl] = qn_sim.response_time_batch(impl=impl, **kw)
        times[impl] = time.perf_counter() - t0
    ref, got = out["jnp"], out["pallas"]
    check(np.isfinite(ref).all() and np.isfinite(got).all(),
          f"non-finite QN estimates: jnp {ref}, pallas {got}")
    rel = float(np.max(np.abs(got - ref) / np.abs(ref)))
    say("qn_event", f"{len(ref)} candidates, slots {kw['slots']}: "
        f"max rel diff pallas vs jnp {rel:.3e} (tolerance {QN_RTOL:g}); "
        f"host wall jnp {times['jnp']:.1f} s, pallas "
        f"{times['pallas']:.1f} s (compilation included)")
    check(rel <= QN_RTOL, f"qn_event kernel vs scan: {rel:.3e} > {QN_RTOL}")


def amva_parity(n: int = 4096) -> None:
    rng = np.random.default_rng(0)
    a = jnp.asarray(rng.uniform(1e2, 1e5, n), jnp.float32)
    b = jnp.asarray(rng.uniform(1e2, 1e4, n), jnp.float32)
    z = jnp.full((n,), 1e4, jnp.float32)
    h = jnp.asarray(rng.integers(1, 21, n), jnp.float32)
    got = np.asarray(amva_ops.ps_fixed_point(a, b, z, h))
    ref = np.asarray(mva.ps_response_batch(a, b, z, h))
    check(np.isfinite(got).all(), "non-finite AMVA fixed points")
    rel = float(np.max(np.abs(got - ref) / np.abs(ref)))
    say("amva", f"{n} points: max rel diff kernel vs jnp {rel:.3e} "
        f"(tolerance {AMVA_RTOL:g})")
    check(rel <= AMVA_RTOL, f"amva kernel vs jnp: {rel:.3e} > {AMVA_RTOL}")


def sharded_round(rows, shards: int) -> None:
    """The service round sharded over ``shards`` chips against the same
    round unsharded: identical plans and point estimates, and a fused
    dispatch whose outputs span every chip."""
    partition.set_shard_spec("off")
    plans_off, est_off = serve(rows, "service off")
    partition.set_shard_spec(shards)
    plans_on, est_on = serve(rows, f"service x{shards}")
    check(plans_on == plans_off, f"sharded plans differ: {plans_on} vs "
          f"{plans_off}")
    check(est_on == est_off, "sharded point estimates differ from the "
          "unsharded round")
    pending = qn_sim.response_time_batch(**qn_window_kwargs(rows[0]),
                                         defer=True)
    devices = pending._mean.sharding.device_set
    check(len(devices) == shards,
          f"sharded outputs span {len(devices)} device(s), not {shards}")
    say("sharded", f"{len(plans_on)} plans and {len(est_on)} point "
        f"estimates identical; outputs span {len(devices)} devices")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the lane-sharded service round")
    args = ap.parse_args(argv)
    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    try:
        check(device["platform"] == "tpu",
              f"JAX computes on {device['platform']!r}, not a TPU")
        check(device["count"] >= args.chips,
              f"{args.chips} chips asked, {device['count']} found")
        say("device", json.dumps(device))
        if args.chips > 1:
            sharded_round(SERVICE_ROWS, args.chips)
        else:
            serve(SERVICE_ROWS, "service")
            run_fast(Q1_250G_5U)
            qn_parity(Q3_1000G)
            amva_parity()
    except SmokeFailure as e:
        print(f"FAIL: {e}", file=sys.stderr, flush=True)
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
