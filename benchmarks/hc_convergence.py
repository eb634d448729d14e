"""Optimization-procedure runtime (paper §4.3 reports ~2 h per run with
JMT-in-the-loop).  Compares:

  * paper-faithful mode: analytic initial solution + Algorithm-1 HC with
    every move verified point-wise by the QN simulator (one device
    dispatch per probe x replication);
  * batched mode: same pipeline, but the HC runs window sweeps through the
    batched frontier evaluator (one fused dispatch per window);
  * beyond-paper fast mode: batched-AMVA frontier proposes nu*, ONE fused
    QN window call verifies (the Pallas-kernel-backed tier).

Reports simulator evaluations, device dispatches and wall time for all
three (same final answer — asserted within 2 VMs), with the wall time of
each mode split into XLA compile vs execute+host (the ``qn.compile_ms``
counters of ``repro.obs.compile``) — on a warm persistent compile cache
(``repro.obs.compile``) the compile share drops to ~0.

All three gaits run with ``race=False`` (the analytic-locked VM choice)
so the comparison isolates gait economics: the point-wise walk always
locks the VM type, and letting only the batched gaits also race the
catalog would charge them for extra work the classic mode never does.
The VM-type race is benchmarked separately (BENCH_vm_race.json).
"""
from __future__ import annotations

from benchmarks.common import emit, save_json, timer
from repro.core.optimizer import DSpace4Cloud
from repro.core.tpcds import scenario_problem
from repro.obs import compile as obs_compile


def _mode(report, t, c0) -> dict:
    c1 = obs_compile.compile_stats()
    compile_s = (c1["compile_ms"] - c0["compile_ms"]) / 1000.0
    return {"evals": report.evals, "wall_s": t.s,
            "compile_s": compile_s,
            "execute_s": t.s - compile_s,     # execute + host bookkeeping
            "compiles": c1["compiles"] - c0["compiles"],
            "compile_cache_hits": c1["cache_hits"] - c0["cache_hits"],
            "dispatches": report.qn_dispatches,
            "cost": report.total_cost_per_h,
            "nu": {k: v.nu for k, v in report.solutions.items()}}


def run(quick: bool = False):
    prob, samples, _ = scenario_problem("Q1", 10, 160_000.0)
    min_jobs = 15 if quick else 25
    out = {}

    tool = DSpace4Cloud(prob, min_jobs=min_jobs, replications=1,
                        samples=samples, batched=False, race=False)
    c0 = obs_compile.compile_stats()
    with timer() as t_classic:
        classic = tool.run()
    out["classic"] = _mode(classic, t_classic, c0)

    tool_b = DSpace4Cloud(prob, min_jobs=min_jobs, replications=1,
                          samples=samples, batched=True, race=False)
    c0 = obs_compile.compile_stats()
    with timer() as t_batched:
        batched = tool_b.run()
    out["batched"] = _mode(batched, t_batched, c0)

    tool2 = DSpace4Cloud(prob, min_jobs=min_jobs, replications=1,
                         samples=samples, batched=True, race=False)
    c0 = obs_compile.compile_stats()
    with timer() as t_fast:
        fast = tool2.run_fast()
    out["fast"] = _mode(fast, t_fast, c0)

    agree = all(
        abs(classic.solutions[k].nu - batched.solutions[k].nu) <= 2
        and abs(classic.solutions[k].nu - fast.solutions[k].nu) <= 2
        for k in classic.solutions)
    assert agree, f"modes disagree beyond 2 VMs: {out}"
    save_json("hc_convergence", out)
    emit("hc_convergence", t_classic.s * 1e6,
         f"classic_evals={classic.evals};classic_s={t_classic.s:.1f};"
         f"classic_compile_s={out['classic']['compile_s']:.1f};"
         f"classic_disp={classic.qn_dispatches};"
         f"batched_evals={batched.evals};batched_s={t_batched.s:.1f};"
         f"batched_compile_s={out['batched']['compile_s']:.1f};"
         f"batched_disp={batched.qn_dispatches};"
         f"fast_evals={fast.evals};fast_s={t_fast.s:.1f};"
         f"fast_compile_s={out['fast']['compile_s']:.1f};"
         f"fast_disp={fast.qn_dispatches};agree={agree};"
         f"paper_wall=~7200s",
         metrics=out)
    return out


if __name__ == "__main__":
    run()
