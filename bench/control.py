#!/usr/bin/env python3
"""The control of ``correct``: the reference, one precision down, in the
program's place.

The configuration states float32; the control computes every point
estimate the sampled jobs received with the same reference in bfloat16,
puts those in place of the program's, and is compared with the float32
reference exactly as a run is.  It has to come out not correct.

    python3 bench/control.py --workload <cell> --seeds 11 12 13 --seconds 10

drives one short window of the cell per seed in this one process and
prints, per seed, the program's numbers and the control's as one JSON
line, each with the verdict of ``correct.judge`` under the
configuration's limits: the readings the limits were set from.  The
benchmark's own runs never run it.
"""
from __future__ import annotations

import json
import sys
import time
from dataclasses import replace
from pathlib import Path
from typing import List

import numpy as np


def control_answers(answers, config: dict) -> List:
    """``answers`` with every probe's estimate replaced by the bfloat16
    reference's."""
    import jax.numpy as jnp

    from bench import correct
    ref = correct.reference(config)
    vms = {vm["name"]: vm for vm in config["vm_types"]}
    s = config["solver"]
    points, where = [], []
    for j, a in enumerate(answers):
        for k, (vm, nu, _) in enumerate(a.probes):
            prof = a.profiles[vm]
            m_list, r_list = a.samples[vm]
            points.append(dict(
                h_users=a.cls["users"], think_ms=config["think_ms"],
                n_map=prof["n_map"], n_reduce=prof["n_reduce"],
                slots=nu * vms[vm]["cores"] * vms[vm]["containers_per_core"],
                m_list=m_list, r_list=r_list))
            where.append((j, k))
    est = ref.point_estimates(points, min_jobs=s["min_jobs"],
                              warmup_jobs=s["warmup_jobs"],
                              replications=s["replications"], seed=s["seed"],
                              dtype=jnp.bfloat16)
    out = [replace(a, probes=list(a.probes)) for a in answers]
    for (j, k), t in zip(where, est):
        vm, nu, _ = out[j].probes[k]
        out[j].probes[k] = (vm, nu, float(t))
    return out


def readings(cell_name: str, seeds, seconds: float, log) -> None:
    """Per seed: one window of the cell, then the program's numbers and
    the control's, each judged by the configuration's limits."""
    from bench import correct, harness
    from bench.traffic import Traffic
    from repro.service import SolverService

    cell = harness.load_cell(cell_name)
    harness.device_info(cell.chips)
    check = cell.config["correct"]
    for seed in seeds:
        t = time.perf_counter()
        traffic = Traffic(cell.config, cell.mix, seed)
        pool = traffic.pool()
        svc = SolverService()
        harness.warm_up(svc, traffic, cell.config, pool)
        done, _, _, _, _, live = harness.closed_loop(svc, pool, cell.config,
                                                     seconds)
        done += harness.settle_late(svc, live, harness.GRACE_S)
        answers = harness.answers_of(svc, done, cell.config)
        rng = np.random.default_rng([seed % (2**31 - 1),
                                     seed // (2**31 - 1), 99])
        picked = correct.sample(answers, int(check["sample_jobs"]), rng)
        run = {"failed_jobs": float(sum(d.state in harness.LOST
                                        for d in done)),
               "missing_jobs": float(len(live))}
        line = {"seed": seed, "jobs": len(done), "sampled": len(picked),
                "probes": sum(len(a.probes) for a in picked)}
        for side, got in (("program", picked),
                          ("control", control_answers(picked, cell.config))):
            numbers = dict(correct.evaluate(got, cell.config), **run)
            ok, _ = correct.judge(numbers, check["limits"])
            line[side] = dict(numbers, correct=ok)
        line["seconds"] = time.perf_counter() - t
        print(json.dumps(harness.finite(line)), flush=True)
        log(f"seed {seed}: program {line['program']}, "
            f"control {line['control']}")


def main(argv=None) -> int:
    import argparse
    ap = argparse.ArgumentParser(description="Readings of the program and "
                                 "of its bfloat16 control, per seed.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)
    readings(args.workload, args.seeds, args.seconds,
             lambda m: print(m, file=sys.stderr, flush=True))
    return 0


if __name__ == "__main__":
    root = Path(__file__).resolve().parents[1]
    sys.path[:0] = [str(root), str(root / "src")]
    from bench import harness as _harness
    _harness.cache_dir()
    sys.exit(main())
