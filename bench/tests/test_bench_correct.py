"""The comparison that decides ``correct``, driven end to end at a CPU
size: a sound run passes, the bfloat16 control and every planted fault
of the timed path fail.  The harness's look for a chip is skipped."""
import json
import time
from pathlib import Path

import numpy as np
import pytest

from bench import control, correct, harness

DATA = Path(__file__).resolve().parents[1] / "testdata"
ROOT = Path(__file__).resolve().parents[2]
SEED = 2**31 + 1234


@pytest.fixture(scope="module")
def cell():
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    config = json.loads((DATA / "tiny_config.json").read_text())
    mix = json.loads((DATA / "tiny_mix.json").read_text())
    return harness.Cell("tiny", 1, config, mix, spec["end_to_end"],
                        spec["per_layer"])


def run(cell, seed=SEED, log=lambda m: None):
    return harness.run(cell, seed, 1.0, False, t_start=time.perf_counter(),
                       require_chip=False, log=log)


def test_sound_run_is_correct(cell):
    lines = []
    r = run(cell, log=lines.append)
    window = [x for x in lines if x.startswith("window ")]
    assert window and window[0].endswith(" 0 compiles"), lines
    assert r["correct"], r["checks"]
    assert r["checks"]["point_gap"]["value"] == 0.0
    assert r["attempted"] >= harness.Traffic(cell.config, cell.mix,
                                             SEED).tenants
    assert set(r["metrics"]) == {"plan_p50_s", "plan_p95_s", "jobs_per_s",
                                 "setup_s"}
    assert list(r)[-1] == "checks"


def test_control_is_not_correct(cell, monkeypatch):
    """The reference in bfloat16, in the program's place."""
    from repro.service import SolverService
    from bench.traffic import Traffic
    traffic = Traffic(cell.config, cell.mix, SEED)
    pool = traffic.pool()
    svc = SolverService()
    harness.warm_up(svc, traffic, cell.config, pool)
    done, *_ = harness.closed_loop(svc, pool, cell.config, 1.0)
    answers = harness.answers_of(svc, done, cell.config)[:4]
    sound = correct.evaluate(answers, cell.config)
    ctl = correct.evaluate(control.control_answers(answers, cell.config),
                           cell.config)
    limits = cell.config["correct"]["limits"]
    assert sound["point_gap"] <= limits["point_gap"]
    assert ctl["point_gap"] > limits["point_gap"]


def _unchanged_state(monkeypatch):
    """Once set-up is done, a service round returns with every job as it
    was."""
    from repro.service import SolverService
    real = harness.warm_up

    def warm_up(*args):
        out = real(*args)
        monkeypatch.setattr(SolverService, "step", lambda self: True)
        return out
    monkeypatch.setattr(harness, "warm_up", warm_up)
    monkeypatch.setattr(harness, "GRACE_S", 1.0)


def _half_the_batch(monkeypatch):
    """Only the first replication of every point is combined."""
    from repro.core import qn_sim
    real = qn_sim.PendingBatch._finish

    def finish(self, mean, cnt):
        mean = np.asarray(mean).reshape(-1, self._R)
        cnt = np.asarray(cnt).reshape(-1, self._R).copy()
        cnt[:, 1:] = 0
        return real(self, mean.reshape(-1), cnt.reshape(-1))
    monkeypatch.setattr(qn_sim.PendingBatch, "_finish", finish)


def _shards_not_gathered(monkeypatch):
    """The second half of each dispatch's lanes never comes back from its
    device: the first half's results stand in for it."""
    from repro.core import qn_sim
    real = qn_sim.PendingBatch._finish

    def finish(self, mean, cnt):
        mean, cnt = np.array(mean), np.array(cnt)
        half = len(mean) // 2
        if half:
            mean[half:2 * half] = mean[:half]
            cnt[half:2 * half] = cnt[:half]
        return real(self, mean, cnt)
    monkeypatch.setattr(qn_sim.PendingBatch, "_finish", finish)


def _estimate_altered(monkeypatch):
    """Every estimate comes back 0.1 % high from the dispatch."""
    from repro.core import qn_sim
    real = qn_sim.PendingBatch._finish

    def finish(self, mean, cnt):
        return real(self, np.asarray(mean) * 1.001, cnt)
    monkeypatch.setattr(qn_sim.PendingBatch, "_finish", finish)


def _plan_altered(monkeypatch):
    """The search reports one VM more than it chose."""
    from repro.core import hillclimb
    real = hillclimb._solution
    monkeypatch.setattr(hillclimb, "_solution",
                        lambda cls, vm, nu, t: real(cls, vm, nu + 1, t))


@pytest.mark.parametrize("fault", [_unchanged_state, _half_the_batch,
                                   _shards_not_gathered, _estimate_altered,
                                   _plan_altered])
def test_fault_is_not_correct(cell, monkeypatch, fault):
    fault(monkeypatch)
    r = run(cell, SEED + 1)
    assert not r["correct"], r["checks"]
