"""Cross-layer regression: the ``impl`` switch must change ONLY the
simulator backend.

Racing (``run_fast``) and private-cloud coordination (``joint.coordinate``
inside ``run``) are driven end to end under ``impl="jnp"`` and
``impl="pallas"``; both must produce bit-identical solutions AND identical
``sim_stats()`` accounting — dispatches, lanes, padding, event totals are
counted at the marshaling layer, before the backend dispatch, so a kernel
swap can never silently alter the optimizer's search path or its dispatch
budget.  The exceptions are ``draw_columns``, the draw-table columns a
backend builds: one per lane under the scan, fewer under the kernel, which
builds its seed-only tables once per replication seed; and
``replay_lookups``, the replay draws the kernel's lookup copies out of the
sample lists: none under the scan, every one under the kernel."""
import jax
import pytest

from repro import obs
from repro.cloud import PrivateCloud, homogeneous_hosts
from repro.core import qn_sim
from repro.core.optimizer import DSpace4Cloud
from repro.core.problem import ApplicationClass, JobProfile, Problem, VMType
from repro.kernels import interpret_mode

STEADY = VMType(name="steady", cores=2, sigma=0.05, pi=0.20)
TURBO = VMType(name="turbo", cores=2, sigma=0.0425, pi=0.17)
ROOMY = VMType(name="roomy", cores=4, sigma=0.05, pi=0.20)
DENSE = VMType(name="dense", cores=2, sigma=0.055, pi=0.22,
               containers_per_core=2)        # same 4 slots, half the cores
PROF = JobProfile(n_map=24, n_reduce=6, m_avg=2000, r_avg=900,
                  m_max=4000, r_max=1800)
PROF_SLOW = JobProfile(n_map=24, n_reduce=6, m_avg=2000, r_avg=900,
                       m_max=6000, r_max=2700)
KW = dict(min_jobs=8, replications=1, seed=3, window=8)


def _race_problem() -> Problem:
    cls = ApplicationClass(name="etl", h_users=4, think_ms=6000.0,
                           deadline_ms=11_000.0, eta=0.25,
                           profiles={"steady": PROF, "turbo": PROF_SLOW})
    return Problem(classes=[cls], vm_types=[STEADY, TURBO])


def _coord_problem() -> Problem:
    classes = [
        ApplicationClass(name=f"c{i}", h_users=4, think_ms=6000.0,
                         deadline_ms=11_000.0, eta=0.25,
                         profiles={"roomy": PROF, "dense": PROF})
        for i in range(3)]
    return Problem(classes=classes, vm_types=[ROOMY, DENSE])


def _with_impl(impl, fn):
    """Run ``fn`` under a process-default impl with fresh counters; return
    (result, sim_stats delta)."""
    old = qn_sim.default_impl()
    qn_sim.reset_dispatch_count()
    try:
        qn_sim.set_default_impl(impl)
        out = fn()
    finally:
        qn_sim.set_default_impl(old)
    stats = qn_sim.sim_stats()
    # sim_stats() reads straight from the metrics registry: the qn.*
    # counters must BE the stats, not a drifting copy
    reg = obs.registry().snapshot("qn.")
    assert {k: reg[f"qn.{k}"] for k in stats} == stats
    return out, stats


def _assert_equivalent(make_report):
    rep_j, stats_j = _with_impl("jnp", make_report)
    rep_p, stats_p = _with_impl("pallas", make_report)
    assert stats_j["dispatches"] > 0
    cols_j, cols_p = stats_j.pop("draw_columns"), stats_p.pop("draw_columns")
    look_j = stats_j.pop("replay_lookups")
    look_p = stats_p.pop("replay_lookups")
    assert stats_j == stats_p                    # identical accounting
    assert cols_j == stats_j["lanes"]            # the scan draws per lane
    assert stats_j["dispatches"] <= cols_p < cols_j
    assert look_j == 0                           # the scan gathers in XLA
    assert look_p == stats_p["replay_draws"]     # the kernel copies each
    assert rep_j.solutions == rep_p.solutions    # bit-identical search result
    assert rep_j.total_cost_per_h == rep_p.total_cost_per_h
    return rep_j


def test_raced_run_fast_dispatch_parity():
    rep = _assert_equivalent(
        lambda: DSpace4Cloud(_race_problem(), **KW).run_fast())
    assert rep.solutions["etl"].feasible


def test_private_cloud_coordination_dispatch_parity():
    # over-committed fleet: 3 classes on roomy need 48 cores, 24 available
    # -> joint.coordinate runs real probe rounds through the fused tier
    def go():
        cloud = PrivateCloud(hosts=homogeneous_hosts(6, 4))
        return DSpace4Cloud(_coord_problem(), deployment=cloud, **KW).run()

    rep = _assert_equivalent(go)
    assert rep.deployment["coordinated"]
    assert rep.deployment["probe_rounds"] >= 1


def test_explicit_impl_overrides_process_default():
    from repro.core.evaluators import make_batched_qn_evaluator
    prob = _race_problem()
    cls, vm = prob.classes[0], prob.vm_types[0]
    old = qn_sim.default_impl()
    try:
        qn_sim.set_default_impl("pallas")
        ev_default = make_batched_qn_evaluator(min_jobs=8, replications=1,
                                               seed=3)
        ev_jnp = make_batched_qn_evaluator(min_jobs=8, replications=1,
                                           seed=3, impl="jnp")
        got_default = ev_default.evaluate_frontier(cls, vm, [2, 3, 4])
        got_jnp = ev_jnp.evaluate_frontier(cls, vm, [2, 3, 4])
    finally:
        qn_sim.set_default_impl(old)
    assert list(got_default) == list(got_jnp)    # parity, different backends


@pytest.mark.parametrize("platform,interpret,impl",
                         [("cpu", True, "jnp"), ("tpu", False, "pallas")])
def test_platform_decides_interpret_mode_and_default_impl(
        monkeypatch, platform, interpret, impl):
    monkeypatch.setattr(jax, "default_backend", lambda: platform)
    monkeypatch.setattr(qn_sim, "_DEFAULT_IMPL", None)   # $REPRO_QN_IMPL unset
    assert interpret_mode() is interpret
    assert qn_sim.default_impl() == impl


def test_other_platforms_have_no_kernel_path(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="'gpu'"):
        interpret_mode()


if __name__ == "__main__":  # pragma: no cover
    import sys
    sys.exit(pytest.main([__file__, "-q"]))
