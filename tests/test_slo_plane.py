"""Per-tenant SLO plane: labeled metrics, P² quantiles, OpenMetrics
round-trip, the scrape endpoints, and the perf-regression sentinel.

The contracts under test:

  * labels are *additive* — the bare metric keeps its process-global
    value (``sim_stats()`` bit-parity), children only refine it, and
    cardinality is bounded by the ``_other`` overflow guard;
  * the P² streaming estimator tracks ``numpy.percentile`` without
    buffering samples (property-tested over seeded random streams —
    hypothesis-style generation without the dependency);
  * ``parse_openmetrics(render_openmetrics())`` round-trips every metric
    kind and rejects malformed payloads (the validator CI scrapes with);
  * ``/metrics`` + ``/healthz`` + ``/statz`` serve real data in-process;
  * tracing + labels stay observationally inert: solver results are
    bit-identical to the untraced path (extends the PR 7 parity test);
  * ``benchmarks/regress.py`` passes its own distillate and fails on an
    injected dispatch-count regression (the CI negative test).
"""
import json
import math
import urllib.request

import numpy as np
import pytest

from repro import obs
from repro.core import qn_sim
from repro.core.optimizer import DSpace4Cloud
from repro.core.problem import ApplicationClass, JobProfile, Problem, VMType
from repro.obs.export import parse_openmetrics, render_openmetrics
from repro.obs.metrics import MetricsRegistry
from repro.obs.slo import P2Quantile, SLOTracker, solve_slo_summary
from repro.service import SolverService

PROF = JobProfile(n_map=8, n_reduce=2, m_avg=1500, m_max=3000,
                  r_avg=700, r_max=1500)
VM = VMType(name="vm", cores=2, sigma=0.05, pi=0.20)
KW = dict(min_jobs=6, replications=1, seed=3)


def one_class_problem(deadline_ms=45_000.0, name="c"):
    cls = ApplicationClass(name=name, h_users=2, think_ms=8000.0,
                           deadline_ms=deadline_ms, eta=0.25,
                           profiles={"vm": PROF})
    return Problem(classes=[cls], vm_types=[VM])


# ---------------------------------------------------------- labeled metrics

def test_labels_are_additive_children_with_flat_snapshot_keys():
    reg = MetricsRegistry()
    c = reg.counter("qn.dispatches")
    c.inc(5)
    c.labels(kind="dag", impl="jnp").inc(3)
    c.labels(kind="mapreduce", impl="jnp").inc(2)
    c.labels(kind="dag", impl="jnp").inc()      # same child, get-or-create
    snap = reg.snapshot()
    assert snap["qn.dispatches"] == 5           # base value untouched
    assert snap['qn.dispatches{impl="jnp",kind="dag"}'] == 4
    assert snap['qn.dispatches{impl="jnp",kind="mapreduce"}'] == 2


def test_label_cardinality_guard_collapses_to_other():
    reg = MetricsRegistry()
    c = reg.counter("t.c")
    c.max_label_sets = 3
    for i in range(10):
        c.labels(tenant=f"t{i}").inc()
    kids = c.children()
    assert len(kids) <= 4                       # 3 real + 1 overflow
    assert (("tenant", "_other"),) in kids
    assert kids[(("tenant", "_other"),)].value == 7
    assert c.label_sets_dropped == 7


def test_labels_reject_empty_and_nested():
    reg = MetricsRegistry()
    c = reg.counter("x")
    with pytest.raises(ValueError):
        c.labels()
    with pytest.raises(TypeError):
        c.labels(a="1").labels(b="2")


def test_reset_by_prefix_zeroes_children_but_keeps_objects():
    reg = MetricsRegistry()
    c = reg.counter("a.hits")
    child = c.labels(tenant="t")
    child.inc(7)
    g = reg.gauge("b.level")
    g.labels(tenant="t").set(4.0)
    reg.reset("a.")
    assert child.value == 0                     # same object, zeroed
    assert c.labels(tenant="t") is child
    assert reg.snapshot()['b.level{tenant="t"}'] == 4.0
    reg.reset()
    assert reg.snapshot()['b.level{tenant="t"}'] == 0.0


def test_histogram_snapshot_mean_and_bounds():
    reg = MetricsRegistry()
    h = reg.histogram("h", buckets=(1, 2, 5))
    for v in (0.5, 1.5, 3.0, 7.0):
        h.observe(v)
    snap = h.snapshot()
    assert snap["bounds"] == [1.0, 2.0, 5.0]
    assert snap["mean"] == pytest.approx(3.0)
    assert sum(snap["buckets"].values()) == snap["count"] == 4
    assert reg.histogram("h0").snapshot()["mean"] == 0.0


def test_labeled_histogram_children_share_buckets():
    reg = MetricsRegistry()
    h = reg.histogram("h", buckets=(10, 20))
    child = h.labels(tenant="t")
    child.observe(15)
    assert child.buckets == h.buckets
    assert child.snapshot()["buckets"]["20.0"] == 1


# ------------------------------------------------------------- P² quantiles

@pytest.mark.parametrize("seed", range(6))
@pytest.mark.parametrize("q", [0.5, 0.9])
@pytest.mark.parametrize("dist", ["uniform", "exponential", "lognormal"])
def test_p2_tracks_numpy_percentile(seed, q, dist):
    # hypothesis-style property sweep without the dependency: many seeded
    # random streams, accuracy judged in *rank* space (the estimate must
    # land within a few percentile ranks of the target), which is scale-
    # free across distributions
    rng = np.random.default_rng(seed)
    xs = getattr(rng, dist)(size=400) if dist != "lognormal" \
        else rng.lognormal(0.0, 1.0, size=400)
    est = P2Quantile(q)
    for x in xs:
        est.observe(x)
    rank = (xs <= est.value()).mean()
    assert abs(rank - q) < 0.06, (dist, seed, q, rank)


def test_p2_exact_on_small_samples_and_rejects_bad_q():
    est = P2Quantile(0.5)
    for v in (5.0, 1.0, 3.0):
        est.observe(v)
    assert est.value() == 3.0                   # exact while n <= 5
    assert P2Quantile(0.5).value() == 0.0
    with pytest.raises(ValueError):
        P2Quantile(1.5)


def test_p2_constant_memory():
    est = P2Quantile(0.99)
    for i in range(10_000):
        est.observe(float(i % 997))
    assert len(est._first) == 5                 # no unbounded buffers
    assert len(est._h) == 5


# ------------------------------------------------------------- SLO tracking

def test_solve_slo_summary_margins_and_violations():
    prob = one_class_problem(deadline_ms=10_000.0)

    class Sol:
        predicted_ms = 4_000.0
        feasible = True

    s = solve_slo_summary(prob, {"c": Sol()}, wall_s=0.5)
    assert s["met"] and s["violations"] == 0
    assert s["worst_margin_ms"] == pytest.approx(6_000.0)

    class Late:
        predicted_ms = 12_000.0
        feasible = True

    s = solve_slo_summary(prob, {"c": Late()}, wall_s=0.5)
    assert not s["met"] and s["violations"] == 1

    class Infeasible:
        predicted_ms = math.inf
        feasible = False

    s = solve_slo_summary(prob, {"c": Infeasible()}, wall_s=0.5)
    assert not s["met"] and s["violations"] == 1


def test_slo_tracker_burn_rate_and_gauges():
    tr = SLOTracker(budget=0.10)
    ok = {"met": True, "worst_margin_ms": 50.0, "violations": 0}
    bad = {"met": False, "worst_margin_ms": -5.0, "violations": 1}
    for _ in range(9):
        tr.observe("acme", ok, wall_ms=10.0)
    tr.observe("acme", bad, wall_ms=30.0)
    s = tr.summary()["acme"]
    assert s["solves"] == 10 and s["violations"] == 1
    assert s["burn_rate"] == pytest.approx(1.0)   # exactly at budget
    assert s["worst_margin_ms"] == -5.0
    snap = obs.registry().snapshot("slo.")
    assert snap['slo.burn_rate{tenant="acme"}'] == pytest.approx(1.0)
    assert snap['slo.margin_ms{tenant="acme"}'] == -5.0


def test_run_report_carries_slo_summary():
    rep = DSpace4Cloud(one_class_problem(), batched=True, window=4,
                       **KW).run()
    assert rep.slo is not None
    assert rep.slo["classes"] == 1
    assert rep.slo["met"] == all(
        s.feasible for s in rep.solutions.values())
    assert json.loads(rep.to_json())["slo"]["classes"] == 1


# --------------------------------------------------------- OpenMetrics text

def _filled_registry():
    reg = MetricsRegistry()
    c = reg.counter("qn.dispatches", "device dispatches")
    c.inc(7)
    c.labels(kind="dag", impl="jnp").inc(3)
    g = reg.gauge("admission.inflight_events")
    g.set(123.5)
    h = reg.histogram("service.round_ms", buckets=(1, 5, 25))
    for v in (0.2, 3.0, 50.0):
        h.observe(v)
    h.labels(tenant="acme").observe(2.0)
    return reg


def test_openmetrics_round_trip():
    reg = _filled_registry()
    text = render_openmetrics(reg)
    assert text.endswith("# EOF\n")
    fams = parse_openmetrics(text)
    assert fams["qn_dispatches"]["type"] == "counter"
    assert fams["qn_dispatches"]["samples"]["qn_dispatches_total"] == 7
    assert fams["qn_dispatches"]["samples"][
        'qn_dispatches_total{impl="jnp",kind="dag"}'] == 3
    assert fams["admission_inflight_events"]["samples"][
        "admission_inflight_events"] == 123.5
    hs = fams["service_round_ms"]["samples"]
    assert hs["service_round_ms_count"] == 3
    assert hs['service_round_ms_bucket{le="+Inf"}'] == 3
    assert hs['service_round_ms_bucket{le="5"}'] == 2       # cumulative
    assert hs['service_round_ms_count{tenant="acme"}'] == 1


def test_openmetrics_parser_rejects_malformed():
    good = render_openmetrics(_filled_registry())
    with pytest.raises(ValueError):
        parse_openmetrics(good.replace("# EOF\n", ""))      # no terminator
    with pytest.raises(ValueError):
        parse_openmetrics("qn_x_total 3\n# EOF\n")          # no TYPE line
    with pytest.raises(ValueError):
        parse_openmetrics("# TYPE h histogram\n"
                          "h_bucket{le=\"1\"} 5\n"
                          "h_bucket{le=\"+Inf\"} 3\n"       # non-cumulative
                          "# EOF\n")
    with pytest.raises(ValueError):
        parse_openmetrics("# TYPE h histogram\n"
                          "h_bucket{le=\"1\"} 1\n"          # no +Inf bucket
                          "# EOF\n")


# ---------------------------------------------------------- scrape surface

def test_endpoints_served_and_scraped_in_process():
    svc = SolverService(window=4)
    handle = svc.serve_http()
    try:
        jid = svc.submit(one_class_problem(), tag="acme", **KW)
        svc.submit(one_class_problem(), tag="beta", **KW)
        svc.run_until_complete()
        assert svc.job(jid).state == "done"

        with urllib.request.urlopen(handle.url + "/healthz",
                                    timeout=10) as r:
            health = json.loads(r.read())
        assert health["ok"] and health["queue_depth"] == 0
        assert health["rounds"] == svc.rounds

        with urllib.request.urlopen(handle.url + "/metrics",
                                    timeout=10) as r:
            assert r.headers["Content-Type"].startswith(
                "application/openmetrics-text")
            fams = parse_openmetrics(r.read().decode())
        acme = [k for f in fams.values() for k in f["samples"]
                if 'tenant="acme"' in k]
        assert any(k.startswith("fusion_points_total") for k in acme)
        assert any(k.startswith("slo_burn_rate") for k in acme)

        with urllib.request.urlopen(handle.url + "/statz",
                                    timeout=10) as r:
            statz = json.loads(r.read())
        assert statz["tenants"]["acme"]["points"] > 0
        # per-tenant dispatch attribution is exact: the per-job split sums
        # to the scheduler's own total
        total = sum(t["points_dispatched"]
                    for t in statz["tenants"].values())
        assert total == svc.scheduler.points_dispatched
        assert statz["slo"]["acme"]["solves"] == 1
        kinds = {ev["kind"] for ev in statz["recorder_tail"]}
        assert "finish" in kinds and "round" in kinds

        with pytest.raises(urllib.error.HTTPError):
            urllib.request.urlopen(handle.url + "/nope", timeout=10)
    finally:
        svc.stop_http()


def test_serve_http_is_idempotent():
    svc = SolverService()
    try:
        assert svc.serve_http() is svc.serve_http()
    finally:
        svc.stop_http()


# ----------------------------------------------------- inertness with labels

def test_tracing_and_labels_stay_bit_inert():
    # extends the PR 7 tracing-inertness parity test: with a tracer
    # installed AND tenant/kind/impl labels active (service path), the
    # solver's solutions and dispatch accounting are bit-identical to a
    # bare solve
    prob = one_class_problem()

    def solve():
        before = qn_sim.sim_stats()
        rep = DSpace4Cloud(prob, batched=True, window=4, **KW).run()
        after = qn_sim.sim_stats()
        return rep, {k: after[k] - before[k] for k in after}

    rep_off, stats_off = solve()
    with obs.tracing():
        # touch labeled children of the hot-path families while solving
        obs.registry().counter("qn.dispatches").labels(
            kind="mapreduce", impl="jnp")
        rep_on, stats_on = solve()
    assert stats_on == stats_off
    assert rep_on.solutions == rep_off.solutions
    drop = "solve_wall_ms"                      # wall clock, not results
    assert {k: v for k, v in rep_on.slo.items() if k != drop} \
        == {k: v for k, v in rep_off.slo.items() if k != drop}


def test_recorder_events_carry_wall_tenant_and_dump_provenance(tmp_path):
    rec = obs.FlightRecorder(8)
    rec.record("submit", tenant="acme", job="j-1")
    ev = rec.events()[0]
    assert ev["tenant"] == "acme"
    assert ev["wall"] > 1e9                     # unix epoch seconds
    assert ev["t"] >= 0.0                       # monotonic relative
    dump = rec.dump()
    assert "qn_impl" in dump["provenance"]
    assert "repro_shard" in dump["provenance"]
    p = tmp_path / "fr.json"
    rec.save(p)
    assert json.loads(p.read_text())["provenance"] == dump["provenance"]


def test_provenance_stamps_the_device():
    import jax
    devs = jax.devices()
    prov = obs.provenance()
    assert prov["device"] == {"platform": devs[0].platform,
                              "kind": devs[0].device_kind,
                              "count": len(devs)}
    assert prov["qn_impl"] == qn_sim.default_impl()


# ------------------------------------------------------ regression sentinel

def _bench_doc(dispatches=8, wall=2.0, parity=True):
    return {"name": "demo", "us_per_call": 1000.0, "derived": "x",
            "unix_time": 0.0, "provenance": {"git_sha": "abc"},
            "metrics": {"dispatches": dispatches, "wall_s": wall,
                        "parity_bit_exact": parity, "violations": 0}}


def test_regress_green_on_own_distillate_and_fails_injected(tmp_path):
    from benchmarks import regress

    (tmp_path / "BENCH_demo.json").write_text(json.dumps(_bench_doc()))
    assert regress.main(["--results", str(tmp_path), "--distill"]) == 0
    assert regress.main(["--results", str(tmp_path),
                         "--out", str(tmp_path / "v")]) == 0

    # inject a dispatch-count regression -> hard fail
    (tmp_path / "BENCH_demo.json").write_text(
        json.dumps(_bench_doc(dispatches=9)))
    assert regress.main(["--results", str(tmp_path),
                         "--out", str(tmp_path / "v")]) == 1
    verdict = json.loads((tmp_path / "v.json").read_text())
    assert verdict["hard"] == 1 and not verdict["ok"]
    assert "dispatches" in verdict["benchmarks"]["BENCH_demo"][0]["metric"]

    # fewer dispatches is an improvement, not a failure
    (tmp_path / "BENCH_demo.json").write_text(
        json.dumps(_bench_doc(dispatches=7)))
    assert regress.main(["--results", str(tmp_path),
                         "--out", str(tmp_path / "v")]) == 0

    # flipped parity bit -> hard fail; wall-time drift -> warn only
    (tmp_path / "BENCH_demo.json").write_text(
        json.dumps(_bench_doc(parity=False)))
    assert regress.main(["--results", str(tmp_path),
                         "--out", str(tmp_path / "v")]) == 1
    (tmp_path / "BENCH_demo.json").write_text(
        json.dumps(_bench_doc(wall=10.0)))
    assert regress.main(["--results", str(tmp_path),
                         "--out", str(tmp_path / "v")]) == 0
    verdict = json.loads((tmp_path / "v.json").read_text())
    assert verdict["warn"] >= 1


def test_regress_missing_metric_is_hard_missing_file_is_skip(tmp_path):
    from benchmarks import regress

    (tmp_path / "BENCH_demo.json").write_text(json.dumps(_bench_doc()))
    regress.main(["--results", str(tmp_path), "--distill"])

    doc = _bench_doc()
    del doc["metrics"]["dispatches"]            # schema drift
    (tmp_path / "BENCH_demo.json").write_text(json.dumps(doc))
    assert regress.main(["--results", str(tmp_path),
                         "--out", str(tmp_path / "v")]) == 1

    (tmp_path / "BENCH_demo.json").unlink()     # benchmark not run: skip
    assert regress.main(["--results", str(tmp_path),
                         "--out", str(tmp_path / "v")]) == 0
    verdict = json.loads((tmp_path / "v.json").read_text())
    assert verdict["skipped"] == 1


def test_regress_repo_baselines_green_against_committed_bench_files():
    # the acceptance check: the committed baselines.json must reproduce a
    # green verdict on the committed BENCH files
    from pathlib import Path

    from benchmarks import regress
    results = Path(__file__).resolve().parent.parent / "results"
    if not (results / "baselines.json").exists():
        pytest.skip("no committed baselines.json")
    baselines = json.loads((results / "baselines.json").read_text())
    verdict = regress.compare(baselines, results)
    assert verdict["ok"], json.dumps(
        {k: v for k, v in verdict["benchmarks"].items()
         if any(f["severity"] == "hard" for f in v)}, indent=1)
