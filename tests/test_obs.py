"""Telemetry plane: span tracing, the metrics registry, and the service
flight recorder (docs/observability.md).

The contract under test: tracing is opt-in and *observationally inert* —
a solve under an installed tracer produces bit-identical solutions and
``sim_stats()`` accounting to the same solve untraced — while the span
tree it records reaches kernel-impl depth
(``solve → tier:qn → race_round → fused_dispatch → kernel:*``) and
exports as schema-valid Chrome trace-event JSON.  The registry's ``qn.*``
counters ARE the ``sim_stats()`` store (one lock, one source of truth),
and the flight recorder preserves the rounds leading up to a job failure.
"""
import glob
import json
import statistics
import time

import pytest

from repro import obs
from repro.core import qn_sim
from repro.core.optimizer import DSpace4Cloud
from repro.core.problem import ApplicationClass, JobProfile, Problem, VMType
from repro.obs.metrics import Counter, Histogram, MetricsRegistry, \
    counter_delta
from repro.obs.recorder import FlightRecorder
from repro.service import AdmissionController, JobState, SolverService

STEADY = VMType(name="steady", cores=2, sigma=0.05, pi=0.20)
TURBO = VMType(name="turbo", cores=2, sigma=0.0425, pi=0.17)
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


def _service_problem(deadline_ms=45_000.0, m_avg=1500.0) -> Problem:
    prof = JobProfile(n_map=8, n_reduce=2, m_avg=m_avg, m_max=2 * m_avg,
                      r_avg=700, r_max=1500)
    cls = ApplicationClass(name="c", h_users=2, think_ms=8000.0,
                           deadline_ms=deadline_ms, eta=0.25,
                           profiles={"vm": prof})
    vm = VMType(name="vm", cores=2, sigma=0.05, pi=0.20)
    return Problem(classes=[cls], vm_types=[vm])


# ------------------------------------------------------------- span tracing

def test_traced_batched_solve_span_tree():
    with obs.tracing() as t:
        rep = DSpace4Cloud(_race_problem(), **KW).run()

    names = {s.name for s in t.spans}
    assert {"solve", "tier:kkt", "tier:qn", "race_round",
            "fused_dispatch"} <= names
    # the analytic tier nests directly under the solve root
    (kkt,) = t.by_name("tier:kkt")
    assert t.chain(kkt) == ["solve", "tier:kkt"]
    # the deepest kernel span carries the full stack above it
    kernels = [s for s in t.spans if s.name.startswith("kernel:")]
    assert kernels, "solve never reached kernel-impl depth"
    deepest = max(kernels, key=lambda s: s.depth)
    assert t.chain(deepest) == [
        "solve", "tier:qn", "race_round", "fused_dispatch", deepest.name]
    assert t.summary()["max_depth"] >= 5
    # the report carries the telemetry the tracer saw
    assert rep.telemetry is not None
    assert rep.telemetry["qn"]["dispatches"] == rep.qn_dispatches > 0
    assert rep.telemetry["spans"]["spans"]["race_round"]["count"] >= 1
    assert "telemetry" in json.loads(rep.to_json())


def test_traced_run_fast_has_amva_tier():
    with obs.tracing() as t:
        rep = DSpace4Cloud(_race_problem(), **KW).run_fast()
    assert rep.solutions["etl"].feasible
    assert t.by_name("tier:amva"), "fast gait must trace the AMVA seeding"
    kernels = [s for s in t.spans if s.name.startswith("kernel:")]
    chain = t.chain(max(kernels, key=lambda s: s.depth))
    for name in ("solve", "tier:qn", "race_round", "fused_dispatch"):
        assert name in chain, f"{name} missing from {chain}"


def test_traced_service_run_spans_reach_kernels():
    with obs.tracing() as t:
        svc = SolverService(window=4)
        jid = svc.submit(_service_problem(), min_jobs=6, replications=1,
                         seed=3)
        jobs = svc.run_until_complete()
    assert jobs[jid].state == JobState.DONE
    kernels = [s for s in t.spans if s.name.startswith("kernel:")]
    assert kernels
    chain = t.chain(max(kernels, key=lambda s: s.depth))
    for name in ("service.run", "service_round", "flush", "fused_dispatch"):
        assert name in chain, f"{name} missing from {chain}"


def test_tracing_is_inert_sim_stats_and_solutions_bit_identical():
    def solve():
        before = qn_sim.sim_stats()
        rep = DSpace4Cloud(_race_problem(), **KW).run()
        after = qn_sim.sim_stats()
        return rep, {k: after[k] - before[k] for k in after}

    rep_off, stats_off = solve()
    with obs.tracing():
        rep_on, stats_on = solve()
    assert stats_off["dispatches"] > 0
    assert stats_on == stats_off
    assert rep_on.solutions == rep_off.solutions
    assert rep_on.total_cost_per_h == rep_off.total_cost_per_h


def test_registry_qn_counters_are_sim_stats():
    DSpace4Cloud(_race_problem(), **KW).run_fast()
    stats = qn_sim.sim_stats()
    reg = obs.registry().snapshot("qn.")
    assert {k: reg[f"qn.{k}"] for k in stats} == stats
    assert qn_sim.dispatch_count() == reg["qn.dispatches"]


def test_reset_sim_stats_is_one_function_clearing_everything():
    # the old aliasing bug: reset_sim_stats silently bound to a function
    # that only cleared the dispatch counter
    assert qn_sim.reset_sim_stats is qn_sim.reset_dispatch_count
    qn_sim._count_dispatch(lanes=4, padded_lanes=2, events_total=100,
                           events_useful=60)
    assert qn_sim.sim_stats()["events_total"] >= 100
    qn_sim.reset_sim_stats()
    assert qn_sim.sim_stats() == {k: 0 for k in qn_sim.sim_stats()}
    assert qn_sim.dispatch_count() == 0


def test_span_helper_is_noop_without_tracer_and_tracing_restores():
    assert obs.active() is None
    with obs.span("anything", cat="x", foo=1) as s:
        assert s is None                        # no tracer: nothing recorded
    with obs.tracing() as outer:
        with obs.tracing() as inner:
            assert obs.active() is inner
            with obs.span("inner-span"):
                pass
        assert obs.active() is outer            # previous tracer restored
        assert not outer.by_name("inner-span")  # recorded on inner only
    assert obs.active() is None
    assert inner.by_name("inner-span")


def test_tracer_bounds_spans_and_counts_drops():
    with obs.tracing(max_spans=2, jax_annotations=False) as t:
        for i in range(5):
            with obs.span("s", i=i):
                pass
    assert len(t.spans) == 2
    assert t.dropped == 3
    assert t.summary()["dropped"] == 3


def _serialized_service_run(impl=None):
    """Three tenants' jobs through a service whose admission budget makes
    every job oversize, so they plan one at a time and the later ones wait
    in the queue; returns ``(tracer, jobs, counter deltas)``."""
    old = qn_sim.default_impl()
    reg = obs.registry()
    try:
        if impl is not None:
            qn_sim.set_default_impl(impl)
        with obs.tracing() as t:
            svc = SolverService(window=4, admission=AdmissionController(
                max_inflight_events=1))
            before = reg.snapshot()
            for i in range(3):
                svc.submit(_service_problem(deadline_ms=45_000.0 + 5e3 * i),
                           min_jobs=6, replications=1, seed=3 + i,
                           tag=f"tenant-{i}")
            jobs = svc.run_until_complete()
            delta = counter_delta(before, reg.snapshot())
    finally:
        qn_sim.set_default_impl(old)
    assert all(j.state == JobState.DONE for j in jobs.values())
    return t, jobs, delta


def test_service_round_spans_nest_under_their_parents_and_name_the_job():
    t, jobs, _ = _serialized_service_run(impl="pallas")
    subs = t.by_name("submit")
    assert sorted(s.args["job"] for s in subs) == sorted(jobs)
    for s in subs:
        assert t.chain(s) == ["submit"]
        assert s.args["tenant"] == jobs[s.args["job"]].tenant
    admits = t.by_name("admit")
    assert all(t.chain(s) == ["service.run", "admit"] for s in admits)
    assert sum(s.args["admitted"] for s in admits) == len(jobs)
    assert max(s.args["queued"] for s in admits) == len(jobs)
    # activation's analytic seed runs inside the admission scan
    for s in t.by_name("tier:kkt"):
        assert t.chain(s) == ["service.run", "admit", "tier:kkt"]
    advances = t.by_name("advance")
    assert {s.args["job"] for s in advances} == set(jobs)
    assert all(t.chain(s) == ["service.run", "service_round", "advance"]
               for s in advances)
    resolves = t.by_name("resolve")
    assert resolves
    for s in resolves:
        assert t.chain(s) == ["service.run", "service_round", "flush",
                              "resolve"]
        assert s.args["batches"] >= 1
    for s in t.by_name("flush"):
        assert {"groups", "points", "cached"} <= set(s.args)
    # the dispatch chain the kernel's roofline reads is unchanged
    kernels = t.by_name("kernel:qn_event")
    assert kernels
    for s in kernels:
        assert t.chain(s)[-3:] == ["fused_dispatch", "kernel:pallas",
                                   "kernel:qn_event"]
        assert t.chain(s)[:3] == ["service.run", "service_round", "flush"]
    outer = {s.sid: s for s in t.by_name("kernel:pallas")}
    for s in kernels:
        assert outer[s.parent].args["max_slots"] == s.args["max_slots"]


def test_service_counters_reconcile_with_the_jobs():
    t, jobs, d = _serialized_service_run()
    waits = [(j.started_ns - j.submitted_ns) / 1e3 for j in jobs.values()]
    assert max(waits) > 0
    assert abs(d["admission.queue_us"] - sum(waits)) <= len(jobs)
    for j in jobs.values():
        assert d[f'admission.queue_us{{tenant="{j.tenant}"}}'] == \
            (j.started_ns - j.submitted_ns) // 1000
    assert d["admission.admit"] == len(jobs)
    # one advance span per (job, round) in which the job was active
    assert d["service.job_rounds"] == len(t.by_name("advance")) \
        == sum(j.rounds for j in jobs.values())
    assert d["service.rounds"] == len(t.by_name("service_round"))
    assert d["service.round_us"] > 0
    assert 0 <= d["qn.sync_wait_us"] <= d["service.round_us"]


def test_tracer_stamps_spans_on_the_real_time_clock():
    with obs.tracing(jax_annotations=False) as t:
        before = time.time_ns()
        with obs.span("probe"):
            pass
        after = time.time_ns()
    (s,) = t.by_name("probe")
    assert before / 1e3 <= s.ts_us <= s.ts_us + s.dur_us <= after / 1e3


def test_span_starts_overlay_their_profiler_annotations(tmp_path):
    import jax
    from jax.profiler import ProfileData
    jax.profiler.start_trace(str(tmp_path))
    try:
        with obs.tracing() as t:
            for i in range(20):
                with obs.span(f"probe{i}"):
                    time.sleep(1e-4)
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                        recursive=True)
    pd = ProfileData.from_file(path)
    (env,) = [p for p in pd.planes if p.name == "Task Environment"]
    t0_ns = dict(env.stats)["profile_start_time"]
    starts = {e.name: t0_ns + e.start_ns for p in pd.planes
              if p.name.startswith("/host:") for line in p.lines
              for e in line.events if e.name.startswith("probe")}
    gaps_us = [abs(s.ts_us - starts[s.name] / 1e3) for s in t.spans]
    assert len(gaps_us) == 20
    assert statistics.median(gaps_us) <= 50.0


# ------------------------------------------------------------ chrome export

def test_chrome_export_schema_and_roundtrip(tmp_path):
    with obs.tracing(jax_annotations=False) as t:
        with obs.span("outer", cat="a", note="x", skipme=[1, 2]):
            with obs.span("inner", cat="b", n=3):
                pass
    path = tmp_path / "trace.json"
    chrome = t.save(path)
    assert obs.validate_chrome_trace(chrome) == 2
    reloaded = json.loads(path.read_text())
    assert obs.validate_chrome_trace(reloaded) == 2
    evs = {e["name"]: e for e in reloaded["traceEvents"] if e["ph"] == "X"}
    # parent linkage survives export; non-scalar args are dropped
    assert evs["inner"]["args"]["parent"] == evs["outer"]["args"]["sid"]
    assert evs["inner"]["args"]["n"] == 3
    assert "skipme" not in evs["outer"]["args"]
    # the inner span is contained in the outer one (Perfetto's nesting rule)
    assert evs["outer"]["ts"] <= evs["inner"]["ts"]
    assert evs["inner"]["ts"] + evs["inner"]["dur"] <= \
        evs["outer"]["ts"] + evs["outer"]["dur"] + 1e-3


@pytest.mark.parametrize("bad", [
    "not a dict",
    {"no": "traceEvents"},
    {"traceEvents": "not a list"},
    {"traceEvents": [{"name": "x", "ph": "Z", "pid": 1, "tid": 1}]},
    {"traceEvents": [{"name": "", "ph": "X", "pid": 1, "tid": 1,
                      "ts": 0, "dur": 1}]},
    {"traceEvents": [{"name": "x", "ph": "X", "pid": "p", "tid": 1,
                      "ts": 0, "dur": 1}]},
    {"traceEvents": [{"name": "x", "ph": "X", "pid": 1, "tid": 1,
                      "ts": -5, "dur": 1}]},
    {"traceEvents": [{"name": "m", "ph": "M", "pid": 1, "tid": 0}]},  # no X
])
def test_validate_chrome_trace_rejects(bad):
    with pytest.raises(ValueError):
        obs.validate_chrome_trace(bad)


# --------------------------------------------------------- metrics registry

def test_registry_get_or_create_and_kind_mismatch():
    reg = MetricsRegistry()
    c = reg.counter("a.count")
    assert reg.counter("a.count") is c          # get-or-create, not replace
    c.inc()
    c.inc(4)
    assert c.value == 5
    with pytest.raises(TypeError, match="already registered"):
        reg.gauge("a.count")
    g = reg.gauge("a.level")
    g.set(2.5)
    h = reg.histogram("a.lat", buckets=(1, 10))
    h.observe(3.0)
    snap = reg.snapshot()
    assert snap["a.count"] == 5 and snap["a.level"] == 2.5
    assert snap["a.lat"]["count"] == 1
    assert reg.snapshot("a.l").keys() == {"a.lat", "a.level"}
    # reset zeroes values but keeps the registered objects alive, so
    # instrumented modules' cached references stay valid
    reg.reset()
    assert reg.counter("a.count") is c and c.value == 0
    assert reg.snapshot()["a.lat"]["count"] == 0


def test_counter_delta_between_snapshots():
    reg = MetricsRegistry()
    c = reg.counter("x")
    h = reg.histogram("h", buckets=(1,))
    before = reg.snapshot()
    c.inc(7)
    h.observe(0.5)
    after = reg.snapshot()
    d = counter_delta(before, after)
    assert d["x"] == 7
    assert d["h"]["count"] == 1                 # histograms pass through


def test_histogram_bucket_counts_sum_to_count_deterministic():
    h = Histogram("t", buckets=(1, 2, 5, 10))
    values = [0.0, 1.0, 1.5, 2.0, 2.0001, 5.0, 9.99, 10.0, 10.0001, 1e9]
    for v in values:
        h.observe(v)
    assert sum(h.bucket_counts) == h.count == len(values)
    snap = h.snapshot()
    assert sum(snap["buckets"].values()) == snap["count"]
    assert snap["sum"] == pytest.approx(sum(values))
    # le-semantics: a value equal to a bound lands in that bucket
    assert snap["buckets"]["1.0"] == 2          # 0.0 and 1.0
    assert snap["buckets"]["+inf"] == 2         # 10.0001 and 1e9
    with pytest.raises(ValueError, match="ascending"):
        Histogram("bad", buckets=(5, 1))
    with pytest.raises(ValueError, match="ascending"):
        Histogram("dup", buckets=(1, 1, 2))


def test_histogram_bucket_counts_sum_property():
    pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=60, deadline=None)
    @given(st.lists(st.floats(min_value=-1e6, max_value=1e6,
                              allow_nan=False), max_size=80),
           st.sets(st.floats(min_value=0, max_value=1e5,
                             allow_nan=False), min_size=1, max_size=8))
    def prop(values, bounds):
        h = Histogram("p", buckets=sorted(bounds))
        for v in values:
            h.observe(v)
        assert sum(h.bucket_counts) == h.count == len(values)
        assert sum(h.snapshot()["buckets"].values()) == len(values)

    prop()


def test_counter_is_exact_under_threads():
    import threading
    reg = MetricsRegistry()
    c = reg.counter("n")

    def work():
        for _ in range(1000):
            c.inc()

    ts = [threading.Thread(target=work) for _ in range(8)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert c.value == 8000
    assert isinstance(Counter("x", reg.lock).snapshot(), int)


# ----------------------------------------------------------- flight recorder

def test_flight_recorder_ring_evicts_oldest():
    fr = FlightRecorder(capacity=8)
    for i in range(20):
        fr.record("tick", i=i)
    assert fr.recorded == 20
    assert fr.dropped == 12
    evs = fr.events()
    assert len(evs) == 8
    assert [e["seq"] for e in evs] == list(range(13, 21))
    assert [e["i"] for e in evs] == list(range(12, 20))
    dump = fr.dump()
    assert dump["capacity"] == 8 and dump["dropped"] == 12
    assert fr.events(kind="nope") == []
    fr.clear()
    assert fr.recorded == 0 and fr.events() == []
    with pytest.raises(ValueError):
        FlightRecorder(capacity=0)


def test_flight_recorder_dumped_on_job_failure(tmp_path):
    # no VM can meet a 10ms deadline at m_avg=1e9: rank_vm_types raises at
    # activation, the job FAILs, and the service auto-dumps the recorder
    path = tmp_path / "flight.json"
    svc = SolverService(window=4, recorder_path=str(path))
    jid = svc.submit(_service_problem(deadline_ms=10.0, m_avg=1e9),
                     min_jobs=6, replications=1, seed=3)
    jobs = svc.run_until_complete()
    assert jobs[jid].state == JobState.FAILED

    assert path.exists(), "failure must auto-dump the flight recorder"
    dump = json.loads(path.read_text())
    kinds = [e["kind"] for e in dump["events"]]
    assert "submit" in kinds and "activate" in kinds and "fail" in kinds
    (fail,) = [e for e in dump["events"] if e["kind"] == "fail"]
    assert fail["job"] == jid and "ValueError" in fail["error"]
    # the on-demand dump matches the auto-dump
    assert svc.dump_flight_recorder()["events"] == dump["events"]
    path2 = tmp_path / "again.json"
    svc.dump_flight_recorder(str(path2))
    assert json.loads(path2.read_text())["events"] == dump["events"]


def test_flight_recorder_logs_rounds_of_a_healthy_run():
    svc = SolverService(window=4)
    jid = svc.submit(_service_problem(), min_jobs=6, replications=1, seed=3)
    jobs = svc.run_until_complete()
    assert jobs[jid].state == JobState.DONE
    rounds = svc.recorder.events(kind="round")
    assert len(rounds) == svc.rounds >= 1
    for ev in rounds:
        assert ev["points"] >= ev["dispatched"] >= 0
        assert ev["wall_ms"] >= 0
    (fin,) = svc.recorder.events(kind="finish")
    assert fin["job"] == jid and fin["state"] == str(JobState.DONE)
    assert svc.stats()["recorder"]["recorded"] >= len(rounds) + 3


if __name__ == "__main__":  # pragma: no cover
    import sys
    sys.exit(pytest.main([__file__, "-q"]))
