"""The per-layer metrics read from the program's service and admission
counters: each reads the counters' changes over the window, and gives no
reading where its base is 0 or where the program lacks the counter."""
import pytest

from bench import harness

BASE = {"service.rounds": 250, "admission.admit": 200,
        "service.job_rounds": 300, "service.round_us": 45_000_000,
        "admission.queue_us": 240_000_000, "qn.sync_wait_us": 40_000_000}


def _read(metric, counters):
    return harness.reader(metric).read({"counters": counters})


@pytest.mark.parametrize("metric,want", [
    ("admission.queue_ms_per_job", 1200.0),
    ("admission.active_jobs_per_round", 1.2),
    ("service.host_self_ms_per_round", 20.0),
])
def test_reader_divides_the_counter_deltas(metric, want):
    assert _read(metric, dict(BASE)) == pytest.approx(want, rel=1e-12)


@pytest.mark.parametrize("metric,base", [
    ("admission.queue_ms_per_job", "admission.admit"),
    ("admission.active_jobs_per_round", "service.rounds"),
    ("service.host_self_ms_per_round", "service.rounds"),
])
def test_reader_gives_none_when_its_base_is_zero(metric, base):
    assert _read(metric, {**BASE, base: 0}) is None
    assert _read(metric, {k: v for k, v in BASE.items() if k != base}) \
        is None


@pytest.mark.parametrize("metric,counter", [
    ("admission.queue_ms_per_job", "admission.queue_us"),
    ("admission.active_jobs_per_round", "service.job_rounds"),
    ("service.host_self_ms_per_round", "service.round_us"),
    ("service.host_self_ms_per_round", "qn.sync_wait_us"),
])
def test_reader_gives_none_without_the_counter(metric, counter):
    # a program that predates the counter: its delta is absent, not 0
    assert _read(metric, {k: v for k, v in BASE.items()
                          if k != counter}) is None


def test_a_zero_counter_with_a_base_reads_zero():
    zero = {**BASE, "admission.queue_us": 0}
    assert _read("admission.queue_ms_per_job", zero) == 0.0
    same = {**BASE, "qn.sync_wait_us": BASE["service.round_us"]}
    assert _read("service.host_self_ms_per_round", same) == 0.0
