"""``qn.replay_lookup_share`` reads the replay draws the lookup kernel
copied over the replay draws needed: the counters' changes over the
window.  It gives no reading where no replay draw was needed or where the
program lacks the counters."""
import pytest

from bench import harness

METRIC = "qn.replay_lookup_share"
BASE = {"qn.replay_draws": 4_194_304, "qn.replay_lookups": 4_194_304}


def _read(counters):
    return harness.reader(METRIC).read({"counters": counters})


@pytest.mark.parametrize("lookups,want", [
    (4_194_304, 100.0),     # every replay draw copied by the kernel
    (1_048_576, 25.0),      # a quarter of the dispatches on the kernel
    (0, 0.0),               # every draw gathered by the scan
])
def test_reader_divides_the_counter_deltas(lookups, want):
    got = _read({**BASE, "qn.replay_lookups": lookups})
    assert got == pytest.approx(want, rel=1e-12)


@pytest.mark.parametrize("counters", [
    {**BASE, "qn.replay_draws": 0},
    {"qn.replay_lookups": 4_194_304},
], ids=["zero", "absent"])
def test_reader_gives_none_when_its_base_is_zero(counters):
    assert _read(counters) is None


def test_reader_gives_none_without_the_counter():
    # a program that predates the counters: their deltas are absent, not 0
    assert _read({"qn.lanes": 2_400, "qn.replay_draws": 4_194_304}) is None
    assert _read({"qn.lanes": 2_400}) is None
