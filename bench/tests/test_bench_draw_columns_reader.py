"""``qn.draw_columns_per_lane`` reads the simulator's draw-table columns
over its lanes: the counters' changes over the window.  It gives no
reading where no lane ran or where the program lacks the counter."""
import pytest

from bench import harness

METRIC = "qn.draw_columns_per_lane"
BASE = {"qn.lanes": 2_400, "qn.draw_columns": 480}


def _read(counters):
    return harness.reader(METRIC).read({"counters": counters})


@pytest.mark.parametrize("columns,want", [
    (480, 0.2),        # tables built once per seed, 5 lanes a seed
    (2_400, 1.0),      # tables built per lane
    (0, 0.0),
])
def test_reader_divides_the_counter_deltas(columns, want):
    got = _read({**BASE, "qn.draw_columns": columns})
    assert got == pytest.approx(want, rel=1e-12)


@pytest.mark.parametrize("counters", [
    {**BASE, "qn.lanes": 0},
    {"qn.draw_columns": 480},
], ids=["zero", "absent"])
def test_reader_gives_none_when_its_base_is_zero(counters):
    assert _read(counters) is None


def test_reader_gives_none_without_the_counter():
    # a program that predates the counter: its delta is absent, not 0
    assert _read({"qn.lanes": 2_400}) is None
