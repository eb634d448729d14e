"""Operations and bytes of the qn_event roofline at known shapes."""
import json
from pathlib import Path

import pytest

from bench.roofline import qn_event

PEAKS = json.loads((Path(__file__).resolve().parents[1]
                    / "peaks.json").read_text())["devices"]


def test_ops_bytes_at_known_shapes():
    # 16 lanes x 524,288 events, 104 container rows, 1 user, 2048-long lists
    ops, nbytes = qn_event.ops_bytes(lanes=16, events=524_288,
                                     slot_rows=104, users=1, list_len=2048)
    assert ops == 16 * 524_288 * (2 * 104 + 3 * 1 + 16)
    assert nbytes == 16 * (32 + 8) + 2 * 2048 * 4


def test_ops_grow_with_rows_users_and_lanes():
    base = qn_event.ops_bytes(8, 1000, 20, 10, 2048)[0]
    assert qn_event.ops_bytes(8, 1000, 21, 10, 2048)[0] - base == 2 * 8000
    assert qn_event.ops_bytes(8, 1000, 20, 11, 2048)[0] - base == 3 * 8000
    assert qn_event.ops_bytes(16, 1000, 20, 10, 2048)[0] == 2 * base


def test_min_seconds_names_its_bound():
    peak = PEAKS["TPU v5 lite"]
    t, bound = qn_event.min_seconds(197e12, 1.0, peak)
    assert (t, bound) == (pytest.approx(1.0), "compute")
    t, bound = qn_event.min_seconds(1.0, 819e9, peak)
    assert (t, bound) == (pytest.approx(1.0), "memory")


def test_peaks_table_names_its_source():
    table = json.loads((Path(__file__).resolve().parents[1]
                        / "peaks.json").read_text())
    assert "TPU v5e" in table["source"]
    assert PEAKS["TPU v5 lite"] == {"ops_per_s": 197e12,
                                    "hbm_bytes_per_s": 819e9}
