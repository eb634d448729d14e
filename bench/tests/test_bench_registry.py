"""The harness finds cells, configurations, mixes, metric readers and
kernels by name: a later change adds files and entries and edits none."""
import json
import shutil
from pathlib import Path

import pytest

from bench import harness

ROOT = Path(__file__).resolve().parents[2]


@pytest.fixture
def checkout(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    return tmp_path


def test_every_cell_resolves():
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    for w in spec["workloads"]:
        cell = harness.load_cell(w["name"])
        assert cell.config["name"] == w["config"]
        names = {m["name"] for m in cell.end_to_end}
        assert {"setup_s", "plan_p50_s"} <= names
        assert cell.per_layer
        for m in cell.per_layer:
            assert callable(harness.reader(m["name"]).read)


def test_new_cell_and_metric_need_no_edit(checkout):
    before = {p: p.read_bytes() for p in (checkout / "bench").rglob("*")
              if p.is_file()}
    (checkout / "bench" / "traffic" / "fresh.solo.json").write_text(
        json.dumps({"tenants": 1, "jobs_per_tenant": 5,
                    "cycle": ["Q5-1000G-1u"], "deadline_strata": 2}))
    (checkout / "bench" / "metrics" / "service.rounds.py").write_text(
        '"""Rounds in the window."""\n\n\ndef read(ctx):\n'
        '    return ctx["rounds"]\n')
    spec = json.loads((checkout / "BENCHMARK.json").read_text())
    spec["workloads"].append({"name": "t3large.solo",
                              "config": "tpcds-t3-large",
                              "traffic": "fresh.solo", "chips": 1,
                              "why": "one tenant"})
    spec["per_layer"].append({"name": "service.rounds", "unit": "rounds",
                              "better": "lower", "source": "program_counter",
                              "layer": "service", "moves": "jobs_per_s",
                              "workloads": ["t3large.solo"]})
    (checkout / "BENCHMARK.json").write_text(json.dumps(spec))

    cell = harness.load_cell("t3large.solo", root=checkout)
    assert cell.mix["tenants"] == 1
    assert "service.rounds" in [m["name"] for m in cell.per_layer]
    assert harness.reader("service.rounds", checkout).read(
        {"rounds": 7}) == 7
    other = harness.load_cell("t3large.fresh", root=checkout)
    assert "service.rounds" not in [m["name"] for m in other.per_layer]
    for p, data in before.items():
        assert p.read_bytes() == data, p


def test_kernels_and_peaks_by_name():
    assert "qn_event" in harness.kernel_events()
    assert harness.peak("TPU v5 lite")["ops_per_s"] == 197e12
    with pytest.raises(KeyError):
        harness.peak("TPU v99")


def test_unknown_cell_is_an_error():
    with pytest.raises(KeyError):
        harness.load_cell("no.such.cell")
