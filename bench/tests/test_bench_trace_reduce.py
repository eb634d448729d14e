"""Reduction of a profiler trace to busy, idle, kernel time and the
breakdown, on hand-made traces and on a small one recorded on a TPU v5e."""
import json
from pathlib import Path

import pytest

from bench import trace_reduce

DATA = Path(__file__).resolve().parents[1] / "testdata"
MS = 1_000_000          # nanoseconds


def test_reduce_hand_made_trace():
    host = [["bench.round", 0, 100 * MS, "main"],
            ["flush", 10 * MS, 60 * MS, "main"],
            ["bench.round", 100 * MS, 100 * MS, "main"],
            ["other thread", 0, 200 * MS, "worker"]]
    dev = {"/device:TPU:0": [["qn_event_kernel.1", 20 * MS, 40 * MS],
                             ["fusion.3", 50 * MS, 20 * MS],
                             ["qn_event_kernel.1", 150 * MS, 30 * MS],
                             ["before", -50 * MS, 10 * MS]]}
    red = trace_reduce.reduce({"devices": dev, "host": host},
                              {"qn_event": ("qn_event",)})
    assert red.window_s == pytest.approx(0.2)
    assert red.busy_s == pytest.approx(0.08)     # [20,70] + [150,180] ms
    assert red.idle_share == pytest.approx(0.6)
    assert red.kernel_s["qn_event"] == pytest.approx(0.07)
    assert [r[2] for r in red.rounds] == pytest.approx([0.05, 0.03])
    ops = dict(red.breakdown["device_ops"])
    assert ops == pytest.approx({"qn_event_kernel.1": 0.07, "fusion.3": 0.02})
    gaps = dict(red.breakdown["idle_gaps"])
    # the gap [0, 20] ms falls in flush; [70, 150] and [180, 200] in rounds
    assert gaps == pytest.approx({"flush": 0.020, "bench.round": 0.1})


def test_reduce_means_over_devices():
    host = [["bench.round", 0, 10 * MS, "main"]]
    dev = {"/device:TPU:0": [["k", 0, 10 * MS]],
           "/device:TPU:1": [["k", 0, 5 * MS]]}
    red = trace_reduce.reduce({"devices": dev, "host": host},
                              {"k": ("k",)})
    assert red.devices == 2
    assert red.busy_s == pytest.approx(0.0075)
    assert red.kernel_s["k"] == pytest.approx(0.015)


def test_reduce_needs_rounds_and_devices():
    with pytest.raises(RuntimeError):
        trace_reduce.reduce({"devices": {"/device:TPU:0": []}, "host": []},
                            {})
    with pytest.raises(RuntimeError):
        trace_reduce.reduce({"devices": {},
                             "host": [["bench.round", 0, 1, "m"]]}, {})


def test_reduce_recorded_v5e_trace():
    """Four service rounds of a t3large.fresh window on one TPU v5e."""
    trace = json.loads((DATA / "v5e_t3large_trace.json").read_text())
    red = trace_reduce.reduce(trace, {"qn_event": ("qn_event_kernel",)})
    assert red.devices == 1
    assert len(red.rounds) == 4
    assert red.window_s == pytest.approx(0.500662355, rel=1e-9)
    assert red.busy_s == pytest.approx(0.463022522, rel=1e-9)
    assert red.kernel_s["qn_event"] == pytest.approx(0.14914226, rel=1e-9)
    ops = dict(red.breakdown["device_ops"])
    assert ops["qn_event_kernel.1"] == pytest.approx(0.14914226, rel=1e-9)
    # the replay-draw gathers outside the kernel, one per list
    assert ops["fusion"] + ops["fusion.1"] > ops["qn_event_kernel.1"]
    assert red.breakdown["idle_gaps"][0][0] == "flush"
    assert sum(v for _, v in red.breakdown["idle_gaps"]) == pytest.approx(
        red.window_s - red.busy_s, rel=1e-6)


def test_load_xplane_keeps_chip_planes_only(tmp_path):
    """A trace recorded here has host planes only: no chip plane."""
    import jax
    import jax.numpy as jnp
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation(trace_reduce.ROUND):
        jnp.ones((8,)).block_until_ready()
    jax.profiler.stop_trace()
    trace = trace_reduce.load_xplane(trace_reduce.find_xplane(str(tmp_path)))
    assert all(trace_reduce.CHIP_PLANE.match(p) for p in trace["devices"])
    assert any(h[0] == trace_reduce.ROUND for h in trace["host"])
