"""From a profiler trace of the window to device busy, idle, kernel time
and the breakdown.

``load_xplane`` flattens the ``.xplane.pb`` that ``jax.profiler`` writes
into plain event lists: per chip's plane, the events of its XLA op line
(``[name, start_ns, duration_ns]``), and the host's annotated events with
the thread line they ran on.  ``reduce`` works on that form alone, so it
is tested on a small recorded trace (``bench/testdata``).

The window is the span from the start of the first ``bench.round``
annotation to the end of the last: the harness opens one around every
``SolverService.step()``, so no host clock has to be matched to the
trace's.  Busy time is the union of a device's op intervals inside the
window; idle time the rest.  Each idle gap is charged to the innermost
host event open on the rounds' thread at the gap's midpoint.
"""
from __future__ import annotations

import glob
import os
import re
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, List, Sequence, Tuple

ROUND = "bench.round"
OP_LINES = ("XLA Ops",)
CHIP_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")   # one per chip


@dataclass
class Reduction:
    window_s: float
    busy_s: float                 # mean over the devices
    devices: int
    kernel_s: Dict[str, float]    # summed over the devices
    rounds: List[Tuple[float, float, float]]   # (start, end, busy in it) s
    breakdown: Dict[str, list] = field(default_factory=dict)

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s


def op_name(event: str) -> str:
    """``qn_event_kernel.1`` of ``%qn_event_kernel.1 = f32[2,24] ...``:
    a device event is named by its HLO instruction's text."""
    return event.split(" = ", 1)[0].lstrip("%")


def load_xplane(path: str) -> dict:
    """The trace as ``{"devices": {plane: [[name, start, dur], ...]},
    "host": [[name, start, dur, line], ...]}`` (nanoseconds)."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    out = {"devices": {}, "host": []}
    for plane in pd.planes:
        if CHIP_PLANE.match(plane.name):
            evs = []
            for line in plane.lines:
                if line.name in OP_LINES:
                    evs += [[e.name, float(e.start_ns), float(e.duration_ns)]
                            for e in line.events]
            out["devices"][plane.name] = evs
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                out["host"] += [[e.name, float(e.start_ns),
                                 float(e.duration_ns), line.name]
                                for e in line.events if e.duration_ns > 0]
    return out


def find_xplane(trace_dir: str) -> str:
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(files) != 1:
        raise RuntimeError(f"{len(files)} xplane files under {trace_dir}")
    return files[0]


def _union(intervals: Sequence[Tuple[float, float]]) -> List[List[float]]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(ivs, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in ivs if e > lo and s < hi]


def _overlap(ivs: List[List[float]], lo: float, hi: float) -> float:
    return sum(e - s for s, e in _clip(ivs, lo, hi))


def reduce(trace: dict, kernels: Dict[str, Sequence[str]],
           top: int = 10) -> Reduction:
    """``kernels`` maps a kernel's metric name to the substrings that name
    its device events."""
    rounds = sorted((s, s + d) for n, s, d, _ in trace["host"]
                    if n == ROUND)
    if not rounds:
        raise RuntimeError(f"no {ROUND} annotation in the trace")
    lo, hi = rounds[0][0], rounds[-1][1]
    round_line = next(ln for n, _, _, ln in trace["host"] if n == ROUND)
    devs = trace["devices"]
    if not devs:
        raise RuntimeError("no device plane in the trace")
    busy = {}
    kernel_ns = defaultdict(float)
    op_ns = defaultdict(float)
    for plane, evs in devs.items():
        ivs = [(s, s + d) for _, s, d in evs]
        busy[plane] = _union(_clip(ivs, lo, hi))
        for name, s, d in evs:
            inside = min(s + d, hi) - max(s, lo)
            if inside <= 0:
                continue
            op_ns[op_name(name)] += inside
            for k, subs in kernels.items():
                if any(sub in name for sub in subs):
                    kernel_ns[k] += inside
    n = len(devs)
    busy_ns = sum(sum(e - s for s, e in b) for b in busy.values()) / n
    per_round = [(s * 1e-9, e * 1e-9,
                  sum(_overlap(b, s, e) for b in busy.values()) / n * 1e-9)
                 for s, e in rounds]

    # idle gaps, charged to the innermost host event open at their middle
    host = sorted((s, s + d, name) for name, s, d, ln in trace["host"]
                  if ln == round_line and s < hi and s + d > lo)
    gaps = defaultdict(float)
    for b in busy.values():
        edges = [lo] + [x for iv in b for x in iv] + [hi]
        for s, e in zip(edges[::2], edges[1::2]):
            if e <= s:
                continue
            mid = 0.5 * (s + e)
            open_ = [h for h in host if h[0] <= mid < h[1]]
            what = max(open_)[2] if open_ else "(no host event)"
            gaps[what] += (e - s) / n
    breakdown = {
        "device_ops": [[k, v / n * 1e-9] for k, v in
                       sorted(op_ns.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[k, v * 1e-9] for k, v in
                      sorted(gaps.items(), key=lambda kv: -kv[1])[:top]],
    }
    return Reduction(window_s=(hi - lo) * 1e-9, busy_s=busy_ns * 1e-9,
                     devices=n,
                     kernel_s={k: kernel_ns[k] * 1e-9 for k in kernels},
                     rounds=per_round, breakdown=breakdown)


def reduce_dir(trace_dir: str, kernels: Dict[str, Sequence[str]]
               ) -> Reduction:
    return reduce(load_xplane(find_xplane(trace_dir)), kernels)
