"""Operations and bytes the QN event simulation needs, for the roofline
share of the ``qn_event`` kernel.

Counted per lane-event from the dispatch's shapes, as the simulation needs
them and not as one kernel happens to move them.  One event of one lane
finds the earliest of its ``slot_rows`` container clocks and its first
free container (two passes over the containers), the earliest think end
and the earliest waiting map and reduce stage among its ``users`` (three
passes over the users), and then makes ``SCALAR_OPS`` scalar updates:
the clock, one container, one user's stage, counters and the response
accumulators.  Random draws are not counted: they can be made where they
are used, so no bytes move for them.  Bytes are what has to cross HBM
once per dispatch: each lane's parameters in and its two results out, and
the two shared replay lists in.
"""
from __future__ import annotations

# device events of the kernel: the Pallas call, named by its jax.named_scope
EVENT_NAMES = ("qn_event_kernel",)
SCALAR_OPS = 16
LANE_PARAM_BYTES = 8 * 4     # n_map, n_reduce, slots, budget, seed, means
LANE_RESULT_BYTES = 2 * 4    # mean response, jobs counted


def ops_bytes(lanes: int, events: int, slot_rows: int, users: int,
              list_len: int) -> tuple:
    """(operations, HBM bytes) of one dispatch of ``lanes`` lanes, each
    simulating ``events`` events."""
    per_event = 2 * slot_rows + 3 * users + SCALAR_OPS
    ops = lanes * events * per_event
    nbytes = lanes * (LANE_PARAM_BYTES + LANE_RESULT_BYTES) \
        + 2 * list_len * 4
    return ops, nbytes


def min_seconds(ops: float, nbytes: float, peak: dict) -> tuple:
    """(least time the chip could take, which bound sets it)."""
    t_ops = ops / peak["ops_per_s"]
    t_mem = nbytes / peak["hbm_bytes_per_s"]
    return (t_ops, "compute") if t_ops >= t_mem else (t_mem, "memory")
