"""Share of the ``qn_event`` kernel's roofline [%]: the least time the
chip could take for the simulation the window's dispatches needed
(``bench/roofline/qn_event.py``, peaks from ``bench/peaks.json``) over
the kernel's device time in the trace.

The dispatches are read from the program's spans: each
``kernel:qn_event`` span gives its slot rows, its parent ``kernel:pallas``
the real candidates and the scan length, and the ``fused_dispatch`` above
them the users.  Lanes are the real candidates times the replications.
A kernel span without those parents is an error, not a silent gap."""
from bench.roofline import qn_event


def read(ctx):
    kernel_s = ctx["trace"].kernel_s.get("qn_event", 0.0)
    spans = {s.sid: s for s in ctx["spans"]}
    cfg = ctx["cell"].config
    reps = cfg["solver"]["replications"]
    list_len = cfg["profiling"]["replay_cap"]
    need = 0.0
    bounds = set()
    n = 0
    for s in spans.values():
        if s.name != "kernel:qn_event":
            continue
        outer = spans.get(s.parent)
        disp = spans.get(outer.parent) if outer is not None else None
        if outer is None or disp is None or disp.name != "fused_dispatch":
            raise RuntimeError(
                f"kernel:qn_event span {s.sid} has no kernel:pallas parent "
                "under a fused_dispatch: its shapes cannot be read")
        ops, nbytes = qn_event.ops_bytes(
            lanes=outer.args["candidates"] * reps,
            events=outer.args["scan_len"], slot_rows=s.args["max_slots"],
            users=disp.args["h_users"], list_len=list_len)
        t, bound = qn_event.min_seconds(ops, nbytes, ctx["peak"])
        need += t
        bounds.add(bound)
        n += 1
    if not n or kernel_s <= 0:
        return None
    ctx.setdefault("notes", {})["qn_event_roofline"] = \
        f"{n} dispatches, bound by {'/'.join(sorted(bounds))}"
    return 100.0 * need / kernel_s
