"""Columns of seed-only draw tables built per simulator lane in the
window [ratio]: the change of ``qn.draw_columns`` over that of
``qn.lanes``.  1 where every lane draws its own tables; 1/candidates
where a dispatch builds them once per replication seed.  A program
without the counter gives no reading."""


def read(ctx):
    c = ctx["counters"]
    lanes = c.get("qn.lanes", 0)
    if "qn.draw_columns" not in c or not lanes:
        return None
    return c["qn.draw_columns"] / lanes
