"""Scheduler: mean share of decode slots holding a decoding request per
decode step, from the server's ``slot_utilization`` counter (summed over
every ``run()`` call of the run, weighted by decode steps)."""


def read(ctx):
    v = ctx["stats"].get("slot_utilization")
    return None if v is None else 100.0 * v
