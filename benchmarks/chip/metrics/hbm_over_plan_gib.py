"""The largest excess, over the traced cycle, of the device's
``bytes_in_use`` over the memory plan's occupancy at the same instruction,
read at every op-group boundary: memory the plan has freed that the
allocator still holds (``CallRecord.hbm_over_plan_bytes``)."""
from traced_calls import traced_records


def read(run):
    recs = traced_records(run)
    if recs is None:
        return None
    return max(r.hbm_over_plan_bytes for r in recs) / 2**30
