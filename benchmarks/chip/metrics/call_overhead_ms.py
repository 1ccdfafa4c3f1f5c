"""Mean host milliseconds per traced step spent in the call outside the
VM's instruction stream: flatten, env solve, resolve, stats, unflatten
(``CallRecord.call_ns - stream_ns``)."""
from traced_calls import traced_records


def read(run):
    recs = traced_records(run)
    if recs is None:
        return None
    return 1e-6 * sum(r.call_ns - r.stream_ns for r in recs) / len(recs)
