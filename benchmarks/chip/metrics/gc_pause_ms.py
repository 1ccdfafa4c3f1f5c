"""Mean milliseconds per traced step that Python's garbage collector
paused the call (``python.gc`` spans; ``CallRecord.gc_ns``)."""
from traced_calls import traced_records


def read(run):
    recs = traced_records(run)
    if recs is None:
        return None
    return 1e-6 * sum(r.gc_ns for r in recs) / len(recs)
