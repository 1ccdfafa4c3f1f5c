"""Host microseconds per primitive bind in the VM's instruction stream,
over the traced cycle: the program's ``vm.stream`` time over its binds
(``CallRecord.stream_ns`` / ``binds``)."""
from traced_calls import traced_records


def read(run):
    recs = traced_records(run)
    if recs is None:
        return None
    return 1e-3 * sum(r.stream_ns for r in recs) / sum(r.binds for r in recs)
