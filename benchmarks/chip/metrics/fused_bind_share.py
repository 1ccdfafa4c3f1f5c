"""Share of the traced cycle's primitive binds that ran inside the VM's
jitted op-group executables, in % (``CallRecord.fused_binds`` / ``binds``).
A program whose records lack the counter gives ``None``."""
from traced_calls import traced_records


def read(run):
    recs = traced_records(run)
    if recs is None or not hasattr(recs[0], "fused_binds"):
        return None
    binds = sum(r.binds for r in recs)
    if binds == 0:
        return None
    return 100.0 * sum(r.fused_binds for r in recs) / binds
