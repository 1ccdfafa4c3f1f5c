"""Device operations per step in the traced cycle (``XLA Ops`` events of
the profiler trace)."""


def read(run):
    tr = run.get("trace")
    if tr is None:
        return None
    return tr["device_ops"] / tr["steps"]
