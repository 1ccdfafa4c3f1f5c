"""Host seconds of the set-up steps, one per distinct shape of the cycle:
the per-op XLA programs compiled, or loaded from the disk cache, for the
cycle's shapes."""


def read(run):
    return run["shape_warm_s"]
