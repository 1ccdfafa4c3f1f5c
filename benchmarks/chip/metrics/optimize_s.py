"""Host seconds of ``optimize()`` in set-up: trace, scheduling, remat,
memory planning and lowering of the symbolic train step."""


def read(run):
    return run["optimize_s"]
