"""The memory plan's own peak (host bookkeeping, ``MemoryStats.device_peak``,
not HBM) at the cycle's largest shape, read after that shape's set-up
step."""


def read(run):
    return run["plan_peak_bytes"] / 2**30
