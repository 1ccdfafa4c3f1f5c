"""The traced steps' least time over the device's busy time, in percent.

The least time of a step is the larger of its required FLOPs over the
chip's peak FLOP/s and its required HBM bytes over the peak bandwidth
(``flops.py``); ``run["trace_bound"]`` says which bound the traced steps
sat on."""


def read(run):
    tr = run.get("trace")
    if tr is None:
        return None
    return 100.0 * run["trace_least_s"] / tr["busy_s"]
