"""Mean host milliseconds per window step from the call into the step to
its return, before the benchmark blocks on the results: dispatch plus the
VM's op-by-op enqueue."""


def read(run):
    steps = run["window"]["steps"]
    return 1e3 * sum(s["enqueue_s"] for s in steps) / len(steps)
