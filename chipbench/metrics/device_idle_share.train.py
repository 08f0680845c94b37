"""1 - the union of the device's operation intervals over the traced
window, from the profiler trace. Layer: the device."""


def read(run):
    if run.trace is None or not run.trace.ops or run.trace.window_s <= 0:
        return None
    return 100.0 * (1.0 - run.trace.busy_s() / run.trace.window_s)
