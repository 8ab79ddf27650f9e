"""Share of the traced window in which the device ran no operation,
in the study cells (``1 - busy / window``, from the profiler trace)."""


def read(run):
    t = run.trace
    if t is None or t.busy_s() <= 0:
        return None
    return 100.0 * (1.0 - t.busy_s() / t.window_s)
