"""Device idle time between consecutive executions of the service's
bucket segment program, mean per boundary, in ms: the service tick's
admission, carry upload and download, archive inserts and bookkeeping.
A ``legacy`` + ``fixed`` engine jits the segment scan as ``run``, so the
trace names its module ``jit_run``."""

MODULES = ("jit_run",)


def read(run):
    if run.trace is None:
        return None
    runs = run.trace.module_runs(MODULES)
    if len(runs) < 2:
        return None
    gaps = [run.trace.idle_s_between(runs[i][1], runs[i + 1][0])
            for i in range(len(runs) - 1)]
    return 1e3 * sum(gaps) / len(gaps)
