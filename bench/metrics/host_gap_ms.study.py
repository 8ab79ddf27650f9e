"""Device idle time between consecutive executions of the study's
segment program (module ``jit__run``), mean per boundary, in ms: the
host segment loop's carry round trip, archive inserts and, at chunk
boundaries, the next chunk's set-up and seed evaluation."""

MODULES = ("jit__run",)


def read(run):
    if run.trace is None:
        return None
    runs = run.trace.module_runs(MODULES)
    if len(runs) < 2:
        return None
    gaps = [run.trace.idle_s_between(runs[i][1], runs[i + 1][0])
            for i in range(len(runs) - 1)]
    return 1e3 * sum(gaps) / len(gaps)
