"""Device milliseconds of the fused segment program per sweep.

The segment program is ``ScenarioEngine``'s tempering scan; a
``mesh_noc`` + ``window`` engine jits it as ``_run``, so the trace names
its module ``jit__run``. Its executions in the window, summed, over the
sweeps they ran (``segment`` each)."""

MODULES = ("jit__run",)


def read(run):
    if run.trace is None:
        return None
    runs = run.trace.module_runs(MODULES)
    if not runs:
        return None
    sweeps = len(runs) * run.traffic["segment"]
    return sum(e - s for s, e in runs) * 1e-6 / sweeps
