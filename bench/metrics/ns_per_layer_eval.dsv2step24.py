"""Device nanoseconds of the fused segment program per (design x layer)
it evaluates, in the network cells.

The segment program is ``ScenarioEngine``'s tempering scan; a
``mesh_noc`` + ``window`` engine jits it as ``_run``, so the trace names
its module ``jit__run``. Each execution runs ``segment`` sweeps over
``cells x chains`` designs, each evaluated on the engine's ``layers``
(the driver's counters). Its executions in the window, summed, over the
(design x layer) evaluations they ran."""

MODULES = ("jit__run",)


def read(run):
    if run.trace is None or not run.counters.get("layers"):
        return None
    runs = run.trace.module_runs(MODULES)
    if not runs:
        return None
    c = run.counters
    evals = (len(runs) * run.traffic["segment"] * c["cells"] * c["chains"]
             * c["layers"])
    return sum(e - s for s, e in runs) / evals
