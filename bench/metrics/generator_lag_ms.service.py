"""95th percentile of how late the benchmark's generator called
``submit`` after a job's due time, in ms (host clock)."""

import statistics


def read(run):
    lag = run.counters.get("lag_s")
    if not lag or len(lag) < 2:
        return None
    return 1e3 * statistics.quantiles(lag, n=20)[18]
