"""Service driver: an open-loop stream of search jobs against a
``PathfinderService``, ticked by the benchmark's one thread.

Set-up builds the service over the configuration's workloads and drains
one short job for every (workload, region) pair of the configuration in
both shape buckets: that fits every normalizer the stream will ask for
and loads or compiles every bucket program.

The window: one thread submits the jobs due by now, runs one scheduling
quantum of the service (``step()``: admit what fits, one segment of
every bucket with live jobs, the service worker's ``_tick``), then reads
the state of its open jobs, until every job is DONE. No worker thread
runs: ``start()``'s worker holds the service's lock through every tick
and takes it again at once, so a client thread waits for it by chance
and its latency would measure that race, not the tick. A job's latency
runs from its due time until the end of the quantum in which it became
DONE. Jobs due in the window are waited for up to ``grace_s`` after it
closes; one not DONE by then has failed and counts with the latency it
had reached.

Traffic keys: ``rate_per_s`` (offered load), ``sweeps`` and
``swap_every`` (the job shapes, used in equal shares), ``directions`` and
``n_chains`` per job, ``grace_s`` and ``limits``. The arrival times and
the job shapes are ``bench.arrivals.schedule``: every seed gets the same
gaps and shapes, in its own order.
"""
from __future__ import annotations

import statistics
import time
from typing import Dict, List

import numpy as np

from bench.arrivals import schedule
from bench.check import Reference, frontier_checks


class _Lanes:
    """The chain lanes of the service's bucket programs, seen from
    outside: while armed, every call of a segment program records the
    populations going in and coming out, and the normalizer rows that
    tell a job's slot from a free one (the service fills a free slot
    with all-ones ``mins`` and ``med``). A lane is a (program, slot,
    chain) that held a job in some call; ``stalled_share`` is the share
    of lanes whose state no call changed while it held one."""

    MINS, MED = 7, 8            # positions in the segment program's call

    def __init__(self, span):
        self.armed = False
        self.calls: Dict[tuple, list] = {}
        self._span = span

    def wrap(self, segment_runner):
        def wrapped(engine, S, n, seg, swap_every, collect_samples=False):
            fn = segment_runner(engine, S, n, seg, swap_every,
                                collect_samples)
            key = (id(engine), int(S), int(n), int(seg), int(swap_every))

            def run(*args):
                # marks the dispatch for the trace reduction
                # (``bench.trace.DISPATCH_SPAN``)
                with self._span("bench.dispatch"):
                    out = fn(*args)
                if self.armed:
                    # device arrays, read back after the window
                    self.calls.setdefault(key, []).append(
                        (args[0], out[0][0], args[self.MINS],
                         args[self.MED]))
                return out

            return run

        return wrapped

    def stalled_share(self) -> float:
        lanes = stalled = 0
        for calls in self.calls.values():
            held = moved = False
            for v_in, v_out, mins, med in calls:
                m = (np.asarray(v_in) != np.asarray(v_out)).any(axis=-1)
                job = ~((np.asarray(mins) == 1).all(axis=-1)
                        & (np.asarray(med) == 1).all(axis=-1))
                job = np.broadcast_to(job[:, None], m.shape)
                held, moved = held | job, moved | (m & job)
            lanes += int(held.sum())
            stalled += int((held & ~moved).sum())
        return stalled / lanes if lanes else float("nan")


class Driver:
    def __init__(self, config: dict, traffic: dict, seed: int):
        from jax.profiler import TraceAnnotation

        from repro.core import GEMMWorkload
        from repro.core.regions import Region
        from repro.pathfinding import ScalarizationSweep, ScenarioEngine
        from repro.serving import JobSpec, JobState, PathfinderService

        self._span = TraceAnnotation
        self._JobSpec, self._DONE = JobSpec, JobState.DONE
        self._terminal = (JobState.DONE, JobState.CANCELLED,
                          JobState.FAILED)
        self._Sweep = ScalarizationSweep
        self.config, self.traffic, self.seed = config, traffic, seed
        self.wls = [GEMMWorkload(w["name"], w["M"], w["K"], w["N"])
                    for w in config["workloads"]]
        self.regions = [Region(carbon_intensity=r["carbon_intensity"],
                               grid_profile=tuple(r["grid_profile"]))
                        for r in config["regions"]]
        self.lanes = _Lanes(TraceAnnotation)
        self._engine_cls = ScenarioEngine
        self._runner = ScenarioEngine.segment_runner
        ScenarioEngine.segment_runner = self.lanes.wrap(self._runner)
        self.svc = PathfinderService(
            self.wls, slots=config["slots"], segment=config["segment"],
            norm_samples=config["norm_samples"],
            norm_seed=config["norm_seed"],
            key=int(np.random.default_rng([seed, 3]).integers(2 ** 31 - 1)))
        swaps = traffic["swap_every"]
        pairs = [(wi, ri) for wi in range(len(self.wls))
                 for ri in range(len(self.regions))]
        for i, (wi, ri) in enumerate(pairs):
            self.svc.submit(self._spec(f"warm{seed}-{i}", wi, ri,
                                       config["segment"],
                                       swaps[i % len(swaps)]))
        self.svc.drain()
        self._runs = 0
        self.results: Dict[str, object] = {}
        self.jobs: List[tuple] = []      # (job id, workload, region, ...)

    def _spec(self, job_id: str, wi: int, ri: int, sweeps: int, swap: int):
        t = self.traffic
        return self._JobSpec(
            job_id=job_id, workload=self.wls[wi].name,
            strategy=self._Sweep(directions=t["directions"],
                                 n_chains=t["n_chains"], sweeps=sweeps,
                                 swap_every=swap),
            region=self.regions[ri], comm=self.config["comm"],
            schedule=self.config["schedule"])

    def run(self, seconds: float) -> dict:
        t = self.traffic
        due_s, shapes = schedule(t, len(self.wls), len(self.regions),
                                 seconds, self.seed)
        n = len(due_s)
        self._runs += 1
        ids = [f"j{self.seed}-{self._runs}-{i}" for i in range(n)]
        lag = np.zeros(n)
        done_at = np.full(n, np.nan)
        open_: List[int] = []
        svc, i = self.svc, 0
        self.lanes.calls.clear()
        self.lanes.armed = True
        t0 = time.perf_counter()
        due = t0 + np.asarray(due_s)
        end = t0 + seconds + t["grace_s"]
        with self._span("bench.stream"):
            while i < n or open_:
                now = time.perf_counter()
                if now > end:
                    break
                with self._span("bench.submit"):
                    while i < n and due[i] <= now:
                        lag[i] = time.perf_counter() - due[i]
                        wi, ri, sweeps, swap = shapes[i]
                        svc.submit(self._spec(ids[i], wi, ri, sweeps, swap))
                        open_.append(i)
                        i += 1
                with self._span("bench.step"):
                    progressed = svc.step()
                with self._span("bench.poll"):
                    now = time.perf_counter()
                    for j in list(open_):
                        state = svc.status(ids[j])
                        if state in self._terminal:
                            open_.remove(j)
                            if state is self._DONE:
                                # fetched at once: the service evicts old
                                # terminal jobs past its retention cap
                                self.results[ids[j]] = svc.result(ids[j])
                                done_at[j] = now
                if not progressed and i < n:
                    time.sleep(max(due[i] - time.perf_counter(), 0))
        self.lanes.armed = False
        gave_up = time.perf_counter()
        done = ~np.isnan(done_at)
        self.jobs += [(ids[j],) + shapes[j] for j in np.flatnonzero(done)]
        latency = np.where(done, done_at, gave_up) - due
        lat = latency.tolist()
        p95 = statistics.quantiles(lat, n=20)[18] if n >= 2 else lat[0]

        def backlog(at: float) -> int:
            """Jobs due by ``at`` and not DONE by then."""
            return int(np.count_nonzero(due <= at)
                       - np.count_nonzero(done_at <= at))

        return dict(
            values=dict(job_latency_p50_s=statistics.median(lat),
                        job_latency_p95_s=p95),
            attempted=n, failed=int(n - done.sum()),
            counters=dict(jobs=n, lag_s=lag.tolist(), latency_s=lat,
                          offered_rate=t["rate_per_s"],
                          backlog_mid=backlog(t0 + seconds / 2),
                          backlog_close=backlog(t0 + seconds),
                          sweeps=[int(s[2]) for s in shapes]))

    def check(self, control: bool = False) -> list:
        ref = Reference(self.config)
        frontiers = []
        for job_id, wi, ri, _, _ in self.jobs:
            res = self.results[job_id]
            frontiers.append([(wi, ri, e, v) for e, v in
                              zip(res.frontier.encoded,
                                  res.frontier.vectors)])
        return frontier_checks(ref, [], frontiers,
                               self.lanes.stalled_share(),
                               self.traffic["limits"], control)

    def close(self) -> None:
        self._engine_cls.segment_runner = self._runner
