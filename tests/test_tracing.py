"""The program's profiler spans (``repro.tracing``): a scenario-grid
search with archives, one service quantum and the build of a network
engine, recorded under
``jax.profiler``, must leave every span the module documents, each with
exactly its documented stats.

Sizes follow ``test_serving.py``'s service (4 slots, 2-sweep segments,
80 normalizer samples) and ``test_scenario_engine.py``'s strategies (2
directions x 2 chains); the grid search runs at the service's bucket
shape (4 cells x 4 chains), so both share the engine's programs."""
import dataclasses
import glob
import os
import re
from collections import defaultdict

import jax
import numpy as np
import pytest

from repro import tracing
from repro.core import workload
from repro.core.network import Network
from repro.pathfinding import (
    ParetoArchive,
    ScalarizationSweep,
    ScenarioEngine,
    fit_normalizer_batched,
)
from repro.serving import JobSpec, JobState, PathfinderService

WL = workload(1)
STRAT = ScalarizationSweep(directions=2, n_chains=2, sweeps=2)


def _documented():
    """``{name: stats}`` of the spans listed in ``repro.tracing``'s
    docstring, one ``- ``name`` (``stat``, ...): ...`` line each."""
    return {m.group(1): set(re.findall(r"``(\w+)``", m.group(2) or ""))
            for m in re.finditer(r"^- ``(repro\.[\w.]+)``(?: \(([^)]*)\))?:",
                                 tracing.__doc__, re.M)}


def _spans(log_dir):
    """``{name: [(start, end, stats)]}`` of the ``repro.*`` host spans in
    the newest trace under ``log_dir``."""
    from jax.profiler import ProfileData

    path = max(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                         recursive=True), key=os.path.getmtime)
    out = defaultdict(list)
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("repro."):
                    s = float(ev.start_ns)
                    out[ev.name].append((s, s + float(ev.duration_ns),
                                         dict(ev.stats)))
    return out


@pytest.fixture(scope="module")
def svc():
    return PathfinderService([WL], slots=4, segment=2, norm_samples=80)


@pytest.fixture(scope="module")
def recorded(svc, tmp_path_factory):
    eng, S, n = svc.engine, 4, STRAT.directions * STRAT.n_chains
    mins, meds = fit_normalizer_batched(
        WL, samples=80, seed=7, space=svc.space).weights_arrays()
    w6 = STRAT.weight_rows()
    archives = [ParetoArchive(max_size=STRAT.frontier_size)
                for _ in range(S)]
    log_dir = str(tmp_path_factory.mktemp("profile"))
    jax.profiler.start_trace(log_dir)
    try:
        assert tracing.enabled()
        eng.parallel_tempering(
            svc.space.sample(S * n, key=3).reshape(S, n, -1),
            np.tile(STRAT.chain_temps(w6.shape[0]), (S, 1)), 4,
            STRAT.swap_every, seed=5, mins=np.tile(mins, (S, 1)),
            medians=np.tile(meds, (S, 1)),
            weights=np.tile(STRAT.chain_weights(w6)[None], (S, 1, 1)),
            pair_mask=np.tile(STRAT.chain_pair_mask(n), (S, 1)),
            ci=np.full(S, 0.475), widx=np.zeros(S, np.int32),
            segment=2, archives=archives)
        svc.submit(JobSpec(job_id="job", workload=WL.name, strategy=STRAT))
        assert svc.step()
        # a second job paused at a boundary and resumed: admitted again
        svc.submit(JobSpec(job_id="paused", workload=WL.name,
                           strategy=dataclasses.replace(STRAT, sweeps=6)))
        svc.step()
        svc.pause("paused")
        svc.step()
        assert svc.status("paused") is JobState.PAUSED
        svc.resume_job("paused")
        svc.step()
        ScenarioEngine((Network("pair", ((WL, 2), (workload(4), 1))),),
                       space=svc.space, use_pallas=False)
    finally:
        jax.profiler.stop_trace()
    assert not tracing.enabled()
    assert svc.status("job") is JobState.DONE
    return _spans(log_dir)


def test_every_span_with_its_stats(recorded):
    documented = _documented()
    assert documented["repro.archive.insert"] == {
        "offered", "screened_out", "prefiltered", "size"}
    for name, stats in documented.items():
        assert recorded.get(name), f"{name} not recorded"
        for _, _, got in recorded[name]:
            assert set(got) == stats, (name, got)
    assert set(recorded) == set(documented)


def test_span_values(recorded, svc):
    r = recorded
    # 4 sweeps in 2-sweep segments, then the service's one segment
    assert [st["sweeps"] for _, _, st in r["repro.segment.dispatch"]] \
        == [2, 2]
    assert r["repro.pt.prepare"][0][2] == {"cells": 4, "chains": 4,
                                          "layers": 1}
    (_, _, tables), = r["repro.network.tables"]
    assert tables["layers"] == 2 and tables["gemms"] == 2
    assert tables["bytes"] > 0
    for _, _, st in r["repro.archive.insert"]:
        assert st["offered"] >= st["prefiltered"] + st["screened_out"]
        assert st["prefiltered"] >= 0 and st["screened_out"] >= 0
        assert 1 <= st["size"] <= STRAT.frontier_size
    # each absorb holds its wait and fetch, and offers its rows to the
    # archives: the seed block and 2 sweeps of 4 x 4 chains, then 2 sweeps
    absorbs = r["repro.segment.absorb"]
    assert [st["rows"] for _, _, st in absorbs] == [48, 32]
    for name in ("repro.segment.wait", "repro.segment.fetch"):
        assert len(r[name]) == 2
        for (s, e, _), (a, b, _) in zip(r[name], absorbs):
            assert a <= s <= e <= b
    for a, b, st in absorbs:
        assert st["rows"] == sum(i["offered"] for s, _, i
                                 in r["repro.archive.insert"] if a <= s <= b)
    # only the outputs the engine reads come back: per sweep the cells'
    # best costs (float64) and every chain's proposal row (int32) and
    # objective vector (3 x float64)
    for _, _, st in r["repro.segment.fetch"]:
        assert st["bytes"] == 2 * 4 * (8 + 4 * (4 * svc.space.width + 3 * 8))
    # the service: one admission, one bucket segment, the job done
    assert r["repro.service.tick"][0][2] == {"admitted": 1, "buckets": 1}
    admits = [st for _, _, st in r["repro.service.admit"]]
    assert [a["first"] for a in admits] == [1, 1, 0]
    assert min(a["queue_wait_us"] for a in admits) >= 0
    assert r["repro.service.boundary"][0][2] == {"jobs": 1, "finished": 1}
    assert r["repro.service.upload"][0][2]["bytes"] > 0
    assert r["repro.service.fetch"][0][2]["bytes"] > 0
    fin = r["repro.service.finish"][0][2]
    assert fin["segments"] == 1 and fin["queue_us"] >= 0 \
        and fin["run_us"] >= 0
