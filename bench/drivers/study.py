"""Study driver: a scenario-grid tempering search run chunk after chunk
through ``ScenarioEngine.parallel_tempering``, the call
``ScenarioSweep.run`` makes.

Set-up builds what ``ScenarioSweep.run`` builds before that call (the
design space, the per-cell ladders and Eq. 17 rows, the region-fitted
normalizers, the stacked engine, the seed populations) and runs one
segment's worth of search, which loads or compiles every program the
window uses. The window then runs chunks of ``sweeps_per_chunk`` sweeps in
``segment``-sweep segments; each chunk starts from the previous chunk's
final populations with a fresh key and feeds the study's per-cell
frontier archives, until ``seconds`` have passed.

Traffic keys: ``sweeps_per_chunk``, ``segment``, ``frontier_cells`` (how
many cells' whole frontiers the check re-evaluates) and ``limits``.
"""
from __future__ import annotations

import time
from typing import List

import numpy as np

from bench.check import Reference, frontier_checks


class _Recorder:
    """A seeded sample of the rows fed to the archives: ``PER_INSERT``
    rows of each insert while armed."""

    PER_INSERT = 4

    def __init__(self, seed: int):
        self.rng = np.random.default_rng([seed, 7])
        self.armed = False
        self.rows: List[tuple] = []

    def take(self, cell: int, enc, vec) -> None:
        if self.armed and len(enc):
            for i in self.rng.integers(len(enc), size=self.PER_INSERT):
                self.rows.append((cell, np.array(enc[i]), np.array(vec[i])))


class Driver:
    def __init__(self, config: dict, traffic: dict, seed: int):
        from jax.profiler import TraceAnnotation

        from repro.core import GEMMWorkload
        from repro.core.regions import Region
        from repro.pathfinding import (
            DesignSpace,
            ParetoArchive,
            ScalarizationSweep,
            fit_region_normalizers,
            get_scenario_engine,
        )

        class Archive(ParetoArchive):
            """The study's per-cell archive, with a span around each
            insert and a seeded sample of what it is fed."""

            def __init__(self, cell, recorder, **kw):
                super().__init__(**kw)
                self.cell, self.recorder = cell, recorder

            def insert(self, encoded, vectors):
                with TraceAnnotation("bench.archive_insert"):
                    n = super().insert(encoded, vectors)
                self.recorder.take(self.cell, encoded, vectors)
                return n

        self._span = TraceAnnotation
        self.config, self.traffic, self.seed = config, traffic, seed
        self.space = DesignSpace(comm=config["comm"],
                                 schedule=config["schedule"])
        self.strat = ScalarizationSweep(**config["search"])
        w6 = self.strat.weight_rows()
        k = w6.shape[0]
        n = k * self.strat.n_chains
        wls = [GEMMWorkload(w["name"], w["M"], w["K"], w["N"])
               for w in config["workloads"]]
        regions = [Region(carbon_intensity=r["carbon_intensity"],
                          grid_profile=tuple(r["grid_profile"]))
                   for r in config["regions"]]
        # cell-major grid as ScenarioSweep lays it out: workloads outer
        self.cells = [(wi, ri) for wi in range(len(wls))
                      for ri in range(len(regions))]
        S = len(self.cells)
        mins = np.zeros((S, 6))
        meds = np.zeros((S, 6))
        for wi, wl in enumerate(wls):
            fitted = fit_region_normalizers(
                wl, regions, samples=config["norm_samples"],
                seed=config["norm_seed"], space=self.space)
            for ri, nz in enumerate(fitted):
                mins[wi * len(regions) + ri], meds[wi * len(regions) + ri] = (
                    nz.weights_arrays())
        self.kw = dict(
            mins=mins, medians=meds,
            weights=np.tile(self.strat.chain_weights(w6)[None], (S, 1, 1)),
            pair_mask=np.tile(self.strat.chain_pair_mask(n), (S, 1)),
            ci=np.asarray([regions[ri].carbon_intensity
                           for _, ri in self.cells]),
            widx=np.asarray([wi for wi, _ in self.cells], np.int32),
            profile=np.stack([regions[ri].profile_array()
                              for _, ri in self.cells]))
        self.temps = np.tile(self.strat.chain_temps(k), (S, 1))
        self.engine = get_scenario_engine(tuple(wls), space=self.space)
        self.v = self.space.sample(
            S * n, key=np.random.default_rng([seed, 0])).reshape(S, n, -1)
        self.recorder = _Recorder(seed)
        self.archives = [Archive(c, self.recorder,
                                 max_size=self.strat.frontier_size)
                         for c in range(S)]
        self._keys = np.random.default_rng([seed, 1])
        self.stalled: List[int] = []     # chains left at their start
        # one segment: loads or compiles the init and segment programs
        self._chunk(traffic["segment"])

    def _chunk(self, sweeps: int) -> int:
        with self._span("bench.chunk"):
            res = self.engine.parallel_tempering(
                self.v, self.temps, sweeps, self.strat.swap_every,
                seed=int(self._keys.integers(2 ** 31 - 1)),
                segment=self.traffic["segment"], archives=self.archives,
                **self.kw)
        # per (cell, chain): the chain's design at the chunk's end is the
        # one it started from
        still = (res.final_enc == self.v).all(axis=2)
        self.stalled.append(int(still.sum()))
        self.v = res.final_enc
        return res.evaluations

    def run(self, seconds: float) -> dict:
        """The measured window: whole chunks until ``seconds`` passed."""
        self.stalled = []
        self.recorder.armed = True
        evals = chunks = 0
        t0 = time.perf_counter()
        while True:
            evals += self._chunk(self.traffic["sweeps_per_chunk"])
            chunks += 1
            wall = time.perf_counter() - t0
            if wall >= seconds:
                break
        self.recorder.armed = False
        sweeps = chunks * self.traffic["sweeps_per_chunk"]
        return dict(
            values=dict(designs_per_s=evals / wall),
            attempted=chunks, failed=0,
            counters=dict(evaluations=evals, wall_s=wall, chunks=chunks,
                          sweeps=sweeps,
                          segments=sweeps // self.traffic["segment"],
                          cells=len(self.cells),
                          chains=self.v.shape[1]))

    def check(self, control: bool = False) -> list:
        ref = Reference(self.config)
        rows = [(*self.cells[c], enc, vec)
                for c, enc, vec in self.recorder.rows]
        pick = np.random.default_rng([self.seed, 2]).choice(
            len(self.cells), self.traffic["frontier_cells"], replace=False)
        frontiers = [[(*self.cells[c], e, v) for e, v in
                      zip(self.archives[c].encoded,
                          self.archives[c].vectors)]
                     for c in sorted(pick)]
        stalled = sum(self.stalled) / (len(self.stalled) * self.v.shape[0]
                                       * self.v.shape[1])
        return frontier_checks(ref, rows, frontiers, stalled,
                               self.traffic["limits"], control)

    def close(self) -> None:
        pass
