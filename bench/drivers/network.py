"""Network driver: the study's scenario-grid search (``study.py``) with
every cell's workload one whole network of GEMMs, the configuration's
``layers`` with their counts, run through ``ScenarioEngine`` as
``ScenarioSweep.run`` runs a network.

Set-up, window and check are the study's: the window runs chunks of
``sweeps_per_chunk`` sweeps in ``segment``-sweep segments until
``seconds`` have passed, each chunk from the last one's populations; the
check compares sampled archive rows and whole frontiers with
``bench/reference/network.py``. The window's counters add ``layers``,
the engine's layer axis: every design evaluated is evaluated on that
many layers.

Traffic keys: those of ``study.py``.
"""
from __future__ import annotations

import numpy as np

from bench.check import frontier_checks
from bench.drivers import study
from bench.reference.network import NetworkReference


def network_of(config: dict):
    """The configuration's network as the program's type."""
    from repro.core import GEMMWorkload
    from repro.core.network import Network

    return Network(config["name"], tuple(
        (GEMMWorkload(w["name"], w["M"], w["K"], w["N"]), w["count"])
        for w in config["layers"]))


class Driver(study.Driver):
    def __init__(self, config: dict, traffic: dict, seed: int):
        from jax.profiler import TraceAnnotation

        from repro.core.regions import Region
        from repro.pathfinding import (
            DesignSpace,
            ScalarizationSweep,
            fit_region_normalizers,
            get_scenario_engine,
        )

        self._span = TraceAnnotation
        self.config, self.traffic, self.seed = config, traffic, seed
        self.space = DesignSpace(comm=config["comm"],
                                 schedule=config["schedule"])
        self.strat = ScalarizationSweep(**config["search"])
        w6 = self.strat.weight_rows()
        k = w6.shape[0]
        n = k * self.strat.n_chains
        net = network_of(config)
        regions = [Region(carbon_intensity=r["carbon_intensity"],
                          grid_profile=tuple(r["grid_profile"]))
                   for r in config["regions"]]
        self.cells = [(0, ri) for ri in range(len(regions))]
        S = len(self.cells)
        fitted = fit_region_normalizers(
            net, regions, samples=config["norm_samples"],
            seed=config["norm_seed"], space=self.space)
        mins = np.stack([nz.weights_arrays()[0] for nz in fitted])
        meds = np.stack([nz.weights_arrays()[1] for nz in fitted])
        self.kw = dict(
            mins=mins, medians=meds,
            weights=np.tile(self.strat.chain_weights(w6)[None], (S, 1, 1)),
            pair_mask=np.tile(self.strat.chain_pair_mask(n), (S, 1)),
            ci=np.asarray([r.carbon_intensity for r in regions]),
            widx=np.zeros(S, np.int32),
            profile=np.stack([r.profile_array() for r in regions]))
        self.temps = np.tile(self.strat.chain_temps(k), (S, 1))
        self.engine = get_scenario_engine((net,), space=self.space)
        self.v = self.space.sample(
            S * n, key=np.random.default_rng([seed, 0])).reshape(S, n, -1)
        self._archives(S)
        self._keys = np.random.default_rng([seed, 1])
        self.stalled = []
        # one segment: loads or compiles the init and segment programs
        self._chunk(traffic["segment"])

    def _archives(self, S: int) -> None:
        """The study's per-cell archives, span and sampled rows included
        (built by its constructor, which the network's cannot share)."""
        from repro.pathfinding import ParetoArchive

        span, recorder = self._span, study._Recorder(self.seed)

        class Archive(ParetoArchive):
            def __init__(self, cell, **kw):
                super().__init__(**kw)
                self.cell = cell

            def insert(self, encoded, vectors):
                with span("bench.archive_insert"):
                    n = super().insert(encoded, vectors)
                recorder.take(self.cell, encoded, vectors)
                return n

        self.recorder = recorder
        self.archives = [Archive(c, max_size=self.strat.frontier_size)
                         for c in range(S)]

    def run(self, seconds: float) -> dict:
        out = super().run(seconds)
        out["counters"]["layers"] = self.engine.layers
        return out

    def check(self, control: bool = False) -> list:
        ref = NetworkReference(self.config)
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
