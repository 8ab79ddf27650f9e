"""A network of GEMMs on one system, from the scalar reference's
per-GEMM terms.

The layers run one after another on the same system under its one
mapping. Each layer's terms are those :func:`.evaluate.evaluate` gives
for its GEMM: Eq. 5's latency and the dynamic (compute and D2D)
energy. The network's latency is ``sum(count * latency)`` and its
dynamic energy ``sum(count * (e_compute + e_d2d))``, in layer order;
static power burns over that latency; area, cost and embodied carbon are
the system's, once; the bill and operational carbon follow from the
totals. Written here from that description, with nothing of the program.
"""
from __future__ import annotations

from typing import Optional, Sequence, Tuple

from . import carbon as carbon_mod
from . import cost as cost_mod
from .decode import decode, region_db
from .evaluate import evaluate
from .scalesim import SimCache
from .schedule import schedule_load_row
from .seqsum import seq_sum
from .system import HISystem, InvalidSystem
from .techdb import TechDB
from .workload import GEMMWorkload

Layers = Sequence[Tuple[GEMMWorkload, int]]


def network_vector(system: HISystem, layers: Layers, db: TechDB,
                   cache: Optional[SimCache] = None
                   ) -> Tuple[float, float, float]:
    """``(latency_s, dollar, total_cfp)`` of ``system`` running
    ``layers``, a list of ``(GEMM, count)``."""
    cache = cache if cache is not None else SimCache()
    per = [(c, evaluate(system, g, db, cache=cache)) for g, c in layers]
    latency = seq_sum(c * m.latency_s for c, m in per)
    e_dynamic = seq_sum(c * (m.e_compute_j + m.e_d2d_j) for c, m in per)
    static_w = seq_sum(ch.static_power_w(db) for ch in system.chiplets)
    energy = e_dynamic + static_w * latency
    area = per[0][1].area_mm2
    load = (schedule_load_row(system.schedule, db)
            if system.schedule is not None else None)
    dollar = (cost_mod.system_cost(system, area, db).total
              + carbon_mod.operational_cost_usd(energy, db, load=load))
    ope = carbon_mod.operational_cfp(energy, latency, db, per_unit=True,
                                     load=load)
    return latency, dollar, per[0][1].emb_cfp_kg + ope


class NetworkReference:
    """Objective vectors of encoded rows under a configuration's network
    (its ``layers``) and regions; the one workload has index 0."""

    def __init__(self, config: dict):
        self.comm = config["comm"]
        self.schedule = config["schedule"]
        self.layers = [(GEMMWorkload(w["name"], w["M"], w["K"], w["N"]),
                        int(w["count"])) for w in config["layers"]]
        self.dbs = [region_db(r["carbon_intensity"], r["grid_profile"])
                    for r in config["regions"]]
        self.cache = SimCache()

    def vector(self, row, wi: int, ri: int
               ) -> Optional[Tuple[float, float, float]]:
        """The reference vector, or ``None`` for a row that names no
        valid system."""
        assert wi == 0, wi
        db = self.dbs[ri]
        try:
            system = decode(row, self.comm, self.schedule, db)
        except InvalidSystem:
            return None
        return network_vector(system, self.layers, db, self.cache)
