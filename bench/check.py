"""The comparison that decides a run's ``correct``.

Every answer the timed path gives is an encoded design with the objective
vector ``(latency_s, dollar, total_cfp)`` the device computed for it. The
plain reference (``bench/reference``, the scalar model) decodes the row
and evaluates it under the cell's workload and region. The numbers a run
compares, each against a limit kept in the cell's traffic file:

- ``vec_gap``: the widest relative gap between a device vector component
  and the reference's, over the compared rows;
- ``invalid_rows``: rows the reference cannot decode into a valid system
  (limit 0);
- ``dominated_rows``: frontier rows that another row of the same frontier
  dominates under the reference's vectors by more than the ``vec_gap``
  limit (limit 0);
- ``stalled_share``: the share of chains that did not move (study: of
  the (cell, chain) pairs of every chunk in the window, those whose
  design at the chunk's end is the one it started from; service: of the
  (bucket program, slot, chain) lanes, those whose state no segment call
  in the window changed).

``control=True`` puts the reference in the program's place, computed in
float32, the precision below the configuration's float64: each reference
vector is rounded to float32, which is the closest any float32
computation can come to it.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from bench.reference.decode import objective_vector, region_db
from bench.reference.system import InvalidSystem
from bench.reference.workload import GEMMWorkload


@dataclasses.dataclass
class Check:
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return bool(np.isfinite(self.value)) and self.value <= self.limit


class Reference:
    """Objective vectors of encoded rows under a configuration's
    workloads and regions (indices as in the configuration file)."""

    def __init__(self, config: dict):
        self.comm = config["comm"]
        self.schedule = config["schedule"]
        self.workloads = [GEMMWorkload(w["name"], w["M"], w["K"], w["N"])
                          for w in config["workloads"]]
        self.dbs = [region_db(r["carbon_intensity"], r["grid_profile"])
                    for r in config["regions"]]

    def vector(self, row, wi: int, ri: int
               ) -> Optional[Tuple[float, float, float]]:
        """The reference vector, or ``None`` for a row that names no
        valid system."""
        try:
            return objective_vector(row, self.workloads[wi], self.dbs[ri],
                                    self.comm, self.schedule)
        except InvalidSystem:
            return None


def rel_gap(dev: Sequence[float], ref: Sequence[float]) -> float:
    d = np.asarray(dev, np.float64)
    r = np.asarray(ref, np.float64)
    return float(np.max(np.abs(d - r) / np.maximum(np.abs(r), 1e-300)))


def dominated_count(vectors: np.ndarray, tol: float) -> int:
    """Rows dominated by another row: no worse in every component and
    better in one, each by more than ``tol`` relative."""
    v = np.asarray(vectors, np.float64)
    if len(v) < 2:
        return 0
    hi = v * (1.0 + tol)
    lo = v * (1.0 - tol)
    no_worse = (v[:, None, :] <= hi[None, :, :]).all(axis=2)
    better = (v[:, None, :] < lo[None, :, :]).any(axis=2)
    dom = no_worse & better          # [j, i]: row j dominates row i
    np.fill_diagonal(dom, False)
    return int(dom.any(axis=0).sum())


def compare_rows(ref: Reference, rows, control: bool = False
                 ) -> Tuple[float, int, List[Optional[np.ndarray]]]:
    """``rows`` are ``(wi, ri, encoded row, device vector)``. Returns the
    widest gap, the count of invalid rows and each row's reference
    vector (``None`` where invalid)."""
    worst, invalid, refs = 0.0, 0, []
    for wi, ri, enc, vec in rows:
        r = ref.vector(enc, wi, ri)
        if r is None:
            invalid += 1
            refs.append(None)
            continue
        r = np.asarray(r, np.float64)
        if control:
            vec = r.astype(np.float32).astype(np.float64)
        worst = max(worst, rel_gap(vec, r))
        refs.append(r)
    return worst, invalid, refs


def frontier_checks(ref: Reference, sampled_rows, frontiers,
                    stalled: float, limits: Dict[str, float],
                    control: bool = False) -> List[Check]:
    """The four numbers of a run. ``sampled_rows`` are single rows drawn
    over the window; ``frontiers`` are whole frontiers, each a list of
    rows, checked row by row and for mutual non-domination."""
    gap, invalid, _ = compare_rows(ref, sampled_rows, control)
    dominated = 0
    for rows in frontiers:
        g, bad, refs = compare_rows(ref, rows, control)
        gap, invalid = max(gap, g), invalid + bad
        ok = [r for r in refs if r is not None]
        if ok:
            dominated += dominated_count(np.stack(ok), limits["vec_gap"])
    return [Check("vec_gap", gap, limits["vec_gap"]),
            Check("invalid_rows", float(invalid), limits["invalid_rows"]),
            Check("dominated_rows", float(dominated),
                  limits["dominated_rows"]),
            Check("stalled_share", stalled, limits["stalled_share"])]
