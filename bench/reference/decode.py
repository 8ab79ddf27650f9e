"""Encoded design rows -> systems, and their objective vectors.

The row layout is the program's public encoding (``DesignSpace``): 9
whole-design columns, 3 per chiplet slot, then 2 NoC columns per slot
under ``mesh_noc`` and 2 schedule columns under ``window``. Written
here from that layout, so a row the program mis-encodes decodes to
another system (or to none) and fails the comparison.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Sequence, Tuple

from .chiplet import Chiplet
from .evaluate import evaluate
from .system import HISystem, InvalidSystem, validate
from .techdb import (
    DATAFLOWS,
    DEFAULT_DB,
    INTEGRATION_STYLES,
    TechDB,
    valid_pairs_25d,
    valid_pairs_3d,
)
from .workload import GEMMWorkload, Mapping

MAX_CHIPLETS = 6
N_HEAD = 9


def decode(row: Sequence[int], comm: str, schedule: str,
           db: TechDB = DEFAULT_DB) -> HISystem:
    """The system an encoded row stands for. Raises ``InvalidSystem``
    for a row that names nothing in the library."""
    r = [int(x) for x in row]
    width = (N_HEAD + 3 * MAX_CHIPLETS
             + (2 * MAX_CHIPLETS if comm == "mesh_noc" else 0)
             + (2 if schedule == "window" else 0))
    if len(r) != width:
        raise InvalidSystem(f"row width {len(r)} != {width}")
    arrays, nodes = tuple(db.array_sizes), tuple(db.tech_nodes)
    memories = tuple(db.memories)
    n = r[0]
    if not 1 <= n <= MAX_CHIPLETS:
        raise InvalidSystem(f"chiplet count {n}")
    try:
        chips = []
        for i in range(n):
            a, t, s = r[N_HEAD + 3 * i:N_HEAD + 3 * i + 3]
            if min(a, t, s) < 0:
                raise IndexError(i)
            array = arrays[a]
            chips.append(Chiplet(array, nodes[t], db.sram_sizes_kb[array][s]))
        p25 = valid_pairs_25d()[r[6]] if r[6] >= 0 else (None, None)
        p3 = valid_pairs_3d()[r[7]] if r[7] >= 0 else (None, None)
        noc: Tuple[Tuple[int, int], ...] = ()
        col = N_HEAD + 3 * MAX_CHIPLETS
        if comm == "mesh_noc":
            noc = tuple((r[col + 2 * i], r[col + 2 * i + 1])
                        for i in range(n))
            col += 2 * MAX_CHIPLETS
        sched: Optional[Tuple[int, int]] = None
        if schedule == "window":
            sched = (r[col], r[col + 1])
        system = HISystem(
            chiplets=tuple(chips), style=INTEGRATION_STYLES[r[1]],
            memory=memories[r[2]],
            mapping=Mapping(r[3], DATAFLOWS[r[4]], r[5]),
            pkg_25d=p25[0], proto_25d=p25[1], pkg_3d=p3[0], proto_3d=p3[1],
            stack=tuple(i for i in range(n) if (r[8] >> i) & 1),
            noc=noc, schedule=sched)
    except (IndexError, KeyError) as e:
        raise InvalidSystem(f"row {r} indexes outside the library: {e}")
    validate(system, db, MAX_CHIPLETS)
    return system


def region_db(carbon_intensity: float, grid_profile: Sequence[float],
              db: TechDB = DEFAULT_DB) -> TechDB:
    """The technology database of one deployment region (scalar
    intensity plus its measured 24h profile; price and embodied factor
    neutral)."""
    return dataclasses.replace(db, carbon_intensity=float(carbon_intensity),
                               grid_profile=tuple(float(x)
                                                  for x in grid_profile))


def objective_vector(row: Sequence[int], wl: GEMMWorkload, db: TechDB,
                     comm: str, schedule: str) -> Tuple[float, float, float]:
    """``(latency_s, dollar, total_cfp)`` of an encoded design."""
    m = evaluate(decode(row, comm, schedule, db), wl, db)
    return (m.latency_s, m.dollar, m.total_cfp)
