"""One-compile scenario sweeps: the stacked grid engine vs per-cell
rebuilds.

Claims asserted:
  (a) the full 5-region x 2-workload :class:`ScenarioSweep` compiles the
      fused scenario program exactly **once** (counted via the jit trace
      hook ``repro.pathfinding.device.trace_count``), with zero per-cell
      fused-program compiles;
  (b) at *equal evaluation budget* it beats the PR-3 per-cell path — a
      fresh ``Pathfinder``/``DeviceEvaluator`` (fresh normalizer fit,
      full program retrace) per (workload, region) cell — by >= 5x
      wall-clock on an unloaded machine (shared CI runners set a lower
      catastrophic-regression floor via ``SCENARIO_SWEEP_MIN_SPEEDUP``);
  (c) per-cell frontier hypervolume under the fixed per-cell keys is no
      worse than the per-cell path's on average (shared per-cell
      reference points; floor via ``SCENARIO_SWEEP_MIN_HV_RATIO``);
  (d) the *lifecycle* grid — every region upgraded to a full
      :class:`repro.core.regions.Region` with a distinct 24h diurnal
      grid-intensity profile, electricity price and embodied factor —
      runs on the same warm engine with exactly **zero** additional
      fused compiles: the three new axes are runtime columns of the one
      stacked program, not trace-time constants.

The derived summary carries cells/sec for both arms, the compile count,
the speedup, the mean hypervolume ratio, and the lifecycle-grid compile
count and timing.

Standalone: ``python -m benchmarks.scenario_sweep [--json out.json]``.
"""
from __future__ import annotations

import dataclasses
import json
import os
import sys
import time

import numpy as np

from repro.core import TEMPLATES, workload
from repro.core.techdb import DEFAULT_DB
from repro.pathfinding import (
    ParetoArchive,
    Pathfinder,
    ScalarizationSweep,
    ScenarioSweep,
    fold_cell_key,
    hypervolume,
)
from repro.core.regions import Region, measured_profile
from repro.pathfinding.device import trace_count
from repro.pathfinding.pareto import REGION_INTENSITIES
from benchmarks.common import row, timed

DIRECTIONS = 4
N_CHAINS = 2
# 8 chains x 100 sweeps = 808 evaluations per cell: enough budget that
# per-cell hypervolume is stable across keys (at ~200 evals/cell the
# key-to-key ratio swings 0.6x-1.8x and the (c) gate would be noise)
SWEEPS = 100
NORM_SAMPLES = 400
BASE_KEY = 1
MIN_SPEEDUP = float(os.environ.get("SCENARIO_SWEEP_MIN_SPEEDUP", "5.0"))
MIN_HV_RATIO = float(os.environ.get("SCENARIO_SWEEP_MIN_HV_RATIO", "0.95"))


def _per_cell_baseline(wls, strat, cell_budget):
    """The PR-3 path, reconstructed faithfully: every (workload, region)
    cell builds a fresh region TechDB -> fresh Pathfinder -> fresh
    normalizer fit -> fresh DeviceEvaluator (full fused-program retrace,
    since only the db *instance* changed). Keys are the same per-cell
    folds the stacked path uses, so the two arms are stream-comparable."""
    results = {}
    idx = 0
    for wl in wls:
        for region, ci in REGION_INTENSITIES.items():
            db_s = dataclasses.replace(DEFAULT_DB, carbon_intensity=ci)
            pf = Pathfinder(wl, TEMPLATES["T1"], db=db_s)
            pf.fit_normalizer(samples=NORM_SAMPLES, seed=1234)
            res = pf.search(strategy=strat, budget=cell_budget,
                            key=fold_cell_key(BASE_KEY, idx))
            results[(wl.name, region)] = res
            idx += 1
    return results


def _lifecycle_regions() -> dict:
    """The scalar-CI regions upgraded to full lifecycle cells: each
    gets its *measured* ElectricityMaps-style 24h grid trace
    (``repro.core.regions.measured_profile``, replacing the synthetic
    sinusoid), a distinct electricity price and a distinct embodied
    factor — five regions, no two sharing any axis value."""
    return {
        name: Region(
            carbon_intensity=ci,
            electricity_price=0.04 + 0.03 * i,
            emb_factor=0.90 + 0.06 * i,
            grid_profile=measured_profile(name))
        for i, (name, ci) in enumerate(REGION_INTENSITIES.items())
    }


def run(out=print) -> str:
    wls = [workload(1), workload(6)]
    strat = ScalarizationSweep(directions=DIRECTIONS, n_chains=N_CHAINS,
                               sweeps=SWEEPS)
    n_cells = len(wls) * len(REGION_INTENSITIES)
    nc = strat.weight_rows().shape[0] * strat.n_chains
    cell_budget = nc * (1 + SWEEPS)
    budget = n_cells * cell_budget
    sweep = ScenarioSweep(strategy=strat, norm_samples=NORM_SAMPLES)

    def compute():
        # -- (a) one compile for the whole grid ---------------------------
        before = {k: trace_count(k)
                  for k in ("scenario_pt", "scenario_init", "scenario_eval")}
        t0 = time.perf_counter()
        sf_cold = sweep.run(wls, budget=budget, key=BASE_KEY)
        t_cold = time.perf_counter() - t0  # includes the one compile
        compiles = trace_count("scenario_pt") - before["scenario_pt"]
        # the grid compiles at most one init and one eval program (the
        # frontier re-cost); a cell searched on its own would add its own
        per_cell_compiles = sum(
            max(0, trace_count(k) - before[k] - 1)
            for k in ("scenario_init", "scenario_eval"))
        t_warm = timed(
            lambda: sweep.run(wls, budget=budget, key=BASE_KEY))[1] / 1e6

        # -- (b) the per-cell rebuild path at equal budget ----------------
        t0 = time.perf_counter()
        base_results = _per_cell_baseline(wls, strat, cell_budget)
        t_base = time.perf_counter() - t0

        evals_new = sum(sf_cold.results[s.key].evaluations
                        for s in sf_cold.scenarios)
        evals_base = sum(r.evaluations for r in base_results.values())

        # -- (c) per-cell hypervolume, shared reference per cell ----------
        ratios = []
        for s in sf_cold.scenarios:
            a = sf_cold.results[s.key].frontier
            b = base_results[s.key].frontier
            union = ParetoArchive(max_size=2 * strat.frontier_size)
            union.merge(a)
            union.merge(b)
            ref = union.reference_point(margin=0.1)
            hv_a, hv_b = a.hypervolume(ref), b.hypervolume(ref)
            if hv_b > 0:
                ratios.append(hv_a / hv_b)

        # -- (d) lifecycle axes as data: zero extra compiles --------------
        # same workloads + db -> same warm ScenarioEngine; the profile /
        # price / embodied columns only change the runtime inputs of the
        # already-compiled program
        sweep_lc = ScenarioSweep(strategy=strat,
                                 regions=_lifecycle_regions(),
                                 norm_samples=NORM_SAMPLES)
        before_lc = trace_count("scenario_pt")
        t0 = time.perf_counter()
        sf_lc = sweep_lc.run(wls, budget=budget, key=BASE_KEY)
        t_lc = time.perf_counter() - t0
        lc_compiles = trace_count("scenario_pt") - before_lc
        evals_lc = sum(sf_lc.results[s.key].evaluations
                       for s in sf_lc.scenarios)
        return (sf_cold, compiles, per_cell_compiles, t_cold, t_warm,
                t_base, evals_new, evals_base, float(np.mean(ratios)),
                lc_compiles, t_lc, evals_lc)

    (sf, compiles, per_cell_compiles, t_cold, t_warm, t_base, evals_new,
     evals_base, hv_ratio, lc_compiles, t_lc, evals_lc), us = \
        timed(compute)
    speedup = t_base / t_cold
    out("# Scenario sweep: stacked one-compile grid vs per-cell rebuilds "
        f"({len(wls)} workloads x {len(REGION_INTENSITIES)} regions)")
    out("metric,value")
    out(f"cells,{len(sf.scenarios)}")
    out(f"budget_total,{evals_new}")
    out(f"fused_compiles,{compiles}")
    out(f"per_cell_compiles,{per_cell_compiles}")
    out(f"stacked_cold_s,{t_cold:.3f}")
    out(f"stacked_warm_s,{t_warm:.3f}")
    out(f"per_cell_s,{t_base:.3f}")
    out(f"cells_per_s_cold,{len(sf.scenarios) / t_cold:.3f}")
    out(f"cells_per_s_warm,{len(sf.scenarios) / t_warm:.3f}")
    out(f"speedup_cold,{speedup:.2f}")
    out(f"speedup_warm,{t_base / t_warm:.2f}")
    out(f"hv_ratio_mean,{hv_ratio:.4f}")
    out(f"lifecycle_compiles,{lc_compiles}")
    out(f"lifecycle_s,{t_lc:.3f}")
    out(f"lifecycle_evals,{evals_lc}")
    derived = (f"compiles={compiles};speedup={speedup:.2f}x;"
               f"warm_speedup={t_base / t_warm:.2f}x;"
               f"cells_per_s={len(sf.scenarios) / t_warm:.2f};"
               f"hv_ratio={hv_ratio:.3f};evals={evals_new};"
               f"lifecycle_compiles={lc_compiles};"
               f"lifecycle_s={t_lc:.2f}")
    assert compiles == 1, (
        f"stacked sweep compiled the fused scenario program {compiles}x "
        "(expected exactly 1)")
    assert per_cell_compiles == 0, (
        f"stacked sweep triggered {per_cell_compiles} per-cell "
        "fused-program compiles (expected 0)")
    assert evals_new == evals_base == budget, (
        f"budget accounting broke: stacked {evals_new}, per-cell "
        f"{evals_base}, budget {budget}")
    assert speedup >= MIN_SPEEDUP, (
        f"stacked sweep speedup {speedup:.2f}x < {MIN_SPEEDUP}x at "
        f"budget {budget}")
    assert hv_ratio >= MIN_HV_RATIO, (
        f"mean per-cell hypervolume ratio {hv_ratio:.3f} < "
        f"{MIN_HV_RATIO} vs the per-cell path")
    assert lc_compiles == 0, (
        f"the lifecycle (profile/price/embodied) grid retraced the "
        f"fused scenario program {lc_compiles}x on the warm engine "
        "(expected 0 — the axes are runtime columns)")
    assert evals_lc == budget, (
        f"lifecycle-grid budget accounting broke: {evals_lc} != {budget}")
    return row("scenario_sweep", us, derived)


def main() -> None:
    args = sys.argv[1:]
    json_path = None
    if "--json" in args:
        i = args.index("--json")
        try:
            json_path = args[i + 1]
        except IndexError:
            sys.exit("--json requires a path argument")
    lines = []
    summary = run(out=lines.append)
    print("\n".join(lines))
    print(summary)
    if json_path:
        name, us, derived = summary.split(",", 2)
        with open(json_path, "w") as f:
            json.dump({"rows": [{"name": name, "us_per_call": float(us),
                                 "derived": derived}]}, f, indent=2)
        print(f"# wrote {json_path}", file=sys.stderr)


if __name__ == "__main__":
    main()
