"""Scenario-engine tests: the stacked one-compile grid sweep, per-cell
RNG key folding, total-budget accounting, batched region-normalizer
fits, and the scenario-axis sharding path.

The compile-count regressions read :func:`repro.pathfinding.device
.trace_count`: a jit-wrapped Python body runs once per fresh XLA compile
and never on cache hits, so before/after deltas count compiles exactly.
"""
import dataclasses

import numpy as np
import pytest

from repro.core import TEMPLATES, workload
from repro.core.techdb import DEFAULT_DB
from repro.pathfinding import (
    DesignSpace,
    ParallelTempering,
    Pathfinder,
    ScalarizationSweep,
    ScenarioSweep,
    fit_normalizer_batched,
    fit_region_normalizers,
    fold_cell_key,
    non_dominated_mask,
)
from repro.pathfinding.device import trace_count
from repro.pathfinding.strategies import DEFAULT_SEARCH_KEY

SPACE = DesignSpace()
WL = workload(1)


# ---------------------------------------------------------------------------
# Per-cell key folding (the shared-RNG bugfix)
# ---------------------------------------------------------------------------


def test_fold_cell_key_distinct_and_deterministic():
    keys = [fold_cell_key(7, i) for i in range(64)]
    assert len(set(keys)) == 64, "cells must get distinct streams"
    assert keys == [fold_cell_key(7, i) for i in range(64)]
    # distinct bases give distinct folds
    assert fold_cell_key(0, 3) != fold_cell_key(1, 3)
    # key=0 is a valid base, distinct from the key=None default
    assert fold_cell_key(0, 0) != fold_cell_key(DEFAULT_SEARCH_KEY, 0)


@pytest.mark.slow
def test_key_zero_distinct_from_default_key(norm_wl1):
    """key=None resolves to DEFAULT_SEARCH_KEY, so key=0 is its own
    stream (previously both collapsed onto seed 0 in _search_device)."""
    pf = Pathfinder(WL, TEMPLATES["T1"], norm=norm_wl1, space=SPACE)
    strat = ParallelTempering(n_chains=4, sweeps=10)
    r_none = pf.search(strategy=strat)
    r_zero = pf.search(strategy=strat, key=0)
    r_default = pf.search(strategy=strat, key=DEFAULT_SEARCH_KEY)
    assert r_none.history == r_default.history
    assert r_none.history != r_zero.history


@pytest.fixture(scope="module")
def norm_wl1():
    return fit_normalizer_batched(WL, samples=400, seed=7, space=SPACE)


# ---------------------------------------------------------------------------
# Batched region-normalizer fits
# ---------------------------------------------------------------------------


def test_region_normalizers_bit_identical_to_per_region_fits():
    """One evaluate_batch + per-region ope rescale must equal a full
    per-region fit exactly — only operational CFP depends on the grid
    intensity, and it is a pure scalar multiple of energy."""
    cis = [0.024, 0.475, 0.82]
    fitted = fit_region_normalizers(WL, cis, samples=120, seed=9,
                                    space=SPACE)
    for ci, nz in zip(cis, fitted):
        db_s = dataclasses.replace(DEFAULT_DB, carbon_intensity=ci)
        ref = fit_normalizer_batched(WL, db_s, samples=120, seed=9,
                                     space=DesignSpace(db_s))
        assert nz.mins == ref.mins
        assert nz.medians == ref.medians


# ---------------------------------------------------------------------------
# Total-budget accounting (the silent budget-multiplication bugfix)
# ---------------------------------------------------------------------------


def test_budget_below_one_eval_per_cell_rejected():
    sweep = ScenarioSweep(
        strategy=ScalarizationSweep(directions=2, n_chains=2, sweeps=3),
        regions={"a": 0.1, "b": 0.5}, norm_samples=80)
    with pytest.raises(ValueError, match="one evaluation per cell"):
        sweep.run(WL, budget=1, key=1)


@pytest.mark.slow
def test_budget_is_total_across_cells():
    """budget= is the sweep total, split evenly — not per cell."""
    sweep = ScenarioSweep(
        strategy=ScalarizationSweep(directions=2, n_chains=2, sweeps=10),
        regions={"clean": 0.024, "dirty": 0.82}, norm_samples=80)
    sf = sweep.run(WL, budget=40, key=2)
    evals = [sf.results[s.key].evaluations for s in sf.scenarios]
    assert sum(evals) <= 40
    # 40 // 2 cells = 20 each; population 4 -> 4 whole sweeps -> 20 evals
    assert evals == [20, 20]
    # a budget below one chain population per cell is rejected loudly
    with pytest.raises(ValueError, match="chain population"):
        sweep.run(WL, budget=7, key=2)


# ---------------------------------------------------------------------------
# The one-compile stacked grid (ISSUE acceptance grid: 5 regions x 2 wl)
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_scenario_sweep_5x2_compiles_once_cells_differ_reproducible():
    wls = [workload(1), workload(6)]
    sweep = ScenarioSweep(
        strategy=ScalarizationSweep(directions=2, n_chains=2, sweeps=3),
        norm_samples=100)  # default REGION_INTENSITIES: 5 regions
    before = {k: trace_count(k)
              for k in ("scenario_pt", "scenario_init", "scenario_eval")}
    sf = sweep.run(wls, key=11)
    assert len(sf.scenarios) == 10
    # exactly ONE fused scenario-scan compile, ZERO per-cell programs: a
    # cell searched alone would add a one-cell init program of its own,
    # and one evaluator of its own beside the grid's frontier re-cost
    assert trace_count("scenario_pt") == before["scenario_pt"] + 1
    assert trace_count("scenario_init") == before["scenario_init"] + 1
    assert trace_count("scenario_eval") == before["scenario_eval"] + 1
    fronts = [sf.results[s.key].frontier.vectors for s in sf.scenarios]
    for f in fronts:
        assert len(f) >= 1 and non_dominated_mask(f).all()
    # distinct cells explore with distinct streams: no two identical
    for i in range(len(fronts)):
        for j in range(i + 1, len(fronts)):
            assert not np.array_equal(fronts[i], fronts[j]), (i, j)
    # reproducible per key, and the rerun hits the jit cache
    sf2 = sweep.run(wls, key=11)
    assert trace_count("scenario_pt") == before["scenario_pt"] + 1
    for s in sf.scenarios:
        assert np.array_equal(sf.results[s.key].frontier.vectors,
                              sf2.results[s.key].frontier.vectors)
        assert (sf.results[s.key].best_cost
                == sf2.results[s.key].best_cost)
    # a different key moves the frontiers (same shapes: still no compile)
    sf3 = sweep.run(wls, key=12)
    assert trace_count("scenario_pt") == before["scenario_pt"] + 1
    assert any(
        not np.array_equal(sf.results[s.key].frontier.vectors,
                           sf3.results[s.key].frontier.vectors)
        for s in sf.scenarios)
    # (the region -> operational-CFP shift itself is asserted at a
    # meaningful budget by test_pareto.test_scenario_sweep_regions_shift_cfp)


@pytest.mark.slow
def test_scenario_sweep_pallas_parity_5x2(monkeypatch):
    """Kernel fast path vs jnp reference on the acceptance grid (5
    regions x 2 workloads): same designs, metrics within the XLA
    fusion-noise band.

    The two runs share everything but ``REPRO_PATHFINDER_PALLAS`` (the
    engine cache keys on the resolved setting, so each run builds its
    own engine). The stacked kernel gathers from int64 prefix tables and
    interpret mode subtracts them exactly, so the only divergence is
    1-2 ulp of downstream float fusion across the pallas custom-call
    boundary — orders of magnitude inside the 1e-6 acceptance bound."""
    wls = [workload(1), workload(6)]
    sweep = ScenarioSweep(
        strategy=ScalarizationSweep(directions=2, n_chains=2, sweeps=3),
        norm_samples=100)  # default REGION_INTENSITIES: 5 regions

    def run(env):
        monkeypatch.setenv("REPRO_PATHFINDER_PALLAS", env)
        return sweep.run(wls, key=11)

    ref, fast = run("0"), run("1")
    assert len(ref.scenarios) == 10
    for s in ref.scenarios:
        a, b = ref.results[s.key], fast.results[s.key]
        assert np.allclose(a.best_cost, b.best_cost,
                           rtol=1e-9, atol=1e-12), s.key
        assert np.allclose(a.history, b.history,
                           rtol=1e-9, atol=1e-12), s.key
        # the search visits the same designs: proposal/accept streams
        # did not diverge anywhere on the grid
        assert a.best == b.best, s.key
        assert a.frontier.vectors.shape == b.frontier.vectors.shape
        assert np.allclose(a.frontier.vectors, b.frontier.vectors,
                           rtol=1e-9, atol=1e-12), s.key


@pytest.mark.slow
def test_run_scenarios_facade(norm_wl1):
    pf = Pathfinder(WL, TEMPLATES["T1"], norm=norm_wl1, space=SPACE)
    sweep = ScenarioSweep(
        strategy=ScalarizationSweep(directions=2, n_chains=2, sweeps=2),
        norm_samples=80)
    sf = pf.run_scenarios(sweep=sweep,
                          regions={"clean": 0.024, "dirty": 0.82}, key=4)
    assert len(sf.scenarios) == 2
    assert {s.region for s in sf.scenarios} == {"clean", "dirty"}
    merged = sf.merged(WL.name)
    assert len(merged) >= 1 and non_dominated_mask(merged.vectors).all()


# ---------------------------------------------------------------------------
# Scenario-axis sharding (run under
# XLA_FLAGS=--xla_force_host_platform_device_count=8 to exercise)
# ---------------------------------------------------------------------------


def test_scenario_sweep_sharded_matches_unsharded():
    import jax

    if len(jax.devices()) < 2:
        pytest.skip("needs >1 local devices "
                    "(XLA_FLAGS=--xla_force_host_platform_device_count=N)")
    from repro.distributed.sharding import scenario_mesh

    assert scenario_mesh() is not None
    wls = [workload(1), workload(6)]
    regions = {"hydro": 0.024, "eu-avg": 0.276,
               "world-avg": 0.475, "coal-heavy": 0.82}
    strat = ScalarizationSweep(directions=2, n_chains=2, sweeps=2)
    run = lambda shard: ScenarioSweep(   # noqa: E731
        strategy=strat, regions=regions, norm_samples=80,
        shard=shard).run(wls, key=5)
    sharded = run("auto")      # 8 cells over the virtual devices
    unsharded = run(False)
    assert len(sharded.scenarios) == 8
    for s in sharded.scenarios:
        a = sharded.results[s.key]
        b = unsharded.results[s.key]
        assert np.isfinite(a.best_cost)
        assert np.array_equal(a.frontier.vectors, b.frontier.vectors)
        assert a.best_cost == b.best_cost


def test_scenario_sweep_sharded_interrupt_resume_matches():
    """Checkpoint/resume under scenario-axis sharding: interrupt the
    sharded grid at a segment boundary, resume, and match the
    uninterrupted sharded run bit-for-bit without a second scan compile
    (the restored carry is re-placed onto the mesh)."""
    import tempfile

    import jax

    if len(jax.devices()) < 2:
        pytest.skip("needs >1 local devices "
                    "(XLA_FLAGS=--xla_force_host_platform_device_count=N)")
    from repro.pathfinding.resume import SearchCheckpointer
    import repro.pathfinding.strategies as strategies_mod

    wls = [workload(1), workload(6)]
    regions = {"hydro": 0.024, "coal-heavy": 0.82}
    sweep = ScenarioSweep(
        strategy=ScalarizationSweep(directions=2, n_chains=2, sweeps=4),
        regions=regions, norm_samples=80, shard="auto")
    run = lambda **kw: sweep.run(wls, key=6, segment=2, **kw)  # noqa: E731
    ref = run()

    class Dying(SearchCheckpointer):
        saves = 0

        def save(self, *a, **kw):
            path = super().save(*a, **kw)
            Dying.saves += 1
            if Dying.saves == 1:
                raise KeyboardInterrupt("simulated preemption")
            return path

    with tempfile.TemporaryDirectory() as d:
        orig = strategies_mod._checkpointer
        strategies_mod._checkpointer = (
            lambda cd: Dying(cd) if cd is not None else None)
        try:
            with pytest.raises(KeyboardInterrupt):
                run(checkpoint_dir=d)
        finally:
            strategies_mod._checkpointer = orig
        before = trace_count("scenario_pt")
        res = run(checkpoint_dir=d)
        # the resumed segment reuses the sharded program signature
        assert trace_count("scenario_pt") == before
    for s in ref.scenarios:
        a, b = res.results[s.key], ref.results[s.key]
        assert np.array_equal(a.frontier.vectors, b.frontier.vectors)
        assert a.best_cost == b.best_cost
        assert a.history == b.history
