"""Device-resident pathfinding tests: jitted + Pallas evaluator parity vs
the scalar reference, vectorized move validity, the lax.scan tempering
engine's trajectory equivalence with a host replay, and the supporting
satellites (LRU topology cache, exact-integer MetricsBatch rows)."""
import json
import math
import os
import random

import numpy as np
import pytest

from repro.core import TEMPLATES, workload
from repro.core.evaluate import evaluate
from repro.core.sa import random_system
from repro.core.scalesim import SimCache
from repro.core.system import is_valid
from repro.core.templates import METRIC_FIELDS, sa_cost
from repro.pathfinding import (
    DesignSpace,
    DeviceEvaluator,
    ParallelTempering,
    ParetoArchive,
    Pathfinder,
    evaluate_batch,
    fit_normalizer_batched,
    get_device_evaluator,
)

SPACE = DesignSpace()
WL = workload(1)
PARITY_FIELDS = METRIC_FIELDS + (
    "l_compute_rd_s", "l_d2d_s", "l_dram_wr_s", "e_compute_j", "e_d2d_j",
    "d2d_bits", "macs")


@pytest.fixture(scope="module")
def dev():
    return get_device_evaluator(WL, space=SPACE)


@pytest.fixture(scope="module")
def norm():
    return fit_normalizer_batched(WL, samples=400, seed=7, space=SPACE)


# ---------------------------------------------------------------------------
# Fused jitted evaluator: parity vs the scalar reference
# ---------------------------------------------------------------------------


def test_device_scalar_parity_500(dev):
    """Property: the jitted fused path matches scalar ``evaluate`` within
    1e-6 relative on every metric field over a >= 500-system random
    population (in practice the match is ~1e-15)."""
    rng = random.Random(20260730)
    systems = [random_system(rng) for _ in range(500)]
    mb = dev.metrics(SPACE.encode_many(systems))
    cache = SimCache()
    for i, sys in enumerate(systems):
        m = evaluate(sys, WL, cache=cache)
        for f in PARITY_FIELDS:
            ref = getattr(m, f)
            got = float(getattr(mb, f)[i])
            assert got == pytest.approx(ref, rel=1e-6, abs=1e-300), (
                f"{sys.describe()} field {f}: scalar {ref} device {got}")


@pytest.mark.slow
def test_device_pallas_parity(dev):
    """The Pallas prefix-gather path (interpreter mode on CPU) produces
    the same metrics as the plain jitted gathers."""
    enc = SPACE.sample(256, key=31)
    dev_pl = DeviceEvaluator(WL, space=SPACE, use_pallas=True)
    a = dev.metrics(enc)
    b = dev_pl.metrics(enc)
    for f in PARITY_FIELDS:
        np.testing.assert_allclose(getattr(a, f), getattr(b, f), rtol=1e-12)


def test_device_matches_host_batch(dev):
    """Device vs host ``evaluate_batch`` across styles/workloads."""
    enc = SPACE.sample(490, key=5)  # shares the 512 bucket with the
    # scalar-parity population: no extra compile
    mb_h = evaluate_batch(enc, WL, space=SPACE)
    mb_d = dev.metrics(enc)
    for f in PARITY_FIELDS:
        a = np.asarray(getattr(mb_h, f), dtype=np.float64)
        b = np.asarray(getattr(mb_d, f), dtype=np.float64)
        rel = np.abs(a - b) / np.maximum(np.abs(a), 1e-300)
        assert rel.max() < 1e-9, f"{f}: {rel.max():.3e}"


def test_device_cost_fused(dev, norm):
    """evaluate_cost's fused Eq. 17 matches Objective.cost_batch."""
    from repro.pathfinding.strategies import Objective

    enc = SPACE.sample(128, key=9)
    tpl = TEMPLATES["T2"]
    mb, cost = dev.evaluate_cost(enc, norm, tpl)
    obj = Objective(WL, tpl, norm, device=False)
    np.testing.assert_allclose(cost, obj.cost_batch(mb), rtol=1e-12)
    # and against the scalar sa_cost for a few rows
    for i in (0, 17, 99):
        m = evaluate(SPACE.decode(enc[i]), WL)
        assert cost[i] == pytest.approx(sa_cost(m, tpl, norm), rel=1e-9)


def test_bucketing_consistency(dev):
    """Odd population sizes are padded to buckets; the padding must not
    leak into real rows."""
    enc = SPACE.sample(97, key=13)
    mb_all = dev.metrics(enc)
    mb_one = dev.metrics(enc[:1])
    assert len(mb_all) == 97 and len(mb_one) == 1
    assert float(mb_all.latency_s[0]) == float(mb_one.latency_s[0])


# ---------------------------------------------------------------------------
# Vectorized hierarchical moves
# ---------------------------------------------------------------------------


def test_propose_batch_valid_and_diverse(dev):
    enc = SPACE.sample(2048, key=3)
    out = dev.propose(enc, seed=5)
    assert out.dtype == np.int32 and out.shape == enc.shape
    assert SPACE.validity_mask(out).all()
    for sys in SPACE.decode_many(out[:128]):
        assert is_valid(sys)
    changed = (out != enc).any(axis=1)
    assert changed.mean() > 0.8  # only no-op moves (e.g. 2D package) skip
    # every move level occurs: mapping cols, memory, chiplet cols, count,
    # package cols
    diff_any = lambda cols: (out[:, cols] != enc[:, cols]).any()  # noqa: E731
    assert diff_any([3, 4, 5]) and diff_any([2]) and diff_any([0])
    assert diff_any([6, 7]) and diff_any(list(range(9, enc.shape[1])))


def test_propose_batch_deterministic(dev):
    enc = SPACE.sample(64, key=1)
    a = dev.propose(enc, seed=42)
    b = dev.propose(enc, seed=42)
    assert (a == b).all()
    c = dev.propose(enc, seed=43)
    assert (a != c).any()


# ---------------------------------------------------------------------------
# The lax.scan tempering engine
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_device_pt_trajectory_matches_host_replay(dev, norm):
    """Fixed-seed trajectory equivalence: replaying the device engine's
    recorded proposals and uniforms through a host loop built on scalar
    ``evaluate`` reproduces the accepted-cost history exactly (within
    float tolerance)."""
    tpl = TEMPLATES["T1"]
    n, sweeps, swap_every = 6, 25, 5
    rng = random.Random(3)
    v0 = SPACE.encode_many([random_system(rng) for _ in range(n)])
    ratio = (1.0 / 4000.0) ** (1.0 / (n - 1))
    temps = np.array([4000.0 * ratio ** i for i in range(n)])
    res = dev.parallel_tempering(v0, temps, sweeps, swap_every, seed=11,
                                 norm=norm, template=tpl, record_trace=True)
    tr = res.trace
    cache = SimCache()

    def scost(vec):
        return sa_cost(evaluate(SPACE.decode(vec), WL, cache=cache),
                       tpl, norm)

    costs = [scost(v0[i]) for i in range(n)]
    hist = [min(costs)]
    best_c = min(costs)
    inv_t = 1.0 / temps
    for s in range(sweeps):
        pcost = [scost(tr["proposals"][s][i]) for i in range(n)]
        u, us = tr["u_accept"][s], tr["u_swap"][s]
        for i in range(n):
            delta = pcost[i] - costs[i]
            if delta <= 0 or u[i] < math.exp(-delta / max(temps[i], 1e-12)):
                costs[i] = pcost[i]
                best_c = min(best_c, pcost[i])
        if s % swap_every == 0:
            for i in range(n - 1):
                d = (inv_t[i] - inv_t[i + 1]) * (costs[i] - costs[i + 1])
                if d >= 0 or us[i] < math.exp(min(d, 0.0)):
                    costs[i], costs[i + 1] = costs[i + 1], costs[i]
        hist.append(costs[-1])
        np.testing.assert_allclose(costs, tr["costs"][s], rtol=1e-9,
                                   err_msg=f"sweep {s}")
    np.testing.assert_allclose(hist, res.history, rtol=1e-9)
    assert res.best_cost == pytest.approx(best_c, rel=1e-9)


@pytest.mark.slow
def test_device_pt_deterministic_and_improves(dev, norm):
    tpl = TEMPLATES["T1"]
    v0 = SPACE.sample(4, key=2)
    temps = np.array([4000.0, 200.0, 10.0, 1.0])
    r1 = dev.parallel_tempering(v0, temps, 30, 5, seed=1, norm=norm,
                                template=tpl)
    r2 = dev.parallel_tempering(v0, temps, 30, 5, seed=1, norm=norm,
                                template=tpl)
    assert r1.history == r2.history and r1.best_cost == r2.best_cost
    assert (r1.best_enc == r2.best_enc).all()
    assert r1.evaluations == 4 + 4 * 30
    assert r1.best_cost <= r1.history[0] + 1e-12
    assert SPACE.validity_mask(r1.final_enc).all()
    assert is_valid(SPACE.decode(r1.best_enc))


GOLDEN_ONE_CELL = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                               "goldens", "device_pt_onecell.json")


@pytest.mark.parametrize("comm,schedule", [("legacy", "fixed"),
                                           ("mesh_noc", "window")])
def test_single_workload_pt_pinned_bit_for_bit(comm, schedule):
    """A short single-workload search (4 chains, 10 sweeps in segments
    of 4, exchange every 5th sweep, an archive fed) is pinned bit for
    bit: history, best cost and row, final population and costs, and
    the archive's vectors. The values were taken from the separate
    single-workload scan program that the one-cell view of the engine
    replaced; both spaces' move levels (live NoC and schedule axes in
    the second) ride the same pin."""
    space = (DesignSpace() if comm == "legacy"
             else DesignSpace(comm=comm, schedule=schedule))
    assert (space.comm, space.schedule) == (comm, schedule)
    norm = fit_normalizer_batched(WL, samples=200, seed=7, space=space)
    archive = ParetoArchive(max_size=64)
    res = get_device_evaluator(WL, space=space).parallel_tempering(
        space.sample(4, key=5), np.array([8.0, 4.0, 2.0, 1.0]), 10, 5,
        seed=11, norm=norm, template=TEMPLATES["T1"], segment=4,
        archive=archive)
    with open(GOLDEN_ONE_CELL) as f:
        pin = json.load(f)[f"{comm}/{schedule}"]

    def hexes(a):
        return [float(x).hex() for x in np.asarray(a).ravel()]

    assert [float(h).hex() for h in res.history] == pin["history"]
    assert float(res.best_cost).hex() == pin["best_cost"]
    assert res.best_enc.tolist() == pin["best_enc"]
    assert res.final_enc.tolist() == pin["final_enc"]
    assert hexes(res.final_costs) == pin["final_costs"]
    assert [hexes(r) for r in archive.vectors] == pin["archive_vectors"]
    assert res.evaluations == 4 + 4 * 10


def test_one_cell_grid_and_single_workload_share_segment_program(dev, norm):
    """A one-cell grid run of the engine and a single-workload run of
    the same workload, chain count, segment and exchange cadence run
    the same segment program: it compiles once between them."""
    from repro.pathfinding.device import get_scenario_engine, trace_count

    eng = get_scenario_engine((WL,), space=SPACE)
    assert dev.engine is eng
    n, sweeps, seg, swap = 3, 6, 3, 3
    v0 = SPACE.sample(n, key=12)
    temps = np.array([4.0, 2.0, 1.0])
    mins, medians = norm.weights_arrays()
    before = trace_count("scenario_pt")
    grid = eng.parallel_tempering(
        v0[None], temps[None], sweeps, swap, 5, mins[None], medians[None],
        np.tile(TEMPLATES["T1"].weights, (1, n, 1)),
        np.ones((1, n - 1), bool), [dev.db.carbon_intensity], [0],
        segment=seg)
    assert trace_count("scenario_pt") == before + 1
    one = dev.parallel_tempering(v0, temps, sweeps, swap, seed=5,
                                 norm=norm, template=TEMPLATES["T1"],
                                 segment=seg)
    assert trace_count("scenario_pt") == before + 1
    assert one.history[0] == grid.history[0, 0]
    assert len(one.history) == grid.history.shape[1] == 1 + sweeps


@pytest.mark.slow
def test_pt_strategy_device_flag(norm):
    """ParallelTempering through the facade: the device engine honors
    budgets (whole sweeps only, evals <= budget) and the scalar fallback
    still engages when device=False."""
    pf = Pathfinder(WL, TEMPLATES["T1"], norm=norm, space=SPACE)
    assert pf.device
    res = pf.search(strategy=ParallelTempering(n_chains=4, sweeps=50),
                    budget=30, key=3)
    assert res.evaluations <= 30
    assert res.evaluations == 4 + 4 * ((30 - 4) // 4)
    assert is_valid(res.best)
    pf_host = Pathfinder(WL, TEMPLATES["T1"], norm=norm, space=SPACE,
                         device=False)
    assert not pf_host.device
    res_h = pf_host.search(
        strategy=ParallelTempering(n_chains=4, sweeps=5), key=3)
    assert is_valid(res_h.best)


def test_grid_sweep_device_matches_host(norm):
    """GridSweep through the fused evaluator finds the same optimum as
    the host path."""
    from repro.core.workload import ALL_MAPPINGS
    from repro.pathfinding import GridSweep

    g = GridSweep(memories=("DDR5",), mappings=ALL_MAPPINGS[:1])
    pf_d = Pathfinder(WL, TEMPLATES["T1"], norm=norm, space=SPACE)
    pf_h = Pathfinder(WL, TEMPLATES["T1"], norm=norm, space=SPACE,
                      device=False)
    rd = pf_d.search(strategy=g)
    rh = pf_h.search(strategy=g)
    assert rd.best == rh.best
    assert rd.best_cost == pytest.approx(rh.best_cost, rel=1e-9)


# ---------------------------------------------------------------------------
# Satellites: LRU topology cache
# ---------------------------------------------------------------------------


def test_topo_cache_lru_eviction(monkeypatch):
    from repro.pathfinding import batch as batch_mod
    from repro.pathfinding.batch import BatchEvaluator

    monkeypatch.setattr(batch_mod, "_TOPO_CACHE_MAX", 8)
    ev = BatchEvaluator(WL, space=SPACE)
    enc = SPACE.sample(64, key=21)
    # only 2.5D/hybrid rows hit the descriptor cache
    ev(enc)
    assert len(ev._topo_cache) <= 8
    keys_after_first = list(ev._topo_cache)
    # re-evaluating the same rows must refresh recency, not grow the dict
    ev(enc[-16:])
    assert len(ev._topo_cache) <= 8
    # and newly seen topologies keep being cached (no silent stop)
    ev(SPACE.sample(64, key=22))
    assert len(ev._topo_cache) == 8
    assert list(ev._topo_cache) != keys_after_first


def test_exact_tile_assignment_matches_float64_host():
    """The device's integer Algorithm 1 reproduces the host's IEEE
    float64 tile counts bit-for-bit, ties included: powers drawn from a
    few table entries put shares on and beside integers all the time."""
    import jax
    import jax.numpy as jnp

    from repro.jaxenv import search_numerics
    from repro.pathfinding.batch import get_evaluator
    from repro.pathfinding.device import _assign_jax, _base_cfg, _exact_ints

    ev = get_evaluator(WL, space=SPACE)
    cfg = _base_cfg(SPACE, ev.db, 6, 12, None)
    rng = np.random.default_rng(0)
    P, C = 4000, cfg.C
    nmask = np.arange(C)[None] < rng.integers(1, C + 1, P)[:, None]
    a = rng.integers(0, ev.t_power.shape[0], (P, C))
    t = rng.integers(0, ev.t_power.shape[1], (P, C))
    same = rng.random((P, C)) < 0.5
    a, t = np.where(same, a[:, :1], a), np.where(same, t[:, :1], t)
    order = rng.integers(0, 2, P)
    total = rng.choice([1, 3, 5, 6, 7, 12, 64, 72, 144], P)
    start_ref, count_ref = ev._assign(
        np.where(nmask, ev.t_power[a, t], 0.0), nmask, order,
        total.astype(np.int64))
    with search_numerics():
        start, count = jax.jit(lambda *x: _assign_jax(*x, cfg))(
            jnp.asarray(_exact_ints(ev.t_power)[a, t]), jnp.asarray(nmask),
            jnp.asarray(order), jnp.asarray(total))
    assert (np.asarray(count)[nmask] == count_ref[nmask]).all()
    assert (np.asarray(start)[nmask] == start_ref[nmask]).all()


def test_floorplan_choices_ignore_float_rounding_of_areas(dev):
    """The slicing floorplan's greedy split, sort orders and destination
    die read the exact integer areas: float64 areas off by a few ulps (a
    TPU's emulated float64) leave every route and hop count unchanged.
    Sums of distinct areas tie exactly often enough that the float
    comparisons flip on a few rows of this sample."""
    import jax
    import jax.numpy as jnp

    from repro.jaxenv import search_numerics
    from repro.pathfinding.device import COL_CHIP, COL_N, _topology_jax

    tb, cfg = dev.tables, dev.cfg
    P, C = 4000, cfg.C
    v = SPACE.sample(P, key=3)
    noise = np.random.default_rng(1).normal(size=(P, C)) * 4e-15

    def topo(v, noise):
        nmask = jnp.arange(C)[None] < v[:, COL_N][:, None]
        chip = v[:, COL_CHIP:COL_CHIP + 3 * C].reshape(P, C, 3)
        a, t, s = [jnp.where(nmask, chip[:, :, k], 0) for k in range(3)]
        areas = jnp.where(nmask, tb["chiplet"][a, t, s][..., 0], 0.0)
        areas_i = jnp.where(nmask, tb["t_area_i"][a, t, s], 0)
        return _topology_jax(v, areas * (1.0 + noise), areas_i, tb, cfg)

    with search_numerics():
        f = jax.jit(topo)
        exact = f(jnp.asarray(v), jnp.zeros((P, C)))
        noisy = f(jnp.asarray(v), jnp.asarray(noise))
    for k in ("hops", "hops3", "inc", "is2d", "interp"):
        np.testing.assert_array_equal(np.asarray(noisy[k]),
                                      np.asarray(exact[k]), err_msg=k)
