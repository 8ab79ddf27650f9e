"""Pareto-frontier machinery tests: host-vs-jnp non-dominated filter
equivalence, archive idempotence/determinism/crowding, hypervolume,
cost-vector parity across the scalar/batched/device paths, and the
ScalarizationSweep / ScenarioSweep strategies."""
import dataclasses
import random

import numpy as np
import pytest

from repro.core import TEMPLATES, workload
from repro.core.evaluate import evaluate
from repro.core.sa import OBJECTIVE_AXES, cost_vector, random_system
from repro.core.system import is_valid
from repro.pathfinding import (
    DesignSpace,
    ParetoArchive,
    Pathfinder,
    ScalarizationSweep,
    ScenarioSweep,
    crowding_distance,
    fit_normalizer_batched,
    get_device_evaluator,
    hypervolume,
    non_dominated_mask,
    non_dominated_mask_jnp,
    simplex_directions,
    workloads_from_configs,
)
from repro.pathfinding import pareto
from repro.pathfinding.pareto import (
    FrontierFeed,
    directions_to_weights,
)

SPACE = DesignSpace()
WL = workload(1)


@pytest.fixture(scope="module")
def norm():
    return fit_normalizer_batched(WL, samples=400, seed=7, space=SPACE)


def _fronts(n_fronts=200, size=24, seed=3):
    rng = np.random.default_rng(seed)
    pts = rng.random((n_fronts, size, 3))
    pts[:, ::5] = pts[:, 1::5]          # exact duplicate rows
    pts[:, 2::4, 1] = pts[:, 3::4, 1]   # single-axis ties
    return pts


# ---------------------------------------------------------------------------
# Non-dominated filtering: host reference vs jnp
# ---------------------------------------------------------------------------


def test_filter_host_jnp_equivalence_random_fronts():
    """The vectorized jnp filter matches the host reference *exactly*
    on random fronts with duplicates and per-axis ties."""
    fronts = _fronts()
    host = np.stack([non_dominated_mask(f) for f in fronts])
    dev = non_dominated_mask_jnp(fronts)   # batched leading dim
    assert host.shape == dev.shape
    assert (host == dev).all()
    # and per-front calls agree with the batched call
    for f in fronts[:10]:
        assert (non_dominated_mask_jnp(f) == non_dominated_mask(f)).all()


def test_filter_known_cases():
    pts = np.array([[1.0, 1.0, 1.0],
                    [2.0, 2.0, 2.0],    # dominated
                    [0.5, 3.0, 1.0],    # trade-off: survives
                    [1.0, 1.0, 1.0]])   # duplicate: survives (dedup later)
    m = non_dominated_mask(pts)
    assert m.tolist() == [True, False, True, True]
    assert (non_dominated_mask_jnp(pts) == m).all()
    assert non_dominated_mask(np.zeros((0, 3))).shape == (0,)
    # no axes: no row is better anywhere, so none is dominated
    assert non_dominated_mask(np.zeros((3, 0))).tolist() == [True] * 3


def _broadcast_mask(points):
    """The host filter as one ``[n, n, axes]`` broadcast reduced over its
    last axis: the oracle the per-axis planes must match bit for bit."""
    p = np.atleast_2d(np.asarray(points, dtype=np.float64))
    if p.shape[0] == 0:
        return np.zeros(0, dtype=bool)
    le = np.all(p[:, None, :] <= p[None, :, :], axis=2)
    lt = np.any(p[:, None, :] < p[None, :, :], axis=2)
    return ~(le & lt).any(axis=0)


def _broadcast_dominated_by(src, dst):
    """:func:`pareto.dominated_by` as one ``[m, n, axes]`` broadcast."""
    s = np.atleast_2d(np.asarray(src, dtype=np.float64))
    p = np.atleast_2d(np.asarray(dst, dtype=np.float64))
    if s.shape[0] == 0 or p.shape[0] == 0:
        return np.zeros(p.shape[0], dtype=bool)
    le = np.all(s[:, None, :] <= p[None, :, :], axis=2)
    lt = np.any(s[:, None, :] < p[None, :, :], axis=2)
    return (le & lt).any(axis=0)


def _hard_front(n, d, seed, whole_rows):
    """A front that exercises every edge of the filter: a trade-off
    surface with dominated rows behind it, per-axis ties (rounded
    values), exact duplicates, and inf, -inf, NaN and signed zeros in
    single cells; ``whole_rows`` also sets whole rows to those values
    (one -inf row dominates nearly every other row)."""
    rng = np.random.default_rng(seed)
    p = rng.dirichlet(np.ones(d), n) if d > 1 else rng.random((n, 1))
    # centred on 0, so that zeros fall inside the front, not before it
    p = np.round(p * (1 + 0.5 * rng.exponential(1.0, (n, d))) - 1 / d, 2)
    if n > 1:
        p[rng.integers(0, n, n // 8)] = p[rng.integers(0, n, n // 8)]
    specials = [np.inf, -np.inf, np.nan, 0.0, -0.0]
    rows = rng.permutation(n)
    for v, i in zip(specials, rows[:5]):
        p[i, rng.integers(0, d)] = v
    for v, i in zip(whole_rows, rows[5:]):
        p[i] = v
    return p


@pytest.mark.parametrize("n", [0, 1, 63, 64, 65, 512, 1088])
@pytest.mark.parametrize("d", [1, 2, 3, 4])
def test_filter_matches_broadcast_oracle(n, d):
    """The per-axis plane filter gives the broadcast oracle's mask
    exactly: ties, duplicates, inf and NaN rows, any axis count, and
    sizes on both sides of the filter's row blocks."""
    for seed, whole_rows in enumerate([
            (), (np.inf, np.nan, 0.0, -0.0),
            (np.inf, -np.inf, np.nan, 0.0, -0.0)]):
        pts = _hard_front(n, d, 1000 * d + seed, whole_rows)
        got = non_dominated_mask(pts)
        want = _broadcast_mask(pts)
        assert got.dtype == bool and got.shape == (n,)
        assert np.array_equal(got, want)


@pytest.mark.parametrize("m, n", [(0, 5), (5, 0), (1, 1), (7, 300),
                                  (255, 512), (256, 320), (257, 64)])
@pytest.mark.parametrize("d", [1, 3])
def test_dominated_by_matches_broadcast_oracle(m, n, d):
    """The archive screen's comparison gives the broadcast oracle's mask
    exactly between two different sets (ties, duplicates, inf and NaN
    cells and rows, one or several blocks of dominators), and shares
    the filter's code path: a set screened against itself is the
    complement of its non-dominated mask."""
    for seed, whole_rows in enumerate([(), (np.inf, np.nan, 0.0, -0.0)]):
        src = _hard_front(m, d, 2000 * d + seed, whole_rows)
        dst = _hard_front(n, d, 3000 * d + seed, whole_rows)
        # shared cells, so some rows of the two sets tie or repeat
        if m and n:
            dst[: n // 3] = src[np.arange(n // 3) % m]
        got = pareto.dominated_by(src, dst)
        assert got.dtype == bool and got.shape == (n,)
        assert np.array_equal(got, _broadcast_dominated_by(src, dst))
        assert np.array_equal(~pareto.dominated_by(dst, dst),
                              non_dominated_mask(dst))


def test_hypervolume_exact_values():
    # one point: a single box
    assert hypervolume([[0.0, 0.0]], [1.0, 1.0]) == pytest.approx(1.0)
    # two staircase points with overlap
    assert hypervolume([[0.0, 0.5], [0.5, 0.0]],
                       [1.0, 1.0]) == pytest.approx(0.75)
    # 3D: unit box minus nothing
    assert hypervolume([[0.0, 0.0, 0.0]], [1, 1, 1]) == pytest.approx(1.0)
    # 3D staircase: two boxes of 0.5 volume overlapping in 0.25
    assert hypervolume([[0.5, 0.0, 0.0], [0.0, 0.5, 0.0]],
                       [1, 1, 1]) == pytest.approx(0.75)
    # points at/behind the reference contribute nothing
    assert hypervolume([[1.0, 1.0, 1.0], [2, 2, 2]], [1, 1, 1]) == 0.0
    # dominated points do not change the volume
    a = hypervolume([[0.2, 0.2, 0.2]], [1, 1, 1])
    b = hypervolume([[0.2, 0.2, 0.2], [0.6, 0.6, 0.6]], [1, 1, 1])
    assert a == pytest.approx(b)


def test_crowding_distance_boundaries_inf():
    pts = np.array([[0.0, 1.0], [0.25, 0.75], [0.5, 0.5], [1.0, 0.0]])
    cd = crowding_distance(pts)
    assert np.isinf(cd[0]) and np.isinf(cd[-1])
    assert np.isfinite(cd[1]) and np.isfinite(cd[2])
    assert crowding_distance(pts[:2]).tolist() == [np.inf, np.inf]


# ---------------------------------------------------------------------------
# The archive
# ---------------------------------------------------------------------------


def _random_batch(n, seed=0, width=12):
    rng = np.random.default_rng(seed)
    return (rng.integers(0, 9, (n, width)).astype(np.int32),
            rng.random((n, 3)))


def test_archive_insert_idempotent():
    enc, vec = _random_batch(500, seed=1)
    a = ParetoArchive(max_size=64)
    a.insert(enc, vec)
    before = (a.vectors, a.encoded)
    a.insert(a.encoded, a.vectors)   # self-insert: must be a no-op
    assert np.array_equal(a.vectors, before[0])
    assert np.array_equal(a.encoded, before[1])
    assert non_dominated_mask(a.vectors).all()


def test_archive_crowding_prune_deterministic():
    """Crowding-prune determinism: the same insert sequence always yields
    the identical archive (single-shot and repeated)."""
    enc, vec = _random_batch(2000, seed=2)
    a = ParetoArchive(max_size=32)
    a.insert(enc, vec)
    b = ParetoArchive(max_size=32)
    b.insert(enc, vec)
    assert np.array_equal(a.vectors, b.vectors)
    assert np.array_equal(a.encoded, b.encoded)
    assert len(a) <= 32
    # chunked feeds in the same sequence are deterministic too
    c = ParetoArchive(max_size=32)
    d = ParetoArchive(max_size=32)
    for lo in range(0, len(vec), 173):
        c.insert(enc[lo:lo + 173], vec[lo:lo + 173])
        d.insert(enc[lo:lo + 173], vec[lo:lo + 173])
    assert np.array_equal(c.vectors, d.vectors)
    assert np.array_equal(c.encoded, d.encoded)


def test_archive_order_invariant_under_bound():
    """While the bound is not hit, insertion order never matters: dedup +
    canonical storage make any order and chunking converge."""
    enc, vec = _random_batch(2000, seed=2)
    a = ParetoArchive(max_size=512)   # front is far smaller than this
    a.insert(enc, vec)
    assert len(a) < 512
    b = ParetoArchive(max_size=512)
    perm = np.random.default_rng(3).permutation(len(vec))
    for lo in range(0, len(vec), 173):   # ragged chunks, shuffled order
        b.insert(enc[perm][lo:lo + 173], vec[perm][lo:lo + 173])
    assert np.array_equal(a.vectors, b.vectors)
    assert np.array_equal(a.encoded, b.encoded)


def test_archive_dedup_and_bound():
    enc, vec = _random_batch(100, seed=4)
    # all-identical vectors: dedup keeps distinct encodings only
    same = np.tile(vec[:1], (100, 1))
    a = ParetoArchive(max_size=256)
    a.insert(np.vstack([enc, enc]), np.vstack([same, same]))
    assert len(a) == len(np.unique(enc, axis=0))
    # bound is enforced
    b = ParetoArchive(max_size=5)
    enc2, _ = _random_batch(400, seed=5)
    theta = np.linspace(0, np.pi / 2, 400)
    front = np.stack([np.cos(theta), np.sin(theta),
                      np.zeros_like(theta)], axis=1)
    b.insert(enc2, front)          # 400 mutually non-dominated points
    assert len(b) == 5
    # crowding keeps the extremes
    assert front[:, 0].min() in b.vectors[:, 0]
    assert front[:, 0].max() in b.vectors[:, 0]


def test_archive_backends_agree():
    enc, vec = _random_batch(600, seed=6)
    a = ParetoArchive(max_size=48, backend="numpy")
    b = ParetoArchive(max_size=48, backend="jnp")
    a.insert(enc, vec)
    b.insert(enc, vec)
    assert np.array_equal(a.vectors, b.vectors)
    assert np.array_equal(a.encoded, b.encoded)


class _MetaLog:
    """Stands in for ``repro.tracing.span`` in the archive: keeps each
    span's name and the stats attached to it."""

    def __init__(self):
        self.entries = []

    def __call__(self, name):
        self.entries.append((name, {}))
        return self

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set_metadata(self, **stats):
        self.entries[-1][1].update(stats)


def _insert_trail(monkeypatch, mask, dominated, batches):
    """Archive contents after each insert of ``batches``, the insert
    spans' stats, and how many crowding prunes ran, with ``mask`` as the
    archive's host filter and ``dominated`` as its screen."""
    log = _MetaLog()
    prunes = []

    def crowding(points):
        prunes.append(len(points))
        return crowding_distance(points)

    with monkeypatch.context() as m:
        m.setattr(pareto, "non_dominated_mask", mask)
        m.setattr(pareto, "dominated_by", dominated)
        m.setattr(pareto, "crowding_distance", crowding)
        m.setattr(pareto, "span", log)
        arch = ParetoArchive(max_size=256)
        trail = []
        for enc, vec in batches:
            arch.insert(enc, vec)
            trail.append((arch.encoded, arch.vectors))
    return trail, log.entries, prunes


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_archive_inserts_match_broadcast_oracle(monkeypatch, seed):
    """A study cell's boundaries, 832 rows each (64 chains x 13 rows of
    41-wide encodings), fed in a row to an archive of 256 whose crowding
    prune engages: contents and span stats match, insert for insert, an
    archive whose filter and screen are the broadcast oracle."""
    rng = np.random.default_rng(seed)
    batches = []
    for _ in range(5):
        vec = rng.dirichlet(np.ones(3), 832) * (
            1 + 0.3 * rng.exponential(1.0, (832, 3)))
        enc = rng.integers(0, 50, (832, 41)).astype(np.int32)
        # stalled chains offer a row again
        again, src = rng.integers(0, 832, (2, 80))
        vec[again], enc[again] = vec[src], enc[src]
        batches.append((enc, vec))
    got, got_log, got_prunes = _insert_trail(
        monkeypatch, non_dominated_mask, pareto.dominated_by, batches)
    want, want_log, want_prunes = _insert_trail(
        monkeypatch, _broadcast_mask, _broadcast_dominated_by, batches)
    for (ge, gv), (we, wv) in zip(got, want):
        assert np.array_equal(ge, we) and np.array_equal(gv, wv)
    assert got_log == want_log
    assert [name for name, _ in got_log] == ["repro.archive.insert"] * 5
    # the prune engaged: a merge held more rows than the bound
    assert got_prunes == want_prunes
    assert any(n > 256 for n in got_prunes)
    assert any(st["size"] == 256 for _, st in got_log)


def _recompute_insert(enc_state, vec_state, max_size, enc, vec):
    """The archive insert as a full re-filter of each chunk's union with
    the archive: the chunk pre-reduced alone (over 64 rows), stacked
    with the archive, deduped and ordered by ``np.unique``, filtered
    whole, pruned by crowding. The reference the incremental merge has
    to match bit for bit; also says whether a prune ran."""
    pruned = False
    if enc_state.shape[1] == 0 and enc.shape[1] > 0:
        enc_state = np.zeros((0, enc.shape[1]), dtype=np.int32)
    for lo in range(0, enc.shape[0], pareto._INSERT_CHUNK):
        ce = enc[lo:lo + pareto._INSERT_CHUNK]
        cv = vec[lo:lo + pareto._INSERT_CHUNK]
        if cv.shape[0] > 64:
            pre = _broadcast_mask(cv)
            ce, cv = ce[pre], cv[pre]
        all_enc = np.vstack([enc_state, ce])
        all_vec = np.vstack([vec_state, cv])
        key = np.hstack([all_vec, all_enc.astype(np.float64)])
        _, uniq = np.unique(key, axis=0, return_index=True)
        all_enc, all_vec = all_enc[uniq], all_vec[uniq]
        mask = _broadcast_mask(all_vec)
        all_enc, all_vec = all_enc[mask], all_vec[mask]
        if all_vec.shape[0] > max_size:
            pruned = True
            cd = crowding_distance(all_vec)
            keep = np.argsort(-cd, kind="stable")[:max_size]
            keep.sort()
            all_enc, all_vec = all_enc[keep], all_vec[keep]
        enc_state, vec_state = all_enc, all_vec
    return enc_state, vec_state, pruned


def _study_batch(rng, n=832, width=41):
    """A search boundary's rows: a trade-off surface with dominated rows
    behind it, encodings drawn independently."""
    vec = rng.dirichlet(np.ones(3), n) * (1 + 0.3 * rng.exponential(1.0,
                                                                    (n, 3)))
    return rng.integers(0, 50, (n, width)).astype(np.int32), vec


def _feed(case, rng):
    """``(archive kwargs, batches, inserts before a checkpoint round trip
    or None)`` for one case of the merge oracle test."""
    kw, restore = dict(max_size=256), None
    if case == "full_832":
        batches = [_study_batch(rng) for _ in range(6)]
    elif case == "stalled_repeats":
        batches = []
        for _ in range(5):
            enc, vec = _study_batch(rng)
            # stalled chains offer the same row within a batch, and rows
            # the archive already holds
            again, src = rng.integers(0, 832, (2, 120))
            vec[again], enc[again] = vec[src], enc[src]
            if batches:
                pe, pv = batches[-1]
                old = rng.integers(0, 832, 200)
                enc[:200], vec[:200] = pe[old], pv[old]
            batches.append((enc, vec))
    elif case == "equal_vectors":
        batches = []
        for _ in range(4):
            enc, vec = _study_batch(rng)
            enc -= 25     # signed encodings: their order decides ties
            # equal vectors under other encodings, in the batch and
            # against the archive's rows
            same = rng.integers(0, 832, (2, 150))
            vec[same[0]] = vec[same[1]]
            if batches:
                vec[:100] = batches[-1][1][rng.integers(0, 832, 100)]
            batches.append((enc, vec))
        kw = dict(max_size=96)
    elif case == "nan_row":
        batches = []
        for i in range(4):
            enc, vec = _study_batch(rng, 520)
            vec[3 + i, i % 3] = np.nan        # a NaN cell
            vec[400] = np.nan                 # a whole NaN row
            vec[401], enc[401] = vec[3 + i], enc[3 + i]   # and its twin
            batches.append((enc, vec))
    elif case == "signed_zeros":
        batches = []
        for _ in range(3):
            enc, vec = _study_batch(rng, 300)
            vec[:, 0] -= np.median(vec[:, 0])
            vec[:40, 0] = 0.0
            vec[40:80] = vec[:40]
            vec[40:80, 0] = -0.0              # the same rows with -0.0
            enc[40:80] = enc[:40]
            batches.append((enc, vec))
    elif case == "empty_width0":
        # the archive's row width is set by its first insert
        batches = [_study_batch(rng, 1), _study_batch(rng, 40),
                   _study_batch(rng, 700)]
    elif case == "zero_width_rows":
        # rows with no encoding: vectors alone tell duplicates apart
        batches = []
        for _ in range(3):
            enc, vec = _study_batch(rng, 600, width=0)
            vec[300:400] = vec[:100]
            batches.append((enc, vec))
        kw = dict(max_size=64)
    elif case == "small_batches":
        # a service job's boundary: 4 chains x 2 sweeps
        batches = [_study_batch(rng, 8) for _ in range(40)]
        kw = dict(max_size=16)
    elif case == "checkpoint":
        batches = [_study_batch(rng) for _ in range(5)]
        restore = 3
    elif case == "jnp":
        batches = [_study_batch(rng, 600) for _ in range(3)]
        kw = dict(max_size=64, backend="jnp")
    return kw, batches, restore


_FEED_CASES = ("full_832", "stalled_repeats", "equal_vectors", "nan_row",
               "signed_zeros", "empty_width0", "zero_width_rows",
               "small_batches", "checkpoint", "jnp")


@pytest.mark.parametrize("case", _FEED_CASES)
def test_archive_merge_matches_full_recompute(case):
    """The incremental merge (archive screen, pre-reduce of the
    survivors, retirement, sorted merge) leaves ``_enc`` and ``_vec``
    bit-identical to a full re-filter of the union, after every insert
    of each feed."""
    rng = np.random.default_rng(_FEED_CASES.index(case))
    kw, batches, restore = _feed(case, rng)
    arch = ParetoArchive(**kw)
    ref_enc = np.zeros((0, 0), dtype=np.int32)
    ref_vec = np.zeros((0, 3))
    pruned = False
    for i, (enc, vec) in enumerate(batches):
        if i == restore:
            arch = arch.from_checkpoint_arrays(arch.checkpoint_arrays())
        arch.insert(enc, vec)
        ref_enc, ref_vec, ref_pruned = _recompute_insert(
            ref_enc, ref_vec, arch.max_size, enc, vec)
        assert arch._enc.dtype == ref_enc.dtype
        assert arch._enc.shape == ref_enc.shape
        assert arch._enc.tobytes() == ref_enc.tobytes()
        assert arch._vec.tobytes() == ref_vec.tobytes()
        pruned |= ref_pruned
    if case in ("full_832", "checkpoint", "small_batches",
                "zero_width_rows"):
        assert pruned


def test_archive_project_2d_front():
    enc, vec = _random_batch(300, seed=7)
    a = ParetoArchive(max_size=128)
    a.insert(enc, vec)
    front2d = a.project((1, 2))
    assert non_dominated_mask(front2d).all()
    # the projected front dominates every archived point on those axes
    for c, f in a.vectors[:, 1:3]:
        assert any(fc <= c + 1e-12 and ff <= f + 1e-12
                   for fc, ff in front2d)


def test_archive_input_validation():
    a = ParetoArchive(max_size=8)
    enc, vec = _random_batch(4, seed=8)
    with pytest.raises(ValueError):
        a.insert(enc[:2], vec)
    with pytest.raises(ValueError):
        a.insert(enc, vec[:, :2])
    with pytest.raises(ValueError):
        ParetoArchive(max_size=0)
    with pytest.raises(ValueError):
        ParetoArchive(backend="cuda")
    a.insert(enc, vec)
    with pytest.raises(ValueError):
        a.insert(enc[:, :5], vec)   # width mismatch after first insert


def test_frontier_feed_disabled_and_buffering():
    feed = FrontierFeed(0)
    feed.add(*_random_batch(10))
    assert feed.done() is None
    feed = FrontierFeed(16, chunk=8)
    enc, vec = _random_batch(20, seed=9)
    for i in range(20):
        feed.add(enc[i], vec[i])
    arch = feed.done()
    ref = ParetoArchive(max_size=16)
    ref.insert(enc, vec)
    assert np.array_equal(arch.vectors, ref.vectors)


# ---------------------------------------------------------------------------
# Directions
# ---------------------------------------------------------------------------


def test_simplex_directions_deterministic_and_cover_corners():
    for k in (1, 3, 7, 16, 64):
        w = simplex_directions(k)
        assert w.shape == (k, 3)
        np.testing.assert_allclose(w.sum(axis=1), 1.0)
        assert np.array_equal(w, simplex_directions(k))
    w = simplex_directions(64)
    for corner in np.eye(3):
        assert (w == corner).all(axis=1).any()


def test_directions_to_weights_axes():
    w6 = directions_to_weights([[0.5, 0.3, 0.2]])
    # energy/area zero; latency->gamma, dollar->theta, cfp->zeta+eta
    np.testing.assert_allclose(w6[0], [0, 0, 0.5, 0.3, 0.2, 0.2])


# ---------------------------------------------------------------------------
# Cost-vector parity: scalar vs batched vs fused device program
# ---------------------------------------------------------------------------


def test_cost_vector_parity_scalar_batch_device(norm):
    rng = random.Random(11)
    systems = [random_system(rng) for _ in range(64)]
    enc = SPACE.encode_many(systems)
    pf = Pathfinder(WL, TEMPLATES["T1"], norm=norm, space=SPACE)
    mb, cost, vec = pf.evaluate_cost_vector(enc)
    assert vec.shape == (64, len(OBJECTIVE_AXES))
    # batched host rendering
    np.testing.assert_allclose(vec, mb.objective_vectors(), rtol=1e-9)
    # scalar reference (the <= 1e-6 device-parity contract)
    for i in (0, 13, 37, 63):
        ref = np.asarray(cost_vector(evaluate(systems[i], WL)))
        np.testing.assert_allclose(vec[i], ref, rtol=1e-6)
    # host (device=False) objective produces the same vectors
    pf_h = Pathfinder(WL, TEMPLATES["T1"], norm=norm, space=SPACE,
                      device=False)
    _, cost_h, vec_h = pf_h.evaluate_cost_vector(enc)
    np.testing.assert_allclose(vec, vec_h, rtol=1e-9)
    np.testing.assert_allclose(cost, cost_h, rtol=1e-9)


def test_device_evaluate_cost_vector_consistent(norm):
    dev = get_device_evaluator(WL, space=SPACE)
    enc = SPACE.sample(96, key=21)
    mb, cost, vec = dev.evaluate_cost_vector(enc, norm, TEMPLATES["T2"])
    mb2, cost2 = dev.evaluate_cost(enc, norm, TEMPLATES["T2"])
    np.testing.assert_allclose(cost, cost2, rtol=0)
    np.testing.assert_allclose(
        vec[:, 2], mb.emb_cfp_kg + mb.ope_cfp_kg, rtol=1e-12)


# ---------------------------------------------------------------------------
# Strategies: frontier field + ScalarizationSweep + ScenarioSweep
# ---------------------------------------------------------------------------


def test_every_strategy_returns_frontier(norm):
    from repro.pathfinding import GridSweep, RandomSearch

    pf = Pathfinder(WL, TEMPLATES["T1"], norm=norm, space=SPACE,
                    device=False)
    for strat in (RandomSearch(batch_size=32),
                  GridSweep(memories=("DDR5",))):
        res = pf.search(strategy=strat, budget=64, key=1)
        assert res.frontier is not None and len(res.frontier) >= 1
        assert non_dominated_mask(res.frontier.vectors).all()
        assert f"frontier={len(res.frontier)}" in repr(res)


@pytest.mark.slow
def test_scalarization_sweep_device(norm):
    pf = Pathfinder(WL, TEMPLATES["T1"], norm=norm, space=SPACE)
    strat = ScalarizationSweep(directions=6, n_chains=3, sweeps=10)
    res = pf.search(strategy=strat, key=5)
    assert res.evaluations == 18 + 18 * 10
    assert len(res.frontier) >= 3
    assert non_dominated_mask(res.frontier.vectors).all()
    assert is_valid(res.best)
    # the best row is drawn from the frontier archive
    assert any(np.array_equal(SPACE.encode(res.best), e)
               for e in res.frontier.encoded)
    # deterministic per key
    res2 = pf.search(strategy=strat, key=5)
    assert np.array_equal(res.frontier.vectors, res2.frontier.vectors)
    assert res.best_cost == res2.best_cost
    # budget truncates to whole sweeps
    res3 = pf.search(strategy=strat, budget=100, key=5)
    assert res3.evaluations <= 100
    with pytest.raises(ValueError):
        pf.search(strategy=strat, budget=10, key=5)   # < one population
    # the frontier IS the sweep's output: disabling it is rejected
    with pytest.raises(ValueError, match="frontier_size"):
        pf.search(strategy=ScalarizationSweep(directions=2, n_chains=2,
                                              frontier_size=0), key=5)


def test_scalarization_sweep_host_fallback(norm):
    pf = Pathfinder(WL, TEMPLATES["T1"], norm=norm, space=SPACE,
                    device=False)
    strat = ScalarizationSweep(directions=3, n_chains=2, sweeps=4)
    res = pf.search(strategy=strat, key=2)
    assert res.frontier is not None and len(res.frontier) >= 2
    assert non_dominated_mask(res.frontier.vectors).all()
    assert res.evaluations == 3 * (2 + 2 * 4)
    assert is_valid(res.best)


@pytest.mark.slow
def test_scenario_sweep_regions_shift_cfp():
    """Operational CFP scales with the region's grid intensity, so the
    clean-grid frontier's best total CFP must beat the dirty grid's."""
    wls = workloads_from_configs(["smollm-135m"], tokens=256)
    sweep = ScenarioSweep(
        strategy=ScalarizationSweep(directions=3, n_chains=2, sweeps=5),
        regions={"clean": 0.024, "dirty": 0.82}, norm_samples=150)
    sf = sweep.run(wls, template="T1", device=False, key=1)
    assert len(sf.scenarios) == 2
    clean = sf.frontier(wls[0].name, "clean")
    dirty = sf.frontier(wls[0].name, "dirty")
    assert len(clean) and len(dirty)
    assert clean.vectors[:, 2].min() < dirty.vectors[:, 2].min()
    merged = sf.merged(wls[0].name)
    assert non_dominated_mask(merged.vectors).all()
    rows = list(sf.rows())
    assert len(rows) == len(clean) + len(dirty)
    assert {r[1] for r in rows} == {"clean", "dirty"}


def test_workloads_from_configs_shapes():
    (wl,) = workloads_from_configs(["smollm-135m"], tokens=128)
    assert wl.M == 128 and wl.K == 576 and wl.N == 1536
    assert "smollm" in wl.name


def test_objective_replace_keeps_vector_axes(norm):
    """Scalarization directions change the template, never the vector:
    frontiers merge across directions because the axes are raw units."""
    pf = Pathfinder(WL, TEMPLATES["T1"], norm=norm, space=SPACE,
                    device=False)
    obj = pf.objective()
    obj2 = dataclasses.replace(
        obj, template=dataclasses.replace(TEMPLATES["T3"], name="dir"))
    enc = SPACE.sample(16, key=1)
    _, c1, v1 = obj.eval_cost_vector_encoded(enc, SPACE)
    _, c2, v2 = obj2.eval_cost_vector_encoded(enc, SPACE)
    np.testing.assert_allclose(v1, v2, rtol=0)
    assert not np.allclose(c1, c2)
