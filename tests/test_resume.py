"""Interruptible pathfinding: the segmented scan engine and its
checkpoint/resume invariants.

The contract under test: segmentation is *invisible* — a run advanced in
fixed-size segments consumes the identical key stream and sweep indices
as the monolithic scan, so (a) segmented == monolithic bit-for-bit, and
(b) a run interrupted at any segment boundary then resumed from its
checkpoint reproduces the uninterrupted run bit-for-bit (history, best,
frontier archive contents). A subprocess variant exercises a real
process death at a boundary; the CI kill-and-resume lane SIGTERMs a live
sweep mid-run (scripts/resume_worker.py).
"""
import os
import subprocess
import sys
import tempfile

import numpy as np
import pytest

from repro.core import TEMPLATES, workload
from repro.pathfinding import (
    DesignSpace,
    ParallelTempering,
    ParetoArchive,
    Pathfinder,
    SearchCheckpointer,
    fit_normalizer_batched,
)
from repro.pathfinding.device import get_device_evaluator, trace_count

SPACE = DesignSpace()
WL = workload(1)
TPL = TEMPLATES["T1"]


@pytest.fixture(scope="module")
def norm():
    return fit_normalizer_batched(WL, samples=400, seed=7, space=SPACE)


@pytest.fixture(scope="module")
def dev():
    return get_device_evaluator(WL, space=SPACE)


def _pt_args(n=4, seed=11):
    rng = np.random.default_rng(0)
    v0 = SPACE.sample(n, key=rng)
    ratio = (1.0 / 4000.0) ** (1.0 / (n - 1))
    temps = np.array([4000.0 * ratio ** i for i in range(n)])
    return v0, temps, seed


def _run(dev, norm, sweeps=12, frontier=4096, **kw):
    """Engine run with an external archive; frontier large enough that
    crowding pruning never engages (archive contents are then chunking-
    independent, so equality checks are exact by construction)."""
    v0, temps, seed = _pt_args()
    archive = ParetoArchive(max_size=frontier)
    res = dev.parallel_tempering(v0, temps, sweeps, 5, seed=seed,
                                 norm=norm, template=TPL,
                                 archive=archive, **kw)
    return res, archive


class _DyingCheckpointer(SearchCheckpointer):
    """Raises (simulating preemption) after N segment-boundary saves —
    the save itself completes first, exactly like SIGTERM landing
    between a finished snapshot and the next segment."""

    def __init__(self, directory, die_after):
        super().__init__(directory)
        self.die_after = die_after
        self._saves = 0

    def save(self, *a, **kw):
        path = super().save(*a, **kw)
        self._saves += 1
        if self._saves >= self.die_after:
            raise KeyboardInterrupt("simulated preemption")
        return path


@pytest.mark.slow
def test_segmented_matches_monolithic_bit_for_bit(dev, norm):
    ref, ref_arch = _run(dev, norm, sweeps=12, segment=None)
    for segment in (5, 1, 12, 30):
        got, got_arch = _run(dev, norm, sweeps=12, segment=segment)
        assert got.history == ref.history, f"segment={segment}"
        assert got.best_cost == ref.best_cost
        assert np.array_equal(got.best_enc, ref.best_enc)
        assert np.array_equal(got.final_enc, ref.final_enc)
        assert np.array_equal(got.final_costs, ref.final_costs)
        assert np.array_equal(got_arch.vectors, ref_arch.vectors)
        assert np.array_equal(got_arch.encoded, ref_arch.encoded)


@pytest.mark.slow
def test_interrupt_any_boundary_resume_bit_identical(dev, norm):
    """Kill after each possible boundary in turn; every resumed run must
    reproduce the uninterrupted segmented reference exactly."""
    ref, ref_arch = _run(dev, norm, sweeps=12, segment=5)  # segs 5,5,2
    for die_after in (1, 2, 3):
        with tempfile.TemporaryDirectory() as d:
            ck = _DyingCheckpointer(d, die_after=die_after)
            # a snapshot follows every segment (incl. the last), so the
            # dying checkpointer fires at every boundary choice
            with pytest.raises(KeyboardInterrupt):
                _run(dev, norm, sweeps=12, segment=5, checkpoint=ck)
            res, arch = _run(dev, norm, sweeps=12, segment=5,
                             checkpoint=SearchCheckpointer(d))
            assert res.history == ref.history, f"die_after={die_after}"
            assert res.best_cost == ref.best_cost
            assert np.array_equal(res.best_enc, ref.best_enc)
            assert np.array_equal(res.final_enc, ref.final_enc)
            assert np.array_equal(arch.vectors, ref_arch.vectors)
            assert np.array_equal(arch.encoded, ref_arch.encoded)


@pytest.mark.slow
def test_resume_after_completion_is_a_noop(dev, norm):
    with tempfile.TemporaryDirectory() as d:
        a, arch_a = _run(dev, norm, sweeps=10, segment=5,
                         checkpoint=SearchCheckpointer(d))
        before = trace_count("pt")
        b, arch_b = _run(dev, norm, sweeps=10, segment=5,
                         checkpoint=SearchCheckpointer(d))
        # restored at sweep 10: no segment runs, no compile, same result
        assert trace_count("pt") == before
        assert b.history == a.history and b.best_cost == a.best_cost
        assert np.array_equal(arch_b.vectors, arch_a.vectors)


@pytest.mark.slow
def test_fingerprint_mismatch_rejected(dev, norm):
    with tempfile.TemporaryDirectory() as d:
        ck = SearchCheckpointer(d)
        _run(dev, norm, sweeps=10, segment=5, checkpoint=ck)
        v0, temps, _ = _pt_args()
        with pytest.raises(ValueError, match="different search"):
            dev.parallel_tempering(
                v0, temps, 10, 5, seed=999, norm=norm, template=TPL,
                archive=ParetoArchive(max_size=64), segment=5,
                checkpoint=SearchCheckpointer(d))
        # a config mismatch must never be misread as corruption: the
        # rejected snapshots stay on disk for the original config
        assert SearchCheckpointer(d).manager.all_steps(), \
            "fingerprint rejection pruned valid snapshots"
        # same protection when the template drops the archive entirely
        # (frontier collection off => different fingerprint, not a
        # checksum-subset false corruption)
        with pytest.raises(ValueError, match="different search"):
            dev.parallel_tempering(
                v0, temps, 10, 5, seed=_pt_args()[2], norm=norm,
                template=TPL, collect_samples=False, segment=5,
                checkpoint=SearchCheckpointer(d))
        assert SearchCheckpointer(d).manager.all_steps()
        # resume=False ignores the stale state and starts fresh
        res = dev.parallel_tempering(
            v0, temps, 10, 5, seed=999, norm=norm, template=TPL,
            archive=ParetoArchive(max_size=64), segment=5,
            checkpoint=SearchCheckpointer(d), resume=False)
        assert len(res.history) == 11


@pytest.mark.slow
def test_zero_sweep_run_returns_seed_only(dev, norm):
    """budget == population clamps sweeps to 0; the segmented loop must
    degrade to the seed evaluation like the monolithic scan did."""
    pf = Pathfinder(WL, TPL, norm=norm, space=SPACE)
    res = pf.search(strategy=ParallelTempering(n_chains=4, sweeps=50),
                    budget=4, key=3)
    assert res.evaluations == 4
    assert len(res.history) == 1
    assert len(res.frontier) >= 1


@pytest.mark.slow
def test_resume_shrunken_budget_rejected(dev, norm):
    """A checkpoint further along than the requested sweep count must
    raise, not silently return the over-run state."""
    with tempfile.TemporaryDirectory() as d:
        _run(dev, norm, sweeps=10, segment=5,
             checkpoint=SearchCheckpointer(d))
        with pytest.raises(ValueError, match="shrinking a resumed"):
            _run(dev, norm, sweeps=5, segment=5,
                 checkpoint=SearchCheckpointer(d))


@pytest.mark.slow
def test_resume_extends_finished_run(dev, norm):
    """The documented extension use case: a finished segment=None run
    resumes under a larger sweep budget and continues its stream (the
    fingerprint hashes the segment knob, not the derived chunk size)."""
    with tempfile.TemporaryDirectory() as d:
        a, _ = _run(dev, norm, sweeps=6, segment=None,
                    checkpoint=SearchCheckpointer(d))
        b, _ = _run(dev, norm, sweeps=10, segment=None,
                    checkpoint=SearchCheckpointer(d))
        assert len(a.history) == 7 and len(b.history) == 11
        assert b.history[:7] == a.history


def test_checkpoint_with_samples_needs_archive(dev, norm):
    v0, temps, seed = _pt_args()
    with tempfile.TemporaryDirectory() as d:
        with pytest.raises(ValueError, match="requires an archive"):
            dev.parallel_tempering(v0, temps, 4, 5, seed=seed, norm=norm,
                                   template=TPL,
                                   checkpoint=SearchCheckpointer(d))


def test_restore_skips_foreign_fingerprint_steps():
    """A stale snapshot from another configuration (e.g. a survivor of
    a resume=False restart sharing the directory) must not block
    resume: restore falls back to the newest snapshot of *this* search
    and leaves the foreign one on disk."""
    from repro.pathfinding.resume import search_fingerprint

    carry = {"x": np.arange(4.0)}
    with tempfile.TemporaryDirectory() as d:
        ck = SearchCheckpointer(d)
        fp_a = search_fingerprint("t", seed=np.int64(1))
        fp_b = search_fingerprint("t", seed=np.int64(2))
        ck.save(4, {"x": np.full(4, 2.0)}, None, np.arange(5.0), fp_b)
        ck.save(10, {"x": np.full(4, 1.0)}, None, np.arange(11.0), fp_a)
        got = SearchCheckpointer(d).restore(carry, None, fp_b)
        assert got is not None and got.sweep_done == 4
        np.testing.assert_array_equal(got.carry["x"], np.full(4, 2.0))
        # the foreign newest step is untouched and still restorable
        assert SearchCheckpointer(d).manager.all_steps() == [4, 10]
        assert SearchCheckpointer(d).restore(carry, None,
                                             fp_a).sweep_done == 10
        # a third config finds snapshots but none of its own: raises
        with pytest.raises(ValueError, match="different search"):
            SearchCheckpointer(d).restore(
                carry, None, search_fingerprint("t", seed=np.int64(3)))
        # a foreign snapshot with a different carry SHAPE (e.g. another
        # chain count) is skipped the same way, not crashed on
        ck.save(20, {"x": np.zeros(9)}, None, np.arange(3.0),
                search_fingerprint("t", seed=np.int64(4)))
        got = SearchCheckpointer(d).restore(carry, None, fp_b)
        assert got is not None and got.sweep_done == 4
        assert SearchCheckpointer(d).manager.all_steps() == [4, 10, 20]


def test_checkpoint_dir_requires_device_engine(norm):
    pf = Pathfinder(WL, TPL, norm=norm, space=SPACE, device=False)
    strat = ParallelTempering(n_chains=4, sweeps=4,
                              checkpoint_dir="/tmp/nonexistent-ok")
    with pytest.raises(ValueError, match="device engine"):
        pf.search(strategy=strat, key=1)


def test_scenario_checkpoint_dir_requires_device_path():
    from repro.pathfinding import ScenarioSweep

    with pytest.raises(ValueError, match="device path"):
        ScenarioSweep().run(WL, device=False,
                            checkpoint_dir="/tmp/nonexistent-ok")


def test_record_trace_cannot_checkpoint(dev, norm):
    v0, temps, seed = _pt_args()
    with tempfile.TemporaryDirectory() as d:
        with pytest.raises(ValueError, match="record_trace"):
            dev.parallel_tempering(v0, temps, 4, 5, seed=seed, norm=norm,
                                   template=TPL, record_trace=True,
                                   checkpoint=SearchCheckpointer(d))


@pytest.mark.slow
def test_pt_strategy_checkpoint_surface(norm):
    """The ParallelTempering facade surface: interrupted strategy run +
    resumed strategy run == uninterrupted run (frontier bit-identical)."""
    import repro.pathfinding.strategies as strategies_mod

    pf = Pathfinder(WL, TPL, norm=norm, space=SPACE)
    mk = lambda d=None: ParallelTempering(   # noqa: E731
        n_chains=4, sweeps=12, segment=4, frontier_size=4096,
        checkpoint_dir=d)
    ref = pf.search(strategy=mk(), key=3)
    with tempfile.TemporaryDirectory() as d:
        orig = strategies_mod._checkpointer
        strategies_mod._checkpointer = (
            lambda cd: _DyingCheckpointer(cd, die_after=2)
            if cd is not None else None)
        try:
            with pytest.raises(KeyboardInterrupt):
                pf.search(strategy=mk(d), key=3)
        finally:
            strategies_mod._checkpointer = orig
        assert SearchCheckpointer(d).manager.all_steps(), "no snapshot"
        res = pf.search(strategy=mk(d), key=3)
    assert res.history == ref.history
    assert res.best_cost == ref.best_cost
    assert np.array_equal(res.frontier.vectors, ref.frontier.vectors)
    assert np.array_equal(res.frontier.encoded, ref.frontier.encoded)
    assert res.best == ref.best


@pytest.mark.slow
def test_scenario_sweep_resume_subprocess_boundary_exit():
    """Real process death: a ScenarioSweep subprocess exits hard (the
    worker's --max-segments preemption) after its first boundary, a
    second invocation resumes, and the final frontiers match an
    uninterrupted reference bit-for-bit."""
    script = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "scripts", "resume_worker.py")
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(os.path.dirname(script), "..", "src")]
        + ([env["PYTHONPATH"]] if "PYTHONPATH" in env else []))
    with tempfile.TemporaryDirectory() as d:
        ckpt, out_ref, out_res = (os.path.join(d, x)
                                  for x in ("ckpt", "ref.npz", "res.npz"))
        run = lambda *a: subprocess.run(       # noqa: E731
            [sys.executable, script, *a], env=env, timeout=1200,
            capture_output=True, text=True)
        ref = run("run", "--out", out_ref)
        assert ref.returncode == 0, ref.stderr[-2000:]
        first = run("run", "--checkpoint-dir", ckpt, "--max-segments", "1")
        assert first.returncode == 3, (first.returncode, first.stderr[-2000:])
        resumed = run("run", "--checkpoint-dir", ckpt, "--out", out_res)
        assert resumed.returncode == 0, resumed.stderr[-2000:]
        a, b = np.load(out_ref), np.load(out_res)
        assert set(a.files) == set(b.files)
        for k in a.files:
            np.testing.assert_array_equal(a[k], b[k], err_msg=k)


@pytest.mark.parametrize("env_dir", [True, False])
def test_compile_cache_dir_is_fixed(env_dir, tmp_path):
    """The resumed worker finds its predecessor's programs only if the
    cache path does not move between runs: ``$JAX_COMPILATION_CACHE_DIR``
    when set (JAX reads it; the helper sets no path), else ``.jax_cache/``
    at the checkout root. Checked in a fresh interpreter, so this
    process's JAX config is untouched and nothing compiles."""
    from repro.jaxenv import DEFAULT_CACHE_DIR

    env = dict(os.environ)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_dir:
        env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path)
    src = os.path.join(os.path.dirname(__file__), os.pardir, "src")
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.abspath(src), env.get("PYTHONPATH", "")])
    out = subprocess.run(
        [sys.executable, "-c",
         "import jax; from repro.jaxenv import use_compile_cache; "
         "p = use_compile_cache(); "
         "print(p); print(jax.config.jax_compilation_cache_dir)"],
        env=env, capture_output=True, text=True, check=True)
    returned, configured = out.stdout.split()[-2:]
    want = str(tmp_path) if env_dir else str(DEFAULT_CACHE_DIR)
    assert returned == configured == want
