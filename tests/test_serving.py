"""Serving-layer tests: job isolation (solo == packed), continuous
batching on the warm engine (zero recompiles after warmup), queue
mechanics (admission, FIFO fairness, cancellation, preemption), whole-
service kill-and-resume through per-job checkpoints, and adaptive
budget donation.

The determinism spine of every test: a job's RNG stream comes from
``fold_job_key(base, job_id)`` and its sweep counter rides per-slot
through the scan, so the same spec must produce bit-identical
history/best/frontier however it is scheduled.
"""
import numpy as np
import pytest

from repro.core import workload
from repro.pathfinding import ScalarizationSweep, fold_job_key
from repro.pathfinding.device import trace_count
from repro.pathfinding.strategies import DEFAULT_SEARCH_KEY
from repro.serving import JobSpec, JobState, PathfinderService

WLS = [workload(1), workload(6)]
STRAT = ScalarizationSweep(directions=2, n_chains=2, sweeps=4)


def make_service(**kw):
    kw.setdefault("slots", 4)
    kw.setdefault("segment", 2)
    kw.setdefault("norm_samples", 80)
    return PathfinderService(WLS, **kw)


def spec(job_id, wl=0, ci=0.475, strategy=STRAT, **kw):
    return JobSpec(job_id=job_id, workload=WLS[wl].name,
                   strategy=strategy, carbon_intensity=ci, **kw)


def run_solo(sp, **svc_kw):
    svc = make_service(**svc_kw)
    svc.submit(sp)
    svc.drain()
    return svc.result(sp.job_id)


def assert_bit_equal(a, b):
    assert a.history == b.history
    assert a.best_cost == b.best_cost
    assert np.array_equal(a.best_enc, b.best_enc)
    assert np.array_equal(a.frontier.vectors, b.frontier.vectors)
    assert np.array_equal(a.frontier.encoded, b.frontier.encoded)


# ---------------------------------------------------------------------------
# Per-job RNG isolation (the serving bugfix)
# ---------------------------------------------------------------------------


def test_fold_job_key_deterministic_and_distinct():
    assert fold_job_key(7, "job-a") == fold_job_key(7, "job-a")
    assert fold_job_key(7, "job-a") != fold_job_key(7, "job-b")
    assert fold_job_key(7, "job-a") != fold_job_key(8, "job-a")
    # job keys are valid PRNGKey seeds (63-bit, like fold_cell_key)
    assert 0 <= fold_job_key(DEFAULT_SEARCH_KEY, "x") < 2 ** 63


def test_job_bit_identical_with_0_1_3_cotenants():
    """The regression test of the RNG-isolation bugfix: pack the same
    seeded job next to 0, 1 and 3 arbitrary co-tenants and bit-compare
    history/best/frontier. Would fail if the stream depended on slot
    index (the engine's on-device per-slot fold_in) or on co-tenant
    contents (any cross-lane op in the scan)."""
    anchor = spec("anchor", wl=0, ci=0.276)
    results = []
    for n_cotenants in (0, 1, 3):
        svc = make_service()
        svc.submit(anchor)
        for i in range(n_cotenants):
            svc.submit(spec(f"noise-{i}", wl=i % 2,
                            ci=[0.024, 0.475, 0.82][i % 3]))
        svc.drain()
        results.append(svc.result("anchor"))
    assert_bit_equal(results[0], results[1])
    assert_bit_equal(results[0], results[2])
    # and the co-tenants are genuinely different searches
    noise = make_service()
    noise.submit(spec("noise-0", wl=1, ci=0.024))
    noise.drain()
    assert noise.result("noise-0").history != results[0].history


# ---------------------------------------------------------------------------
# Continuous batching on the warm engine
# ---------------------------------------------------------------------------


def test_admission_into_partially_full_batch_zero_recompiles():
    """Jobs join the live batch at segment boundaries: a job admitted
    while another is mid-flight still reproduces its solo run, and
    after the bucket warmup no program is ever retraced (the N>=4
    concurrent-jobs acceptance gate)."""
    svc = make_service()
    svc.submit(spec("early", wl=0, ci=0.475))
    assert svc.step()           # bucket warmup + admit + first segment
    before = {k: trace_count(k)
              for k in ("scenario_pt", "scenario_init", "scenario_eval")}
    # join mid-flight, mixed workloads/regions, same bucket shape
    svc.submit(spec("late-0", wl=1, ci=0.024))
    svc.submit(spec("late-1", wl=0, ci=0.82))
    svc.submit(spec("late-2", wl=1, ci=0.475))
    svc.drain()
    after = {k: trace_count(k) for k in before}
    assert after == before, "admission/draining must replay cached programs"
    for jid in ("early", "late-0", "late-1", "late-2"):
        assert svc.status(jid) is JobState.DONE
    # every job matches its solo uninterrupted reference, bit for bit
    assert_bit_equal(svc.result("early"),
                     run_solo(spec("early", wl=0, ci=0.475)))
    assert_bit_equal(svc.result("late-0"),
                     run_solo(spec("late-0", wl=1, ci=0.024)))


@pytest.mark.slow
def test_mixed_shape_buckets_compile_once_each():
    fat = ScalarizationSweep(directions=2, n_chains=4, sweeps=4)
    svc = make_service(slots=2)
    svc.submit(spec("thin", strategy=STRAT))
    svc.submit(spec("wide", strategy=fat))
    svc.step()                  # both buckets warm up (2 programs each)
    before = {k: trace_count(k)
              for k in ("scenario_pt", "scenario_init")}
    svc.submit(spec("thin-2", strategy=STRAT, ci=0.82))
    svc.submit(spec("wide-2", strategy=fat, ci=0.82))
    svc.drain()
    assert {k: trace_count(k) for k in before} == before
    assert svc.result("wide").sweeps == 4
    assert_bit_equal(svc.result("thin-2"),
                     run_solo(spec("thin-2", strategy=STRAT, ci=0.82)))


# ---------------------------------------------------------------------------
# Queue mechanics
# ---------------------------------------------------------------------------


def test_fifo_fairness_under_contention():
    svc = make_service(slots=1)
    order = []
    for jid in ("a", "b", "c"):
        svc.submit(spec(jid, strategy=ScalarizationSweep(
            directions=2, n_chains=2, sweeps=4)))
    while svc._work_left():
        svc.step()
        for jid in ("a", "b", "c"):
            if svc.status(jid) is JobState.RUNNING and (
                    not order or order[-1] != jid):
                order.append(jid)
    assert order == ["a", "b", "c"], "single slot must serve FIFO"
    assert all(svc.status(j) is JobState.DONE for j in "abc")


def test_cancel_releases_slot_for_next_job():
    svc = make_service(slots=1)
    long = ScalarizationSweep(directions=2, n_chains=2, sweeps=8)
    svc.submit(spec("doomed", strategy=long))
    svc.submit(spec("next", strategy=STRAT))
    svc.step()
    assert svc.status("doomed") is JobState.RUNNING
    assert svc.status("next") is JobState.PENDING
    svc.cancel("doomed")
    svc.step()                  # boundary applies the cancel
    assert svc.status("doomed") is JobState.CANCELLED
    svc.drain()
    assert svc.status("next") is JobState.DONE
    with pytest.raises(RuntimeError, match="cancelled"):
        svc.result("doomed")
    # the freed slot served the successor bit-identically to solo
    assert_bit_equal(svc.result("next"), run_solo(spec("next")))
    # cancelling a PENDING job never occupies a slot
    svc.submit(spec("never-ran"))
    svc.cancel("never-ran")
    assert svc.status("never-ran") is JobState.CANCELLED


def test_pause_at_boundary_then_resume_bit_identical():
    sp = spec("pausee", strategy=ScalarizationSweep(
        directions=2, n_chains=2, sweeps=8))
    svc = make_service()
    svc.submit(sp)
    svc.step()
    svc.pause("pausee")
    svc.step()                  # one more segment, then parked
    assert svc.status("pausee") is JobState.PAUSED
    assert not svc._work_left()         # paused jobs don't block drain
    svc.resume_job("pausee")
    svc.drain()
    assert_bit_equal(svc.result("pausee"), run_solo(sp))


def test_submit_validation():
    svc = make_service()
    with pytest.raises(ValueError, match="unknown workload"):
        svc.submit(JobSpec(job_id="x", workload="nope"))
    with pytest.raises(ValueError, match="frontier_size"):
        svc.submit(spec("x", strategy=ScalarizationSweep(
            directions=2, n_chains=2, sweeps=2, frontier_size=0)))
    svc.submit(spec("dup"))
    with pytest.raises(ValueError, match="already"):
        svc.submit(spec("dup"))
    with pytest.raises(KeyError):
        svc.status("ghost")
    with pytest.raises(RuntimeError, match="no worker"):
        svc.result("dup")


def test_worker_thread_and_budget():
    """Background worker mode + the budget_sweeps total-split semantics
    (budget 12 at population 4 pays 2 whole sweeps -> rounded up to one
    2-sweep segment)."""
    with make_service().start() as svc:
        svc.submit(spec("bg", budget=12))
        res = svc.result("bg", timeout=300)
    assert res.sweeps == 2
    assert res.evaluations == 4 * (1 + 2)
    # budget validation happens lazily at admission and surfaces as a
    # FAILED job, not a submit-time exception
    svc2 = make_service()
    svc2.submit(spec("starved", budget=3))
    svc2.drain()
    assert svc2.status("starved") is JobState.FAILED
    with pytest.raises(RuntimeError, match="failed"):
        svc2.result("starved")


def test_terminal_job_gc_evicts_oldest_past_retention_cap():
    """Terminal-job GC: with ``retain_jobs=2``, finishing four jobs
    keeps only the two newest-finished records; evicted ids raise
    :class:`JobEvictedError` (a ``KeyError`` that says *why* the id is
    gone) instead of a bare unknown-job KeyError, and resubmitting an
    evicted id starts a fresh job."""
    from repro.serving import JobEvictedError

    svc = make_service(retain_jobs=2)
    ids = [f"gc-{i}" for i in range(4)]
    for jid in ids:
        svc.submit(spec(jid, wl=0))
    svc.drain()
    evicted = [jid for jid in ids if jid not in svc._jobs]
    kept = [jid for jid in ids if jid in svc._jobs]
    assert len(evicted) == 2 and len(kept) == 2
    # kept jobs stay fully readable
    for jid in kept:
        assert svc.status(jid) is JobState.DONE
        assert svc.result(jid).job_id == jid
    # evicted ids: status AND result raise the self-explaining subclass
    for jid in evicted:
        for access in (svc.status, svc.result):
            with pytest.raises(JobEvictedError) as ei:
                access(jid)
            assert isinstance(ei.value, KeyError)
            msg = str(ei.value)
            assert "retain_jobs=2" in msg and jid in msg
            assert "resubmit" in msg
    # a never-seen id is still a plain unknown-job KeyError
    with pytest.raises(KeyError) as ei:
        svc.status("never-submitted")
    assert not isinstance(ei.value, JobEvictedError)
    # resubmitting an evicted id clears the tombstone and runs again
    svc.submit(spec(evicted[0], wl=0))
    svc.drain()
    assert svc.status(evicted[0]) is JobState.DONE
    assert svc.result(evicted[0]).history == run_solo(
        spec(evicted[0], wl=0)).history
    # the cap is validated up front
    with pytest.raises(ValueError, match="retain_jobs"):
        make_service(retain_jobs=0)


# ---------------------------------------------------------------------------
# Kill-and-resume of the whole service
# ---------------------------------------------------------------------------


def test_service_restart_resumes_jobs_bit_identical(tmp_path):
    specs = [spec("r0", wl=0, ci=0.475,
                  strategy=ScalarizationSweep(directions=2, n_chains=2,
                                              sweeps=8)),
             spec("r1", wl=1, ci=0.024,
                  strategy=ScalarizationSweep(directions=2, n_chains=2,
                                              sweeps=8))]
    refs = [run_solo(sp) for sp in specs]

    svc = make_service(checkpoint_root=str(tmp_path))
    for sp in specs:
        svc.submit(sp)
    svc.step()
    svc.step()                  # two boundaries snapshotted, then "die"
    del svc

    before = {k: trace_count(k)
              for k in ("scenario_pt", "scenario_init")}
    svc2 = make_service(checkpoint_root=str(tmp_path))
    for sp in specs:
        svc2.submit(sp)         # same job ids -> restore from snapshots
    svc2.drain()
    # the restarted service replays the warm engine's cached programs
    assert {k: trace_count(k) for k in before} == before
    for sp, ref in zip(specs, refs):
        assert_bit_equal(svc2.result(sp.job_id), ref)
        assert svc2.result(sp.job_id).sweeps == ref.sweeps


def test_restored_complete_job_finalizes_without_rerun(tmp_path):
    sp = spec("done-before", strategy=STRAT)
    svc = make_service(checkpoint_root=str(tmp_path))
    svc.submit(sp)
    svc.drain()
    ref = svc.result("done-before")
    svc2 = make_service(checkpoint_root=str(tmp_path))
    svc2.submit(sp)
    svc2.drain()
    res = svc2.result("done-before")
    assert res.sweeps == ref.sweeps
    assert_bit_equal(res, ref)


# ---------------------------------------------------------------------------
# Adaptive per-cell budgets
# ---------------------------------------------------------------------------


def test_hypervolume_stall_donates_sweeps_to_hard_jobs():
    """A converged job's remaining sweeps move to a still-improving one:
    the donor stops early, the drawer overshoots its nominal budget by
    exactly the donation, total consumption never exceeds the total
    nominal budget, and the drawer's trajectory is a bit-identical
    *extension* of its fixed-budget run (donation changes when a job
    stops, never the stream it consumes)."""
    eight = ScalarizationSweep(directions=2, n_chains=2, sweeps=8)
    donor = spec("donor", wl=0, strategy=eight, stall_tol=1e9,
                 stall_segments=1)
    drawer = spec("drawer", wl=1, ci=0.82, strategy=eight,
                  stall_tol=-1.0)
    svc = make_service(adaptive=True)
    svc.submit(donor)
    svc.submit(drawer)
    svc.step()
    before = {k: trace_count(k)
              for k in ("scenario_pt", "scenario_init")}
    svc.drain()
    d, w = svc.result("donor"), svc.result("drawer")
    # donor converged at its 2nd boundary (ref at 1st, stalled at 2nd)
    assert d.converged_early and d.sweeps == 4
    # drawer drew the donated 4 sweeps beyond its nominal 8
    assert not w.converged_early and w.sweeps == 12
    assert d.sweeps + w.sweeps == 16        # conservation at equal total
    assert svc.donated_pool(donor.bucket_key()) == 0
    # extension property: fixed-budget run is a strict prefix
    fixed = run_solo(spec("drawer", wl=1, ci=0.82, strategy=eight))
    assert w.history[:len(fixed.history)] == fixed.history
    assert len(w.history) == len(fixed.history) + 4
    # donated segments replay the same compiled program
    assert {k: trace_count(k) for k in before} == before


def test_adaptive_mean_hypervolume_not_worse_than_fixed():
    """The acceptance gate, in miniature: at equal total sweep budget,
    adaptive mode's mean per-cell hypervolume >= fixed mode's (donated
    sweeps only ever extend still-improving frontiers; archives are
    unpruned at these sizes, so extra sweeps cannot lose points)."""
    eight = ScalarizationSweep(directions=2, n_chains=2, sweeps=8)
    cells = [("c0", 0, 0.024), ("c1", 1, 0.475), ("c2", 0, 0.82)]

    def run(adaptive):
        svc = make_service(adaptive=adaptive, stall_segments=1,
                           stall_tol=0.0)
        for jid, wl, ci in cells:
            svc.submit(spec(jid, wl=wl, ci=ci, strategy=eight))
        svc.drain()
        return [svc.result(jid) for jid, *_ in cells]

    fixed, adapt = run(False), run(True)
    assert sum(r.sweeps for r in adapt) <= sum(r.sweeps for r in fixed)
    # compare on common per-cell reference points (fixed's nadir+margin)
    from repro.pathfinding.pareto import hypervolume

    hv_f, hv_a = [], []
    for rf, ra in zip(fixed, adapt):
        ref = np.maximum(rf.frontier.reference_point(),
                         ra.frontier.reference_point())
        hv_f.append(hypervolume(rf.frontier.vectors, ref))
        hv_a.append(hypervolume(ra.frontier.vectors, ref))
    assert np.mean(hv_a) >= np.mean(hv_f) - 1e-12
