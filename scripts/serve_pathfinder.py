#!/usr/bin/env python
"""Serving smoke proof: kill-and-resume a whole pathfinding service.

Two entry points:

``run``
    Start a :class:`~repro.serving.PathfinderService` over a fixed
    2-workload catalog, submit six mixed jobs spanning two bucket
    shapes (swap cadences 5 and 3 at four chains each), drain inline,
    and optionally write every job's history/best/frontier to an
    ``.npz``. With ``--checkpoint-root`` each job snapshots at every
    segment boundary and a rerun resumes all of them from their newest
    snapshots. ``--solo`` runs ONE job in a fresh single-job service
    (the bit-identity reference); ``--mode solo`` does that for the
    whole job table sequentially. ``--max-segments N`` hard-exits the
    process (code 3) right after the N-th snapshot; ``--sleep S``
    sleeps after each snapshot to widen the window for a real SIGTERM.

``check``
    The full CI lane: solo uninterrupted references for all six jobs,
    a live multiplexed service SIGTERMed mid-flight, a restarted
    service that resumes every job, and a final assertion that each
    resumed job is **bit-identical** to its solo reference — packing,
    preemption and restart are all invisible to a job's trajectory.
    The parent never imports JAX and runs its children one after
    another, so exactly one process holds the device at a time (a TPU
    belongs to one process). The children share JAX's persistent
    compilation cache (:func:`repro.jaxenv.use_compile_cache`:
    ``$JAX_COMPILATION_CACHE_DIR``, else ``.jax_cache/`` at the checkout
    root), so only the first pays the XLA compile.

Usage::

    PYTHONPATH=src python scripts/serve_pathfinder.py check
    PYTHONPATH=src python scripts/serve_pathfinder.py run --out ref.npz
"""
from __future__ import annotations

import argparse
import glob
import os
import shutil
import signal
import subprocess
import sys
import tempfile
import time

import numpy as np

# the fixed job table: big enough for contention (6 jobs, 4 slots) and
# several boundaries per job, small enough for CI
KEY = 5
SLOTS = 4
SEGMENT = 2
SWEEPS = 8
NORM_SAMPLES = 80
#          job id        workload  carbon    swap_every
JOBS = [("wl1-mid", 0, 0.475, 5),
        ("wl1-hydro", 0, 0.024, 5),
        ("wl6-coal", 1, 0.82, 5),
        ("wl6-mid", 1, 0.475, 3),
        ("wl1-coal", 0, 0.82, 3),
        ("wl6-hydro", 1, 0.024, 3)]


def _workloads():
    from repro.core import workload

    return [workload(1), workload(6)]


def _spec(job_id: str, widx: int, ci: float, swap: int):
    from repro.pathfinding import ScalarizationSweep
    from repro.serving import JobSpec

    return JobSpec(
        job_id=job_id, workload=_workloads()[widx].name,
        strategy=ScalarizationSweep(directions=2, n_chains=2,
                                    sweeps=SWEEPS, swap_every=swap),
        carbon_intensity=ci)


def _service(checkpoint_root=None):
    from repro.serving import PathfinderService

    return PathfinderService(
        _workloads(), slots=SLOTS, segment=SEGMENT,
        norm_samples=NORM_SAMPLES, key=KEY,
        checkpoint_root=checkpoint_root)


def _collect(svc, jobs, payload):
    for job_id, *_ in jobs:
        res = svc.result(job_id)
        payload[f"enc_{job_id}"] = res.frontier.encoded
        payload[f"vec_{job_id}"] = res.frontier.vectors
        payload[f"hist_{job_id}"] = np.asarray(res.history)
        payload[f"best_cost_{job_id}"] = np.float64(res.best_cost)
        payload[f"best_enc_{job_id}"] = res.best_enc
        payload[f"sweeps_{job_id}"] = np.int64(res.sweeps)


def cmd_run(args: argparse.Namespace) -> int:
    from repro.jaxenv import use_compile_cache

    use_compile_cache()
    if args.max_segments or args.sleep:
        from repro.pathfinding.resume import SearchCheckpointer

        orig_save = SearchCheckpointer.save
        state = {"saves": 0}

        def save(self, *a, **kw):
            path = orig_save(self, *a, **kw)
            state["saves"] += 1
            if args.sleep:
                time.sleep(args.sleep)
            if args.max_segments and state["saves"] >= args.max_segments:
                # hard exit: no cleanup, exactly like a preemption
                os._exit(3)
            return path

        SearchCheckpointer.save = save

    jobs = JOBS
    if args.solo:
        jobs = [j for j in JOBS if j[0] == args.solo]
        assert jobs, f"unknown job {args.solo!r}"
    payload = {}
    if args.mode == "solo":
        # one fresh single-job service per job: the reference runs that
        # multiplexed/preempted/restarted jobs must match bit for bit
        for job in jobs:
            svc = _service()
            svc.submit(_spec(*job))
            svc.drain()
            _collect(svc, [job], payload)
    else:
        svc = _service(checkpoint_root=args.checkpoint_root)
        for job in jobs:
            svc.submit(_spec(*job))
        svc.drain()
        _collect(svc, jobs, payload)
    if args.out:
        np.savez(args.out, **payload)
    n_pts = sum(len(payload[f"enc_{j}"]) for j, *_ in jobs)
    print(f"service drained: {len(jobs)} jobs, "
          f"{n_pts} frontier points")
    return 0


def _finished_steps(root: str):
    """Completed snapshot dirs across all job subdirectories — torn
    ``step_N.tmp`` dirs from a save interrupted mid-write count for
    nothing (restore ignores them too)."""
    return [d for d in glob.glob(os.path.join(root, "*", "step_*"))
            if not d.endswith(".tmp")
            and os.path.exists(os.path.join(d, "checkpoint.json"))]


def _wait_for_checkpoint(root: str, proc: subprocess.Popen,
                         timeout: float) -> bool:
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if proc.poll() is not None:
            return False  # finished (or died) before any snapshot
        if _finished_steps(root):
            return True
        time.sleep(0.05)
    return False


def cmd_check(args: argparse.Namespace) -> int:
    workdir = args.workdir or tempfile.mkdtemp(prefix="serve-smoke-")
    os.makedirs(workdir, exist_ok=True)
    me = os.path.abspath(__file__)

    def worker(*extra: str) -> subprocess.Popen:
        return subprocess.Popen([sys.executable, me, "run", *extra])

    ref_npz = os.path.join(workdir, "reference.npz")
    res_npz = os.path.join(workdir, "resumed.npz")
    ckpt = os.path.join(workdir, "ckpt")

    print("[1/4] solo uninterrupted reference runs", flush=True)
    assert worker("--mode", "solo",
                  "--out", ref_npz).wait() == 0, "reference runs failed"

    print("[2/4] multiplexed service + SIGTERM mid-flight", flush=True)
    killed = False
    for attempt, sleep_s in enumerate((1.0, 3.0), 1):
        # fresh checkpoint root per attempt: stale snapshots from an
        # attempt that drained before its SIGTERM must not satisfy the
        # wait (the lane would then "resume" finished jobs and prove
        # nothing)
        shutil.rmtree(ckpt, ignore_errors=True)
        proc = worker("--checkpoint-root", ckpt, "--sleep", str(sleep_s))
        if _wait_for_checkpoint(ckpt, proc, timeout=args.timeout):
            proc.send_signal(signal.SIGTERM)
            rc = proc.wait()
            print(f"    SIGTERM delivered (attempt {attempt}), "
                  f"service exit code {rc}", flush=True)
            assert rc != 0, "service survived SIGTERM?"
            killed = True
            break
        proc.wait()
        print(f"    attempt {attempt}: service drained before SIGTERM "
              "window; widening sleep", flush=True)
    assert killed, "could not interrupt the service mid-flight"
    steps = _finished_steps(ckpt)
    assert steps, "no checkpoint survived the kill"
    by_job = sorted({os.path.basename(os.path.dirname(s)) for s in steps})
    print(f"    jobs with snapshots on disk: {by_job}", flush=True)

    print("[3/4] restart service, resume all jobs", flush=True)
    assert worker("--checkpoint-root", ckpt,
                  "--out", res_npz).wait() == 0, "restarted service failed"

    print("[4/4] bit-identical comparison against solo references",
          flush=True)
    a, b = np.load(ref_npz), np.load(res_npz)
    assert set(a.files) == set(b.files), (a.files, b.files)
    for k in sorted(a.files):
        if not np.array_equal(a[k], b[k]):
            print(f"MISMATCH in {k}:\nref={a[k]!r}\nres={b[k]!r}")
            return 1
    print(f"serving kill-and-resume OK: {len(JOBS)} jobs, "
          f"{len(a.files)} arrays bit-identical (workdir {workdir})")
    return 0


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    sub = ap.add_subparsers(dest="cmd", required=True)
    run = sub.add_parser("run", help="one service process")
    run.add_argument("--mode", choices=("service", "solo"),
                     default="service")
    run.add_argument("--solo", default=None, metavar="JOB_ID",
                     help="restrict to one job from the table")
    run.add_argument("--checkpoint-root", default=None)
    run.add_argument("--out", default=None)
    run.add_argument("--max-segments", type=int, default=0)
    run.add_argument("--sleep", type=float, default=0.0)
    chk = sub.add_parser("check", help="full serving kill-and-resume proof")
    chk.add_argument("--workdir", default=None)
    chk.add_argument("--timeout", type=float, default=900.0,
                     help="max seconds to wait for the first checkpoint")
    args = ap.parse_args()
    return cmd_run(args) if args.cmd == "run" else cmd_check(args)


if __name__ == "__main__":
    sys.exit(main())
