#!/usr/bin/env python
"""Kill-and-resume proof for interruptible scenario sweeps.

Two entry points:

``run``
    Execute a small, fixed ScenarioSweep (2 regions x 1 workload,
    2 directions x 2 chains, 8 sweeps advanced in 2-sweep segments) and
    optionally write its final per-cell frontiers/histories to an
    ``.npz``. With ``--checkpoint-dir`` the sweep snapshots every
    segment boundary and resumes from the newest valid snapshot.
    ``--max-segments N`` hard-exits the process (code 3) right after the
    N-th snapshot — a deterministic boundary preemption used by the
    pytest variant; ``--sleep S`` sleeps after each snapshot to widen
    the window for a real SIGTERM.

``check``
    The full CI lane: run an uninterrupted reference, launch a live
    worker and SIGTERM it mid-run (after its first checkpoint appears),
    rerun the worker to resume, and assert the resumed frontiers are
    **bit-identical** to the reference. The parent never imports JAX
    and runs its children one after another, so exactly one process
    holds the device at a time (a TPU belongs to one process). The
    children share JAX's persistent compilation cache
    (:func:`repro.jaxenv.use_compile_cache`: ``$JAX_COMPILATION_CACHE_DIR``,
    else ``.jax_cache/`` at the checkout root), so only the first pays
    the XLA compile.

Usage::

    PYTHONPATH=src python scripts/resume_worker.py check
    PYTHONPATH=src python scripts/resume_worker.py run --out ref.npz
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

# the fixed tiny sweep: big enough for 4 boundaries, small enough for CI
KEY = 5
SEGMENT = 2
SWEEPS = 8
REGIONS = {"hydro": 0.024, "coal-heavy": 0.82}
NORM_SAMPLES = 80


def _build_sweep():
    from repro.pathfinding import ScalarizationSweep, ScenarioSweep

    return ScenarioSweep(
        strategy=ScalarizationSweep(directions=2, n_chains=2,
                                    sweeps=SWEEPS),
        regions=dict(REGIONS), norm_samples=NORM_SAMPLES)


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

    from repro.core import workload

    sweep = _build_sweep()
    sf = sweep.run(workload(1), key=KEY, segment=SEGMENT,
                   checkpoint_dir=args.checkpoint_dir)
    if args.out:
        payload = {}
        for i, s in enumerate(sf.scenarios):
            res = sf.results[s.key]
            payload[f"enc_{i}"] = res.frontier.encoded
            payload[f"vec_{i}"] = res.frontier.vectors
            payload[f"hist_{i}"] = np.asarray(res.history)
            payload[f"best_cost_{i}"] = np.float64(res.best_cost)
        np.savez(args.out, **payload)
    print(f"sweep done: {len(sf.scenarios)} cells, "
          f"{sum(len(sf.results[s.key].frontier) for s in sf.scenarios)} "
          "frontier points")
    return 0


def _finished_steps(directory: str):
    """Completed snapshot dirs only — a torn ``step_N.tmp`` from a save
    interrupted mid-write must satisfy neither the SIGTERM wait nor the
    survived-the-kill assertion (restore ignores it too)."""
    return [d for d in glob.glob(os.path.join(directory, "step_*"))
            if not d.endswith(".tmp")
            and os.path.exists(os.path.join(d, "checkpoint.json"))]


def _wait_for_checkpoint(directory: str, proc: subprocess.Popen,
                         timeout: float) -> bool:
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if proc.poll() is not None:
            return False  # finished (or died) before any snapshot
        if _finished_steps(directory):
            return True
        time.sleep(0.05)
    return False


def cmd_check(args: argparse.Namespace) -> int:
    workdir = args.workdir or tempfile.mkdtemp(prefix="kill-resume-")
    os.makedirs(workdir, exist_ok=True)
    me = os.path.abspath(__file__)

    def worker(*extra: str) -> subprocess.Popen:
        return subprocess.Popen([sys.executable, me, "run", *extra])

    ref_npz = os.path.join(workdir, "reference.npz")
    res_npz = os.path.join(workdir, "resumed.npz")
    ckpt = os.path.join(workdir, "ckpt")

    print("[1/4] uninterrupted reference run", flush=True)
    assert worker("--out", ref_npz).wait() == 0, "reference run failed"

    print("[2/4] live run + SIGTERM after first checkpoint", flush=True)
    killed = False
    for attempt, sleep_s in enumerate((1.0, 3.0), 1):
        # a fresh directory per attempt: stale snapshots from an attempt
        # that finished before its SIGTERM must not satisfy the wait (the
        # lane would then "resume" a completed run and prove nothing)
        shutil.rmtree(ckpt, ignore_errors=True)
        proc = worker("--checkpoint-dir", ckpt, "--sleep", str(sleep_s))
        if _wait_for_checkpoint(ckpt, proc, timeout=args.timeout):
            proc.send_signal(signal.SIGTERM)
            rc = proc.wait()
            print(f"    SIGTERM delivered (attempt {attempt}), "
                  f"worker exit code {rc}", flush=True)
            assert rc != 0, "worker survived SIGTERM?"
            killed = True
            break
        proc.wait()
        print(f"    attempt {attempt}: run finished before SIGTERM "
              "window; widening sleep", flush=True)
    assert killed, "could not interrupt the worker mid-run"
    steps = _finished_steps(ckpt)
    assert steps, "no checkpoint survived the kill"
    print(f"    checkpoints on disk: {sorted(os.path.basename(s) for s in steps)}",
          flush=True)

    print("[3/4] resume from newest valid checkpoint", flush=True)
    assert worker("--checkpoint-dir", ckpt,
                  "--out", res_npz).wait() == 0, "resume failed"

    print("[4/4] bit-identical frontier comparison", flush=True)
    a, b = np.load(ref_npz), np.load(res_npz)
    assert set(a.files) == set(b.files), (a.files, b.files)
    for k in sorted(a.files):
        if not np.array_equal(a[k], b[k]):
            print(f"MISMATCH in {k}:\nref={a[k]!r}\nres={b[k]!r}")
            return 1
    print(f"kill-and-resume OK: {len(a.files)} arrays bit-identical "
          f"(workdir {workdir})")
    return 0


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    sub = ap.add_subparsers(dest="cmd", required=True)
    run = sub.add_parser("run", help="one sweep invocation")
    run.add_argument("--checkpoint-dir", default=None)
    run.add_argument("--out", default=None)
    run.add_argument("--max-segments", type=int, default=0)
    run.add_argument("--sleep", type=float, default=0.0)
    chk = sub.add_parser("check", help="full kill-and-resume proof")
    chk.add_argument("--workdir", default=None)
    chk.add_argument("--timeout", type=float, default=900.0,
                     help="max seconds to wait for the first checkpoint")
    args = ap.parse_args()
    return cmd_run(args) if args.cmd == "run" else cmd_check(args)


if __name__ == "__main__":
    sys.exit(main())
