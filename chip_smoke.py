#!/usr/bin/env python
"""Chip smoke test: the carbon-aware scenario engine and the search
service, run once on a TPU through the entry points a user calls, with
every result checked against the plain scalar reference
(``repro.core.evaluate.evaluate``).

Default (one chip), all in this one process:

(a) The full study grid through ``ScenarioSweep.run(ScenarioSpec)``:
    the six Table IV workloads x the six measured grids x 2 seasons x
    2 day types (144 cells, each a ``Region`` at its trace's mean
    intensity with the measured 24h profile), ``comm="mesh_noc"``,
    ``schedule="window"``, 64 tempering chains per cell (8 directions x
    8-chain ladders), checkpointed segments.
(b) Every cell's best design re-evaluated by the scalar model under that
    cell's region: the device's (latency, dollar, total CFP) agree within
    1e-6 relative; every cell has a finite best cost and a non-empty
    frontier.
(c) The same grid on the other gather path (the Pallas kernel and the
    plain jnp gathers): identical best designs, costs and frontiers.
(d) ``PathfinderService`` drains the six-job, two-bucket table of
    ``scripts/serve_pathfinder.py`` plus one ``mesh_noc`` job; every job
    ends DONE and its best passes the scalar check. A second round of
    the same shapes traces nothing new.

A cold run is mostly XLA compiles of large float64 programs, and a
compile runs on one host core. So (a), (c) and the first service round
run in parallel threads of this process, and their programs compile
side by side; the checks and the warm service round follow. One process
holds the chip; the script starts no other.

``--chips 4`` runs only the sharded path and what it is compared with:
the 144-cell grid with ``shard="auto"`` over four devices (36 cells
each, the split asserted on the placed arrays) against the same grid on
one device of this process, in two threads; the two must be
bit-identical.

Run from the root of a checkout (the script imports ``src/``)::

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # the sharded path on four chips

The script exits non-zero, without a result line, when JAX finds no TPU
or when it is not inside a checkout. The compile cache goes where
``repro.jaxenv.use_compile_cache`` puts it. The last line of standard
output is ``{"ok": true, "device": {"platform", "kind", "count"}}``;
details go to ``chiprun_out/chip_smoke/report_<chips>chip.json``.
"""
from __future__ import annotations

import argparse
import concurrent.futures
import dataclasses
import json
import os
import shutil
import sys
import threading
import time
import warnings

HERE = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(HERE, "src")

PARITY_RTOL = 1e-6


@dataclasses.dataclass(frozen=True)
class Sizes:
    """How big each phase runs. ``FULL`` is the study size."""

    n_workloads: int = 6
    n_grids: int = 6
    directions: int = 8
    chains: int = 8          # per direction: 64 chains per cell
    sweeps: int = 24
    segment: int = 12        # two checkpointed segments
    norm_samples: int = 400


FULL = Sizes()


def _log(msg: str) -> None:
    print(msg, flush=True)


class CompileLog:
    """Backend compile events (name, seconds) from JAX's monitoring, by
    the thread that compiled them (a compile runs in its caller's
    thread, so each phase's thread owns its compiles)."""

    def __init__(self):
        import jax

        self.events = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.events.append((threading.current_thread().name,
                                kw.get("fun_name", "?"), float(duration)))

    def of(self, thread: str):
        return [(n, s) for t, n, s in self.events if t == thread]

    @staticmethod
    def summary(events) -> str:
        big = [(n, s) for n, s in events if s >= 0.5]
        total = sum(s for _, s in events)
        parts = ", ".join(f"{n}={s:.1f}s" for n, s in big)
        return f"{len(events)} compiles, {total:.1f}s [{parts}]"


def run_threads(**phases):
    """Run ``name=callable`` phases in parallel threads named after them
    and return ``{name: result}``. XLA compiles release the GIL, so the
    phases' large float64 programs compile side by side; a phase's
    exception is raised here."""
    with concurrent.futures.ThreadPoolExecutor(len(phases)) as ex:
        futs = {name: ex.submit(_named, name, fn)
                for name, fn in phases.items()}
        return {name: f.result() for name, f in futs.items()}


def _named(name, fn):
    threading.current_thread().name = name
    return fn()


# ---------------------------------------------------------------------------
# the study grid
# ---------------------------------------------------------------------------


def study_regions(n_grids: int):
    """``{grid-season-day: Region}`` over the measured traces."""
    import numpy as np

    from repro.core.grid_traces import DAY_TYPES, GRID_TRACES, SEASONS
    from repro.core.regions import Region, measured_profile

    out = {}
    for grid in list(GRID_TRACES)[:n_grids]:
        for season in SEASONS:
            for day in DAY_TYPES:
                prof = measured_profile(grid, season=season, day=day)
                out[f"{grid}-{season}-{day}"] = Region(
                    carbon_intensity=float(np.mean(prof)),
                    grid_profile=prof)
    return out


def study_spec(sz: Sizes, checkpoint_dir=None):
    from repro.core import workload
    from repro.pathfinding.scenario import ScenarioSpec

    return ScenarioSpec(
        workloads=tuple(workload(i) for i in range(1, sz.n_workloads + 1)),
        regions=study_regions(sz.n_grids), comm="mesh_noc",
        schedule="window", segment=sz.segment,
        checkpoint_dir=checkpoint_dir)


def run_grid(sz: Sizes, pallas: bool, shard=False, checkpoint_dir=None,
             key: int = 7):
    """One study-grid sweep on the given gather path. Returns
    (ScenarioFrontier, wall seconds)."""
    from repro.pathfinding import ScalarizationSweep, ScenarioSweep

    sweep = ScenarioSweep(
        strategy=ScalarizationSweep(directions=sz.directions,
                                    n_chains=sz.chains, sweeps=sz.sweeps),
        norm_samples=sz.norm_samples, shard=shard, use_pallas=pallas)
    t0 = time.perf_counter()
    sf = sweep.run(study_spec(sz, checkpoint_dir), key=key)
    return sf, time.perf_counter() - t0


def _rel(a: float, b: float) -> float:
    return abs(a - b) / max(abs(b), 1e-300)


def check_against_scalar(enc, vec, space, wl, region) -> float:
    """Worst relative error of a device objective vector against the
    scalar model's (latency_s, dollar, total_cfp) for the same design."""
    from repro.core.evaluate import evaluate
    from repro.core.techdb import DEFAULT_DB

    db = dataclasses.replace(DEFAULT_DB, **region.db_overrides())
    m = evaluate(space.decode(enc), wl, db)
    ref = (m.latency_s, m.dollar, m.total_cfp)
    return max(_rel(float(v), r) for v, r in zip(vec, ref))


def _row_of(frontier, enc):
    import numpy as np

    hit = np.flatnonzero((frontier.encoded == enc[None]).all(axis=1))
    return int(hit[0]) if hit.size else None


def check_grid(sf, space) -> dict:
    """Phase (b): every cell's best design against the scalar model."""
    import numpy as np

    worst, n = 0.0, 0
    for s in sf.scenarios:
        res = sf.results[s.key]
        assert np.isfinite(res.best_cost), (s.key, res.best_cost)
        assert len(res.frontier) > 0, s.key
        enc = space.encode(res.best)
        i = _row_of(res.frontier, enc)
        assert i is not None, f"{s.key}: best design not in its frontier"
        err = check_against_scalar(enc, res.frontier.vectors[i], space,
                                   s.workload, s.spec)
        assert err <= PARITY_RTOL, (s.key, err)
        worst, n = max(worst, err), n + 1
    return dict(cells=n, worst_rel_err=worst)


def same_grid(a, b) -> None:
    """Two sweeps of one grid found the same designs, with bit-identical
    costs, histories and frontiers."""
    import numpy as np

    assert [s.key for s in a.scenarios] == [s.key for s in b.scenarios]
    for s in a.scenarios:
        x, y = a.results[s.key], b.results[s.key]
        assert x.best == y.best, s.key
        assert x.best_cost == y.best_cost, (s.key, x.best_cost, y.best_cost)
        assert x.history == y.history, s.key
        assert np.array_equal(x.frontier.encoded, y.frontier.encoded)
        assert np.array_equal(x.frontier.vectors, y.frontier.vectors)


# ---------------------------------------------------------------------------
# the service
# ---------------------------------------------------------------------------


def service_jobs(suffix: str):
    """The six-job, two-bucket table of scripts/serve_pathfinder.py plus
    one mesh_noc job, as (JobSpec, DesignSpace) pairs."""
    from repro.core.regions import Region
    from repro.pathfinding import ScalarizationSweep
    from repro.pathfinding.space import DesignSpace
    from repro.serving import JobSpec

    sys.path.insert(0, os.path.join(HERE, "scripts"))
    import serve_pathfinder as table

    wls = table._workloads()
    jobs = []
    for job_id, widx, ci, swap in table.JOBS:
        spec = JobSpec(
            job_id=job_id + suffix, workload=wls[widx].name,
            strategy=ScalarizationSweep(directions=2, n_chains=2,
                                        sweeps=table.SWEEPS,
                                        swap_every=swap),
            region=Region(carbon_intensity=ci))
        jobs.append((spec, DesignSpace(comm="legacy", schedule="fixed")))
    mesh = JobSpec(
        job_id="wl1-mesh" + suffix, workload=wls[0].name,
        strategy=ScalarizationSweep(directions=2, n_chains=2,
                                    sweeps=table.SWEEPS),
        region=Region(carbon_intensity=0.475), comm="mesh_noc")
    jobs.append((mesh, DesignSpace(comm="mesh_noc", schedule="fixed")))
    return table, wls, jobs


def device_vectors(svc, jobs, results):
    """The device program's (latency, dollar, total CFP) of each job's
    best design, from the seed-evaluation program its bucket already
    compiled (``slots`` lanes x ``nc`` rows, the best repeated over a
    lane): no new program. The objective vectors do not depend on the
    normalizer or weight rows."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.jaxenv import search_numerics

    S = svc.slots
    out = {}
    by_engine = {}
    for spec, _ in jobs:
        by_engine.setdefault((spec.comm, spec.schedule), []).append(spec)
    for (comm, sched), specs in by_engine.items():
        engine = svc._engine_for(comm, sched)
        for lo in range(0, len(specs), S):
            chunk = specs[lo:lo + S] + [specs[lo]] * (S - len(specs[lo:]))
            nc = chunk[0].strategy.directions * chunk[0].strategy.n_chains
            regions = [spec.resolved_region() for spec in chunk]
            enc = np.stack([results[spec.job_id].best_enc
                            for spec in chunk])
            col = lambda f: jnp.asarray(  # noqa: E731
                np.asarray([f(r) for r in regions], np.float64))
            with search_numerics():
                _, _, vec = engine._init_fn(S, nc)(
                    jnp.asarray(np.repeat(enc[:, None], nc, axis=1)),
                    jnp.ones((S, 6)), jnp.ones((S, 6)),
                    jnp.ones((S, nc, 6)),
                    col(lambda r: r.carbon_intensity),
                    col(lambda r: r.electricity_price),
                    col(lambda r: r.emb_factor),
                    jnp.asarray(np.stack([r.profile_array()
                                          for r in regions])),
                    jnp.asarray(np.stack([r.price_array()
                                          for r in regions])),
                    jnp.asarray(np.asarray(
                        [svc._widx[spec.workload] for spec in chunk],
                        np.int32)),
                    jax.random.PRNGKey(0))
            vec = np.asarray(vec)
            out.update({spec.job_id: vec[i, 0]
                        for i, spec in enumerate(specs[lo:lo + S])})
    return out


def service_round(svc, by_name, rnd: int) -> dict:
    """One phase-(d) round: submit the job table, drain, and check every
    job ended DONE with a best that passes the scalar check."""
    from repro.serving import JobState

    _, _, jobs = service_jobs(f"-r{rnd}")
    t0 = time.perf_counter()
    for spec, _ in jobs:
        svc.submit(spec)
    svc.drain()
    wall = time.perf_counter() - t0
    for spec, _ in jobs:
        st = svc.status(spec.job_id)
        assert st is JobState.DONE, (spec.job_id, st)
    results = {spec.job_id: svc.result(spec.job_id) for spec, _ in jobs}
    vecs = device_vectors(svc, jobs, results)
    worst = 0.0
    for spec, space in jobs:
        err = check_against_scalar(
            results[spec.job_id].best_enc, vecs[spec.job_id], space,
            by_name[spec.workload], spec.resolved_region())
        assert err <= PARITY_RTOL, (spec.job_id, err)
        worst = max(worst, err)
    return dict(jobs=len(jobs), wall_s=wall, worst_rel_err=worst)


def _traces():
    from repro.pathfinding.device import trace_count

    return trace_count("scenario_pt"), trace_count("scenario_init")


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------


def one_chip(sz: Sizes, out_dir: str, clog: CompileLog) -> dict:
    """Phases (a)-(d). The two grid sweeps and the service's first round
    run side by side; the checks and the warm service round follow."""
    from repro.pathfinding.device import _resolve_pallas
    from repro.pathfinding.space import DesignSpace

    ckpt = os.path.join(out_dir, "ckpt")
    shutil.rmtree(ckpt, ignore_errors=True)
    kernel = _resolve_pallas(None)   # the platform default path
    table, wls, _ = service_jobs("")
    by_name = {wl.name: wl for wl in wls}
    svc = table._service()
    out = run_threads(
        a=lambda: run_grid(sz, kernel, checkpoint_dir=ckpt),
        c=lambda: run_grid(sz, not kernel),
        d=lambda: service_round(svc, by_name, 0))
    (sf, wall), (sf2, wall2), d0 = out["a"], out["c"], out["d"]

    report = {}
    evals = sum(r.evaluations for r in sf.results.values())
    report["a_grid"] = dict(cells=len(sf.scenarios), wall_s=wall,
                            evaluations=evals, pallas=kernel,
                            compiles=clog.of("a"))
    _log(f"(a) grid: {len(sf.scenarios)} cells, "
         f"{'kernel' if kernel else 'jnp'} gathers, {wall:.1f}s wall, "
         f"{evals} evaluations; {CompileLog.summary(clog.of('a'))}")

    report["b_parity"] = check_grid(sf, DesignSpace(comm="mesh_noc",
                                                    schedule="window"))
    _log(f"(b) scalar parity: {report['b_parity']['cells']} cells, "
         f"worst rel err {report['b_parity']['worst_rel_err']:.3e}")

    same_grid(sf, sf2)
    report["c_other_path"] = dict(pallas=not kernel, wall_s=wall2,
                                  compiles=clog.of("c"))
    _log(f"(c) {'jnp' if kernel else 'kernel'} gathers: identical best "
         f"designs, costs and frontiers in all {len(sf2.scenarios)} "
         f"cells, {wall2:.1f}s wall; {CompileLog.summary(clog.of('c'))}")

    # the warm round: every bucket shape is compiled, so nothing traces
    before = _traces()
    d1 = run_threads(d_warm=lambda: service_round(svc, by_name, 1))["d_warm"]
    after = _traces()
    assert after == before, (before, after)
    report["d_service"] = dict(rounds=[d0, d1], traces=after,
                               compiles=clog.of("d"),
                               compiles_warm=clog.of("d_warm"))
    _log(f"(d) service: 2 rounds x {d0['jobs']} jobs DONE, walls "
         f"{d0['wall_s']:.1f}s cold / {d1['wall_s']:.1f}s warm, traces "
         f"(pt, init) {after} flat over the warm round, worst rel err "
         f"{max(d0['worst_rel_err'], d1['worst_rel_err']):.3e}; "
         f"{CompileLog.summary(clog.of('d'))}; warm round "
         f"{CompileLog.summary(clog.of('d_warm'))}")
    return report


def four_chips(sz: Sizes, n_dev: int, clog: CompileLog) -> dict:
    """The 144-cell grid sharded over ``n_dev`` devices against the same
    grid on one device, side by side; bit-identical results and a real
    split of every placed scenario array are asserted."""
    import repro.distributed.sharding as sharding
    from repro.pathfinding.device import _resolve_pallas

    kernel = _resolve_pallas(None)
    placed = []
    orig = sharding.shard_scenarios

    def recording(arrays, mesh):
        out = orig(arrays, mesh)
        placed.extend((k, x.shape, x.sharding.shard_shape(x.shape),
                       len(x.sharding.device_set)) for k, x in out.items())
        return out

    sharding.shard_scenarios = recording
    try:
        out = run_threads(
            sharded=lambda: run_grid(sz, kernel, shard="auto"),
            single=lambda: run_grid(sz, kernel, shard=False))
    finally:
        sharding.shard_scenarios = orig
    (sharded, wall_s), (single, wall_1) = out["sharded"], out["single"]
    n_cells = len(sharded.scenarios)
    assert placed, "the sharded run placed nothing on a mesh"
    for k, shape, shard_shape, n in placed:
        assert n == n_dev and shard_shape[0] * n_dev == shape[0], (
            k, shape, shard_shape, n)
    per_dev = placed[0][2][0]
    _log(f"sharded: {n_cells} cells over {n_dev} devices "
         f"({per_dev} per device, {len(placed)} placed arrays split), "
         f"{wall_s:.1f}s wall; {CompileLog.summary(clog.of('sharded'))}")
    same_grid(sharded, single)
    _log(f"unsharded on one device: {wall_1:.1f}s wall; "
         f"{CompileLog.summary(clog.of('single'))}; bit-identical to the "
         f"sharded run in all {n_cells} cells")
    return dict(cells=n_cells, devices=n_dev, cells_per_device=per_dev,
                pallas=kernel, sharded_wall_s=wall_s, single_wall_s=wall_1,
                compiles_sharded=clog.of("sharded"),
                compiles_single=clog.of("single"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the sharded four-chip path")
    ap.add_argument("--out", default=os.path.join(HERE, "chiprun_out",
                                                  "chip_smoke"))
    args = ap.parse_args(argv)
    if not os.path.isdir(os.path.join(SRC, "repro")):
        print("chip_smoke: run from a checkout (no src/repro next to "
              "this script)", file=sys.stderr)
        return 2
    sys.path.insert(0, SRC)
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {dev.platform}); nothing "
              "measured", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 1
    from repro.jaxenv import use_compile_cache

    cache = use_compile_cache()
    warnings.simplefilter("ignore", DeprecationWarning)
    os.makedirs(args.out, exist_ok=True)
    _log(f"device: {dev.device_kind} x{len(devices)} ({dev.platform}); "
         f"jax {jax.__version__}; compile cache {cache}")
    clog = CompileLog()
    t0 = time.perf_counter()
    if args.chips == 4:
        report = dict(four_chips=four_chips(FULL, 4, clog))
    else:
        report = one_chip(FULL, args.out, clog)
    total = time.perf_counter() - t0
    _log(f"total wall {total:.1f}s; "
         f"{CompileLog.summary([(n, d) for _, n, d in clog.events])}")
    report.update(device=dict(platform=dev.platform, kind=dev.device_kind,
                              count=len(devices)), total_wall_s=total)
    with open(os.path.join(args.out, f"report_{args.chips}chip.json"),
              "w") as f:
        json.dump(report, f, indent=1, default=str)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
