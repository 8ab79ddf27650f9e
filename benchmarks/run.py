"""Benchmark orchestrator: one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV (one row per benchmark) to stdout;
per-benchmark data tables go to ``benchmarks/out/<name>.csv``.

Usage:
    PYTHONPATH=src python -m benchmarks.run           # everything
    PYTHONPATH=src python -m benchmarks.run fig05 t11 # substring filter
    PYTHONPATH=src python -m benchmarks.run --json perf.json  # + summary

``--json <path>`` additionally writes the summary rows as a JSON perf
snapshot: {"rows": [{"name", "us_per_call", "derived"}, ...]}.

``--trajectory <path> [--commit <sha>]`` appends the measured rows to
the committed perf *trajectory* (``BENCH_pathfinder.json``): one entry
per (benchmark, commit) with ``{"benchmark", "commit", "metrics"}``
keys. Re-measuring the same commit replaces its entries; the file is
validated in CI by ``benchmarks/validate_bench.py``.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import traceback
from typing import Optional

from benchmarks import (
    carbon_scheduling,
    checkpoint_resume,
    comm_models,
    fig05_latency_vs_chiplets,
    fig06_energy_pkg,
    fig07_cost_pkg,
    fig08_latency_cost_scatter,
    fig09_mapping_latency,
    fig10_perfsi_chiplets,
    fig11_perfsi_cost_scatter,
    fig12_perfsi_mapping,
    fig13_cfp_vs_cost,
    pareto_frontier,
    pathfinder_batch,
    pathfinder_device,
    prefix_gather,
    roofline,
    scenario_sweep,
    serving_throughput,
    table06_sa_flows,
    table11_runtime,
)

ALL = [
    ("fig05", fig05_latency_vs_chiplets),
    ("fig06", fig06_energy_pkg),
    ("fig07", fig07_cost_pkg),
    ("fig08", fig08_latency_cost_scatter),
    ("fig09", fig09_mapping_latency),
    ("fig10", fig10_perfsi_chiplets),
    ("fig11", fig11_perfsi_cost_scatter),
    ("fig12", fig12_perfsi_mapping),
    ("fig13", fig13_cfp_vs_cost),
    ("table06", table06_sa_flows),
    ("table11", table11_runtime),
    ("roofline", roofline),
    ("pathfinder_batch", pathfinder_batch),
    ("pathfinder_device", pathfinder_device),
    ("prefix_gather", prefix_gather),
    ("pareto_frontier", pareto_frontier),
    ("scenario_sweep", scenario_sweep),
    ("comm_models", comm_models),
    ("carbon_scheduling", carbon_scheduling),
    ("checkpoint_resume", checkpoint_resume),
    ("serving_throughput", serving_throughput),
]

OUT_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)), "out")


def _take_flag(args, flag):
    if flag not in args:
        return None
    i = args.index(flag)
    try:
        value = args[i + 1]
    except IndexError:
        sys.exit(f"{flag} requires an argument")
    del args[i:i + 2]
    return value


def append_trajectory(path: str, rows, commit: Optional[str]) -> None:
    """Append measured rows to the committed perf trajectory, replacing
    any existing entries for the same (benchmark, commit)."""
    if commit is None:
        commit = subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"], capture_output=True,
            text=True, check=True).stdout.strip()
    doc = {"schema": 1, "entries": []}
    if os.path.exists(path):
        with open(path) as f:
            loaded = json.load(f)
        # keep only a well-formed trajectory; a foreign layout (e.g. a
        # --json snapshot's {"rows": ...}) must not leak stale top-level
        # keys into the file the bench-file CI gate validates
        if isinstance(loaded, dict) and isinstance(loaded.get("entries"),
                                                   list):
            doc["entries"] = loaded["entries"]
    names = {r["name"] for r in rows}
    doc["entries"] = [e for e in doc["entries"]
                      if not (e.get("commit") == commit
                              and e.get("benchmark") in names)]
    for r in rows:
        doc["entries"].append({
            "benchmark": r["name"], "commit": commit,
            "metrics": {"us_per_call": r["us_per_call"],
                        "derived": r["derived"]},
        })
    with open(path, "w") as f:
        json.dump(doc, f, indent=2)
        f.write("\n")


def main() -> None:
    from repro.jaxenv import use_compile_cache

    use_compile_cache()
    args = sys.argv[1:]
    json_path = _take_flag(args, "--json")
    traj_path = _take_flag(args, "--trajectory")
    commit = _take_flag(args, "--commit")
    filters = [a for a in args if not a.startswith("-")]
    os.makedirs(OUT_DIR, exist_ok=True)
    print("name,us_per_call,derived")
    failures = 0
    summaries = []
    for name, mod in ALL:
        if filters and not any(f in name for f in filters):
            continue
        lines = []
        try:
            summary = mod.run(out=lines.append)
            print(summary, flush=True)
        except AssertionError as e:
            failures += 1
            summary = f"{name},0,ASSERT_FAIL:{e}"
            print(summary, flush=True)
        except Exception as e:  # noqa: BLE001
            failures += 1
            traceback.print_exc(file=sys.stderr)
            summary = f"{name},0,ERROR:{type(e).__name__}"
            print(summary, flush=True)
        summaries.append(summary)
        with open(os.path.join(OUT_DIR, f"{name}.csv"), "w") as f:
            f.write("\n".join(lines) + "\n")
    rows = []
    for s in summaries:
        bname, us, derived = s.split(",", 2)
        try:
            us_val = float(us)
        except ValueError:
            us_val = us  # keep the raw field rather than lose the dump
        rows.append({"name": bname, "us_per_call": us_val,
                     "derived": derived})
    if json_path:
        with open(json_path, "w") as f:
            json.dump({"rows": rows}, f, indent=2)
        print(f"# wrote {json_path}", file=sys.stderr)
    if traj_path:
        if failures:
            print("# trajectory NOT updated: benchmark failures",
                  file=sys.stderr)
        else:
            append_trajectory(traj_path, rows, commit)
            print(f"# appended {len(rows)} entries to {traj_path}",
                  file=sys.stderr)
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
