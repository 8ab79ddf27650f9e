#!/usr/bin/env python3
"""Run one cell of the benchmark once.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

from the root of a checkout. The cell is an entry of ``workloads`` in
``BENCHMARK.json``; everything else is found by name:

- ``bench/configs/<config>.json``: the deployment (sizes, source);
- ``bench/traffic/<traffic>.json``: the mix's parameters, the name of
  its driver and the limits of the comparison;
- ``bench/drivers/<driver>.py``: ``Driver(config, traffic, seed)`` sets
  up, ``run(seconds)`` is the measured window, ``check()`` the
  comparison with the plain reference;
- ``bench/metrics/<metric>.py``: one reader per per-layer metric,
  ``read(run)`` -> number or ``None``.

``--trace 0`` reports the cell's end-to-end metrics, ``--trace 1`` runs
the window under the profiler and reports its per-layer metrics. Set-up
(``setup_s``) runs from the start of this script to the first timed
call. The last line of standard output is the result as one JSON object;
the last lines of standard error are the compared numbers beside their
limits. The run fails without a result when JAX finds no TPU, or fewer
chips than the cell asks for.
"""
import time

T_START = time.monotonic()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import threading  # noqa: E402
from types import SimpleNamespace  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


class BenchError(Exception):
    """A run that cannot produce a result."""


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or not os.path.isfile(path):
        raise BenchError(f"no module at {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _load_json(path: str) -> dict:
    if not os.path.isfile(path):
        raise BenchError(f"missing {path}")
    with open(path) as f:
        return json.load(f)


def resolve(benchmark: dict, cell: str, bench_dir: str = HERE):
    """The files a cell needs, found by the names in ``benchmark``."""
    cells = {w["name"]: w for w in benchmark["workloads"]}
    if cell not in cells:
        raise BenchError(f"unknown workload {cell!r}; known: {sorted(cells)}")
    w = cells[cell]
    configs = {c["name"]: c for c in benchmark["configs"]}
    if w["config"] not in configs:
        raise BenchError(f"{cell}: no configuration {w['config']!r}")
    config = _load_json(os.path.join(bench_dir, "configs",
                                     w["config"] + ".json"))
    traffic = _load_json(os.path.join(bench_dir, "traffic",
                                      w["traffic"] + ".json"))
    driver = os.path.join(bench_dir, "drivers", traffic["driver"] + ".py")
    if not os.path.isfile(driver):
        raise BenchError(f"{cell}: no driver {driver}")

    def mine(m):
        return "workloads" not in m or cell in m["workloads"]

    e2e = [m for m in benchmark["end_to_end"] if mine(m)]
    layers = [m for m in benchmark["per_layer"] if mine(m)]
    readers = {}
    for m in layers:
        path = os.path.join(bench_dir, "metrics", m["name"] + ".py")
        if not os.path.isfile(path):
            raise BenchError(f"{cell}: no reader {path}")
        readers[m["name"]] = path
    return SimpleNamespace(name=cell, chips=w["chips"], config=config,
                           traffic=traffic, driver=driver, end_to_end=e2e,
                           per_layer=layers, readers=readers)


class CompileLog:
    """Backend compiles counted by phase from JAX's monitoring events: a
    program request that the persistent cache serves also raises the
    backend-compile event, so a compile is such an event less a cache
    hit. One listener per process: JAX offers no way to remove one."""

    _instance = None

    def __init__(self):
        import jax

        self.phase = "setup"
        self.counts = {}
        self._lock = threading.Lock()
        jax.monitoring.register_event_duration_secs_listener(self._on)
        jax.monitoring.register_event_listener(self._on_event)

    @classmethod
    def get(cls) -> "CompileLog":
        if cls._instance is None:
            cls._instance = cls()
        return cls._instance

    def _on(self, event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self._add(1)

    def _on_event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self._add(-1)

    def _add(self, n: int) -> None:
        with self._lock:
            self.counts[self.phase] = self.counts.get(self.phase, 0) + n

    def reset(self, phase: str) -> None:
        with self._lock:
            self.phase, self.counts = phase, {}


def _peak_bytes(devices) -> int:
    peaks = []
    for d in devices:
        try:
            peaks.append(int(d.memory_stats()["peak_bytes_in_use"]))
        except (TypeError, KeyError, RuntimeError):
            pass
    return max(peaks) if peaks else 0


def run_cell(cell, seed: int, seconds: float, trace: bool,
             on_chip: bool = True, control: bool = False):
    """Set up, measure, check. Returns ``(result, check lines, window
    report)``. ``on_chip=False`` (the CPU tests) skips the look for a TPU
    and the persistent compilation cache; ``control=True`` judges the
    control in the program's place (``bench.check``)."""
    import jax

    devices = jax.devices()
    dev = devices[0]
    if on_chip and dev.platform != "tpu":
        raise BenchError(f"no TPU: JAX found {dev.platform}")
    if len(devices) < cell.chips:
        raise BenchError(f"{cell.name} needs {cell.chips} chips, JAX sees "
                         f"{len(devices)}")
    used = devices[:cell.chips]
    peaks = _load_json(os.path.join(HERE, "peaks.json"))
    if on_chip and dev.device_kind not in peaks["devices"]:
        raise BenchError(f"no peaks for device kind {dev.device_kind!r} in "
                         "bench/peaks.json")
    if on_chip:
        from repro.jaxenv import use_compile_cache

        use_compile_cache()
    clog = CompileLog.get()
    clog.reset("setup")
    driver_mod = load_module(cell.driver, f"bench_driver_{cell.name}")
    drv = driver_mod.Driver(cell.config, cell.traffic, seed)
    try:
        setup_s = time.monotonic() - T_START
        setup_compiles = clog.counts.get("setup", 0)
        clog.reset("window")
        trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
        try:
            if trace:
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0
                jax.profiler.start_trace(trace_dir, profiler_options=opts)
            try:
                with jax.profiler.TraceAnnotation("bench.window"):
                    window = drv.run(seconds)
            finally:
                if trace:
                    jax.profiler.stop_trace()
            window_compiles = clog.counts.get("window", 0)
            clog.reset("check")
            summary = None
            if trace:
                from bench.trace import find_xplane, reduce_trace

                summary = reduce_trace(find_xplane(trace_dir))
        finally:
            if trace_dir is not None:
                shutil.rmtree(trace_dir, ignore_errors=True)
        memory_peak = _peak_bytes(used)
        checks = drv.check(control=control)
    finally:
        drv.close()
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(used), "memory_peak_bytes": memory_peak}
    result = {"correct": all(c.ok for c in checks),
              "attempted": window["attempted"], "failed": window["failed"],
              "metrics": {}, "device": device}
    if trace:
        ctx = SimpleNamespace(trace=summary, counters=window["counters"],
                              config=cell.config, traffic=cell.traffic,
                              peaks=peaks["devices"].get(dev.device_kind))
        for m in cell.per_layer:
            reader = load_module(cell.readers[m["name"]],
                                 "bench_metric_" + m["name"].replace(".", "_"))
            v = reader.read(ctx)
            if v is not None:
                result["metrics"][m["name"]] = {"value": float(v),
                                                "unit": m["unit"]}
        device["busy_s"] = summary.busy_s()
        device["window_s"] = summary.window_s
        result["breakdown"] = {"device_ops": summary.top_ops(10),
                               "idle_gaps": summary.longest_gaps(10)}
    else:
        values = dict(window["values"], setup_s=setup_s)
        for m in cell.end_to_end:
            result["metrics"][m["name"]] = {"value": float(values[m["name"]]),
                                            "unit": m["unit"]}
    result["compiles"] = {"setup": setup_compiles,
                          "window": window_compiles}
    result["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                        for c in checks}
    lines = [f"check {c.name}: {c.value!r} limit {c.limit!r} "
             f"{'ok' if c.ok else 'FAILED'}" for c in checks]
    return result, lines, window


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
            raise BenchError("run from a checkout: no src/repro beside bench/")
        bench = _load_json(os.path.join(ROOT, "BENCHMARK.json"))
        cell = resolve(bench, args.workload)
        result, lines, _ = run_cell(cell, args.seed, args.seconds,
                                    bool(args.trace))
    except BenchError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 1
    for line in lines:
        print(line, file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


def _paths() -> None:
    """Import ``bench.*`` and the program from the checkout root, and not
    this directory (``trace`` would shadow the standard library's)."""
    if sys.path and os.path.abspath(sys.path[0]) == HERE:
        sys.path.pop(0)
    for p in (os.path.join(ROOT, "src"), ROOT):
        if p not in sys.path:
            sys.path.insert(0, p)


if __name__ == "__main__":
    _paths()
    sys.exit(main())
