#!/usr/bin/env python3
"""Find the knee of a service cell: the highest offered rate at which
the backlog does not grow over a window.

    python3 bench/knee.py --workload service6.poisson --seed <n> \\
        --seconds <s> --rates 10 20 40 80

Sets the cell up once and runs one window per rate, in the order given,
on the chip it is started on. Prints one JSON line per rate: the
latency median and 95th percentile, the jobs attempted and failed, and
the open jobs at half-time and at the close of arrivals. The rate in
the cell's traffic file is then set to 0.8 x the knee by hand.
"""
import argparse
import json
import os
import statistics
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)
    from bench import run

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        cell = run.resolve(json.load(f), args.workload)
    import jax

    if jax.devices()[0].platform != "tpu":
        print("knee: no TPU", file=sys.stderr)
        return 1
    from repro.jaxenv import use_compile_cache

    use_compile_cache()
    drv = run.load_module(cell.driver, "bench_driver").Driver(
        cell.config, cell.traffic, args.seed)
    try:
        for rate in args.rates:
            drv.traffic = dict(cell.traffic, rate_per_s=rate)
            w = drv.run(args.seconds)
            c = w["counters"]
            lat = c["latency_s"]
            print(json.dumps(dict(
                rate_per_s=rate, attempted=w["attempted"],
                failed=w["failed"], p50_s=statistics.median(lat),
                p95_s=statistics.quantiles(lat, n=20)[18],
                backlog_mid=c["backlog_mid"],
                backlog_close=c["backlog_close"])), flush=True)
    finally:
        drv.close()
    return 0


if __name__ == "__main__":
    if sys.path and os.path.abspath(sys.path[0]) == HERE:
        sys.path.pop(0)
    sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]
    sys.exit(main())
