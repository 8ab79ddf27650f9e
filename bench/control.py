#!/usr/bin/env python3
"""Readings for a cell's limits: the program on many seeds, and the
control in its place.

    python3 bench/control.py --workload <cell> --seconds <s> --seeds 1 2 3
    python3 bench/control.py --workload <cell> --seconds <s> --seeds 1 2 3 \
        --fault half

For each seed, in one process on the chip it is started on: set the cell
up, run one window, then print one JSON line with the compared numbers
of the program (``program``) and of the control (``control``: the
reference computed in float32 put in the program's place, see
``bench/check.py``). With ``--fault`` the program runs with that fault
planted underneath (``bench/faults.py``) and only its numbers are
printed. The benchmark's own runs never run the control or a fault.
"""
import argparse
import contextlib
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def main(argv=None) -> int:
    from bench import faults, run

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--fault", choices=faults.FAULTS)
    args = ap.parse_args(argv)

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        cell = run.resolve(json.load(f), args.workload)
    import jax

    if jax.devices()[0].platform != "tpu":
        print("control: no TPU", file=sys.stderr)
        return 1
    from repro.jaxenv import use_compile_cache

    use_compile_cache()
    driver = run.load_module(cell.driver, "bench_driver")
    judged = (("program", False),) if args.fault else (
        ("program", False), ("control", True))
    for seed in args.seeds:
        with (faults.planted(args.fault) if args.fault
              else contextlib.nullcontext()):
            drv = driver.Driver(cell.config, cell.traffic, seed)
            try:
                w = drv.run(args.seconds)
                out = dict(seed=seed, fault=args.fault,
                           attempted=w["attempted"], failed=w["failed"])
                for name, control in judged:
                    out[name] = {c.name: c.value
                                 for c in drv.check(control=control)}
            finally:
                drv.close()
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    if sys.path and os.path.abspath(sys.path[0]) == HERE:
        sys.path.pop(0)
    sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]
    sys.exit(main())
