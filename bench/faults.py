"""Faults planted underneath the timed path: each must turn a run's
``correct`` false. The CPU tests plant them at a tiny size;
``bench/control.py --fault`` reads them on the chip at a cell's size.

The faults sit in the engine's segment program, which both the study's
``parallel_tempering`` and the service's tick run:

- ``unchanged``: the step returns its state unchanged (proposals are
  made and evaluated, none is ever taken);
- ``half``: the second half of the chains of every cell (study) or slot
  (service) keep their state, the others run as they should;
- ``altered``: every objective vector is altered where it is produced.

The per-sweep outputs other than the vectors are left as the program
made them. A four-chip exchange does not exist in the one-chip cells.
"""
from __future__ import annotations

import contextlib

import numpy as np

FAULTS = ("unchanged", "half", "altered")


def broken_pt_fn(fault: str, orig):
    """A stand-in for ``ScenarioEngine._pt_fn`` (``orig``) whose program
    has ``fault``."""
    if fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}; known: {FAULTS}")

    def pt_fn(self, S, n, seg, swap_every, collect_samples):
        fn = orig(self, S, n, seg, swap_every, collect_samples)

        def broken(*args):
            carry, ys = fn(*args)
            v0, c0, bv0, bc0 = (np.asarray(a) for a in args[:4])
            keep = np.zeros((S, n), dtype=bool)     # chains left as they were
            if fault == "unchanged":
                keep[:] = True
            elif fault == "half":
                keep[:, n // 2:] = True
            v, c = np.array(carry[0]), np.array(carry[1])
            v[keep], c[keep] = v0[keep], c0[keep]
            cell = keep.all(axis=1)
            bv, bc = np.array(carry[2]), np.array(carry[3])
            bv[cell], bc[cell] = bv0[cell], bc0[cell]
            if fault == "altered":
                ys = ys[:3] + (np.asarray(ys[3]) * (1 + 1e-6),) + ys[4:]
            return (v, c, bv, bc, carry[4]), ys

        return broken

    return pt_fn


@contextlib.contextmanager
def planted(fault: str):
    """Run the enclosed code with ``fault`` in the segment program."""
    from repro.pathfinding import ScenarioEngine

    orig = ScenarioEngine._pt_fn
    ScenarioEngine._pt_fn = broken_pt_fn(fault, orig)
    try:
        yield
    finally:
        ScenarioEngine._pt_fn = orig
