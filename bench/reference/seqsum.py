"""Left-to-right float summation for the scalar reference.

Since Python 3.12 the builtin ``sum()`` compensates float rounding
(Neumaier). The batched and device evaluators add in plain left-to-right
order, and ulp-level ties (Algorithm 1's fractional tile shares, the
floorplan's balanced cuts) make that order part of their parity
contract with the scalar model, so float sums there go through here.
"""
from __future__ import annotations

from typing import Iterable


def seq_sum(values: Iterable, start=0):
    """``sum(values, start)`` with plain (uncompensated) float addition."""
    total = start
    for v in values:
        total = total + v
    return total
