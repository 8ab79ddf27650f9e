"""Spans and counters of the program, for the JAX profiler.

Each span is a ``jax.profiler.TraceAnnotation``: under a running
profiler (``jax.profiler.trace(dir)``) it is recorded on the host thread
that opened it, in the same ``.xplane.pb`` as the device's own events and
on the same clock; otherwise it costs under a microsecond. Counts known
only after the work are attached to the open span with
``set_metadata(...)``; a count that costs more than a ``len()`` is taken
only when :func:`enabled`. Every span the program opens is listed
below with its stats; ``tests/test_tracing.py`` holds the program to
this list.

Host segment loop (``resume.run_segmented`` under the tempering engines):

- ``repro.pt.prepare`` (``cells``, ``chains``, ``layers``): the scenario
  engine's upload of its per-cell columns before the loop; ``layers`` is
  the length of its layer axis, the layers each design is evaluated on
  (1 for an engine of single GEMMs).
- ``repro.segment.init``: the seed-population program and its read-back.
- ``repro.segment.dispatch`` (``sweeps``): enqueueing one segment.
- ``repro.segment.absorb`` (``rows``): feeding one segment's outputs to
  the histories and archives; holds the next two.
- ``repro.segment.wait``: the host waiting for the segment's outputs.
- ``repro.segment.fetch`` (``bytes``): copying them to the host.

Scenario engine (``ScenarioEngine`` construction):

- ``repro.network.tables`` (``layers``, ``gemms``, ``bytes``): building
  the per-GEMM prefix tables, stacked by distinct GEMM (by workload in
  an engine of single GEMMs), and a network engine's layer tables; the
  layer axis's length, the stacked GEMMs, the stacks' bytes.

Archive (``ParetoArchive.insert``):

- ``repro.archive.insert`` (``offered``, ``screened_out``,
  ``prefiltered``, ``size``): rows in; rows an archive row dominated,
  dropped by each chunk's screen against the archive; rows that entered
  the merge, left after the screen and the survivors' own non-dominance
  pre-reduce (0 when the archive dominates the whole batch); archive
  rows after.

Service tick (``PathfinderService._tick``):

- ``repro.service.tick`` (``admitted``, ``buckets``): one tick.
- ``repro.service.admit`` (``queue_wait_us``, ``first``): one admission;
  the wait since the job last entered the queue, and 0 for a job resumed
  from PAUSED.
- ``repro.service.upload`` (``bytes``): building a bucket segment's
  device arguments, the carry and the per-slot columns together, one
  host-to-device call each.
- ``repro.service.dispatch``: enqueueing the bucket segment.
- ``repro.service.wait``: the host waiting for its outputs.
- ``repro.service.fetch`` (``bytes``): copying carry and outputs back.
- ``repro.service.boundary`` (``jobs``, ``finished``): the per-slot
  history, archive insert and boundary work.
- ``repro.service.finish`` (``queue_us``, ``run_us``, ``segments``): a
  marker as a job becomes DONE: submit to first admission, first
  admission to now, sweeps done in segments.
"""
from __future__ import annotations

from jax.profiler import TraceAnnotation

# ``span(name, **stats)``: a span named ``name`` (one listed above) with
# the stats known on entry; the rest go to ``set_metadata`` on the span
span = TraceAnnotation
# whether a profiler is recording spans now
enabled = TraceAnnotation.is_enabled


def nbytes(arrays) -> int:
    """Summed ``nbytes`` of ``arrays`` (host or device arrays)."""
    return sum(int(a.nbytes) for a in arrays)
