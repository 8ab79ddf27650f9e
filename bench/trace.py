"""Reduction of a profiler trace (``.xplane.pb``) to what the metric
readers need.

A traced run writes one ``.xplane.pb``. ``reduce_trace`` reads it with
``jax.profiler.ProfileData`` (nothing else of JAX is used) into a
``TraceSummary``:

- the window: the host span named ``bench.window`` that the harness puts
  around the measured calls (``jax.profiler.TraceAnnotation``, so host
  spans and device events share one clock), cut where the device trace
  stops if it stops early (``_covered``);
- device operations: the events of each accelerator plane's ``XLA Ops``
  line, each with the XLA module it ran in;
- module executions: the events of each plane's ``XLA Modules`` line;
- host spans: every event on a host thread whose name starts with
  ``bench.``.

From these it computes busy time (the union of operation intervals in the
window), idle gaps (the rest of the window), device time per module, the
top operations, and the longest idle gaps labelled by the innermost host
span that covers each gap's midpoint. A trace with no accelerator plane
(a CPU run) yields no device events: nothing is invented for it.
"""
from __future__ import annotations

import dataclasses
import glob
import os
from typing import Dict, Iterable, List, Optional, Tuple

import numpy as np

WINDOW_SPAN = "bench.window"
DISPATCH_SPAN = "bench.dispatch"
SPAN_PREFIX = "bench."
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def module_base(name: str) -> str:
    """``jit_run(12)`` -> ``jit_run``: a module's name without the
    program id the trace appends."""
    return name.split("(", 1)[0].strip()


@dataclasses.dataclass
class TraceSummary:
    """Intervals in nanoseconds on the trace's clock.

    ``ops[d]`` is device ``d``'s operations as an ``[k, 2]`` array of
    (start, end), with parallel integer arrays ``op_label[d]`` (indices
    into ``labels``, the operations' names) and ``op_module[d]``
    (indices into ``module_names``, base names; -1 where no module
    execution holds the operation); ``modules[d]`` is its module
    executions as ``(start, end, name)``; ``spans`` are the host
    ``bench.*`` spans as ``(start, end, name)``. A busy window holds
    millions of operations, so everything per operation is an array."""

    window: Tuple[float, float]
    ops: List[np.ndarray]
    op_label: List[np.ndarray]
    op_module: List[np.ndarray]
    labels: List[str]
    module_names: List[str]
    modules: List[List[Tuple[float, float, str]]]
    spans: List[Tuple[float, float, str]]
    _unions: Dict[int, np.ndarray] = dataclasses.field(
        default_factory=dict, repr=False)
    _cums: Dict[int, np.ndarray] = dataclasses.field(
        default_factory=dict, repr=False)
    _kinds: Dict[int, tuple] = dataclasses.field(
        default_factory=dict, repr=False)

    # -- window ------------------------------------------------------------

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    @property
    def n_devices(self) -> int:
        return len(self.ops)

    def _in_window(self, d: int) -> np.ndarray:
        """Device ``d``'s operations clipped to the window (``[k, 2]``,
        one row per operation, empty rows where outside it)."""
        if d >= self.n_devices:
            return np.zeros((0, 2))
        lo, hi = self.window
        c = np.clip(self.ops[d], lo, hi)
        c[:, 1] = np.maximum(c[:, 1], c[:, 0])
        return c

    @staticmethod
    def _union(iv: np.ndarray) -> np.ndarray:
        """Disjoint sorted intervals covering ``iv``."""
        if iv.size == 0:
            return iv.reshape(0, 2)
        iv = iv[np.argsort(iv[:, 0], kind="stable")]
        reach = np.maximum.accumulate(iv[:, 1])
        first = np.ones(len(iv), dtype=bool)
        first[1:] = iv[1:, 0] > reach[:-1]
        idx = np.flatnonzero(first)
        return np.stack([iv[idx, 0], np.maximum.reduceat(iv[:, 1], idx)],
                        axis=1)

    def _busy(self, d: int) -> np.ndarray:
        if d not in self._unions:
            c = self._in_window(d)
            self._unions[d] = self._union(c[c[:, 1] > c[:, 0]])
        return self._unions[d]

    # -- busy and idle -----------------------------------------------------

    def busy_ns(self, d: int) -> float:
        u = self._busy(d)
        return float((u[:, 1] - u[:, 0]).sum()) if u.size else 0.0

    def busy_s(self) -> float:
        """Busy seconds averaged over the devices that ran anything."""
        used = [d for d in range(self.n_devices) if self.ops[d].size]
        if not used:
            return 0.0
        return sum(self.busy_ns(d) for d in used) / len(used) * 1e-9

    def idle_gaps(self, d: int = 0) -> np.ndarray:
        """``[g, 2]`` idle intervals of device ``d`` inside the window."""
        lo, hi = self.window
        u = self._busy(d)
        if u.size == 0:
            return np.asarray([[lo, hi]], dtype=np.float64)
        starts = np.concatenate([[lo], u[:, 1]])
        ends = np.concatenate([u[:, 0], [hi]])
        g = np.stack([starts, ends], axis=1)
        return g[g[:, 1] > g[:, 0]]

    def _busy_until(self, t: float, d: int) -> float:
        """Busy nanoseconds of device ``d`` in the window before ``t``."""
        u = self._busy(d)
        if u.size == 0:
            return 0.0
        if d not in self._cums:
            self._cums[d] = np.concatenate([[0.0],
                                            np.cumsum(u[:, 1] - u[:, 0])])
        i = int(np.searchsorted(u[:, 0], t, side="right"))
        if i == 0:
            return 0.0
        last = min(max(t - u[i - 1, 0], 0.0), u[i - 1, 1] - u[i - 1, 0])
        return float(self._cums[d][i - 1]) + last

    def idle_s_between(self, a: float, b: float, d: int = 0) -> float:
        """Seconds of ``[a, b]`` (clipped to the window) in which device
        ``d`` ran nothing."""
        lo, hi = self.window
        a, b = max(a, lo), min(b, hi)
        if b <= a:
            return 0.0
        busy = self._busy_until(b, d) - self._busy_until(a, d)
        return (b - a - busy) * 1e-9

    # -- modules -----------------------------------------------------------

    def module_runs(self, names: Iterable[str], d: int = 0
                    ) -> List[Tuple[float, float]]:
        """Executions of the modules whose base name is in ``names`` that
        overlap the window, in time order. (Host and device clocks agree
        to about a microsecond, so an execution that starts with the
        window can appear to start just before it.)"""
        want = set(names)
        lo, hi = self.window
        if d >= len(self.modules):
            return []
        return sorted((s, e) for s, e, n in self.modules[d]
                      if module_base(n) in want and e > lo and s < hi)

    def module_time_s(self) -> Dict[str, Tuple[int, float]]:
        """``{module: (executions, seconds)}`` on device 0 in the window."""
        out: Dict[str, Tuple[int, float]] = {}
        lo, hi = self.window
        if not self.modules:
            return out
        for s, e, n in self.modules[0]:
            if e <= lo or s >= hi:
                continue
            k = module_base(n)
            c, t = out.get(k, (0, 0.0))
            out[k] = (c + 1, t + (e - s) * 1e-9)
        return out

    # -- operations --------------------------------------------------------

    def _by_kind(self, d: int) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        """The distinct (module, label) pairs of device ``d``'s
        operations in the window: ``(pairs [p, 2], per-pair seconds,
        per-pair count)``."""
        if d in self._kinds:
            return self._kinds[d]
        if d >= self.n_devices or not self.ops[d].size:
            z = np.zeros(0)
            return np.zeros((0, 2), dtype=np.int64), z, z
        c = self._in_window(d)
        dur = c[:, 1] - c[:, 0]
        inside = dur > 0
        n = max(len(self.labels), 1)
        key = (self.op_module[d][inside] + 1) * n + self.op_label[d][inside]
        keys, inv = np.unique(key, return_inverse=True)
        inv = inv.reshape(-1)
        pairs = np.stack([keys // n - 1, keys % n], axis=1)
        self._kinds[d] = (pairs,
                          np.bincount(inv, weights=dur[inside]) * 1e-9,
                          np.bincount(inv))
        return self._kinds[d]

    def _names(self, pair) -> Tuple[str, str]:
        m, lab = int(pair[0]), int(pair[1])
        return self.labels[lab], (self.module_names[m] if m >= 0 else "")

    def op_time_s(self, match, d: int = 0) -> float:
        """Summed duration (seconds, in the window) of the device
        operations for which ``match(name, module)`` holds."""
        pairs, secs, _ = self._by_kind(d)
        return float(sum(secs[i] for i, p in enumerate(pairs)
                         if match(*self._names(p))))

    def op_count(self, match, d: int = 0) -> int:
        """Device operations in the window for which ``match(name,
        module)`` holds."""
        pairs, _, count = self._by_kind(d)
        return int(sum(count[i] for i, p in enumerate(pairs)
                       if match(*self._names(p))))

    def top_ops(self, k: int = 10, d: int = 0) -> List[List[object]]:
        """The ``k`` operations (by name within their module) that took
        the most device time in the window, as ``[name, seconds]``."""
        pairs, secs, _ = self._by_kind(d)
        order = np.argsort(-secs, kind="stable")[:k]
        out = []
        for i in order:
            name, module = self._names(pairs[i])
            out.append([f"{module}/{name}", float(secs[i])])
        return out

    # -- host attribution ---------------------------------------------------

    def covering_span(self, t: float) -> str:
        """The innermost ``bench.*`` span (other than the window) that
        covers instant ``t``; ``unattributed`` when none does."""
        best: Optional[Tuple[float, str]] = None
        for s, e, n in self.spans:
            if n == WINDOW_SPAN or not s <= t <= e:
                continue
            if best is None or e - s < best[0]:
                best = (e - s, n)
        return best[1] if best else "unattributed"

    def longest_gaps(self, k: int = 10, d: int = 0) -> List[List[object]]:
        """The ``k`` longest idle gaps as ``[label, seconds]``, each
        labelled by the host span covering its midpoint."""
        g = self.idle_gaps(d)
        if g.size == 0:
            return []
        order = np.argsort(-(g[:, 1] - g[:, 0]), kind="stable")[:k]
        return [[self.covering_span(0.5 * (g[i, 0] + g[i, 1])),
                 float(g[i, 1] - g[i, 0]) * 1e-9] for i in order]


def find_xplane(log_dir: str) -> str:
    """The newest ``.xplane.pb`` under a profiler log directory."""
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return max(paths, key=os.path.getmtime)


def short_name(name: str) -> str:
    """An operation's name: on the TPU the event is named by its whole
    HLO instruction, of which only what precedes `` = `` is kept."""
    return name.split(" = ", 1)[0]


def _is_device_plane(name: str) -> bool:
    return name.startswith("/device:") and not name.startswith("/device:CPU")


def _ops_line(line, names: Dict[str, int]):
    """A plane's operations: ``([k, 2] (start, end), [k] name ids)``,
    with new names added to ``names``."""
    starts, durs, ids = [], [], []
    for ev in line.events:
        starts.append(ev.start_ns)
        durs.append(ev.duration_ns)
        nm = ev.name
        k = names.get(nm)
        if k is None:
            k = names[nm] = len(names)
        ids.append(k)
    s = np.asarray(starts, dtype=np.float64)
    return (np.stack([s, s + np.asarray(durs, dtype=np.float64)], axis=1)
            .reshape(-1, 2), np.asarray(ids, dtype=np.int64))


def _stat_modules(line) -> List[str]:
    """The ``hlo_module`` stat of every operation (slow: only for a
    plane whose trace has no module executions)."""
    out = []
    for ev in line.events:
        try:
            out.append(str(dict(ev.stats).get("hlo_module", "")))
        except (TypeError, ValueError):
            out.append("")
    return out


def _covered(window, ops, modules, spans) -> Tuple[float, float]:
    """The part of the window that the device trace holds. The profiler
    keeps a bounded number of device events: past it, a busy window's
    trace simply ends. A driver marks each program it dispatches with a
    ``bench.dispatch`` span, and every dispatched program runs after its
    dispatch starts; so a dispatch that starts after the last recorded
    device event shows where the device trace stopped, and the window is
    cut there."""
    ends = [float(a[:, 1].max()) for a in ops if a.size]
    ends += [max(r[1] for r in runs) for runs in modules if runs]
    if not ends:
        return window
    last = max(ends)
    lo, hi = window
    if last < hi and any(n == DISPATCH_SPAN and last < s < hi
                         for s, _, n in spans):
        return (lo, last)
    return window


def reduce_trace(path: str) -> TraceSummary:
    """Read one ``.xplane.pb`` into a :class:`TraceSummary`."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    names: Dict[str, int] = {}
    module_ids: Dict[str, int] = {}
    ops, op_label, op_module, modules = [], [], [], []
    spans: List[Tuple[float, float, str]] = []
    window = None

    def module_id(name: str) -> int:
        if not name:
            return -1
        base = module_base(name)
        if base not in module_ids:
            module_ids[base] = len(module_ids)
        return module_ids[base]

    for plane in pd.planes:
        if _is_device_plane(plane.name):
            iv, lab, runs, ops_line = np.zeros((0, 2)), None, [], None
            for line in plane.lines:
                if line.name == OPS_LINE:
                    iv, lab = _ops_line(line, names)
                    ops_line = line
                elif line.name == MODULES_LINE:
                    for ev in line.events:
                        s = float(ev.start_ns)
                        runs.append((s, s + float(ev.duration_ns), ev.name))
            if not iv.size and not runs:
                continue
            if lab is None:
                lab = np.zeros(0, dtype=np.int64)
            # an op's module: the module execution that holds it, else
            # (a trace without executions) the op's own stat
            mod = np.full(len(iv), -1, dtype=np.int64)
            if runs:
                rs = sorted(runs)
                r_start = np.asarray([r[0] for r in rs])
                r_end = np.asarray([r[1] for r in rs])
                r_id = np.asarray([module_id(r[2]) for r in rs])
                j = np.searchsorted(r_start, iv[:, 0], side="right") - 1
                held = (j >= 0) & (r_end[np.maximum(j, 0)] >= iv[:, 0])
                mod[held] = r_id[j[held]]
            elif ops_line is not None:
                mod = np.asarray([module_id(m)
                                  for m in _stat_modules(ops_line)],
                                 dtype=np.int64)
            ops.append(iv)
            op_label.append(lab)
            op_module.append(mod)
            modules.append(runs)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if not ev.name.startswith(SPAN_PREFIX):
                        continue
                    s = float(ev.start_ns)
                    e = s + float(ev.duration_ns)
                    spans.append((s, e, ev.name))
                    if ev.name == WINDOW_SPAN and (
                            window is None or e - s > window[1] - window[0]):
                        window = (s, e)
    if window is None:
        raise ValueError(f"{path}: no {WINDOW_SPAN!r} span in the trace")
    window = _covered(window, ops, modules, spans)
    short: Dict[str, int] = {}
    remap = np.asarray([short.setdefault(short_name(n), len(short))
                        for n in sorted(names, key=names.get)],
                       dtype=np.int64)
    op_label = [remap[lab] if lab.size else lab for lab in op_label]
    labels = list(short)
    return TraceSummary(window=window, ops=ops, op_label=op_label,
                        op_module=op_module, labels=labels,
                        module_names=sorted(module_ids, key=module_ids.get),
                        modules=modules, spans=spans)
