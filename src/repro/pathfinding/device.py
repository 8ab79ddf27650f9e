"""Device-resident pathfinding: the fused evaluate+cost program,
vectorized moves, and one ``lax.scan`` parallel-tempering engine.

The batched :func:`repro.pathfinding.batch.evaluate_batch` vectorizes
the metric *arithmetic* but keeps the search loop host-bound: a per-row Python
topology pass (``_topo_one``), an un-jitted ``jax.numpy`` stage 3, Python
``propose()`` per chain and a host<->device round-trip every sweep. This
module moves the whole explore -> evaluate -> accept loop onto the device:

* The fused evaluator (:func:`_eval_cost_jax`) — stages 1-3 of the
  batched evaluator *and* the Eq. 17 ``sa_cost`` in one XLA program. The
  per-row Python floorplan/BFS pass is replaced by an exact vectorized
  rendering (the slicing-floorplan recursion unrolled level-by-level over
  fixed ``max_chiplets`` slots, BFS with queue-order tie-breaking as a
  masked fixed-point, link tables in a fixed ``(C*(C-1)/2 + C-1)``-slot
  layout), so stage 2 becomes gathers + elementwise arithmetic with no
  data-dependent Python.
* The hierarchical move generator (:func:`_propose_jax`) — the move
  distribution of :func:`repro.core.sa.propose` (application /
  chip-architecture / chiplet / package levels, style repair,
  hierarchical package-then-protocol draws) applied to encoded ``int32``
  rows with ``jax.random``; candidates that fail the vectorized validity
  rules keep the incumbent row (the batched rendering of the scalar
  retry loop).
* :class:`ScenarioEngine` — the one tempering engine. Propose, evaluate,
  Metropolis accept and sequential adjacent-pair replica exchange run in
  one ``lax.scan`` over a ``vmap``-ped cell axis: the grid carbon
  intensity, per-cell normalizer/weight rows and the per-workload tile
  totals are *runtime* data of the program (tile prefix tables ride in a
  bucket-padded per-workload stack), so a whole region x workload
  :class:`~repro.pathfinding.pareto.ScenarioSweep` runs with one XLA
  compile, ``fold_in``-derived per-cell keys and optional scenario-axis
  sharding over local devices. A workload may be a whole network of
  GEMMs (:mod:`repro.core.network`): its layers ride a ``vmap``-ped layer
  axis of the same program. The scan is advanced in host-driven chunks
  (``segment=`` sweeps each); the chunking is bit-invisible — same key
  stream, same sweep indices — and its boundaries are where long
  searches snapshot carry + frontier archives for checkpoint/resume
  (:mod:`repro.pathfinding.resume`).
* :class:`DeviceEvaluator` — one workload's view of that engine: a
  one-cell grid whose cell key is the caller's key, with the
  single-workload surface (``evaluate_cost``, ``metrics``, ``propose``,
  ``parallel_tempering``) and checkpoint layout. ``record_trace=True``
  additionally returns every proposal and uniform draw so a host
  reference can replay the exact trajectory (the trajectory-equivalence
  tests).

Numerics: everything runs in float64 (:func:`repro.jaxenv.search_numerics`
scoped to this module's entry points) and replicates the host evaluator's
operation order wherever floating-point ties matter (greedy floorplan
accumulation order, Algorithm 1's sorted-order power summation), so the
jitted path stays within the 1e-6 relative parity contract of the scalar
:func:`repro.core.evaluate.evaluate` — in practice ~1e-15.

The hottest stage-3 inner loop (prefix-table gather + per-slot split-K
select) can run through the Pallas kernel in
:mod:`repro.kernels.prefix_gather` (``use_pallas=True`` or
``REPRO_PATHFINDER_PALLAS=1``; default auto = TPU backends only). It
reads a packed int32 hi/lo copy of the int64 prefix tables and is
bit-equal to the jnp gathers; it compiles on TPU and runs in
interpreter mode elsewhere (exact but slow).

The scalar fallback (``Pathfinder(device=False)`` or any non-CarbonPATH
objective backend, e.g. ChipletGym) keeps that host path.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import os
import warnings
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro.core import comm as comm_mod
from repro.core import schedule as sched_mod
from repro.core.carbon import SECONDS_PER_YEAR
from repro.core.scalesim import OPERAND_BYTES
from repro.core.techdb import DEFAULT_DB, HOURS_PER_DAY, TechDB
from repro.core.templates import Normalizer, Template
from repro.core.network import Network, Workload, as_network
from repro.core.workload import DEFAULT_TILE, GEMMWorkload
from repro.pathfinding.batch import (
    MetricsBatch,
    _SIM_METRICS,
    get_evaluator,
)
from repro.pathfinding.space import (
    COL_CHIP,
    COL_DATAFLOW,
    COL_MEM,
    COL_N,
    COL_ORDER,
    COL_PAIR25,
    COL_PAIR3,
    COL_SPLITK,
    COL_STACK,
    COL_STYLE,
    DEFAULT_MAX_CHIPLETS,
    DesignSpace,
    S_25D,
    S_2D,
    S_3D,
    S_HYBRID,
)
from repro.tracing import nbytes, span

P_APPLICATION = 0.35  # sa.propose's application-level move probability


@dataclasses.dataclass(frozen=True)
class _Cfg:
    """Static (trace-time) constants baked into the jitted programs."""

    C: int            # max chiplet slots
    W: int            # encoded row width
    A: int            # array-size options
    T_nodes: int      # tech-node options
    S: int            # max SRAM options
    M: int            # memory options
    n_pairs25: int
    n_pairs3: int
    n_pkg25: int
    n_pkg3: int
    L: int            # fixed link slots: C*(C-1)/2 plane + C-1 chain
    T0: int           # tile bucket without split-K (prefix-table bound)
    T1: int           # tile bucket with split-K
    acost: float
    substrate_cost_mm2: float
    substrate_cfp_mm2: float
    interposer_cpa: float
    interposer_defect: float
    interposer_wafer_cost: float
    yield_alpha: float
    wafer_diameter_mm: float
    lifetime_years: float
    use_fraction: float
    duty_runs_per_s: float
    router_area_frac: float           # NoC share of die mfg carbon -> C_HI
    comm: str                         # communication model (repro.core.comm)
    noc_col: int                      # first NoC column (mesh_noc layouts)
    n_mesh: int                       # len(comm.MESH_DIMS)
    n_entry: int                      # len(comm.ENTRY_PLACEMENTS)
    noc_hop_latency_s: float
    noc_energy_pj_bit: float
    # shared per-hop package latency when every protocol agrees (the
    # bit-pinned hops * h form); None switches the hop term to the
    # per-link-kind split using the p25_hl/p3_hl tables
    hop_uniform: Optional[float]
    noc_live: bool                    # NoC axes searchable (not frozen)
    # temporal scheduling seam (repro.core.schedule): the 24h duty
    # weighting rides in the trace-constant tb["sched_tab"] lookup —
    # fixed spaces gather its row 0 (= db.load_profile verbatim),
    # window spaces gather per-design (start, shape) columns
    schedule: str                     # schedule model (fixed | window)
    sched_col: int                    # first schedule column (window)
    n_sched: int                      # schedule-shape table rows
    sched_live: bool                  # schedule axes searchable
    # (rows, T0 + 1, T1 + 1) of the packed prefix-gather kernel table;
    # None runs the stage as plain jnp gathers
    pallas_layout: Optional[Tuple[int, int, int]]


def _popcount(x, bits: int):
    import jax.numpy as jnp

    out = jnp.zeros_like(x)
    for i in range(bits):
        out = out + ((x >> i) & 1)
    return out


# ---------------------------------------------------------------------------
# Stage 1: Algorithm 1 tile assignment (exact jnp port of batch._assign)
# ---------------------------------------------------------------------------
#
# Algorithm 1 floors ``p / psum * T`` and hands leftover tiles to the
# largest fractional parts. The compute powers sit in simple ratios, so
# those shares land exactly on (or an ulp beside) integers and each other
# all the time, and the result turns on the last bit of IEEE float64
# rounding. The TPU emulates float64 at ~48 bits and does not round
# division correctly, which moved tile counts there. So the device
# reproduces the IEEE float64 operations bit-for-bit in exact int64
# arithmetic: the powers arrive as integers scaled by a power of two
# (``_exact_ints``), and every add, divide and multiply below rounds to
# 53 significant bits, half to even, exactly as the host does. The
# floorplan's greedy area split (stage 2) compares IEEE sums of chiplet
# areas the same way.


def _exact_ints(table: np.ndarray) -> np.ndarray:
    """Non-negative float64 table values as exact int64 multiples of one
    power of two (the smallest value's last mantissa bit is 1)."""
    t = np.asarray(table, dtype=np.float64)
    assert (t >= 0).all()
    mant, exp = np.frexp(t)  # t = mant * 2**exp, 0.5 <= mant < 1
    shift = int(53 - exp[t > 0].min())
    ints = np.ldexp(mant, exp + shift).astype(np.int64)
    assert (np.ldexp(ints.astype(np.float64), -shift) == t).all()
    # sums of six values must stay clear of int64 overflow
    assert ints.max() < 2 ** 59, "table values span too many octaves"
    return ints


def _bit_length(x):
    """Bit length of non-negative int64 values (0 for 0): how many of
    2**0 .. 2**62 ``x`` reaches, one compare and one reduce deep (these
    ops sit on long dependency chains, and the TPU compiler recurses
    along such chains)."""
    import jax.numpy as jnp

    pw = jnp.left_shift(jnp.int64(1), jnp.arange(63, dtype=jnp.int64))
    return jnp.sum(x[..., None] >= pw, axis=-1, dtype=x.dtype)


def _round53(x):
    """Round non-negative int64 values to 53 significant bits, half to
    even (the value stays an integer)."""
    import jax.numpy as jnp

    drop = jnp.maximum(_bit_length(x) - 53, 0)
    q = x >> drop
    rem = x - (q << drop)
    half = jnp.where(drop > 0, jnp.left_shift(1, jnp.maximum(drop - 1, 0)),
                     0).astype(x.dtype)
    up = (rem > half) | ((rem == half) & (drop > 0) & ((q & 1) == 1))
    return (q + up) << drop


def _div_round(p, s):
    """Correctly rounded ``p / s`` for int64 ``0 <= p <= s``, ``s > 0``:
    ``(m, e)`` with value ``m * 2**e`` and ``2**52 <= m < 2**53`` (or
    ``m == 0``)."""
    import jax.numpy as jnp
    from jax import lax

    d = _bit_length(s) - _bit_length(p)
    num = p << d
    low = num < s
    num = jnp.where(low, num << 1, num)
    d = d + low

    def step(_, mr):  # long division, one mantissa bit per step
        m, r = mr
        r = r << 1
        b = r >= s
        return (m << 1) + b, jnp.where(b, r - s, r)

    m, r = lax.fori_loop(0, 52, step, (jnp.ones_like(p), num - s))
    r2 = r << 1
    m = m + ((r2 > s) | ((r2 == s) & ((m & 1) == 1)))
    carry = m == (1 << 53)
    m = jnp.where(carry, m >> 1, m)
    e = -52 - d + carry
    zero = p == 0
    return jnp.where(zero, 0, m), jnp.where(zero, 0, e)


def _assign_jax(powers_i, nmask, order, total, cfg: _Cfg):
    """Per-core (start, count) into the canonical tile list, bit-equal to
    the IEEE float64 ``batch._assign`` (see the section comment).
    ``powers_i`` are the ``_exact_ints`` of the cores' compute powers."""
    return _assign_counts(*_assign_shares(powers_i, nmask, order, cfg),
                          total)


def _assign_shares(powers_i, nmask, order, cfg: _Cfg):
    """The design's part of :func:`_assign_jax`, shared by every GEMM of
    a network: the cores' order and each one's share ``p / psum`` as a
    53-bit mantissa and exponent."""
    import jax.numpy as jnp

    C = cfg.C
    i64 = jnp.int64
    powers_i = jnp.where(nmask, powers_i, 0).astype(i64)
    key = jnp.where((order == 0)[:, None], -powers_i, powers_i)
    key = jnp.where(nmask, key, jnp.iinfo(i64).max)  # padding sorts last
    pos = jnp.argsort(key, axis=1)  # stable
    p_sorted = jnp.take_along_axis(powers_i, pos, axis=1)
    # sequential fold in sorted order, each partial sum rounded like a
    # float64 add: equal-power cores make the fractional parts ulp-level
    # ties, so summation order is part of the parity contract
    psum = jnp.zeros(powers_i.shape[0], i64)
    for c in range(C):
        psum = _round53(psum + p_sorted[:, c])
    psum = jnp.where(psum > 0, psum, 1)
    # ideal = fl(fl(p / psum) * total) = m2 * 2**e
    m, e = _div_round(p_sorted, psum[:, None])
    return pos, m, e


def _assign_counts(pos, m, e, total):
    """The GEMM's part of :func:`_assign_jax`: ``total`` tiles dealt by
    the shares of :func:`_assign_shares`."""
    import jax.numpy as jnp

    i64 = jnp.int64
    m2 = _round53(m * total.astype(i64)[:, None])
    sh = -e
    counts = m2 >> sh
    frac = m2 - (counts << sh)
    # fractional parts on one scale (each is < 1, so < 2**62 there)
    up = jnp.maximum(62 - sh, 0)
    frac = (frac << up) >> jnp.maximum(sh - 62, 0)
    remaining = total.astype(i64) - jnp.sum(counts, axis=1)
    frac_pos = jnp.argsort(-frac, axis=1)  # stable
    rank = jnp.argsort(frac_pos, axis=1)   # exact inverse permutation
    counts_i = counts + (rank < remaining[:, None])
    starts = jnp.concatenate(
        [jnp.zeros_like(counts_i[:, :1]),
         jnp.cumsum(counts_i[:, :-1], axis=1)], axis=1)
    inv = jnp.argsort(pos, axis=1)
    start = jnp.take_along_axis(starts, inv, axis=1)
    count = jnp.take_along_axis(counts_i, inv, axis=1)
    return start, count


# ---------------------------------------------------------------------------
# Stage 2: vectorized topology (exact rendering of batch._topo_one /
# batch._topology, incl. the slicing floorplan and sorted-BFS routes)
# ---------------------------------------------------------------------------


def _topology_jax(v, areas, areas_i, tb, cfg: _Cfg):
    """``areas`` are the slot areas in float64, ``areas_i`` the same
    values as :func:`_exact_ints`: every discrete choice on areas (sort
    orders, the destination die, the floorplan's greedy split) reads the
    exact integers, so it matches the IEEE float64 host on any backend."""
    import jax.numpy as jnp
    from jax import lax

    C, L = cfg.C, cfg.L
    P = v.shape[0]
    rows = jnp.arange(P)
    slot = jnp.arange(C, dtype=jnp.int32)

    n = v[:, COL_N].astype(jnp.int32)
    style = v[:, COL_STYLE]
    is2d = style == S_2D
    is25 = style == S_25D
    is3d = style == S_3D
    ishyb = style == S_HYBRID
    active = slot[None, :] < n[:, None]

    memtot = tb["m_bw"][jnp.clip(v[:, COL_MEM], 0, cfg.M - 1)]
    p25i = jnp.clip(v[:, COL_PAIR25], 0, cfg.n_pairs25 - 1)
    p3i = jnp.clip(v[:, COL_PAIR3], 0, cfg.n_pairs3 - 1)
    p25row = tb["p25"][p25i]  # one gather for all 7 package fields
    pitch25, y25, cfp25, scale25, rate25, eta25, ebit25 = [
        p25row[:, i] for i in range(7)]
    interp25 = tb["p25_interp"][p25i]
    p3row = tb["p3"][p3i]
    pitch3, y3, cfp3, scale3, rate3, eta3, ebit3 = [
        p3row[:, i] for i in range(7)]

    # -- 3D chain: members sorted by non-increasing area, ties by index ----
    member = ((v[:, COL_STACK][:, None] >> slot[None, :]) & 1) == 1
    member = jnp.where(ishyb[:, None], member & active,
                       jnp.where(is3d[:, None], active, False))
    chain_len = member.sum(axis=1).astype(jnp.int32)
    i64_max = jnp.iinfo(jnp.int64).max
    chain_slots = jnp.argsort(
        jnp.where(member, -areas_i, i64_max), axis=1).astype(jnp.int32)
    a_chain = jnp.take_along_axis(areas, chain_slots, axis=1)
    base_slot = chain_slots[:, 0]
    tier = jnp.arange(C)
    tmask = (tier[None, :] >= 1) & (tier[None, :] < chain_len[:, None])
    # Eq. 7 per bond: bumps over the (smaller) upper die's face
    face = jnp.minimum(a_chain[:, :-1], a_chain[:, 1:])
    nb3 = jnp.maximum(1.0, jnp.trunc(face * 1e6 / (pitch3 * pitch3)[:, None]))
    cbw = rate3[:, None] * 1e9 * nb3 * eta3[:, None]
    bond_exists = ((jnp.arange(C - 1)[None, :] + 1 < chain_len[:, None])
                   & (is3d | ishyb)[:, None])

    # -- planar set in floorplan input order: non-members asc + base -------
    planar_mask = active & ~member
    porder = jnp.argsort(
        jnp.where(planar_mask, slot[None, :], C + 1), axis=1
    ).astype(jnp.int32)
    n_nonmem = planar_mask.sum(axis=1).astype(jnp.int32)
    porder = jnp.where(ishyb[:, None] & (slot[None, :] == n_nonmem[:, None]),
                       base_slot[:, None], porder)
    m_planar = n_nonmem + ishyb.astype(jnp.int32)
    pvalid = slot[None, :] < m_planar[:, None]
    ar_p = jnp.where(pvalid, jnp.take_along_axis(areas, porder, axis=1), 0.0)
    ar_pi = jnp.where(pvalid, jnp.take_along_axis(areas_i, porder, axis=1), 0)

    # planar-order sequential sums (parity with Python sum())
    tot = jnp.zeros(P)
    for j in range(C):
        tot = tot + ar_p[:, j]
    side = jnp.sqrt(tot * (1.0 + 0.10))

    # -- slicing floorplan, recursion unrolled level by level --------------
    # the greedy iteration order (area desc, ties by input position) is
    # invariant across levels: children receive items already sorted.
    # groups are tiny (<= C members), so all per-group accumulation is
    # expressed as pairwise same-group comparisons — pure fusable
    # elementwise chains, no scatters (the dominant cost on CPU)
    sorder = jnp.argsort(jnp.where(pvalid, -ar_pi, i64_max),
                         axis=1).astype(jnp.int32)
    inv_sorder = jnp.argsort(sorder, axis=1)
    a_s = jnp.take_along_axis(ar_p, sorder, axis=1)       # sorted areas
    v_s = jnp.take_along_axis(pvalid, sorder, axis=1)
    contrib = [jnp.where(v_s[:, t], a_s[:, t], 0.0) for t in range(C)]
    a_si = jnp.take_along_axis(ar_pi, sorder, axis=1)
    contrib_i = [jnp.where(v_s[:, t], a_si[:, t], 0) for t in range(C)]
    g = jnp.zeros((P, C), dtype=jnp.int32)
    bx = jnp.zeros((P, C))
    by = jnp.zeros((P, C))
    bwid = jnp.broadcast_to(side[:, None], (P, C))
    bhei = jnp.broadcast_to(side[:, None], (P, C))
    for level in range(max(C - 1, 1)):
        g_s = jnp.take_along_axis(g, sorder, axis=1)
        # greedy pass in sorted order: left iff al <= ar of the item's
        # group so far. The running sums are exact integers rounded like
        # float64 adds in the scalar iteration order: areas whose exact
        # sums tie are common, and the split turns on the last bit.
        # ``run[t]`` holds (al, ar) of item t's group after item t; an
        # item starts from the latest earlier item of its group
        left_s, run = [], []
        zero = jnp.zeros(P, jnp.int64)
        for t in range(C):
            al_t, ar_t = zero, zero
            for t2 in range(t):
                same = g_s[:, t2] == g_s[:, t]
                al_t = jnp.where(same, run[t2][0], al_t)
                ar_t = jnp.where(same, run[t2][1], ar_t)
            left = al_t <= ar_t
            grown = _round53(jnp.where(left, al_t, ar_t) + contrib_i[t])
            run.append((jnp.where(left, grown, al_t),
                        jnp.where(left, ar_t, grown)))
            left_s.append(left)
        # final per-group totals / counts, accumulated per original
        # position in the same sorted order as the scalar greedy
        # (skipped other-group items add 0.0, which is exact)
        frac_cols, split_cols = [], []
        for j in range(C):
            gj = g[:, j]
            al_j = jnp.zeros(P)
            ar_j = jnp.zeros(P)
            cnt_j = jnp.zeros(P, dtype=jnp.int32)
            for t2 in range(C):
                same = g_s[:, t2] == gj
                al_j = al_j + jnp.where(same & left_s[t2], contrib[t2], 0.0)
                ar_j = ar_j + jnp.where(same & ~left_s[t2], contrib[t2],
                                        0.0)
                cnt_j = cnt_j + (same & v_s[:, t2]).astype(jnp.int32)
            den = al_j + ar_j
            frac_cols.append(al_j / jnp.where(den > 0, den, 1.0))
            split_cols.append(cnt_j >= 2)
        frac_j = jnp.stack(frac_cols, axis=1)
        split_j = jnp.stack(split_cols, axis=1) & pvalid
        goleft = jnp.take_along_axis(jnp.stack(left_s, axis=1),
                                     inv_sorder, axis=1)
        if level % 2 == 0:  # vertical cut, alternating by depth
            wl_ = bwid * frac_j
            bx = jnp.where(split_j & ~goleft, bx + wl_, bx)
            bwid = jnp.where(split_j,
                             jnp.where(goleft, wl_, bwid - wl_), bwid)
        else:
            hl_ = bhei * frac_j
            by = jnp.where(split_j & ~goleft, by + hl_, by)
            bhei = jnp.where(split_j,
                             jnp.where(goleft, hl_, bhei - hl_), bhei)
        g = jnp.where(split_j, g * 2 + (~goleft).astype(jnp.int32), g * 2)
    width = jnp.max(jnp.where(pvalid, bx + bwid, -jnp.inf), axis=1)
    height = jnp.max(jnp.where(pvalid, by + bhei, -jnp.inf), axis=1)
    bbox = width * height

    # -- links in a fixed slot layout: plane pairs then chain bonds --------
    # per-link values are computed as fusable elementwise [P] chains and
    # scattered into the slot-space adjacency/link tables in one batched
    # op each (valid links never collide: plane links have at most one
    # stacked endpoint — the base — while chain bonds have two)
    pairs = [(j1, j2) for j1 in range(C) for j2 in range(j1 + 1, C)]
    plane_row = is25 | ishyb
    tol = 1e-9
    j1v = jnp.asarray([j1 for j1, _ in pairs], dtype=jnp.int32)
    j2v = jnp.asarray([j2 for _, j2 in pairs], dtype=jnp.int32)
    x1, y1, w1, h1 = bx[:, j1v], by[:, j1v], bwid[:, j1v], bhei[:, j1v]
    x2, y2, w2, h2 = bx[:, j2v], by[:, j2v], bwid[:, j2v], bhei[:, j2v]
    cond_v = (jnp.abs(x1 + w1 - x2) < tol) | (jnp.abs(x2 + w2 - x1) < tol)
    lo_v = jnp.where(y1 > y2, y1, y2)
    hi_v = jnp.minimum(y1 + h1, y2 + h2)
    edge_v = jnp.where(hi_v > lo_v, hi_v - lo_v, 0.0)
    cond_h = (jnp.abs(y1 + h1 - y2) < tol) | (jnp.abs(y2 + h2 - y1) < tol)
    lo_h = jnp.where(x1 > x2, x1, x2)
    hi_h = jnp.minimum(x1 + w1, x2 + w2)
    edge_h = jnp.where(hi_h > lo_h, hi_h - lo_h, 0.0)
    edge = jnp.where(cond_v, edge_v, jnp.where(cond_h, edge_h, 0.0))
    r25 = (rate25 * 1e9)[:, None]
    e25 = eta25[:, None]
    pit25 = pitch25[:, None]
    bwk = r25 * jnp.maximum(1.0, jnp.trunc(edge * 1e3 / pit25)) * e25
    for aa in (ar_p[:, j1v], ar_p[:, j2v]):  # Eq. 6 endpoint perimeter cap
        perim = 4.0 * jnp.sqrt(aa)
        bwk = jnp.minimum(
            bwk, r25 * jnp.maximum(1.0, jnp.trunc(perim * 1e3 / pit25))
            * e25)
    s1a = jnp.concatenate([porder[:, j1v], chain_slots[:, :C - 1]], axis=1)
    s2a = jnp.concatenate([porder[:, j2v], chain_slots[:, 1:]], axis=1)
    exa = jnp.concatenate(
        [plane_row[:, None] & (j2v[None, :] < m_planar[:, None])
         & (edge > 1e-9), bond_exists], axis=1)
    link_bw = jnp.where(exa, jnp.concatenate([bwk, cbw], axis=1), jnp.inf)
    link_e = jnp.where(
        exa, jnp.concatenate(
            [jnp.broadcast_to(ebit25[:, None], bwk.shape),
             jnp.broadcast_to(ebit3[:, None], cbw.shape)], axis=1), 0.0)
    # one-hot reduction instead of scatters (cheaper than scatter thunks
    # on CPU; valid links never collide, so the sum packs exact link ids)
    pm_half = ((s1a[:, :, None] == slot[None, None, :])[:, :, :, None]
               & (s2a[:, :, None] == slot[None, None, :])[:, :, None, :]
               & exa[:, :, None, None])                 # [P, L, C, C]
    kplus1 = jnp.arange(1, L + 1, dtype=jnp.int32)[None, :, None, None]
    lid_half = jnp.sum(pm_half * kplus1, axis=1)
    lid = lid_half + jnp.swapaxes(lid_half, 1, 2) - 1
    adj = lid >= 0

    # -- DRAM attach: planar shares, base-die-mediated chain (Eqs. 8-10) ---
    # both scatters target permutations (porder / chain_slots), so a
    # single batched .add per table is collision-free
    share = memtot[:, None] * ar_p / jnp.where(tot > 0, tot, 1.0)[:, None]
    base_share = jnp.take_along_axis(share, n_nonmem[:, None], axis=1)[:, 0]
    base_bw0 = jnp.where(ishyb, base_share, memtot)
    cmin = lax.cummin(jnp.where(bond_exists, cbw, jnp.inf), axis=1)
    eff_chain = jnp.minimum(base_bw0[:, None], cmin)
    plane_val = jnp.where(pvalid & plane_row[:, None], share, 0.0)
    chain_val = jnp.concatenate(
        [jnp.where((chain_len > 0) & is3d, memtot, 0.0)[:, None],
         jnp.where(tmask[:, 1:] & (is3d | ishyb)[:, None],
                   eff_chain, 0.0)], axis=1)
    rl1 = rows[:, None]
    eff_bw = (jnp.zeros((P, C)).at[rl1, porder].add(plane_val)
              .at[rl1, chain_slots].add(chain_val))
    dram_val = jnp.where(tmask & (is3d | ishyb)[:, None],
                         jnp.arange(C)[None, :] * ebit3[:, None], 0.0)
    dram_e = jnp.zeros((P, C)).at[rl1, chain_slots].add(dram_val)
    eff_bw = eff_bw.at[:, 0].set(jnp.where(is2d, memtot, eff_bw[:, 0]))

    # -- reduction routes: BFS per source, queue-order tie-breaking --------
    dest = jnp.argmax(jnp.where(active, areas_i, -1), axis=1
                      ).astype(jnp.int32)
    INF_I = jnp.int32(10 ** 6)
    eye = jnp.eye(C, dtype=bool)[None]
    ordv = jnp.where(eye, 0, jnp.full((P, C, C), INF_I, dtype=jnp.int32))
    prev = jnp.where(eye, slot[None, :, None],
                     jnp.full((P, C, C), -1, dtype=jnp.int32))
    counter = jnp.ones((P, C), dtype=jnp.int32)
    # step k processes the (unique) node with discovery rank k — exactly
    # the scalar queue pop order. C-1 steps suffice: a node with rank k
    # is found while processing rank k-1 <= C-2, so the last rank
    # discovers nothing
    for k in range(max(C - 1, 1)):
        at_k = ordv == k
        u = jnp.argmax(at_k, axis=2).astype(jnp.int32)
        valid_u = jnp.any(at_k, axis=2)
        adj_u = adj[rows[:, None], u]  # [P, src, node]
        # expand u's neighbours in ascending slot order: discovery rank
        # within this expansion is the exclusive prefix count of newly
        # discovered nodes (identical to the scalar queue-append order)
        newly = valid_u[..., None] & adj_u & (ordv == INF_I)
        ni = newly.astype(jnp.int32)
        offs = jnp.cumsum(ni, axis=2) - ni
        prev = jnp.where(newly, u[..., None], prev)
        ordv = jnp.where(newly, counter[..., None] + offs, ordv)
        counter = counter + jnp.sum(ni, axis=2)

    srcs = jnp.broadcast_to(slot[None, :], (P, C))
    route_on = (~is2d)[:, None] & active & (srcs != dest[:, None])
    node = jnp.broadcast_to(dest[:, None], (P, C)).astype(jnp.int32)
    hops = jnp.zeros((P, C), dtype=jnp.int64)
    hops3 = jnp.zeros((P, C), dtype=jnp.int64)
    n_plane = C * (C - 1) // 2  # link ids >= n_plane are 3D chain bonds
    inc_s = jnp.zeros((P, C, L))
    for _ in range(C - 1):
        pu = jnp.take_along_axis(prev, node[..., None], axis=2)[..., 0]
        go = route_on & (node != srcs) & (pu >= 0)
        lk = lid[rows[:, None], jnp.where(go, pu, 0), node]
        inc_s = inc_s + ((jnp.arange(L)[None, None, :] == lk[..., None])
                         & go[..., None]).astype(jnp.float64)
        hops = hops + go
        if cfg.hop_uniform is None:
            hops3 = hops3 + (go & (lk >= n_plane))
        node = jnp.where(go, pu, node)
    inc = jnp.swapaxes(inc_s, 1, 2)  # [P, link, src]

    # -- bonding yield / assembly / carbon rates (Eqs. 15-16, 2) -----------
    n_f = n.astype(jnp.float64)
    m_f = m_planar.astype(jnp.float64)
    cl_f = chain_len.astype(jnp.float64)
    bond_y = jnp.where(
        is2d, 1.0,
        jnp.where(is25, y25 ** n_f,
                  jnp.where(is3d, y3 ** (n_f - 1.0),
                            (y25 ** m_f) * (y3 ** (cl_f - 1.0)))))
    assembly = jnp.where(
        is2d, cfg.acost,
        jnp.where(is25, n_f * cfg.acost * scale25,
                  jnp.where(is3d, n_f * cfg.acost * scale3,
                            m_f * cfg.acost * scale25
                            + cl_f * cfg.acost * scale3)))
    p3_bonded = jnp.where(is3d | ishyb,
                          cfp3 * jnp.sum(jnp.where(tmask, a_chain, 0.0),
                                         axis=1), 0.0)
    pkg_area = jnp.where(is2d, areas[:, 0],
                         jnp.where(is3d, a_chain[:, 0], bbox))
    return dict(
        eff_bw=eff_bw, dram_e=dram_e, hops=hops, hops3=hops3,
        link_bw=link_bw,
        link_e=link_e, inc=inc, pkg_area=pkg_area, bond_y=bond_y,
        assembly=assembly, interp=(is25 | ishyb) & interp25,
        p25_rate=jnp.where(is25 | ishyb, cfp25, 0.0),
        p3_bonded=p3_bonded, is2d=is2d)


# ---------------------------------------------------------------------------
# Stage 3 + cost: the fused jitted evaluator
# ---------------------------------------------------------------------------


def _gather_sims(v, a_idx, s_idx, di, start, end, tb, cfg: _Cfg, rt):
    """Prefix-table gathers for both split-K tables + per-row select.

    With ``cfg.pallas_layout`` set, both split-K gathers for all five sim
    metrics, the per-row clip to the true tile totals and the split
    select run as one Pallas launch (:func:`repro.kernels.prefix_gather.
    prefix_select_gather`) over the packed int32 hi/lo table
    ``tb["pallas_table"]``; its int64 result is bit-equal to the plain
    jnp gathers of the other branch (the reference path). ``rt`` (the
    engine's per-cell runtime constants) selects the workload-stacked
    table: the row index picks up the per-workload offset ``wi*A*S*3``
    and the clip bounds are the traced per-cell tile totals.

    A network engine's layer passes ``rt["gid"]``, the layer's GEMM id
    into the per-GEMM stacks: the jnp gathers index the stacks directly
    and the kernel reads block ``gid`` of the layer-blocked table
    ``tb["pallas_blocks"]``.
    """
    import jax.numpy as jnp

    split1 = (v[:, COL_SPLITK] == 1)[:, None]
    gid = rt.get("gid")
    sims = {}
    if cfg.pallas_layout is not None:
        from repro.kernels.prefix_gather import (
            prefix_select_gather,
            prefix_select_gather_blocked,
        )

        P = v.shape[0]
        ridx = ((a_idx * cfg.S + s_idx) * 3 + di).astype(jnp.int32)
        if gid is None:
            ridx = ridx + jnp.int32(cfg.A * cfg.S * 3) * \
                rt["wi"].astype(jnp.int32)
        t0v = jnp.broadcast_to(rt["T0"].astype(jnp.int32), (P,))
        t1v = jnp.broadcast_to(rt["T1"].astype(jnp.int32), (P,))
        if gid is None:
            sel = prefix_select_gather(
                tb["pallas_table"], ridx, start, end, v[:, COL_SPLITK],
                t0v, t1v, layout=cfg.pallas_layout, nf=len(_SIM_METRICS))
        else:
            sel = prefix_select_gather_blocked(
                tb["pallas_blocks"], gid, ridx, start, end,
                v[:, COL_SPLITK], t0v, t1v, layout=cfg.pallas_layout,
                nf=len(_SIM_METRICS))
    else:
        s0 = jnp.clip(start, 0, cfg.T0)
        e0 = jnp.clip(end, 0, cfg.T0)
        s1 = jnp.clip(start, 0, cfg.T1)
        e1 = jnp.clip(end, 0, cfg.T1)
        # tables carry the 5 sim metrics in the trailing axis, so each
        # (split, bound) pair is a single gather of [P, C, 5]
        if gid is None:
            t0, t1 = tb["pref0"], tb["pref1"]
            g0 = t0[a_idx, s_idx, di, e0] - t0[a_idx, s_idx, di, s0]
            g1 = t1[a_idx, s_idx, di, e1] - t1[a_idx, s_idx, di, s1]
        else:
            t0, t1 = tb["pref0w"], tb["pref1w"]
            g0 = (t0[gid, a_idx, s_idx, di, e0]
                  - t0[gid, a_idx, s_idx, di, s0])
            g1 = (t1[gid, a_idx, s_idx, di, e1]
                  - t1[gid, a_idx, s_idx, di, s1])
        sel = jnp.where(split1[..., None], g1, g0)
    for fi, f in enumerate(_SIM_METRICS):
        sims[f] = sel[..., fi]
    if gid is None:
        mn0t, mn1t = tb["mn0"], tb["mn1"]
    else:
        mn0t, mn1t = tb["mn0w"][gid], tb["mn1w"][gid]
    mn0 = mn0t[jnp.clip(end, 0, cfg.T0)] - mn0t[jnp.clip(start, 0, cfg.T0)]
    mn1 = mn1t[jnp.clip(end, 0, cfg.T1)] - mn1t[jnp.clip(start, 0, cfg.T1)]
    mn_bits = jnp.where(split1, mn1, mn0)
    return sims, mn_bits


class _Design:
    """The design's own arrays that the per-GEMM terms read (node rates,
    DRAM bandwidths, reduction paths, memory energies), each computed
    where it is first read: a network computes them once for all its
    layers, and a single GEMM's program keeps one straight order."""

    def __init__(self, v, tb, cfg: _Cfg, slot, nmask, t_idx, dest, topo):
        self.v, self.tb, self.cfg, self.slot = v, tb, cfg, slot
        self.nmask, self.t_idx, self.dest, self.topo = (nmask, t_idx, dest,
                                                         topo)

    @functools.cached_property
    def nphys(self):  # [P, C, 4] node-scaled rates
        return self.tb["node"][self.t_idx]

    @functools.cached_property
    def freq(self):
        import jax.numpy as jnp

        return jnp.where(self.nmask, self.nphys[:, :, 0], 1.0)

    @functools.cached_property
    def den_bw(self):
        import jax.numpy as jnp

        eff_bw = self.topo["eff_bw"]
        return jnp.where(eff_bw > 0, eff_bw, 1.0)

    @functools.cached_property
    def is_dest(self):
        return self.slot[None, :] == self.dest[:, None]

    @functools.cached_property
    def noc(self):
        """Per-slot NoC hop counts and router counts (mesh_noc)."""
        import jax.numpy as jnp

        C, v, nmask = self.cfg.C, self.v, self.nmask
        nocv = v[:, self.cfg.noc_col:self.cfg.noc_col + 2 * C].reshape(
            v.shape[0], C, 2)
        mi = jnp.where(nmask, nocv[:, :, 0], 0)
        ei = jnp.where(nmask, nocv[:, :, 1], 0)
        return (jnp.where(nmask, self.tb["noc_hops"][mi, ei], 0.0),
                jnp.where(nmask, self.tb["noc_routers"][mi], 1.0))

    @functools.cached_property
    def paths(self):
        """(path latency per source slot, src + dest NoC hops or None)."""
        import jax.numpy as jnp

        cfg, tb, v, topo = self.cfg, self.tb, self.v, self.topo
        f8 = lambda x: jnp.asarray(x, dtype=jnp.float64)  # noqa: E731
        # per-source path latency: package hops x per-hop latency. With a
        # uniform hop latency the product commutes with the masked max
        # bit-exactly (h > 0 is monotone and the winning element is the
        # same), so the legacy hops * HOP_LATENCY_S program is reproduced
        # verbatim; heterogeneous protocol latencies split the hop count
        # by link kind (2.5D plane vs 3D bond) instead.
        mesh_on = cfg.comm == "mesh_noc"
        if mesh_on:
            noc_h, _ = self.noc
        if cfg.hop_uniform is not None:
            path_lat = f8(topo["hops"]) * cfg.hop_uniform
        else:
            h25 = tb["p25_hl"][jnp.maximum(v[:, COL_PAIR25], 0)]
            h3 = tb["p3_hl"][jnp.maximum(v[:, COL_PAIR3], 0)]
            path_lat = (f8(topo["hops"] - topo["hops3"]) * h25[:, None]
                        + f8(topo["hops3"]) * h3[:, None])
        if not mesh_on:
            return path_lat, None
        # on-chiplet mesh traversal: source egress + destination ingress
        # mean hop counts (closed-form Manhattan distances to the NoI
        # entry router), per NoC hop latency
        noc_dest = jnp.take_along_axis(noc_h, self.dest[:, None], axis=1)
        pair_noc = noc_h + noc_dest
        return path_lat + pair_noc * cfg.noc_hop_latency_s, pair_noc

    @functools.cached_property
    def eff_dest(self):
        import jax.numpy as jnp

        return jnp.take_along_axis(self.topo["eff_bw"], self.dest[:, None],
                                   axis=1)[:, 0]

    @functools.cached_property
    def mrow(self):  # [P, 3]: rd/wr energy + cost
        import jax.numpy as jnp

        return self.tb["mem3"][jnp.clip(self.v[:, COL_MEM], 0,
                                        self.cfg.M - 1)]

    def all(self) -> "_Design":
        for name in ("nphys", "freq", "den_bw", "is_dest", "paths",
                     "eff_dest", "mrow"):
            getattr(self, name)
        return self


def _gemm_terms_jax(sims, mn_bits, split, wr_bits, d: _Design, cfg: _Cfg):
    """The per-GEMM terms of one workload from its gathered sims, each
    ``[P]`` but the last two: Eq. 5's latency and its three terms
    (``l_cr``, ``l_d2d``, ``l_wr``), the compute and D2D energies (J),
    and the per-link reduction loads ``[P, L]`` and per-slot MACs
    ``[P, C]``."""
    import jax.numpy as jnp

    topo = d.topo
    f8 = lambda x: jnp.asarray(x, dtype=jnp.float64)  # noqa: E731
    cyc, rd, wr = f8(sims["cycles"]), f8(sims["rd"]), f8(sims["wr"])
    sram_b, macs = f8(sims["sram"]), f8(sims["macs"])
    freq, den_bw = d.freq, d.den_bw

    # Eq. 5 term 1: max_i (L_compute,i + L_DRAM_RD,i)
    l_comp = cyc / (freq * 1e9)
    l_rd = jnp.where(rd > 0, rd / den_bw, 0.0)
    l_cr = jnp.max(l_comp + l_rd, axis=1)

    # Eq. 5 term 2: reduction-phase D2D over shared links (Fig. 4)
    sbits = jnp.where(d.is_dest, 0.0, f8(mn_bits))
    loads = jnp.einsum("plc,pc->pl", topo["inc"], sbits)
    l_link = jnp.max(loads / topo["link_bw"], axis=1)
    path_lat, pair_noc = d.paths
    hop_term = jnp.max(jnp.where(sbits > 0, path_lat, 0.0), axis=1)
    l_d2d = l_link + hop_term

    # Eq. 5 term 3: DRAM write-back (split-K dependent)
    wr_split = wr_bits / d.eff_dest
    wr_direct = jnp.max(jnp.where(wr > 0, wr / den_bw, 0.0), axis=1)
    l_wr = jnp.where(split == 1, wr_split, wr_direct)
    latency = l_cr + l_d2d + l_wr

    # dynamic energy (Eqs. 12-14)
    m_rd = d.mrow[:, 0][:, None]
    m_wr = d.mrow[:, 1][:, None]
    sram_e = d.nphys[:, :, 1]
    mac_e = d.nphys[:, :, 2]
    e_comp_pj = jnp.sum(rd * m_rd + wr * m_wr + sram_b * sram_e
                        + macs * mac_e, axis=1)
    e_mem_d2d_pj = jnp.sum((rd + wr) * topo["dram_e"], axis=1)
    e_link_pj = jnp.sum(loads * topo["link_e"], axis=1)
    if pair_noc is not None:
        # NoC traversal energy: routed reduction bits x mesh hops x pJ/bit
        e_link_pj = e_link_pj + (jnp.sum(sbits * pair_noc, axis=1)
                                 * cfg.noc_energy_pj_bit)
    e_compute_j = e_comp_pj * 1e-12
    e_d2d_j = (e_link_pj + e_mem_d2d_pj) * 1e-12
    return latency, l_cr, l_d2d, l_wr, e_compute_j, e_d2d_j, loads, macs


def _metrics_jax(v, tb, cfg: _Cfg, ci, price, embf, profile, pprofile, rt):
    """The 13 MetricsBatch arrays for an encoded population, fully jitted.

    Mirrors ``BatchEvaluator.__call__`` stage by stage (same operation
    order where floating-point ties matter).

    ``ci`` is the grid carbon intensity as a *runtime* scalar (or
    per-row vector): region sweeps ride through the compiled program as
    data instead of forcing a retrace per region. ``price`` ($/kWh),
    ``embf`` (regional embodied multiplier), ``profile`` (24h grid
    intensity row) and ``pprofile`` (24h electricity-price row) are the
    remaining regional axes, runtime data too; their neutral values
    (0.0, 1.0, flat-at-ci, flat-at-price) reproduce the scalar model
    bit-for-bit — operational CFP uses
    ``ci + sum((profile - ci) * load)`` and the lifetime bill
    ``price + sum((pprofile - price) * load)``, whose correction terms
    are exactly +0.0 for flat rows. The ``load`` weights come from the
    trace-constant ``tb["sched_tab"]``: fixed-schedule programs read
    row 0 (= ``db.load_profile`` verbatim), window programs gather the
    per-design encoded (start_hour, shape_idx) columns — schedules are
    data, not shapes. ``rt`` holds the cell's per-workload constants as
    traced values (``T0``/``T1`` tile totals, ``wr_bits``, the workload
    id ``wi``) — the engine's workload axis; ``cfg.T0``/``cfg.T1`` only
    bound the (padded) prefix-table gathers.

    A network engine's ``rt`` holds the cell's layer table instead:
    ``gid`` (``[L]`` GEMM ids into the per-GEMM stacks) and ``cnt``
    (``[L]`` counts, 0 on padding). The per-GEMM stage (Algorithm 1's
    deal, the gathers, the per-GEMM terms) then runs over the layers as
    a ``vmap``-ped axis, the design's stages (topology, floorplan,
    static power, package) once, and the terms are summed with their
    counts in list order (:func:`repro.core.network.evaluate_network`).
    """
    import jax
    import jax.numpy as jnp

    C = cfg.C
    P = v.shape[0]
    slot = jnp.arange(C, dtype=jnp.int32)
    n = v[:, COL_N]
    nmask = slot[None, :] < n[:, None]
    chip = v[:, COL_CHIP:COL_CHIP + 3 * C].reshape(P, C, 3)
    a_idx = jnp.where(nmask, chip[:, :, 0], 0)
    t_idx = jnp.where(nmask, chip[:, :, 1], 0)
    s_idx = jnp.where(nmask, chip[:, :, 2], 0)

    cphys = tb["chiplet"][a_idx, t_idx, s_idx]  # [P, C, 4] physicals
    areas = jnp.where(nmask, cphys[:, :, 0], 0.0)
    areas_i = jnp.where(nmask, tb["t_area_i"][a_idx, t_idx, s_idx], 0)
    dest = jnp.argmax(jnp.where(nmask, areas_i, -1), axis=1)

    powers = tb["t_power_i"][a_idx, t_idx]
    split = v[:, COL_SPLITK]
    network = "gid" in rt

    def gemm_sims(rt_g, shares=None):
        total = jnp.where(split == 1, rt_g["T1"], rt_g["T0"])
        if shares is None:
            shares = _assign_shares(powers, nmask, v[:, COL_ORDER], cfg)
        start, count = _assign_counts(*shares, total)
        end = start + count
        di = jnp.broadcast_to(v[:, COL_DATAFLOW][:, None], (P, C))
        return _gather_sims(v, a_idx, s_idx, di, start, end, tb, cfg, rt_g)

    if network:
        shares = _assign_shares(powers, nmask, v[:, COL_ORDER], cfg)
    else:
        sims, mn_bits = gemm_sims(rt)

    topo = _topology_jax(v, areas, areas_i, tb, cfg)
    d = _Design(v, tb, cfg, slot, nmask, t_idx, dest, topo)
    mask = nmask

    if not network:
        (latency, l_cr, l_d2d, l_wr, e_compute_j, e_d2d_j, loads,
         macs) = _gemm_terms_jax(sims, mn_bits, split, rt["wr_bits"], d,
                                 cfg)
    else:
        d.all()  # the design's arrays, once for every layer

        def layer(gid):
            rt_g = dict(T0=tb["t0w"][gid], T1=tb["t1w"][gid], gid=gid)
            t = _gemm_terms_jax(*gemm_sims(rt_g, shares), split,
                                tb["wrw"][gid], d, cfg)
            return (t[0], t[4] + t[5]) + t[1:6] + (
                jnp.sum(t[6], axis=1), jnp.sum(t[7], axis=1))

        def add(acc, lx):  # list order, each term times its count
            c, xs = lx
            return tuple(a + c * x for a, x in zip(acc, xs)), None

        per = jax.vmap(layer)(rt["gid"])
        sums, _ = jax.lax.scan(add, tuple(jnp.zeros(P) for _ in per),
                               (rt["cnt"], per))
        (latency, e_dynamic, l_cr, l_d2d, l_wr, e_compute_j, e_d2d_j,
         d2d_bits, macs_sum) = sums
    static_w = jnp.where(mask, cphys[:, :, 1], 0.0)
    e_static_j = jnp.sum(static_w, axis=1) * latency
    energy = (e_dynamic if network else e_compute_j + e_d2d_j) + e_static_j

    # area, dollar cost (Eqs. 15-16)
    area = topo["pkg_area"]
    chip_cost = jnp.sum(jnp.where(mask, cphys[:, :, 2], 0.0), axis=1)
    icost = jnp.where(topo["interp"], _interposer_cost(area, cfg), 0.0)
    package = cfg.substrate_cost_mm2 * area + topo["assembly"]
    bond_y = topo["bond_y"]
    active_s = cfg.lifetime_years * SECONDS_PER_YEAR * cfg.use_fraction
    runs = cfg.duty_runs_per_s * active_s
    # decoded duty weights: window spaces roll the gathered shape row to
    # the per-design start hour; fixed spaces read the shared row 0
    # (= the legacy static load_profile values). Both branches shape
    # the weights [P, 24] — a scalar-vs-vector effective intensity
    # would let XLA reassociate the operational products differently
    # between the fixed and window programs, an ulp of cross-program
    # drift the neutral-schedule bit-invisibility contract forbids.
    if cfg.schedule == "window":
        sc = cfg.sched_col
        s_start = v[:, sc]
        s_shape = jnp.clip(v[:, sc + 1], 0, cfg.n_sched - 1)
        hrs = jnp.arange(HOURS_PER_DAY, dtype=jnp.int32)
        roll = (hrs[None, :] - s_start[:, None]) % HOURS_PER_DAY
        load = jnp.take_along_axis(tb["sched_tab"][s_shape], roll,
                                   axis=-1)
    else:
        load = jnp.broadcast_to(tb["sched_tab"][0], (P, HOURS_PER_DAY))
    eff_price = price + jnp.sum((pprofile - price) * load, axis=-1)
    dollar = ((chip_cost + icost + package) / bond_y + d.mrow[:, 2]
              + energy * runs / 3.6e6 * eff_price)

    # embodied + operational CFP (Eqs. 2-3)
    mfg_pc = jnp.where(mask, cphys[:, :, 3], 0.0)
    mfg = jnp.sum(mfg_pc, axis=1)
    des = jnp.sum(jnp.where(mask, d.nphys[:, :, 3], 0.0), axis=1)
    icfp = jnp.where(
        topo["interp"],
        area * cfg.interposer_cpa / _nb_yield(
            area, cfg.interposer_defect, cfg.yield_alpha), 0.0)
    pkg_cfp_multi = (cfg.substrate_cfp_mm2 * area
                     + topo["p25_rate"] * area + icfp
                     + topo["p3_bonded"]) / bond_y
    pkg_cfp = jnp.where(topo["is2d"], cfg.substrate_cfp_mm2 * area,
                        pkg_cfp_multi)
    if cfg.comm == "mesh_noc":
        # router carbon scales with each die's physical router count
        # (mx * my) instead of the flat per-die share
        pkg_cfp = pkg_cfp + cfg.router_area_frac * jnp.sum(
            mfg_pc * d.noc[1], axis=1)
    else:
        pkg_cfp = pkg_cfp + cfg.router_area_frac * mfg
    emb = (mfg + des + pkg_cfp) * embf
    eff_ci = ci + jnp.sum((profile - ci) * load, axis=-1)
    ope = energy * runs / 3.6e6 * eff_ci

    if not network:
        d2d_bits, macs_sum = jnp.sum(loads, axis=1), jnp.sum(macs, axis=1)
    return (latency, energy, area, dollar, emb, ope, l_cr, l_d2d, l_wr,
            e_compute_j, e_d2d_j, d2d_bits, macs_sum)


def _interposer_cost(area, cfg: _Cfg):
    import jax.numpy as jnp
    import math

    r = cfg.wafer_diameter_mm / 2.0
    dpw = (math.pi * r * r / area
           - math.pi * cfg.wafer_diameter_mm / jnp.sqrt(2.0 * area))
    dpw = jnp.maximum(1.0, jnp.trunc(dpw))
    y = _nb_yield(area, cfg.interposer_defect, cfg.yield_alpha)
    return cfg.interposer_wafer_cost / dpw / y


def _nb_yield(area, d0: float, alpha: float):
    return (1.0 + area * d0 / alpha) ** (-alpha)


def _eval_cost_jax(v, mins, medians, w, ci, price, embf, profile,
                   pprofile, tb, cfg: _Cfg, rt):
    """Fused metrics + Eq. 17 cost (METRIC_FIELDS column order) + the
    ``OBJECTIVE_AXES`` vector ``(latency_s, dollar, total_cfp)``.

    ``w`` is either a single ``[6]`` weight row or a per-row ``[P, 6]``
    matrix (the scalarization-sweep case: every chain scalarizes with
    its own direction inside the same program). ``ci``/``price``/
    ``embf``/``profile``/``pprofile``/``rt`` are the runtime
    region/workload knobs of :func:`_metrics_jax`."""
    import jax.numpy as jnp

    mets = _metrics_jax(v, tb, cfg, ci, price, embf, profile, pprofile,
                        rt)
    x = jnp.stack([mets[1], mets[2], mets[0], mets[3], mets[4], mets[5]],
                  axis=1)
    cost = ((x - mins[None, :]) / medians[None, :]
            * jnp.atleast_2d(w)).sum(axis=1)
    vec = jnp.stack([mets[0], mets[3], mets[4] + mets[5]], axis=1)
    return mets, cost, vec


# ---------------------------------------------------------------------------
# Vectorized hierarchical moves (device rendering of sa.propose)
# ---------------------------------------------------------------------------


def _validity_jax(v, tb, cfg: _Cfg):
    """jnp port of :meth:`DesignSpace.validity_mask`."""
    import jax.numpy as jnp

    C = cfg.C
    n = v[:, COL_N]
    style = v[:, COL_STYLE]
    p25, p3, stck = v[:, COL_PAIR25], v[:, COL_PAIR3], v[:, COL_STACK]
    ok = (n >= 1) & (n <= C)
    ok &= (style >= 0) & (style < 4)
    ok &= (v[:, COL_MEM] >= 0) & (v[:, COL_MEM] < cfg.M)
    ok &= (v[:, COL_ORDER] >= 0) & (v[:, COL_ORDER] <= 1)
    ok &= (v[:, COL_DATAFLOW] >= 0) & (v[:, COL_DATAFLOW] < 3)
    ok &= (v[:, COL_SPLITK] >= 0) & (v[:, COL_SPLITK] <= 1)
    chip = v[:, COL_CHIP:COL_CHIP + 3 * C].reshape(-1, C, 3)
    active = jnp.arange(C, dtype=jnp.int32)[None, :] < n[:, None]
    a, t, s = chip[:, :, 0], chip[:, :, 1], chip[:, :, 2]
    a_ok = (a >= 0) & (a < cfg.A)
    chip_ok = (a_ok & (t >= 0) & (t < cfg.T_nodes) & (s >= 0)
               & (s < tb["n_sram"][jnp.where(a_ok, a, 0)]))
    ok &= jnp.all(chip_ok | ~active, axis=1)
    if cfg.comm == "mesh_noc":
        nocv = v[:, cfg.noc_col:cfg.noc_col + 2 * C].reshape(-1, C, 2)
        mi, ei = nocv[:, :, 0], nocv[:, :, 1]
        noc_ok = ((mi >= 0) & (mi < cfg.n_mesh)
                  & (ei >= 0) & (ei < cfg.n_entry))
        ok &= jnp.all(noc_ok | ~active, axis=1)
    if cfg.schedule == "window":
        st_ = v[:, cfg.sched_col]
        sh_ = v[:, cfg.sched_col + 1]
        ok &= ((st_ >= 0) & (st_ < HOURS_PER_DAY)
               & (sh_ >= 0) & (sh_ < cfg.n_sched))
    pc = _popcount(stck, C)
    no3d, no25, nostk = p3 == -1, p25 == -1, stck == 0
    has25 = (p25 >= 0) & (p25 < cfg.n_pairs25)
    has3 = (p3 >= 0) & (p3 < cfg.n_pairs3)
    in_range = stck < jnp.left_shift(1, jnp.minimum(n, 30))
    ok &= jnp.where(style == S_2D, (n == 1) & no25 & no3d & nostk, True)
    ok &= jnp.where(style == S_25D, (n >= 2) & has25 & no3d & nostk, True)
    ok &= jnp.where(style == S_3D, (n >= 2) & has3 & no25 & nostk, True)
    ok &= jnp.where(style == S_HYBRID,
                    (n >= 3) & has25 & has3 & (pc >= 2) & (pc < n)
                    & in_range & (stck >= 0), True)
    return ok


def _propose_jax(key, v, tb, cfg: _Cfg, noc_on=None, sched_on=None):
    """One hierarchical move per encoded row, mirroring the level/branch
    distribution of :func:`repro.core.sa.propose` with ``jax.random``.

    Chiplet redraw-until-different uses two resamples instead of an
    unbounded loop (residual collision probability ~ (1/80)^3); rows whose
    candidate fails validity keep the incumbent (the batched rendering of
    the scalar retry loop).

    Under the mesh_noc comm model a fourth move level redraws one
    chiplet's (mesh dims, entry placement) pair, fed by a ``fold_in``
    side-stream so the base draw matrix — and with it every legacy
    move's randomness — is untouched. ``noc_on`` (0.0/1.0, traced
    scalar) widens the level draw to include it; ``None`` falls back to
    the static ``cfg.noc_live`` (frozen mesh spaces keep the exact
    3-level legacy distribution).

    Under the window schedule model one more level perturbs the design's
    (start_hour, shape_idx) schedule pair, fed by its own ``fold_in``
    side-stream (the temporal twin of the NoC level); ``sched_on``
    (0.0/1.0, traced scalar) gates it the same way, with ``None``
    falling back to the static ``cfg.sched_live`` — forced-neutral
    window spaces consume no extra base draws and replay the legacy
    level distribution exactly."""
    import jax
    import jax.numpy as jnp

    C = cfg.C
    P = v.shape[0]
    slot = jnp.arange(C, dtype=jnp.int32)
    mesh = cfg.comm == "mesh_noc"
    win = cfg.schedule == "window"
    # one threefry pass supplies every draw of the sweep: row i is the
    # i-th logical random stream (uniform ints come from floor(u * m))
    U = jax.random.uniform(key, (31 + C, P), dtype=jnp.float64)

    def uni(i):
        return U[i]

    def ri(i, maxv):
        return jnp.floor(U[i] * maxv).astype(jnp.int32)

    n = v[:, COL_N]
    style = v[:, COL_STYLE]
    mem = v[:, COL_MEM]
    order = v[:, COL_ORDER]
    df = v[:, COL_DATAFLOW]
    sk = v[:, COL_SPLITK]
    p25 = v[:, COL_PAIR25]
    p3 = v[:, COL_PAIR3]
    stck = v[:, COL_STACK]
    chip = v[:, COL_CHIP:COL_CHIP + 3 * C].reshape(P, C, 3)

    # -- application level: dataflow | split-K | order ----------------------
    which = ri(0, 3)
    cand_app = (
        v.at[:, COL_DATAFLOW].set(
            jnp.where(which == 0, (df + 1 + ri(1, 2)) % 3, df))
        .at[:, COL_SPLITK].set(jnp.where(which == 1, 1 - sk, sk))
        .at[:, COL_ORDER].set(jnp.where(which == 2, 1 - order, order)))

    # -- memory move --------------------------------------------------------
    cand_mem = v.at[:, COL_MEM].set((mem + 1 + ri(2, cfg.M - 1)) % cfg.M)

    # -- chiplet replacement ------------------------------------------------
    def draw_chiplet(ia, it, iu):
        a = ri(ia, cfg.A)
        t = ri(it, cfg.T_nodes)
        s = jnp.floor(uni(iu)
                      * tb["n_sram"][a].astype(jnp.float64)).astype(jnp.int32)
        return jnp.stack([a, t, s], axis=1)

    r_rep = jnp.floor(uni(3) * n.astype(jnp.float64)).astype(jnp.int32)
    old = jnp.take_along_axis(
        chip, jnp.broadcast_to(r_rep[:, None, None], (P, 1, 3)),
        axis=1)[:, 0]
    new = draw_chiplet(4, 5, 6)
    for ia, it, iu in ((7, 8, 9), (10, 11, 12)):
        new = jnp.where(jnp.all(new == old, axis=1)[:, None],
                        draw_chiplet(ia, it, iu), new)
    chip_rep = jnp.where(slot[None, :, None] == r_rep[:, None, None],
                         new[:, None, :], chip)
    cand_rep = v.at[:, COL_CHIP:COL_CHIP + 3 * C].set(
        chip_rep.reshape(P, -1).astype(jnp.int32))

    # -- chip-architecture: grow / shrink + dynamic HI-type repair ----------
    dlt = jnp.where(uni(13) < 0.5, -1, 1).astype(jnp.int32)
    n2a = jnp.clip(n + dlt, 1, C)
    n2 = jnp.where(n2a == n, jnp.clip(n - dlt, 1, C), n2a)
    grow = n2 > n
    r_del = jnp.floor(uni(14) * n.astype(jnp.float64)).astype(jnp.int32)
    idx_shift = jnp.minimum(
        slot[None, :] + (slot[None, :] >= r_del[:, None]), C - 1)
    chip_shr = jnp.take_along_axis(
        chip, jnp.broadcast_to(idx_shift[:, :, None], (P, C, 3)), axis=1)
    chip_grow = jnp.where(slot[None, :, None] == n[:, None, None],
                          draw_chiplet(15, 16, 17)[:, None, :], chip)
    chip_gs = jnp.where(grow[:, None, None], chip_grow, chip_shr)
    chip_gs = jnp.where((slot[None, :] < n2[:, None])[:, :, None],
                        chip_gs, -1)
    style2 = jnp.where(
        n2 == 1, S_2D,
        jnp.where((n2 == 2) & (style == S_HYBRID), S_3D,
                  jnp.where((n2 >= 2) & (style == S_2D), S_25D, style)))
    need25 = (style2 == S_25D) | (style2 == S_HYBRID)
    need3 = (style2 == S_3D) | (style2 == S_HYBRID)
    pkg_d = ri(18, cfg.n_pkg25)
    pr_d = jnp.floor(
        uni(19) * tb["p25_cnt"][pkg_d].astype(jnp.float64)).astype(jnp.int32)
    pair25_draw = tb["p25_flat"][tb["p25_off"][pkg_d] + pr_d]
    pair3_draw = tb["pair3_of_pkg"][ri(20, cfg.n_pkg3)]
    p25_2 = jnp.where(need25, jnp.where(p25 < 0, pair25_draw, p25), -1)
    p3_2 = jnp.where(need3, jnp.where(p3 < 0, pair3_draw, p3), -1)
    keep = stck & (jnp.left_shift(1, n2) - 1)
    pc = _popcount(keep, C)
    bad = (pc < 2) | (pc >= n2)
    size = jnp.where(
        n2 > 2,
        2 + jnp.floor(uni(21)
                      * (n2 - 2).astype(jnp.float64)).astype(jnp.int32), 2)
    scores = jnp.where(slot[None, :] < n2[:, None],
                       U[31:31 + C].T, jnp.inf)
    rank = jnp.argsort(jnp.argsort(scores, axis=1), axis=1)
    mask_new = jnp.sum(
        (rank < size[:, None]).astype(jnp.int32) << slot[None, :], axis=1)
    stack2 = jnp.where(style2 == S_HYBRID,
                       jnp.where(bad, mask_new, keep), 0)
    head = jnp.stack([n2, style2, mem, order, df, sk, p25_2, p3_2, stack2],
                     axis=1)
    if mesh:
        # mirror the chiplet-slot shift/append on the NoC columns: grown
        # slots seed the neutral (1x1, corner) = (0, 0) pair — exactly
        # sa._move_chip_arch's NOC_NEUTRAL append
        noc = v[:, cfg.noc_col:cfg.noc_col + 2 * C].reshape(P, C, 2)
        noc_shr = jnp.take_along_axis(
            noc, jnp.broadcast_to(idx_shift[:, :, None], (P, C, 2)),
            axis=1)
        noc_grow = jnp.where(slot[None, :, None] == n[:, None, None],
                             0, noc)
        noc_gs = jnp.where(grow[:, None, None], noc_grow, noc_shr)
        noc_gs = jnp.where((slot[None, :] < n2[:, None])[:, :, None],
                           noc_gs, -1)
        gs_parts = [head, chip_gs.reshape(P, -1), noc_gs.reshape(P, -1)]
    else:
        gs_parts = [head, chip_gs.reshape(P, -1)]
    if win:
        # whole-design schedule columns ride through grow/shrink intact
        gs_parts.append(v[:, cfg.sched_col:cfg.sched_col + 2])
    cand_gs = jnp.concatenate(gs_parts, axis=1).astype(jnp.int32)

    # -- package level ------------------------------------------------------
    cur_pkg25 = tb["pair25_pkg"][jnp.maximum(p25, 0)]
    new_pkg25 = (cur_pkg25 + 1 + ri(23, cfg.n_pkg25 - 1)) % cfg.n_pkg25
    kept = tb["pair25_by_pkg_proto"][new_pkg25,
                                     tb["pair25_proto"][jnp.maximum(p25, 0)]]
    cnt_np = tb["p25_cnt"][new_pkg25]
    rnd_pair = tb["p25_flat"][
        tb["p25_off"][new_pkg25]
        + jnp.floor(uni(24) * cnt_np.astype(jnp.float64)).astype(jnp.int32)]
    pkg25_res = jnp.where(kept >= 0, kept, rnd_pair)
    cnt_cur = tb["p25_cnt"][cur_pkg25]
    others = cnt_cur - 1
    loc = tb["pair25_local"][jnp.maximum(p25, 0)]
    j_o = jnp.floor(
        uni(25) * jnp.maximum(others, 1).astype(jnp.float64)
    ).astype(jnp.int32)
    proto25_res = tb["p25_flat"][
        tb["p25_off"][cur_pkg25]
        + (loc + 1 + j_o) % jnp.maximum(cnt_cur, 1)]
    cur_pkg3 = tb["pair3_pkg"][jnp.maximum(p3, 0)]
    pkg3_res = tb["pair3_of_pkg"][
        (cur_pkg3 + 1 + ri(26, cfg.n_pkg3 - 1)) % cfg.n_pkg3]
    n_opts = jnp.where(style == S_25D, 2,
                       jnp.where(style == S_HYBRID, 3, 1))
    pick = jnp.floor(uni(27) * n_opts.astype(jnp.float64)).astype(jnp.int32)
    has_plane = (style == S_25D) | (style == S_HYBRID)
    sel_pkg25 = has_plane & (pick == 0)
    sel_proto25 = has_plane & (pick == 1) & (others > 0)
    sel_pkg3 = (style == S_3D) | ((style == S_HYBRID) & (pick == 2))
    cand_pkg = (
        v.at[:, COL_PAIR25].set(
            jnp.where(sel_pkg25, pkg25_res,
                      jnp.where(sel_proto25, proto25_res, p25)))
        .at[:, COL_PAIR3].set(jnp.where(sel_pkg3, pkg3_res, p3)))

    # -- NoC level: redraw one chiplet's (mesh dims, entry) pair ------------
    if mesh:
        # side-stream so the base U matrix (= the legacy draw stream) is
        # byte-identical whether or not NoC moves are enabled
        Un = jax.random.uniform(jax.random.fold_in(key, 7), (5, P),
                                dtype=jnp.float64)
        r_noc = jnp.floor(Un[0] * n.astype(jnp.float64)).astype(jnp.int32)

        def draw_noc(im, ie):
            m_ = jnp.floor(Un[im] * cfg.n_mesh).astype(jnp.int32)
            e_ = jnp.floor(Un[ie] * cfg.n_entry).astype(jnp.int32)
            return jnp.stack([m_, e_], axis=1)

        old_noc = jnp.take_along_axis(
            noc, jnp.broadcast_to(r_noc[:, None, None], (P, 1, 2)),
            axis=1)[:, 0]
        new_noc = draw_noc(1, 2)
        new_noc = jnp.where(jnp.all(new_noc == old_noc, axis=1)[:, None],
                            draw_noc(3, 4), new_noc)
        noc_mv = jnp.where(slot[None, :, None] == r_noc[:, None, None],
                           new_noc[:, None, :], noc)
        cand_noc = v.at[:, cfg.noc_col:cfg.noc_col + 2 * C].set(
            noc_mv.reshape(P, -1).astype(jnp.int32))

    # -- schedule level: nudge start hour or redraw the window shape --------
    if win:
        # own fold_in side-stream (8), mirroring the NoC stream (7): the
        # base U matrix and the NoC draws stay byte-identical whether or
        # not schedule moves exist, so forced-neutral window spaces
        # replay legacy/mesh trajectories bit-for-bit
        Us = jax.random.uniform(jax.random.fold_in(key, 8), (3, P),
                                dtype=jnp.float64)
        sc = cfg.sched_col
        s_start = v[:, sc]
        s_shape = v[:, sc + 1]
        start2 = (s_start + 1 + jnp.floor(
            Us[1] * (HOURS_PER_DAY - 1)).astype(jnp.int32)) % HOURS_PER_DAY
        shape2 = (s_shape + 1 + jnp.floor(
            Us[2] * (cfg.n_sched - 1)).astype(jnp.int32)) % cfg.n_sched
        s_coin = Us[0] < 0.5  # start-hour nudge vs shape redraw
        cand_sched = (
            v.at[:, sc].set(jnp.where(s_coin, start2, s_start))
            .at[:, sc + 1].set(jnp.where(s_coin, s_shape, shape2)))

    # -- hierarchical branch selection + validity gate ----------------------
    is_app = uni(28) < P_APPLICATION
    coin = uni(30)
    if mesh or win:
        # noc_on/sched_on in {0.0, 1.0} widen the uniform level draw
        # from 3 to up-to-5 options as runtime data: floor(u * 3.0) ==
        # the legacy ri(29, 3) exactly, so frozen-axis cells replay the
        # 3-level distribution
        noc_on_f = ((noc_on if noc_on is not None
                     else (1.0 if cfg.noc_live else 0.0))
                    if mesh else None)
        sched_on_f = ((sched_on if sched_on is not None
                       else (1.0 if cfg.sched_live else 0.0))
                      if win else None)
        n_levels = 3.0
        if mesh:
            n_levels = n_levels + noc_on_f
        if win:
            n_levels = n_levels + sched_on_f
        level = jnp.floor(U[29] * n_levels).astype(jnp.int32)
        if mesh and win:
            # runtime mapping: the schedule level sits after the NoC
            # level iff NoC moves are on for this row/cell
            noc_i = jnp.floor(noc_on_f).astype(jnp.int32)
            is_noc = (level == 3) & (noc_i == 1)
            lower = jnp.where(
                (level == 1)[:, None], cand_rep,
                jnp.where((level == 2)[:, None], cand_pkg,
                          jnp.where(is_noc[:, None], cand_noc,
                                    cand_sched)))
        elif mesh:
            lower = jnp.where(
                (level == 1)[:, None], cand_rep,
                jnp.where((level == 2)[:, None], cand_pkg, cand_noc))
        else:
            lower = jnp.where(
                (level == 1)[:, None], cand_rep,
                jnp.where((level == 2)[:, None], cand_pkg, cand_sched))
    else:
        level = ri(29, 3)
        lower = jnp.where((level == 1)[:, None], cand_rep, cand_pkg)
    cand = jnp.where(
        is_app[:, None], cand_app,
        jnp.where((level == 0)[:, None],
                  jnp.where((coin < 0.5)[:, None], cand_gs, cand_mem),
                  lower))
    ok = _validity_jax(cand, tb, cfg)
    return jnp.where(ok[:, None], cand, v).astype(jnp.int32)


def _exchange_fn(inv_t, us, pair_ok):
    """Adjacent-pair replica-exchange step for ``lax.fori_loop`` (each
    cell's exchange in the engine's tempering step).

    ``d >= 0`` short-circuits in the host loop, so only exp of
    non-positive ``d`` is ever compared; ``pair_ok`` gates swaps across
    independent ladders (scalarization-direction / cell boundaries)."""
    import jax.numpy as jnp

    def ex_body(j, vc):
        vv, cc = vc
        c_i, c_j = cc[j], cc[j + 1]
        d = (inv_t[j] - inv_t[j + 1]) * (c_i - c_j)
        sw = pair_ok[j] & (
            (d >= 0) | (us[j] < jnp.exp(jnp.minimum(d, 0.0))))
        cc = cc.at[j].set(jnp.where(sw, c_j, c_i)) \
               .at[j + 1].set(jnp.where(sw, c_i, c_j))
        v_i, v_j = vv[j], vv[j + 1]
        vv = vv.at[j].set(jnp.where(sw, v_j, v_i)) \
               .at[j + 1].set(jnp.where(sw, v_i, v_j))
        return (vv, cc)

    return ex_body


def _key_to_np(key) -> np.ndarray:
    """Raw PRNG key data as a host array (typed-key safe) — the carry's
    RNG stream position is checkpointed as plain uint32 words."""
    import jax

    try:
        return np.asarray(key)
    except TypeError:
        return np.asarray(jax.random.key_data(key))


def _key_from_np(data: np.ndarray, like_key):
    """Rebuild a key usable by ``jax.random`` from saved raw words,
    matching the flavor (raw/typed) of ``like_key``."""
    import jax
    import jax.numpy as jnp

    try:
        np.asarray(like_key)
        return jnp.asarray(data)
    except TypeError:
        return jax.random.wrap_key_data(jnp.asarray(data))


# ---------------------------------------------------------------------------
# Compile accounting + shared table/cfg builders
# ---------------------------------------------------------------------------

# program-family name -> number of traces. A jit-wrapped Python function
# body runs exactly once per fresh XLA compile (shape/dtype/sharding
# cache misses) and never on cache hits, so counting calls from inside
# the wrapped function is a faithful compile counter — the hook the
# one-compile regression tests and benchmarks read via trace_count().
_TRACE_COUNTS: Dict[str, int] = {}


def _count_trace(name: str) -> None:
    _TRACE_COUNTS[name] = _TRACE_COUNTS.get(name, 0) + 1


def trace_count(name: str) -> int:
    """Traces (= XLA compiles) of the named fused-program family in this
    process: ``"scenario_pt"`` (the tempering scan — one compile per
    distinct shape and segment length), ``"scenario_init"`` (its
    seed-population eval) and ``"scenario_eval"`` (the one-shot eval).
    A single-workload :class:`DeviceEvaluator` runs these programs too,
    as a one-cell grid."""
    return _TRACE_COUNTS.get(name, 0)


def _base_cfg(sp: DesignSpace, db: TechDB, T0: int, T1: int,
              pallas_layout: Optional[Tuple[int, int, int]]) -> _Cfg:
    """The static trace-time constants shared by every fused program over
    one (TechDB, DesignSpace) — the tile buckets vary per engine."""
    # the exact tile assignment multiplies a 53-bit share by the tile
    # count in int64
    assert max(T0, T1) < 1024, f"tile counts {T0}/{T1} exceed 1023"
    return _Cfg(
        C=sp.max_chiplets, W=sp.width, A=len(sp.arrays),
        T_nodes=len(sp.nodes), S=int(sp.n_sram.max()),
        M=len(sp.memories), n_pairs25=len(sp.pairs_25d),
        n_pairs3=len(sp.pairs_3d),
        n_pkg25=len(sp.pkg25_pairs), n_pkg3=len(sp.pkg3_pairs),
        L=sp.max_chiplets * (sp.max_chiplets - 1) // 2
        + sp.max_chiplets - 1,
        T0=T0, T1=T1,
        acost=db.assembly_cost,
        substrate_cost_mm2=db.substrate_cost_mm2,
        substrate_cfp_mm2=db.substrate_cfp_mm2,
        interposer_cpa=db.interposer_cpa,
        interposer_defect=db.interposer_defect,
        interposer_wafer_cost=db.interposer_wafer_cost,
        yield_alpha=db.yield_alpha,
        wafer_diameter_mm=db.wafer_diameter_mm,
        lifetime_years=db.lifetime_years,
        use_fraction=db.use_fraction,
        duty_runs_per_s=db.duty_runs_per_s,
        router_area_frac=db.router_area_frac,
        comm=sp.comm,
        noc_col=sp.noc_col,
        n_mesh=len(comm_mod.MESH_DIMS),
        n_entry=len(comm_mod.ENTRY_PLACEMENTS),
        noc_hop_latency_s=db.noc_hop_latency_s,
        noc_energy_pj_bit=db.noc_energy_pj_bit,
        hop_uniform=db.uniform_hop_latency(),
        noc_live=sp.noc_live,
        schedule=sp.schedule,
        sched_col=sp.sched_col if sp.schedule == "window" else -1,
        n_sched=sched_mod.n_schedule_shapes(),
        sched_live=sp.sched_live,
        pallas_layout=pallas_layout,
    )


def _shared_tables(host, sp: DesignSpace) -> dict:
    """Workload-independent jnp tables (chiplet physicals, node rates,
    memory energies, package info, move tables) — identical for every
    workload and every deployment region over one (db, space). Call
    under ``search_numerics``."""
    import jax.numpy as jnp

    mt = sp.move_tables()
    noc_h, noc_r = comm_mod.noc_tables()
    return dict(
        # per-chiplet physicals / node rates / memory energies are
        # stacked along a trailing axis: one gather per site
        chiplet=jnp.asarray(np.stack(
            [host.t_area, host.t_static, host.t_cost, host.t_mfg],
            axis=-1)),
        node=jnp.asarray(np.stack(
            [host.t_freq, host.t_sram_e, host.t_mac_e, host.t_des],
            axis=-1)),
        mem3=jnp.asarray(np.stack(
            [host.m_rd, host.m_wr, host.m_cost], axis=-1)),
        t_power_i=jnp.asarray(_exact_ints(host.t_power)),
        t_area_i=jnp.asarray(_exact_ints(host.t_area)),
        m_bw=jnp.asarray(host.m_bw),
        p25=jnp.asarray([i[:7] for i in host.p25_info]),
        p25_interp=jnp.asarray([i[7] for i in host.p25_info]),
        p3=jnp.asarray([i[:7] for i in host.p3_info]),
        # per-pair hop latencies (the heterogeneous-latency hop split)
        # and the closed-form mesh-NoC lookup tables — tiny constants,
        # carried unconditionally; legacy programs never gather them
        p25_hl=jnp.asarray(host.p25_hl),
        p3_hl=jnp.asarray(host.p3_hl),
        noc_hops=jnp.asarray(noc_h),
        noc_routers=jnp.asarray(noc_r),
        # duty-weight shape table (row 0 = db.load_profile verbatim):
        # fixed-schedule programs gather row 0, window programs gather
        # the encoded per-design (start, shape) columns against it
        sched_tab=jnp.asarray(sched_mod.schedule_tables(host.db)),
        n_sram=jnp.asarray(sp.n_sram),
        **{k: jnp.asarray(a) for k, a in mt.items()},
    )


def _pallas_table(hosts, tb0: int, tb1: int):
    """The packed prefix-gather kernel table for a workload stack:
    each workload's per-metric ``[5, A*S*3, T+1]`` prefix tables,
    edge-padded to the shared tile buckets ``tb0``/``tb1`` and stacked
    along the row axis, so the kernel row is ``((wi*A + a)*S + s)*3 +
    d``; clip bounds stay at the true (unpadded) per-cell tile totals.
    Returns ``(tables dict, layout)``. Call under ``search_numerics``."""
    import jax.numpy as jnp

    from repro.kernels.prefix_gather import pack_tables

    stacks = []
    for sk, bucket in ((0, tb0), (1, tb1)):
        mats = []
        for h in hosts:
            pref = np.stack(
                [h.tiles[sk]["pref"][f] for f in _SIM_METRICS])
            pref = _pad_tiles(pref, bucket, axis=-1)
            mats.append(pref.reshape(pref.shape[0], -1, bucket + 1))
        stacks.append(np.concatenate(mats, axis=1))
    table, layout = pack_tables(*stacks)
    return dict(pallas_table=jnp.asarray(table)), layout


def _pallas_blocks(hosts, tb0: int, tb1: int):
    """The layer-blocked kernel table of a network engine: each GEMM's
    prefix tables packed alone (:func:`_pallas_table` of one host at the
    shared buckets), stacked ``[G, rows, 128]`` by GEMM id, so the
    kernel copies one GEMM's block into VMEM at a time (the whole stack
    of a network can outgrow it). Call under ``search_numerics``."""
    import jax.numpy as jnp

    packed = [_pallas_table([h], tb0, tb1) for h in hosts]
    blocks = jnp.stack([t["pallas_table"] for t, _ in packed])
    return dict(pallas_blocks=blocks), packed[0][1]


# ---------------------------------------------------------------------------
# Results + the kernel switch
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class DevicePTResult:
    """Output of the fused parallel-tempering scan."""

    best_enc: np.ndarray          # encoded best row
    best_cost: float
    history: List[float]          # [initial best] + coldest-chain per sweep
    evaluations: int
    final_enc: np.ndarray         # [n_chains, width] final population
    final_costs: np.ndarray
    trace: Optional[Dict[str, np.ndarray]] = None
    # every evaluated design + its OBJECTIVE_AXES vector (seed population
    # first): enc [1 + sweeps, n, width], vec [1 + sweeps, n, 3] — the
    # Pareto archive's input
    samples: Optional[Dict[str, np.ndarray]] = None


_PALLAS_ENV_WARNED = False


def _resolve_pallas(use_pallas: Optional[bool]) -> bool:
    """Resolve the kernel fast-path switch.

    An explicit ``use_pallas`` argument wins. Otherwise the
    ``REPRO_PATHFINDER_PALLAS`` environment variable decides: ``1`` (or
    ``true``/``yes``) forces the Pallas path, ``0`` (``false``/``no``)
    forces plain jnp, and ``auto`` (the default) enables the kernel on
    TPU backends only. Any other value warns once per process and falls
    back to ``auto``.
    """
    global _PALLAS_ENV_WARNED
    if use_pallas is not None:
        return use_pallas
    env = os.environ.get("REPRO_PATHFINDER_PALLAS", "auto").lower()
    if env in ("1", "true", "yes"):
        return True
    if env in ("0", "false", "no"):
        return False
    if env != "auto" and not _PALLAS_ENV_WARNED:
        _PALLAS_ENV_WARNED = True
        warnings.warn(
            f"unrecognized REPRO_PATHFINDER_PALLAS value {env!r}; accepted "
            "values are 0/1/auto (aliases: false/no and true/yes) — "
            "falling back to auto (Pallas on TPU backends only)",
            RuntimeWarning, stacklevel=2)
    import jax

    return jax.default_backend() == "tpu"


# ---------------------------------------------------------------------------
# The tempering engine: one compile for a region x workload grid
# ---------------------------------------------------------------------------


def _tile_bucket(t: int) -> int:
    """Power-of-two tile-count bucket (>= 64): workload sets whose max
    tile counts land in the same bucket produce identically shaped
    stacked programs (the scenario twin of the population `_pad`)."""
    return max(64, 1 << (int(t) - 1).bit_length())


def _pad_tiles(a: np.ndarray, bucket: int, axis: int) -> np.ndarray:
    """Edge-pad a prefix table's T+1 axis to bucket+1 slots. Tile-range
    gathers never index past the true per-workload total (starts/ends
    sum to it), and edge replication makes any clipped tail slot
    difference to exactly zero anyway."""
    cur = a.shape[axis]
    if cur == bucket + 1:
        return a
    pad = [(0, 0)] * a.ndim
    pad[axis] = (0, bucket + 1 - cur)
    return np.pad(a, pad, mode="edge")


@dataclasses.dataclass
class ScenarioPTResult:
    """Per-cell outputs of the stacked scenario tempering scan (leading
    axis = scenario cell everywhere)."""

    best_enc: np.ndarray          # [S, width]
    best_cost: np.ndarray         # [S]
    history: np.ndarray           # [S, 1 + sweeps] coldest-chain costs
    evaluations: int              # total across all cells
    final_enc: np.ndarray         # [S, n, width]
    final_costs: np.ndarray       # [S, n]
    # every evaluated design + its OBJECTIVE_AXES vector, seed population
    # first: enc [1 + sweeps, S, n, width], vec [1 + sweeps, S, n, 3]
    samples: Optional[Dict[str, np.ndarray]] = None


class ScenarioEngine:
    """The tempering engine: one fused program for a whole scenario grid.

    The per-cell knobs of a (workload x deployment region) sweep are all
    runtime data of the fused evaluate+cost program: the grid carbon
    intensity (a scalar multiplier of operational CFP), the per-cell
    normalizer rows and Eq. 17 weight rows, and the per-workload tile
    totals / DRAM write-back bits (their prefix tables ride in a stacked,
    tile-bucket-padded lookup indexed by a per-cell workload id). One
    ``lax.scan`` over a ``vmap``-ped per-cell tempering step therefore
    sweeps the full grid in a *single* XLA compile. A single workload is
    the one-cell grid: :class:`DeviceEvaluator` is that view, and this
    class holds the only init, segment and eval programs.

    Per-cell RNG: the init program folds the cell index into the base
    key (``jax.random.fold_in``), so every cell gets a distinct,
    deterministic proposal stream that depends only on (seed, cell
    index) — not on the grid's size or order. The single-workload view
    gives its one cell the caller's key itself.

    The scenario axis can be sharded across local devices with a mesh
    from :func:`repro.distributed.sharding.scenario_mesh` (pass it as
    ``mesh=``); inputs are placed with their leading axis split over the
    mesh's data axes and XLA partitions the scan accordingly.

    The grid scan is *segmented* (``segment=`` sweeps per host-driven
    chunk, bit-invisible) so a multi-thousand-cell sweep checkpoints at
    boundaries and resumes bit-identically
    (:mod:`repro.pathfinding.resume`).

    Kernel fast path: the engine takes ``use_pallas`` (default: the
    ``REPRO_PATHFINDER_PALLAS``
    resolution, see :func:`_resolve_pallas`). When enabled, the gather +
    split-select stage of every cell's tempering step runs through the
    :func:`repro.kernels.prefix_gather.prefix_select_gather` kernel on
    the workload-stacked packed table — its ``custom_vmap`` rule folds
    the scenario-cell axis into the kernel's system axis, so the whole
    ``[S, n]`` population is one launch per sweep (one per device, under
    ``shard_map``, when the scenario axis is sharded). The jnp path
    stays the bit-pinned reference; the same
    ``scenario_pt``/``scenario_init`` programs are emitted either way,
    so segmentation, checkpoints and serving replay are unaffected.

    Networks (:class:`repro.core.network.Network`): when any workload is
    a network, the tables stack by distinct GEMM instead of by workload
    and each workload gets a runtime layer table (GEMM ids and counts,
    padded with count 0 to the longest network; a plain GEMM is a
    one-layer network). Every design is then evaluated on ``layers``
    layers: the per-GEMM stage over a ``vmap``-ped layer axis, the
    design stages once (:func:`_metrics_jax`); the kernel reads a
    table blocked by GEMM (:func:`_pallas_blocks`). An engine of single
    GEMMs has ``layers == 1`` and no layer axis."""

    def __init__(self, workloads: Sequence[Workload],
                 db: TechDB = DEFAULT_DB,
                 tile_sizes: Tuple[int, int, int] = DEFAULT_TILE,
                 space: Optional[DesignSpace] = None,
                 use_pallas: Optional[bool] = None):
        import jax.numpy as jnp
        from repro.jaxenv import search_numerics

        self.workloads = tuple(workloads)
        if not self.workloads:
            raise ValueError("ScenarioEngine needs >= 1 workload")
        self.db, self.tile_sizes = db, tile_sizes
        # a network engine stacks its tables by distinct GEMM and gives
        # each workload a layer table of GEMM ids and counts; an engine
        # of single GEMMs stacks by workload and has no layer axis
        networks = None
        gemms = self.workloads
        if any(isinstance(w, Network) for w in self.workloads):
            networks = [as_network(w) for w in self.workloads]
            gemms = tuple(dict.fromkeys(
                g for net in networks for g in net.gemms))
        self.layers = (1 if networks is None
                       else max(len(net.layers) for net in networks))
        hosts = [get_evaluator(wl, db, tile_sizes, space) for wl in gemms]
        self.hosts = hosts
        self.space = hosts[0].space
        sp = self.space
        t0s = [h.tiles[0]["T"] for h in hosts]
        t1s = [h.tiles[1]["T"] for h in hosts]
        tb0, tb1 = _tile_bucket(max(t0s)), _tile_bucket(max(t1s))
        layout = None
        with search_numerics(), span("repro.network.tables",
                                     layers=self.layers,
                                     gemms=len(gemms)) as sp_tables:
            tb = _shared_tables(hosts[0], sp)
            if _resolve_pallas(use_pallas):
                pal, layout = (_pallas_table(hosts, tb0, tb1)
                               if networks is None
                               else _pallas_blocks(hosts, tb0, tb1))
                tb.update(pal)
            tb.update(
                pref0w=jnp.asarray(np.stack([
                    _pad_tiles(np.stack(
                        [h.tiles[0]["pref"][f] for f in _SIM_METRICS],
                        axis=-1), tb0, axis=-2) for h in hosts])),
                pref1w=jnp.asarray(np.stack([
                    _pad_tiles(np.stack(
                        [h.tiles[1]["pref"][f] for f in _SIM_METRICS],
                        axis=-1), tb1, axis=-2) for h in hosts])),
                mn0w=jnp.asarray(np.stack(
                    [_pad_tiles(h.tiles[0]["mn_pref"], tb0, axis=0)
                     for h in hosts])),
                mn1w=jnp.asarray(np.stack(
                    [_pad_tiles(h.tiles[1]["mn_pref"], tb1, axis=0)
                     for h in hosts])),
                t0w=jnp.asarray(np.asarray(t0s, dtype=np.int32)),
                t1w=jnp.asarray(np.asarray(t1s, dtype=np.int32)),
                wrw=jnp.asarray(np.asarray(
                    [float(wl.M * wl.N * OPERAND_BYTES * 8)
                     for wl in gemms])),
            )
            if networks is not None:
                gid = {g: i for i, g in enumerate(gemms)}
                lg = np.zeros((len(networks), self.layers), np.int32)
                lc = np.zeros((len(networks), self.layers), np.float64)
                for w, net in enumerate(networks):
                    for l, (g, c) in enumerate(net.layers):
                        lg[w, l], lc[w, l] = gid[g], c
                tb.update(layer_gemm=jnp.asarray(lg),
                          layer_count=jnp.asarray(lc))
            sp_tables.set_metadata(bytes=nbytes(
                tb[k] for k in ("pref0w", "pref1w", "mn0w", "mn1w",
                                "pallas_table", "pallas_blocks",
                                "layer_gemm", "layer_count") if k in tb))
        self.cfg = _base_cfg(sp, db, T0=tb0, T1=tb1, pallas_layout=layout)
        self.tables = tb
        self._fn_cache: Dict[tuple, object] = {}

    # -- per-cell table/runtime slices (wi is a traced scalar) -------------

    def _cell_tables(self, wi):
        tb = self.tables
        if "layer_gemm" in tb:
            return tb, dict(gid=tb["layer_gemm"][wi],
                            cnt=tb["layer_count"][wi])
        tbc = dict(tb, pref0=tb["pref0w"][wi], pref1=tb["pref1w"][wi],
                   mn0=tb["mn0w"][wi], mn1=tb["mn1w"][wi])
        rt = dict(T0=tb["t0w"][wi], T1=tb["t1w"][wi],
                  wr_bits=tb["wrw"][wi], wi=wi)
        return tbc, rt

    # -- one cell's fused evaluate + cost (vmapped over the cells) ---------

    def _eval_cell(self, v_s, mins_s, med_s, w_s, ci_s, price_s, embf_s,
                   profile_s, pprofile_s, wi, metrics: bool = False):
        tbc, rt = self._cell_tables(wi)
        mets, cost, vec = _eval_cost_jax(v_s, mins_s, med_s, w_s, ci_s,
                                         price_s, embf_s, profile_s,
                                         pprofile_s, tbc, self.cfg, rt)
        return (mets, cost, vec) if metrics else (cost, vec)

    # -- one-shot stacked evaluation (normalizer fits, finalization) -------

    def _eval_fn(self, S: int, m: int, metrics: bool = False):
        key_t = ("eval", S, m, metrics)
        fn = self._fn_cache.get(key_t)
        if fn is not None:
            return fn
        import jax

        def run(v, mins, med, w, ci, price, embf, profile, pprofile,
                widx):
            _count_trace("scenario_eval")
            return jax.vmap(functools.partial(self._eval_cell,
                                              metrics=metrics))(
                v, mins, med, w, ci, price, embf, profile, pprofile, widx)

        fn = jax.jit(run)
        self._fn_cache[key_t] = fn
        return fn

    @staticmethod
    def _region_cols(S: int, ci: np.ndarray, price=None, embf=None,
                     profile=None, pprofile=None
                     ) -> Tuple[np.ndarray, np.ndarray, np.ndarray,
                                np.ndarray]:
        """Normalize/synthesize the per-cell region columns: ``price``
        [S] (default zeros), ``embf`` [S] (default ones), ``profile``
        [S, 24] (default flat-at-ci rows, whose in-program correction
        is exactly +0.0) and ``pprofile`` [S, 24] (default
        flat-at-price rows, correction +0.0 too). Always materialized
        so the jitted programs have ONE signature — legacy scalar-CI
        callers and full six-axis callers share the same compile. A
        one-region caller passes its TechDB's price, embodied factor and
        (possibly ``None``) grid and price profiles."""
        ci = np.asarray(ci, np.float64).reshape(S)
        price = (np.zeros(S, np.float64) if price is None
                 else np.asarray(price, np.float64).reshape(S))
        embf = (np.ones(S, np.float64) if embf is None
                else np.asarray(embf, np.float64).reshape(S))
        profile = (np.repeat(ci[:, None], HOURS_PER_DAY, axis=1)
                   if profile is None
                   else np.asarray(profile, np.float64).reshape(
                       S, HOURS_PER_DAY))
        pprofile = (np.repeat(price[:, None], HOURS_PER_DAY, axis=1)
                    if pprofile is None
                    else np.asarray(pprofile, np.float64).reshape(
                        S, HOURS_PER_DAY))
        return price, embf, profile, pprofile

    def evaluate_cost(self, encoded: np.ndarray, mins: np.ndarray,
                      medians: np.ndarray, weights: np.ndarray,
                      ci: np.ndarray, widx: np.ndarray,
                      price: Optional[np.ndarray] = None,
                      embf: Optional[np.ndarray] = None,
                      profile: Optional[np.ndarray] = None,
                      pprofile: Optional[np.ndarray] = None
                      ) -> Tuple[np.ndarray, np.ndarray]:
        """Fused cost + objective vectors for a stacked ``[S, m, width]``
        population (per-cell ``[S, 6]`` normalizer rows / weight rows,
        ``[S]`` carbon intensities and workload ids, plus the optional
        regional axes ``price`` [S], ``embf`` [S], ``profile`` [S, 24]
        and ``pprofile`` [S, 24] — omitted axes synthesize their
        neutral columns).
        Returns ``(cost [S, m], vec [S, m, 3])``; the row axis is
        padded to a power-of-two bucket so repeated calls share one
        program."""
        return self._evaluate(encoded, mins, medians, weights, ci, widx,
                              price, embf, profile, pprofile)[1:]

    def _evaluate(self, encoded, mins, medians, weights, ci, widx,
                  price=None, embf=None, profile=None, pprofile=None,
                  metrics: bool = False):
        """:meth:`evaluate_cost` after the 13 metric arrays (``[S, m]``
        each, :class:`MetricsBatch` order; ``None`` unless ``metrics``)."""
        import jax.numpy as jnp
        from repro.jaxenv import search_numerics

        with search_numerics():
            v = np.asarray(encoded, dtype=np.int32)
            S, m, _ = v.shape
            mb = max(64, 1 << (m - 1).bit_length())
            if mb != m:
                v = np.concatenate(
                    [v, np.repeat(v[:, :1], mb - m, axis=1)], axis=1)
            ci_a = np.asarray(ci, np.float64).reshape(S)
            price_a, embf_a, profile_a, pprofile_a = self._region_cols(
                S, ci_a, price, embf, profile, pprofile)
            fn = self._eval_fn(S, mb, metrics)
            out = fn(
                jnp.asarray(v),
                jnp.asarray(np.asarray(mins, np.float64).reshape(S, 6)),
                jnp.asarray(np.asarray(medians, np.float64).reshape(S, 6)),
                jnp.asarray(np.asarray(weights, np.float64).reshape(S, 6)),
                jnp.asarray(ci_a), jnp.asarray(price_a),
                jnp.asarray(embf_a), jnp.asarray(profile_a),
                jnp.asarray(pprofile_a),
                jnp.asarray(np.asarray(widx, np.int32).reshape(S)))
            mets = (tuple(np.asarray(x)[:, :m] for x in out[0])
                    if metrics else None)
            return (mets, np.asarray(out[-2])[:, :m],
                    np.asarray(out[-1])[:, :m])

    def _propose(self, encoded: np.ndarray, seed: int = 0) -> np.ndarray:
        """One vectorized hierarchical move per encoded row (see
        :func:`_propose_jax`: the space's own move levels; invalid
        candidates keep the incumbent row)."""
        import jax
        import jax.numpy as jnp
        from repro.jaxenv import search_numerics

        fn = self._fn_cache.get("propose")
        if fn is None:
            fn = self._fn_cache["propose"] = jax.jit(
                lambda key, v: _propose_jax(key, v, self.tables, self.cfg))
        with search_numerics():
            v = np.atleast_2d(np.asarray(encoded, dtype=np.int32))
            return np.asarray(fn(jax.random.PRNGKey(seed), jnp.asarray(v)))

    # -- the stacked tempering scan ----------------------------------------
    #
    # A host loop advances the scan in fixed-size chunks with the carry
    # (per-cell populations, costs, incumbents and key streams)
    # round-tripping between jit calls; the per-sweep body, the key
    # stream and the sweep indices are those of a monolithic scan, so
    # the chunking is bit-invisible. "scenario_init" evaluates the seed
    # populations + folds the per-cell keys; each distinct segment
    # length compiles one "scenario_pt".

    def _init_fn(self, S: int, n: int):
        key_t = ("init", S, n)
        fn = self._fn_cache.get(key_t)
        if fn is not None:
            return fn
        import jax
        import jax.numpy as jnp

        eval_cell = self._eval_cell

        def init(v0, mins, med, w, ci, price, embf, profile, pprofile,
                 widx, key):
            _count_trace("scenario_init")
            keys0 = jax.vmap(
                lambda i: jax.random.fold_in(key, i))(jnp.arange(S))
            cost0, vec0 = jax.vmap(eval_cell)(v0, mins, med, w, ci,
                                              price, embf, profile,
                                              pprofile, widx)
            return keys0, cost0, vec0

        fn = jax.jit(init)
        self._fn_cache[key_t] = fn
        return fn

    def _pt_fn(self, S: int, n: int, seg: int, swap_every: int,
               collect_samples: bool, record_trace: bool = False):
        """The segment program. ``record_trace`` appends every cell's
        per-sweep ``(proposals, proposal costs, accept draws, swap draws,
        accepted, post-exchange costs)`` to the outputs, for a host
        replay; without it the program carries none of them."""
        key_t = ("pt", S, n, seg, swap_every, collect_samples, record_trace)
        fn = self._fn_cache.get(key_t)
        if fn is not None:
            return fn
        import jax
        import jax.numpy as jnp

        tb, cfg = self.tables, self.cfg
        eval_cell = self._eval_cell
        mesh_comm = cfg.comm == "mesh_noc"
        win_sched = cfg.schedule == "window"

        def cell_step(key_s, v_s, costs_s, temps_s, inv_s, mins_s, med_s,
                      w_s, pair_s, ci_s, price_s, embf_s, profile_s,
                      pprofile_s, wi, noc_s, sched_s, sweep):
            key_s, kp, ka, ksw = jax.random.split(key_s, 4)
            prop = _propose_jax(kp, v_s, tb, cfg,
                                noc_on=noc_s if mesh_comm else None,
                                sched_on=sched_s if win_sched else None)
            pcost, pvec = eval_cell(prop, mins_s, med_s, w_s, ci_s,
                                    price_s, embf_s, profile_s,
                                    pprofile_s, wi)
            u = jax.random.uniform(ka, (n,), dtype=jnp.float64)
            delta = pcost - costs_s
            accept = (delta <= 0) | (
                u < jnp.exp(-delta / jnp.maximum(temps_s, 1e-12)))
            v_s = jnp.where(accept[:, None], prop, v_s)
            costs_s = jnp.where(accept, pcost, costs_s)
            acc = jnp.where(accept, pcost, jnp.inf)
            i = jnp.argmin(acc)
            cand_c, cand_v = acc[i], prop[i]
            us = jax.random.uniform(ksw, (max(n - 1, 1),),
                                    dtype=jnp.float64)
            do_swap = (sweep % swap_every) == 0
            ex_body = _exchange_fn(inv_s, us, pair_s)
            v_s, costs_s = jax.lax.cond(
                do_swap,
                lambda vc: jax.lax.fori_loop(0, n - 1, ex_body, vc),
                lambda vc: vc, (v_s, costs_s))
            out = (key_s, v_s, costs_s, cand_v, cand_c, prop, pvec)
            return out + ((pcost, u, us, accept) if record_trace else ())

        def _run(v0, costs0, best_v0, best_c0, keys0, sweep0, temps, mins,
                 med, w, pair_ok, ci, price, embf, profile, pprofile,
                 widx, noc_on, sched_on):
            # ``sweep0`` is a per-cell [S] vector of job-local sweep
            # counters: every cell keeps its own swap schedule, so a
            # serving job that joins the batch mid-stream sees the same
            # sweep indices it would solo. Lockstep callers pass
            # ``done * ones(S)`` and get the exact pre-vector program
            # semantics (the swap cond is per-lane either way).
            # ``noc_on``/``sched_on`` are the per-cell [S] NoC-move /
            # schedule-move gates (mesh_noc / window engines only; dead
            # inputs elsewhere).
            _count_trace("scenario_pt")
            inv_t = 1.0 / temps

            def body(carry, t):
                v, costs, best_v, best_c, keys = carry
                out = jax.vmap(
                    cell_step,
                    in_axes=(0,) * 18,
                )(keys, v, costs, temps, inv_t, mins, med, w, pair_ok,
                  ci, price, embf, profile, pprofile, widx, noc_on,
                  sched_on, sweep0 + t)
                keys, v, costs, cand_v, cand_c, prop, pvec = out[:7]
                better = cand_c < best_c
                best_c = jnp.where(better, cand_c, best_c)
                best_v = jnp.where(better[:, None], cand_v, best_v)
                ys = (costs[:, -1], best_c)
                if collect_samples:
                    ys = ys + (prop, pvec)
                if record_trace:
                    ys = ys + (prop,) + out[7:] + (costs,)
                return (v, costs, best_v, best_c, keys), ys

            carry, ys = jax.lax.scan(
                body, (v0, costs0, best_v0, best_c0, keys0),
                jnp.arange(seg))
            return carry, ys

        # the public replay contract (the serving layer's) is exactly 17
        # positional args, plus a trailing ``noc_on`` iff mesh_noc and a
        # trailing ``sched_on`` iff window — neutral gates for absent
        # axes are dead inputs the compiler strips, so every engine
        # whose optional axes are off emits the same program it did
        # before those axes existed
        if mesh_comm and win_sched:
            run = _run
        elif mesh_comm:
            def run(v0, costs0, best_v0, best_c0, keys0, sweep0, temps,
                    mins, med, w, pair_ok, ci, price, embf, profile,
                    pprofile, widx, noc_on):
                return _run(v0, costs0, best_v0, best_c0, keys0, sweep0,
                            temps, mins, med, w, pair_ok, ci, price,
                            embf, profile, pprofile, widx, noc_on,
                            jnp.zeros_like(ci))
        elif win_sched:
            def run(v0, costs0, best_v0, best_c0, keys0, sweep0, temps,
                    mins, med, w, pair_ok, ci, price, embf, profile,
                    pprofile, widx, sched_on):
                return _run(v0, costs0, best_v0, best_c0, keys0, sweep0,
                            temps, mins, med, w, pair_ok, ci, price,
                            embf, profile, pprofile, widx,
                            jnp.zeros_like(ci), sched_on)
        else:
            def run(v0, costs0, best_v0, best_c0, keys0, sweep0, temps,
                    mins, med, w, pair_ok, ci, price, embf, profile,
                    pprofile, widx):
                return _run(v0, costs0, best_v0, best_c0, keys0, sweep0,
                            temps, mins, med, w, pair_ok, ci, price,
                            embf, profile, pprofile, widx,
                            jnp.zeros_like(ci), jnp.zeros_like(ci))

        fn = jax.jit(run)
        self._fn_cache[key_t] = fn
        return fn

    def segment_runner(self, S: int, n: int, seg: int, swap_every: int,
                       collect_samples: bool = False):
        """Public handle on the fused segment program.

        The serving layer (``repro.serving``) drives one segment at a
        time from its own scheduler, so it needs the compiled program
        without the host loop in :meth:`parallel_tempering`. The
        returned callable has signature ``run(v, costs, best_v, best_c,
        keys, sweep0, temps, mins, med, w, pair_ok, ci, price, embf,
        profile, pprofile, widx)`` — ``price``/``embf`` are the
        per-cell [S] regional price and embodied-factor columns,
        ``profile`` the [S, 24] grid-intensity rows and ``pprofile``
        the [S, 24] electricity-price rows (neutral cells pass 0.0 /
        1.0 / flat-at-ci / flat-at-price); mesh_noc engines take an
        extra trailing ``noc_on`` [S] column and window-schedule
        engines a trailing ``sched_on`` [S] column (0.0/1.0 per-cell
        move gates) — where ``sweep0`` is the per-cell [S] vector of
        job-local sweep counters; calling it twice with the same static
        shape tuple reuses the cached jit program
        (``trace_count("scenario_pt")`` does not move)."""
        return self._pt_fn(int(S), int(n), int(seg), int(swap_every),
                           bool(collect_samples))

    def parallel_tempering(self, v0: np.ndarray, temps, sweeps: int,
                           swap_every: int, seed: int, mins, medians,
                           weights, pair_mask, ci, widx,
                           price=None, embf=None, profile=None,
                           pprofile=None, noc_on=None, sched_on=None,
                           collect_samples: bool = True,
                           mesh=None, segment: Optional[int] = None,
                           checkpoint=None, resume: bool = True,
                           archives: Optional[Sequence] = None
                           ) -> ScenarioPTResult:
        """Run the whole scenario grid in one fused scan.

        ``v0`` is ``[S, n, width]`` (cell-major seed populations),
        ``temps``/``weights``/``pair_mask`` the per-cell ladder / Eq. 17
        rows / exchange gates, ``mins``/``medians`` the per-cell
        normalizer rows, ``ci`` the per-cell grid carbon intensities and
        ``widx`` the per-cell workload indices into this engine's
        workload tuple. ``price``/``embf``/``profile``/``pprofile``
        are the optional per-cell regional axes ([S] electricity
        prices, [S] embodied factors, [S, 24] grid-intensity profiles,
        [S, 24] electricity-price profiles); omitted axes synthesize
        their neutral columns (0.0 / 1.0 / flat-at-ci /
        flat-at-price), so legacy scalar-CI grids compile and run the
        exact same program — the columns are always part of the jitted
        signature and ``trace_count("scenario_pt")`` stays flat across
        axis mixes. ``noc_on`` ([S], mesh_noc engines only) gates the
        per-cell NoC move level as runtime data (default: all-on for
        live-NoC spaces, all-off for frozen ones) and ``sched_on``
        ([S], window-schedule engines only) gates the per-cell
        schedule move level the same way, so mixed legacy-replay and
        axis-searching cells share one compile.
        ``mesh`` (optional) shards the scenario axis.

        ``segment``/``checkpoint``/``resume`` work as in
        :meth:`DeviceEvaluator.parallel_tempering`, with the per-cell
        sweep counters and key streams in the carry and one archive per
        cell: the chunking never changes a bit of any cell's
        trajectory. ``archives`` is one
        :class:`~repro.pathfinding.pareto.ParetoArchive` per cell, fed
        in place of returning ``.samples``."""
        from repro.jaxenv import search_numerics

        with search_numerics():
            v0 = np.asarray(v0, dtype=np.int32)
            if v0.ndim != 3:
                raise ValueError(f"v0 must be [S, n, width], got {v0.shape}")
            S = v0.shape[0]
            seg_size = _seg_size(segment, sweeps)
            if checkpoint is not None and collect_samples \
                    and archives is None:
                raise ValueError(
                    "checkpointing with collect_samples requires "
                    "archives= to feed: bulk .samples live only in "
                    "process memory and would be lost across a resume")
            if archives is not None and len(archives) != S:
                raise ValueError(
                    f"need one archive per cell: {len(archives)} != {S}")
            arrays = self._grid(v0, temps, mins, medians, weights,
                                pair_mask, ci, widx, price, embf, profile,
                                pprofile, noc_on, sched_on)
            fp = (None if checkpoint is None else self._fingerprint(
                arrays, seed, swap_every, segment, collect_samples))
            return self._drive(
                arrays, seed, sweeps=int(sweeps), seg_size=seg_size,
                swap_every=swap_every, collect_samples=collect_samples,
                fingerprint=fp, checkpoint=checkpoint, resume=resume,
                archives=archives, mesh=mesh)[0]

    def _fingerprint(self, arrays: Dict[str, np.ndarray], seed: int,
                     swap_every: int, segment: Optional[int],
                     collect: bool, one_cell: bool = False) -> np.ndarray:
        """The checkpoint fingerprint of a run over ``arrays`` (see
        :func:`~repro.pathfinding.resume.segment_fingerprint`): kind
        ``"scenario_pt"`` for a grid; ``"device_pt"`` over the one
        cell's columns, without workload ids or move gates, for the
        single-workload view (``one_cell``)."""
        from repro.pathfinding.resume import segment_fingerprint

        a = {k: x[0] for k, x in arrays.items()} if one_cell else arrays
        extra = {} if one_cell else dict(widx=a["widx"])
        if self.cfg.comm != "legacy":
            # non-legacy comm reshapes the encoding + the fused
            # program: pre-NoC checkpoints must mismatch cleanly
            extra["comm"] = np.frombuffer(self.cfg.comm.encode(),
                                          dtype=np.uint8)
            if not one_cell:
                extra["noc_on"] = a["noc_on"]
        if self.cfg.schedule != "fixed":
            # the window encoding reshapes the row the same way:
            # pre-schedule checkpoints must mismatch cleanly, while
            # fixed-schedule fingerprints stay byte-identical to ones
            # written before the schedule axis existed
            extra["schedule"] = np.frombuffer(self.cfg.schedule.encode(),
                                              dtype=np.uint8)
            if not one_cell:
                extra["sched_on"] = a["sched_on"]
        if not np.all(a["pprofile"] == a["price"][..., None]):
            extra["pprofile"] = a["pprofile"]
        return segment_fingerprint(
            "device_pt" if one_cell else "scenario_pt", v0=a["v0"],
            temps=a["temps"], swap_every=swap_every, seed=seed,
            mins=a["mins"], medians=a["med"], weights=a["w"],
            pair_mask=a["pair_ok"], ci=a["ci"], segment=segment,
            collect=collect, price=a["price"], embf=a["embf"],
            profile=a["profile"], **extra)

    def _grid(self, v0, temps, mins, medians, weights, pair_mask, ci, widx,
              price, embf, profile, pprofile, noc_on, sched_on
              ) -> Dict[str, np.ndarray]:
        """The checked per-cell host columns of a grid run, omitted
        regional axes and move gates synthesized (``noc_on`` only on
        mesh_noc engines, ``sched_on`` only on window ones)."""
        S, n, _ = v0.shape
        widx_a = np.asarray(widx, dtype=np.int32).reshape(S)
        if widx_a.min(initial=0) < 0 or \
                widx_a.max(initial=0) >= len(self.workloads):
            raise ValueError(
                f"widx out of range for {len(self.workloads)} workloads")
        ci_a = np.asarray(ci, np.float64).reshape(S)
        price_a, embf_a, profile_a, pprofile_a = self._region_cols(
            S, ci_a, price, embf, profile, pprofile)
        arrays = dict(
            v0=v0,
            temps=np.asarray(temps, np.float64).reshape(S, n),
            mins=np.asarray(mins, np.float64).reshape(S, 6),
            med=np.asarray(medians, np.float64).reshape(S, 6),
            w=np.asarray(weights, np.float64).reshape(S, n, 6),
            pair_ok=np.asarray(pair_mask, bool).reshape(
                S, max(n - 1, 1)),
            ci=ci_a,
            price=price_a,
            embf=embf_a,
            profile=profile_a,
            pprofile=pprofile_a,
            widx=widx_a,
        )
        if self.cfg.comm == "mesh_noc":
            arrays["noc_on"] = (np.full(
                S, 1.0 if self.space.noc_live else 0.0, np.float64)
                if noc_on is None
                else np.asarray(noc_on, np.float64).reshape(S))
        elif noc_on is not None:
            raise ValueError(
                "noc_on is only meaningful for mesh_noc engines")
        if self.cfg.schedule == "window":
            arrays["sched_on"] = (np.full(
                S, 1.0 if self.space.sched_live else 0.0, np.float64)
                if sched_on is None
                else np.asarray(sched_on, np.float64).reshape(S))
        elif sched_on is not None:
            raise ValueError(
                "sched_on is only meaningful for window-schedule "
                "engines")
        return arrays

    def _drive(self, arrays: Dict[str, np.ndarray], seed: int, *,
               sweeps: int, seg_size: int, swap_every: int,
               collect_samples: bool, fingerprint=None, checkpoint=None,
               resume: bool = True, archives=None, mesh=None, keys=None,
               record_trace: bool = False, unbatched: bool = False):
        """The host segment loop over the init and segment programs
        (:func:`~repro.pathfinding.resume.run_segmented`), for the grid
        and for the single-workload view.

        ``keys`` (``[S]`` PRNG keys) replaces the init program's
        ``fold_in(PRNGKey(seed), cell)`` cell keys. ``record_trace``
        collects the segment program's replay fields. ``unbatched``
        keeps a one-cell search's checkpoints in the single-workload
        layout: the carry without its cell axis (``key`` for ``keys``),
        a flat history and a scalar sweep counter. Returns
        ``(ScenarioPTResult, trace)``; the trace (``None`` unless
        ``record_trace``) has a cell axis after its sweep axis."""
        import jax
        import jax.numpy as jnp
        from repro.pathfinding.resume import run_segmented

        v0 = arrays["v0"]
        S, n, width = v0.shape
        if mesh is not None:
            from repro.distributed.sharding import shard_scenarios

            arrays = shard_scenarios(arrays, mesh)
        key0 = jax.random.PRNGKey(seed)
        with span("repro.pt.prepare", cells=S, chains=n,
                  layers=self.layers):
            args = tuple(jnp.asarray(arrays[k]) for k in (
                "temps", "mins", "med", "w", "pair_ok", "ci", "price",
                "embf", "profile", "pprofile", "widx", "noc_on",
                "sched_on") if k in arrays)

        st = dict(hist_parts=None, seed_block=None, cost0=None,
                  sweep_done=np.zeros(S, dtype=np.int64))
        enc_parts, vec_parts, trace_parts = [], [], []

        def feed_cells(enc_s, vec_s):
            for s in range(S):
                archives[s].insert(
                    enc_s[:, s].reshape(-1, width),
                    vec_s[:, s].reshape(-1, vec_s.shape[-1]))

        def fresh():
            keys0, cost0, vec0 = self._init_fn(S, n)(
                jnp.asarray(arrays["v0"]), args[1], args[2], args[3],
                args[5], args[6], args[7], args[8], args[9],
                args[10], key0)
            if keys is not None:
                keys0 = keys
            bi0 = jnp.argmin(cost0, axis=1)
            best_v0 = jnp.take_along_axis(
                jnp.asarray(arrays["v0"]), bi0[:, None, None],
                axis=1)[:, 0]
            best_c0 = jnp.take_along_axis(
                cost0, bi0[:, None], axis=1)[:, 0]
            st["cost0"] = np.asarray(cost0)
            st["hist_parts"] = [np.min(st["cost0"], axis=1)[:, None]]
            if collect_samples:
                st["seed_block"] = (v0[None], np.asarray(vec0)[None])
            return (jnp.asarray(arrays["v0"]), cost0, best_v0,
                    best_c0, keys0)

        def from_restored(r):
            c = _cell_axis(r.carry) if unbatched else dict(r.carry)
            if mesh is not None:
                # the fresh path's carry inherits the scenario-axis
                # sharding from `arrays`; the restored one comes from
                # host numpy and must be re-placed, or the first
                # post-resume segment jits a second (unsharded)
                # program signature
                from repro.distributed.sharding import shard_scenarios

                c = shard_scenarios(c, mesh)
            st["sweep_done"] = np.asarray(
                r.sweep_done_per_cell, dtype=np.int64).reshape(S)
            st["hist_parts"] = [r.history.reshape(S, -1)]
            return (jnp.asarray(c["v"]), jnp.asarray(c["costs"]),
                    jnp.asarray(c["best_v"]), jnp.asarray(c["best_c"]),
                    _key_from_np(c["keys"], key0))

        # a traced run alone names its flag: stand-ins for the segment
        # program (bench/faults.py) take the five shape arguments
        trace_kw = dict(record_trace=True) if record_trace else {}

        def run_segment(carry, done, seg):
            fn = self._pt_fn(S, n, seg, int(swap_every),
                             bool(collect_samples), **trace_kw)
            if mesh is not None:
                # re-pin the carry to the scenario sharding: outputs
                # may come back laid out otherwise, and a second
                # layout would compile the segment program again
                from repro.distributed.sharding import shard_scenarios

                carry = tuple(shard_scenarios(
                    dict(enumerate(carry)), mesh).values())
            return fn(*carry, jnp.asarray(st["sweep_done"]), *args)

        off = 4 if collect_samples else 2

        def absorb(ys, seg):
            # copy back only the outputs this call reads
            want = [0] + [2, 3] * collect_samples
            if record_trace:
                want += [*range(off, off + 6), 1]
            with span("repro.segment.fetch") as sp:
                h = {i: np.asarray(ys[i]) for i in want}
                sp.set_metadata(bytes=nbytes(h.values()))
            st["hist_parts"].append(h[0].T)
            rows = 0
            if collect_samples:
                enc_s, vec_s = h[2], h[3]
                if st["seed_block"] is not None:
                    enc_s = np.concatenate(
                        [st["seed_block"][0], enc_s])
                    vec_s = np.concatenate(
                        [st["seed_block"][1], vec_s])
                    st["seed_block"] = None
                if archives is not None:
                    feed_cells(enc_s, vec_s)
                else:
                    enc_parts.append(enc_s)
                    vec_parts.append(vec_s)
                rows = enc_s.size // width
            if record_trace:
                trace_parts.append(
                    tuple(h[i] for i in range(off, off + 6)) + (h[1],))
            st["sweep_done"] = st["sweep_done"] + seg
            return rows

        def carry_np(carry):
            c = dict(v=np.asarray(carry[0]), costs=np.asarray(carry[1]),
                     best_v=np.asarray(carry[2]),
                     best_c=np.asarray(carry[3]),
                     keys=_key_to_np(carry[4]))
            return _one_cell(c) if unbatched else c

        key_np = _key_to_np(key0)
        carry_like = None if checkpoint is None else carry_np((
            np.zeros((S, n, width), np.int32), np.zeros((S, n)),
            np.zeros((S, width), np.int32), np.zeros(S),
            np.zeros((S,) + key_np.shape, key_np.dtype)))

        def history_np():
            h = np.concatenate(st["hist_parts"], axis=1)
            return h[0] if unbatched else h

        def flush_seed():
            if st["seed_block"] is not None and archives is not None:
                feed_cells(*st["seed_block"])
                st["seed_block"] = None

        # the mesh in context lets the gather kernel (which XLA
        # cannot partition) run per device under a shard_map
        with (jax.set_mesh(mesh) if mesh is not None
              else contextlib.nullcontext()):
            carry, _ = run_segmented(
                sweeps=sweeps, seg_size=seg_size,
                checkpoint=checkpoint, resume=resume,
                fingerprint=fingerprint, archives=archives,
                carry_like=carry_like, fresh=fresh,
                from_restored=from_restored, run_segment=run_segment,
                absorb=absorb, carry_np=carry_np, history_np=history_np,
                sweep_counter=lambda done: (st["sweep_done"][0] if unbatched
                                            else st["sweep_done"]),
                flush_seed=flush_seed)
        seed_block = st["seed_block"]

        v_fin, costs_fin, best_v, best_c, _ = carry
        samples = None
        if collect_samples and archives is None:
            blocks_e = ([seed_block[0]] if seed_block is not None
                        else []) + enc_parts
            blocks_v = ([seed_block[1]] if seed_block is not None
                        else []) + vec_parts
            if blocks_e:
                samples = dict(enc=np.concatenate(blocks_e),
                               vec=np.concatenate(blocks_v))
        trace = None
        if record_trace:
            fields = ("proposals", "proposal_costs", "u_accept", "u_swap",
                      "accepted", "costs", "best_per_sweep")
            # per-sweep shapes, for the zero-sweep edge
            tails = ((n, width), (n,), (n,), (max(n - 1, 1),), (n,), (n,), ())
            trace = {f: (np.concatenate([p[i] for p in trace_parts])
                         if trace_parts else np.zeros((0, S) + tails[i]))
                     for i, f in enumerate(fields)}
            trace["initial_costs"] = st["cost0"]
        return ScenarioPTResult(
            best_enc=np.asarray(best_v),
            best_cost=np.asarray(best_c),
            history=np.concatenate(st["hist_parts"], axis=1),
            evaluations=S * n * (1 + sweeps),
            final_enc=np.asarray(v_fin),
            final_costs=np.asarray(costs_fin),
            samples=samples), trace


def _seg_size(segment: Optional[int], sweeps: int) -> int:
    """Sweeps per host-driven chunk (default: one chunk for the run)."""
    if segment is not None and int(segment) < 1:
        raise ValueError(f"segment must be >= 1, got {segment}")
    return max(1, int(sweeps)) if segment is None else int(segment)


def _one_cell(carry: Dict[str, np.ndarray]) -> Dict[str, np.ndarray]:
    """A one-cell carry in the single-workload checkpoint layout."""
    return {("key" if k == "keys" else k): a[0] for k, a in carry.items()}


def _cell_axis(carry: Dict[str, np.ndarray]) -> Dict[str, np.ndarray]:
    """The inverse of :func:`_one_cell`."""
    return {("keys" if k == "key" else k): np.asarray(a)[None]
            for k, a in carry.items()}


_SCENARIO_ENGINES: Dict[tuple, Tuple[TechDB, "ScenarioEngine"]] = {}
# grid engines and the one-workload engines under DeviceEvaluator share it
_SCENARIO_ENGINE_CACHE_MAX = 12


def get_scenario_engine(workloads: Sequence[Workload],
                        db: TechDB = DEFAULT_DB,
                        tile_sizes: Tuple[int, int, int] = DEFAULT_TILE,
                        space: Optional[DesignSpace] = None,
                        use_pallas: Optional[bool] = None
                        ) -> ScenarioEngine:
    """Cached :class:`ScenarioEngine` per (workload tuple, db, tiles,
    chiplet bound) — jit warmup is expensive, so grids and
    single-workload views (:func:`get_device_evaluator`) share engines
    like ``get_evaluator`` shares host tables. Workloads may be GEMMs or
    networks.

    The resolved Pallas setting (``use_pallas``, else
    :func:`_resolve_pallas`) is part of the key, so flipping
    ``REPRO_PATHFINDER_PALLAS`` mid-process builds a fresh engine
    instead of silently returning the cached other-path one.

    The db's ``_Cfg``-static lifecycle knobs (``load_profile``,
    ``router_area_frac``) are default-resolved into the key as values:
    two TechDBs that differ only in those knobs can never alias onto
    one cached engine even if ``id()`` is recycled after a gc (the
    ``hit[0] is db`` identity check in ``cached_evaluator`` guards the
    rest of the db)."""
    from repro.pathfinding.batch import cached_evaluator

    use_pallas = _resolve_pallas(use_pallas)
    key = (tuple(workloads), id(db), tile_sizes,
           space.max_chiplets if space is not None else
           DEFAULT_MAX_CHIPLETS, use_pallas,
           tuple(db.load_profile), db.router_area_frac,
           (space.comm, space.noc_live) if space is not None else
           (comm_mod.resolve_comm(None), False),
           (space.schedule, space.sched_live) if space is not None else
           (sched_mod.resolve_schedule(None), False))
    return cached_evaluator(
        _SCENARIO_ENGINES, key, db,
        lambda: ScenarioEngine(workloads, db, tile_sizes, space,
                               use_pallas),
        _SCENARIO_ENGINE_CACHE_MAX)


# ---------------------------------------------------------------------------
# The single-workload view + functional entry points
# ---------------------------------------------------------------------------


class DeviceEvaluator:
    """One workload's view of :class:`ScenarioEngine`: the cached
    one-workload engine's programs (:func:`get_scenario_engine`) run as
    a one-cell grid, the cell's key the caller's ``PRNGKey(seed)`` and
    its region the TechDB's own. ``space``, ``cfg`` and ``tables`` are
    the engine's; the view builds no program of its own."""

    def __init__(self, wl: GEMMWorkload, db: TechDB = DEFAULT_DB,
                 tile_sizes: Tuple[int, int, int] = DEFAULT_TILE,
                 space: Optional[DesignSpace] = None,
                 use_pallas: Optional[bool] = None):
        self.wl, self.db, self.tile_sizes = wl, db, tile_sizes
        self.engine = get_scenario_engine((wl,), db, tile_sizes, space,
                                          use_pallas)
        self.space, self.cfg, self.tables = (
            self.engine.space, self.engine.cfg, self.engine.tables)

    def _region(self) -> dict:
        """The TechDB's region as one cell's columns: a ``None`` grid
        (price) profile becomes the flat row at ``carbon_intensity``
        (``electricity_price``), whose in-program correction is +0.0."""
        db = self.db
        return dict(ci=[db.carbon_intensity], price=[db.electricity_price],
                    embf=[db.emb_factor], profile=db.grid_profile,
                    pprofile=db.price_profile)

    def evaluate_cost(self, encoded: np.ndarray, norm: Normalizer,
                      template: Template
                      ) -> Tuple[MetricsBatch, np.ndarray]:
        """Fused metrics + Eq. 17 cost for an encoded population (rows
        padded to a power-of-two bucket >= 64: a handful of programs)."""
        return self.evaluate_cost_vector(encoded, norm, template)[:2]

    def evaluate_cost_vector(self, encoded: np.ndarray, norm: Normalizer,
                             template: Template
                             ) -> Tuple[MetricsBatch, np.ndarray,
                                        np.ndarray]:
        """Fused metrics + cost + ``(latency, dollar, total_cfp)`` vectors
        — all three outputs of the engine's one eval program."""
        v = np.atleast_2d(np.asarray(encoded, dtype=np.int32))
        mins, medians = norm.weights_arrays()
        mets, cost, vec = self.engine._evaluate(
            v[None], mins, medians, template.weights, widx=[0],
            metrics=True, **self._region())
        return MetricsBatch(*(m[0] for m in mets)), cost[0], vec[0]

    def metrics(self, encoded: np.ndarray) -> MetricsBatch:
        """Raw metrics through the jitted path (identity normalizer)."""
        from repro.core.templates import IDENTITY_NORMALIZER, TEMPLATES

        return self.evaluate_cost(encoded, IDENTITY_NORMALIZER,
                                  TEMPLATES["T1"])[0]

    def propose(self, encoded: np.ndarray, seed: int = 0) -> np.ndarray:
        """One vectorized hierarchical move per row (valid rows only)."""
        return self.engine._propose(encoded, seed)

    def parallel_tempering(self, v0: np.ndarray, temps, sweeps: int,
                           swap_every: int, seed: int, norm: Normalizer,
                           template: Template,
                           record_trace: bool = False,
                           weights: Optional[np.ndarray] = None,
                           pair_mask: Optional[np.ndarray] = None,
                           collect_samples: bool = True,
                           segment: Optional[int] = None,
                           checkpoint=None, resume: bool = True,
                           archive=None) -> DevicePTResult:
        """Run the fused propose/evaluate/accept/exchange scan.

        ``v0`` is the encoded seed population (one row per chain, coldest
        last as in the host strategy), ``temps`` its ladder. ``weights``
        (``[n, 6]``, default ``template.weights``) gives each chain its
        own Eq. 17 row and ``pair_mask`` (``[max(n-1, 1)]`` bool) turns
        off exchange across chosen adjacent pairs: K independent
        scalarization ladders in one program
        (:class:`~repro.pathfinding.pareto.ScalarizationSweep`).
        ``collect_samples`` returns every evaluated design and its
        objective vector in ``.samples``, or feeds them to ``archive`` (a
        :class:`~repro.pathfinding.pareto.ParetoArchive`) segment by
        segment. ``segment`` chops the scan into host-driven chunks
        (default: one), invisible in the results; ``checkpoint`` (a
        :class:`~repro.pathfinding.resume.SearchCheckpointer`) snapshots
        carry, archive and history at every boundary, and with
        ``resume=True`` the newest valid snapshot is restored and the run
        continues to ``sweeps``. ``record_trace`` returns every proposal
        and draw for a host replay (not with a checkpoint)."""
        import jax
        from repro.jaxenv import search_numerics

        with search_numerics():
            v0 = np.atleast_2d(np.asarray(v0, dtype=np.int32))
            n = v0.shape[0]
            seg_size = _seg_size(segment, sweeps)
            if checkpoint is not None and record_trace:
                raise ValueError(
                    "record_trace records host-replay state for the full "
                    "run and cannot be checkpointed/resumed")
            if checkpoint is not None and collect_samples and archive is None:
                raise ValueError(
                    "checkpointing with collect_samples requires an "
                    "archive= to feed: bulk .samples live only in process "
                    "memory and would be lost across a resume")
            mins, medians = norm.weights_arrays()
            w = (np.tile(np.asarray(template.weights, np.float64), (n, 1))
                 if weights is None else np.asarray(weights, np.float64))
            if w.shape != (n, 6):
                raise ValueError(f"weights must be [{n}, 6], got {w.shape}")
            pair_ok = np.asarray(np.ones(max(n - 1, 1)) if pair_mask is None
                                 else pair_mask, dtype=bool)
            if pair_ok.shape != (max(n - 1, 1),):
                raise ValueError(f"pair_mask must be [{max(n - 1, 1)}], "
                                 f"got {pair_ok.shape}")
            eng = self.engine
            arrays = eng._grid(v0[None], temps, mins, medians, w, pair_ok,
                               widx=[0], noc_on=None, sched_on=None,
                               **self._region())
            fp = (None if checkpoint is None else eng._fingerprint(
                arrays, seed, swap_every, segment, collect_samples,
                one_cell=True))
            res, trace = eng._drive(
                arrays, seed, sweeps=int(sweeps), seg_size=seg_size,
                swap_every=swap_every, collect_samples=collect_samples,
                fingerprint=fp, checkpoint=checkpoint, resume=resume,
                archives=None if archive is None else [archive],
                keys=jax.random.PRNGKey(seed)[None],
                record_trace=record_trace, unbatched=True)
            if trace is not None:
                trace = {k: a[0] if k == "initial_costs" else a[:, 0]
                         for k, a in trace.items()}
            return DevicePTResult(
                best_enc=res.best_enc[0], best_cost=float(res.best_cost[0]),
                history=res.history[0].tolist(),
                evaluations=res.evaluations, final_enc=res.final_enc[0],
                final_costs=res.final_costs[0], trace=trace,
                samples=None if res.samples is None else {
                    k: a[:, 0] for k, a in res.samples.items()})


def get_device_evaluator(wl: GEMMWorkload, db: TechDB = DEFAULT_DB,
                         tile_sizes: Tuple[int, int, int] = DEFAULT_TILE,
                         space: Optional[DesignSpace] = None
                         ) -> DeviceEvaluator:
    """:class:`DeviceEvaluator` over the cached one-workload engine of
    :func:`get_scenario_engine` (the resolved Pallas setting is part of
    that key)."""
    return DeviceEvaluator(wl, db, tile_sizes, space)


def evaluate_batch_device(encoded: np.ndarray, wl: GEMMWorkload,
                          db: TechDB = DEFAULT_DB,
                          tile_sizes: Tuple[int, int, int] = DEFAULT_TILE,
                          space: Optional[DesignSpace] = None
                          ) -> MetricsBatch:
    """Jitted counterpart of :func:`repro.pathfinding.evaluate_batch`."""
    return get_device_evaluator(wl, db, tile_sizes, space).metrics(encoded)


def propose_batch(encoded: np.ndarray, wl: GEMMWorkload,
                  db: TechDB = DEFAULT_DB,
                  space: Optional[DesignSpace] = None,
                  seed: int = 0) -> np.ndarray:
    """Vectorized hierarchical moves over encoded rows (see
    :func:`_propose_jax`); invalid candidates keep the incumbent row."""
    return get_device_evaluator(wl, db, space=space).propose(encoded, seed)
