"""Pallas kernel validation: shape/dtype sweeps against pure-jnp oracles.

All kernels run in interpret mode on CPU; tolerances account for blocked
fp32 accumulation-order differences.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import (
    gemm_ref,
    pack_tables,
    prefix_select_gather,
    prefix_select_ref,
    rglru,
    rglru_assoc_ref,
    rglru_ref,
    systolic_gemm,
    wkv6,
    wkv6_ref_vmapped,
)
from repro.kernels.prefix_gather import prefix_select_gather_blocked

KEY = jax.random.PRNGKey(0)


# ---------------------------------------------------------------------------
# systolic_gemm
# ---------------------------------------------------------------------------

GEMM_SHAPES = [
    (128, 128, 128),   # exact blocks
    (200, 300, 450),   # ragged
    (64, 512, 64),     # deep K
    (1, 256, 257),     # degenerate M
]


@pytest.mark.parametrize("shape", GEMM_SHAPES)
@pytest.mark.parametrize("dataflow", ["OS", "WS", "IS"])
def test_gemm_dataflows(shape, dataflow):
    m, k, n = shape
    a = jax.random.normal(jax.random.fold_in(KEY, 1), (m, k), jnp.float32)
    b = jax.random.normal(jax.random.fold_in(KEY, 2), (k, n), jnp.float32)
    out = systolic_gemm(a, b, bm=64, bk=64, bn=64, dataflow=dataflow)
    np.testing.assert_allclose(out, gemm_ref(a, b), rtol=3e-4, atol=3e-4)


@pytest.mark.parametrize("split_k", [2, 4])
def test_gemm_split_k(split_k):
    a = jax.random.normal(jax.random.fold_in(KEY, 3), (96, 512), jnp.float32)
    b = jax.random.normal(jax.random.fold_in(KEY, 4), (512, 160), jnp.float32)
    out = systolic_gemm(a, b, bm=32, bk=64, bn=32, dataflow="OS",
                        split_k=split_k)
    np.testing.assert_allclose(out, gemm_ref(a, b), rtol=3e-4, atol=3e-4)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_gemm_dtypes(dtype):
    a = (jax.random.normal(jax.random.fold_in(KEY, 5), (128, 128))
         .astype(dtype))
    b = (jax.random.normal(jax.random.fold_in(KEY, 6), (128, 128))
         .astype(dtype))
    out = systolic_gemm(a, b, bm=64, bk=64, bn=64)
    assert out.dtype == dtype
    ref = gemm_ref(a, b)
    tol = 5e-2 if dtype == jnp.bfloat16 else 3e-4
    np.testing.assert_allclose(out.astype(jnp.float32),
                               ref.astype(jnp.float32), rtol=tol, atol=tol)


def test_gemm_block_shape_sweep():
    a = jax.random.normal(jax.random.fold_in(KEY, 7), (160, 224), jnp.float32)
    b = jax.random.normal(jax.random.fold_in(KEY, 8), (224, 96), jnp.float32)
    ref = gemm_ref(a, b)
    for bm, bk, bn in [(32, 32, 32), (64, 128, 32), (128, 64, 96)]:
        out = systolic_gemm(a, b, bm=bm, bk=bk, bn=bn)
        np.testing.assert_allclose(out, ref, rtol=3e-4, atol=3e-4,
                                   err_msg=f"block {(bm, bk, bn)}")


# ---------------------------------------------------------------------------
# wkv6
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("g,t,d,ct", [(2, 64, 32, 16), (4, 48, 16, 48),
                                      (1, 100, 64, 25)])
def test_wkv6_shapes(g, t, d, ct):
    ks = jax.random.split(jax.random.fold_in(KEY, 9), 5)
    r = jax.random.normal(ks[0], (g, t, d)) * 0.4
    k = jax.random.normal(ks[1], (g, t, d)) * 0.4
    v = jax.random.normal(ks[2], (g, t, d)) * 0.4
    w = jax.nn.sigmoid(jax.random.normal(ks[3], (g, t, d))) * 0.5 + 0.45
    u = jax.random.normal(ks[4], (g, d)) * 0.1
    out = wkv6(r, k, v, w, u, ct=ct)
    ref = wkv6_ref_vmapped(r, k, v, w, u)
    np.testing.assert_allclose(out, ref, rtol=2e-4, atol=2e-4)


def test_wkv6_state_persistence_across_chunks():
    """Chunked execution must match unchunked (state carries in VMEM)."""
    ks = jax.random.split(jax.random.fold_in(KEY, 10), 5)
    g, t, d = 2, 64, 16
    r, k, v = (jax.random.normal(ks[i], (g, t, d)) * 0.3 for i in range(3))
    w = jax.nn.sigmoid(jax.random.normal(ks[3], (g, t, d))) * 0.4 + 0.5
    u = jax.random.normal(ks[4], (g, d)) * 0.1
    np.testing.assert_allclose(wkv6(r, k, v, w, u, ct=8),
                               wkv6(r, k, v, w, u, ct=64),
                               rtol=2e-4, atol=2e-4)


# ---------------------------------------------------------------------------
# rglru
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("b,t,c,bc,ct", [(2, 64, 128, 128, 16),
                                         (1, 80, 200, 128, 40),
                                         (3, 33, 64, 64, 33)])
def test_rglru_shapes(b, t, c, bc, ct):
    ks = jax.random.split(jax.random.fold_in(KEY, 11), 2)
    a = jax.nn.sigmoid(jax.random.normal(ks[0], (b, t, c))) * 0.9
    x = jax.random.normal(ks[1], (b, t, c)) * 0.3
    out = rglru(a, x, bc=bc, ct=ct)
    np.testing.assert_allclose(out, rglru_ref(a, x), rtol=2e-4, atol=2e-4)


def test_rglru_assoc_matches_sequential():
    ks = jax.random.split(jax.random.fold_in(KEY, 12), 2)
    a = jax.nn.sigmoid(jax.random.normal(ks[0], (2, 50, 32))) * 0.95
    x = jax.random.normal(ks[1], (2, 50, 32))
    np.testing.assert_allclose(rglru_assoc_ref(a, x), rglru_ref(a, x),
                               rtol=1e-4, atol=1e-4)


def test_rglru_identity_decay():
    """a == 1 everywhere -> cumulative sum of inputs."""
    x = jnp.ones((1, 10, 8))
    out = rglru(jnp.ones_like(x), x, bc=8, ct=10)
    np.testing.assert_allclose(out[0, :, 0], jnp.arange(1, 11, dtype=jnp.float32),
                               rtol=1e-5)


# ---------------------------------------------------------------------------
# prefix_gather (device pathfinder stage-3 inner loop)
# ---------------------------------------------------------------------------


def _packed(p0, p1):
    tab, layout = pack_tables(p0, p1)
    return jnp.asarray(tab), layout


def _gather_np(pref, rows, start, end):
    """Plain numpy ``pref[f, rows, end] - pref[f, rows, start]``."""
    pref = np.asarray(pref)
    rows, start, end = (np.asarray(x) for x in (rows, start, end))
    return (pref[:, rows, end] - pref[:, rows, start]).transpose(1, 2, 0)


@pytest.mark.parametrize("shape", [(48, 91, 64, 6), (5, 13, 17, 3)])
def test_prefix_gather_matches_ref(shape):
    """Interpreter-mode kernel vs the pure-jnp oracle on one split table:
    bit-exact, the values are prefix-sum differences of exact integers."""
    from repro.jaxenv import search_numerics

    R, T1, P, C = shape
    with search_numerics():
        rng = np.random.default_rng(1)
        pref = np.cumsum(rng.integers(0, 10**9, (5, R, T1)), axis=2)
        tab, layout = _packed(pref, pref)
        rows = jnp.asarray(rng.integers(0, R, (P, C)).astype(np.int32))
        start = rng.integers(0, T1, (P, C)).astype(np.int32)
        end = np.minimum(start + rng.integers(0, T1, (P, C)),
                         T1 - 1).astype(np.int32)
        split = jnp.zeros((P,), jnp.int32)
        tv = jnp.full((P,), T1 - 1, jnp.int32)
        sel = prefix_select_gather(tab, rows, jnp.asarray(start),
                                   jnp.asarray(end), split, tv, tv,
                                   layout=layout)
        sel_r = prefix_select_ref(jnp.asarray(pref), jnp.asarray(pref),
                                  rows, jnp.asarray(start),
                                  jnp.asarray(end), split, tv, tv)
        assert sel.dtype == jnp.int64
        assert (np.asarray(sel) == np.asarray(sel_r)).all()
        assert (np.asarray(sel) == _gather_np(pref, rows, start, end)).all()


def test_prefix_gather_int32_path():
    """The packed int32 hi/lo words reproduce prefix differences far past
    the int32 (and float32) exact range: values up to ~2^45."""
    from repro.jaxenv import search_numerics

    with search_numerics():
        rng = np.random.default_rng(2)
        pref = np.cumsum(rng.integers(0, 2**40, (5, 8, 33)), axis=2)
        assert pref.max() > 2**44
        tab, layout = _packed(pref, pref)
        rows = jnp.asarray(rng.integers(0, 8, (16, 4)).astype(np.int32))
        start = np.full((16, 4), 2, dtype=np.int32)
        end = np.full((16, 4), 30, dtype=np.int32)
        tv = jnp.full((16,), 32, jnp.int32)
        sel = prefix_select_gather(tab, rows, jnp.asarray(start),
                                   jnp.asarray(end),
                                   jnp.zeros((16,), jnp.int32), tv, tv,
                                   layout=layout)
        assert (np.asarray(sel) == _gather_np(pref, rows, start, end)).all()


# ---------------------------------------------------------------------------
# prefix_select (fused stacked gather -> split-select)
# ---------------------------------------------------------------------------


def _select_tables(rng, F, R, t0, t1, tb0, tb1):
    """Integer prefix tables with true totals t0/t1, edge-padded to the
    tile buckets tb0/tb1 (exactly what the stacked engine builds)."""
    p0 = np.cumsum(rng.integers(0, 10**9, (F, R, t0 + 1)), axis=2)
    p1 = np.cumsum(rng.integers(0, 10**9, (F, R, t1 + 1)), axis=2)
    pad0 = np.pad(p0, [(0, 0), (0, 0), (0, tb0 - t0)], mode="edge")
    pad1 = np.pad(p1, [(0, 0), (0, 0), (0, tb1 - t1)], mode="edge")
    return jnp.asarray(pad0), jnp.asarray(pad1)


def test_prefix_select_matches_ref_t0_ne_t1():
    """Fused kernel vs the jnp oracle with T0 != T1 split tables and
    per-row clip bounds: bit-exact integer prefix differences."""
    from repro.jaxenv import search_numerics

    with search_numerics():
        rng = np.random.default_rng(7)
        F, R, P, C = 5, 36, 48, 6
        t0, t1, tb0, tb1 = 37, 81, 64, 128
        p0, p1 = _select_tables(rng, F, R, t0, t1, tb0, tb1)
        tab, layout = _packed(p0, p1)
        rows = jnp.asarray(rng.integers(0, R, (P, C)).astype(np.int32))
        # bounds deliberately overrun both true totals -> must clip
        start = jnp.asarray(rng.integers(0, tb1, (P, C)).astype(np.int32))
        end = start + jnp.asarray(
            rng.integers(0, tb1, (P, C)).astype(np.int32))
        split = jnp.asarray(rng.integers(0, 2, (P,)).astype(np.int32))
        t0v = jnp.full((P,), t0, jnp.int32)
        t1v = jnp.full((P,), t1, jnp.int32)
        sel = prefix_select_gather(tab, rows, start, end, split, t0v, t1v,
                                   layout=layout)
        sel_r = prefix_select_ref(p0, p1, rows, start, end, split, t0v,
                                  t1v)
        assert (np.asarray(sel) == np.asarray(sel_r)).all()
        # cross-check against plain numpy: clip, gather each split
        # table, select per row
        d0 = _gather_np(p0, rows, np.clip(start, 0, t0),
                        np.clip(end, 0, t0))
        d1 = _gather_np(p1, rows, np.clip(start, 0, t1),
                        np.clip(end, 0, t1))
        want = np.where(np.asarray(split)[:, None, None] == 1, d1, d0)
        assert (np.asarray(sel) == want).all()


def test_prefix_select_empty_segments_and_padded_rows():
    """Bucket-padding boundaries: start == end slots contribute exactly
    zero, and ranges clipped into the edge-replicated padding match the
    unpadded tables bit-for-bit."""
    from repro.jaxenv import search_numerics

    with search_numerics():
        rng = np.random.default_rng(8)
        F, R, P, C = 5, 12, 16, 4
        t0, t1, tb0, tb1 = 19, 23, 64, 64
        p0, p1 = _select_tables(rng, F, R, t0, t1, tb0, tb1)
        tab, layout = _packed(p0, p1)
        rows = jnp.asarray(rng.integers(0, R, (P, C)).astype(np.int32))
        base = rng.integers(0, tb0 + 1, (P, C)).astype(np.int32)
        start = jnp.asarray(base)
        end = jnp.asarray(base)  # every segment empty
        split = jnp.asarray(rng.integers(0, 2, (P,)).astype(np.int32))
        t0v = jnp.full((P,), t0, jnp.int32)
        t1v = jnp.full((P,), t1, jnp.int32)
        sel = prefix_select_gather(tab, rows, start, end, split, t0v, t1v,
                                   layout=layout)
        assert (np.asarray(sel) == 0).all()
        # whole-range gathers that overrun into the padded tail equal
        # the true totals of the unpadded tables
        start = jnp.zeros((P, C), jnp.int32)
        end = jnp.full((P, C), tb0, jnp.int32)  # beyond both true totals
        sel = prefix_select_gather(tab, rows, start, end, split, t0v, t1v,
                                   layout=layout)
        pick = np.where(np.asarray(split)[None, :, None] == 1,
                        np.asarray(p1)[:, np.asarray(rows), t1]
                        - np.asarray(p1)[:, np.asarray(rows), 0],
                        np.asarray(p0)[:, np.asarray(rows), t0]
                        - np.asarray(p0)[:, np.asarray(rows), 0]
                        ).transpose(1, 2, 0)
        assert (np.asarray(sel) == pick).all()


def test_prefix_select_two_workload_stack():
    """A 2-workload stack with different true tile counts: rows offset
    by wi*R reproduce each workload's solo gather bit-for-bit."""
    from repro.jaxenv import search_numerics

    with search_numerics():
        rng = np.random.default_rng(9)
        F, R, P, C = 5, 10, 24, 5
        # workload a: 11/17 tiles, workload b: 45/29 -> shared buckets
        ta0, ta1, tb_0, tb_1 = 11, 17, 45, 29
        bk0, bk1 = 64, 64
        a0, a1 = _select_tables(rng, F, R, ta0, ta1, bk0, bk1)
        b0, b1 = _select_tables(rng, F, R, tb_0, tb_1, bk0, bk1)
        stacked_tab, stacked_layout = _packed(
            jnp.concatenate([a0, b0], axis=1),  # [F, 2R, bk0+1]
            jnp.concatenate([a1, b1], axis=1))
        rows = jnp.asarray(rng.integers(0, R, (P, C)).astype(np.int32))
        start = jnp.asarray(rng.integers(0, 50, (P, C)).astype(np.int32))
        end = start + jnp.asarray(
            rng.integers(0, 30, (P, C)).astype(np.int32))
        split = jnp.asarray(rng.integers(0, 2, (P,)).astype(np.int32))
        for wi, (w0, w1, tt0, tt1) in enumerate(
                [(a0, a1, ta0, ta1), (b0, b1, tb_0, tb_1)]):
            t0v = jnp.full((P,), tt0, jnp.int32)
            t1v = jnp.full((P,), tt1, jnp.int32)
            tab, layout = _packed(w0, w1)
            solo = prefix_select_gather(tab, rows, start, end, split, t0v,
                                        t1v, layout=layout)
            stacked = prefix_select_gather(
                stacked_tab, rows + wi * R, start, end, split, t0v, t1v,
                layout=stacked_layout)
            assert (np.asarray(solo) == np.asarray(stacked)).all()


def test_prefix_select_vmap_flattens_cell_axis():
    """The custom_vmap rule (scenario cells -> kernel systems) matches a
    per-cell loop bit-for-bit, the table shared across the mapped axis."""
    from repro.jaxenv import search_numerics

    with search_numerics():
        rng = np.random.default_rng(10)
        F, R, P, C, B = 5, 8, 6, 4, 3
        t0, t1 = 21, 13
        p0, p1 = _select_tables(rng, F, R, t0, t1, 64, 64)
        tab, layout = _packed(p0, p1)
        rows = jnp.asarray(rng.integers(0, R, (B, P, C)).astype(np.int32))
        start = jnp.asarray(
            rng.integers(0, 30, (B, P, C)).astype(np.int32))
        end = start + jnp.asarray(
            rng.integers(0, 10, (B, P, C)).astype(np.int32))
        split = jnp.asarray(rng.integers(0, 2, (B, P)).astype(np.int32))
        t0v = jnp.asarray(rng.integers(1, t0 + 1, (B, P)).astype(np.int32))
        t1v = jnp.asarray(rng.integers(1, t1 + 1, (B, P)).astype(np.int32))
        sel_v = jax.vmap(
            lambda r, s, e, sp, a, b: prefix_select_gather(
                tab, r, s, e, sp, a, b, layout=layout))(
            rows, start, end, split, t0v, t1v)
        assert sel_v.shape == (B, P, C, F)
        for i in range(B):
            sel_i = prefix_select_gather(
                tab, rows[i], start[i], end[i], split[i], t0v[i], t1v[i],
                layout=layout)
            assert (np.asarray(sel_v[i]) == np.asarray(sel_i)).all()


@pytest.mark.parametrize("P", [20, 136])
def test_prefix_select_blocked_under_two_vmaps(P):
    """A layer-blocked table (one block per GEMM, each with its own true
    totals) read under a vmap over layers inside a vmap over cells, as a
    network engine reads it: bit-equal to the oracle on each block,
    for a population under one kernel block and one spanning two."""
    from repro.jaxenv import search_numerics

    with search_numerics():
        rng = np.random.default_rng(11)
        F, R, C, G, S, L = 5, 12, 6, 3, 2, 4
        totals = [(19, 47), (64, 128), (7, 3)]
        tables = [_select_tables(rng, F, R, a, b, 64, 128)
                  for a, b in totals]
        packed = [_packed(p0, p1) for p0, p1 in tables]
        blocks = jnp.stack([t for t, _ in packed])
        layout = packed[0][1]
        gids = rng.integers(0, G, (S, L)).astype(np.int32)
        rows = rng.integers(0, R, (S, L, P, C)).astype(np.int32)
        start = rng.integers(0, 140, (S, L, P, C)).astype(np.int32)
        end = start + rng.integers(0, 40, (S, L, P, C)).astype(np.int32)
        split = rng.integers(0, 2, (S, P)).astype(np.int32)
        t0v = np.asarray([[totals[g][0]] * P for g in gids.ravel()],
                         np.int32).reshape(S, L, P)
        t1v = np.asarray([[totals[g][1]] * P for g in gids.ravel()],
                         np.int32).reshape(S, L, P)

        def cell(g, r, s, e, sp, a, b):
            return jax.vmap(
                lambda gg, rr, ss, ee, aa, bb: prefix_select_gather_blocked(
                    blocks, gg, rr, ss, ee, sp, aa, bb, layout=layout))(
                g, r, s, e, a, b)

        sel = np.asarray(jax.vmap(cell)(gids, rows, start, end, split, t0v,
                                        t1v))
        assert sel.shape == (S, L, P, C, F)
        for i in range(S):
            for j in range(L):
                p0, p1 = tables[gids[i, j]]
                want = prefix_select_ref(p0, p1, rows[i, j], start[i, j],
                                         end[i, j], split[i], t0v[i, j],
                                         t1v[i, j])
                assert (sel[i, j] == np.asarray(want)).all()
