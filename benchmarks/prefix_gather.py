"""Stacked prefix-gather kernel: fused gather + split-select
(``prefix_select_gather``) vs the plain jnp reference path, on the real
2-workload stacked engine tables.

Claims asserted:
  (a) the kernel (interpret mode on CPU, compiled on TPU) matches the
      jnp reference bit-for-bit on every chain count — the tables are
      int64 prefix sums and both paths subtract them exactly;
  (b) on TPU backends, the compiled kernel sustains >= the jnp gather
      throughput at 4096 chains (``PREFIX_GATHER_MIN_SPEEDUP`` floor,
      default 1.0). Off-TPU the gate is skipped: interpret mode is a
      correctness vehicle, not a fast path, and its timing is reported
      for the record only.
"""
from __future__ import annotations

import os

import numpy as np

from repro.core import workload
from repro.kernels.prefix_gather import prefix_select_gather, prefix_select_ref
from repro.pathfinding.device import ScenarioEngine
from benchmarks.common import row, timed

CHAINS = (256, 1024, 4096)
GATE_CHAINS = 4096
REPEATS = 5
MIN_SPEEDUP = float(os.environ.get("PREFIX_GATHER_MIN_SPEEDUP", "1.0"))


def _inputs(rng, tb, cfg, P):
    """Random but in-contract gather operands for P chains: rows inside
    the stacked table, segments clipped like the tempering step's."""
    import jax.numpy as jnp

    R = cfg.pallas_layout[0]
    C = cfg.C
    wi = rng.integers(0, 2, (P,))
    rows = (rng.integers(0, R // 2, (P, C))
            + (wi * (R // 2))[:, None]).astype(np.int32)
    start = rng.integers(0, cfg.T0, (P, C)).astype(np.int32)
    end = np.minimum(start + rng.integers(0, 16, (P, C)),
                     cfg.T0).astype(np.int32)
    split = rng.integers(0, 2, (P,)).astype(np.int32)
    t0 = np.full((P,), cfg.T0, np.int32)
    t1 = np.full((P,), cfg.T1, np.int32)
    return tuple(jnp.asarray(a) for a in
                 (rows, start, end, split, t0, t1))


def run(out=print) -> str:
    import jax
    import jax.numpy as jnp
    from repro.jaxenv import search_numerics

    on_tpu = jax.default_backend() == "tpu"

    def compute():
        eng = ScenarioEngine([workload(1), workload(6)], use_pallas=True)
        tb, cfg = eng.tables, eng.cfg
        ref_fn = jax.jit(prefix_select_ref)
        tab = tb["pallas_table"]

        def kern(*a):
            return prefix_select_gather(tab, *a, layout=cfg.pallas_layout)

        def flat(pw):
            # [Wk, A, S, 3, T+1, 5] -> the kernel's [5, Wk*A*S*3, T+1]
            pw = np.asarray(pw)
            return jnp.asarray(np.moveaxis(
                pw.reshape(-1, pw.shape[-2], pw.shape[-1]), -1, 0))

        rng = np.random.default_rng(2026)
        stats = {}
        with search_numerics():
            # int64 tables under x64 like the engine: an int32 truncation
            # would wrap the WL2-sized prefix sums
            p0, p1 = flat(tb["pref0w"]), flat(tb["pref1w"])
            for P in CHAINS:
                args = _inputs(rng, tb, cfg, P)
                sel_r = ref_fn(p0, p1, *args)
                sel_k = kern(*args)
                assert (np.asarray(sel_r) == np.asarray(sel_k)).all()

                def bench(fn, *a):
                    fn(*a).block_until_ready()  # warm
                    return min(
                        timed(lambda: fn(*a).block_until_ready())[1]
                        for _ in range(REPEATS))
                stats[P] = (bench(ref_fn, p0, p1, *args),
                            bench(kern, *args))
        return stats

    stats, us = timed(compute)
    out("# Stacked prefix-gather kernel vs jnp reference")
    out("chains,jnp_us,kernel_us,kernel_mode,speedup")
    mode = "compiled" if on_tpu else "interpret"
    for P, (t_ref, t_k) in stats.items():
        out(f"{P},{t_ref:.0f},{t_k:.0f},{mode},{t_ref / t_k:.3f}")
    t_ref, t_k = stats[GATE_CHAINS]
    speedup = t_ref / t_k
    derived = (f"parity=bitwise;mode={mode};"
               f"speedup@{GATE_CHAINS}={speedup:.2f}x;"
               f"jnp_us={t_ref:.0f};kernel_us={t_k:.0f}")
    if on_tpu:
        assert speedup >= MIN_SPEEDUP, (
            f"compiled prefix-gather kernel {speedup:.2f}x < "
            f"{MIN_SPEEDUP}x the jnp path at {GATE_CHAINS} chains")
    else:
        derived += ";gate=skipped-non-tpu"
    return row("prefix_gather", us, derived)


if __name__ == "__main__":
    print(run())
