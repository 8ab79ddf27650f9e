"""Pure-jnp oracle for the prefix-gather kernel."""
from __future__ import annotations

import jax.numpy as jnp


def prefix_select_ref(pref0: jnp.ndarray, pref1: jnp.ndarray,
                      rows: jnp.ndarray, start: jnp.ndarray,
                      end: jnp.ndarray, split: jnp.ndarray,
                      t0: jnp.ndarray, t1: jnp.ndarray) -> jnp.ndarray:
    """Oracle for the fused gather → split-select kernel.

    ``pref0``/``pref1`` are ``[F, R, T+1]`` split-K table stacks (tile
    axes may differ and may be padded past the true totals);
    ``rows``/``start``/``end`` are ``[P, C]``; ``split``/``t0``/``t1``
    per-system ``[P]``. Gathers clip to the per-row true tile totals,
    then the split selector picks per system which table's difference
    survives: ``sel[p, c, f] = pref[f, rows[p, c], end] - pref[f,
    rows[p, c], start]``. Returns ``sel [P, C, F]``.
    """
    def gather(pref, s, e):
        tab = pref[:, rows]  # [F, P, C, T+1]
        d = (jnp.take_along_axis(tab, e[None, ..., None], axis=3)
             - jnp.take_along_axis(tab, s[None, ..., None], axis=3)
             )[..., 0]
        return jnp.moveaxis(d, 0, -1)  # [P, C, F]

    s0 = jnp.clip(start, 0, t0[:, None])
    e0 = jnp.clip(end, 0, t0[:, None])
    s1 = jnp.clip(start, 0, t1[:, None])
    e1 = jnp.clip(end, 0, t1[:, None])
    return jnp.where((split == 1)[:, None, None],
                     gather(pref1, s1, e1), gather(pref0, s0, e0))
