"""Whole networks as workloads: an ordered list of GEMM layers, each run
``count`` times, on one HI system under one O-D-K mapping.

The layers run one after another on the same system, each tiled by
Algorithm 1 under the design's mapping. So a network's latency is
``sum(c_l * latency_l)`` and its dynamic energy ``sum(c_l * (e_compute_l
+ e_d2d_l))``; static energy is the system's static power over that
latency; area, cost and embodied CFP are the system's, taken once; and
operational CFP and the bill follow from the totals
(:func:`repro.core.evaluate.system_metrics`). Sums run in list order
through :func:`repro.core.seqsum.seq_sum`, the order the batched and
device evaluators add in, so a one-layer network of count 1 is
:func:`repro.core.evaluate.evaluate` bit for bit.

:func:`deepseek_v2_step` builds the GEMMs of one serving step of a
DeepSeek-V2-style model (MLA attention in its absorbed form, dense and
MoE feed-forwards, the LM head) from a :class:`~repro.configs.base
.ModelConfig`.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple, Union

from repro.core import d2d as d2d_mod
from repro.core.evaluate import GemmTerms, Metrics, gemm_terms, system_metrics
from repro.core.scalesim import SimCache
from repro.core.seqsum import seq_sum
from repro.core.system import HISystem
from repro.core.techdb import DEFAULT_DB, TechDB
from repro.core.workload import DEFAULT_TILE, GEMMWorkload


@dataclasses.dataclass(frozen=True)
class Network:
    """An ordered list of ``(GEMM, count)`` layers."""

    name: str
    layers: Tuple[Tuple[GEMMWorkload, int], ...]

    def __post_init__(self):
        layers = tuple((g, int(c)) for g, c in self.layers)
        if not layers:
            raise ValueError(f"network {self.name!r} has no layers")
        for g, c in layers:
            if not isinstance(g, GEMMWorkload) or c < 1:
                raise ValueError(f"bad layer ({g!r}, {c}) in {self.name!r}")
        object.__setattr__(self, "layers", layers)

    @classmethod
    def single(cls, wl: GEMMWorkload) -> "Network":
        """``wl`` as a one-layer network of count 1."""
        return cls(wl.name, ((wl, 1),))

    @property
    def gemms(self) -> Tuple[GEMMWorkload, ...]:
        """The distinct GEMMs, in order of first appearance."""
        return tuple(dict.fromkeys(g for g, _ in self.layers))

    @property
    def macs(self) -> int:
        return sum(c * g.macs for g, c in self.layers)


Workload = Union[GEMMWorkload, Network]


def as_network(wl: Workload) -> Network:
    return wl if isinstance(wl, Network) else Network.single(wl)


def evaluate_network(
    sys: HISystem,
    net: Network,
    db: TechDB = DEFAULT_DB,
    tile_sizes: Tuple[int, int, int] = DEFAULT_TILE,
    cache: Optional[SimCache] = None,
) -> Metrics:
    """PPAC + CFP of ``sys`` running every layer of ``net`` in turn (see
    the module docstring for how the layers combine)."""
    cache = cache if cache is not None else SimCache()
    topo = d2d_mod.build_topology(sys, db)
    per = [(c, gemm_terms(sys, g, topo, db, tile_sizes, cache))
           for g, c in net.layers]
    parts = GemmTerms(
        l_compute_rd_s=seq_sum(c * t.l_compute_rd_s for c, t in per),
        l_d2d_s=seq_sum(c * t.l_d2d_s for c, t in per),
        l_dram_wr_s=seq_sum(c * t.l_dram_wr_s for c, t in per),
        e_compute_j=seq_sum(c * t.e_compute_j for c, t in per),
        e_d2d_j=seq_sum(c * t.e_d2d_j for c, t in per),
        d2d_bits=sum(c * t.d2d_bits for c, t in per),
        macs=sum(c * t.macs for c, t in per),
    )
    latency = seq_sum(c * t.latency_s for c, t in per)
    e_dynamic = seq_sum(c * t.e_dynamic_j for c, t in per)
    return system_metrics(sys, topo, latency, e_dynamic, parts, db)


def deepseek_v2_step(cfg, prefill: int = 512, decode: int = 128,
                     context: int = 32768, ep: int = 8) -> Network:
    """One serving step of a DeepSeek-V2-style model on one package.

    The step holds a ``prefill``-token chunk of one prompt and ``decode``
    sequences of one token each, every one at ``context`` positions of
    cache (``tok = prefill + decode`` rows through the projections). The
    package is one of ``ep`` in an expert-parallel group with
    data-parallel attention: it runs its own step's attention and dense
    projections, and ``n_experts / ep`` routed experts, each of which
    gets ``ep * tok * top_k / n_experts`` tokens under uniform routing.

    Attention is MLA in its absorbed form (arXiv:2405.04434, Sec.
    2.1.2): ``W_UK`` folds into the query (``q_absorb``) and ``W_UV``
    into the output (``v_absorb``), so scores and values are taken
    against the latent cache (``kv_lora_rank`` plus the shared RoPE
    key) and it is never expanded. Prefill attention counts all
    ``context`` keys. The decode rows take their heads as the GEMM's M.
    The LM head runs over the ``decode + 1`` sampled positions, in
    ``ep`` column blocks of the vocabulary. Norms, RoPE, softmax, SiLU
    and top-k are not GEMMs and are left out. Counts cover every layer:
    ``first_dense`` dense feed-forwards, the rest MoE."""
    tok = prefill + decode
    L, H, d = cfg.n_layers, cfg.n_heads, cfg.d_model
    nope, rope, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    kv, qr = cfg.kv_lora_rank, cfg.q_lora_rank
    moe = L - cfg.first_dense
    held, routed = divmod(cfg.n_experts, ep)
    per_expert, r2 = divmod(ep * tok * cfg.top_k, cfg.n_experts)
    vblock, r3 = divmod(cfg.vocab, ep)
    if routed or r2 or r3:
        raise ValueError(
            f"{cfg.name}: {ep} packages do not split the experts, routed "
            "tokens or vocabulary evenly")
    shared = cfg.n_shared_experts * cfg.moe_d_ff
    rows = [
        ("q_a", tok, d, qr, L),
        ("q_b", tok, qr, H * (nope + rope), L),
        ("kv_a", tok, d, kv + rope, L),
        ("q_absorb", tok, nope, kv, L * H),
        ("attn_qk_prefill", prefill, kv + rope, context, L * H),
        ("attn_pv_prefill", prefill, context, kv, L * H),
        ("attn_qk_decode", H, kv + rope, context, L * decode),
        ("attn_pv_decode", H, context, kv, L * decode),
        ("v_absorb", tok, kv, dv, L * H),
        ("o_proj", tok, H * dv, d, L),
        ("dense_gate_up", tok, d, 2 * cfg.dense_d_ff, cfg.first_dense),
        ("dense_down", tok, cfg.dense_d_ff, d, cfg.first_dense),
        ("router", tok, d, cfg.n_experts, moe),
        ("shared_gate_up", tok, d, 2 * shared, moe),
        ("shared_down", tok, shared, d, moe),
        ("expert_gate_up", per_expert, d, 2 * cfg.moe_d_ff, moe * held),
        ("expert_down", per_expert, cfg.moe_d_ff, d, moe * held),
        ("lm_head_block", decode + 1, d, vblock, ep),
    ]
    return Network(
        f"{cfg.name}-step{prefill}+{decode}x{context}-ep{ep}",
        tuple((GEMMWorkload(n, m, k, nn), c) for n, m, k, nn, c in rows
              if c))
