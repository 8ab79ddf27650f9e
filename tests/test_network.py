"""Whole networks as workloads (``repro.core.network``): the scalar
model, the batched evaluator, the device engine's layer axis and a
``ScenarioSweep`` over a network, and the DeepSeek-V2 serving-step
builder against the benchmark's configuration.

A network runs its layers in turn on one system: per-GEMM terms summed
with their counts in list order, system terms taken once. A one-layer
network of count 1 is ``evaluate()`` bit for bit; the engine's
``vmap``-ped layer axis (jnp gathers and the layer-blocked gather kernel
in interpret mode) agrees with the scalar network model to the device
parity tolerance."""
import dataclasses
import json
import os

import numpy as np
import pytest

from repro.configs import deepseek_v2_236b
from repro.core import WORKLOADS, evaluate
from repro.core.network import Network, deepseek_v2_step, evaluate_network
from repro.core.regions import Region
from repro.core.techdb import DEFAULT_DB
from repro.pathfinding import DesignSpace, ScalarizationSweep, ScenarioSweep
from repro.pathfinding.batch import evaluate_batch
from repro.pathfinding.device import ScenarioEngine

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
STEP = deepseek_v2_236b.serving_step()
# q_absorb, attn_qk_prefill, attn_pv_prefill, attn_qk_decode
CUT = Network("dsv2-cut", STEP.layers[3:7])
# router, shared_gate_up, shared_down: another layer count (padding)
CUT2 = Network("dsv2-moe-cut", STEP.layers[12:15])
MESH = DesignSpace(comm="mesh_noc", schedule="window")
DB = dataclasses.replace(DEFAULT_DB, electricity_price=0.12)
FIELDS = ("latency_s", "energy_j", "area_mm2", "dollar", "emb_cfp_kg",
          "ope_cfp_kg", "l_compute_rd_s", "l_d2d_s", "l_dram_wr_s",
          "e_compute_j", "e_d2d_j", "d2d_bits", "macs")


@pytest.mark.parametrize("comm,schedule", [("legacy", "fixed"),
                                           ("mesh_noc", "window")])
def test_one_layer_network_is_evaluate(comm, schedule):
    sp = DesignSpace(comm=comm, schedule=schedule)
    for k, row in enumerate(sp.sample(24, key=7)):
        system, wl = sp.decode(row), WORKLOADS[k % len(WORKLOADS)]
        assert evaluate_network(system, Network.single(wl), DB) == \
            evaluate(system, wl, DB)


def test_network_is_the_hand_sum_of_its_layers():
    net = Network("mix", ((WORKLOADS[0], 3), (WORKLOADS[3], 1),
                          (WORKLOADS[0], 2)))
    for row in MESH.sample(12, key=9):
        system = MESH.decode(row)
        per = [(c, evaluate(system, g, DB)) for g, c in net.layers]
        m = evaluate_network(system, net, DB)
        lat = e_dyn = 0.0
        for c, t in per:
            lat += c * t.latency_s
            e_dyn += c * (t.e_compute_j + t.e_d2d_j)
        for f in ("l_compute_rd_s", "l_d2d_s", "l_dram_wr_s",
                  "e_compute_j", "e_d2d_j"):
            hand = 0.0
            for c, t in per:
                hand += c * getattr(t, f)
            assert getattr(m, f) == hand, f
        assert m.latency_s == lat
        static = 0.0
        for ch in system.chiplets:
            static += ch.static_power_w(DB)
        assert m.energy_j == e_dyn + static * lat
        assert m.macs == sum(c * t.macs for c, t in per)
        assert m.d2d_bits == sum(c * t.d2d_bits for c, t in per)
        # taken once: the system's own
        assert m.area_mm2 == per[0][1].area_mm2
        assert m.emb_cfp_kg == per[0][1].emb_cfp_kg
        # operational CFP and the bill are linear in the total energy
        one = per[0][1]
        assert m.ope_cfp_kg == pytest.approx(
            one.ope_cfp_kg * m.energy_j / one.energy_j, rel=1e-12)
        two = per[1][1]
        slope = (two.dollar - one.dollar) / (two.energy_j - one.energy_j)
        assert m.dollar == pytest.approx(
            one.dollar + slope * (m.energy_j - one.energy_j), rel=1e-9)


def test_batch_network_matches_the_scalar_model():
    rows = MESH.sample(40, key=3)
    mb = evaluate_batch(rows, CUT, DEFAULT_DB, space=MESH)
    for k, row in enumerate(rows):
        m, b = evaluate_network(MESH.decode(row), CUT), mb.row(k)
        for f in FIELDS:
            np.testing.assert_allclose(getattr(b, f), getattr(m, f),
                                       rtol=1e-12, err_msg=f)


@pytest.mark.parametrize("use_pallas", [False, True])
def test_engine_layer_axis_matches_the_scalar_model(use_pallas):
    """2 cells x 16 designs of a 4-layer and a 3-layer network (so one
    cell's layer table is padded) through the engine's stacked program,
    against ``evaluate_network`` under each cell's region."""
    eng = ScenarioEngine((CUT, CUT2), space=MESH, use_pallas=use_pallas)
    assert eng.layers == 4
    S, n = 2, 16
    regions = [Region(carbon_intensity=0.3,
                      grid_profile=tuple(0.2 + 0.01 * h for h in range(24))),
               Region(carbon_intensity=0.05)]
    enc = MESH.sample(S * n, key=11).reshape(S, n, -1)
    _, vec = eng.evaluate_cost(
        enc, np.zeros((S, 6)), np.ones((S, 6)), np.ones((S, 6)),
        [r.carbon_intensity for r in regions], [0, 1],
        profile=np.stack([r.profile_array() for r in regions]))
    for s, (net, reg) in enumerate(zip((CUT, CUT2), regions)):
        db = dataclasses.replace(DEFAULT_DB, **reg.db_overrides())
        for row, got in zip(enc[s], vec[s]):
            m = evaluate_network(MESH.decode(row), net, db)
            np.testing.assert_allclose(
                got, [m.latency_s, m.dollar, m.total_cfp], rtol=1e-12)


def test_scenario_sweep_searches_a_network():
    """``ScenarioSweep.run`` over a network: each cell's best metrics are
    ``evaluate_network``'s, its frontier vectors agree with it."""
    net = Network("pair", ((WORKLOADS[0], 3), (WORKLOADS[3], 2)))
    sweep = ScenarioSweep(
        strategy=ScalarizationSweep(directions=2, n_chains=2, sweeps=2),
        regions={"clean": 0.05, "dirty": 0.8}, norm_samples=40,
        use_pallas=False)
    sf = sweep.run([net], key=3)
    space = DesignSpace()
    for (name, region), res in sf.results.items():
        assert name == "pair"
        db = dataclasses.replace(DEFAULT_DB,
                                 carbon_intensity=sweep.regions[region])
        assert res.best_metrics == evaluate_network(res.best, net, db)
        for row, got in zip(res.frontier.encoded, res.frontier.vectors):
            m = evaluate_network(space.decode(row), net, db)
            np.testing.assert_allclose(
                got, [m.latency_s, m.dollar, m.total_cfp], rtol=1e-12)
    with pytest.raises(ValueError, match="device path"):
        sweep.run([net], device=False)


def test_builder_reproduces_the_benchmark_configuration():
    with open(os.path.join(ROOT, "bench", "configs",
                           "dsv2step24.json")) as f:
        cfg = json.load(f)
    mc = deepseek_v2_236b.CONFIG
    ep = cfg["expert_parallel"]
    net = deepseek_v2_step(mc, ep=ep, **cfg["step"])
    assert net == STEP
    assert cfg["layers"] == [dict(name=g.name, M=g.M, K=g.K, N=g.N, count=c)
                             for g, c in net.layers]
    # every width as published; the routed experts are this package's
    assert (cfg["hidden_size"], cfg["num_hidden_layers"],
            cfg["num_attention_heads"], cfg["q_lora_rank"],
            cfg["kv_lora_rank"], cfg["qk_nope_head_dim"],
            cfg["qk_rope_head_dim"], cfg["v_head_dim"],
            cfg["moe_intermediate_size"], cfg["intermediate_size"],
            cfg["n_shared_experts"], cfg["num_experts_per_tok"],
            cfg["vocab_size"], cfg["first_k_dense_replace"]) == (
        mc.d_model, mc.n_layers, mc.n_heads, mc.q_lora_rank,
        mc.kv_lora_rank, mc.qk_nope_head_dim, mc.qk_rope_head_dim,
        mc.v_head_dim, mc.moe_d_ff, mc.dense_d_ff, mc.n_shared_experts,
        mc.top_k, mc.vocab, mc.first_dense)
    assert cfg["n_routed_experts_published"] == mc.n_experts
    assert cfg["n_routed_experts"] * ep == mc.n_experts
    assert cfg["reduced"] == ["n_routed_experts"]
    # attention takes ~93% of the step's MACs
    att = sum(c * g.macs for g, c in net.layers
              if g.name.startswith(("attn", "q_absorb", "v_absorb")))
    assert 0.92 < att / net.macs < 0.94


SMALL = dataclasses.replace(
    deepseek_v2_236b.CONFIG, name="small-moe", n_layers=3, d_model=64,
    n_heads=4, vocab=1024, q_lora_rank=48, kv_lora_rank=32,
    qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16, n_experts=16,
    top_k=2, moe_d_ff=24, dense_d_ff=96)


@pytest.mark.parametrize("cfg,ep,step", [
    (deepseek_v2_236b.CONFIG, 8, dict(prefill=512, decode=128,
                                      context=32768)),
    (SMALL, 4, dict(prefill=8, decode=4, context=64))])
def test_expert_shares_add_up_to_the_uncut_layer(cfg, ep, step):
    """The ``ep`` packages' MoE layers (router, shared and routed
    experts, each package over its own tokens) add up to the uncut
    layer over the group's tokens with every expert held."""
    net = deepseek_v2_step(cfg, ep=ep, **step)
    moe_layers = cfg.n_layers - cfg.first_dense
    names = ("router", "shared_gate_up", "shared_down", "expert_gate_up",
             "expert_down")
    share = sum(c * g.macs for g, c in net.layers if g.name in names)
    assert share % moe_layers == 0
    tokens = ep * (step["prefill"] + step["decode"])
    d, f = cfg.d_model, cfg.moe_d_ff
    uncut = (tokens * d * cfg.n_experts
             + tokens * 3 * d * cfg.n_shared_experts * f
             + tokens * cfg.top_k * 3 * d * f)
    assert ep * share // moe_layers == uncut
