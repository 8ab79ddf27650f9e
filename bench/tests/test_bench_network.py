"""The network cell's files on the CPU: the network reference agrees
with the program's scalar network model, a tiny run of the network
driver is correct, and the float32 control and each network fault in
the program (a layer dropped, every count set to 1) turn ``correct``
false."""
import dataclasses
import json
import os

import pytest

from bench import run
from bench.reference.network import NetworkReference
from bench.tests.test_bench_faults import SEED, _cell

CELL = ("tiny_network", "tiny_netsearch", "network")


def test_reference_matches_the_scalar_network_model():
    from repro.core import GEMMWorkload
    from repro.core.network import Network, evaluate_network
    from repro.core.regions import Region
    from repro.core.techdb import DEFAULT_DB
    from repro.pathfinding import DesignSpace

    with open(os.path.join(run.HERE, "configs", "dsv2step24.json")) as f:
        cfg = json.load(f)
    net = Network(cfg["name"], tuple(
        (GEMMWorkload(w["name"], w["M"], w["K"], w["N"]), w["count"])
        for w in cfg["layers"]))
    for comm, sched in (("mesh_noc", "window"), ("legacy", "fixed")):
        ref = NetworkReference(dict(cfg, comm=comm, schedule=sched))
        space = DesignSpace(comm=comm, schedule=sched)
        for k, row in enumerate(space.sample(12, key=5)):
            ri = (7 * k) % len(cfg["regions"])
            r = cfg["regions"][ri]
            reg = Region(carbon_intensity=r["carbon_intensity"],
                         grid_profile=tuple(r["grid_profile"]))
            m = evaluate_network(space.decode(row), net, dataclasses.replace(
                DEFAULT_DB, **reg.db_overrides()))
            assert ref.vector(row, 0, ri) == (m.latency_s, m.dollar,
                                              m.total_cfp)
        bad = space.sample(1, key=3)[0].copy()
        bad[0] = 7                                  # no 7-chiplet systems
        assert ref.vector(bad, 0, 0) is None


def test_sound_network_run_is_correct():
    res, lines, window = run.run_cell(_cell(*CELL), SEED, 1.0, False,
                                      on_chip=False)
    assert res["correct"], lines
    assert res["failed"] == 0 and res["attempted"] > 0
    assert window["counters"]["layers"] == 4


def test_network_control_is_not_correct():
    res, lines, _ = run.run_cell(_cell(*CELL), SEED + 1, 1.0, False,
                                 on_chip=False, control=True)
    assert not res["correct"], lines
    assert res["checks"]["vec_gap"]["value"] > 1e-9


def _dropped(net):
    return dataclasses.replace(net, layers=net.layers[:-1])


def _ones(net):
    return dataclasses.replace(net, layers=tuple((g, 1)
                                                 for g, _ in net.layers))


@pytest.mark.parametrize("fault", [_dropped, _ones],
                         ids=["layer_dropped", "counts_one"])
def test_network_fault_is_not_correct(fault, monkeypatch):
    """The engine the driver builds runs a broken network; the
    reference reads the configuration's."""
    import repro.pathfinding as pf

    orig = pf.get_scenario_engine
    monkeypatch.setattr(pf, "get_scenario_engine",
                        lambda wls, *a, **k: orig(tuple(map(fault, wls)),
                                                  *a, **k))
    res, lines, _ = run.run_cell(_cell(*CELL), SEED + 2, 1.0, False,
                                 on_chip=False)
    assert not res["correct"], lines
    assert res["checks"]["vec_gap"]["value"] > res["checks"]["vec_gap"][
        "limit"]


class _Trace:
    """Two segment executions of 1 s and 3 s, and 24 kernel operations
    of 2 ms each."""

    def module_runs(self, names):
        assert names == ("jit__run",)
        return [(0.0, 1e9), (2e9, 5e9)]

    def op_count(self, match):
        return 24 if match("prefix_select_gather_blocked", "jit__run") else 0

    def op_time_s(self, match):
        return 24 * 2e-3 if match("_grouped_kernel", "jit__run") else 0.0


def _reader(name):
    return run.load_module(os.path.join(run.HERE, "metrics", name + ".py"),
                           name.replace(".", "_"))


def _run(**counters):
    from types import SimpleNamespace

    c = dict(cells=24, chains=64, layers=18)
    c.update(counters)
    return SimpleNamespace(trace=_Trace(), counters=c,
                           traffic={"segment": 12},
                           peaks={"hbm_bytes_per_s": 819e9})


def test_network_readers_by_hand():
    ns = _reader("ns_per_layer_eval.dsv2step24")
    # 4 s of segment program over 2 x 12 sweeps x 24 x 64 designs x 18
    assert ns.read(_run()) == pytest.approx(4e9 / (2 * 12 * 24 * 64 * 18))
    roof = _reader("prefix_gather_roofline.dsv2step24")
    # 24 launches x 24 x 64 x 18 gathers of 576 bytes in 48 ms
    assert roof.read(_run()) == pytest.approx(
        100 * 24 * 24 * 64 * 18 * 576 / 819e9 / 48e-3)
    # a run without the layer count (the parent's program) reads nothing
    for reader in (ns, roof):
        assert reader.read(_run(layers=0)) is None
