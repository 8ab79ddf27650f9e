"""A run of each driver on the CPU at a tiny size, with the timed path
sound, with the control in its place, and broken underneath: each fault
the one-chip cells can have (``bench.faults``) must turn ``correct``
false.
"""
import json
import os
from types import SimpleNamespace

import pytest

from bench import faults, run

DATA = os.path.join(os.path.dirname(__file__), "data")
SEED = 2 ** 33 + 12345


def _cell(config: str, traffic: str, driver: str):
    with open(os.path.join(DATA, config + ".json")) as f:
        cfg = json.load(f)
    with open(os.path.join(DATA, traffic + ".json")) as f:
        tr = json.load(f)
    return SimpleNamespace(
        name=config, chips=1, config=cfg, traffic=tr,
        driver=os.path.join(run.HERE, "drivers", driver + ".py"),
        end_to_end=[], per_layer=[], readers={})


CELLS = {"study": ("tiny_study", "tiny_search", "study"),
         "service": ("tiny_service", "tiny_poisson", "service")}


@pytest.mark.parametrize("kind", sorted(CELLS))
def test_sound_run_is_correct(kind):
    res, lines, _ = run.run_cell(_cell(*CELLS[kind]), SEED, 2.0, False,
                                 on_chip=False)
    assert res["correct"], lines
    assert res["failed"] == 0 and res["attempted"] > 0
    assert list(res)[-1] == "checks"


@pytest.mark.parametrize("kind", sorted(CELLS))
def test_control_is_not_correct(kind):
    res, lines, _ = run.run_cell(_cell(*CELLS[kind]), SEED + 1, 2.0, False,
                                 on_chip=False, control=True)
    assert not res["correct"], lines
    assert res["checks"]["vec_gap"]["value"] > 1e-9


@pytest.mark.parametrize("fault", faults.FAULTS)
@pytest.mark.parametrize("kind", sorted(CELLS))
def test_fault_is_not_correct(kind, fault):
    with faults.planted(fault):
        res, lines, _ = run.run_cell(_cell(*CELLS[kind]), SEED + 2, 2.0,
                                     False, on_chip=False)
    assert not res["correct"], (fault, lines)
    caught = "vec_gap" if fault == "altered" else "stalled_share"
    assert res["checks"][caught]["value"] > res["checks"][caught]["limit"]
