"""The benchmark's files on the CPU: every cell resolves by name, a cell
added by files alone resolves, the stream generator is seeded, the
reference agrees with the program's scalar model, the comparison's
helpers count what they say, and ``run.py`` refuses a machine without a
TPU."""
import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

from bench import arrivals, check, run

ROOT = os.path.dirname(run.HERE)
with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    BENCH = json.load(_f)
NAME_OK = set("abcdefghijklmnopqrstuvwxyzABCDEFGHIJKLMNOPQRSTUVWXYZ"
              "0123456789_.-")


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_every_cell_resolves(cell):
    c = run.resolve(BENCH, cell)
    assert os.path.isfile(c.driver)
    assert any(m["name"] == "setup_s" for m in c.end_to_end)
    assert len(c.end_to_end) >= 2 and c.per_layer
    assert set(c.readers) == {m["name"] for m in c.per_layer}
    assert c.traffic["limits"]["invalid_rows"] == 0
    assert c.traffic["limits"]["dominated_rows"] == 0


def test_benchmark_names_and_files():
    keys = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}
    assert set(BENCH) == keys
    names = [e["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for e in BENCH[k]]
    assert len(names) == len(set(names))
    for n in names:
        assert set(n) <= NAME_OK and len(n) <= 64, n
    for c in BENCH["configs"]:
        assert c["file"] == f"bench/configs/{c['name']}.json"
        with open(os.path.join(ROOT, c["file"])) as f:
            cfg = json.load(f)
        assert cfg["name"] == c["name"] and cfg["reduced"] == c["reduced"]
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert set(m["unit"]) <= NAME_OK | set("/%")
    assert BENCH["command"] == ["python3", "bench/run.py"]


def test_cell_added_by_files_alone(tmp_path):
    bench_dir = tmp_path / "bench"
    shutil.copytree(run.HERE, bench_dir,
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    data = os.path.join(run.HERE, "tests", "data")
    shutil.copy(os.path.join(data, "tiny_study.json"),
                bench_dir / "configs" / "fixture.json")
    shutil.copy(os.path.join(data, "tiny_search.json"),
                bench_dir / "traffic" / "fixture_mix.json")
    (bench_dir / "metrics" / "fixture_chunks.py").write_text(
        "def read(run):\n    return run.counters.get('chunks')\n")
    bench = json.loads(json.dumps(BENCH))
    bench["configs"].append(dict(name="fixture", source="x", reduced=[],
                                 file="bench/configs/fixture.json", why="x"))
    bench["workloads"].append(dict(name="fixture.cell", config="fixture",
                                   traffic="fixture_mix", chips=1, why="x"))
    bench["end_to_end"][0]["workloads"].append("fixture.cell")
    bench["per_layer"].append(dict(
        name="fixture_chunks", unit="chunks", better="higher",
        source="program_counter", layer="x", moves="designs_per_s",
        workloads=["fixture.cell"]))
    c = run.resolve(bench, "fixture.cell", str(bench_dir))
    assert c.config["name"] == "tiny_study"
    assert c.driver == str(bench_dir / "drivers" / "study.py")
    assert list(c.readers) == ["fixture_chunks"]
    reader = run.load_module(c.readers["fixture_chunks"], "fixture_reader")
    assert reader.read(type("R", (), {"counters": {"chunks": 3}})) == 3
    with pytest.raises(run.BenchError):
        run.resolve(bench, "no.such.cell", str(bench_dir))


def test_arrivals_seeded():
    mix = {"rate_per_s": 30.0, "sweeps": [8, 16, 32], "swap_every": [5, 3]}
    a = arrivals.schedule(mix, 6, 24, 10.0, 2 ** 40 + 7)
    b = arrivals.schedule(mix, 6, 24, 10.0, 2 ** 40 + 7)
    c = arrivals.schedule(mix, 6, 24, 10.0, 2 ** 40 + 8)
    assert np.array_equal(a[0], b[0]) and a[1] == b[1]
    assert not np.array_equal(a[0], c[0]) and a[1] != c[1]
    # the same work in another order
    assert len(a[0]) == len(c[0]) == 300
    q = (np.arange(300) + 0.5) / 300
    quantiles = -np.log1p(-q) / 30.0
    for due, _ in (a, c):
        gaps = np.diff(due)
        hit = np.isclose(gaps[:, None], quantiles[None, :], rtol=0,
                         atol=1e-12).any(axis=1)
        assert hit.all() and len(np.unique(np.round(gaps, 12))) == 299
    assert sorted(a[1]) == sorted(c[1])
    assert a[0][0] == 0.0 and 9.0 < a[0][-1] < 10.0
    sweeps = [s[2] for s in a[1]]
    assert sweeps.count(8) == sweeps.count(16) == sweeps.count(32) == 100


def test_reference_matches_the_scalar_model():
    import dataclasses

    from repro.core import GEMMWorkload, evaluate
    from repro.core.regions import Region
    from repro.core.techdb import DEFAULT_DB
    from repro.pathfinding import DesignSpace

    with open(os.path.join(run.HERE, "configs", "study144.json")) as f:
        cfg = json.load(f)
    for comm, sched in (("mesh_noc", "window"), ("legacy", "fixed")):
        ref = check.Reference(dict(cfg, comm=comm, schedule=sched))
        space = DesignSpace(comm=comm, schedule=sched)
        for k, row in enumerate(space.sample(40, key=11)):
            wi, ri = k % 6, (5 * k) % 24
            w, r = cfg["workloads"][wi], cfg["regions"][ri]
            reg = Region(carbon_intensity=r["carbon_intensity"],
                         grid_profile=tuple(r["grid_profile"]))
            m = evaluate(space.decode(row),
                         GEMMWorkload(w["name"], w["M"], w["K"], w["N"]),
                         dataclasses.replace(DEFAULT_DB,
                                             **reg.db_overrides()))
            assert ref.vector(row, wi, ri) == (m.latency_s, m.dollar,
                                               m.total_cfp)
        bad = space.sample(1, key=3)[0].copy()
        bad[0] = 7                                  # no 7-chiplet systems
        assert ref.vector(bad, 0, 0) is None


def test_comparison_helpers():
    v = np.array([[1.0, 1.0, 1.0], [2.0, 2.0, 2.0], [0.5, 3.0, 1.0],
                  [1.0, 1.0, 1.0 + 1e-12]])
    # row 1 is dominated by row 0; row 3 only within the tolerance
    assert check.dominated_count(v, 1e-10) == 1
    assert check.dominated_count(v, 0.0) == 2
    assert check.rel_gap([1.0, 2.0], [1.0, 2.0 * (1 + 1e-9)]) == \
        pytest.approx(1e-9, rel=1e-6)
    c = check.Check("vec_gap", 2e-10, 1e-10)
    assert not c.ok and check.Check("x", 0.0, 0).ok
    assert not check.Check("x", float("nan"), 1.0).ok


def test_prefix_gather_bytes_by_hand():
    reader = run.load_module(os.path.join(
        run.HERE, "metrics", "prefix_gather_roofline.study.py"), "pg")
    # 9,216 systems = 72 blocks of 128; per system two 8-slot int32 index
    # rows (64 B) and one 128-lane int32 output row (512 B)
    assert reader.prefix_gather_bytes(9216) == 9216 * (64 + 512)
    # 130 systems pad to 256
    assert reader.prefix_gather_bytes(130) == 256 * 576


def _run_py(cwd, env_extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **env_extra)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "study144.search",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_run_refuses_a_machine_without_tpu():
    p = _run_py(ROOT, {})
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_run_refuses_a_directory_without_the_program(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(run.HERE, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run_py(tmp_path, {})
    assert p.returncode != 0 and p.stdout.strip() == ""
