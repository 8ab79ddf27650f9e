"""Every per-layer reader in ``bench/metrics`` against traces whose
numbers are known: the small text trace of ``test_bench_trace.py`` (its
``jit_run`` module also renamed to the study's ``jit__run``) and the
trace recorded on one TPU v5e. A change to the reduction must leave
each reading as it is here."""
import json
import os
from types import SimpleNamespace

import pytest

from bench import run
from bench.trace import reduce_trace

DATA = os.path.join(os.path.dirname(__file__), "data")
with open(os.path.join(run.HERE, "peaks.json")) as _f:
    V5E = json.load(_f)["devices"]["TPU v5 lite"]
COUNTERS = dict(cells=2, chains=64, lag_s=[0.01, 0.02, 0.03])
SEGMENT = 12
# the small trace: busy 9 of 14 ms; jit_run 0-4 and 6-10 ms with 2 ms of
# idle between; the kernel once for 1 ms over 2 x 64 designs, which must
# move 128 x (2 x 8 x 4 + 128 x 4) bytes
SMALL_IDLE = 100 * (1 - 9 / 14)
ROOFLINE = 100 * 128 * (2 * 8 * 4 + 128 * 4) / V5E["hbm_bytes_per_s"] / 1e-3
# the v5e trace: busy 0.19312 of 12.31882 ms, no module a reader names
V5E_IDLE = 100 * (1 - 0.00019312 / 0.01231882)
LAG_P95 = 38.0   # statistics.quantiles([10, 20, 30] ms, n=20)[18]

EXPECTED = {
    "small": {
        "device_idle_share.service": SMALL_IDLE,
        "device_idle_share.study": SMALL_IDLE,
        "generator_lag_ms.service": LAG_P95,
        "host_gap_ms.service": 2.0,
        "host_gap_ms.study": None,
        "prefix_gather_roofline.study": ROOFLINE,
        "segment_device_ms_per_sweep.study": None,
    },
    "small_study": {
        "device_idle_share.service": SMALL_IDLE,
        "device_idle_share.study": SMALL_IDLE,
        "generator_lag_ms.service": LAG_P95,
        "host_gap_ms.service": None,
        "host_gap_ms.study": 2.0,
        "prefix_gather_roofline.study": ROOFLINE,
        "segment_device_ms_per_sweep.study": 8.0 / (2 * SEGMENT),
    },
    "v5e": {
        "device_idle_share.service": V5E_IDLE,
        "device_idle_share.study": V5E_IDLE,
        "generator_lag_ms.service": LAG_P95,
        "host_gap_ms.service": None,
        "host_gap_ms.study": None,
        "prefix_gather_roofline.study": None,
        "segment_device_ms_per_sweep.study": None,
    },
}


@pytest.fixture(scope="module")
def traces(tmp_path_factory):
    from jax.profiler import ProfileData

    with open(os.path.join(DATA, "small_trace.pbtxt")) as f:
        text = f.read()
    out = {}
    for name, t in (("small", text),
                    ("small_study", text.replace('"jit_run(1)"',
                                                 '"jit__run(1)"'))):
        path = tmp_path_factory.mktemp(name) / "t.xplane.pb"
        path.write_bytes(ProfileData.text_proto_to_serialized_xspace(t))
        out[name] = reduce_trace(str(path))
    out["v5e"] = reduce_trace(os.path.join(DATA, "v5e_small.xplane.pb"))
    return out


@pytest.mark.parametrize("metric", sorted(EXPECTED["small"]))
def test_every_reader_is_pinned(metric):
    # each pinned reader is still there and pinned on every trace; a new
    # reader file needs no edit here
    assert os.path.isfile(os.path.join(run.HERE, "metrics", metric + ".py"))
    for expected in EXPECTED.values():
        assert metric in expected


@pytest.mark.parametrize("trace, metric", [
    (t, m) for t in EXPECTED for m in sorted(EXPECTED[t])])
def test_reader_value(traces, trace, metric):
    reader = run.load_module(
        os.path.join(run.HERE, "metrics", metric + ".py"),
        "bench_metric_" + metric.replace(".", "_"))
    ctx = SimpleNamespace(trace=traces[trace], counters=COUNTERS,
                          config={}, traffic=dict(segment=SEGMENT),
                          peaks=V5E)
    want = EXPECTED[trace][metric]
    got = reader.read(ctx)
    if want is None:
        assert got is None
    else:
        assert got == pytest.approx(want, rel=1e-9)
