"""The trace reduction on a small trace whose numbers are known: a
device plane with three module executions and four operations, and a
host thread with the window span, one benchmark span and one other.

Times in ms from the window's start (the window is 14 ms): modules
``jit_run`` 0-4 and 6-10, ``jit_init`` 12-13; operations 0-1, 1-4, 6-10
and the kernel 12-13; ``bench.host`` covers 3.5-6.5, ``other.span``
9-10."""
import os

import pytest

from bench.trace import module_base, reduce_trace

DATA = os.path.join(os.path.dirname(__file__), "data")


def _reduce(tmp_path, dispatch_ps=None):
    """The small trace, with a ``bench.dispatch`` span at ``dispatch_ps``
    after the window's start when given."""
    from jax.profiler import ProfileData

    with open(os.path.join(DATA, "small_trace.pbtxt")) as f:
        text = f.read()
    if dispatch_ps is not None:
        text = text.replace(
            "  event_metadata { key: 1 value { id: 1 name: \"bench.window\" } }",
            "  event_metadata { key: 1 value { id: 1 name: \"bench.window\" } }\n"
            "  event_metadata { key: 4 value { id: 4 name: \"bench.dispatch\" } }")
        text = text.replace(
            "    events { metadata_id: 3 offset_ps: 9000000000",
            f"    events {{ metadata_id: 4 offset_ps: {dispatch_ps} "
            "duration_ps: 100000000 }\n"
            "    events { metadata_id: 3 offset_ps: 9000000000")
    path = tmp_path / "small.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(text))
    return reduce_trace(str(path))


@pytest.fixture(scope="module")
def summary(tmp_path_factory):
    return _reduce(tmp_path_factory.mktemp("trace"))


def test_busy_and_idle(summary):
    assert summary.window_s == pytest.approx(0.014, abs=1e-12)
    assert summary.busy_s() == pytest.approx(0.009, abs=1e-12)
    gaps = (summary.idle_gaps() - summary.window[0]) * 1e-6
    assert gaps.ravel().tolist() == pytest.approx([4, 6, 10, 12, 13, 14])


def test_modules_and_gaps(summary):
    assert summary.module_time_s() == {
        "jit_run": (2, pytest.approx(0.008)),
        "jit_init": (1, pytest.approx(0.001))}
    runs = summary.module_runs(["jit_run"])
    assert len(runs) == 2
    assert summary.idle_s_between(runs[0][1], runs[1][0]) == \
        pytest.approx(0.002)
    # the kernel inside jit_init: 1 ms; the window holds no other
    assert summary.op_time_s(lambda name, mod: "_select_kernel" in name) \
        == pytest.approx(0.001)
    assert module_base("jit__run(123)") == "jit__run"


def test_breakdown(summary):
    top = summary.top_ops(2)
    assert top[0][0] == "jit_run/fusion.7" and top[0][1] == \
        pytest.approx(0.007)
    assert len(top) == 2
    # the 4-6 gap's midpoint lies in bench.host; the others in no
    # benchmark span (other.span is not one)
    assert summary.longest_gaps(3) == [
        ["bench.host", pytest.approx(0.002)],
        ["unattributed", pytest.approx(0.002)],
        ["unattributed", pytest.approx(0.001)]]


@pytest.mark.parametrize("dispatch_ms, window_ms", [(12.5, 14), (13.5, 13)])
def test_window_cut_where_device_trace_stops(tmp_path, dispatch_ms,
                                             window_ms):
    """A dispatch after the last device event (13 ms) shows that the
    device trace stopped there; one before it changes nothing."""
    s = _reduce(tmp_path, int(dispatch_ms * 1e9))
    assert s.window_s == pytest.approx(window_ms * 1e-3, abs=1e-12)
    assert s.busy_s() == pytest.approx(0.009, abs=1e-12)


def test_recorded_v5e_trace():
    """A trace recorded on one TPU v5e: three rounds of two jitted steps
    (``jit_step_a``, ``jit_step_b``) inside ``bench.window``, with a
    2 ms ``bench.host`` sleep after each ``step_a``."""
    s = reduce_trace(os.path.join(DATA, "v5e_small.xplane.pb"))
    assert s.n_devices == 1
    assert s.window_s == pytest.approx(0.01231882, rel=1e-9)
    assert s.busy_s() == pytest.approx(0.00019312, rel=1e-9)
    assert [len(s.module_runs([m])) for m in ("jit_step_a", "jit_step_b")] \
        == [3, 3]
    assert sorted(n for n, _ in s.module_time_s().items()) == [
        "jit_step_a", "jit_step_b"]
    gaps = s.longest_gaps(4)
    assert [g[0] for g in gaps[:3]] == ["bench.host"] * 3
    assert gaps[0][1] == pytest.approx(0.003731283, rel=1e-9)
    top = s.top_ops(1)
    assert top[0][0].startswith("jit_step_b/%sort")
