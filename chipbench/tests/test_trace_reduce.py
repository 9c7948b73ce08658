"""The reduction from a profiler trace to busy time, idle share, top
device ops and idle gaps: on a hand-made trace whose answers are known,
and on a small trace recorded on a TPU v5e."""

import gzip
import pathlib
import shutil

import pytest

from chipbench import trace_reduce as tr

# 0.05 s of alexnet227_offline on one v5e, traced by chipbench/harness.py
RECORDED = pathlib.Path(__file__).parent / "data" / "v5e_alexnet.xplane.pb.gz"


def _hand_made():
    ms = 1_000_000
    return tr.Trace(
        devices={"/device:TPU:0": [
            ("fusion.1", 10 * ms, 20 * ms),
            ("_kernel", 15 * ms, 30 * ms),      # overlaps fusion.1
            ("fusion.1", 50 * ms, 60 * ms),
            ("copy", 95 * ms, 120 * ms),        # runs past the window
        ]},
        host=[
            ("chipbench.window", 0, 100 * ms),
            ("serve.stage", 30 * ms, 48 * ms),
            ("serve.dispatch", 48 * ms, 50 * ms),
            ("chipbench.wait_arrival", 60 * ms, 95 * ms),
            ("serve.assemble", 0, 9 * ms),
        ])


def test_union_merges_overlaps():
    assert tr.union([(5, 7), (0, 2), (1, 3), (3, 4)]) == [(0, 4), (5, 7)]


def test_busy_idle_and_top_ops():
    out = tr.reduce(_hand_made())
    assert out["window_s"] == pytest.approx(0.100)
    # busy: [10, 30] + [50, 60] + [95, 100] inside the window
    assert out["busy_s"] == pytest.approx(0.035)
    assert out["idle_share"] == pytest.approx(0.65)
    assert out["device_ops"] == [["fusion.1", pytest.approx(0.020)],
                                 ["_kernel", pytest.approx(0.015)],
                                 ["copy", pytest.approx(0.005)]]


def test_idle_gaps_are_named_by_the_covering_span():
    gaps = tr.reduce(_hand_made())["idle_gaps"]
    assert gaps == [["chipbench.wait_arrival", pytest.approx(0.035)],
                    ["serve.stage", pytest.approx(0.020)],
                    ["serve.assemble", pytest.approx(0.010)]]


def test_no_device_plane_is_an_error():
    with pytest.raises(ValueError, match="no device plane"):
        tr.reduce(tr.Trace(devices={}, host=[]))


def test_recorded_v5e_trace(tmp_path):
    path = tmp_path / "trace.xplane.pb"
    with gzip.open(RECORDED) as src, open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    trace = tr.load(str(path))
    assert list(trace.devices) == ["/device:TPU:0"]
    assert any(n == tr.WINDOW_SPAN for n, _, _ in trace.host)
    assert any(n.startswith("serve.") for n, _, _ in trace.host)
    out = tr.reduce(trace)
    assert 0 < out["busy_s"] <= out["window_s"]
    assert 0 <= out["idle_share"] < 1
    names = [n for n, _ in out["device_ops"]]
    assert len(names) == len(set(names)) <= 10
    # conv1+pool1's direct Pallas kernel takes most of AlexNet's device time
    assert names[0] == "%chain_conv.5"
    assert out["device_ops"][0][1] > 0.5 * out["busy_s"]
    assert sum(s for _, s in out["device_ops"]) <= 1.001 * out["busy_s"]
    for span, secs in out["idle_gaps"]:
        assert span == "none" or span.startswith(tr.HOST_PREFIXES)
        assert 0 < secs <= out["window_s"]
