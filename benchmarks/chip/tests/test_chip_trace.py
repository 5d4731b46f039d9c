"""The trace reduction, on a small trace recorded on a TPU v5e and on
intervals made up by hand.

``data/probe.xplane.pb``: one ``bench.window`` span holding four
``bench.step`` spans (four calls of a 1024x1024 matmul-and-tanh program,
then a wait for the result) each followed by a ``bench.host_sleep`` span
of 20 ms in which the device has nothing to do.
"""
import os

import pytest

from chipbench import trace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


@pytest.fixture(scope="module")
def probe():
    return trace.load(os.path.join(DATA, "probe.xplane.pb"))


def test_loads_device_ops_and_spans(probe):
    assert list(probe.devices) == ["/device:TPU:0"]
    ops = probe.devices["/device:TPU:0"]
    assert len(ops) == 48                          # 16 calls x 3 operations
    assert {name.split(".")[0] for name, _, _ in ops} == {
        "copy-start", "copy-done", "fusion"}
    names = sorted(name for name, _, _ in probe.spans)
    assert names == ["bench.host_sleep"] * 4 + ["bench.step"] * 4 + [
        "bench.window"]


def test_busy_is_the_union_inside_the_window(probe):
    s = trace.summarize(probe)
    (lo, hi), = [(a, b) for n, a, b in probe.spans if n == "bench.window"]
    assert s.window_s == pytest.approx((hi - lo) * 1e-9)
    # brute force: every nanosecond covered by some operation, clipped
    covered = set()
    for _, a, b in probe.devices["/device:TPU:0"]:
        covered.update(range(max(a, lo), min(b, hi)))
    assert s.busy_s == pytest.approx(len(covered) * 1e-9)
    assert 0 < s.busy_s < 0.01 * s.window_s
    assert s.idle_share == pytest.approx(1 - s.busy_s / s.window_s)


def test_longest_gaps_are_the_host_sleeps(probe):
    s = trace.summarize(probe)
    assert len(s.idle_gaps) == 10
    assert [n for n, _ in s.idle_gaps[:4]] == ["bench.host_sleep"] * 4
    assert all(0.019 < sec < 0.025 for _, sec in s.idle_gaps[:4])
    assert [sec for _, sec in s.idle_gaps] == sorted(
        (sec for _, sec in s.idle_gaps), reverse=True)
    total = sum(sec for _, sec in s.device_ops)
    assert total == pytest.approx(s.busy_s, rel=0.5)


def test_union_and_labels_by_hand():
    assert trace.union([(5, 7), (0, 2), (1, 3), (7, 9)]) == [(0, 3), (5, 9)]
    spans = [("bench.window", 0, 100), ("bench.job", 10, 90),
             ("bench.step", 20, 30)]
    assert trace.label_at(spans, 25) == "bench.step"
    assert trace.label_at(spans, 50) == "bench.job"
    assert trace.label_at(spans, 150) == "(no span)"
    t = trace.Trace(
        devices={"/device:TPU:0": [("a", 10, 30), ("b", 20, 40),
                                   ("a", 60, 70)],
                 "/device:TPU:1": [("a", 0, 100)]},
        spans=[("bench.window", 0, 100), ("bench.wait", 40, 60)])
    s = trace.summarize(t)
    assert s.window_s == pytest.approx(100e-9)
    assert s.busy_s == pytest.approx((40 + 100) / 2 * 1e-9)
    assert s.device_ops[0] == ("a", pytest.approx(65e-9))
    assert s.idle_gaps[:2] == [("bench.window", pytest.approx(30e-9)),
                               ("bench.wait", pytest.approx(20e-9))]


def test_containers_are_not_counted_as_operations():
    ops = [("while.1", 0, 100), ("fusion.1", 0, 40), ("fusion.2", 50, 100),
           ("call.3", 60, 90), ("fusion.4", 60, 70), ("copy.5", 120, 130)]
    assert sorted(n for n, _, _ in trace.leaf_ops(ops)) == [
        "copy.5", "fusion.1", "fusion.4"]
    s = trace.summarize(trace.Trace(devices={"d": ops},
                                    spans=[("bench.window", 0, 200)]))
    assert dict(s.device_ops) == {"fusion.1": pytest.approx(40e-9),
                                  "fusion.4": pytest.approx(10e-9),
                                  "copy.5": pytest.approx(10e-9)}
    assert s.busy_s == pytest.approx(110e-9)


def test_op_names_are_shortened():
    assert trace.op_name("%fusion.12 = f32[8]{0} fusion(%p), kind=kLoop") \
        == "fusion.12"
    assert trace.op_name("plain") == "plain"


def test_a_window_is_required():
    with pytest.raises(ValueError):
        trace.summarize(trace.Trace(devices={"d": [("a", 0, 1)]}, spans=[]))
