"""The harness finds every cell's files by name, and BENCHMARK.json keeps
to the shape its runs rely on."""
import json
import os
import re

import pytest

from chipbench import compare, harness

BENCH = os.path.join(harness.ROOT, "BENCHMARK.json")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


@pytest.fixture(scope="module")
def bench():
    with open(BENCH) as f:
        return json.load(f)


def _cells(bench):
    return [w["name"] for w in bench["workloads"]]


def test_every_cell_resolves(bench):
    for cell in _cells(bench):
        spec = harness.resolve(cell)
        driver = os.path.join(harness.HERE, spec["traffic"]["driver"])
        assert os.path.isfile(driver)
        assert set(spec["limits"]) <= set(compare.TRAIN_CHECKS)
        assert spec["limits"]
        assert os.path.isfile(os.path.join(harness.HERE,
                                           spec["config"]["reference"]))
        assert "setup_s" in spec["end_to_end"]
        assert len(spec["end_to_end"]) >= 2 and spec["per_layer"]


def test_metrics_apply_by_name(bench, tmp_path):
    spec = harness.resolve("train.qwen3-0.6b.s4096")
    assert spec["end_to_end"] == ["train_tokens_per_s", "setup_s"]
    assert spec["per_layer"] == ["train_mfu", "device_idle_share.train"]
    with pytest.raises(KeyError):
        harness.resolve("no.such.cell")
    # a metric without "workloads" applies where its end-to-end metric does
    other = dict(bench, end_to_end=bench["end_to_end"] + [
        {"name": "other_s", "unit": "s", "better": "lower", "bound": 0.1,
         "source": "host_clock", "workloads": ["train.qwen3-0.6b.s1024"]}],
        per_layer=[{"name": "x", "unit": "s", "better": "lower",
                    "source": "host_clock", "layer": "device",
                    "moves": "other_s"}])
    path = tmp_path / "BENCHMARK.json"
    path.write_text(json.dumps(other))
    assert harness.resolve("train.qwen3-0.6b.s1024", str(path))[
        "per_layer"] == ["x"]
    assert harness.resolve("train.qwen3-0.6b.s4096", str(path))[
        "per_layer"] == []


def test_each_metric_has_a_reader_that_finds_nothing_in_nothing(bench):
    empty = harness.Reading(counters={}, summary=None, peaks={},
                            config={}, traffic={})
    for m in bench["per_layer"]:
        reader = harness.load_module(os.path.join(harness.HERE, "metrics",
                                                  m["name"] + ".py"))
        assert reader.read(empty) is None


def test_contract_shape(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert isinstance(bench["run_seconds"], int)
    assert 1 <= bench["run_seconds"] <= 51
    names = ([c["name"] for c in bench["configs"]] + _cells(bench)
             + [m["name"] for m in bench["end_to_end"] + bench["per_layer"]])
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    used = {w["config"] for w in bench["workloads"]}
    assert used == {c["name"] for c in bench["configs"]}
    for c in bench["configs"]:
        assert os.path.isfile(os.path.join(harness.ROOT, c["file"]))
    for w in bench["workloads"]:
        assert w["chips"] in (1, 4) and 0 < len(w["why"]) <= 200
    four = sum(w["chips"] == 4 for w in bench["workloads"])
    assert four <= max(1, len(bench["workloads"]) // 2)
    for m in bench["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    layers = {m["layer"] for m in bench["per_layer"]}
    with open(os.path.join(harness.ROOT, "PERF.md")) as f:
        perf = f.read()
    assert all(f"`{layer}`" in perf for layer in layers)
    assert os.path.getsize(BENCH) <= 64 * 1024
