"""The split of device time by the program's named scopes
(``chipbench.scopes``), on two traces recorded on a TPU v5e and on
operation names written by hand.

``data/probe.xplane.pb`` (``test_chip_trace.py`` describes it) runs a
program without scopes. ``data/scoped_probe.xplane.pb``: the program's
jitted ``make_train_step`` for ``data/tiny-lm.json`` (AdamW, remat=block,
bf16, one microbatch, state donated) at B=2, S=128, two warm steps, then
one ``bench.window`` span holding two ``bench.step`` spans, each a step
and a read of its loss.
"""
import os
import sys

import pytest

from chipbench import scopes, trace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
PROBE = os.path.join(DATA, "probe.xplane.pb")
SCOPED_PROBE = os.path.join(DATA, "scoped_probe.xplane.pb")
TPU0 = "/device:TPU:0"


@pytest.fixture(scope="module")
def probe():
    return trace.load(PROBE), scopes.load_tf_ops(PROBE)


def test_reads_tf_op_of_each_fusion_and_none_of_copies(probe):
    tr, tf_ops = probe
    assert list(tf_ops) == [TPU0]
    names = tf_ops[TPU0]
    ops = [n for n, _, _ in tr.devices[TPU0]]
    with_op = [n for n in ops if names.get(n)]
    assert len(with_op) == 16 and set(with_op) == {"fusion"}
    assert names["fusion"] == "jit(<lambda>)/dot_general"
    assert names["copy-start"] is None and names["copy-done"] is None


def test_scoped_step_names_every_layer_on_the_chip():
    tr, tf_ops = trace.load(SCOPED_PROBE), scopes.load_tf_ops(SCOPED_PROBE)
    seconds = scopes.split(tr, tf_ops)
    assert sum(seconds.values()) == pytest.approx(_leaf_total(tr), abs=1e-12)
    dense = set(scopes.SCOPES) - {"moe", "recurrence", "grad_accum"}
    assert {scope for scope, _ in seconds} == dense | {"unscoped"}
    for scope in ("attn_core", "attn_proj", "mlp", "norm"):
        assert {p for s, p in seconds if s == scope} == {
            "forward", "backward", "recompute"}
    # the compiler's asynchronous copies carry no op_name
    names = tf_ops[TPU0]
    copies = [n for n, _, _ in tr.devices[TPU0] if n.startswith("copy-")]
    assert copies and all(names.get(n) is None for n in copies)


@pytest.mark.parametrize("op_name,scope,phase", [
    ("jit(train_step)/jvp(layer_scan)/while/body/closed_call/attn_core/"
     "bskgd,btkd->bkgst/dot_general", "attn_core", "forward"),
    ("jit(train_step)/transpose(jvp(layer_scan))/while/body/closed_call/"
     "checkpoint/attn_core/while/body/squeeze", "attn_core", "backward"),
    ("jit(train_step)/transpose(jvp(layer_scan))/while/body/closed_call/"
     "checkpoint/rematted_computation/attn_proj/norm/mul", "norm",
     "recompute"),
    ("jit(train_step)/transpose(jvp(layer_scan))/while/body/dynamic_slice",
     "layer_scan", "backward"),
    ("jit(train_step)/transpose(jvp(head))/bsd,dv->bsv/dot_general", "head",
     "backward"),
    ("jit(train_step)/optimizer/mul", "optimizer", "forward"),
    ("jit(train_step)/while/body/closed_call/grad_accum/add", "grad_accum",
     "forward"),
    ("jit(train_step)/jvp(loss)/reduce_sum", "unscoped", "forward"),
    ("jit(train_step)/transpose(jvp(loss))/mul", "unscoped", "backward"),
    ("jit(train_step)/jvp(xent)/jit(take_along_axis)/gather", "xent",
     "forward"),
    ("jit(train_step)/vmap(moe)/jit(silu)/mul", "moe", "forward"),
    ("jit(<lambda>)/dot_general", "unscoped", "forward"),
    ("", "unscoped", "forward"),
])
def test_classify_by_hand(op_name, scope, phase):
    assert scopes.classify(op_name) == (scope, phase)


def _leaf_total(tr):
    s = trace.summarize(tr, top=sys.maxsize)
    return sum(sec for _, sec in s.device_ops)


def test_scopes_partition_the_probe(probe):
    tr, tf_ops = probe
    seconds = scopes.split(tr, tf_ops)
    assert sum(seconds.values()) == pytest.approx(_leaf_total(tr), abs=1e-12)
    assert set(seconds) == {("unscoped", "forward")}
    assert scopes.by_scope(seconds)["unscoped"] == pytest.approx(
        trace.summarize(tr).busy_s)


def test_scopes_partition_by_hand():
    fwd = "jit(f)/jvp(layer_scan)/while/body/closed_call/mlp/dot_general"
    bwd = "jit(f)/transpose(jvp(layer_scan))/while/body/closed_call/" \
        "checkpoint/attn_core/while/body/dynamic_slice"
    tr = trace.Trace(
        devices={"/device:TPU:0": [("while.1", 0, 100), ("fusion.1", 0, 40),
                                   ("fusion.2", 50, 100), ("copy.3", 120,
                                                           130)],
                 "/device:TPU:1": [("fusion.1", -10, 60),
                                   ("fusion.9", 60, 300)]},
        spans=[("bench.window", 0, 200)])
    tf_ops = {"/device:TPU:0": {"fusion.1": fwd, "fusion.2": bwd,
                                "while.1": fwd, "copy.3": None},
              "/device:TPU:1": {"fusion.1": bwd}}
    seconds = scopes.split(tr, tf_ops)
    assert seconds == {
        ("mlp", "forward"): pytest.approx(40 / 2 * 1e-9),
        ("attn_core", "backward"): pytest.approx((50 + 60) / 2 * 1e-9),
        ("unscoped", "forward"): pytest.approx((10 + 140) / 2 * 1e-9)}
    assert sum(seconds.values()) == pytest.approx(_leaf_total(tr), abs=1e-15)
    per_scope = scopes.by_scope(seconds)
    assert set(per_scope) == set(scopes.SCOPES) | {"unscoped"}
    assert per_scope["optimizer"] == 0.0


def test_a_window_is_required():
    with pytest.raises(ValueError):
        scopes.split(trace.Trace(devices={"d": [("a", 0, 1)]}, spans=[]), {})


def test_the_split_tool_reduces_a_recorded_trace():
    from chipbench import harness
    tool = harness.load_module(os.path.join(harness.HERE, "scope_split.py"))
    line = tool.reduce_trace(PROBE, None)
    assert sum(line["scopes"].values()) == pytest.approx(line["leaf_s"],
                                                         abs=1e-12)
    assert line["busy_s"] == pytest.approx(trace.summarize(
        trace.load(PROBE)).busy_s)
    assert line["top_ops"][0][:3] == ["fusion", "unscoped", "forward"]
    assert all(r[1] == "unscoped" for r in line["top_unscoped"])
    assert line["share_pct"]["unscoped"] == pytest.approx(
        100 * line["leaf_s"] / line["window_s"])
    assert line["reduce_s"] > 0 and line["scopes_s"] > 0
