"""Reduction from a profiler trace to the benchmark's device numbers.

The JAX profiler writes ``<dir>/plugins/profile/<run>/<host>.xplane.pb``.
Each TPU is a plane named ``/device:TPU:<n>``; its ``XLA Ops`` line holds
one event per operation that ran. The host plane carries the benchmark's
own spans, ``jax.profiler.TraceAnnotation``s whose names start with
``bench.``, on the same clock.

On a TPU v5e the device's timestamps read about 1.3 ms behind the host's
(``tests/data/probe.xplane.pb``); over windows of seconds that moves the
busy share by a few hundredths of a percent at most.

Busy time is the union of the operation intervals of one device, clipped
to the ``bench.window`` span, averaged over the devices the cell uses. Idle
gaps are what the union leaves out, each labelled with the innermost
benchmark span open at its middle (``bench.window`` itself where the host
was in no finer span).
"""
from __future__ import annotations

import collections
import dataclasses
import glob
import os
from typing import Dict, List, Optional, Sequence, Tuple

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"

Interval = Tuple[str, int, int]          # (name, start_ns, end_ns)


@dataclasses.dataclass
class Trace:
    devices: Dict[str, List[Interval]]   # device plane name -> operations
    spans: List[Interval]                # benchmark spans, any host thread


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float                        # averaged over devices
    n_devices: int
    device_ops: List[Tuple[str, float]]  # top leaf operations, s per device
    idle_gaps: List[Tuple[str, float]]   # longest gaps, labelled

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s


def op_name(text: str) -> str:
    """``%fusion.12 = f32[...] fusion(...)`` -> ``fusion.12``."""
    if text.startswith("%"):
        return text[1:].split(" ", 1)[0]
    return text


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def load(path: str, devices: Optional[Sequence[int]] = None) -> Trace:
    """Read the operations of the TPUs numbered in ``devices`` (all when
    None) and the benchmark's spans from one ``.xplane.pb``."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    ops: Dict[str, List[Interval]] = {}
    spans: List[Interval] = []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            if devices is not None and \
                    int(plane.name[len(DEVICE_PREFIX):]) not in devices:
                continue
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops[plane.name] = [
                        (op_name(e.name), int(e.start_ns),
                         int(e.start_ns + e.duration_ns))
                        for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append((e.name, int(e.start_ns),
                                      int(e.start_ns + e.duration_ns)))
    return Trace(devices=ops, spans=spans)


def union(intervals: Sequence[Tuple[int, int]]) -> List[Tuple[int, int]]:
    """Merge overlapping (start, end) intervals; the result is sorted."""
    out: List[List[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def leaf_ops(ops: Sequence[Interval]) -> List[Interval]:
    """The operations that contain no other operation: a ``while`` or
    ``call`` event spans the events of its body on the same line."""
    order = sorted(ops, key=lambda o: (o[1], -o[2]))
    containers = set()
    stack: List[int] = []
    for i, (_, s, e) in enumerate(order):
        while stack and order[stack[-1]][2] <= s:
            stack.pop()
        if stack and e <= order[stack[-1]][2]:
            containers.add(stack[-1])
        stack.append(i)
    return [o for i, o in enumerate(order) if i not in containers]


def label_at(spans: Sequence[Interval], t: float) -> str:
    """The innermost (latest started) benchmark span open at ``t``."""
    best = None
    for name, s, e in spans:
        if s <= t < e and (best is None or s > best[1]):
            best = (name, s)
    return best[0] if best else "(no span)"


def summarize(trace: Trace, top: int = 10) -> Summary:
    windows = [(s, e) for name, s, e in trace.spans if name == WINDOW_SPAN]
    if len(windows) != 1:
        raise ValueError(f"expected one {WINDOW_SPAN} span, found "
                         f"{len(windows)}")
    if not trace.devices:
        raise ValueError("the trace holds no TPU operations")
    lo, hi = windows[0]
    n = len(trace.devices)
    busy = 0
    per_op: Dict[str, float] = collections.defaultdict(float)
    gaps: List[Tuple[int, int]] = []
    for ops in trace.devices.values():
        clipped = _clip([(s, e) for _, s, e in ops], lo, hi)
        merged = union(clipped)
        busy += sum(e - s for s, e in merged)
        for name, s, e in leaf_ops(ops):
            s, e = max(s, lo), min(e, hi)
            if e > s:
                per_op[name] += (e - s) / n
        prev = lo
        for s, e in merged:
            if s > prev:
                gaps.append((prev, s))
            prev = e
        if hi > prev:
            gaps.append((prev, hi))
    gaps.sort(key=lambda g: g[0] - g[1])
    idle = [(label_at(trace.spans, (s + e) / 2), (e - s) * 1e-9)
            for s, e in gaps[:top]]
    ops_top = sorted(per_op.items(), key=lambda kv: -kv[1])[:top]
    return Summary(window_s=(hi - lo) * 1e-9, busy_s=busy / n * 1e-9,
                   n_devices=n,
                   device_ops=[(k, v * 1e-9) for k, v in ops_top],
                   idle_gaps=idle)
