"""Device time of the train step split among the program's named scopes.

The program names each layer of its train step with ``jax.named_scope``
(``embed``, ``cast``, ``norm``, ``attn_proj``, ``attn_core``, ``mlp``,
``moe``, ``recurrence``, ``layer_scan``, ``head``, ``xent``,
``grad_accum``, ``optimizer``). JAX writes the scope path into each HLO
instruction's ``op_name``, and the profiler copies a fused operation's
``op_name`` into the ``tf_op`` stat of its event metadata in the
``.xplane.pb``. ``ProfileData`` does not expose those stats, so
:func:`load_tf_ops` reads them from the protobuf wire format itself.

An operation belongs to the innermost component of its ``op_name`` that is
a known scope, after ``jvp(...)``, ``transpose(...)`` and ``vmap(...)`` are
unwrapped: JAX renders ``transpose(jvp(layer_scan))`` for a scope that the
backward pass entered. A component that unwraps to no known scope, such as
``jvp(loss)`` for a differentiated function, is skipped. Operations without
a ``tf_op`` (the compiler's own copies) or with no known scope count as
``unscoped``. The phase is ``recompute`` inside ``rematted_computation``,
else ``backward`` under ``transpose(``, else ``forward``.

:func:`split` takes the leaf operations of each device, as
``trace.summarize`` does, clips them to ``bench.window`` and averages the
seconds over devices; the seconds of all scopes, ``unscoped`` included, add
up to the leaf operations' total.
"""
from __future__ import annotations

import collections
import re
from typing import Dict, Iterator, Optional, Tuple

from chipbench import trace as trace_lib

SCOPES = ("embed", "cast", "norm", "attn_proj", "attn_core", "mlp", "moe",
          "recurrence", "layer_scan", "head", "xent", "grad_accum",
          "optimizer")
UNSCOPED = "unscoped"
TF_OP = "tf_op"

_WRAPPER = re.compile(r"^(?:jvp|transpose|vmap)\((.*)\)$")


def classify(op_name: str) -> Tuple[str, str]:
    """``(scope, phase)`` of one ``op_name`` (or ``tf_op`` value)."""
    scope = UNSCOPED
    for part in reversed(op_name.split("/")):
        m = _WRAPPER.match(part)
        while m:
            part = m.group(1)
            m = _WRAPPER.match(part)
        if part in SCOPES:
            scope = part
            break
    if "rematted_computation" in op_name:
        phase = "recompute"
    elif "transpose(" in op_name:
        phase = "backward"
    else:
        phase = "forward"
    return scope, phase


# --- protobuf wire format (tsl/profiler/protobuf/xplane.proto) -------------
# XSpace.planes = 1; XPlane.name = 2, .event_metadata = 4 (map<int64,
# XEventMetadata>), .stat_metadata = 5 (map<int64, XStatMetadata>);
# XEventMetadata.name = 2, .display_name = 4, .stats = 5;
# XStatMetadata.id = 1, .name = 2; XStat.metadata_id = 1, .str_value = 5,
# .ref_value = 7 (the id of a stat metadata whose name is the string).

def _varint(buf: bytes, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf: bytes) -> Iterator[Tuple[int, object]]:
    """``(field number, value)``: an int for varints and fixed widths, the
    bytes of a length-delimited field."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        num, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 1:
            value, i = int.from_bytes(buf[i:i + 8], "little"), i + 8
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire == 5:
            value, i = int.from_bytes(buf[i:i + 4], "little"), i + 4
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        yield num, value


def _map_values(entries):
    for entry in entries:
        for num, value in _fields(entry):
            if num == 2:
                yield value


def _plane_tf_ops(plane: bytes) -> Tuple[str, Dict[str, Optional[str]]]:
    name, events, stat_entries = "", [], []
    for num, value in _fields(plane):
        if num == 2:
            name = value.decode()
        elif num == 4:
            events.append(value)
        elif num == 5:
            stat_entries.append(value)
    stat_names: Dict[int, str] = {}
    for meta in _map_values(stat_entries):
        f = dict(_fields(meta))
        stat_names[f.get(1, 0)] = f.get(2, b"").decode()
    tf_ops: Dict[str, Optional[str]] = {}
    for meta in _map_values(events):
        ev_name, display, tf_op = "", "", None
        for num, value in _fields(meta):
            if num == 2:
                ev_name = value.decode(errors="replace")
            elif num == 4:
                display = value.decode(errors="replace")
            elif num == 5:
                stat = dict(_fields(value))
                if stat_names.get(stat.get(1)) != TF_OP:
                    continue
                if 5 in stat:
                    tf_op = stat[5].decode(errors="replace")
                elif 7 in stat:
                    tf_op = stat_names.get(stat[7])
        for key in {ev_name, display} - {""}:
            if key in tf_ops and tf_ops[key] != tf_op:
                tf_op = None             # two operations share the name
            tf_ops[key] = tf_op
    return name, tf_ops


def load_tf_ops(path: str) -> Dict[str, Dict[str, Optional[str]]]:
    """Device plane name -> {operation name, as ``trace.load`` shortens it
    -> ``op_name`` from its ``tf_op`` stat, or None where it has none}."""
    with open(path, "rb") as f:
        space = f.read()
    out: Dict[str, Dict[str, Optional[str]]] = {}
    for num, plane in _fields(space):
        if num != 1:
            continue
        name, tf_ops = _plane_tf_ops(plane)
        if not name.startswith(trace_lib.DEVICE_PREFIX):
            continue
        short = out.setdefault(name, {})
        for key, tf_op in tf_ops.items():
            if tf_op is not None:
                tf_op = tf_op.rsplit(":", 1)[0]  # "<op_name>:<op type>"
            op = trace_lib.op_name(key)
            if op in short and short[op] != tf_op:
                tf_op = None
            short[op] = tf_op
    return out


def split(trace: trace_lib.Trace,
          tf_ops: Dict[str, Dict[str, Optional[str]]]
          ) -> Dict[Tuple[str, str], float]:
    """Seconds of leaf operations per ``(scope, phase)`` inside the
    ``bench.window`` span, averaged over the trace's devices."""
    windows = [(s, e) for name, s, e in trace.spans
               if name == trace_lib.WINDOW_SPAN]
    if len(windows) != 1:
        raise ValueError(f"expected one {trace_lib.WINDOW_SPAN} span, found "
                         f"{len(windows)}")
    lo, hi = windows[0]
    n = len(trace.devices)
    out: Dict[Tuple[str, str], float] = collections.defaultdict(float)
    for plane, ops in trace.devices.items():
        names = tf_ops.get(plane, {})
        for name, s, e in trace_lib.leaf_ops(ops):
            s, e = max(s, lo), min(e, hi)
            if e > s:
                out[classify(names.get(name) or "")] += (e - s) / n * 1e-9
    return dict(out)


def by_scope(seconds: Dict[Tuple[str, str], float]) -> Dict[str, float]:
    """``split``'s seconds summed over phases, for every scope and
    ``unscoped`` (0 where none ran)."""
    out = dict.fromkeys(SCOPES + (UNSCOPED,), 0.0)
    for (scope, _), sec in seconds.items():
        out[scope] += sec
    return out
