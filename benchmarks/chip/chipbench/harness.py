"""Runs one cell of ``BENCHMARK.json`` and prints its result line.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

Everything that belongs to one cell is found by name:

* the cell's configuration file is the ``file`` of its ``configs`` entry;
* its traffic mix is ``traffic/<traffic>.json``, which names the driver
  (``drivers/<kind>.py``) that runs it and holds the driver's parameters;
* the limits of its output comparison are ``limits/<cell>.json``;
* each per-layer metric is read by ``metrics/<metric>.py``, whose
  ``read(reading)`` returns a number, or None where it finds nothing.

A driver's ``run(bench)`` builds the system under test from the seed, warms
it up, calls ``bench.setup_done()``, measures inside ``bench.window()``,
reads ``bench.read_memory_peak`` and then compares what the timed path
produced with the plain reference. It returns an :class:`Outcome`.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics, or
with ``--trace 1`` its per-layer ones), ``device``, with ``--trace 1``
``breakdown``, and last ``checks``: each compared number beside its limit.
The same numbers are the last lines of standard error. Without a TPU, or
with fewer chips than the cell asks for, nothing is printed on standard
output and the exit code is 3.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import gc
import importlib.util
import json
import math
import os
import shutil
import sys
import tempfile
import time
import traceback
from typing import Any, Dict, List, Optional, Tuple

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(os.path.dirname(HERE))
CACHE_DIR = os.path.join(ROOT, ".jax_cache")
NO_CHIP = 3


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer than the cell asks for."""


@dataclasses.dataclass
class Check:
    """One number that decides ``correct``: it passes at or under ``limit``."""
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return math.isfinite(self.value) and self.value <= self.limit


@dataclasses.dataclass
class Outcome:
    e2e: Dict[str, float]               # end-to-end metrics the driver took
    attempted: int
    failed: int
    checks: List[Check]
    counters: Dict[str, Any]            # what the per-layer readers read
    devices: List[Any]                  # the jax devices the cell used


@dataclasses.dataclass
class Reading:
    """What a per-layer metric's ``read`` sees."""
    counters: Dict[str, Any]
    summary: Any                        # trace.Summary of the window
    peaks: Dict[str, float]
    config: Dict[str, Any]
    traffic: Dict[str, Any]


def load_module(path: str):
    """Import a file of the benchmark by its path (names such as
    ``device_idle_share.train.py`` are no valid module names)."""
    name = "chipbench_" + os.path.relpath(path, HERE).replace(
        os.sep, "_").replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _read_json(path: str) -> Any:
    with open(path) as f:
        return json.load(f)


def _applies(metric: dict, cell: str, e2e_names: List[str]) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    return metric.get("moves", metric["name"]) in e2e_names


def resolve(cell: str, benchmark_path: str = None) -> dict:
    """Everything ``run`` needs for ``cell``, found by name."""
    bench = _read_json(benchmark_path or os.path.join(ROOT, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if cell not in cells:
        raise KeyError(f"unknown workload {cell!r}; known: {sorted(cells)}")
    w = cells[cell]
    configs = {c["name"]: c for c in bench["configs"]}
    e2e = [m["name"] for m in bench["end_to_end"]
           if "workloads" not in m or cell in m["workloads"]]
    return {
        "cell": w,
        "config": _read_json(os.path.join(ROOT, configs[w["config"]]["file"])),
        "traffic": _read_json(os.path.join(HERE, "traffic",
                                           w["traffic"] + ".json")),
        "limits": _read_json(os.path.join(HERE, "limits", cell + ".json")),
        "end_to_end": e2e,
        "per_layer": [m["name"] for m in bench["per_layer"]
                      if _applies(m, cell, e2e)],
        "units": {m["name"]: m["unit"]
                  for m in bench["end_to_end"] + bench["per_layer"]},
    }


class Bench:
    """The harness as a driver sees it."""

    def __init__(self, spec: dict, seed: int, seconds: float, trace: bool,
                 t0: float, fault: Optional[str] = None):
        self.cell = spec["cell"]
        self.config = spec["config"]
        self.traffic = spec["traffic"]
        self.limits = spec["limits"]
        self.seed, self.seconds, self.trace = seed, seconds, trace
        self.fault = fault              # tests only: break the timed path
        self.t0 = t0
        self.setup_s: Optional[float] = None
        self.memory_peak: Optional[int] = None
        self.trace_dir: Optional[str] = None

    def span(self, name: str):
        """A host span on the profiler's clock, in traced runs only."""
        if not self.trace:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation("bench." + name)

    @contextlib.contextmanager
    def window(self):
        """The measured window; traced when ``--trace 1``.

        What set-up left for the garbage collector (the traced and lowered
        programs, the reference's modules) is collected and frozen first,
        so that no collection of set-up's objects lands in the window: on a
        TPU v5e single host stalls of 0.1 to 0.3 s otherwise moved a 10 s
        window's rate by up to 2.7%."""
        if self.setup_s is None:
            raise RuntimeError("setup_done() was not called before window()")
        gc.collect()
        gc.freeze()
        if not self.trace:
            yield
            return
        import jax
        self.trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-")
        jax.profiler.start_trace(self.trace_dir)
        try:
            with jax.profiler.TraceAnnotation("bench.window"):
                yield
        finally:
            jax.profiler.stop_trace()

    def setup_done(self) -> None:
        self.setup_s = time.monotonic() - self.t0

    def read_memory_peak(self, devices) -> int:
        """Peak bytes in use on the fullest of ``devices`` (0 where the
        backend keeps no count, as the CPU in the tests)."""
        self.memory_peak = max(
            int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
            for d in devices)
        return self.memory_peak


def check_devices(chips: int):
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"needs a TPU; JAX found {devs[0].platform!r} "
                     f"({devs[0].device_kind}, {len(devs)} device(s))")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips; JAX found {len(devs)}")
    return devs


def run(spec: dict, seed: int, seconds: float, trace: bool,
        t0: Optional[float] = None, require_chip: bool = True,
        fault: Optional[str] = None) -> Tuple[dict, dict]:
    """Run one cell; returns the result object (not yet printed) and notes
    for standard error."""
    t0 = time.monotonic() if t0 is None else t0
    import jax
    from chipbench import peaks as peaks_lib
    from chipbench import trace as trace_lib
    if require_chip:
        devs = check_devices(spec["cell"]["chips"])
        peaks = peaks_lib.peaks(devs[0].device_kind)
    else:
        devs = jax.devices()
        peaks = peaks_lib.PEAKS["TPU v5 lite"]
    bench = Bench(spec, seed, seconds, trace, t0, fault=fault)
    driver = load_module(os.path.join(HERE, spec["traffic"]["driver"]))
    try:
        out: Outcome = driver.run(bench)
        summary = None
        if trace:
            ids = [d.id for d in out.devices]
            summary = trace_lib.summarize(trace_lib.load(
                trace_lib.find_xplane(bench.trace_dir), devices=ids))
    finally:
        if bench.trace_dir:
            shutil.rmtree(bench.trace_dir, ignore_errors=True)
    units = spec["units"]
    metrics: Dict[str, dict] = {}
    if not trace:
        values = dict(out.e2e, setup_s=bench.setup_s)
        for name in spec["end_to_end"]:
            if name in values:
                metrics[name] = {"value": values[name], "unit": units[name]}
    else:
        reading = Reading(counters=out.counters, summary=summary,
                          peaks=peaks, config=spec["config"],
                          traffic=spec["traffic"])
        for name in spec["per_layer"]:
            value = load_module(os.path.join(
                HERE, "metrics", name + ".py")).read(reading)
            if value is not None:
                metrics[name] = {"value": value, "unit": units[name]}
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": bench.memory_peak}
    result = {"correct": all(c.ok for c in out.checks) and bool(out.checks),
              "attempted": out.attempted, "failed": out.failed,
              "metrics": metrics, "device": device}
    if trace:
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        result["breakdown"] = {
            "device_ops": [[n, s] for n, s in summary.device_ops],
            "idle_gaps": [[n, s] for n, s in summary.idle_gaps]}
    result["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                        for c in out.checks}
    notes = {"setup_s": bench.setup_s,
             "reference_s": out.counters.get("reference_s", float("nan"))}
    return result, notes


def configure_jax() -> None:
    """Before JAX is imported: the persistent compile cache at a fixed path
    in the checkout, every program cached, so that only a checkout's first
    run of a cell compiles."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "true"
    if os.path.join(ROOT, "src") not in sys.path:
        sys.path.insert(0, os.path.join(ROOT, "src"))


def parse_args(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None, t0: Optional[float] = None) -> int:
    t0 = time.monotonic() if t0 is None else t0
    args = parse_args(argv)
    spec = resolve(args.workload)
    configure_jax()
    try:
        result, notes = run(spec, args.seed, args.seconds, bool(args.trace),
                            t0=t0)
    except NoChip as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return NO_CHIP
    except Exception:                    # noqa: BLE001 - report, no result
        traceback.print_exc()
        return 1
    print(f"chipbench: {args.workload} seed {args.seed}: "
          f"set-up {notes['setup_s']:.2f} s, "
          f"reference {notes['reference_s']:.2f} s", file=sys.stderr)
    for name, c in result["checks"].items():
        verdict = "ok" if math.isfinite(c["value"]) and \
            c["value"] <= c["limit"] else "FAILED"
        print(f"check {name} {c['value']!r} limit {c['limit']!r} {verdict}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0
