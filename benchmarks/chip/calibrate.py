#!/usr/bin/env python3
"""Readings that set the limits of a cell's output comparison.

    python3 benchmarks/chip/calibrate.py --workload <cell> \
        --seeds 1,2,...,12 --control-seeds 1,2,3 --out readings.jsonl
    python3 benchmarks/chip/calibrate.py --workload <cell> \
        --judge readings.jsonl

Run on the chip, at the cell's own sizes; the benchmark's own runs never
run it. For every seed it runs the cell's driver as a run does (with a
window of one step) and records the numbers the comparison reads: the
lower readings. For the control seeds it also records the control, the
reference computed with float8 matmul operands in the program's place,
and the fault that leaves half of every batch out (where a batch has two
rows or more), both against the float32 reference.

Each reading is judged against the cell's ``limits/<cell>.json`` as a run
judges the program: ``correct`` and the numbers over their limits. The
line also counts the leaves that ``update_norm_gap`` compares. One JSON
object per line goes to ``--out`` and to standard output. ``--judge``
judges a file of such lines again against the limits as they stand now,
without a chip.
"""
import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

from chipbench import harness  # noqa: E402


def _ints(text):
    return [int(x) for x in text.split(",") if x]


def verdict(gaps: dict, limits: dict) -> dict:
    """``correct`` of ``gaps`` under ``limits``, as a run decides it, and
    the names of the numbers over their limits."""
    checks = [harness.Check(n, gaps[n], limits[n])
              for n in sorted(limits) if n in gaps]
    return {"correct": bool(checks) and all(c.ok for c in checks),
            "over": [c.name for c in checks if not c.ok]}


def judge(line: dict, limits: dict) -> dict:
    """Every reading of ``line`` judged against ``limits``."""
    return {k: verdict(line[k], limits)
            for k in ("program", "control", "half_batch") if k in line}


def train_extra(bench, out, ref_mod):
    from chipbench import compare, lm
    t, model = bench.traffic, bench.config["model"]
    ref = out.counters["readings"]["reference"]
    pool = lm.token_pool(bench.seed, t["check_steps"], t["batch"], t["seq"],
                         model["vocab"])
    control = ref_mod.train_readings(model, t["optimizer"], bench.seed, pool,
                                     mode="fp8")
    extra = {"control": compare.train_gaps(control, ref)}
    if t["batch"] >= 2:              # a batch of one has no half to leave out
        half = ref_mod.train_readings(model, t["optimizer"], bench.seed,
                                      pool, batch_rows=t["batch"] // 2)
        extra["half_batch"] = compare.train_gaps(half, ref)
    return extra


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=_ints, default=[])
    ap.add_argument("--control-seeds", type=_ints, default=[])
    ap.add_argument("--out")
    ap.add_argument("--judge", metavar="READINGS")
    args = ap.parse_args(argv)
    spec = harness.resolve(args.workload)
    if args.judge:
        with open(args.judge) as f:
            for text in f:
                line = json.loads(text)
                if line.get("workload") == args.workload:
                    print(json.dumps({"seed": line["seed"], "verdicts":
                                      judge(line, spec["limits"])}))
        return 0
    if not (args.seeds and args.out):
        ap.error("--seeds and --out are needed without --judge")
    harness.configure_jax()
    import jax  # noqa: F401
    from chipbench import compare
    harness.check_devices(spec["cell"]["chips"])
    driver = harness.load_module(os.path.join(HERE, spec["traffic"]["driver"]))
    ref_mod = harness.load_module(os.path.join(HERE,
                                               spec["config"]["reference"]))
    with open(args.out, "a") as f:
        for seed in args.seeds:
            t0 = time.monotonic()
            bench = harness.Bench(spec, seed, 0.0, False, t0)
            out = driver.run(bench)
            readings = out.counters["readings"]
            keep = compare.moving(readings["reference"])
            line = {"workload": args.workload, "seed": seed,
                    "program": compare.train_gaps(readings["program"],
                                                  readings["reference"]),
                    "moving_leaves": int(keep.sum()), "leaves": len(keep),
                    "memory_peak_bytes": bench.memory_peak}
            if seed in args.control_seeds:
                line.update(train_extra(bench, out, ref_mod))
            line["verdicts"] = judge(line, spec["limits"])
            line["seconds"] = time.monotonic() - t0
            print(json.dumps(line), flush=True)
            f.write(json.dumps(line) + "\n")
            f.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
