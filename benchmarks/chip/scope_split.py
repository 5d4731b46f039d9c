#!/usr/bin/env python3
"""Device time of a training cell, split by the program's named scopes.

    python3 benchmarks/chip/scope_split.py --workload <cell> \
        --seeds 1,2,3 --seconds 10 --out split.jsonl

Run on the chip; the benchmark's own runs never run it. For every seed it
runs the cell's driver as a ``--trace 1`` run does, reduces the window's
trace as ``chipbench.trace`` does, and splits the leaf operations' seconds
by scope and phase with ``chipbench.scopes`` before the trace is removed.
One JSON object per seed goes to ``--out`` and to standard output:

* ``window_s``, ``busy_s`` and ``leaf_s`` (the leaf operations' seconds,
  which the scopes partition), averaged over devices;
* ``tokens_per_s`` of the traced window;
* ``scopes``: seconds per ``"<scope>/<phase>"``, and ``share_pct``: each
  scope's seconds over the window, in percent;
* ``top_ops`` and ``top_unscoped``: the ten longest operations, and the ten
  longest without a scope, each with its scope, phase and seconds;
* ``reduce_s``: what ``trace`` took to load and summarize the window, and
  ``scopes_s``: what the scope split took on top of it.
"""
import argparse
import json
import os
import shutil
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

from chipbench import harness  # noqa: E402

TOP = 10


def _ints(text):
    return [int(x) for x in text.split(",") if x]


def reduce_trace(path, devices):
    """The window of one ``.xplane.pb``, reduced and split by scope."""
    from chipbench import scopes, trace
    t0 = time.perf_counter()
    tr = trace.load(path, devices=devices)
    summary = trace.summarize(tr)
    t1 = time.perf_counter()
    tf_ops = scopes.load_tf_ops(path)
    seconds = scopes.split(tr, tf_ops)
    t2 = time.perf_counter()
    every_op = trace.summarize(tr, top=sys.maxsize).device_ops
    names = {}
    for plane in tr.devices:
        names.update(tf_ops.get(plane, {}))

    def row(name, sec):
        scope, phase = scopes.classify(names.get(name) or "")
        return [name, scope, phase, sec]

    rows = [row(n, s) for n, s in every_op]
    return {"window_s": summary.window_s, "busy_s": summary.busy_s,
            "leaf_s": sum(s for _, s in every_op),
            "scopes": {f"{k[0]}/{k[1]}": v
                       for k, v in sorted(seconds.items())},
            "share_pct": {k: 100.0 * v / summary.window_s
                          for k, v in scopes.by_scope(seconds).items()},
            "top_ops": rows[:TOP],
            "top_unscoped": [r for r in rows
                             if r[1] == scopes.UNSCOPED][:TOP],
            "reduce_s": t1 - t0, "scopes_s": t2 - t1}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=_ints, required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    spec = harness.resolve(args.workload)
    harness.configure_jax()
    from chipbench import trace
    harness.check_devices(spec["cell"]["chips"])
    driver = harness.load_module(os.path.join(HERE, spec["traffic"]["driver"]))
    with open(args.out, "a") as f:
        for seed in args.seeds:
            bench = harness.Bench(spec, seed, args.seconds, True,
                                  time.monotonic())
            try:
                out = driver.run(bench)
                line = {"workload": args.workload, "seed": seed,
                        "tokens_per_s": out.e2e["train_tokens_per_s"]}
                line.update(reduce_trace(trace.find_xplane(bench.trace_dir),
                                         [d.id for d in out.devices]))
            finally:
                if bench.trace_dir:
                    shutil.rmtree(bench.trace_dir, ignore_errors=True)
            print(json.dumps(line), flush=True)
            f.write(json.dumps(line) + "\n")
            f.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
