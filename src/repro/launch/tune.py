"""HPT-job launcher: run a full PipeTune (or baseline) tuning job.

    PYTHONPATH=src python -m repro.launch.tune --workload lenet-mnist \
        --system pipetune --scheduler hyperband --epochs 9 --parallelism 4

Tuners, backends, and schedulers resolve through the ``repro.api``
registries — ``--system``/``--backend``/``--scheduler`` accept anything
registered there, including third-party plugins imported via ``--plugin``.
"""
from __future__ import annotations

import argparse
import importlib
import json

from repro.api import (Experiment, available_backends, available_executors,
                       available_schedulers, available_tuners)
from repro.core import SearchSpace
from repro.core.job import HPTJob, Param
from repro.launch.cache import enable_compile_cache
from repro.launch.sysargs import (add_executor_args, add_kernel_db_arg,
                                  add_store_args, executor_from_args,
                                  install_kernel_db_from_args,
                                  store_client_from_args)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default="lenet-mnist")
    ap.add_argument("--system", default="pipetune",
                    help=f"tuner name; registered: {available_tuners()}")
    ap.add_argument("--scheduler", default="hyperband",
                    help="scheduler name; registered: "
                         f"{available_schedulers()}")
    ap.add_argument("--epochs", type=int, default=6)
    ap.add_argument("--backend", default="real",
                    help=f"backend name; registered: {available_backends()}")
    add_executor_args(ap)   # --executor / --parallelism / --cluster-nodes
    add_store_args(ap)      # --store / --gt-store / --store-reset
    add_kernel_db_arg(ap)   # --kernel-db: tuned kernel configs
    ap.add_argument("--plugin", action="append", default=[],
                    help="module to import for register_* side effects")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    enable_compile_cache()
    for mod in args.plugin:
        importlib.import_module(mod)
    install_kernel_db_from_args(args)

    space = SearchSpace([
        Param("batch_size", "choice", choices=(32, 64, 128)),
        Param("learning_rate", "log", 0.001, 0.1),
        Param("dropout", "float", 0.0, 0.5),
    ])
    job = HPTJob(workload=args.workload, space=space, max_epochs=args.epochs)

    # real trials spread round-robin over as many local devices as run
    # concurrently: --parallelism 4 on a four-chip host uses every chip
    backend_kw = {"n_train": 1024, "n_eval": 256, "steps_per_epoch": 8,
                  "n_devices": args.parallelism} \
        if args.backend == "real" else {}
    tuner_kw = {"max_probes": 4} if args.system == "pipetune" else {}
    sched_kw = {"n_trials": 6} if args.scheduler == "random" else {}

    exp = (Experiment(job)
           .with_tuner(args.system, **tuner_kw)
           .with_backend(args.backend, **backend_kw)
           .with_scheduler(args.scheduler, **sched_kw))
    if args.system == "pipetune" or args.store != "inproc" or \
            args.gt_store or args.store_reset:
        # only attach a store client when the tuner consumes one (or the
        # user asked for a specific store): a v1 job with remote workers
        # must not trip over a ground-truth client it would never use
        exp = exp.with_groundtruth(store_client_from_args(args))
    executor = executor_from_args(args)
    res = exp.run(executor=executor)

    # name the executor actually built: --workers/--coordinator upgrade the
    # default serial choice, and the printout should say so
    print(f"workload={args.workload} system={args.system} "
          f"scheduler={args.scheduler} "
          f"executor={type(executor).__name__} "
          f"(registered: {available_executors()})")
    print(f"  best accuracy : {res.best_accuracy:.4f}")
    print(f"  best hparams  : {res.best_hparams}")
    print(f"  tuning time   : {res.tuning_time_s:.1f}s "
          f"({len(res.records)} trials)")
    if res.sim_time_s:
        print(f"  cluster makespan: {res.sim_time_s:.1f}s simulated")
    print(f"  compile time  : {res.compile_s:.1f}s")
    print(f"  energy        : {res.energy_j/1e3:.1f} kJ "
          "(power-model estimate, not measured)")
    if args.system == "pipetune":
        print(f"  ground truth  : {res.gt_hits} hits / {res.gt_misses} misses")
    if args.out:
        with open(args.out, "w") as f:
            json.dump({"accuracy": res.best_accuracy,
                       "hparams": res.best_hparams,
                       "tuning_time_s": res.tuning_time_s,
                       "energy_j": res.energy_j}, f, indent=1)
    return res


if __name__ == "__main__":
    main()
