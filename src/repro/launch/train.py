"""Training launcher: real training of a (reduced or custom) arch on local
devices, with checkpoint/restart and optional PipeTune system tuning.

    PYTHONPATH=src python -m repro.launch.train --arch qwen3-0.6b \
        --steps 100 --ckpt /tmp/ck

``--no-reduced`` trains the published widths instead of the smoke config.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs
from repro.checkpoint import CheckpointManager
from repro.data import synthetic
from repro.launch import steps as steps_lib
from repro.launch.cache import enable_compile_cache
from repro.launch.sysargs import (add_kernel_db_arg, add_system_args,
                                  install_kernel_db_from_args,
                                  system_config_from_args)
from repro.optim import optimizers


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-0.6b")
    ap.add_argument("--reduced", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="train the family-preserving smoke config "
                         "(default); --no-reduced trains the published one")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=3e-4)
    add_system_args(ap)
    add_kernel_db_arg(ap)   # tuned kernel configs from a prior tune run
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--ckpt-every", type=int, default=25)
    ap.add_argument("--resume", action="store_true")
    return ap.parse_args(argv)


def main(argv=None) -> dict:
    """Train; returns ``{"losses", "step_times"}`` for the steps run, each
    step timed on the host clock until its loss is back on the host (the
    first step's time includes compiling)."""
    args = parse_args(argv)
    enable_compile_cache()
    cfg = configs.get_reduced(args.arch) if args.reduced \
        else configs.get_config(args.arch)
    if steps_lib.is_encdec(cfg):
        raise SystemExit("use whisper paths via examples; train.py covers LM")
    install_kernel_db_from_args(args)
    sys = system_config_from_args(args)
    opt = optimizers.adamw(
        optimizers.warmup_cosine(args.lr, 10, args.steps), weight_decay=0.01)
    step_fn = jax.jit(steps_lib.make_train_step(cfg, sys, opt),
                      donate_argnums=(0,))
    state = steps_lib.make_train_state(jax.random.PRNGKey(0), cfg, opt)

    mgr = CheckpointManager(args.ckpt, keep=2) if args.ckpt else None
    start = 0
    if mgr and args.resume:
        restored, meta = mgr.restore(jax.eval_shape(lambda: state))
        if restored is not None:
            state, start = restored, meta["step"]
            print(f"resumed from step {start}")

    toks = synthetic.make_lm_dataset(0, args.batch * args.seq * 32, cfg.vocab)
    stream = toks[:args.batch * args.seq * 32].reshape(-1, args.batch,
                                                       args.seq)
    losses, step_times = [], []
    loss = float("nan")
    for step in range(start, args.steps):
        chunk = stream[step % len(stream)]
        batch = {"tokens": jnp.asarray(chunk),
                 "labels": jnp.asarray(np.roll(chunk, -1, -1))}
        t0 = time.perf_counter()
        state, m = step_fn(state, batch)
        loss = float(m["loss"])
        step_times.append(time.perf_counter() - t0)
        losses.append(loss)
        if mgr and (step + 1) % args.ckpt_every == 0:
            mgr.save(step + 1, state, metadata={"step": step + 1})
        if step == start or (step + 1) % 10 == 0:
            print(f"step {step+1:4d} loss={loss:.4f} "
                  f"({step_times[-1]:.3f}s this step)")
    if mgr:
        mgr.wait()
    print(f"done: final loss {loss:.4f}")
    return {"losses": losses, "step_times": step_times}


if __name__ == "__main__":
    main()
