#!/usr/bin/env python3
"""Bring-up check of the main path on a TPU, in one process.

    python chip_smoke.py            # one chip: tune, train, kernels
    python chip_smoke.py --chips 4  # four chips: concurrent trials only

Phases, in order; any failure ends the run with a non-zero exit:

  device   — the first JAX device must be a TPU of a kind with published
             peaks (``repro.analysis.roofline.CHIPS``); there is no CPU path.
  tune     — real-backend PipeTune + HyperBand jobs through
             ``repro.launch.tune.main`` on lenet-mnist and lstm-news20.
  train    — ``repro.launch.train.main`` on qwen3-0.6b at its published
             widths (bf16, remat=block, B=8, S=1024) for 20 steps: the loss
             must be finite and fall, and the peak bytes fit the device.
  kernels  — flash attention fwd+bwd (qwen3-0.6b widths), mlstm
             (xlstm-350m) and rglru (recurrentgemma-9b), compiled, each
             against ``repro.kernels.ref`` within a stated tolerance.

``--chips 4`` runs only the four-chip phase: the lenet-mnist job at
``--parallelism 1`` (one chip) and ``--parallelism 4`` (trials spread over
four chips); every chip must show trial memory.

The last line of standard output is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``,
printed only when every phase passed.
"""
from __future__ import annotations

import argparse
import gc
import json
import math
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

TUNE_ARGS = ["--backend", "real", "--system", "pipetune",
             "--scheduler", "hyperband", "--epochs", "3"]

# max |kernel - ref| / max |ref| allowed per kernel: bf16 outputs carry
# ~2^-8 relative rounding; rglru is f32 elementwise end to end
TOLERANCE = {"flash_fwd": 2e-2, "flash_bwd": 2e-2, "mlstm": 2e-2,
             "rglru": 1e-3}


class SmokeFailure(Exception):
    pass


def check(cond, msg):
    if not cond:
        raise SmokeFailure(msg)


def phase_device(chips: int):
    import jax
    devs = jax.devices()
    d = devs[0]
    check(d.platform == "tpu",
          f"needs a TPU; JAX found platform {d.platform!r} "
          f"({d.device_kind}, {len(devs)} device(s))")
    check(len(devs) >= chips,
          f"--chips {chips} needs {chips} devices; JAX found {len(devs)}")
    from repro.analysis.roofline import chip_spec
    spec = chip_spec(d.device_kind)      # unknown kind -> KeyError
    print(f"[device] {d.platform} {d.device_kind!r} x{len(devs)}; "
          f"published HBM {spec.hbm_bytes:.3g} B, "
          f"bytes_limit {d.memory_stats()['bytes_limit']} B")
    return devs


def peak_bytes(dev) -> int:
    return int(dev.memory_stats()["peak_bytes_in_use"])


def run_tune(workload: str, extra=()):
    from repro.launch import tune
    t0 = time.perf_counter()
    res = tune.main(TUNE_ARGS + ["--workload", workload, *extra])
    wall = time.perf_counter() - t0
    n = len(res.records)
    print(f"[tune] {workload} {' '.join(extra)}: best accuracy "
          f"{res.best_accuracy:.4f}, {n} trials, ground truth "
          f"{res.gt_hits} hits / {res.gt_misses} misses, compile "
          f"{res.compile_s:.1f}s, wall {wall:.1f}s")
    check(n > 0, f"{workload}: no trials ran")
    check(0.0 <= res.best_accuracy <= 1.0,
          f"{workload}: best accuracy {res.best_accuracy} out of [0, 1]")
    check(res.gt_hits + res.gt_misses == n,
          f"{workload}: {res.gt_hits} hits + {res.gt_misses} misses "
          f"!= {n} trials")
    return res


def phase_tune():
    res = run_tune("lenet-mnist")
    # the synthetic digits are separable: a trained trial is far above
    # the 10-class chance level of 0.1
    check(res.best_accuracy > 0.5,
          f"lenet-mnist best accuracy {res.best_accuracy} <= 0.5")
    run_tune("lstm-news20")


def phase_train(dev):
    from repro.launch import train
    out = train.main(["--arch", "qwen3-0.6b", "--no-reduced",
                      "--steps", "20", "--batch", "8", "--seq", "1024",
                      "--precision", "bf16", "--remat", "block"])
    losses, times = out["losses"], out["step_times"]
    warm = sorted(times[1:])[len(times[1:]) // 2]
    peak, limit = peak_bytes(dev), int(dev.memory_stats()["bytes_limit"])
    print(f"[train] qwen3-0.6b full width B=8 S=1024 bf16 remat=block: "
          f"loss {losses[0]:.4f} -> {losses[-1]:.4f} over {len(losses)} "
          f"steps; first step {times[0]:.2f}s (compile included), warm "
          f"step {warm:.4f}s (median of {len(times) - 1}), "
          f"{8 * 1024 / warm:.0f} tokens/s; peak_bytes_in_use {peak} of "
          f"bytes_limit {limit}")
    check(all(math.isfinite(x) for x in losses), f"non-finite loss {losses}")
    check(losses[-1] < losses[0],
          f"loss did not fall: {losses[0]} -> {losses[-1]}")
    check(peak <= limit, f"peak {peak} B above bytes_limit {limit} B")


def rel_err(out, ref) -> float:
    import jax.numpy as jnp
    out, ref = out.astype(jnp.float32), ref.astype(jnp.float32)
    return float(jnp.max(jnp.abs(out - ref)) / jnp.max(jnp.abs(ref)))


def report(name, err):
    tol = TOLERANCE[name]
    print(f"[kernels] {name}: max|kernel - ref| / max|ref| = {err:.3e} "
          f"(tolerance {tol:g})")
    check(math.isfinite(err) and err <= tol,
          f"{name} error {err} above tolerance {tol}")


def phase_kernels():
    import jax
    import jax.numpy as jnp
    from repro import configs
    from repro.kernels import ops, ref

    def normal(key, shape, dtype=jnp.float32):
        return jax.random.normal(key, shape, jnp.float32).astype(dtype)

    ks = jax.random.split(jax.random.PRNGKey(0), 12)
    hp = jax.default_matmul_precision("highest")

    # flash attention at qwen3-0.6b widths: D=128, K=8 kv heads, G=2
    qc = configs.get_config("qwen3-0.6b")
    B, S, K, D = 8, 1024, qc.n_kv_heads, qc.head_dim
    G = qc.n_heads // K
    q = normal(ks[0], (B, S, K, G, D), jnp.bfloat16)
    k = normal(ks[1], (B, S, K, D), jnp.bfloat16)
    v = normal(ks[2], (B, S, K, D), jnp.bfloat16)
    co = normal(ks[3], (B, S, K, G, D))

    def fa_loss(q, k, v):
        return jnp.sum(ops.flash_attention_fused(q, k, v, interpret=False)
                       .astype(jnp.float32) * co)

    def ref_loss(q, k, v):
        return jnp.sum(ref.attention_direct_ref(q, k, v) * co)

    out = jax.jit(lambda q, k, v: ops.flash_attention_fused(
        q, k, v, interpret=False))(q, k, v)
    f32 = [x.astype(jnp.float32) for x in (q, k, v)]
    with hp:
        want = jax.jit(ref.attention_direct_ref)(*f32)
        grads_want = jax.jit(jax.grad(ref_loss, argnums=(0, 1, 2)))(*f32)
    report("flash_fwd", rel_err(out, want))
    grads = jax.jit(jax.grad(fa_loss, argnums=(0, 1, 2)))(q, k, v)
    report("flash_bwd", max(rel_err(a, b) for a, b in zip(grads, grads_want)))

    # mlstm at xlstm-350m widths: H=4 heads of D=512
    mc = configs.get_config("xlstm-350m").mlstm_cfg()
    H, Dm = mc.n_heads, mc.head_dim
    q, k, v = (normal(ks[4 + i], (B, S, H, Dm), jnp.bfloat16)
               for i in range(3))
    ig = normal(ks[7], (B, S, H))
    fg = normal(ks[8], (B, S, H)) + 2.0
    out = jax.jit(lambda *a: ops.mlstm(*a, interpret=False))(q, k, v, ig, fg)
    with hp:
        want = jax.jit(lambda *a: ref.mlstm_ref(*a)[0])(
            *(x.astype(jnp.float32) for x in (q, k, v)), ig, fg)
    report("mlstm", rel_err(out, want))

    # rglru at recurrentgemma-9b widths: R = d_rnn = 4096
    R = configs.get_config("recurrentgemma-9b").d_rnn
    la = -jnp.abs(normal(ks[9], (B, S, R))) * 0.5
    b = normal(ks[10], (B, S, R))
    h0 = normal(ks[11], (B, R))
    h, _ = jax.jit(lambda *a: ops.rglru(*a, interpret=False))(la, b, h0)
    with hp:
        want, _ = jax.jit(ref.rglru_ref)(la, b, h0)
    report("rglru", rel_err(h, want))


def phase_four_chips(devs):
    # a device that never held a trial keeps the peak it had before any
    # job ran (the runtime's own few KiB)
    devs = devs[:4]
    idle = [peak_bytes(d) for d in devs]
    print(f"[4chips] before any job, peak_bytes_in_use per device: {idle}")
    run_tune("lenet-mnist", ["--parallelism", "1"])
    one = [peak_bytes(d) for d in devs]
    print(f"[4chips] after --parallelism 1, peak_bytes_in_use per device: "
          f"{one}")
    check(one[0] > idle[0] and one[1:] == idle[1:],
          f"--parallelism 1 should use one chip; peaks {one}, idle {idle}")
    run_tune("lenet-mnist", ["--parallelism", "4"])
    four = [peak_bytes(d) for d in devs]
    print(f"[4chips] after --parallelism 4, peak_bytes_in_use per device: "
          f"{four}")
    check(all(p > i for p, i in zip(four, idle)),
          f"a device got no trial; peaks {four}, idle {idle}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the concurrent-trials phase on a "
                         "four-chip host")
    args = ap.parse_args(argv)

    devs = phase_device(args.chips)
    from repro.launch.cache import enable_compile_cache
    print(f"[device] compile cache: {enable_compile_cache()}")
    if args.chips == 4:
        phases = [("4chips", lambda: phase_four_chips(devs))]
    else:
        phases = [("tune", phase_tune), ("train", lambda: phase_train(devs[0])),
                  ("kernels", phase_kernels)]
    for name, fn in phases:
        t0 = time.perf_counter()
        fn()
        gc.collect()
        print(f"[{name}] passed in {time.perf_counter() - t0:.1f}s",
              flush=True)
    d = devs[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind, "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except SmokeFailure as e:
        print(f"chip_smoke FAILED: {e}", file=sys.stderr)
        sys.exit(1)
