"""Training cells: a closed loop of the program's own train step.

The step is ``repro.launch.steps.make_train_step`` under ``jax.jit`` with
the state donated, as ``repro.launch.train`` builds it, compiled once in
set-up. The weights are the benchmark's (``chipbench.lm``), made on the
device from the seed; the optimizer is the program's AdamW with the
configuration's settings. Each step uploads its batch from the host and
reads its loss back, as the launcher's loop does.

Set-up drives that same compiled step through the traffic's first
``check_steps`` steps, on the window's own feed, and keeps what the
comparison needs: each step's loss, the first gradient as the optimizer got
it (the first moment after one step, over ``1 - beta1``) and the weights'
change after the last of them. The window then runs on from there for
``--seconds``. After it, with the program's state freed, the reference
trains the same steps from the same weights and batches in float32.

Traffic keys: ``batch``, ``seq``, ``pool_batches`` (host batches drawn
from the seed, cycled), ``check_steps``, ``remat``, ``precision``, ``microbatches`` (the
program's ``SystemConfig``) and ``optimizer``.
"""
from __future__ import annotations

import dataclasses
import gc
import math
import os
import time


def _program_model(model: dict):
    from repro.models.transformer import ModelConfig
    fields = {f.name for f in dataclasses.fields(ModelConfig)}
    return ModelConfig(**{k: v for k, v in model.items() if k in fields})


def _program_optimizer(o: dict):
    from repro.optim import optimizers
    return optimizers.adamw(
        optimizers.warmup_cosine(o["lr"], o["warmup_steps"],
                                 o["total_steps"], o["final_frac"]),
        b1=o["beta1"], b2=o["beta2"], eps=o["eps"],
        weight_decay=o["weight_decay"], clip_norm=o["clip_norm"])


def _same_layout(a, b) -> bool:
    import jax
    la, ta = jax.tree.flatten(a)
    lb, tb = jax.tree.flatten(b)
    return ta == tb and all(x.shape == y.shape and x.dtype == y.dtype
                            for x, y in zip(la, lb))


def _step_with_fault(step, program_step, fault, batch):
    """Tests only: the timed path broken underneath."""
    import jax
    if fault is None:
        return step
    if fault == "unchanged":                # the state comes back as it went
        return jax.jit(lambda s, b: (s, program_step(s, b)[1]))
    if fault == "half_batch":               # the mean over half the rows
        return jax.jit(lambda s, b: program_step(
            s, {k: v[:batch // 2] for k, v in b.items()}),
            donate_argnums=(0,))
    raise ValueError(f"unknown fault {fault!r}")


def run(bench):
    import jax
    import jax.numpy as jnp
    from chipbench import compare, flops, lm
    from chipbench.harness import HERE, Check, Outcome, load_module
    from repro.launch import steps as steps_lib
    from repro.models.transformer import SystemConfig

    model, t = bench.config["model"], bench.traffic
    o = t["optimizer"]
    B, S, n_check = t["batch"], t["seq"], t["check_steps"]
    mc = _program_model(model)
    opt = _program_optimizer(o)
    program_step = steps_lib.make_train_step(
        mc, SystemConfig(remat=t["remat"], precision=t["precision"],
                         microbatches=t["microbatches"]), opt)
    step = _step_with_fault(jax.jit(program_step, donate_argnums=(0,)),
                            program_step, bench.fault, B)

    def make_state(key):
        params = lm.init_params(model, key)
        return {"params": params, "opt": opt.init(params),
                "step": jnp.zeros((), jnp.int32)}

    key = lm.seed_key(bench.seed)
    make_state = jax.jit(make_state)
    if not _same_layout(jax.eval_shape(make_state, key), jax.eval_shape(
            lambda: steps_lib.make_train_state(jax.random.PRNGKey(0), mc,
                                               opt))):
        raise ValueError("the benchmark's weights do not have the layout of "
                         "the program's train state for this configuration "
                         "(the program pads a vocabulary that is no "
                         "multiple of 256 with rows that the configuration "
                         "does not have)")
    state = make_state(key)
    pool = lm.token_pool(bench.seed, t["pool_batches"], B, S, model["vocab"])

    def feed(i):
        tokens, labels = pool[i % len(pool)]
        return {"tokens": jnp.asarray(tokens), "labels": jnp.asarray(labels)}

    compiled = step.lower(state, feed(0)).compile()
    grad_norms = jax.jit(lambda m: lm.leaf_norms(model, m) / (1 - o["beta1"]))
    delta_norms = jax.jit(lambda p, key: lm.leaf_norms(model, jax.tree.map(
        jnp.subtract, p, lm.init_params(model, key))))
    prog = {"losses": []}
    for i in range(n_check):
        state, met = compiled(state, feed(i))
        prog["losses"].append(float(met["loss"]))
        if i == 0:
            prog["grad_norms"] = jax.device_get(grad_norms(state["opt"]["m"]))
    prog["delta_norms"] = jax.device_get(delta_norms(state["params"], key))
    bench.setup_done()

    steps, failed, i = 0, 0, n_check
    with bench.window():
        t0 = time.perf_counter()
        while True:
            with bench.span("train.step"):
                state, met = compiled(state, feed(i))
                loss = float(met["loss"])
            steps, i = steps + 1, i + 1
            failed += not math.isfinite(loss)
            if time.perf_counter() - t0 >= bench.seconds:
                break
        elapsed = time.perf_counter() - t0

    device = jax.devices()[0]
    bench.read_memory_peak([device])
    del state, met, compiled
    gc.collect()

    t_ref = time.perf_counter()
    ref_mod = load_module(os.path.join(HERE, bench.config["reference"]))
    ref = ref_mod.train_readings(model, o, bench.seed, pool[:n_check])
    t_ref = time.perf_counter() - t_ref
    gaps = compare.train_gaps(prog, ref)
    tokens_per_s = steps * B * S / elapsed
    return Outcome(
        e2e={"train_tokens_per_s": tokens_per_s},
        attempted=steps, failed=failed,
        checks=[Check(name, gaps[name], bench.limits[name])
                for name in compare.TRAIN_CHECKS if name in bench.limits],
        counters={"tokens_per_s": tokens_per_s, "steps": steps,
                  "flops_per_token": flops.lm_train_flops_per_token(model, S),
                  "chips": 1,
                  "readings": {"program": prog, "reference": ref},
                  "reference_s": t_ref},
        devices=[device])
