"""Training backends behind PipeTune's trial runner.

RealBackend  — actually trains the paper's small workloads on local devices,
               epoch-at-a-time, with per-epoch switchable system parameters
               (microbatching, remat, precision, donation). Candidate system
               configs compile asynchronously off the critical path, which is
               this repo's version of the paper's "all additional steps are
               done in parallel".
SimBackend   — lives in repro.cluster.sim; same interface, modeled time.
"""
from __future__ import annotations

import concurrent.futures as cf
import dataclasses
import itertools
import threading
import time
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs
from repro.core import energy as energy_lib
from repro.core.profiler import EpochProfile, Profiler
from repro.core.seeding import stable_hash
from repro.data import synthetic
from repro.models import small
from repro.optim import optimizers


# Memory-conservative production default (grad accumulation + remat —
# the "safe" config an operator picks without workload knowledge; the paper's
# trials likewise all start from one fixed default). PipeTune's probing
# discovers when the aggressive configs fit and are faster.
SYS_DEFAULT = {"remat": "block", "microbatches": 4, "precision": "fp32"}

# The power model's utilization input for real epochs. Nothing measures it
# yet, so RealBackend energies are estimates, not measurements.
ASSUMED_UTILIZATION = 0.5


@dataclasses.dataclass(frozen=True)
class BackendCapabilities:
    """What a training backend can do, declared instead of duck-typed.

    async_precompile — candidate system configs compile off the critical path
                       (the runner may call ``precompile_async``).
    simulated        — epochs are modeled, not executed (wall time is free).
    deterministic    — ``run_epoch`` is a pure function of (state, sys_cfg),
                       so results are bit-identical regardless of the order
                       trials execute in (safe for parallel executors that
                       need reproducibility).
    """
    async_precompile: bool = False
    simulated: bool = False
    deterministic: bool = False


def backend_capabilities(backend) -> BackendCapabilities:
    """Capabilities of ``backend``, with a duck-typing fallback for
    third-party backends that predate the protocol."""
    fn = getattr(backend, "capabilities", None)
    if fn is not None:
        return fn()
    return BackendCapabilities(
        async_precompile=callable(getattr(backend, "precompile_async", None)))


def sys_key(sys_cfg: dict) -> str:
    return "|".join(f"{k}={sys_cfg[k]}" for k in sorted(sys_cfg))


@dataclasses.dataclass
class EpochResult:
    duration_s: float
    energy_j: float
    loss: float
    accuracy: float
    profile: EpochProfile
    sys_config: dict
    step_times: list
    compile_s: float = 0.0


@dataclasses.dataclass
class TrialState:
    workload: str
    hparams: dict
    cfg: Any
    params: Any
    opt_state: Any
    step: int
    epoch: int
    data: Any              # Batches
    eval_batch: dict
    seed: int
    loss_last: float = float("nan")
    device: Any = None     # the device holding this trial's arrays


class RealBackend:
    """Trains repro.models.small workloads for real (paper Table 3).

    Each trial's params, optimizer state and batches live on one device,
    chosen round-robin over the first ``n_devices`` local devices (all of
    them when None) when the trial starts, so concurrent trials
    (``--parallelism N``) spread over a host's chips.
    """

    def __init__(self, n_train: int = 2048, n_eval: int = 512,
                 steps_per_epoch: Optional[int] = 8, compile_workers: int = 2,
                 n_devices: Optional[int] = None):
        self.n_train, self.n_eval = n_train, n_eval
        self.steps_per_epoch = steps_per_epoch
        self._devices = jax.local_devices()[:n_devices]
        self._device_turn = itertools.count()
        self._step_cache: Dict[tuple, Any] = {}
        self._compile_pool = cf.ThreadPoolExecutor(max_workers=compile_workers)
        self._pending: Dict[tuple, cf.Future] = {}
        self._lock = threading.Lock()
        self.profiler = Profiler()

    def capabilities(self) -> BackendCapabilities:
        # real training: step-time measurements are host-noisy, so parallel
        # execution is allowed but not bit-reproducible
        return BackendCapabilities(async_precompile=True, simulated=False,
                                   deterministic=False)

    # ------------------------------------------------------------------ data
    def _dataset(self, workload: str, seed: int):
        cfg = configs.get_config(workload)
        wl_seed = seed + stable_hash(workload) % 1000
        if cfg.kind == "lenet":
            d = synthetic.make_image_dataset(wl_seed,
                                             self.n_train + self.n_eval,
                                             n_classes=cfg.n_classes)
        else:
            d = synthetic.make_text_dataset(wl_seed,
                                            self.n_train + self.n_eval,
                                            n_classes=cfg.n_classes,
                                            vocab=cfg.vocab,
                                            seq_len=cfg.seq_len)
        return synthetic.train_test_split(d, test_frac=self.n_eval /
                                          (self.n_train + self.n_eval),
                                          seed=seed)

    # ----------------------------------------------------------------- trial
    def init_trial(self, workload: str, hparams: dict, seed: int = 0
                   ) -> TrialState:
        import dataclasses as dc
        cfg = configs.get_config(workload)
        upd = {}
        if "embed_dim" in hparams and cfg.kind != "lenet":
            upd["embed_dim"] = int(hparams["embed_dim"])
        if "dropout" in hparams:
            upd["dropout"] = float(hparams["dropout"])
        cfg = dc.replace(cfg, **upd)
        train, test = self._dataset(workload, seed)
        bs = int(hparams.get("batch_size", 64))
        bs = min(bs, len(next(iter(train.values()))))
        data = synthetic.Batches(train, bs, seed=seed)
        device = self._devices[next(self._device_turn) % len(self._devices)]
        params = jax.device_put(small.init(jax.random.PRNGKey(seed), cfg),
                                device)
        opt_state = jax.device_put(self._opt(hparams).init(params), device)
        return TrialState(workload=workload, hparams=dict(hparams), cfg=cfg,
                          params=params, opt_state=opt_state, step=0,
                          epoch=0, data=data,
                          eval_batch=jax.device_put(
                              {k: v[:256] for k, v in test.items()}, device),
                          seed=seed, device=device)

    def _opt(self, hparams):
        lr = float(hparams.get("learning_rate", 0.01))
        return optimizers.sgd(lr, momentum=0.9)

    # ----------------------------------------------------- compiled functions
    def _build_step(self, cfg, hparams, sys_cfg, batch_shape_key):
        opt = self._opt(hparams)
        n_micro = int(sys_cfg.get("microbatches", 1))
        remat = sys_cfg.get("remat", "none")
        dtype = jnp.bfloat16 if sys_cfg.get("precision") == "bf16" \
            else jnp.float32

        def loss_fn(params, batch, rng):
            cparams = jax.tree.map(
                lambda a: a.astype(dtype)
                if jnp.issubdtype(a.dtype, jnp.floating) else a, params)
            batch = {k: (v.astype(dtype) if jnp.issubdtype(v.dtype,
                                                           jnp.floating)
                         else v) for k, v in batch.items()}
            l, m = small.loss_fn(cparams, batch, cfg, rng=rng)
            return l.astype(jnp.float32), m

        if remat != "none":
            loss_fn = jax.checkpoint(loss_fn)
        grad_fn = jax.value_and_grad(loss_fn, has_aux=True)

        def train_step(params, opt_state, step, batch, rng):
            if n_micro > 1:
                mbs = jax.tree.map(
                    lambda x: x.reshape((n_micro, x.shape[0] // n_micro)
                                        + x.shape[1:]), batch)

                def micro(carry, mb):
                    g_acc, l_acc, a_acc = carry
                    (l, m), g = grad_fn(params, mb, rng)
                    return (jax.tree.map(jnp.add, g_acc, g), l_acc + l,
                            a_acc + m["accuracy"]), None
                g0 = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32),
                                  params)
                (g, l, a), _ = jax.lax.scan(
                    micro, (g0, jnp.float32(0), jnp.float32(0)), mbs)
                g = jax.tree.map(lambda x: x / n_micro, g)
                l, a = l / n_micro, a / n_micro
            else:
                (l, m), g = grad_fn(params, batch, rng)
                a = m["accuracy"]
            updates, opt_state = opt.update(g, opt_state, params, step)
            params = optimizers.apply_updates(params, updates)
            return params, opt_state, l, a

        donate = (0, 1) if sys_cfg.get("donate", True) else ()
        jitted = jax.jit(train_step, donate_argnums=donate)

        def eval_step(params, batch):
            logits = small.forward(params, batch, cfg)
            return jnp.mean((jnp.argmax(logits, -1) ==
                             batch["labels"]).astype(jnp.float32))
        return jitted, jax.jit(eval_step)

    def _step_key(self, ts: TrialState, sys_cfg: dict):
        hp = ts.hparams
        return (ts.workload, hp.get("embed_dim"), hp.get("dropout"),
                int(hp.get("batch_size", 64)), sys_key(sys_cfg))

    def _effective_sys(self, ts: TrialState, sys_cfg: dict) -> dict:
        """Fill sys-config keys the caller left unspecified from the kernel
        find-db's tuned ``train_step`` entry for this (workload, batch).

        Explicit keys always win, so tuner-driven probing (which passes
        complete configs) is byte-for-byte unaffected; only callers that
        rely on defaults pick up tuned values. Idempotent, and applied
        before ``_step_key`` everywhere so cache keys stay coherent."""
        from repro.kernels import findb
        tuned = findb.lookup_or_default(
            "train_step", findb.train_step_shape_key(
                arch=ts.workload, batch=int(ts.hparams.get("batch_size", 64))),
            default={})
        fill = {k: v for k, v in tuned.items()
                if k not in sys_cfg
                and k in ("remat", "microbatches", "precision", "donate")}
        return {**fill, **sys_cfg} if fill else sys_cfg

    def get_step(self, ts: TrialState, sys_cfg: dict):
        """Compiled (train_step, eval_step), building if needed."""
        sys_cfg = self._effective_sys(ts, sys_cfg)
        key = self._step_key(ts, sys_cfg)
        with self._lock:
            if key in self._step_cache:
                return self._step_cache[key], 0.0
            fut = self._pending.pop(key, None)
        t0 = time.time()
        if fut is not None:
            pair = fut.result()
        else:
            pair = self._build_step(ts.cfg, ts.hparams, sys_cfg,
                                    int(ts.hparams.get("batch_size", 64)))
        with self._lock:
            self._step_cache[key] = pair
        return pair, time.time() - t0

    def precompile_async(self, ts: TrialState, sys_cfg: dict):
        """Compile a candidate system config off the critical path."""
        sys_cfg = self._effective_sys(ts, sys_cfg)
        key = self._step_key(ts, sys_cfg)
        with self._lock:
            if key in self._step_cache or key in self._pending:
                return
            self._pending[key] = self._compile_pool.submit(
                self._build_step, ts.cfg, ts.hparams, sys_cfg,
                int(ts.hparams.get("batch_size", 64)))

    # ----------------------------------------------------------------- epoch
    def run_epoch(self, ts: TrialState, sys_cfg: dict, collect_profile=True
                  ) -> Tuple[TrialState, EpochResult]:
        sys_cfg = self._effective_sys(ts, sys_cfg)
        (train_step, eval_step), compile_s = self.get_step(ts, sys_cfg)
        n_micro = int(sys_cfg.get("microbatches", 1))
        bs = int(ts.hparams.get("batch_size", 64))
        bs = (bs // n_micro) * n_micro if bs >= n_micro else n_micro
        params, opt_state = ts.params, ts.opt_state
        step_times, losses, accs = [], [], []
        rng = jax.random.PRNGKey(ts.seed * 7919 + ts.epoch)
        n_steps = 0
        for batch in ts.data.epoch(ts.epoch):
            if self.steps_per_epoch and n_steps >= self.steps_per_epoch:
                break
            b = jax.device_put({k: v[:bs] for k, v in batch.items()},
                               ts.device)
            t0 = time.time()
            params, opt_state, l, a = train_step(
                params, opt_state, jnp.int32(ts.step), b,
                jax.random.fold_in(rng, n_steps))
            jax.block_until_ready(l)
            step_times.append(time.time() - t0)
            losses.append(float(l))
            accs.append(float(a))
            ts.step += 1
            n_steps += 1
        # first call of a freshly-built step function compiles inline; strip
        # that from the *training-time* books (it is accounted in compile_s —
        # the cluster model charges switch costs with async-overlap factors).
        # Applied identically to every runner: probe measurements must compare
        # warm-vs-warm or the already-warm default always wins.
        if len(step_times) >= 3:
            med = float(np.median(step_times[1:]))
            if step_times[0] > 3.0 * med:
                compile_s += step_times[0] - med
                step_times[0] = med
        acc = float(eval_step(params, ts.eval_batch))
        # not measured: energy comes from the power model at this assumed
        # utilization, so it is an estimate proportional to step time
        util = ASSUMED_UTILIZATION
        e = energy_lib.epoch_energy(step_times, util, chips=1)
        profile = None
        if collect_profile:
            profile = self.profiler.build(
                step_times=step_times,
                sys_config=None,
                workload_meta={"batch": bs,
                               "seq_or_dim": getattr(ts.cfg, "seq_len", 28),
                               "params": sum(np.prod(p.shape) for p in
                                             jax.tree.leaves(ts.params)),
                               "layers": 2, "d_model":
                                   getattr(ts.cfg, "embed_dim", 0),
                               "vocab": getattr(ts.cfg, "vocab", 0)},
                loss_start=losses[0] if losses else 0.0,
                loss_end=losses[-1] if losses else 0.0,
                power_w=energy_lib.power_w(util, 1), compile_time=compile_s,
                tokens_per_step=bs)
        ts.params, ts.opt_state = params, opt_state
        ts.epoch += 1
        ts.loss_last = losses[-1] if losses else float("nan")
        return ts, EpochResult(
            duration_s=float(np.sum(step_times)), energy_j=e,
            loss=ts.loss_last, accuracy=acc,
            profile=profile or EpochProfile({}), sys_config=dict(sys_cfg),
            step_times=step_times, compile_s=compile_s)
