"""Process-local kernel find-db: tuned configs resolved at every call site.

MIOpen ships a "find-db" — a table of known-best kernel configs keyed by
problem shape and hardware — so production never re-tunes what the fleet
already measured. This module is that table's process-local face for our
Pallas kernels:

- ``DEFAULTS`` holds the fallback config per kernel.
- ``lookup_or_default(kernel, shape, default)`` is the fast path wired into
  ``flash_attention``/``mlstm``/``rglru`` and ``RealBackend``: a plain dict
  read against the active :class:`~repro.core.groundtruth.KernelConfigDB`.
  A miss returns the default immediately — it never times anything, never
  touches the network, never blocks a trial.
- ``shape_key``/``attention_shape_key``/... build the canonical shape keys.
  The tuner (``repro.kernels.tune``) writes entries under these exact keys,
  so a tuned config is picked up by the very next kernel call with no
  plumbing in between.
- ``default_interpret()`` auto-detects the platform: Pallas kernels run
  ``interpret=True`` only where no compiled Pallas path exists (anything
  but TPU). Callers can always pass ``interpret=`` explicitly to override.

The active db defaults to an empty in-process store; ``set_find_db`` points
it at one primed from a golden table, a service journal, or a live TCP
store (see ``repro.kernels.tune`` and the ``--kernel-db`` launch flag).
"""
from __future__ import annotations

import threading
from typing import Optional

from repro.core.groundtruth import KernelConfigDB

__all__ = ["DEFAULTS", "attention_shape_key", "default_interpret",
           "get_find_db", "hardware_key", "lookup_or_default",
           "mlstm_shape_key", "rglru_shape_key", "set_find_db",
           "shape_key", "train_step_shape_key"]

# the miss-path answer of every lookup. Flash blocks timed on a TPU v5e at
# qwen3-0.6b's attention (K=8, G=2, D=128, bf16, causal; B=1, S=4096 and
# B=8, S=1024): 1024 x 1024 is the fastest forward at both and the fastest
# backward in the train step (``kernels.ops`` cuts them for wider rows).
# The rest hand-picked.
DEFAULTS = {
    "flash_attention": {"q_block": 1024, "kv_block": 1024},
    "flash_attention_bwd": {"q_block": 1024, "kv_block": 1024},
    "mlstm": {"chunk": 128},
    "rglru": {"chunk": 128, "r_block": 128},
    "train_step": {},
}

_lock = threading.Lock()
_active_db = KernelConfigDB()
_hw_key: Optional[str] = None


def get_find_db() -> KernelConfigDB:
    """The process-wide active find-db."""
    return _active_db


def set_find_db(db: KernelConfigDB) -> KernelConfigDB:
    """Swap the active find-db (e.g. for one primed from a golden table);
    returns the previous one so callers can restore it."""
    global _active_db
    with _lock:
        prev, _active_db = _active_db, db
    return prev


def _platform() -> str:
    import jax
    return jax.default_backend()


def default_interpret() -> bool:
    """Interpret only when no compiled Pallas path exists: ``False`` on
    TPU (compiled Mosaic path), ``True`` everywhere else. The silent perf
    footgun was the old ``interpret=True`` default running interpreted
    kernels on real TPU backends unless every call site remembered to
    override it."""
    return _platform() != "tpu"


def hardware_key() -> str:
    """Stable id of the device the process tunes/runs on, e.g.
    ``cpu/TFRT_CPU_0``-class strings become ``cpu/cpu``. Memoized — jax
    device enumeration is not free."""
    global _hw_key
    if _hw_key is None:
        import jax
        dev = jax.devices()[0]
        kind = str(getattr(dev, "device_kind", dev.platform))
        with _lock:
            _hw_key = f"{dev.platform}/{kind}".replace(" ", "_").lower()
    return _hw_key


def shape_key(**dims) -> str:
    """Canonical shape key: sorted ``k=v`` pairs, so every writer and
    reader agrees independent of argument order."""
    return ",".join(f"{k}={dims[k]}" for k in sorted(dims))


def attention_shape_key(*, B, S, K, G, D, T, causal, window) -> str:
    return shape_key(B=B, S=S, K=K, G=G, D=D, T=T,
                     causal=bool(causal),
                     window="none" if window is None else int(window))


def mlstm_shape_key(*, B, S, H, D) -> str:
    return shape_key(B=B, S=S, H=H, D=D)


def rglru_shape_key(*, B, S, R) -> str:
    return shape_key(B=B, S=S, R=R)


def train_step_shape_key(*, arch, batch) -> str:
    return shape_key(arch=str(arch), batch=int(batch))


def lookup_or_default(kernel: str, shape: str,
                      default: Optional[dict] = None,
                      hardware: Optional[str] = None) -> dict:
    """Tuned config for ``(kernel, shape, hardware)`` overlaid on the
    kernel's built-in default. Pure dict read on the active db; a miss
    returns the default immediately (never blocks, never tunes)."""
    if default is None:
        default = DEFAULTS.get(kernel, {})
    return _active_db.lookup_or_default(
        kernel, shape, default,
        hardware=hardware if hardware is not None else hardware_key())
