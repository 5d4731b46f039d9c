"""Roofline-term derivation from compiled dry-run artifacts.

Three terms per (arch, shape, mesh) cell — all in seconds:
    compute    = HLO_FLOPs        / (chips * PEAK_FLOPS)
    memory     = HLO_bytes        / (chips * HBM_BW)
    collective = collective_bytes / (chips * ICI_BW)

``compiled.cost_analysis()`` reports the *per-device* partitioned program, so
flops/bytes are multiplied back by chip count before normalizing (net effect:
divide by one chip's peak). collective_bytes comes from parsing the optimized
HLO (collectives only exist after SPMD partitioning) and summing operand
sizes of all-gather / all-reduce / reduce-scatter / all-to-all /
collective-permute ops.

The terms are estimates for a target chip, never measurements: the
dry-run compiles on the host, so its peaks come from ``CHIPS`` under
``TARGET_KIND``, not from the device that runs the compile.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict, Optional


@dataclasses.dataclass(frozen=True)
class ChipSpec:
    """Published per-chip peaks."""
    peak_bf16_flops: float       # FLOP/s
    hbm_bytes: float             # HBM capacity
    hbm_bw: float                # bytes/s
    ici_bw: float                # bytes/s per link


# Keyed by ``jax.Device.device_kind``. Source: Google Cloud documentation,
# "TPU v5e": 197 TFLOP/s bf16, 16 GB HBM at 819 GB/s, 1,600 Gbit/s of
# interconnect over 4 links (50 GB/s each).
CHIPS: Dict[str, ChipSpec] = {
    "TPU v5 lite": ChipSpec(peak_bf16_flops=197e12, hbm_bytes=16e9,
                            hbm_bw=819e9, ici_bw=50e9),
}


def chip_spec(device_kind: str) -> ChipSpec:
    """Peaks of ``device_kind``; a kind not in ``CHIPS`` is an error."""
    try:
        return CHIPS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device_kind "
                       f"{device_kind!r}; known: {sorted(CHIPS)}") from None


TARGET_KIND = "TPU v5 lite"  # the chip the dry-run estimates model
PEAK_FLOPS = CHIPS[TARGET_KIND].peak_bf16_flops
HBM_BW = CHIPS[TARGET_KIND].hbm_bw
ICI_BW = CHIPS[TARGET_KIND].ici_bw

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "s32": 4, "s16": 2, "s8": 1, "u64": 8, "u32": 4, "u16": 2,
    "u8": 1, "pred": 1, "c64": 8, "c128": 16,
}

_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute")

_SHAPE_RE = re.compile(r"\b(f64|f32|f16|bf16|f8e4m3fn|f8e5m2|s64|s32|s16|s8|"
                       r"u64|u32|u16|u8|pred|c64|c128)\[([0-9,]*)\]")


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    if dims:
        for d in dims.split(","):
            n *= int(d)
    return n * _DTYPE_BYTES[dtype]


def collective_bytes(hlo_text: str) -> Dict[str, int]:
    """Per-collective-type operand bytes from optimized HLO text."""
    out = {c: 0 for c in _COLLECTIVES}
    out["count"] = 0
    for line in hlo_text.splitlines():
        m = re.search(r"=\s+(?:\([^)]*\)|\S+)\s+(" + "|".join(_COLLECTIVES)
                      + r")(?:-start|-done)?\(", line)
        if not m:
            continue
        kind = m.group(1)
        if "-done(" in line:
            continue                      # avoid double-count of async pairs
        # operand shapes: everything inside the call parens
        args = line[m.end():]
        shapes = _SHAPE_RE.findall(args)
        if not shapes:                    # fall back to the result shape
            shapes = _SHAPE_RE.findall(line[:m.start()])
        out[kind] += sum(_shape_bytes(d, s) for d, s in shapes)
        out["count"] += 1
    out["total"] = sum(out[c] for c in _COLLECTIVES)
    return out


@dataclasses.dataclass
class RooflineTerms:
    compute_s: float
    memory_s: float                  # as-compiled XLA traffic
    collective_s: float
    hlo_flops: float                 # global (all chips)
    hlo_bytes: float                 # global
    coll_bytes: float                # global
    chips: int
    model_flops: float = 0.0
    memory_kernelized_s: float = 0.0  # with Pallas flash kernels (score-class
    #                                   tensors stay in VMEM); 0 = same

    @property
    def memory_best_s(self) -> float:
        return self.memory_kernelized_s or self.memory_s

    @property
    def dominant(self) -> str:
        terms = {"compute": self.compute_s, "memory": self.memory_best_s,
                 "collective": self.collective_s}
        return max(terms, key=terms.get)

    @property
    def step_time_s(self) -> float:
        """Optimistic (full-overlap) step time: max of the three terms,
        with the kernelized memory term (kernels are part of the system)."""
        return max(self.compute_s, self.memory_best_s, self.collective_s)

    @property
    def useful_flop_fraction(self) -> float:
        return self.model_flops / self.hlo_flops if self.hlo_flops else 0.0

    @property
    def mfu(self) -> float:
        """Model-FLOPs utilization at the roofline-projected step time."""
        t = self.step_time_s
        return (self.model_flops / (self.chips * PEAK_FLOPS)) / t if t else 0.0

    def to_dict(self):
        return {
            "compute_s": self.compute_s, "memory_s": self.memory_s,
            "memory_kernelized_s": self.memory_kernelized_s,
            "collective_s": self.collective_s, "dominant": self.dominant,
            "hlo_flops": self.hlo_flops, "hlo_bytes": self.hlo_bytes,
            "coll_bytes": self.coll_bytes, "chips": self.chips,
            "model_flops": self.model_flops,
            "useful_flop_fraction": self.useful_flop_fraction,
            "step_time_s": self.step_time_s, "mfu": self.mfu,
        }


def terms_from_cost(cost: dict, coll: Dict[str, int], chips: int,
                    model_flops: float = 0.0) -> RooflineTerms:
    """cost: compiled.cost_analysis() of the per-device program."""
    per_dev_flops = float(cost.get("flops", 0.0))
    per_dev_bytes = float(cost.get("bytes accessed", 0.0))
    flops = per_dev_flops * chips
    nbytes = per_dev_bytes * chips
    cbytes = float(coll.get("total", 0))
    return RooflineTerms(
        compute_s=flops / (chips * PEAK_FLOPS),
        memory_s=nbytes / (chips * HBM_BW),
        collective_s=cbytes / (chips * ICI_BW),
        hlo_flops=flops, hlo_bytes=nbytes, coll_bytes=cbytes, chips=chips,
        model_flops=model_flops)


def terms_from_hlo(hcost, chips: int, model_flops: float = 0.0
                   ) -> RooflineTerms:
    """hcost: repro.analysis.hlo_analysis.Cost of the per-device program.

    Collective bytes are per-device payload; every chip pushes its share over
    its own links, so the collective term is payload_per_device / ICI_BW.
    """
    flops = hcost.flops * chips
    nbytes = hcost.bytes * chips
    cbytes = hcost.coll_bytes * chips
    return RooflineTerms(
        compute_s=flops / (chips * PEAK_FLOPS),
        memory_s=nbytes / (chips * HBM_BW),
        memory_kernelized_s=hcost.kernelized_bytes / HBM_BW,
        collective_s=cbytes / (chips * ICI_BW),
        hlo_flops=flops, hlo_bytes=nbytes, coll_bytes=cbytes, chips=chips,
        model_flops=model_flops)


# ---------------------------------------------------------------------------
# model-FLOPs estimates (6ND convention)
# ---------------------------------------------------------------------------

def count_params(abstract_params, active_expert_frac: Optional[float] = None):
    """(total, active) param counts. Expert tensors scale by the active
    fraction (top_k [+ shared] / E) for the MoE 6*N_active*D convention."""
    import jax
    total = active = 0
    for path, leaf in jax.tree_util.tree_flatten_with_path(abstract_params)[0]:
        ps = "/".join(str(getattr(p, "key", p)) for p in path)
        n = 1
        for d in leaf.shape:
            n *= d
        total += n
        if active_expert_frac is not None and re.search(
                r"moe/w_(gate|up|down)", ps):
            active += int(n * active_expert_frac)
        else:
            active += n
    return total, active


def model_flops(cfg, shape, abstract_params) -> float:
    frac = None
    if getattr(cfg, "n_experts", 0):
        frac = cfg.top_k / cfg.n_experts
    total, active = count_params(abstract_params, frac)
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * active * tokens
    if shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * active * tokens
    return 2.0 * active * shape.global_batch          # decode: one token/seq
