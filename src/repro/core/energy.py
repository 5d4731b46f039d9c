"""Energy accounting (paper §3.2: trapezoidal integration of PDU power).

No PDU exists in this container; power comes from an activity model
    P(chip) = P_IDLE + P_DYN * utilization
with utilization from the roofline terms (compute_term / step_time). The
paper's integration is kept: we integrate P over per-step wall times with the
trapezoidal rule, so measured-time jitter shows up in energy exactly as the
paper's 1-second PDU samples did.
"""
from __future__ import annotations

from typing import Sequence

import numpy as np

P_IDLE_W = 70.0          # per chip
P_DYN_W = 130.0          # per chip at full utilization
HOST_W = 150.0           # per host (shared)
CHIPS_PER_HOST = 8


def power_w(utilization: float, chips: int = 1) -> float:
    u = min(max(utilization, 0.0), 1.0)
    hosts = max(1, chips // CHIPS_PER_HOST)
    return chips * (P_IDLE_W + P_DYN_W * u) + hosts * HOST_W / CHIPS_PER_HOST


def trapezoidal_energy(power_samples: Sequence[float],
                       dt_s: float = 1.0) -> float:
    """Joules from power samples at fixed dt (the paper's PDU integration)."""
    p = np.asarray(power_samples, np.float64)
    if p.size < 2:
        return float(p.sum() * dt_s)
    return float(np.trapezoid(p, dx=dt_s))


def epoch_energy(step_times: Sequence[float], utilization: float,
                 chips: int = 1) -> float:
    """Energy of one epoch: P(util) integrated over measured step times."""
    t = float(np.sum(step_times))
    return power_w(utilization, chips) * t
