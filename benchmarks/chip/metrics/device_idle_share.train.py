"""Share of the training window in which no operation ran on the device,
from the profiler trace (``chipbench.trace``), in percent."""


def read(r):
    if r.summary is None:
        return None
    return 100.0 * r.summary.idle_share
