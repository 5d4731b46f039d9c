"""The control, the step below the configuration's precision, fails the
comparison that sound runs pass (tiny sizes, on the CPU; the chip's
readings at the cells' sizes are in PERF.md)."""
import pytest

from chipbench import compare, lm

import chip_cells as cells


@pytest.mark.parametrize("seed", [3, 2**31 + 11])
def test_float8_reference_fails_the_train_limits(seed):
    b = cells.bench(seed)
    out = cells.driver().run(b)
    assert all(c.ok for c in out.checks)
    t, model = b.traffic, b.config["model"]
    ref = out.counters["readings"]["reference"]
    pool = lm.token_pool(seed, t["check_steps"], t["batch"], t["seq"],
                         model["vocab"])
    control = compare.train_gaps(cells.reference().train_readings(
        model, t["optimizer"], seed, pool, mode="fp8"), ref)
    program = {c.name: c.value for c in out.checks}
    assert any(control[n] > b.limits[n] for n in compare.TRAIN_CHECKS)
    assert all(control[n] >= 3 * program[n] for n in compare.TRAIN_CHECKS)

