"""A run with the chip check skipped and the timed path broken underneath
comes out not correct; the same run unbroken comes out correct."""
import pytest

from chipbench import harness

import chip_cells as cells


@pytest.mark.parametrize("fault", [None, "unchanged", "half_batch"])
def test_fault_makes_the_run_incorrect(fault):
    result, _ = harness.run(cells.spec(), 2**31 + 5, 0.0, False,
                            require_chip=False, fault=fault)
    failing = [n for n, c in result["checks"].items()
               if not c["value"] <= c["limit"]]
    assert result["correct"] is (fault is None), result["checks"]
    assert bool(failing) is (fault is not None)
    assert list(result)[-1] == "checks"
    assert result["attempted"] > 0 and result["failed"] == 0


def test_a_vocabulary_the_program_pads_is_refused():
    config = cells.spec()["config"]
    config = dict(config, model=dict(config["model"], vocab=500))
    with pytest.raises(ValueError, match="pads"):
        cells.driver().run(cells.bench(3, config))
