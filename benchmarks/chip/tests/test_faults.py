"""With the timed path broken underneath, a run's ``correct`` comes out
false: once for each fault a cell can have (the exchange between chips does
not exist in these one-chip cells).  The harness's look for a chip is
skipped; everything after it runs, at a tiny size on the CPU."""
import pytest

from bench import faults
from test_rehearsal import rehearse


@pytest.mark.parametrize("fault", faults.ROUND_FAULTS)
def test_round_fault_is_caught(fault):
    res = rehearse("round", seed=31, fault=fault)
    assert res["correct"] is False, res["checks"]
