"""The released configuration's cell, `aids_score_b1000` (nonzero GCN
biases), run whole on the CPU at a test size with the check for a chip
skipped: the sound program comes out correct, and the control (the
reference at `high` precision in the program's place) does not."""

import _paths  # noqa: F401
import pytest
from test_bench_faults import CONTROL, SMALL, _run

CELL = "aids_score_b1000"
STAND_IN = "aids_gcn_nobias_score_b1000"


@pytest.mark.parametrize("control", [None, "high"])
def test_released_cell(control, tmp_path):
    # The same traffic, at the same test size, as the stand-in cell.
    traffic = (SMALL if control is None else CONTROL)[STAND_IN]
    result = _run(CELL, tmp_path, traffic, control=control)
    if control is None:
        assert result["correct"], result["checks"]
        assert result["attempted"] > 0 and result["failed"] == 0
    else:
        assert not result["correct"]
