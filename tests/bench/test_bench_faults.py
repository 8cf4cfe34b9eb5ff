"""Each fault a training cell can have, planted under the timed path,
makes a whole run of the cell come out not ``correct``: a step that
returns its state unchanged, or that leaves out half of its batch."""

import pytest

from benchruns import CELLS, FAULTS, run_small
from bench import calibrate


@pytest.mark.parametrize("name,fault", [(n, f) for n in CELLS for f in FAULTS])
def test_planted_fault_is_not_correct(name, fault):
    with getattr(calibrate, fault)():
        assert not run_small(name)["correct"]
