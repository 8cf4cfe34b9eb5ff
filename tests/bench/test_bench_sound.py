"""The program as the configuration states it comes out ``correct`` in a
whole run of each cell, at a test's size in interpret mode."""

import pytest

from benchruns import CELLS, M, run_small
from bench import manifest


@pytest.mark.parametrize("name", CELLS)
def test_sound_run_is_correct(name):
    res = run_small(name)
    assert res["correct"], res["checks"]
    assert list(res)[-1] == "checks"
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert set(res["metrics"]) == {m["name"] for m in manifest.cell(name, M).end_to_end}
    assert res["info"]["compiles_in_window"] == 0
