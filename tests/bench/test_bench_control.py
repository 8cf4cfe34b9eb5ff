"""The control, the program's own next-lower weight precision (INT4),
comes out not ``correct`` in a whole run of each cell."""

import functools

import pytest

from benchruns import CELLS, run_small
from bench import calibrate, model


@pytest.mark.parametrize("name", CELLS)
def test_control_is_not_correct(name, monkeypatch):
    monkeypatch.setattr(model, "open_session",
                        functools.partial(model.open_session, **calibrate.CONTROL))
    assert not run_small(name)["correct"]
