"""The controls of ``control.py`` come out not correct, at a size a CPU
test can hold: the check compares closely enough to refuse the step
below the configuration's precision."""
from __future__ import annotations

import pytest

from conftest import tiny


@pytest.mark.parametrize("name", ["deep96-exact.poisson",
                                  "deep96-scored.backlog",
                                  "deep96-exact.ingest"])
@pytest.mark.parametrize("seed", [11, 2_147_483_659])
def test_control_is_not_correct(name, seed):
    from chipbench import control
    _, w, cfg, traffic = tiny(name)
    got = control.control(w, cfg, traffic, seed, 1.0)
    assert got
    for numbers in got.values():
        assert any(v > lim for v, lim in numbers.values()), got
