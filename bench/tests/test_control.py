"""The control of every cell comes out as not correct: the plain
reference computed in the precision below the cell's, put in the
program's place, at a size a test run holds."""

import copy

import pytest

from bench.control import control
from bench.run import ROOT, load_cell

CELLS = ["sebs.grid-f32", "sebs.grid-kills", "sebs.paper-cold"]


def small(name):
    _, _, cfg, tr = load_cell(ROOT, name)
    cfg, tr = copy.deepcopy(cfg), copy.deepcopy(tr)
    tr["seeds_per_block"] = 1
    tr["intensities"] = tr["intensities"][:1]
    tr["policies"] = ["sept", "fc"]
    return cfg, tr


@pytest.mark.parametrize("name", CELLS)
def test_control_is_not_correct(name):
    cfg, tr = small(name)
    for seed in (11, 2**31 + 7):
        out = control(cfg, tr, seed)
        assert out["correct"] is False, out
        assert any(v["value"] > v["limit"] for v in out["check"].values())


@pytest.mark.parametrize("name", CELLS)
def test_reference_against_itself_is_correct(name):
    cfg, tr = small(name)
    tr["control_precision"] = "float64"
    out = control(cfg, tr, 3)
    assert out["correct"] is True, out
    assert all(v["value"] == 0 for v in out["check"].values())
