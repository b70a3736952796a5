"""A whole run, past the look for a chip, on the CPU at a small size: the
unbroken program comes out correct, and each fault planted in the timed
path underneath makes ``correct`` come out false."""

import copy
import io
import json

import pytest

from bench import run as R

GRID = ["sebs.grid-f32", "sebs.grid-kills"]


def small(name):
    _, _, cfg, tr = R.load_cell(R.ROOT, name)
    cfg, tr = copy.deepcopy(cfg), copy.deepcopy(tr)
    tr.update(nodes=tr["nodes"][:1], intensities=tr["intensities"][:1],
              seeds_per_block=4)
    return cfg, tr


def run_cell(name, seconds=1.0):
    cfg, tr = small(name)
    out, err = io.StringIO(), io.StringIO()
    rc = R.run(name, 2**31 + 99, seconds, False, require_tpu=False,
               config=cfg, traffic=tr, out=out, err=err)
    assert rc == 0, err.getvalue()
    result = json.loads(out.getvalue().strip().splitlines()[-1])
    assert list(result)[-1] == "check"
    assert err.getvalue().strip().splitlines()[-1].startswith(
        "check lost_calls ")
    return result


@pytest.mark.parametrize("name", GRID)
def test_unbroken_program_is_correct(name):
    result = run_cell(name)
    assert result["correct"] is True, result["check"]
    assert result["attempted"] > 0 and result["failed"] == 0
    assert set(result["metrics"]) == {"sim_calls_per_s", "setup_s"}


def _alter_grid_answers(monkeypatch):
    from repro.core import fastpath

    real = fastpath._cell_scan_metrics

    def altered(*a, **k):
        row = real(*a, **k)
        row.resp = row.resp.copy()
        row.resp[len(row.resp) // 2] += 10.0     # one call, 10 s late
        return row

    monkeypatch.setattr(fastpath, "_cell_scan_metrics", altered)


def _alter_fc_answers(monkeypatch):
    """FC cells only, the cells that take the Pallas kernel's FC branch on
    the chip: one call 0.1 s late in each."""
    from repro.core import fastpath

    real = fastpath._cell_scan_metrics

    def altered(cell, *a, **k):
        row = real(cell, *a, **k)
        if cell.policy == "fc":
            row.resp = row.resp.copy()
            row.resp[len(row.resp) // 2] += 0.1
        return row

    monkeypatch.setattr(fastpath, "_cell_scan_metrics", altered)


def _drop_half_the_batch(monkeypatch):
    from repro.core import fastpath

    real = fastpath._run_scan_cells

    def half(cells, metrics_only=False):
        rows = real(cells[:(len(cells) + 1) // 2], metrics_only)
        return (rows * 2)[:len(cells)]           # the rest copied over

    monkeypatch.setattr(fastpath, "_run_scan_cells", half)


@pytest.mark.parametrize("name", GRID)
@pytest.mark.parametrize("fault", [_alter_grid_answers, _alter_fc_answers,
                                   _drop_half_the_batch])
def test_grid_fault_is_not_correct(name, fault, monkeypatch):
    fault(monkeypatch)
    assert run_cell(name)["correct"] is False


def test_fc_fault_reads_the_fc_share_of_the_f32_sample(monkeypatch):
    """One policy of five diverges: 20% of the sampled cells, above the
    f32 cell's limit."""
    _alter_fc_answers(monkeypatch)
    shown = run_cell("sebs.grid-f32")["check"]["diverged_cells_pct"]
    assert shown["value"] == pytest.approx(20.0)
    assert shown["value"] > shown["limit"]


def test_run_refuses_a_device_that_is_not_a_tpu():
    out, err = io.StringIO(), io.StringIO()
    rc = R.run("sebs.grid-f32", 1, 1.0, False, out=out, err=err)
    assert rc != 0 and out.getvalue() == ""
    assert "needs a TPU" in err.getvalue()
