"""The memory-bound cell ``sebs.tight-cold``: its files load, its traffic
keeps the container table busy in the benchmark's own reference, its two
metric readers read the program's table counters, and a small run of it
on the CPU comes out correct."""

import copy
import importlib
import io
import json
from types import SimpleNamespace

import pytest

import repro.core
from bench import run as R
from bench.drivers import GridDriver, _medians, _ref_fleet
from bench.reference import cluster as ref
from bench.window import WINDOW

CELL = "sebs.tight-cold"


def test_cell_and_config_load():
    spec, cell, cfg, tr = R.load_cell(R.ROOT, CELL)
    assert cell["config"] == "sebs-openwhisk-tight" and cell["chips"] == 1
    base = json.loads((R.ROOT / "bench" / "configs"
                       / "sebs-openwhisk.json").read_text())
    changed = {k for k in set(base) | set(cfg) if base.get(k) != cfg.get(k)}
    assert changed == {"source", "assumed", "node_memory_mb",
                       "container_mb"}
    assert (cfg["node_memory_mb"], cfg["container_mb"]) == (31842, 256)
    assert tr["warm"] is False and tr["precision"] == "float64"
    assert tr["intensities"] == [20, 25, 30] and tr["nodes"] == [4]
    names = [m["name"] for m in R.metrics_for(spec, CELL, per_layer=True)]
    assert {"pool_rows_per_node", "pool_pad_share",
            "event_step_roofline"} <= set(names)


def test_a_grid_point_creates_and_evicts_in_the_reference(monkeypatch):
    """SEPT at intensity 20 on one seeded burst: the benchmark's reference
    fills the nodes, then creates function-sized containers and evicts
    idle ones, so the cell keeps exercising the table."""
    _, _, cfg, tr = R.load_cell(R.ROOT, CELL)
    driver = GridDriver(cfg, tr, 2**31 + 7)
    f, r, p = driver._bursts(WINDOW, 0)[20, 0]
    created = []
    acquire = ref.Pool.acquire

    def counting(self, fn, now):
        got = acquire(self, fn, now)
        if got is not None and got[2] == ref.COLD_CREATE_S:
            created.append(fn)
        return got

    monkeypatch.setattr(ref.Pool, "acquire", counting)
    fleet = _ref_fleet(4, tr["cores_per_node"], "sept",
                       cfg["node_memory_mb"], cfg["container_mb"], None)
    out = ref.simulate(driver.fns, f, r, p, fleet, warm=False,
                       medians=_medians(cfg), fn_memory=cfg["fn_memory_mb"])
    assert len(created) > 0
    assert out.counters["evictions"] > 0


TOTALS = {"pool_node_slots": 40, "pool_rows": 40 * 248, "pool_peak": 4000}


@pytest.mark.parametrize("name,want", [
    ("pool_rows_per_node", 248.0),
    ("pool_pad_share", 100.0 * (1 - 4000 / (40 * 248))),
])
def test_table_readers(name, want, monkeypatch):
    reader = importlib.import_module(f"bench.metrics.{name}")
    ctx = SimpleNamespace(calls=1)
    monkeypatch.setattr(repro.core, "scan_phase_totals", lambda: TOTALS,
                        raising=False)
    assert reader.read(ctx) == pytest.approx(want)
    # a program without the table counters, or a run that never used it
    monkeypatch.setattr(repro.core, "scan_phase_totals",
                        lambda: {"step_slots": 8, "call_steps": 8})
    assert reader.read(ctx) is None
    monkeypatch.setattr(repro.core, "scan_phase_totals",
                        lambda: dict.fromkeys(TOTALS, 0))
    assert reader.read(ctx) is None
    monkeypatch.delattr(repro.core, "scan_phase_totals", raising=False)
    assert reader.read(ctx) is None


def test_small_run_is_correct():
    """One intensity, one burst a block, on the CPU."""
    _, _, cfg, tr = R.load_cell(R.ROOT, CELL)
    tr = copy.deepcopy(tr)
    tr.update(intensities=tr["intensities"][:1], seeds_per_block=1)
    out, err = io.StringIO(), io.StringIO()
    rc = R.run(CELL, 2**31 + 99, 1.0, False, require_tpu=False,
               config=cfg, traffic=tr, out=out, err=err)
    assert rc == 0, err.getvalue()
    result = json.loads(out.getvalue().strip().splitlines()[-1])
    assert result["correct"] is True, result["check"]
    assert result["failed"] == 0
