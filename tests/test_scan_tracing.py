"""Phase spans and step counters of the scan entry (CPU).

Contracts under test:

* every ``fastpath.<phase>`` span of a batch is written into a
  ``jax.profiler`` trace on a host plane, nested inside the batch's one
  ``fastpath.entry`` span (which carries the cell and call counts), and
  matches ``scan_phase_totals()`` phase for
  phase and count for count;
* the per-chunk timing records and the phase totals agree: ``fill`` is the
  summed ``build_s``, and every record carries ``wait_s`` / ``unpack_s``;
* self times are non-negative and add up to no more than the entry's wall;
* ``step_slots`` / ``call_steps`` count padded and real event steps exactly;
* the step's XLA module is ``jit_event_step``, which every benchmark
  cell's ``step_pattern`` matches and the init module does not.
"""

import json
import re
import time
from pathlib import Path

import pytest

jax = pytest.importorskip("jax")

from repro.core import (  # noqa: E402
    scan_bucket_timings,
    scan_phase_totals,
    scan_timings_clear,
    simulate_cluster_cells_scan,
)
from repro.core import fastpath as fp  # noqa: E402
from repro.core.request import Request  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
PHASES = ("entry", "prepare", "tune", "compile", "fill", "dispatch", "wait",
          "unpack")


def _requests(n: int) -> list:
    return [Request(fn="sleep", r=0.05 * i, p_true=0.04) for i in range(n)]


def _item(n: int, nodes: int = 2) -> tuple:
    # push / least-loaded / fifo: no per-function queue table and no FC
    # ring, so the bucket key depends on the call count and fleet alone
    return (_requests(n), nodes, 2, "fifo", "push")


# two buckets: three cells of 64 padded calls on 2 nodes, one of 128 on 4
BATCH_COUNTS = ((40, 2), (50, 2), (60, 2), (100, 4))


def _batch() -> list:
    return [_item(n, nodes) for n, nodes in BATCH_COUNTS]


def _run_clean() -> tuple[dict, list, float]:
    simulate_cluster_cells_scan(_batch(), metrics_only=True)   # compile
    scan_timings_clear()
    t0 = time.perf_counter()
    simulate_cluster_cells_scan(_batch(), metrics_only=True)
    wall = time.perf_counter() - t0
    return scan_phase_totals(), scan_bucket_timings(), wall


def test_spans_nest_inside_one_entry(tmp_path):
    from jax.profiler import ProfileData

    simulate_cluster_cells_scan(_batch(), metrics_only=True)   # compile
    scan_timings_clear()
    with jax.profiler.trace(str(tmp_path)):
        simulate_cluster_cells_scan(_batch(), metrics_only=True)
    totals = scan_phase_totals()
    (path,) = tmp_path.rglob("*.xplane.pb")
    spans = [ev for plane in ProfileData.from_file(str(path)).planes
             if not plane.name.startswith("/device:")
             for line in plane.lines for ev in line.events
             if ev.name.startswith("fastpath.")]
    (entry,) = [ev for ev in spans if ev.name == "fastpath.entry"]
    stats = {k: v for k, v in entry.stats}
    assert stats["cells"] == 4 and stats["calls"] == 250
    lo, hi = entry.start_ns, entry.start_ns + entry.duration_ns
    for ev in spans:
        assert lo <= ev.start_ns and ev.start_ns + ev.duration_ns <= hi
    counts: dict = {}
    for ev in spans:
        counts[ev.name] = counts.get(ev.name, 0) + 1
    assert counts == {f"fastpath.{p}": totals[p]["n"]
                      for p in PHASES if p in totals}
    assert {"fastpath.prepare", "fastpath.fill", "fastpath.dispatch",
            "fastpath.wait", "fastpath.unpack"} <= set(counts)
    assert counts["fastpath.fill"] == 2                  # one per bucket


def test_records_match_phase_totals():
    totals, recs, _ = _run_clean()
    assert len(recs) == 2
    for r in recs:
        assert {"wait_s", "unpack_s", "build_s", "dispatch_s",
                "sync_s"} <= set(r)
    for phase, key in (("fill", "build_s"), ("dispatch", "dispatch_s"),
                       ("wait", "wait_s")):
        assert totals[phase]["s"] == pytest.approx(
            sum(r[key] for r in recs), rel=1e-12)
        assert totals[phase]["n"] == len(recs)
    # the bucket-level fold adds to the chunks' own unpack
    assert totals["unpack"]["s"] >= sum(r["unpack_s"] for r in recs)


def test_self_times_bounded_by_entry():
    totals, _, wall = _run_clean()
    phases = {p: v for p, v in totals.items() if isinstance(v, dict)}
    assert set(phases) <= set(PHASES)
    assert phases["entry"]["n"] == 1
    assert all(v["s"] >= 0.0 for v in phases.values())
    assert sum(v["s"] for v in phases.values()) <= wall


def test_step_counters_exact():
    totals, _, _ = _run_clean()
    # bucket one: 3 cells padded to a batch of 4, 64-call rows, 2 x 64
    # steps each; bucket two: 1 cell, 128-call rows; no extra steps
    assert totals["step_slots"] == 4 * 2 * 64 + 1 * 2 * 128
    assert totals["call_steps"] == 2 * (40 + 50 + 60 + 100)
    scan_timings_clear()
    assert scan_phase_totals() == {"step_slots": 0, "call_steps": 0}


@pytest.mark.filterwarnings("ignore:Some donated buffers were not usable")
def test_step_module_name_matches_step_pattern():
    key = (0, 8, 2, 2, 1, 8, 10, 1, 1, 1, 0)
    init_fn, scan_fn = fp._runner_fns(key)
    specs = {k: jax.ShapeDtypeStruct(v.shape, v.dtype)
             for k, v in fp._alloc_bucket_inputs(key, 2).items()}
    clk, ctr = jax.eval_shape(init_fn, specs)

    def module_name(lowered) -> str:
        head = lowered.as_text(dialect="hlo").splitlines()[0]
        return re.match(r"HloModule (\S+?),", head).group(1)

    step = module_name(scan_fn.lower(clk, ctr, specs))
    init = module_name(init_fn.lower(specs))
    assert step == "jit_event_step"
    cells = sorted((ROOT / "bench" / "workloads").glob("*.json"))
    assert cells
    for path in cells:
        pattern = re.compile(json.loads(path.read_text())["step_pattern"])
        assert pattern.search(step), path.name
        assert not pattern.search(init), path.name
