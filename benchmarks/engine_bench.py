"""Beyond-paper: the simulation-backend speedup rows and the scan path's
sweep, stream and capability rows.

``backend_speedup_rows`` times the simulation engines themselves on a
high-intensity sweep grid (workload generation and metric aggregation are
identical across backends and excluded): reference event loop vs the
vectorized fast path (exact), plus the batched jax.lax.scan variant when
JAX is importable.

``cluster_speedup_rows`` is the cluster-scale version: a >=1k-cell
nodes x intensity x policy x seed grid through the bucketed multi-node scan
path (one XLA dispatch per padded bucket shape) against the reference
event-loop Cluster, whose cost is estimated from a stratified cell sample.
The scan wall is measured post-compile (a warm-up pass populates the bucket
cache first); the cold wall and the bucket count are reported alongside.

``matrix_rows`` (``--rows matrix``) sweeps the closed capability-matrix
rows -- hedging x autoscale x failure schedules, duplicate-mode racing,
and the cold (``warm=False``) regime -- entirely on the scan backend,
asserting zero degraded cells and exact backup/steal/failure counts
against a stratified reference sample.

``mega_rows`` (``--rows mega``) is the fused-path headline: a 100k-cell
policy x intensity x fleet grid through the metrics-only interactive
path (shared workloads, async bucket dispatch, plane-packed carries),
cross-checked bit-identically against the write-back path and by rtol
against the reference event loop, with a roofline-style per-bucket
breakdown (build / compile / dispatch / host-sync) and the measured
cells/sec ratio over the legacy per-cell pipeline."""

import json
import time
from dataclasses import replace

from .common import emit

from repro.core import (
    SweepCell,
    SweepSpec,
    rolling_restart,
    run_cells_scan,
    run_sweep,
    scan_cache_stats,
    simulate_single_node,
)
from repro.core.sweep import make_workload, run_cell


def speedup_spec(quick: bool = False) -> SweepSpec:
    """High-intensity grid for the backend shoot-out: every policy at the
    paper's heaviest published load (10 cores, intensity 120)."""
    return SweepSpec(policies=("fifo", "sept", "eect", "rect", "fc"),
                     intensities=(60,) if quick else (120, 180),
                     cores=(10,), seeds=1 if quick else 2)


def _time_backend(cells, backend: str) -> float:
    """Simulation wall-clock over the grid (workloads pre-generated)."""
    total = 0.0
    for cell in cells:
        reqs = make_workload(cell)
        t0 = time.perf_counter()
        simulate_single_node(reqs, cores=cell.cores, policy=cell.policy,
                             mode="ours", warm=cell.warm, backend=backend)
        total += time.perf_counter() - t0
    return total


def backend_speedup_rows(quick: bool = False,
                         backend: str = "vectorized") -> list[dict]:
    # the speedup row compares concrete fast engines against the event loop;
    # sweep-level selectors (auto/cross-check/reference) from run.py's
    # --backend, and scan without an importable jax, degrade to the
    # vectorized backend instead of erroring out
    if backend not in ("vectorized", "scan"):
        backend = "vectorized"
    if backend == "scan":
        try:
            import jax  # noqa: F401
        except ImportError:
            backend = "vectorized"
    cells = speedup_spec(quick).cells()
    t_ref = _time_backend(cells, "reference")
    t_fast = _time_backend(cells, backend)
    derived = (f"ref_s={t_ref:.2f};{backend}_s={t_fast:.3f};"
               f"speedup={t_ref / t_fast:.1f}x;cells={len(cells)}")
    if backend == "scan":
        # the per-cell timing above pays one jit compile then reuses it;
        # the batched row shows the whole grid as ONE vmapped scan
        from repro.core import run_cells_scan
        t0 = time.perf_counter()
        run_cells_scan(cells)
        derived += f";scan_batch_s={time.perf_counter() - t0:.2f}"
    return [{"name": "engine/simbackend_speedup",
             "us_per_call": t_fast / len(cells) * 1e6,
             "derived": derived}]


def cluster_speedup_spec(quick: bool = False) -> SweepSpec:
    """The cluster-scale grid: nodes x intensity x all five policies x seeds
    through the pull model (the paper's fig6 shape, scaled up).  Full mode is
    1035 cells; quick is a 36-cell smoke grid for CI."""
    if quick:
        return SweepSpec(policies=("fifo", "sept", "fc"),
                         nodes=(2, 4), cores=(8,), intensities=(20, 30),
                         seeds=3, backends=("scan",))
    return SweepSpec(policies=("fifo", "sept", "eect", "rect", "fc"),
                     nodes=(2, 4, 8), cores=(8,), intensities=(30, 50, 70),
                     seeds=23, backends=("scan",))


def cluster_speedup_rows(quick: bool = False) -> list[dict]:
    """Bucketed cluster-scan vs reference event loop on the cluster grid."""
    try:
        import jax  # noqa: F401
    except ImportError:
        return [{"name": "engine/cluster_scan_speedup", "us_per_call": 0.0,
                 "derived": "skipped=no-jax"}]
    cells = cluster_speedup_spec(quick).cells()

    before = scan_cache_stats()            # other rows may have used the
                                           # cache; report deltas, not totals
    t0 = time.perf_counter()
    run_cells_scan(cells)                  # compiles + runs (cold)
    t_cold = time.perf_counter() - t0
    t0 = time.perf_counter()
    run_cells_scan(cells)                  # post-compile, cache hits only
    t_scan = time.perf_counter() - t0

    # reference cost from a stratified sample of the same grid (the full
    # event-loop run would take ~half an hour -- that is the point)
    stride = max(1, len(cells) // (8 if quick else 24))
    sample = cells[::stride]
    t0 = time.perf_counter()
    for cell in sample:
        run_cell(replace(cell, backend="reference", cross_check=False))
    t_ref = (time.perf_counter() - t0) / len(sample) * len(cells)
    stats = scan_cache_stats()
    derived = (f"ref_est_s={t_ref:.1f};scan_s={t_scan:.2f};"
               f"scan_cold_s={t_cold:.2f};speedup={t_ref / t_scan:.1f}x;"
               f"cells={len(cells)};ref_sample={len(sample)};"
               f"buckets={stats['misses'] - before['misses']};"
               f"cache_hits={stats['hits'] - before['hits']}")
    return [{"name": "engine/cluster_scan_speedup",
             "us_per_call": t_scan / len(cells) * 1e6,
             "derived": derived}]


def frontier_spec(quick: bool = False) -> SweepSpec:
    """The autoscaler frontier grid: initial-node-count x provision-delay x
    scale-up-threshold with a static-fleet baseline, one shared burst per
    seed (``workload_cores`` pinned to the largest static fleet so every
    node count faces the *same* offered load).  This is the paper's capstone
    scenario -- "with good scheduling, fewer machines give the same tail" --
    swept at cluster scale through the dynamic-capacity scan kernel."""
    nodes = (2, 3) if quick else (2, 3, 4, 5)
    return SweepSpec(
        policies=("fc",),
        nodes=nodes,
        cores=(8,),
        intensities=(30,) if quick else (40,),
        autoscale=(False, True),
        provision_delays=(10.0,) if quick else (10.0, 30.0, 60.0),
        scale_ups=(2.0,),
        max_nodes=max(nodes) + 2,
        seeds=2 if quick else 5,
        workload_cores=8 * max(nodes),
        backends=("scan",),
    )


def _timed_scan_sweep(spec, sample_div: int, keys=("R_avg", "R_p95",
                                                   "max_c"),
                      exact: bool = False, name: str = "scan"):
    """Shared scaffold for the frontier/straggler rows: run the sweep twice
    (cold = compiles, warm = cache hits), estimate the reference wall from a
    stratified cell sample that doubles as the cross-check (``keys`` within
    ``CLUSTER_XCHECK_RTOL``; with ``exact``, the ``CROSS_CHECK_EXACT`` count
    metrics must match bit-identically).  Returns
    ``(result, cells, timings: dict)``."""
    from repro.core.sweep import CLUSTER_XCHECK_RTOL, CROSS_CHECK_EXACT

    cells = spec.cells()
    t0 = time.perf_counter()
    run_sweep(spec, workers=1)
    t_cold = time.perf_counter() - t0
    t0 = time.perf_counter()
    result = run_sweep(spec, workers=1)
    t_scan = time.perf_counter() - t0

    stride = max(1, len(cells) // sample_div)
    sample = cells[::stride]
    worst_err = 0.0
    t0 = time.perf_counter()
    for cell in sample:
        ref_m = run_cell(replace(cell, backend="reference",
                                 cross_check=False))
        scan_m = next(cr.metrics for cr in result.results
                      if cr.cell == cell)
        cell_err = max(abs(ref_m[k] - scan_m[k]) / max(abs(ref_m[k]), 1e-9)
                       for k in keys)
        worst_err = max(worst_err, cell_err)
        if cell_err > CLUSTER_XCHECK_RTOL:
            raise AssertionError(
                f"{name} cross-check breach on {cell.label()}: "
                f"{cell_err:.3f}")
        if exact:
            for k in CROSS_CHECK_EXACT:
                if ref_m.get(k) != scan_m.get(k):
                    raise AssertionError(
                        f"{name} count mismatch on {cell.label()}: "
                        f"{k} scan={scan_m.get(k)} ref={ref_m.get(k)}")
    t_ref = (time.perf_counter() - t0) / len(sample) * len(cells)
    return result, cells, {"scan_s": t_scan, "scan_cold_s": t_cold,
                           "ref_est_s": t_ref, "worst_err": worst_err,
                           "n_sample": len(sample)}


def frontier_rows(quick: bool = False,
                  artifacts: str | None = None) -> list[dict]:
    """Sweep the frontier grid on the scan backend, cross-check a sample
    against the reference event loop at ``CLUSTER_XCHECK_RTOL``, report the
    measured scan-vs-reference speedup, and extract the paper's claim: the
    best autoscaled config at N initial nodes vs the static fleet at N+1."""
    try:
        import jax  # noqa: F401
    except ImportError:
        return [{"name": "engine/frontier", "us_per_call": 0.0,
                 "derived": "skipped=no-jax"}]
    result, cells, t = _timed_scan_sweep(
        frontier_spec(quick), sample_div=4 if quick else 8,
        name="frontier")

    # the claim: best autoscaled config at N nodes vs static fleet at N+1
    agg = result.aggregate()
    static = {int(r["nodes"]): r for r in agg if not r["autoscale"]}
    best_auto: dict[int, dict] = {}
    for r in agg:
        if r["autoscale"]:
            n = int(r["nodes"])
            if n not in best_auto or r["R_p95"] < best_auto[n]["R_p95"]:
                best_auto[n] = r
    claim = ""
    for n in sorted(best_auto):
        big = static.get(n + 1)
        if big is None:
            continue
        small = best_auto[n]
        if small["R_p95"] <= big["R_p95"]:
            claim = (f"{n}n+auto(pd{small['provision_delay']:g}) "
                     f"p95={small['R_p95']:.2f} <= {n + 1}n static "
                     f"p95={big['R_p95']:.2f}")
            break
    if not claim:
        claim = "no-frontier-point"

    if artifacts:
        import os
        os.makedirs(artifacts, exist_ok=True)
        csv_path = f"{artifacts}/frontier.csv"
        result.to_csv(csv_path)
        try:
            from .plots import plot_frontier
            plot_frontier(agg, "R_p95", f"{artifacts}/frontier_R_p95.png")
        except Exception as e:  # noqa: BLE001  (matplotlib optional)
            print(f"# frontier plot skipped: {e}")

    derived = (f"{claim};scan_s={t['scan_s']:.2f};"
               f"scan_cold_s={t['scan_cold_s']:.2f};"
               f"ref_est_s={t['ref_est_s']:.1f};"
               f"speedup={t['ref_est_s'] / max(t['scan_s'], 1e-9):.1f}x;"
               f"cells={len(cells)};xcheck_n={t['n_sample']};"
               f"xcheck_worst={t['worst_err']:.2e}")
    return [{"name": "engine/frontier",
             "us_per_call": t["scan_s"] / len(cells) * 1e6,
             "derived": derived}]


# straggler grid intensity tiers: the hedging-recovery claim lives at
# moderate load (healthy peers have slack to absorb steals; all push cells
# run here); the pull severity curves continue into sustained backlog,
# where the reference event loop is O(queue) per pull and the scan kernel
# is not -- that asymmetry is where the grid's speedup comes from
STRAGGLER_V = {"claim": 18, "mid": 45, "heavy": 96}
STRAGGLER_V_QUICK = {"claim": 15, "mid": 15, "heavy": 15}


def straggler_spec(quick: bool = False) -> SweepSpec:
    """The straggler frontier grid: degradation severity x hedged/unhedged x
    pull vs push through the scan kernel.  One node runs ``sev`` x slow for
    most of the burst; the push model uses the OpenWhisk home-invoker
    balancer (blind hash routing -- the regime where a slow node actually
    accumulates a queue; least-loaded already self-corrects), the pull model
    is the late-binding alternative whose global queue needs no hedging.
    Tiered intensities (:data:`STRAGGLER_V`): hedged cells run at the claim
    tier, push-unhedged up to mid, pull severity curves through heavy
    backlog -- the regime the scan backend exists for."""
    severities = (2.0, 8.0) if quick else (2.0, 4.0, 6.0, 8.0)
    degrades = (None,) + tuple(((0, 2.0, 300.0, s),) for s in severities)
    tiers = STRAGGLER_V_QUICK if quick else STRAGGLER_V
    return SweepSpec(
        policies=("fc",),
        nodes=(4,),
        cores=(8,),
        intensities=tuple(sorted(set(tiers.values()))),
        assignments=("pull", "push"),
        lbs=("home",),
        degrades=degrades,
        hedge_multiples=(None, 3.0),
        seeds=2 if quick else 5,
        workload_cores=32,
        backends=("scan",),
        cell_filter=(_straggler_cell_filter_quick if quick
                     else _straggler_cell_filter),
    )


def _straggler_cell_filter(cell: SweepCell) -> bool:
    """Tiered ragged grid: hedging is a structural no-op under pull and
    pointless on a healthy fleet (dropped); push cells (hedged or not) run
    at the claim intensity; pull severity curves run at every tier."""
    if cell.hedge_multiple is not None:
        return (cell.assignment == "push" and cell.degrade is not None
                and cell.intensity == STRAGGLER_V["claim"])
    if cell.assignment == "push":
        return cell.intensity == STRAGGLER_V["claim"]
    return True


def _straggler_cell_filter_quick(cell: SweepCell) -> bool:
    if cell.hedge_multiple is None:
        return True
    return cell.assignment == "push" and cell.degrade is not None


def _severity(row: dict) -> float:
    from .plots import row_severity
    return row_severity(row)


def straggler_rows(quick: bool = False,
                   artifacts: str | None = None) -> list[dict]:
    """Sweep the straggler frontier on the scan backend, cross-check a
    sample against the reference event loop (metrics within
    ``CLUSTER_XCHECK_RTOL``; ``backups``/``steals``/``failures`` must match
    exactly), report the measured scan speedup, and extract the claim:
    hedging recovers most of the p95 a degraded node costs the push model,
    while the pull model rides it out structurally."""
    try:
        import jax  # noqa: F401
    except ImportError:
        return [{"name": "engine/straggler", "us_per_call": 0.0,
                 "derived": "skipped=no-jax"}]
    result, cells, t = _timed_scan_sweep(
        straggler_spec(quick), sample_div=4 if quick else 10,
        exact=True, name="straggler")

    # the claim, at the worst swept severity and the claim intensity tier:
    # hedging recovers most of the p95 the slow node cost the push model
    agg = result.aggregate()
    sev_max = max(_severity(r) for r in agg)
    v_claim = min(r["intensity"] for r in agg)
    def _find(assignment, sev, hedged):
        for r in agg:
            if (r["assignment"] == assignment and _severity(r) == sev
                    and r["intensity"] == v_claim
                    and (r["hedge_multiple"] is not None) == hedged):
                return r
        return None
    healthy = _find("push", 1.0, False)
    degraded = _find("push", sev_max, False)
    hedged = _find("push", sev_max, True)
    pull_deg = _find("pull", sev_max, False)
    claim = "no-straggler-point"
    if healthy and degraded and hedged:
        lost = degraded["R_p95"] - healthy["R_p95"]
        rec = (degraded["R_p95"] - hedged["R_p95"]) / max(lost, 1e-9)
        claim = (f"sev{sev_max:g}: push p95 {healthy['R_p95']:.1f}->"
                 f"{degraded['R_p95']:.1f}, hedged {hedged['R_p95']:.1f} "
                 f"(recovered {rec:.0%}, {hedged['backups']:.0f} backups)")
        if pull_deg is not None:
            claim += f", pull {pull_deg['R_p95']:.1f}"

    if artifacts:
        import os
        os.makedirs(artifacts, exist_ok=True)
        result.to_csv(f"{artifacts}/straggler.csv")
        try:
            from .plots import plot_straggler
            plot_straggler(agg, "R_p95",
                           f"{artifacts}/straggler_R_p95.png")
        except Exception as e:  # noqa: BLE001  (matplotlib optional)
            print(f"# straggler plot skipped: {e}")

    derived = (f"{claim};scan_s={t['scan_s']:.2f};"
               f"scan_cold_s={t['scan_cold_s']:.2f};"
               f"ref_est_s={t['ref_est_s']:.1f};"
               f"speedup={t['ref_est_s'] / max(t['scan_s'], 1e-9):.1f}x;"
               f"cells={len(cells)};xcheck_n={t['n_sample']};"
               f"xcheck_worst={t['worst_err']:.2e}")
    return [{"name": "engine/straggler",
             "us_per_call": t["scan_s"] / len(cells) * 1e6,
             "derived": derived}]


def _dup_matrix_filter(cell: SweepCell) -> bool:
    """Duplicate-mode x failure schedules x push is the matrix's one
    documented value-dependent rejection (racing copies of a single id
    across dying nodes); every other combination in the grid runs."""
    return not (cell.assignment == "push" and cell.fail_spec is not None)


def _cold_matrix_filter(cell: SweepCell) -> bool:
    """Tiered cold grid (same shape as the straggler tiers): the push
    model runs at the claim intensity; the pull severity curve continues
    into sustained backlog, where the reference event loop is O(queue)
    per pull and the scan kernel is not."""
    return cell.assignment == "pull" or cell.intensity == 18


def matrix_specs(quick: bool = False) -> list[tuple[str, SweepSpec]]:
    """The newly-closed capability-matrix rows as three scan sub-grids:
    ``steal`` (hedging x autoscale x failure schedules, including kills
    that lose queued calls), ``dup`` (duplicate-mode racing, static and
    under pull-side failures), and ``cold`` (the ``warm=False`` regime on
    both assignment models, with a heavy-backlog pull tier)."""
    steal = SweepSpec(
        policies=("fc",) if quick else ("fc", "sept"),
        nodes=(3,), cores=(6,),
        intensities=(16,) if quick else (16, 25),
        assignments=("push",),
        degrades=(((0, 1.0, 300.0, 5.0),),),
        hedge_multiples=(2.0,),
        fail_specs=(None, rolling_restart(1, start=8.0)),
        autoscale=(False, True),
        scale_ups=(1.0,), provision_delays=(2.0,), max_nodes=5,
        seeds=1 if quick else 2, backends=("scan",),
    )
    dup = SweepSpec(
        policies=("fc",),
        nodes=(3,), cores=(6,),
        intensities=(16,) if quick else (16, 45),
        assignments=("pull", "push"),
        degrades=(((0, 1.0, 300.0, 5.0),),),
        hedge_multiples=(2.0,), hedge_mode="duplicate",
        fail_specs=(None, rolling_restart(1, start=8.0)),
        seeds=1 if quick else 4, backends=("scan",),
        cell_filter=_dup_matrix_filter,
    )
    cold = SweepSpec(
        policies=("fc",) if quick else ("fc", "sept"),
        nodes=(4,), cores=(8,), workload_cores=32,
        intensities=(18,) if quick else (18, 96, 140),
        assignments=("pull", "push"), warm=False,
        seeds=1 if quick else 5, backends=("scan",),
        cell_filter=None if quick else _cold_matrix_filter,
    )
    return [("steal", steal), ("dup", dup), ("cold", cold)]


def matrix_rows(quick: bool = False,
                artifacts: str | None = None) -> list[dict]:
    """Run the closed capability rows end-to-end on the scan backend:
    every cell must stay on the scan path (zero degraded), the stratified
    reference cross-check must hold with ``backups``/``steals``/
    ``failures`` bit-identical, and the summary row reports the combined
    scan-vs-reference speedup."""
    try:
        import jax  # noqa: F401
    except ImportError:
        return [{"name": "engine/matrix", "us_per_call": 0.0,
                 "derived": "skipped=no-jax"}]
    rows: list[dict] = []
    tot_scan = tot_ref = 0.0
    tot_cells = 0
    for name, mspec in matrix_specs(quick):
        result, cells, t = _timed_scan_sweep(
            mspec, sample_div=4 if quick else 8, exact=True,
            name=f"matrix/{name}")
        degraded = sum(1 for cr in result.results
                       if cr.metrics.get("degraded"))
        if degraded:
            raise AssertionError(
                f"matrix/{name}: {degraded} degraded cell(s) -- a "
                "supports()=True row fell off the scan path")
        tot_scan += t["scan_s"]
        tot_ref += t["ref_est_s"]
        tot_cells += len(cells)
        if artifacts:
            import os
            os.makedirs(artifacts, exist_ok=True)
            result.to_csv(f"{artifacts}/matrix_{name}.csv")
        rows.append({
            "name": f"engine/matrix_{name}",
            "us_per_call": t["scan_s"] / len(cells) * 1e6,
            "derived": (
                f"cells={len(cells)};degraded=0;"
                f"scan_s={t['scan_s']:.2f};"
                f"scan_cold_s={t['scan_cold_s']:.2f};"
                f"ref_est_s={t['ref_est_s']:.1f};"
                f"speedup={t['ref_est_s'] / max(t['scan_s'], 1e-9):.1f}x;"
                f"xcheck_n={t['n_sample']};"
                f"xcheck_worst={t['worst_err']:.2e}"),
        })
    rows.append({
        "name": "engine/matrix",
        "us_per_call": tot_scan / max(tot_cells, 1) * 1e6,
        "derived": (f"cells={tot_cells};degraded=0;"
                    f"scan_s={tot_scan:.2f};ref_est_s={tot_ref:.1f};"
                    f"speedup={tot_ref / max(tot_scan, 1e-9):.1f}x"),
    })
    return rows


def mega_spec(quick: bool = False) -> SweepSpec:
    """The 100k-cell interactive-sweep grid: every policy x intensity x
    fleet at a pinned offered load (``workload_cores=16``, so cells that
    differ only in policy or fleet share one generated burst through the
    metrics-only path's workload cache).  Full mode is 100,000 cells
    (5 policies x 2 fleets x 5 intensities x 2000 seeds); quick is a
    240-cell CI slice of the same shape."""
    if quick:
        return SweepSpec(policies=("fifo", "sept", "fc"),
                         nodes=(2, 4), cores=(8,),
                         intensities=(10, 20), seeds=20,
                         workload_cores=16, backends=("scan",))
    return SweepSpec(policies=("fifo", "sept", "eect", "rect", "fc"),
                     nodes=(2, 4), cores=(8,),
                     intensities=(10, 15, 20, 25, 30), seeds=2000,
                     workload_cores=16, backends=("scan",))


def analyse_scan_buckets(recs: list[dict]) -> list[dict]:
    """Roofline-style breakdown for the simulator's scan path: aggregate the
    per-dispatch timing records from ``repro.core.scan_bucket_timings()``
    into one row per bucket shape, splitting the wall into host build (row
    fill), XLA compile, async dispatch (enqueue) and host sync (the block on
    results), with the dominant term named."""
    by_bucket: dict[str, dict] = {}
    for r in recs:
        agg = by_bucket.setdefault(r["bucket"], {
            "bucket": r["bucket"], "bsz": r["bsz"], "cells": 0,
            "chunks": 0, "build_s": 0.0, "compile_s": 0.0,
            "dispatch_s": 0.0, "sync_s": 0.0, "tune_s": 0.0})
        agg["cells"] += r["cells"]
        agg["chunks"] += 1 if r["cells"] else 0   # tune records aren't chunks
        agg["bsz"] = max(agg["bsz"], r["bsz"])
        for k in ("build_s", "compile_s", "dispatch_s", "sync_s"):
            agg[k] += r[k]
        agg["tune_s"] += r.get("tune_s", 0.0)
    out = []
    for agg in by_bucket.values():
        terms = {k: agg[k] for k in ("build_s", "compile_s",
                                     "dispatch_s", "sync_s", "tune_s")}
        agg["dominant"] = max(terms, key=terms.get)
        agg["total_s"] = sum(terms.values())
        agg["cells_per_s"] = (agg["cells"] / agg["total_s"]
                              if agg["total_s"] > 0 else 0.0)
        out.append(agg)
    out.sort(key=lambda a: -a["total_s"])
    return out


def mega_rows(quick: bool = False,
              artifacts: str | None = None) -> list[dict]:
    """The fused-path headline: run the mega grid through
    ``run_cells_scan(metrics_only=True)`` (strict -- a single cell falling
    off the scan path fails the row), cross-check a stratified sample two
    ways (bit-identical against the write-back scan path, rtol against the
    reference event loop), report cells/sec against the legacy per-cell
    pipeline, and emit a roofline-style per-bucket breakdown of where the
    wall went (build vs compile vs dispatch vs host sync)."""
    try:
        import jax  # noqa: F401
    except ImportError:
        return [{"name": "engine/mega", "us_per_call": 0.0,
                 "derived": "skipped=no-jax"}]
    from repro.core import scan_bucket_timings, scan_timings_clear
    from repro.core.sweep import CLUSTER_XCHECK_RTOL

    cells = mega_spec(quick).cells()

    scan_timings_clear()
    t0 = time.perf_counter()
    rows_mo = run_cells_scan(cells, metrics_only=True)
    t_mega = time.perf_counter() - t0
    degraded = sum(1 for m in rows_mo if m.get("degraded"))
    if degraded:        # strict=True already raises; belt and braces
        raise AssertionError(f"mega: {degraded} degraded cell(s)")
    buckets = analyse_scan_buckets(scan_bucket_timings())
    tune_new = sum(b["tune_s"] for b in buckets)
    compile_new = sum(b["compile_s"] for b in buckets) + tune_new

    # legacy rate: the same cells through the PR-6-era interactive path
    # (per-cell workload generation + full write-back).  XLA compiles and
    # chunk auto-tune probes are one-time-per-process on BOTH paths, so the
    # headline ratio compares the setup-excluded walls (each path's own
    # timing records say how much of its wall was compile/tune)
    stride = max(1, len(cells) // (24 if quick else 64))
    sample_idx = list(range(0, len(cells), stride))
    sample = [cells[i] for i in sample_idx]
    scan_timings_clear()
    t0 = time.perf_counter()
    rows_wb = run_cells_scan(sample, metrics_only=False)
    t_legacy = time.perf_counter() - t0
    compile_old = sum(r["compile_s"] + r.get("tune_s", 0.0)
                      for r in scan_bucket_timings())
    rate_new = len(cells) / max(t_mega - compile_new, 1e-9)
    rate_old = len(sample) / max(t_legacy - compile_old, 1e-9)

    # cross-check 1: metrics-only rows must be BIT-identical to the
    # write-back path's rows on the stratified sample
    for i, wb in zip(sample_idx, rows_wb):
        mo = rows_mo[i]
        for k, v in wb.items():
            if mo.get(k) != v:
                raise AssertionError(
                    f"mega metrics-only mismatch on {cells[i].label()}: "
                    f"{k} {mo.get(k)} != {v}")
    # cross-check 2: a small slice against the reference event loop
    ref_n = 3 if quick else 6
    ref_idx = sample_idx[::max(1, len(sample_idx) // ref_n)]
    worst_err = 0.0
    for i in ref_idx:
        cell = cells[i]
        ref_m = run_cell(replace(cell, backend="reference",
                                 cross_check=False))
        mo = rows_mo[i]
        err = max(abs(ref_m[k] - mo[k]) / max(abs(ref_m[k]), 1e-9)
                  for k in ("R_avg", "R_p95", "max_c"))
        worst_err = max(worst_err, err)
        if err > CLUSTER_XCHECK_RTOL:
            raise AssertionError(
                f"mega reference cross-check breach on {cell.label()}: "
                f"{err:.3f}")

    if artifacts:
        import os
        os.makedirs(artifacts, exist_ok=True)
        with open(f"{artifacts}/mega_timings.json", "w") as fh:
            json.dump({"cells": len(cells), "mega_s": t_mega,
                       "compile_s": compile_new, "tune_s": tune_new,
                       "cells_per_s": rate_new,
                       "legacy_cells_per_s": rate_old,
                       "speedup": rate_new / max(rate_old, 1e-9),
                       "degraded": 0, "buckets": buckets}, fh, indent=1)
    if not quick:
        _write_bench_trajectory("BENCH_mega.json", "engine/mega",
                                cells_or_invocations=len(cells),
                                wall_s=round(t_mega, 3),
                                rate=round(rate_new, 2),
                                speedup=round(rate_new / max(rate_old, 1e-9),
                                              3))

    rows = [{
        "name": "engine/mega",
        "us_per_call": t_mega / len(cells) * 1e6,
        "derived": (
            f"cells={len(cells)};degraded=0;mega_s={t_mega:.2f};"
            f"compile_s={compile_new:.2f};tune_s={tune_new:.2f};"
            f"cells_per_s={rate_new:.0f};"
            f"legacy_cells_per_s={rate_old:.0f};"
            f"speedup={rate_new / max(rate_old, 1e-9):.1f}x;"
            f"buckets={len(buckets)};xcheck_exact_n={len(sample)};"
            f"xcheck_ref_n={len(ref_idx)};"
            f"xcheck_worst={worst_err:.2e}"),
    }]
    for i, b in enumerate(buckets[:8]):
        rows.append({
            "name": f"engine/mega_bucket{i}",
            "us_per_call": b["total_s"] / max(b["cells"], 1) * 1e6,
            "derived": (
                f"dominant={b['dominant']};{b['bucket']};bsz={b['bsz']};"
                f"cells={b['cells']};chunks={b['chunks']};"
                f"build_ms={b['build_s']*1e3:.0f};"
                f"compile_ms={b['compile_s']*1e3:.0f};"
                f"tune_ms={b['tune_s']*1e3:.0f};"
                f"dispatch_ms={b['dispatch_s']*1e3:.0f};"
                f"sync_ms={b['sync_s']*1e3:.0f};"
                f"cells_per_s={b['cells_per_s']:.0f}"),
        })
    return rows


# --------------------------------------------------------------------------
# storm: metastable-overload / retry-storm hysteresis (ISSUE 8)
# --------------------------------------------------------------------------
# Six client behaviours on the same ramp-and-release workload: no client
# retries / naive immediate retries / capped exponential backoff + jitter,
# each with and without admission control.  All share the same per-attempt
# timeout, which is what converts a transient burst into retry fuel.
STORM_SCENARIOS = (
    ("no-retry", None, False),
    ("no-retry+shed", None, True),
    ("naive", "immediate", False),
    ("naive+shed", "immediate", True),
    ("backoff", "backoff", False),
    ("backoff+shed", "backoff", True),
)


def _storm_resilience(retry_mode, shed: bool):
    from repro.core import (AdmissionPolicy, ResilienceSpec, RetryPolicy,
                            TimeoutSpec)
    retry = None
    if retry_mode is not None:
        retry = RetryPolicy(max_attempts=4, mode=retry_mode,
                            base_delay_s=0.5, cap_delay_s=8.0, jitter=0.5)
    return ResilienceSpec(
        timeout=TimeoutSpec(multiple=3.0, floor_s=2.0),
        retry=retry,
        admission=AdmissionPolicy(threshold_s=2.0) if shed else None)


def _windowed_goodput(requests, a: float, b: float) -> float:
    """Completions per second observed by clients in [a, b)."""
    n = sum(1 for r in requests if r.c is not None and a <= r.c < b)
    return n / max(b - a, 1e-9)


def storm_rows(quick: bool = False, artifacts: str | None = None,
               duration_s: float | None = None) -> list[dict]:
    """Retry-storm / metastable-overload benchmark (``--rows storm``).

    A ramp-and-release arrival process (base Poisson rate with a burst
    window at [T/3, T/2)) drives every :data:`STORM_SCENARIOS` cell through
    the batched scan kernel AND the reference event loop: the resilience
    counters (timed_out / shed / retries_issued) must match **exactly**
    per cell, and the post-compile scan wall is reported against the
    reference wall.  The hysteresis claim is computed from windowed
    goodput: naive immediate retries stay depressed after the burst
    releases, capped backoff + shedding recovers most of the pre-burst
    goodput."""
    try:
        import jax  # noqa: F401
    except ImportError:
        return [{"name": "engine/storm", "us_per_call": 0.0,
                 "derived": "skipped=no-jax"}]
    import copy

    from repro.core import (generate_trace_burst, simulate_cluster,
                            simulate_cluster_cells_scan)

    nodes, cores, policy = 2, 4, "sept"
    T = float(duration_s) if duration_s else 60.0
    seeds = range(4 if quick else 10)
    burst_t0, burst_t1 = T / 3.0, T / 2.0
    # intensity chosen so the base rate loads the cluster well below
    # saturation (~40%: pre-burst goodput tracks the offered rate with few
    # timeouts) but the 6x burst overshoots capacity ~2.5x: timeouts fire,
    # and what happens next is pure client policy
    intensity, burst_x = 14, 6.0
    bursts = {s: generate_trace_burst(
        cores=nodes * cores, intensity=intensity, seed=1000 + s,
        kind="ramp", duration_s=T, burst_factor=burst_x,
        burst_start_frac=1 / 3, burst_end_frac=1 / 2) for s in seeds}
    cells = [(name, rmode, shed, s)
             for (name, rmode, shed) in STORM_SCENARIOS for s in seeds]

    def _items():
        # fresh Request copies every run: both engines mutate in place
        return [(copy.deepcopy(bursts[s]), nodes, cores, policy, "push",
                 "least_loaded", None, None, None, True,
                 _storm_resilience(rmode, shed))
                for (name, rmode, shed, s) in cells]

    simulate_cluster_cells_scan(_items())          # compile warm-up
    t0 = time.perf_counter()
    scan_res = simulate_cluster_cells_scan(_items())
    scan_s = time.perf_counter() - t0

    t0 = time.perf_counter()
    ref_res = []
    for (name, rmode, shed, s) in cells:
        ref_res.append(simulate_cluster(
            copy.deepcopy(bursts[s]), nodes=nodes, cores_per_node=cores,
            policy=policy, assignment="push", warm=True,
            resilience=_storm_resilience(rmode, shed)))
    ref_s = time.perf_counter() - t0

    # exact-count cross-check: every cell, every resilience counter
    for (name, rmode, shed, s), sr, rr in zip(cells, scan_res, ref_res):
        for k in ("timed_out", "shed", "retries_issued"):
            if getattr(sr, k) != getattr(rr, k):
                raise AssertionError(
                    f"storm counter mismatch on {name}#seed{s}: {k} "
                    f"scan={getattr(sr, k)} ref={getattr(rr, k)}")

    # hysteresis: windowed goodput before the burst vs after it releases.
    # The post window starts a couple of timeout periods after release so
    # a healthy policy has had time to drain the genuine backlog; a
    # metastable one is still burning slots on retries there.
    pre_w = (5.0, burst_t0)
    post_w = (burst_t1 + 0.10 * T, min(burst_t1 + 0.35 * T, T))
    summary: dict[str, dict] = {}
    for (name, rmode, shed, s), sr in zip(cells, scan_res):
        d = summary.setdefault(name, {"pre": [], "post": [], "timed_out": 0,
                                      "shed": 0, "retries_issued": 0})
        d["pre"].append(_windowed_goodput(sr.requests, *pre_w))
        d["post"].append(_windowed_goodput(sr.requests, *post_w))
        d["timed_out"] += sr.timed_out
        d["shed"] += sr.shed
        d["retries_issued"] += sr.retries_issued
    for d in summary.values():
        d["pre"] = sum(d["pre"]) / len(d["pre"])
        d["post"] = sum(d["post"]) / len(d["post"])
        d["recovery"] = d["post"] / max(d["pre"], 1e-9)

    naive, good = summary["naive"], summary["backoff+shed"]
    hysteresis = good["recovery"] - naive["recovery"]

    if artifacts:
        import csv
        import os
        os.makedirs(artifacts, exist_ok=True)
        with open(f"{artifacts}/storm.csv", "w", newline="") as fh:
            w = csv.writer(fh)
            w.writerow(["scenario", "retry_mode", "shed", "pre_goodput",
                        "post_goodput", "recovery", "timed_out", "shed_n",
                        "retries_issued"])
            for (name, rmode, shed) in STORM_SCENARIOS:
                d = summary[name]
                w.writerow([name, rmode or "none", shed,
                            f"{d['pre']:.4f}", f"{d['post']:.4f}",
                            f"{d['recovery']:.4f}", d["timed_out"],
                            d["shed"], d["retries_issued"]])
        # time-binned goodput series for the hysteresis figure
        bin_s = max(2.0, T / 40.0)
        edges = [i * bin_s for i in range(int(T / bin_s) + 1)]
        series = []
        for (name, rmode, shed) in STORM_SCENARIOS:
            reqs = [r for (cname, _rm, _sh, _s), sr in zip(cells, scan_res)
                    if cname == name for r in sr.requests]
            n_seeds = len(list(seeds))
            for a, b in zip(edges[:-1], edges[1:]):
                series.append({
                    "scenario": name, "t": (a + b) / 2.0,
                    "goodput": _windowed_goodput(reqs, a, b) / n_seeds,
                    "burst_t0": burst_t0, "burst_t1": burst_t1})
        with open(f"{artifacts}/storm_series.csv", "w", newline="") as fh:
            w = csv.DictWriter(fh, fieldnames=["scenario", "t", "goodput",
                                               "burst_t0", "burst_t1"])
            w.writeheader()
            w.writerows(series)
        try:
            from .plots import plot_storm
            plot_storm(series, out=f"{artifacts}/storm_goodput.png")
        except (ImportError, ValueError):
            pass

    rows = [{
        "name": "engine/storm",
        "us_per_call": scan_s / len(cells) * 1e6,
        "derived": (
            f"cells={len(cells)};T={T:g}s;scan_s={scan_s:.2f};"
            f"ref_s={ref_s:.2f};speedup={ref_s / max(scan_s, 1e-9):.1f}x;"
            f"xcheck_exact_n={len(cells)};"
            f"naive_recovery={naive['recovery']:.2f};"
            f"backoff_shed_recovery={good['recovery']:.2f};"
            f"hysteresis={hysteresis:.2f}"),
    }]
    for (name, rmode, shed) in STORM_SCENARIOS:
        d = summary[name]
        rows.append({
            "name": f"engine/storm_{name}",
            "us_per_call": d["post"] * 1e6,
            "derived": (
                f"pre_goodput={d['pre']:.2f}/s;"
                f"post_goodput={d['post']:.2f}/s;"
                f"recovery={d['recovery']:.2f};"
                f"timed_out={d['timed_out']};shed={d['shed']};"
                f"retries={d['retries_issued']}"),
        })
    return rows


# --------------------------------------------------------------------------
# planet: the million-invocation streaming frontier (ISSUE 9)
# --------------------------------------------------------------------------
PLANET_SEED = 7
PLANET_FNS = 10_000
# the 32-fn slice fits alpha~2.0 on its own head; the full Azure dataset's
# app popularity decays much milder, so the synthetic tail uses ~0.7 --
# steep enough to stay heavy-tailed, mild enough that all 10k functions
# are actually invoked over a day-scale stream (see synth.expand_catalog)
PLANET_TAIL_ALPHA = 0.7
PLANET_RATE_SCALE = 40.0          # ~175 invocations/s offered


def _planet_model():
    from pathlib import Path

    from repro.core.synth import expand_catalog, fit_azure_csv

    trace = (Path(__file__).resolve().parent.parent / "data"
             / "azure_trace_slice.csv")
    return expand_catalog(fit_azure_csv(trace), PLANET_FNS,
                          rate_scale=PLANET_RATE_SCALE,
                          tail_alpha=PLANET_TAIL_ALPHA)


def _planet_fleet():
    """Lambda-style fleet: single-concurrency micro-VMs (one core each, 4 MB
    per warm container so the 10k-function catalog stays resident).  The
    fleet starts at 96 nodes -- just under the stream's mean demand of ~91
    busy cores (rho ~0.95), the overnight-low provisioning a real operator
    would run -- and the queue-pressure autoscaler (one node per 15s tick,
    60s provision delay) ratchets it up to 128 across the diurnal bursts.
    The cap also sizes the kernel's pow2 node axis, so 128 keeps the padded
    node plane half the size 129+ would cost."""
    from repro.core import ClusterDynamics

    dyn = ClusterDynamics(autoscale=True, autoscale_interval_s=15.0,
                          scale_up_queue_per_slot=0.5,
                          provision_delay_s=60.0, max_nodes=128)
    return dict(nodes=96, cores_per_node=1, policy="sept", assignment="pull",
                warm=True, container_mb=4, dynamics=dyn)


def planet_rows(quick: bool = False,
                artifacts: str | None = None) -> list[dict]:
    """The streaming frontier (``--rows planet``): replay a multi-hour,
    10k-function, Azure-calibrated synthetic day (:mod:`repro.core.synth`)
    through the chunked carry-handoff path on an autoscaled 96->128-node
    fleet.  Evidence reported with the headline steady-state rate:

    * **bounded memory** -- the same stream replayed at half length must hit
      the *same* peak request-tensor footprint (peak is O(chunk), not O(n));
    * **stratified cross-check** -- materialized prefixes (remapped onto
      their active functions, which keeps the single-shot path's dense
      ``(f_b, kq)`` queue tables small) run through the single-shot scan:
      counters must match exactly and clocks within the documented
      ``CLUSTER_XCHECK_RTOL``, and the honest chunked-vs-single-shot wall
      ratio on those short streams is part of the row."""
    try:
        import jax  # noqa: F401
    except ImportError:
        return [{"name": "engine/planet", "us_per_call": 0.0,
                 "derived": "skipped=no-jax"}]
    import numpy as np

    from repro.core.fastpath import simulate_cluster_scan
    from repro.core.request import Request
    from repro.core.streamscan import (simulate_cluster_stream,
                                       stream_from_requests)
    from repro.core.sweep import CLUSTER_XCHECK_RTOL

    model = _planet_model()
    # 2^20 invocations ~= a 1.7-hour day-slice at the offered ~175/s.  The
    # chunk budget is the peak-memory knob AND the throughput knob: the
    # streaming path sizes each fresh slice adaptively so carried backlog +
    # fresh events fill a 4096-row compiled shape.  A fixed fresh count is
    # measurably worse here -- the steady ~500-1000-row queue pushed every
    # 3584-fresh chunk over the pow2 boundary to 8192 padded rows and the
    # marginal rate halved (~300/s -> ~150/s)
    n_inv = 50_000 if quick else 1 << 20
    chunk = 4096
    fleet = _planet_fleet()

    def _replay(limit):
        import sys

        def _tick(chunks_done, events_done, wall):
            print(f"planet: chunk {chunks_done} done, {events_done}/{limit} "
                  f"events, {wall:.0f}s ({events_done / max(wall, 1e-9):.0f}"
                  "/s incl. compile)", file=sys.stderr, flush=True)

        stream = model.stream(PLANET_SEED, max_invocations=limit)
        return simulate_cluster_stream(stream, chunk=chunk, progress=_tick,
                                       **fleet)

    sr = _replay(n_inv)                      # the headline run
    half = _replay(n_inv // 2)               # memory evidence: half length
    if sr.peak_rows != half.peak_rows:
        raise AssertionError(
            f"planet peak not flat: peak_rows {sr.peak_rows} at n={n_inv} "
            f"vs {half.peak_rows} at n={n_inv // 2}")
    s = sr.summary()
    sim_hours = float(sr.t[-1] - sr.t[0]) / 3600.0 if sr.n else 0.0

    # stratified cross-check: materialized prefixes vs the single-shot scan
    prefixes = (1_000, 2_500) if quick else (2_000, 5_000, 8_000)
    worst_drift = 0.0
    t_single = t_chunked = 0.0
    for k in prefixes:
        reqs = []
        for ch in model.stream(PLANET_SEED,
                               max_invocations=k).iter_chunks():
            reqs.extend(Request(fn=model.fns[fi], r=float(t),
                                p_true=float(p))
                        for t, fi, p in zip(ch.r, ch.fn, ch.p))
        t0 = time.perf_counter()
        ref = simulate_cluster_scan(
            [Request(fn=q.fn, r=q.r, p_true=q.p_true) for q in reqs],
            **fleet)
        t_single += time.perf_counter() - t0
        stream, order = stream_from_requests(reqs, chunk=1024)
        t0 = time.perf_counter()
        pr = simulate_cluster_stream(stream, chunk=1024, **fleet)
        t_chunked += time.perf_counter() - t0
        for key, want in (("failures", ref.failures),
                          ("cold_starts", ref.cold_starts),
                          ("timed_out", ref.timed_out),
                          ("shed", ref.shed),
                          ("retries_issued", ref.retries_issued),
                          ("steals_won", ref.steals_won),
                          ("backups_issued", ref.backups_issued)):
            if pr.counters[key] != want:
                raise AssertionError(
                    f"planet prefix={k} counter {key}: "
                    f"chunked={pr.counters[key]} single={want}")
        ref_start = np.array([np.nan if r.start is None else r.start
                              for r in ref.requests])[order]
        if not np.array_equal(np.isnan(pr.start), np.isnan(ref_start)):
            raise AssertionError(f"planet prefix={k}: served-set mismatch")
        ok = np.isfinite(ref_start)
        drift = float(np.max(np.abs(pr.start[ok] - ref_start[ok]) /
                             np.maximum(np.abs(ref_start[ok]), 1.0),
                             initial=0.0))
        worst_drift = max(worst_drift, drift)
        if drift > CLUSTER_XCHECK_RTOL:
            raise AssertionError(
                f"planet prefix={k}: clock drift {drift:.3e} beyond "
                f"{CLUSTER_XCHECK_RTOL}")
    # honest short-stream overhead: both walls include their own compiles
    vs_single = t_chunked / max(t_single, 1e-9)

    if artifacts:
        import csv
        import os
        os.makedirs(artifacts, exist_ok=True)
        with open(f"{artifacts}/planet.csv", "w", newline="") as fh:
            w = csv.writer(fh)
            w.writerow(["invocations", "fns", "sim_hours", "wall_s", "rate",
                        "nodes_used", "peak_rows", "peak_rows_half",
                        "peak_bytes", "chunks", "mean_resp", "p99",
                        "xcheck_prefixes", "xcheck_worst_drift",
                        "chunked_vs_single_wall"])
            w.writerow([sr.n, len(model.fns), f"{sim_hours:.3f}",
                        f"{sr.wall_s:.2f}", f"{s['rate']:.1f}",
                        sr.nodes_used, sr.peak_rows, half.peak_rows,
                        sr.peak_bytes, sr.chunks,
                        f"{s.get('mean_resp', 0.0):.4f}",
                        f"{s.get('p99', 0.0):.4f}",
                        "/".join(str(k) for k in prefixes),
                        f"{worst_drift:.3e}", f"{vs_single:.2f}"])
        # time-binned completions/s + provisioned nodes for the figure
        t_end = float(sr.t[-1]) if sr.n else 0.0
        bin_s = max(60.0, t_end / 120.0)
        fin = sr.finish[sr.failed == 0]
        act = (np.array(sr.timeline.activate)
               if sr.timeline is not None else np.zeros(sr.nodes_used))
        series = []
        for i in range(int(t_end / bin_s) + 1):
            a, b = i * bin_s, (i + 1) * bin_s
            series.append({
                "t": (a + b) / 2.0,
                "rate": float(((fin >= a) & (fin < b)).sum()) / bin_s,
                "nodes": int((act <= b).sum()),
            })
        with open(f"{artifacts}/planet_series.csv", "w", newline="") as fh:
            w = csv.DictWriter(fh, fieldnames=["t", "rate", "nodes"])
            w.writeheader()
            w.writerows(series)
        try:
            from .plots import plot_planet
            plot_planet(series, out=f"{artifacts}/planet_rate.png")
        except (ImportError, ValueError):
            pass

    if not quick:
        _write_bench_trajectory("BENCH_planet.json", "engine/planet",
                                cells_or_invocations=sr.n,
                                wall_s=round(sr.wall_s, 3),
                                rate=round(s["rate"], 2),
                                speedup=round(1.0 / max(vs_single, 1e-9), 3))

    return [{
        "name": "engine/planet",
        "us_per_call": sr.wall_s / max(sr.n, 1) * 1e6,
        "derived": (
            f"inv={sr.n};fns={len(model.fns)};sim_hours={sim_hours:.2f};"
            f"wall_s={sr.wall_s:.1f};rate={s['rate']:.0f}/s;"
            f"nodes_used={sr.nodes_used};chunks={sr.chunks};"
            f"peak_rows={sr.peak_rows};peak_rows_half={half.peak_rows};"
            f"peak_flat=yes;mean_resp={s.get('mean_resp', 0.0):.3f};"
            f"p99={s.get('p99', 0.0):.3f};"
            f"xcheck={'/'.join(str(k) for k in prefixes)};"
            f"xcheck_drift={worst_drift:.1e};"
            f"chunked_vs_single_wall={vs_single:.2f}x"),
    }]


def _write_bench_summary(group: str, rows: list[dict]) -> None:
    """Write/refresh ``BENCH_<group>.json`` at the repo root: the group's
    benchmark rows plus the flight-recorder run manifest (git sha, jax
    platform, scan-cache/bucket-timing stats, ``REPRO_*``/``JAX_*``/
    ``XLA_*`` env) so a committed number is reproducible later.  Merges
    into any existing payload -- the mega/planet trajectory keys written
    by :func:`_write_bench_trajectory` survive."""
    from pathlib import Path

    from repro.core import run_manifest

    path = Path(__file__).resolve().parent.parent / f"BENCH_{group}.json"
    payload = {}
    if path.exists():
        try:
            payload = json.loads(path.read_text())
        except (ValueError, OSError):
            payload = {}
    payload["rows"] = rows
    payload["manifest"] = run_manifest()
    path.write_text(json.dumps(payload, indent=1, sort_keys=True) + "\n")


def _write_bench_trajectory(fname: str, row: str, **metrics) -> None:
    """Append/refresh a row in a committed ``BENCH_*.json`` trajectory
    artifact at the repo root (schema: row name -> {cells_or_invocations,
    wall_s, rate, speedup})."""
    from pathlib import Path

    path = Path(__file__).resolve().parent.parent / fname
    payload = {}
    if path.exists():
        try:
            payload = json.loads(path.read_text())
        except (ValueError, OSError):
            payload = {}
    payload[row] = metrics
    path.write_text(json.dumps(payload, indent=1, sort_keys=True) + "\n")


ROW_GROUPS = ("all", "backend", "cluster", "frontier", "straggler",
              "matrix", "mega", "storm", "planet")


def run(quick: bool = False, backend: str = "vectorized",
        rows_group: str = "all", artifacts: str | None = None) -> list[dict]:
    rows = []

    def _group(name: str, new_rows: list[dict]) -> None:
        rows.extend(new_rows)
        _write_bench_summary(name, new_rows)

    if rows_group in ("all", "backend"):
        _group("backend", backend_speedup_rows(quick, backend=backend))
    if rows_group in ("all", "cluster"):
        _group("cluster", cluster_speedup_rows(quick))
    if rows_group in ("all", "frontier"):
        _group("frontier", frontier_rows(quick, artifacts=artifacts))
    if rows_group in ("all", "straggler"):
        _group("straggler", straggler_rows(quick, artifacts=artifacts))
    if rows_group in ("all", "matrix"):
        _group("matrix", matrix_rows(quick, artifacts=artifacts))
    if rows_group in ("all", "mega"):
        _group("mega", mega_rows(quick, artifacts=artifacts))
    if rows_group in ("all", "storm"):
        _group("storm", storm_rows(quick, artifacts=artifacts))
    if rows_group in ("all", "planet"):
        _group("planet", planet_rows(quick, artifacts=artifacts))
    return rows


def main(quick: bool = False, backend: str = "vectorized",
         rows_group: str = "all", json_path: str | None = None,
         artifacts: str | None = None) -> None:
    rows = run(quick, backend=backend, rows_group=rows_group,
               artifacts=artifacts)
    emit(rows)
    if json_path:
        with open(json_path, "w") as fh:
            json.dump(rows, fh, indent=1)


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--backend", default="vectorized",
                    choices=("vectorized", "scan"),
                    help="fast backend for the speedup row")
    ap.add_argument("--rows", default="all", choices=ROW_GROUPS,
                    help="which benchmark rows to run")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="also write the rows as a JSON artifact")
    ap.add_argument("--artifacts", default=None, metavar="DIR",
                    help="directory for the frontier CSV/plot artifacts")
    args = ap.parse_args()
    main(args.quick, backend=args.backend, rows_group=args.rows,
         json_path=args.json, artifacts=args.artifacts)
