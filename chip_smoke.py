#!/usr/bin/env python3
"""Chip smoke test: drive the simulator's main path once on one TPU.

Run from the root of a checkout, with no arguments:

    python chip_smoke.py

It refuses to run (nonzero exit, no result line) unless JAX's first device
is a TPU, and it runs everything in this one process, which holds the chip.

* Phase A -- float32 base pull, the Pallas event-step kernel: the mega
  grid's fleet (5 policies x {2, 4} nodes x 8 cores x intensities 10-30 at
  ``workload_cores=16``, 20 seeds: 1,000 cells) through
  ``run_cells_scan(metrics_only=True, strict=True)``.  Zero degraded cells;
  every Pallas bucket's compiled scan holds a ``tpu_custom_call``; one
  bucket per FC/non-FC shape compared on the chip between the Pallas kernel
  and the jnp oracle; a stratified sample cross-checked against the
  reference ``Cluster``.  The paper's 4-node x 18-core cluster (Fig. 6) is
  outside the warm regime's memory bound, so it runs in phase B cold.
* Phase B -- the float64 families: pull autoscale with node kills, push
  steal hedging (with kills and autoscale), cold starts (including the
  paper's 4 x 18 cluster at intensity 30) and the retry-storm resilience
  cell, each cross-checked against the reference ``Cluster``.
* Phase C -- the planet fleet streamed: 10 simulated minutes of the
  10k-function Azure-fitted catalog on the 96->128-node autoscaled fleet
  through ``simulate_cluster_stream``; prefixes checked against the
  single-shot scan and the reference ``Cluster``.

Counters in ``CROSS_CHECK_EXACT`` must match exactly and clocks within
``CLUSTER_XCHECK_RTOL``; any mismatch fails the script.  Each phase prints
its device, work, compile and wall seconds, rate, and the kernel path of
every bucket.  The last line is one JSON object naming the device.
JAX's persistent compilation cache sits where ``JAX_COMPILATION_CACHE_DIR``
says, else in ``.jax_cache/`` of the checkout, so a second run on the same
machine reports far fewer compile seconds.
"""

from __future__ import annotations

import copy
import json
import sys
import time
from dataclasses import replace
from pathlib import Path

ROOT = Path(__file__).resolve().parent

# phase sizes (the defaults are the real run; a CPU rehearsal passes less)
MEGA_SEEDS = 20                 # x 50 fleet points = 1,000 cells
XCHECK_STRIDE = 50              # phase A reference sample: every 50th cell
STORM_SEEDS = 4                 # x 6 client behaviours = 24 cells
PLANET_MINUTES = 10             # simulated minutes streamed (~105k calls)
PLANET_PREFIXES = (2_000, 5_000)    # single-shot vs streamed prefixes
PLANET_REF_PREFIX = 2_000           # reference Cluster prefix


class SmokeFailure(AssertionError):
    """A phase produced a wrong or incomplete result."""


def _log(phase: str, msg: str) -> None:
    print(f"[{phase}] {msg}", flush=True)


def _check(ok: bool, msg: str) -> None:
    if not ok:
        raise SmokeFailure(msg)


def _device() -> str:
    import jax

    dev = jax.devices()[0]
    return f"platform={dev.platform} device_kind={dev.device_kind}"


def _compile_s() -> float:
    """Bucket compile seconds of the runners compiled since the last
    ``scan_cache_clear`` (cache hits of the persistent cache included)."""
    from repro.core import scan_cache_stats

    return sum(e["compile_s"] for e in scan_cache_stats()["entries"].values())


def _bucket_report(phase: str) -> dict:
    """Print each compiled bucket's kernel path and check that the Pallas
    path's compiled scan holds the TPU kernel; returns ``{path: buckets}``."""
    from repro.core import fastpath as fp
    from repro.core import scan_bucket_timings
    from repro.kernels import ops

    cells: dict[str, int] = {}
    for rec in scan_bucket_timings():
        cells[rec["bucket"]] = cells.get(rec["bucket"], 0) + rec["cells"]
    paths: dict[str, int] = {}
    for key, entry in fp._SCAN_CACHE.items():
        _, step_kw = fp._runner_kwargs(key)
        path = ops.event_step_path(**step_kw)
        paths[path] = paths.get(path, 0) + 1
        texts = [scan_c.as_text() for _, scan_c in entry.runners.values()]
        custom = all("tpu_custom_call" in t for t in texts)
        if path == "pallas":
            _check(custom, f"{phase}: Pallas bucket {fp._bucket_tag(key)} "
                           "compiled without tpu_custom_call")
        tag = fp._bucket_tag(key)
        _log(phase, f"bucket {tag} path={path} cells={cells.get(tag, 0)} "
                    f"batches={sorted(entry.runners)} "
                    f"compile_s={sum(entry.compile_s.values())!r} "
                    f"tpu_custom_call={'yes' if custom else 'no'}")
    return paths


def _xcheck_cells(phase: str, cells, rows) -> float:
    """Cross-check scan rows against reference ``Cluster`` runs of the same
    cells: exact ``CROSS_CHECK_EXACT`` counters, clocks within
    ``CLUSTER_XCHECK_RTOL``.  Returns the worst relative clock error."""
    from repro.core.sweep import CLUSTER_XCHECK_RTOL, _cross_check, run_cell

    worst = 0.0
    for cell, row in zip(cells, rows):
        ref = run_cell(replace(cell, backend="reference", cross_check=False))
        _check("degraded" not in ref, f"{phase}: reference run degraded")
        worst = max(worst, _cross_check(cell, ref, row, "scan",
                                        rtol=CLUSTER_XCHECK_RTOL))
    return worst


def _scan_cells(cells):
    """Prepared ``_ScanCell``s (pull, warm) grouped by bucket key."""
    from repro.core import fastpath as fp
    from repro.core.sweep import make_workload

    groups: dict[tuple, list] = {}
    for cell in cells:
        reqs = make_workload(cell)
        sc = fp._ScanCell(requests=reqs, feats=fp._arrival_features(reqs),
                          cores=cell.cores, nodes=cell.nodes,
                          policy=cell.policy, assignment=cell.assignment)
        groups.setdefault(sc.bucket(), []).append(sc)
    return groups


def pallas_vs_oracle(key, cells) -> str:
    """Run one bucket through the Pallas kernel and the jnp oracle on the
    current device; rows ``[:n]`` must agree bit for bit.  Returns
    ``"bit-identical"`` or the first differing cell, field and event."""
    from functools import partial

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import fastpath as fp
    from repro.kernels import ops

    state_kw, step_kw = fp._runner_kwargs(key)
    inp = fp._fill_bucket_inputs(key, cells, fp._pow2(len(cells)))
    arrs = {k: jnp.asarray(v) for k, v in inp.items()}
    clk, ctr = jax.jit(jax.vmap(partial(fp._make_planes, **state_kw)))(arrs)
    outs = {force: jax.tree_util.tree_map(np.asarray, jax.jit(
        partial(ops.event_step, force=force, **step_kw))(clk, ctr, arrs))
        for force in ("ref", "pallas")}
    for b, cell in enumerate(cells):
        n = len(cell.feats.t)
        for i, name in enumerate(("start", "finish", "prio", "node")):
            a, c = outs["ref"][i][b, :n], outs["pallas"][i][b, :n]
            if not np.array_equal(a, c):
                e = int(np.nonzero(a != c)[0][0])
                return (f"cell {b} field {name} event {e}: oracle {a[e]!r} "
                        f"pallas {c[e]!r}")
    return "bit-identical"


def phase_a(seeds: int = MEGA_SEEDS, stride: int = XCHECK_STRIDE) -> None:
    from repro.core import (SweepSpec, run_cells_scan, scan_cache_clear,
                            scan_timings_clear)
    from repro.core.fastpath import cluster_scan_eligible
    from repro.core.sweep import SweepCell, make_workload

    spec = SweepSpec(policies=("fifo", "sept", "eect", "rect", "fc"),
                     nodes=(2, 4), cores=(8,),
                     intensities=(10, 15, 20, 25, 30), seeds=seeds,
                     workload_cores=16, backends=("scan",))
    cells = spec.cells()
    scan_cache_clear()
    scan_timings_clear()
    t0 = time.perf_counter()
    rows = run_cells_scan(cells, metrics_only=True, strict=True)
    wall = time.perf_counter() - t0
    compile_s = _compile_s()
    degraded = sum(1 for m in rows if m.get("degraded"))
    _check(degraded == 0, f"A: {degraded} degraded cells")
    _log("A", f"{_device()} cells={len(cells)} compile_s={compile_s!r} "
              f"wall_s={wall!r} "
              f"rate={len(cells) / wall!r} cells/s "
              f"steady_rate={len(cells) / max(wall - compile_s, 1e-9)!r} "
              f"cells/s degraded=0")
    paths = _bucket_report("A")
    _check(paths.get("pallas", 0) > 0, "A: no bucket ran the Pallas kernel")

    # one bucket per FC / non-FC shape: Pallas vs the jnp oracle, on chip
    from repro.core.fastpath import _mask_features

    seen = set()
    for key, scs in sorted(_scan_cells(cells[::10]).items()):
        use_fc = _mask_features(key[0])["use_fc"]
        if use_fc in seen:
            continue
        seen.add(use_fc)
        _log("A", f"pallas_vs_oracle use_fc={use_fc} cells={len(scs)}: "
                  f"{pallas_vs_oracle(key, scs)}")

    sample = list(range(0, len(cells), stride))
    worst = _xcheck_cells("A", [cells[i] for i in sample],
                          [rows[i] for i in sample])
    _log("A", f"reference xcheck cells={len(sample)} worst_rel_err={worst!r}")

    paper = SweepCell(policy="fc", nodes=4, cores=18, intensity=30,
                      workload_cores=72, seed=0, backend="scan")
    ok = cluster_scan_eligible(make_workload(paper), 4, 18, "fc")
    _log("A", f"paper 4x18 warm cluster scan-eligible={ok} (warm-up of 18 "
              "containers per SeBS function exceeds a 40 GB node; it runs "
              "cold in phase B)")


def phase_b(storm_seeds: int = STORM_SEEDS) -> None:
    import numpy as np

    from benchmarks.engine_bench import (STORM_SCENARIOS, _storm_resilience,
                                         matrix_specs)
    from repro.core import (SweepSpec, generate_trace_burst, run_cells_scan,
                            scan_cache_clear, scan_timings_clear,
                            simulate_cluster, simulate_cluster_cells_scan)
    from repro.core.stragglers import rolling_restart
    from repro.core.sweep import CLUSTER_XCHECK_RTOL, SweepCell

    # the capability-matrix sub-grids at their CI size (one bucket shape
    # per feature combination), plus pull-side kills under the autoscaler
    # and the paper's 4 x 18 cluster in the cold regime
    pull_dyn = SweepSpec(policies=("fc", "sept"), nodes=(3,), cores=(6,),
                         intensities=(16,), assignments=("pull",),
                         fail_specs=(rolling_restart(1, start=8.0),),
                         autoscale=(True,), scale_ups=(1.0,),
                         provision_delays=(2.0,), max_nodes=5, seeds=2,
                         backends=("scan",)).cells()
    specs = dict(matrix_specs(quick=True))
    cold = specs["cold"].cells() + [
        SweepCell(policy=p, nodes=4, cores=18, intensity=30,
                  workload_cores=72, warm=False, seed=0, backend="scan")
        for p in ("fc", "sept")]
    families = (("pull-autoscale-kills", pull_dyn),
                ("push-steal-hedging", specs["steal"].cells()),
                ("cold-starts", cold))

    scan_cache_clear()
    scan_timings_clear()
    t_all = time.perf_counter()
    for name, cells in families:
        t0 = time.perf_counter()
        rows = run_cells_scan(cells, metrics_only=True, strict=True)
        wall = time.perf_counter() - t0
        degraded = sum(1 for m in rows if m.get("degraded"))
        _check(degraded == 0, f"B {name}: {degraded} degraded cells")
        worst = _xcheck_cells(f"B {name}", cells, rows)
        _log("B", f"{name}: cells={len(cells)} wall_s={wall!r} "
                  f"rate={len(cells) / wall!r} cells/s degraded=0 "
                  f"reference xcheck cells={len(cells)} "
                  f"worst_rel_err={worst!r}")

    # retry storm: ramp-and-release burst, six client behaviours
    nodes, cores, T = 2, 4, 60.0
    bursts = {s: generate_trace_burst(
        cores=nodes * cores, intensity=14, seed=1000 + s, kind="ramp",
        duration_s=T, burst_factor=6.0, burst_start_frac=1 / 3,
        burst_end_frac=1 / 2) for s in range(storm_seeds)}
    storm = [(rmode, shed, s) for (_, rmode, shed) in STORM_SCENARIOS
             for s in range(storm_seeds)]
    t0 = time.perf_counter()
    scan = simulate_cluster_cells_scan([
        (copy.deepcopy(bursts[s]), nodes, cores, "sept", "push",
         "least_loaded", None, None, None, True, _storm_resilience(rm, sh))
        for (rm, sh, s) in storm])
    wall = time.perf_counter() - t0
    worst = 0.0
    for (rm, sh, s), sr in zip(storm, scan):
        ref = simulate_cluster(copy.deepcopy(bursts[s]), nodes=nodes,
                               cores_per_node=cores, policy="sept",
                               assignment="push", warm=True,
                               resilience=_storm_resilience(rm, sh))
        for k in ("timed_out", "shed", "retries_issued"):
            _check(getattr(sr, k) == getattr(ref, k),
                   f"B storm {rm}/{sh}#{s}: {k} scan={getattr(sr, k)} "
                   f"reference={getattr(ref, k)}")
        # deep copies keep request ids: compare request by request
        fa = {r.id: r for r in sr.requests}
        fb = {r.id: r for r in ref.requests}
        _check({i: r.failed for i, r in fa.items()}
               == {i: r.failed for i, r in fb.items()},
               f"B storm {rm}/{sh}#{s}: failed requests differ")
        ok = [i for i, r in fb.items() if r.finish is not None]
        a = np.array([fa[i].finish for i in ok])
        b = np.array([fb[i].finish for i in ok])
        err = float(np.max(np.abs(a - b) / np.maximum(np.abs(b), 1.0),
                           initial=0.0))
        _check(err <= CLUSTER_XCHECK_RTOL,
               f"B storm {rm}/{sh}#{s}: clock drift {err!r}")
        worst = max(worst, err)
    # the same storm cell through the single-call public entry point
    rm, sh, s = storm[-1]
    one = simulate_cluster(copy.deepcopy(bursts[s]), nodes=nodes,
                           cores_per_node=cores, policy="sept",
                           assignment="push", warm=True, backend="scan",
                           resilience=_storm_resilience(rm, sh))
    _check([r.finish for r in one.requests]
           == [r.finish for r in scan[-1].requests],
           "B storm: simulate_cluster(backend='scan') differs from the batch")
    _log("B", f"retry-storm: cells={len(storm)} wall_s={wall!r} "
              f"rate={len(storm) / wall!r} cells/s reference xcheck "
              f"cells={len(storm)} worst_rel_err={worst!r}")
    _log("B", f"{_device()} cells={sum(len(c) for _, c in families)}"
              f"+{len(storm)} compile_s={_compile_s()!r} "
              f"wall_s={time.perf_counter() - t_all!r}")
    paths = _bucket_report("B")
    _check(set(paths) == {"jnp"}, f"B: unexpected kernel paths {paths}")


def phase_c(minutes: int = PLANET_MINUTES,
            prefixes: tuple = PLANET_PREFIXES,
            ref_prefix: int = PLANET_REF_PREFIX) -> None:
    import numpy as np

    from benchmarks.engine_bench import (PLANET_SEED, _planet_fleet,
                                         _planet_model)
    from repro.core import scan_cache_clear, simulate_cluster
    from repro.core.fastpath import simulate_cluster_scan
    from repro.core.request import Request
    from repro.core.streamscan import (simulate_cluster_stream,
                                       stream_from_requests)
    from repro.core.sweep import CLUSTER_XCHECK_RTOL

    model = _planet_model()
    fleet = _planet_fleet()
    def _tick(chunks, events, wall):
        if chunks % 5 == 0:
            _log("C", f"chunk {chunks}: {events} invocations in {wall!r}s")

    scan_cache_clear()
    sr = simulate_cluster_stream(model.stream(PLANET_SEED, minutes=minutes),
                                 chunk=4096, progress=_tick, **fleet)
    compile_s = _compile_s()
    span = float(sr.t[-1] - sr.t[0]) if sr.n else 0.0
    _check(sr.n > 0 and bool(np.all(sr.failed == 0)),
           "C: stream served nothing or lost calls")
    _log("C", f"{_device()} invocations={sr.n} sim_s={span!r} "
              f"chunks={sr.chunks} "
              f"compile_s={compile_s!r} wall_s={sr.wall_s!r} "
              f"rate={sr.n / sr.wall_s!r} inv/s "
              f"steady_rate={sr.n / max(sr.wall_s - compile_s, 1e-9)!r} "
              f"inv/s nodes_used={sr.nodes_used} peak_rows={sr.peak_rows}")
    _bucket_report("C")

    d = fleet["dynamics"]
    ref_kw = dict(nodes=fleet["nodes"], cores_per_node=fleet["cores_per_node"],
                  policy=fleet["policy"], assignment=fleet["assignment"],
                  warm=fleet["warm"], container_mb=fleet["container_mb"],
                  autoscale=d.autoscale,
                  autoscale_interval_s=d.autoscale_interval_s,
                  scale_up_queue_per_slot=d.scale_up_queue_per_slot,
                  provision_delay_s=d.provision_delay_s,
                  max_nodes=d.max_nodes)

    def _prefix(k):
        reqs = []
        for ch in model.stream(PLANET_SEED, max_invocations=k).iter_chunks():
            reqs.extend(Request(fn=model.fns[fi], r=float(t), p_true=float(p))
                        for t, fi, p in zip(ch.r, ch.fn, ch.p))
        return reqs

    def _fresh(reqs):
        return [Request(fn=q.fn, r=q.r, p_true=q.p_true) for q in reqs]

    worst = 0.0
    for k in prefixes:
        reqs = _prefix(k)
        single = simulate_cluster_scan(_fresh(reqs), **fleet)
        stream, order = stream_from_requests(reqs, chunk=1024)
        pr = simulate_cluster_stream(stream, chunk=1024, **fleet)
        for key, want in (("failures", single.failures),
                          ("cold_starts", single.cold_starts),
                          ("timed_out", single.timed_out),
                          ("shed", single.shed),
                          ("retries_issued", single.retries_issued)):
            _check(pr.counters[key] == want,
                   f"C prefix={k}: {key} streamed={pr.counters[key]} "
                   f"single={want}")
        want = np.array([r.start for r in single.requests])[order]
        err = float(np.max(np.abs(pr.start - want)
                           / np.maximum(np.abs(want), 1.0), initial=0.0))
        _check(err <= CLUSTER_XCHECK_RTOL,
               f"C prefix={k}: streamed-vs-single drift {err!r}")
        worst = max(worst, err)
    _log("C", f"streamed vs single-shot prefixes={list(prefixes)} "
              f"worst_rel_err={worst!r}")

    reqs = _prefix(ref_prefix)
    single = simulate_cluster_scan(_fresh(reqs), **fleet)
    ref = simulate_cluster(_fresh(reqs), backend="reference", **ref_kw)
    _check(single.failures == ref.failures,
           f"C reference prefix: failures scan={single.failures} "
           f"reference={ref.failures}")
    a = np.array([r.finish for r in single.requests])
    b = np.array([r.finish for r in ref.requests])
    err = float(np.max(np.abs(a - b) / np.maximum(np.abs(b), 1.0)))
    _check(err <= CLUSTER_XCHECK_RTOL, f"C reference prefix: drift {err!r}")
    _log("C", f"reference xcheck prefix={ref_prefix} failures={ref.failures} "
              f"nodes_used scan={single.nodes_used} ref={ref.nodes_used} "
              f"worst_rel_err={err!r}")


def main() -> int:
    if not (ROOT / "src" / "repro").is_dir():
        print("chip_smoke: run from a checkout of the repository "
              "(src/repro not found)", file=sys.stderr)
        return 2
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform!r}",
              file=sys.stderr)
        return 3
    from repro.core.fastpath import ensure_compile_cache

    cache = ensure_compile_cache()
    print(f"platform={dev.platform} device_kind={dev.device_kind} "
          f"count={len(devices)} jax={jax.__version__} "
          f"compile_cache={cache}", flush=True)
    t0 = time.perf_counter()
    for phase in (phase_a, phase_b, phase_c):
        t = time.perf_counter()
        phase()
        print(f"{phase.__name__} done in {time.perf_counter() - t!r}s",
              flush=True)
    print(f"all phases done in {time.perf_counter() - t0!r}s", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
