"""Memory-bound ``warm=False`` cells on the scan's container table.

Contracts under test:

* on nodes whose memory cannot hold every function's containers, the scan
  replays ``ContainerPool`` on a per-node container table: clocks agree
  with the program's :class:`Cluster` (and, for pull, with the benchmark's
  independent reference) to float64 rounding, and ``cold_starts``,
  ``evictions`` and ``creations`` agree exactly;
* the cases below reach every acquire path -- a warm hit, a stem-cell
  (prewarm) hit, a stem-cell replenish that memory stops short, a create,
  an LRU eviction followed by a create, and (push and single-node cells)
  a blocked queue head.  The release trim cannot fire in the ours
  discipline: a function's containers are created only while all of them
  are busy, so they never outnumber the cores, and the probe asserts it;
* ample and memory-bound cells of one batch land in separate buckets;
* on ample-memory nodes, where the scan runs the per-(node, fn) count
  path, the container table forced in its place gives the same clocks and
  counters, bit for bit;
* what the table does not model is still refused: pull cells whose head
  could block, nodes too small for one container, and memory-bound
  streams.
"""

import collections
import copy
import sys
from pathlib import Path

import numpy as np
import pytest

from repro.core import (
    cluster_scan_eligible,
    first_divergence,
    generate_burst,
    scan_eligible,
    simulate_cluster,
)
from repro.core import containers as _containers
from repro.core import fastpath
from repro.core.fastpath import (
    CLUSTER_CONTAINER_MB,
    CLUSTER_MEMORY_MB,
    POLICY_NAMES,
    _pool_width,
    scan_cache_clear,
    scan_cache_stats,
    scan_phase_totals,
    scan_timings_clear,
    simulate_cells_scan,
    simulate_cluster_cells_scan,
)
from repro.core.simulator import simulate_single_node
from repro.core.workload import PROFILES, SEBS_MEMORY_MB

ROOT = Path(__file__).resolve().parents[1]

STEM_MB = 256
# 2 nodes x 2 cores on 2.5 GB: tight enough to create and evict, and
# 2 x 1024 MB fits, so no pull head can block
PULL = dict(nodes=2, cores=2, memory_mb=2560, intensity=30)
# 2 nodes x 4 cores on 2.5 GB: four busy containers can fill a node
PUSH = dict(nodes=2, cores=4, memory_mb=2560, intensity=30)
SINGLE = dict(nodes=1, cores=4, memory_mb=2560, intensity=30)


def _burst(nodes, cores, intensity, seed=0, **_):
    return generate_burst(cores=nodes * cores, intensity=intensity,
                          seed=seed)


class _PathProbe:
    """Counts which branch of ``ContainerPool.acquire`` each reference
    acquire took, and the release trims, while active."""

    def __init__(self, monkeypatch):
        self.hits = collections.Counter()
        pool = _containers.ContainerPool
        acquire, replenish = pool.acquire, pool._replenish_prewarm
        trim = pool._trim_ours
        hits = self.hits

        def _acquire(self, fn, now):
            ev0 = self.evictions
            warm = any(not c.busy and c.fn == fn for c in self.containers)
            stem = any(not c.busy and c.fn is None for c in self.containers)
            got = acquire(self, fn, now)
            hits["block" if got is None else "warm" if warm
                 else "prewarm" if stem
                 else "evict_create" if self.evictions > ev0
                 else "create"] += 1
            return got

        def _replenish(self):
            replenish(self)
            if (sum(c.fn is None for c in self.containers)
                    < self.prewarm_count):
                hits["replenish_short"] += 1

        def _trim(self, now):
            ev0 = self.evictions
            trim(self, now)
            hits["trim"] += self.evictions - ev0

        monkeypatch.setattr(pool, "acquire", _acquire)
        monkeypatch.setattr(pool, "_replenish_prewarm", _replenish)
        monkeypatch.setattr(pool, "_trim_ours", _trim)


def _clocks(res):
    return np.array([q.c for q in res.requests], dtype=np.float64)


def _gap(a, b):
    return float(np.max(np.abs(a - b) / np.maximum(np.abs(b), 1.0)))


def _counters(res):
    return (res.cold_starts, res.evictions, res.creations)


def _cluster_pair(case, policy, assignment, monkeypatch, seed=0):
    """The same burst through the reference Cluster (path-probed) and the
    scan; returns (reference, scan, probe hits)."""
    a, b = _burst(seed=seed, **case), _burst(seed=seed, **case)
    kw = dict(assignment=assignment, warm=False,
              memory_mb=case["memory_mb"], container_mb=STEM_MB)
    assert cluster_scan_eligible(b, case["nodes"], case["cores"], policy,
                                 **kw)
    probe = _PathProbe(monkeypatch)
    ref = simulate_cluster(a, case["nodes"], case["cores"], policy,
                           backend="reference", **kw)
    monkeypatch.undo()
    got = simulate_cluster(b, case["nodes"], case["cores"], policy,
                           backend="scan", **kw)
    return ref, got, probe.hits


def _bench_reference(reqs, case, policy):
    """The benchmark's own reference (imports nothing of the program) on
    the same calls."""
    if str(ROOT) not in sys.path:
        sys.path.insert(0, str(ROOT))
    from bench.reference import cluster as bref

    fns = sorted({q.fn for q in reqs})
    fid = {f: i for i, f in enumerate(fns)}
    cfg = dict(nodes=case["nodes"], cores_per_node=case["cores"],
               policy=policy, memory_mb=case["memory_mb"],
               container_mb=STEM_MB, failure_detect_s=1.0)
    return bref.simulate(
        fns, [fid[q.fn] for q in reqs], [q.r for q in reqs],
        [q.p_true for q in reqs], cfg, warm=False,
        medians={f: PROFILES[f].median_s for f in fns},
        fn_memory=dict(SEBS_MEMORY_MB))


@pytest.mark.parametrize("policy", POLICY_NAMES)
def test_pull_table_matches_both_references(policy, monkeypatch):
    ref, got, hits = _cluster_pair(PULL, policy, "pull", monkeypatch)
    for path in ("warm", "prewarm", "replenish_short", "create",
                 "evict_create"):
        assert hits[path] > 0, (path, dict(hits))
    assert hits["block"] == 0 and hits["trim"] == 0
    assert _gap(_clocks(got), _clocks(ref)) <= 1e-12
    assert _counters(got) == _counters(ref)
    assert ref.evictions > 0 and ref.creations > 0
    # the benchmark's reference: clocks and both counters it keeps
    bench = _bench_reference(got.requests, PULL, policy)
    assert _gap(_clocks(got), bench.c) <= 1e-12
    assert bench.counters["cold_starts"] == got.cold_starts
    assert bench.counters["evictions"] == got.evictions
    assert not np.isnan(bench.c).any()


@pytest.mark.parametrize("policy", POLICY_NAMES)
def test_push_table_blocks_heads_like_the_reference(policy, monkeypatch):
    ref, got, hits = _cluster_pair(PUSH, policy, "push", monkeypatch)
    for path in ("warm", "prewarm", "replenish_short", "create",
                 "evict_create", "block"):
        assert hits[path] > 0, (path, dict(hits))
    assert hits["trim"] == 0
    assert _gap(_clocks(got), _clocks(ref)) <= 1e-12
    assert _counters(got) == _counters(ref)
    assert all(q.finish is not None for q in got.requests)
    assert ([q.cold_start for q in got.requests]
            == [q.cold_start for q in ref.requests])


@pytest.mark.parametrize("policy", POLICY_NAMES)
def test_single_node_table_matches_reference_and_vectorized(policy,
                                                            monkeypatch):
    case = SINGLE
    a, b, c = (_burst(**case) for _ in range(3))
    kw = dict(memory_mb=case["memory_mb"], container_mb=STEM_MB,
              warm=False)
    assert scan_eligible(b, case["cores"], policy, **kw)
    probe = _PathProbe(monkeypatch)
    ref = simulate_single_node(a, case["cores"], policy, **kw)
    monkeypatch.undo()
    assert probe.hits["block"] > 0 and probe.hits["evict_create"] > 0
    vec = simulate_single_node(c, case["cores"], policy,
                               backend="vectorized", **kw)
    got = simulate_cells_scan([(b, case["cores"], policy, False)],
                              memory_mb=case["memory_mb"],
                              container_mb=STEM_MB)[0]
    assert _gap(_clocks(got), _clocks(ref)) <= 1e-12
    assert _counters(got) == _counters(ref) == _counters(vec)


@pytest.mark.parametrize("seed", [1, 2])
def test_push_table_other_seeds_and_tighter_nodes(seed, monkeypatch):
    case = dict(PUSH, memory_mb=2048, intensity=20)
    for policy in ("fifo", "fc"):
        ref, got, hits = _cluster_pair(case, policy, "push", monkeypatch,
                                       seed=seed)
        assert hits["block"] > 0
        assert _gap(_clocks(got), _clocks(ref)) <= 1e-12
        assert _counters(got) == _counters(ref)


def test_metrics_only_counts_creations_and_table_counters():
    reqs = _burst(**PULL)
    ref = simulate_cluster(copy.deepcopy(reqs), PULL["nodes"],
                           PULL["cores"], "sept", warm=False,
                           backend="reference",
                           memory_mb=PULL["memory_mb"],
                           container_mb=STEM_MB)
    scan_timings_clear()
    row = simulate_cluster_cells_scan(
        [(reqs, PULL["nodes"], PULL["cores"], "sept", "pull",
          "least_loaded", None, None, None, False)],
        memory_mb=PULL["memory_mb"], container_mb=STEM_MB,
        metrics_only=True)[0]
    assert (row.cold_starts, row.evictions, row.creations) == \
        _counters(ref)
    totals = scan_phase_totals()
    width = _pool_width(sorted({q.fn for q in reqs}), PULL["cores"],
                        PULL["memory_mb"], STEM_MB)
    # one real cell padded to a batch of 1, nodes padded to 2
    assert totals["pool_node_slots"] == 2
    assert totals["pool_rows"] == 2 * width
    assert 0 < totals["pool_peak"] <= totals["pool_rows"]


def test_ample_and_memory_bound_cells_take_separate_buckets():
    """One batch on 2.5 GB nodes: a one-function cell stays in the ample
    prewarm regime (count path), the full SeBS mix is memory-bound (table
    path); each lands in its own bucket and matches its reference run."""
    scan_cache_clear()
    one_fn = [q for q in _burst(**PULL) if q.fn == "graph-bfs"]
    mixed = _burst(**PULL)
    kw = dict(warm=False, memory_mb=PULL["memory_mb"], container_mb=STEM_MB)
    refs = [simulate_cluster(copy.deepcopy(r), 2, 2, "fc",
                             backend="reference", **kw)
            for r in (one_fn, mixed)]
    rows = simulate_cluster_cells_scan(
        [(r, 2, 2, "fc", "pull", "least_loaded", None, None, None, False)
         for r in (one_fn, mixed)],
        memory_mb=PULL["memory_mb"], container_mb=STEM_MB)
    widths = sorted(int(t.split(",pool=")[1].split(",")[0])
                    for t in scan_cache_stats()["entries"])
    assert len(widths) == 2 and widths[0] == 0 and widths[1] > 0
    for got, ref in zip(rows, refs):
        assert _gap(_clocks(got), _clocks(ref)) <= 1e-12
        assert _counters(got) == _counters(ref)
    assert rows[0].creations == 0 and rows[1].creations > 0


# ample 40 GB nodes: every cold cell runs the count path unless the table
# is forced; each layout's cells run as one batch a path
AMPLE_LAYOUTS = {"pull": (2, 4), "push": (2, 4), "single": (1, 4)}
AMPLE_SEEDS = (0, 1)


def _forced_table_width(f, cores, warm, memory_mb, container_mb):
    return 0 if warm else _pool_width(f.fns, cores, memory_mb, container_mb)


@pytest.fixture(scope="module", params=sorted(AMPLE_LAYOUTS))
def ample_runs(request):
    """Every policy x seed cell of one layout at intensity 30 through the
    reference, the scan's count path and the scan with the container table
    forced; returns the layout and ``{(policy, seed): (reference, count,
    table)}`` with the table rows each scan run dispatched."""
    layout = request.param
    nodes, cores = AMPLE_LAYOUTS[layout]
    keys = [(p, s) for p in POLICY_NAMES for s in AMPLE_SEEDS]
    kw = dict(memory_mb=CLUSTER_MEMORY_MB, container_mb=CLUSTER_CONTAINER_MB)

    def bursts():
        return [_burst(nodes, cores, 30, seed=s) for _, s in keys]

    if layout == "single":
        refs = [simulate_single_node(r, cores, p, warm=False, **kw)
                for r, (p, _) in zip(bursts(), keys)]

        def scan():
            return simulate_cells_scan(
                [(r, cores, p, False) for r, (p, _) in zip(bursts(), keys)],
                **kw)
    else:
        refs = [simulate_cluster(r, nodes, cores, p, assignment=layout,
                                 warm=False, backend="reference", **kw)
                for r, (p, _) in zip(bursts(), keys)]

        def scan():
            return simulate_cluster_cells_scan(
                [(r, nodes, cores, p, layout, "least_loaded", None, None,
                  None, False) for r, (p, _) in zip(bursts(), keys)], **kw)

    scan_timings_clear()
    count = scan()
    count_rows = scan_phase_totals()["pool_rows"]
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(fastpath, "_cell_pool_width", _forced_table_width)
        scan_timings_clear()
        table = scan()
        table_rows = scan_phase_totals()["pool_rows"]
    return layout, dict(zip(keys, zip(refs, count, table))), \
        (count_rows, table_rows)


@pytest.mark.parametrize("seed", AMPLE_SEEDS)
@pytest.mark.parametrize("policy", POLICY_NAMES)
def test_table_matches_count_path_on_ample_cells(ample_runs, policy, seed):
    layout, runs, (count_rows, table_rows) = ample_runs
    ref, count, table = runs[policy, seed]
    assert count_rows == 0 and table_rows > 0, layout
    assert _gap(_clocks(table), _clocks(count)) == 0.0
    assert _gap(_clocks(table), _clocks(ref)) == 0.0
    assert _counters(table) == _counters(count) == _counters(ref)


@pytest.mark.parametrize("policy", ("sept", "fc"))
def test_flight_recorder_streams_agree(policy):
    a, b = _burst(**PUSH), _burst(**PUSH)
    kw = dict(assignment="push", warm=False, memory_mb=PUSH["memory_mb"],
              container_mb=STEM_MB, trace=True)
    ref = simulate_cluster(a, PUSH["nodes"], PUSH["cores"], policy,
                           backend="reference", **kw)
    got = simulate_cluster(b, PUSH["nodes"], PUSH["cores"], policy,
                           backend="scan", **kw)
    assert got.trace.meta.get("backend") == "scan"
    assert ref.trace.counts().get("container_evict", 0) > 0
    remap = {qb.id: qa.id for qa, qb in zip(a, b)}
    assert first_divergence(ref.trace, got.trace.relabel(remap),
                            rtol=1e-12, atol=1e-9) is None


class TestRefusals:
    def test_pull_head_that_could_block_is_refused(self):
        # four busy 1 GB containers overfill a 2.5 GB pull invoker
        reqs = _burst(**PUSH)
        assert not cluster_scan_eligible(reqs, 2, 4, "fc", warm=False,
                                         memory_mb=2560,
                                         container_mb=STEM_MB)
        with pytest.raises(ValueError, match="memory-bound pool"):
            simulate_cluster_cells_scan(
                [(reqs, 2, 4, "fc", "pull", "least_loaded", None, None,
                  None, False)], memory_mb=2560, container_mb=STEM_MB)

    def test_node_smaller_than_one_container_is_refused(self):
        reqs = _burst(**PUSH)        # holds 1024 MB dna-visualisation
        assert not cluster_scan_eligible(reqs, 2, 4, "fc", warm=False,
                                         assignment="push", memory_mb=768,
                                         container_mb=STEM_MB)
        assert not scan_eligible(reqs, 4, "fc", warm=False, memory_mb=768,
                                 container_mb=STEM_MB)

    def test_table_with_dynamics_or_hedging_is_refused(self):
        from repro.core import ClusterDynamics, HedgingSpec

        reqs = _burst(**PULL)
        kw = dict(warm=False, memory_mb=2560, container_mb=STEM_MB)
        assert cluster_scan_eligible(reqs, 2, 2, "fc", **kw)
        assert not cluster_scan_eligible(
            reqs, 2, 2, "fc", dynamics=ClusterDynamics(autoscale=True),
            **kw)
        assert not cluster_scan_eligible(
            reqs, 2, 4, "fc", assignment="push",
            hedging=HedgingSpec(mode="steal"), **kw)

    def test_memory_bound_stream_is_refused(self):
        from repro.core.streamscan import (
            simulate_cluster_stream,
            stream_from_requests,
        )

        stream, _ = stream_from_requests(_burst(**PULL))
        with pytest.raises(ValueError, match="does not carry a table"):
            simulate_cluster_stream(stream, nodes=2, cores_per_node=2,
                                    policy="fc", warm=False,
                                    memory_mb=2560, container_mb=STEM_MB)
