"""Cluster-scale scan backend: multi-node kernel parity, bucketed compile
cache, and sweep-engine batch dispatch.

Contracts under test:

* the scan kernel reproduces the reference :class:`Cluster` (pull model:
  any policy; push model: least-loaded/home for everything but FC) within
  ``CLUSTER_XCHECK_RTOL`` in the always-warm regime -- typical cells are at
  float32 rounding;
* the compilation cache is keyed by padded bucket shape: re-running a sweep
  reuses compiled runners (hits grow, misses do not);
* ``run_sweep`` dispatches scan-backend cells as bucketed batches and its
  results match the per-cell reference engines;
* eligibility rules reject what the kernel cannot model (push-FC, partial
  warm-up, autoscaling/failures), and ``simulate_cluster(backend=...)``
  raises/falls back accordingly.
"""

import pytest

from repro.core import (
    ClusterConfig,
    SweepCell,
    SweepSpec,
    cluster_scan_eligible,
    generate_burst,
    home_invoker_index,
    least_loaded_index,
    most_free_index,
    run_cell,
    run_cells_scan,
    run_sweep,
    scan_cache_clear,
    scan_cache_stats,
    simulate_cluster,
    summarize,
)
from repro.core.fastpath import (
    CLUSTER_CONTAINER_MB,
    CLUSTER_MEMORY_MB,
    simulate_cluster_cells_scan,
)
from repro.core.sweep import CLUSTER_XCHECK_RTOL

try:
    import jax  # noqa: F401
    HAVE_JAX = True
except ImportError:
    HAVE_JAX = False

needs_jax = pytest.mark.skipif(not HAVE_JAX, reason="jax not installed")

POLICIES = ("fifo", "sept", "eect", "rect", "fc")
SMALL = dict(nodes=2, cores=6, intensity=15)


def _burst(nodes=2, cores=6, intensity=15, seed=0):
    return generate_burst(cores=nodes * cores, intensity=intensity, seed=seed)


def _metrics(res):
    s = summarize(res.requests)
    return {"R_avg": s.response_avg, "R_p50": s.response_pct[50],
            "R_p95": s.response_pct[95], "S_avg": s.stretch_avg,
            "max_c": s.max_completion, "n": s.n}


def _worst_rel(a, b):
    return max(abs(a[k] - b[k]) / max(abs(a[k]), abs(b[k]), 1e-9) for k in a)


class TestEligibility:
    def test_pull_any_policy(self):
        reqs = _burst()
        for policy in POLICIES:
            assert cluster_scan_eligible(reqs, 2, 6, policy)

    def test_push_accepts_all_policies(self):
        """Push-FC is modelled with per-(node, fn) count rings, so the full
        5-policy x {pull, push-LL, push-home} matrix is scan-eligible."""
        reqs = _burst()
        for policy in POLICIES:
            for lb in ("least_loaded", "home"):
                assert cluster_scan_eligible(reqs, 2, 6, policy,
                                             assignment="push", lb=lb)
        assert not cluster_scan_eligible(reqs, 2, 6, "sept",
                                         assignment="push", lb="round_robin")

    def test_partial_warmup_ineligible(self):
        """18-core nodes overflow the 40 GB warm-up for the full SeBS set
        (the paper's fig6 sizing) -- outside the always-warm regime."""
        reqs = _burst(cores=18)
        assert not cluster_scan_eligible(reqs, 2, 18, "fc")

    def test_cold_regime_eligibility(self):
        """warm=False is in-matrix in the ample-memory prewarm regime and
        on memory-bound nodes (the container table: creates and LRU
        evictions, exact counters); a node smaller than one container, or
        a pull node whose head could block, stays reference-only."""
        assert cluster_scan_eligible(_burst(), 2, 6, "fc", warm=False)
        # 6 GB with 256 MB stem cells: below the ample bound, yet six
        # 1 GB containers fit
        tight = dict(warm=False, memory_mb=6144, container_mb=256)
        assert cluster_scan_eligible(_burst(), 2, 6, "fc", **tight)
        ref = simulate_cluster(_burst(), 2, 6, "fc", backend="reference",
                               **tight)
        got = simulate_cluster(_burst(), 2, 6, "fc", backend="scan",
                               **tight)
        assert got.evictions == ref.evictions > 0
        assert got.creations == ref.creations > 0
        assert got.cold_starts == ref.cold_starts
        assert _worst_rel(_metrics(got), _metrics(ref)) <= 1e-12
        assert not cluster_scan_eligible(_burst(), 2, 6, "fc", warm=False,
                                         memory_mb=512)
        assert not cluster_scan_eligible(_burst(), 2, 6, "fc", warm=False,
                                         memory_mb=4096)

    def test_defaults_mirror_cluster_config(self):
        """fastpath's eligibility constants must track ClusterConfig, or the
        scan path would judge warm-up against the wrong node size."""
        cfg = ClusterConfig()
        assert CLUSTER_MEMORY_MB == cfg.memory_mb
        assert CLUSTER_CONTAINER_MB == cfg.container_mb


class TestRoutingFunctions:
    """The pure controller-routing functions the scan kernel mirrors."""

    def test_least_loaded_first_on_ties(self):
        assert least_loaded_index([2, 1, 1]) == 1
        assert least_loaded_index([0, 0]) == 0

    def test_most_free_first_on_ties(self):
        assert most_free_index([0, 3, 3]) == 1
        assert most_free_index([1]) == 0

    def test_home_walks_to_free_else_stays(self):
        fn = "graph-bfs"
        from repro.core import stable_hash
        home = stable_hash(fn) % 3
        assert home_invoker_index(fn, [1, 1, 1]) == home
        blocked = [1, 1, 1]
        blocked[home] = 0
        assert home_invoker_index(fn, blocked) == (home + 1) % 3
        assert home_invoker_index(fn, [0, 0, 0]) == home


@needs_jax
class TestClusterScanParity:
    @pytest.mark.parametrize("policy", POLICIES)
    def test_pull_matches_reference(self, policy):
        ref = simulate_cluster(_burst(), nodes=2, cores_per_node=6,
                               policy=policy)
        scan = simulate_cluster_cells_scan([(_burst(), 2, 6, policy)])[0]
        assert _worst_rel(_metrics(ref), _metrics(scan)) < CLUSTER_XCHECK_RTOL

    @pytest.mark.parametrize("lb", ("least_loaded", "home"))
    def test_push_matches_reference(self, lb):
        for policy in ("fifo", "sept", "rect"):
            ref = simulate_cluster(_burst(seed=1), nodes=2, cores_per_node=6,
                                   policy=policy, assignment="push", lb=lb)
            scan = simulate_cluster_cells_scan(
                [(_burst(seed=1), 2, 6, policy, "push", lb)])[0]
            assert _worst_rel(_metrics(ref), _metrics(scan)) \
                < CLUSTER_XCHECK_RTOL

    def test_pull_eect_equals_sept(self):
        """Documented pull-model identity: EECT ranks by now + E[p] with a
        shared `now`, so it orders exactly like SEPT -- in the reference and
        therefore in the scan coefficients too."""
        a = simulate_cluster(_burst(), nodes=2, cores_per_node=6,
                             policy="sept")
        b = simulate_cluster(_burst(), nodes=2, cores_per_node=6,
                             policy="eect")
        assert _metrics(a) == _metrics(b)

    def test_batch_preserves_cell_order(self):
        batch = [(_burst(seed=s), 2, 6, p)
                 for s in (0, 1) for p in ("fifo", "sept")]
        results = simulate_cluster_cells_scan(batch)
        assert len(results) == 4
        for (reqs, nodes, cores, policy), res in zip(batch, results):
            assert res.meta["policy"] == policy
            assert res.meta["nodes"] == nodes
            assert res.requests is reqs

    def test_deterministic(self):
        a = simulate_cluster_cells_scan([(_burst(), 3, 6, "fc")])[0]
        b = simulate_cluster_cells_scan([(_burst(), 3, 6, "fc")])[0]
        assert _metrics(a) == _metrics(b)

    def test_requests_spread_across_nodes(self):
        res = simulate_cluster_cells_scan([(_burst(nodes=3), 3, 6, "fc")])[0]
        assert {r.node for r in res.requests} == {"node0", "node1", "node2"}

    def test_ineligible_batch_raises(self):
        with pytest.raises(ValueError, match="ours regime"):
            simulate_cluster_cells_scan(
                [(_burst(), 2, 6, "fc", "push", "round_robin")])


@needs_jax
class TestSimulateClusterBackend:
    def test_scan_backend_matches_reference(self):
        ref = simulate_cluster(_burst(), nodes=2, cores_per_node=6,
                               policy="fc", backend="reference")
        scan = simulate_cluster(_burst(), nodes=2, cores_per_node=6,
                                policy="fc", backend="scan")
        assert scan.meta["backend"] == "scan"
        assert _worst_rel(_metrics(ref), _metrics(scan)) < CLUSTER_XCHECK_RTOL

    def test_scan_strict_raises_outside_regime(self):
        with pytest.raises(ValueError, match="ours regime"):
            simulate_cluster(_burst(cores=18), nodes=2, cores_per_node=18,
                             policy="fc", backend="scan")

    def test_auto_falls_back(self):
        res = simulate_cluster(_burst(cores=18), nodes=2, cores_per_node=18,
                               policy="fc", backend="auto")
        assert len(res.requests) == len(_burst(cores=18))

    def test_extra_kwargs_force_reference(self):
        res = simulate_cluster(_burst(), nodes=2, cores_per_node=6,
                               policy="fc", backend="auto",
                               backup_requests=True)
        assert res.meta.get("backend") != "scan"

    def test_unknown_backend_raises(self):
        with pytest.raises(ValueError, match="unknown cluster backend"):
            simulate_cluster(_burst(), nodes=2, backend="warp")


@needs_jax
class TestSweepBatching:
    def _spec(self, **kw):
        base = dict(policies=("fifo", "fc"), nodes=(1, 2), cores=(6,),
                    intensities=(15,), seeds=2, backends=("scan",))
        base.update(kw)
        return SweepSpec(**base)

    def test_run_sweep_batches_scan_cells(self):
        res = run_sweep(self._spec(), workers=1)
        assert res.meta["scan_batched"] == len(res)
        ref = run_sweep(self._spec(backends=("reference",)), workers=1)
        for a, b in zip(res.results, ref.results):
            assert abs(a.metrics["R_avg"] - b.metrics["R_avg"]) \
                <= CLUSTER_XCHECK_RTOL * b.metrics["R_avg"]

    def test_batched_sweep_deterministic(self):
        a = run_sweep(self._spec(), workers=1)
        b = run_sweep(self._spec(), workers=1)
        assert [c.metrics for c in a.results] == \
            [c.metrics for c in b.results]

    def test_mixed_grid_falls_back_per_cell(self):
        """Baseline cells are never scan-batchable; they run through
        run_cell and land in the right output slots."""
        spec = self._spec(policies=("baseline", "fc"), nodes=(2,))
        res = run_sweep(spec, workers=1)
        assert res.meta["scan_batched"] == 2          # the fc seed-group
        by_policy = {r["policy"]: r for r in res.aggregate()}
        ref = run_cell(SweepCell(policy="baseline", mode="baseline",
                                 nodes=2, cores=6, intensity=15, seed=0))
        assert by_policy["baseline"]["seeds"] == 2
        assert by_policy["baseline"]["R_avg"] > 0
        assert ref["R_avg"] > 0

    def test_run_cells_scan_strict_false_degrades(self):
        """Partial-warm-up cells degrade to run_cell and are *counted*:
        the degraded column marks them, eligible cells carry none."""
        cells = [SweepCell(policy="fc", nodes=2, cores=18, intensity=15,
                           seed=0),
                 SweepCell(policy="fc", nodes=2, cores=6, intensity=15,
                           seed=0)]
        ms = run_cells_scan(cells, strict=False)
        assert ms[0].pop("degraded") == 1.0
        assert ms[0] == run_cell(cells[0])
        assert ms[1]["n"] > 0
        assert "degraded" not in ms[1]


@needs_jax
class TestCompileCache:
    def test_bucket_reuse_across_sweeps(self):
        """The acceptance contract: a second run_sweep over the same grid
        shapes compiles nothing new -- every bucket dispatch is a cache hit."""
        scan_cache_clear()
        spec = SweepSpec(policies=("fifo", "sept"), nodes=(2,), cores=(6,),
                         intensities=(15,), seeds=2, backends=("scan",))
        run_sweep(spec, workers=1)
        first = scan_cache_stats()
        assert first["misses"] >= 1
        run_sweep(spec, workers=1)
        second = scan_cache_stats()
        assert second["misses"] == first["misses"]    # no recompile
        assert second["hits"] > first["hits"]
        assert second["size"] == first["misses"]

    def test_bucket_shapes_are_padded_pow2(self):
        from repro.core.fastpath import (
            _ScanCell,
            _arrival_features,
            _mask_features,
        )
        reqs = _burst()
        cell = _ScanCell(requests=reqs, feats=_arrival_features(reqs),
                         cores=6, nodes=3, policy="fc", assignment="pull")
        (mask, n_b, nodes_b, slots_b, f_b, kq, window, fc_ring, n_ep,
         n_copies, pool_w, xtra) = cell.bucket()
        flags = _mask_features(mask)
        assert not flags["freeze"] and flags["use_fc"]
        assert not flags["fc_push"] and not flags["cold"]
        assert not any(flags[k] for k in ("dyn", "het", "hedge", "dup"))
        assert xtra == 0 and n_copies == 1 and pool_w == 0
        for v in (n_b, nodes_b, slots_b, f_b, kq):
            assert v & (v - 1) == 0                   # powers of two
        assert n_b >= len(reqs) and nodes_b >= 3 and slots_b >= 6

    def test_clear_resets(self):
        scan_cache_clear()
        assert scan_cache_stats() == {"hits": 0, "misses": 0, "size": 0,
                                      "entries": {}}


@needs_jax
class TestClusterCrossCheck:
    def test_validate_samples_scan_cluster_cells(self):
        spec = SweepSpec(policies=("fc",), nodes=(2,), cores=(6,),
                         intensities=(15,), seeds=2, backends=("scan",),
                         validate="cross-check")
        cells = spec.cells()
        assert all(c.cross_check for c in cells)
        res = run_sweep(spec, workers=1)
        errs = [cr.metrics["xcheck_err"] for cr in res.results]
        assert len(errs) == 2
        assert max(errs) <= CLUSTER_XCHECK_RTOL

    def test_single_node_scan_only_axis_still_rejected(self):
        """Without cluster cells a scan-only axis has nothing to validate
        against (single-node scan parity lives in test_fastpath)."""
        with pytest.raises(ValueError, match="vectorized backend"):
            SweepSpec(backends=("scan",), validate="cross-check").cells()
