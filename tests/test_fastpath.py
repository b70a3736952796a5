"""Backend registry + vectorized/scan fast-path parity tests.

The contract under test: the ``vectorized`` backend is *bit-exact* against
the reference event loop (same priorities, container decisions, LRU
eviction order and event tie-breaking), across all five policies, cold and
tight-memory regimes; the ``scan`` backend agrees within float32 rounding;
and the sweep engine's cross-check mode enforces the 1% budget."""

import time

import pytest

from repro.core import (
    BackendMismatchError,
    SweepCell,
    SweepSpec,
    available_backends,
    generate_burst,
    generate_fairness_burst,
    generate_trace_burst,
    get_backend,
    run_cell,
    run_cells_scan,
    run_sweep,
    scan_eligible,
    simulate_single_node,
)
from repro.core.sweep import CROSS_CHECK_RTOL, _cross_check, make_workload

try:
    import jax  # noqa: F401
    HAVE_JAX = True
except ImportError:
    HAVE_JAX = False

POLICIES = ("fifo", "sept", "eect", "rect", "fc")


def _request_state(reqs):
    """Everything the simulation writes onto a request, id-independent."""
    return sorted((r.fn, r.r, r.r_prime, r.start, r.finish, r.c,
                   r.priority, r.cold_start) for r in reqs)


def _run_pair(policy, cores, intensity, seed=0, gen=generate_burst, **kw):
    a = gen(cores=cores, intensity=intensity, seed=seed)
    b = gen(cores=cores, intensity=intensity, seed=seed)
    ra = simulate_single_node(a, cores=cores, policy=policy,
                              backend="reference", **kw)
    rb = simulate_single_node(b, cores=cores, policy=policy,
                              backend="vectorized", **kw)
    return a, b, ra, rb


class TestBackendRegistry:
    def test_available_backends(self):
        assert {"reference", "vectorized"} <= set(available_backends())

    def test_unknown_backend_raises(self):
        with pytest.raises(ValueError, match="unknown simulation backend"):
            get_backend("nope")

    def test_vectorized_rejects_baseline_mode(self):
        reqs = generate_burst(cores=5, intensity=10, seed=0)
        with pytest.raises(ValueError, match="does not support"):
            simulate_single_node(reqs, cores=5, mode="baseline",
                                 backend="vectorized")

    def test_meta_records_backend(self):
        reqs = generate_burst(cores=5, intensity=10, seed=0)
        res = simulate_single_node(reqs, cores=5, backend="vectorized")
        assert res.meta["backend"] == "vectorized"


class TestVectorizedExactness:
    """The acceptance grid: policy x intensity x cores, metric agreement
    asserted cell by cell -- and in fact bit-exact."""

    @pytest.mark.parametrize("policy", POLICIES)
    @pytest.mark.parametrize("cores,intensity", [(5, 20), (10, 60)])
    def test_warm_grid_bit_exact(self, policy, cores, intensity):
        a, b, ra, rb = _run_pair(policy, cores, intensity)
        assert _request_state(a) == _request_state(b)
        assert (ra.cold_starts, ra.evictions, ra.creations) == \
            (rb.cold_starts, rb.evictions, rb.creations)

    @pytest.mark.parametrize("policy", ("sept", "fc"))
    def test_cold_start_regime_bit_exact(self, policy):
        """cores=20 overflows the 32 GB warm-up: prewarm/create/evict paths."""
        a, b, ra, rb = _run_pair(policy, 20, 40)
        assert _request_state(a) == _request_state(b)
        assert ra.cold_starts == rb.cold_starts > 0
        assert ra.evictions == rb.evictions

    def test_warm_false_bit_exact(self):
        a, b, ra, rb = _run_pair("sept", 10, 30, warm=False)
        assert _request_state(a) == _request_state(b)
        assert ra.cold_starts == rb.cold_starts > 0

    def test_tight_memory_bit_exact(self):
        a, b, ra, rb = _run_pair("fc", 10, 30, memory_mb=4 * 1024)
        assert _request_state(a) == _request_state(b)
        assert ra.evictions == rb.evictions > 0

    @pytest.mark.parametrize("kind", ["poisson", "mmpp"])
    def test_stochastic_arrivals_bit_exact(self, kind):
        gen = lambda cores, intensity, seed: generate_trace_burst(  # noqa: E731
            cores=cores, intensity=intensity, seed=seed, kind=kind)
        a, b, *_ = _run_pair("rect", 10, 30, gen=gen)
        assert _request_state(a) == _request_state(b)

    def test_fairness_burst_bit_exact(self):
        gen = lambda cores, intensity, seed: generate_fairness_burst(  # noqa: E731
            cores=cores, intensity=intensity, seed=seed)
        a, b, *_ = _run_pair("fc", 10, 90, gen=gen)
        assert _request_state(a) == _request_state(b)

    def test_sweep_cell_metrics_identical(self):
        cell = dict(policy="fc", cores=5, intensity=20, seed=3)
        ref = run_cell(SweepCell(**cell))
        vec = run_cell(SweepCell(**cell, backend="vectorized"))
        assert ref == vec

    def test_vectorized_deterministic(self):
        cell = SweepCell(policy="sept", cores=5, intensity=20, seed=1,
                         backend="vectorized")
        assert run_cell(cell) == run_cell(cell)


class TestSweepBackendSelection:
    def test_backend_axis_expands(self):
        spec = SweepSpec(policies=("fifo",), intensities=(20,), cores=(5,),
                         seeds=1, backends=("reference", "vectorized"))
        cells = spec.cells()
        assert [c.backend for c in cells] == ["reference", "vectorized"]
        assert cells[1].label().endswith("vectorized")

    def test_unknown_backend_axis_raises(self):
        with pytest.raises(ValueError, match="unknown backend"):
            SweepSpec(backends=("warp",)).cells()

    def test_baseline_cells_fall_back_to_reference(self):
        """An explicit fast selector still sweeps stock-system cells."""
        ref = run_cell(SweepCell(policy="baseline", cores=5, intensity=20))
        vec = run_cell(SweepCell(policy="baseline", cores=5, intensity=20,
                                 backend="vectorized"))
        assert ref == vec

    def test_cluster_cells_fall_back_to_reference(self):
        ref = run_cell(SweepCell(policy="fc", nodes=2, cores=5, intensity=20))
        vec = run_cell(SweepCell(policy="fc", nodes=2, cores=5, intensity=20,
                                 backend="vectorized"))
        assert ref == vec


class TestCrossCheck:
    def test_validate_marks_eligible_cells(self):
        spec = SweepSpec(policies=("fifo", "baseline"), intensities=(20,),
                         cores=(5,), seeds=2, validate="cross-check")
        cells = spec.cells()
        by_policy = {}
        for c in cells:
            assert c.backend == "reference"   # identity untouched
            by_policy.setdefault(c.policy, []).append(c.cross_check)
        assert by_policy["fifo"] == [True, True]
        assert by_policy["baseline"] == [False, False]   # ineligible

    def test_validate_stride_samples_whole_groups(self):
        """Stride samples cell *identities*: a seed group is either fully
        cross-checked or not at all, so aggregation rows never split."""
        spec = SweepSpec(policies=("fifo", "sept"), intensities=(20,),
                         cores=(5,), seeds=2, validate="cross-check",
                         validate_stride=2)
        by_policy = {}
        for c in spec.cells():
            by_policy.setdefault(c.policy, []).append(c.cross_check)
        assert by_policy["fifo"] == [True, True]
        assert by_policy["sept"] == [False, False]

    def test_cross_check_axis_sugar(self):
        """backends=("cross-check",) -- the --backend flag form -- expands
        to a reference axis with validation on."""
        spec = SweepSpec(policies=("fifo",), intensities=(20,), cores=(5,),
                         seeds=2, backends=("cross-check",))
        cells = spec.cells()
        assert [c.backend for c in cells] == ["reference", "reference"]
        assert all(c.cross_check for c in cells)

    def test_validate_on_fast_backend_axis_keeps_groups_whole(self):
        """Regression: cross-checking sampled cells of a vectorized axis
        must not split a seed group into two aggregated rows."""
        spec = SweepSpec(policies=("fifo",), intensities=(20,), cores=(5,),
                         seeds=4, backends=("vectorized",),
                         validate="cross-check", validate_stride=2)
        res = run_sweep(spec, workers=1)
        agg = res.aggregate()
        assert len(agg) == 1 and agg[0]["seeds"] == 4
        assert res.find(policy="fifo")["R_avg"] > 0

    def test_validate_two_fast_backends_no_merge(self):
        """Regression: cross_check is a flag, not a backend identity, so
        validating one axis can neither merge nor split any series."""
        if not HAVE_JAX:
            pytest.skip("scan axis needs jax")
        spec = SweepSpec(policies=("fifo",), intensities=(20,), cores=(5,),
                         seeds=2, backends=("vectorized", "scan"),
                         validate="cross-check")
        cells = spec.cells()
        assert sorted((c.backend, c.cross_check) for c in cells) == \
            [("scan", False), ("scan", False),
             ("vectorized", True), ("vectorized", True)]
        res = run_sweep(spec, workers=1)
        agg = res.aggregate()
        assert sorted((r["backend"], r["seeds"]) for r in agg) == \
            [("scan", 2), ("vectorized", 2)]

    def test_csv_keeps_ragged_metric_columns(self, tmp_path):
        """xcheck_err must survive to_csv even when the first aggregated
        group (here: ineligible baseline) does not carry it."""
        import csv as _csv
        spec = SweepSpec(policies=("baseline", "fifo"), intensities=(20,),
                         cores=(5,), seeds=1, validate="cross-check")
        res = run_sweep(spec, workers=1)
        res.to_csv(tmp_path / "s.csv")
        with open(tmp_path / "s.csv") as fh:
            rows = list(_csv.DictReader(fh))
        assert "xcheck_err" in rows[0]
        by_policy = {r["policy"]: r for r in rows}
        assert by_policy["baseline"]["xcheck_err"] == ""
        assert float(by_policy["fifo"]["xcheck_err"]) == 0.0

    def test_validate_with_reference_twin_no_merge(self):
        """With both a reference and a fast axis, only reference groups are
        sampled, so normalised cross-check cells cannot merge into the
        reference twin row."""
        spec = SweepSpec(policies=("fifo",), intensities=(20,), cores=(5,),
                         seeds=2, backends=("reference", "vectorized"),
                         validate="cross-check")
        res = run_sweep(spec, workers=1)
        agg = res.aggregate()
        assert sorted(r["backend"] for r in agg) == \
            ["reference", "vectorized"]
        assert all(r["seeds"] == 2 for r in agg)

    def test_bad_validate_mode_raises(self):
        with pytest.raises(ValueError, match="validate"):
            SweepSpec(validate="paranoid").cells()

    def test_validate_requires_vectorized_compatible_axis(self):
        """A scan-only axis must not be silently replaced by
        reference+vectorized dual-runs that never exercise scan."""
        with pytest.raises(ValueError, match="vectorized backend"):
            SweepSpec(backends=("scan",), validate="cross-check").cells()

    def test_stride_on_fast_axis_keeps_one_label_family(self):
        """Regression: sampling every other *identity* of a vectorized axis
        must not alternate the series between reference- and
        vectorized-labelled rows."""
        spec = SweepSpec(policies=("fifo",), intensities=(20, 40), cores=(5,),
                         seeds=1, backends=("vectorized",),
                         validate="cross-check", validate_stride=2)
        res = run_sweep(spec, workers=1)
        agg = res.aggregate()
        assert [r["backend"] for r in agg] == ["vectorized", "vectorized"]
        assert all(r["label"].endswith("vectorized") for r in agg)
        assert "xcheck_err" in agg[0] and "xcheck_err" not in agg[1]

    def test_cross_check_label_matches_reference_group(self):
        """Sampled and unsampled cells of one identity share the emit/CSV
        series name (the cross-check is visible via xcheck_err, not the
        label)."""
        spec = SweepSpec(policies=("fifo",), intensities=(20,), cores=(5,),
                         seeds=2, validate="cross-check")
        labels = {c.label() for c in spec.cells()}
        assert labels == {SweepCell(policy="fifo", intensity=20,
                                    cores=5).label()}

    def test_cross_check_grid_green(self):
        """The PR acceptance check: a sampled policy x intensity x cores
        grid agrees within 1% per cell (here: exactly)."""
        spec = SweepSpec(policies=POLICIES, intensities=(20, 40), cores=(5,),
                         seeds=1, validate="cross-check")
        res = run_sweep(spec, workers=1)
        errs = [cr.metrics["xcheck_err"] for cr in res.results]
        assert len(errs) == 10
        assert max(errs) == 0.0   # the vectorized backend is exact

    def test_cross_check_raises_on_disagreement(self):
        cell = SweepCell(policy="fifo", cores=5, intensity=20)
        good = {"R_avg": 10.0, "S_avg": 5.0}
        bad = {"R_avg": 10.0 * (1 + 2 * CROSS_CHECK_RTOL), "S_avg": 5.0}
        assert _cross_check(cell, good, dict(good), "vectorized") == 0.0
        with pytest.raises(BackendMismatchError, match="disagrees"):
            _cross_check(cell, good, bad, "vectorized")


@pytest.mark.skipif(not HAVE_JAX, reason="jax not installed")
class TestScanBackend:
    def test_scan_eligibility(self):
        reqs = generate_burst(cores=10, intensity=20, seed=0)
        assert scan_eligible(reqs, cores=10, policy="sept")
        assert not scan_eligible(reqs, cores=20, policy="sept")  # partial warm
        # the cold regime is in-matrix when memory is ample...
        assert scan_eligible(reqs, cores=10, policy="sept", warm=False)
        # ...but a tight pool (evict-for-memory reachable) stays reference
        assert not scan_eligible(reqs, cores=10, policy="sept", warm=False,
                                 memory_mb=512)
        assert not scan_eligible(reqs, cores=10, policy="sept",
                                 mode="baseline")

    @pytest.mark.parametrize("policy", POLICIES)
    def test_scan_matches_reference_within_budget(self, policy):
        cell = dict(policy=policy, cores=5, intensity=20, seed=0)
        ref = run_cell(SweepCell(**cell))
        scan = run_cell(SweepCell(**cell, backend="scan"))
        for k in ("R_avg", "R_p50", "R_p95", "S_avg", "max_c", "n"):
            assert scan[k] == pytest.approx(ref[k], rel=1e-3)

    def test_scan_batch_runs_whole_grid(self):
        """An intensity x policy grid as ONE lax.scan over padded tensors."""
        spec = SweepSpec(policies=("fifo", "sept", "fc"),
                         intensities=(10, 20), cores=(5,), seeds=1)
        cells = spec.cells()
        batched = run_cells_scan(cells)
        assert len(batched) == 6
        for cell, m in zip(cells, batched):
            ref = run_cell(cell)
            assert m["n"] == ref["n"]
            assert m["R_avg"] == pytest.approx(ref["R_avg"], rel=1e-3)

    def test_scan_falls_back_when_ineligible(self):
        """cores=20 is outside the always-warm regime: the sweep engine
        degrades scan -> vectorized (which is exact) and *marks* the cell."""
        ref = run_cell(SweepCell(policy="sept", cores=20, intensity=20))
        scn = run_cell(SweepCell(policy="sept", cores=20, intensity=20,
                                 backend="scan"))
        assert scn.pop("degraded") == 1.0
        assert ref == scn

    def test_run_cells_scan_rejects_ineligible(self):
        """Autoscaling and cold-pool cells run on the scan kernel since the
        capability-matrix close; duplicate racing under push churn is a
        documented rejection and strict mode refuses it."""
        auto = run_cells_scan([SweepCell(policy="fc", nodes=2, cores=5,
                                         intensity=10, autoscale=True)])
        assert auto[0]["n"] > 0 and "degraded" not in auto[0]
        cold = run_cells_scan([SweepCell(policy="fc", nodes=2, cores=5,
                                         intensity=10, warm=False)])
        assert cold[0]["n"] > 0 and "degraded" not in cold[0]
        bad = SweepCell(policy="fc", nodes=2, cores=5, intensity=10,
                        autoscale=True, assignment="push",
                        hedge_multiple=2.0, hedge_mode="duplicate")
        with pytest.raises(ValueError, match="not scan-eligible"):
            run_cells_scan([bad])
        # ...and strict=False degrades such cells to run_cell instead
        got = run_cells_scan([bad], strict=False)[0]
        assert got.pop("degraded") == 1.0
        assert got == run_cell(bad)

    def test_run_cells_scan_runs_cold_cells(self):
        """warm=False is in-matrix now: the ample-memory prewarm regime
        runs on the scan kernel with exact cold-start accounting."""
        cell = SweepCell(policy="sept", cores=5, intensity=20, warm=False)
        got = run_cells_scan([cell])[0]
        ref = run_cell(cell)
        assert "degraded" not in got
        assert got["cold"] == ref["cold"] > 0
        for k in ("R_avg", "R_p95", "S_avg", "n"):
            assert got[k] == pytest.approx(ref[k], rel=1e-2)


@pytest.mark.slow
class TestFastpathSpeed:
    def test_vectorized_speedup_on_high_intensity_grid(self):
        """The engine_bench acceptance claim, with slack for noisy CI boxes:
        the exact fast path is many times quicker than the event loop."""
        cells = SweepSpec(policies=POLICIES, intensities=(120,), cores=(10,),
                          seeds=1).cells()
        wall = {}
        for backend in ("reference", "vectorized"):
            total = 0.0
            for cell in cells:
                reqs = make_workload(cell)
                t0 = time.perf_counter()
                simulate_single_node(reqs, cores=cell.cores,
                                     policy=cell.policy, backend=backend)
                total += time.perf_counter() - t0
            wall[backend] = total
        assert wall["reference"] / wall["vectorized"] > 4.0


@pytest.mark.skipif(not HAVE_JAX, reason="jax not installed")
class TestCompileCache:
    """Placement of JAX's persistent compilation cache: an outside
    ``JAX_COMPILATION_CACHE_DIR`` wins untouched; otherwise one fixed,
    git-ignored directory inside the checkout, the same on every call."""

    def test_env_dir_is_left_in_place(self, tmp_path):
        import os
        import subprocess
        import sys

        prog = (
            "import jax\n"
            "from repro.core import generate_burst\n"
            "from repro.core.fastpath import simulate_cluster_scan\n"
            "simulate_cluster_scan(generate_burst(cores=8, intensity=5,"
            " seed=0), nodes=2, cores_per_node=4, policy='fifo')\n"
            "print(jax.config.jax_compilation_cache_dir)\n")
        env = {**os.environ, "JAX_PLATFORMS": "cpu",
               "JAX_COMPILATION_CACHE_DIR": str(tmp_path)}
        out = subprocess.run([sys.executable, "-c", prog], env=env,
                             capture_output=True, text=True, timeout=300,
                             check=True)
        assert out.stdout.strip().splitlines()[-1] == str(tmp_path)

    def test_fixed_in_checkout_dir_when_unset(self, monkeypatch):
        from pathlib import Path

        import jax
        from jax.experimental.compilation_cache import compilation_cache

        from repro.core import fastpath as fp

        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        before = jax.config.jax_compilation_cache_dir
        try:
            jax.config.update("jax_compilation_cache_dir", None)
            first = fp.ensure_compile_cache()
            second = fp.ensure_compile_cache()
        finally:
            jax.config.update("jax_compilation_cache_dir", before)
            compilation_cache.reset_cache()
        root = Path(__file__).resolve().parent.parent
        assert first == second == str(root / ".jax_cache")
        ignored = (root / ".gitignore").read_text().splitlines()
        assert ".jax_cache/" in ignored
