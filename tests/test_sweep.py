"""Sweep engine + trace-driven workload tests.

Covers the engine's contract: per-cell determinism, serial == parallel,
emit/CSV/JSON compatibility, ragged grids; and the arrival processes:
empirical rate within tolerance of the configured rate, Azure-style trace
loading."""

import numpy as np
import pytest

from repro.core import (
    SweepCell,
    SweepSpec,
    diurnal_arrivals,
    generate_trace_burst,
    load_azure_trace,
    mmpp_arrivals,
    poisson_arrivals,
    requests_from_trace,
    run_cell,
    run_sweep,
    stable_hash,
)

SMALL = dict(cores=5, intensity=20)  # keeps every cell < ~100 requests


class TestCellDeterminism:
    @pytest.mark.parametrize("cell", [
        SweepCell(policy="sept", seed=1, **SMALL),
        SweepCell(policy="fc", arrival="poisson", seed=2, **SMALL),
        SweepCell(policy="baseline", seed=0, **SMALL),
        SweepCell(policy="fc", nodes=2, seed=3, **SMALL),
    ], ids=["sept", "poisson", "baseline", "cluster"])
    def test_same_cell_bit_identical(self, cell):
        """Two runs of one cell produce bit-identical metrics."""
        assert run_cell(cell) == run_cell(cell)

    def test_different_seeds_differ(self):
        a = run_cell(SweepCell(seed=0, **SMALL))
        b = run_cell(SweepCell(seed=1, **SMALL))
        assert a["R_avg"] != b["R_avg"]

    def test_paired_cells_share_workload(self):
        """Cells differing only in policy see the same burst (common random
        numbers): the request count matches exactly."""
        a = run_cell(SweepCell(policy="fifo", seed=4, **SMALL))
        b = run_cell(SweepCell(policy="sept", seed=4, **SMALL))
        assert a["n"] == b["n"]


class TestParallelRunner:
    def _spec(self):
        return SweepSpec(policies=("fifo", "sept"), intensities=(20,),
                         cores=(5,), seeds=2)

    def test_serial_equals_parallel(self):
        """workers=1 and workers=2 produce identical results, cell by cell."""
        r1 = run_sweep(self._spec(), workers=1)
        r2 = run_sweep(self._spec(), workers=2)
        assert r2.workers == 2
        assert [c.metrics for c in r1.results] == \
            [c.metrics for c in r2.results]
        assert r1.aggregate() == r2.aggregate()

    def test_aggregate_groups_seeds(self):
        res = run_sweep(self._spec(), workers=1)
        agg = res.aggregate()
        assert len(res) == 4 and len(agg) == 2
        assert all(r["seeds"] == 2 for r in agg)
        by_pol = {r["policy"]: r for r in agg}
        assert set(by_pol) == {"fifo", "sept"}

    def test_find_and_rows_contract(self):
        res = run_sweep(self._spec(), workers=1)
        row = res.find(policy="sept")
        assert row["R_avg"] > 0
        with pytest.raises(KeyError):
            res.find(policy="nope")
        emitted = res.rows(prefix="t")
        assert len(emitted) == 2
        assert all({"name", "us_per_call", "derived"} <= set(r)
                   for r in emitted)

    def test_csv_json_emission(self, tmp_path):
        res = run_sweep(self._spec(), workers=1)
        res.to_csv(tmp_path / "s.csv")
        res.to_json(tmp_path / "s.json")
        import csv as _csv
        import json as _json
        with open(tmp_path / "s.csv") as fh:
            rows = list(_csv.DictReader(fh))
        assert len(rows) == 2 and "R_avg" in rows[0]
        payload = _json.loads((tmp_path / "s.json").read_text())
        assert payload["cells"] == 4
        assert len(payload["results"]) == 4

    def test_cell_filter_prunes_grid(self):
        spec = SweepSpec(policies=("fifo", "sept"), intensities=(20,),
                         cores=(5,), seeds=1,
                         cell_filter=lambda c: c.policy != "fifo")
        cells = spec.cells()
        assert [c.policy for c in cells] == ["sept"]

    def test_failure_injection_cell(self):
        cell = SweepCell(policy="fc", nodes=2, fail_at=5.0, seed=0, **SMALL)
        m = run_cell(cell)
        assert m["failures"] > 0          # something was in flight
        assert m["n"] > 0                 # pull model recovered the rest


class TestOneProcessPerChip:
    """A parent that holds an accelerator through JAX starts no pool: a
    child initialising a JAX backend on the held chip would fail or hang.
    The test steers the parent's backend answer; nothing here needs a
    chip."""

    SPEC = SweepSpec(policies=("fifo", "sept"), intensities=(20,),
                     cores=(5,), seeds=2)

    @staticmethod
    def _spy(monkeypatch):
        import repro.core.sweep as sweep

        started = []
        real = sweep.ProcessPoolExecutor

        def spy(*a, **kw):
            started.append(kw.get("max_workers"))
            return real(*a, **kw)

        monkeypatch.setattr(sweep, "ProcessPoolExecutor", spy)
        return started

    def test_no_pool_while_parent_holds_accelerator(self, monkeypatch):
        jax = pytest.importorskip("jax")
        jax.devices()                     # the parent initialises a backend
        started = self._spy(monkeypatch)
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        res = run_sweep(self.SPEC, workers=2)
        assert started == []
        assert [c.metrics for c in res.results] == \
            [c.metrics for c in run_sweep(self.SPEC, workers=1).results]

    def test_cpu_parent_still_uses_the_pool(self, monkeypatch):
        pytest.importorskip("jax").devices()
        started = self._spy(monkeypatch)
        run_sweep(self.SPEC, workers=2)
        assert started == [2]


class TestArrivalProcesses:
    RATE, DUR = 8.0, 60.0

    def _mean_count(self, fn, n=40, **kw):
        return float(np.mean([
            len(fn(self.RATE, self.DUR, np.random.default_rng(s), **kw))
            for s in range(n)]))

    @pytest.mark.parametrize("fn", [poisson_arrivals, diurnal_arrivals,
                                    mmpp_arrivals],
                             ids=["poisson", "diurnal", "mmpp"])
    def test_empirical_rate_matches_configured(self, fn):
        expect = self.RATE * self.DUR
        assert abs(self._mean_count(fn) - expect) / expect < 0.15

    @pytest.mark.parametrize("fn", [poisson_arrivals, diurnal_arrivals,
                                    mmpp_arrivals],
                             ids=["poisson", "diurnal", "mmpp"])
    def test_times_sorted_within_window(self, fn):
        t = fn(self.RATE, self.DUR, np.random.default_rng(0))
        assert np.all(np.diff(t) >= 0)
        assert t.size == 0 or (t[0] >= 0 and t[-1] < self.DUR)

    def test_mmpp_is_burstier_than_poisson(self):
        """Dispersion index (var/mean of per-second counts) >> 1 for MMPP."""
        def dispersion(fn):
            ds = []
            for s in range(20):
                t = fn(self.RATE, self.DUR, np.random.default_rng(s))
                counts = np.bincount(t.astype(int), minlength=int(self.DUR))
                ds.append(counts.var() / max(counts.mean(), 1e-9))
            return float(np.mean(ds))
        assert dispersion(mmpp_arrivals) > 2.0 * dispersion(poisson_arrivals)

    def test_generate_trace_burst_kinds(self):
        for kind in ("poisson", "diurnal", "mmpp"):
            reqs = generate_trace_burst(seed=0, kind=kind, **SMALL)
            assert reqs == sorted(reqs, key=lambda r: r.r)
            assert all(r.p_true > 0 for r in reqs)
        with pytest.raises(ValueError):
            generate_trace_burst(seed=0, kind="nope", **SMALL)


class TestAzureTrace:
    def _write(self, tmp_path, text):
        p = tmp_path / "trace.csv"
        p.write_text(text)
        return p

    def test_load_and_expand(self, tmp_path):
        p = self._write(tmp_path,
                        "function,m0,m1,m2\n"
                        "thumbnailer,3,0,2\n"
                        "my-custom-fn,1,4,0\n")
        trace = load_azure_trace(p)
        assert trace == {"thumbnailer": [3, 0, 2], "my-custom-fn": [1, 4, 0]}
        reqs = requests_from_trace(trace, seed=0)
        assert len(reqs) == 10
        # arrivals land inside their minute
        for r in reqs:
            if r.fn == "thumbnailer":
                assert 0 <= r.r < 60 or 120 <= r.r < 180
        # deterministic for a seed
        again = requests_from_trace(trace, seed=0)
        assert [(r.fn, r.r, r.p_true) for r in reqs] == \
            [(r.fn, r.r, r.p_true) for r in again]

    def test_unknown_fn_maps_to_stable_profile(self, tmp_path):
        from repro.core.traces import profile_for
        assert profile_for("thumbnailer") == "thumbnailer"
        mapped = profile_for("my-custom-fn")
        assert mapped == profile_for("my-custom-fn")  # stable
        assert stable_hash("my-custom-fn") == stable_hash("my-custom-fn")

    def test_sweep_cell_over_trace(self, tmp_path):
        p = self._write(tmp_path, "f1,40,40\nf2,10,10\n")
        cell = SweepCell(policy="sept", cores=4, arrival="trace",
                         trace_path=str(p), seed=0)
        m = run_cell(cell)
        assert m["n"] == 100
        assert run_cell(cell) == m

    def test_bad_trace_raises(self, tmp_path):
        with pytest.raises(ValueError):
            load_azure_trace(self._write(tmp_path, "header,only\n"))
        with pytest.raises(ValueError):
            load_azure_trace(self._write(tmp_path, "f1,-3\n"))
        # a corrupt *data* row must raise, not be skipped as a header
        with pytest.raises(ValueError, match="f2"):
            load_azure_trace(self._write(tmp_path, "f1,3\nf2,1,,4\n"))


@pytest.mark.slow
class TestSweepScale:
    def test_200_cell_grid_end_to_end(self):
        """The acceptance grid: 200+ cells through the pool, serial ==
        parallel on the aggregate."""
        spec = SweepSpec(policies=("fifo", "sept", "eect", "rect", "fc"),
                         intensities=(20, 40), cores=(5,),
                         arrivals=("uniform", "poisson"), seeds=11)
        cells = spec.cells()
        assert len(cells) == 220
        res = run_sweep(spec)
        assert len(res) == 220
        agg = res.aggregate()
        assert len(agg) == 20
        assert all(r["seeds"] == 11 for r in agg)
