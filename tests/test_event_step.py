"""Fused event-step megakernel: Pallas parity, metrics-only parity, the
chunk auto-tuner, and the scan-path observability hooks.

Contracts under test:

* the Pallas megakernel (``repro.kernels.event_step``) is **bit-identical**
  to the pure-jnp oracle on rows ``[:n]`` for every supported combination
  (base pull, with and without FC pull counts), running under
  ``interpret=True`` on CPU; unsupported combinations refuse
  ``force="pallas"`` loudly instead of silently falling back;
* ``run_cells_scan(metrics_only=True)`` rows are exactly equal to the
  write-back rows across the whole supported feature matrix -- including
  the failure / backup / steal counters;
* the chunk auto-tuner runs once per bucket shape, persists its choice on
  the cache entry (visible in ``scan_cache_stats()``), and repeated asks
  are memoized no-ops -- the determinism contract;
* degraded (ineligible) cells under ``strict=False`` do not churn the
  compile cache: batch-size variation folds into one entry per shape;
* ``scan_bucket_timings()`` records every dispatched chunk, and a
  jax.profiler trace around a sweep holds the ``fastpath.*`` spans.
"""

import sys
from functools import partial
from pathlib import Path

import numpy as np
import pytest

from repro.core import (
    SweepCell,
    run_cell,
    run_cells_scan,
    scan_bucket_timings,
    scan_cache_clear,
    scan_cache_stats,
    scan_timings_clear,
)

try:
    import jax  # noqa: F401
    import jax.numpy as jnp
    HAVE_JAX = True
except ImportError:
    HAVE_JAX = False

needs_jax = pytest.mark.skipif(not HAVE_JAX, reason="jax not installed")

ROOT = Path(__file__).resolve().parent.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))


BASE_FLAGS = dict(freeze=False, use_fc=False, fc_push=False, dyn=False,
                  het=False, hedge=False, cold=False, dup=False)


def _smoke_inputs(use_fc, B=3, n=8, F=2, NN=2, NS=4, W=4, KQ=8, seed=0):
    """Small hand-built bucket: sorted arrivals, warm-seeded estimator ring,
    three coefficient rows (FIFO / SEPT / FC-ish) so every dispatch branch
    of the kernel is exercised."""
    rng = np.random.default_rng(seed)
    n1 = n + 1
    inp = {
        "t": np.full((B, n1), np.inf, dtype=np.float32),
        "fnid": np.zeros((B, n1), dtype=np.int32),
        "p": np.zeros((B, n1), dtype=np.float32),
        "cost": np.zeros((B, n1), dtype=np.float32),
        "cnt": np.zeros((B, n1), dtype=np.float32),
        "home0": np.zeros((B, n1), dtype=np.int32),
        "coef": np.zeros((B, 5), dtype=np.float32),
        "cores": np.zeros(B, dtype=np.int32),
        "nodes": np.ones(B, dtype=np.int32),
        "route": np.zeros(B, dtype=np.int32),
        "ring0": np.zeros((B, 1, F, W), dtype=np.float32),
        "rsum0": np.zeros((B, 1, F), dtype=np.float32),
        "rlen0": np.zeros((B, 1, F), dtype=np.int32),
        "rpos0": np.zeros((B, 1, F), dtype=np.int32),
        "cumf": np.zeros((B, n1 if use_fc else 1, F), dtype=np.float32),
        "fn_ev": np.full((B, F, KQ), n, dtype=np.int32),
    }
    coefs = [[1.0, 0.0, 0.0, 0.0, 0.0],      # FIFO
             [0.0, 0.0, 1.0, 0.0, 0.0],      # SEPT
             [0.0, 0.0, 1.0, 0.3, 0.0]]      # FC-ish
    for b in range(B):
        t = np.sort(rng.uniform(0, 2.0, n)).astype(np.float32)
        fn = rng.integers(0, F, n).astype(np.int32)
        inp["t"][b, :n] = t
        inp["fnid"][b, :n] = fn
        inp["p"][b, :n] = rng.lognormal(-1, 0.5, n).astype(np.float32)
        inp["cost"][b, :n] = 0.001
        inp["coef"][b] = coefs[b % len(coefs)]
        inp["cores"][b] = 1 + (b % 2)
        inp["nodes"][b] = 1 + b % NN
        inp["ring0"][b, 0, :, 0] = 0.5
        inp["rsum0"][b, 0, :] = 0.5
        inp["rlen0"][b, 0, :] = 1
        if use_fc:
            for f in range(F):
                inp["cumf"][b, 1:, f] = np.cumsum(fn == f)
        for f in range(F):
            ev = np.nonzero(fn == f)[0]
            inp["fn_ev"][b, f, :len(ev)] = ev
    # _make_planes takes the carry-shaping flags only; use_fc is a kernel
    # static that does not change the carry layout
    plane_flags = dict(n_nodes=NN, n_slots=NS, window=W, n_copies=1,
                      fc_ring=1,
                      **{k: v for k, v in BASE_FLAGS.items() if k != "use_fc"})
    static = dict(plane_flags, use_fc=use_fc, n_ep=1, horizon=1.0,
                  n_steps=2 * n + 2)
    return inp, plane_flags, static, n


@needs_jax
class TestPallasParity:
    """The megakernel is bit-identical to the jnp oracle (interpret=True)."""

    @pytest.mark.parametrize("use_fc", [False, True])
    def test_bit_identical_outputs(self, use_fc):
        from repro.core import fastpath as _fp
        from repro.kernels import ops

        inp, flags, static, n = _smoke_inputs(use_fc)
        arrs = {k: jnp.asarray(v) for k, v in inp.items()}
        clk, ctr = jax.vmap(partial(_fp._make_planes, **flags))(arrs)

        ref = ops.event_step(clk, ctr, arrs, force="ref", **static)
        pal = ops.event_step(clk, ctr, arrs, force="pallas",
                             interpret=True, **static)
        for name, a, b in zip(("start", "finish", "prio", "node"),
                              ref[:4], pal[:4]):
            # row n is the shared no-op sentinel both paths scribble into
            np.testing.assert_array_equal(
                np.asarray(a)[:, :n], np.asarray(b)[:, :n],
                err_msg=f"{name} diverged (use_fc={use_fc})")

    @pytest.mark.parametrize("use_fc", [False, True])
    def test_mixed_lengths_run_their_own_steps(self, use_fc):
        # one batch: a full cell (n = R), shorter cells of other lengths and
        # an all-padded cell (n = 0); the kernel runs each cell's own 2n
        # steps, the oracle the bucket's whole static budget
        from repro.core import fastpath as _fp
        from repro.kernels import ops

        inp, flags, static, n = _smoke_inputs(use_fc, B=4)
        lengths = (n, 5, 3, 0)
        idle = _fp._alloc_bucket_inputs(
            (0x2 if use_fc else 0x0, n, 2, 4, 2, 8, 4, 1, 1, 1, 0, 0), 1)
        for b, m in enumerate(lengths):
            if m == 0:                    # the idle allocation's cell
                for k in inp:
                    inp[k][b] = idle[k][0]
                continue
            inp["t"][b, m:] = np.inf
            for k in ("fnid", "p", "cost"):
                inp[k][b, m:] = 0
            if use_fc:
                inp["cumf"][b, m + 1:] = inp["cumf"][b, m]
            inp["fn_ev"][b] = n
            for f in range(inp["fn_ev"].shape[1]):
                ev = np.nonzero(inp["fnid"][b, :m] == f)[0]
                inp["fn_ev"][b, f, :len(ev)] = ev
        arrs = {k: jnp.asarray(v) for k, v in inp.items()}
        clk, ctr = jax.vmap(partial(_fp._make_planes, **flags))(arrs)

        ref = ops.event_step(clk, ctr, arrs, force="ref", **static)
        pal = ops.event_step(clk, ctr, arrs, force="pallas",
                             interpret=True, **static)
        for name, a, b in zip(("start", "finish", "prio", "node"),
                              ref[:4], pal[:4]):
            a, b = np.asarray(a), np.asarray(b)
            np.testing.assert_array_equal(
                a[:, :n], b[:, :n], err_msg=f"{name} diverged "
                f"(use_fc={use_fc}, lengths={lengths})")
            for row, m in enumerate(lengths):
                assert np.all(a[row, m:n] == 0), name   # never dispatched
            # no step ran in the padded cell: not even the no-op sentinel
            assert np.all(b[lengths.index(0)] == 0), name

    def test_supported_matrix(self):
        from repro.kernels.event_step import event_step_supported

        assert event_step_supported(**BASE_FLAGS)
        assert event_step_supported(**{**BASE_FLAGS, "use_fc": True})
        for feat in ("freeze", "fc_push", "dyn", "het", "hedge", "cold",
                     "dup"):
            assert not event_step_supported(**{**BASE_FLAGS, feat: True}), \
                feat

    def test_force_pallas_refuses_unsupported(self):
        from repro.core import fastpath as _fp
        from repro.kernels import ops

        inp, flags, static, _ = _smoke_inputs(False)
        arrs = {k: jnp.asarray(v) for k, v in inp.items()}
        clk, ctr = jax.vmap(partial(_fp._make_planes, **flags))(arrs)
        bad = dict(static, dyn=True)
        with pytest.raises(NotImplementedError):
            ops.event_step(clk, ctr, arrs, force="pallas", interpret=True,
                           **bad)


# every supports()=yes regime: base pull per policy, push, FC pull counts,
# capacity dynamics, heterogeneity + degradation, hedging, cold starts
PARITY_CELLS = [
    SweepCell(policy="fifo", nodes=2, cores=4, intensity=10, seed=0,
              backend="scan"),
    SweepCell(policy="sept", nodes=2, cores=4, intensity=10, seed=1,
              backend="scan"),
    SweepCell(policy="fc", nodes=2, cores=4, intensity=10, seed=2,
              backend="scan"),
    SweepCell(policy="sept", assignment="push", lb="least_loaded", nodes=2,
              cores=4, intensity=10, seed=3, backend="scan"),
    SweepCell(policy="sept", nodes=2, cores=4, intensity=10, seed=4,
              autoscale=True, backend="scan"),
    SweepCell(policy="sept", nodes=3, cores=4, intensity=10, seed=5,
              fail_spec=((1, 2.0),), backend="scan"),
    SweepCell(policy="sept", nodes=2, cores=4, intensity=10, seed=6,
              node_speeds=(1.0, 1.6),
              degrade=((1, 1.0, 3.0, 2.0),), backend="scan"),
    SweepCell(policy="sept", nodes=2, cores=4, intensity=10, seed=7,
              hedge_multiple=3.0, backend="scan"),
    SweepCell(policy="sept", nodes=2, cores=4, intensity=10, seed=8,
              warm=False, backend="scan"),
]


@needs_jax
class TestMetricsOnlyParity:
    def test_rows_exactly_equal_write_back(self):
        """metrics_only=True rows are bit-identical to the write-back rows
        across the supported feature matrix, including the lost / backup /
        steal counters (satellite contract of the mega sweep)."""
        wb = run_cells_scan(PARITY_CELLS, metrics_only=False)
        mo = run_cells_scan(PARITY_CELLS, metrics_only=True)
        for cell, a, b in zip(PARITY_CELLS, wb, mo):
            assert set(a) == set(b), cell.label()
            for k, v in a.items():
                assert b[k] == v, f"{cell.label()}: {k} {b[k]} != {v}"

    def test_workload_sharing_matches_unshared(self):
        """Cells differing only by policy share one burst under
        metrics_only -- and still match their individually-run rows."""
        cells = [SweepCell(policy=p, nodes=2, cores=4, intensity=10, seed=0,
                           backend="scan")
                 for p in ("fifo", "sept", "eect", "rect", "fc")]
        together = run_cells_scan(cells, metrics_only=True)
        for cell, row in zip(cells, together):
            solo = run_cells_scan([cell], metrics_only=True)[0]
            assert solo == row, cell.label()


@needs_jax
class TestAutotune:
    # tiny base-pull bucket: tuning compiles two candidate runners only
    KEY = (0x0, 16, 2, 4, 4, 4, 4, 1, 1, 1, 0, 0)

    def test_tunes_once_and_memoizes(self, monkeypatch):
        from repro.core import fastpath as fp

        scan_cache_clear()
        monkeypatch.setattr(fp, "SCAN_AUTOTUNE", True)
        monkeypatch.setattr(fp, "SCAN_BATCH_MAX", 64)   # force a tune at 130
        calls = []
        real = fp._autotune_chunk

        def counting(key, n_cells, rec=None):
            calls.append(key)
            return real(key, n_cells, rec)

        monkeypatch.setattr(fp, "_autotune_chunk", counting)
        c1 = fp._bucket_chunk(self.KEY, 130)
        c2 = fp._bucket_chunk(self.KEY, 130)
        assert c1 == c2
        assert c1 in (128, 256)          # _pow2(130) caps the candidates
        assert len(calls) == 1           # second ask is a memoized no-op
        tag = fp._bucket_tag(self.KEY)
        assert scan_cache_stats()["entries"][tag]["chunk"] == c1
        scan_cache_clear()

    def test_probes_run_the_full_step_budget(self, monkeypatch):
        # an idle cell runs no Pallas steps, so the tuner times probes
        # whose every cell holds 2 * n_b finite-arrival steps
        from repro.core import fastpath as fp

        n_b = self.KEY[1]
        _, step_kw = fp._runner_kwargs(self.KEY)
        seen = []
        real = fp._probe_inputs

        def recording(key, bsz):
            inp = real(key, bsz)
            seen.append(inp)
            return inp

        scan_cache_clear()
        monkeypatch.setattr(fp, "_probe_inputs", recording)
        fp._autotune_chunk(self.KEY, 130)
        scan_cache_clear()
        assert [inp["t"].shape[0] for inp in seen] == [128, 256]
        for inp in seen:
            steps = 2 * np.isfinite(inp["t"]).sum(axis=1)
            assert (steps == 2 * n_b).all()
            assert (steps == step_kw["n_steps"]).all()
            assert (np.diff(inp["t"][:, :n_b], axis=1) > 0).all()
        idle = fp._alloc_bucket_inputs(self.KEY, 2)
        assert not np.isfinite(idle["t"]).any()

    def test_no_tuning_below_default_chunk(self, monkeypatch):
        from repro.core import fastpath as fp

        scan_cache_clear()
        monkeypatch.setattr(fp, "SCAN_AUTOTUNE", True)
        monkeypatch.setattr(fp, "_autotune_chunk",
                            lambda *a: pytest.fail("tuned a small bucket"))
        assert fp._bucket_chunk(self.KEY, 64) == fp.SCAN_BATCH_MAX
        scan_cache_clear()

    def test_autotune_disabled(self, monkeypatch):
        from repro.core import fastpath as fp

        scan_cache_clear()
        monkeypatch.setattr(fp, "SCAN_AUTOTUNE", False)
        assert fp._bucket_chunk(self.KEY, 5000) == fp.SCAN_BATCH_MAX
        scan_cache_clear()


@needs_jax
class TestDegradedCacheChurn:
    def test_degraded_cells_do_not_churn_cache(self):
        """strict=False fallback cells never touch the scan cache, and
        batch-size variation folds into one entry per bucket shape --
        re-running a mixed grid adds hits, not misses (regression: degraded
        cells used to recompile per call)."""
        eligible = [SweepCell(policy="fifo", nodes=2, cores=4, intensity=10,
                              seed=s, backend="scan") for s in range(3)]
        degraded = SweepCell(policy="fc", nodes=2, cores=18, intensity=15,
                             seed=0, backend="scan")
        scan_cache_clear()
        ms = run_cells_scan(eligible + [degraded], strict=False,
                            metrics_only=True)
        assert ms[-1]["degraded"] == 1.0
        assert all("degraded" not in m for m in ms[:-1])
        s1 = scan_cache_stats()
        assert s1["misses"] > 0

        ms2 = run_cells_scan(eligible + [degraded], strict=False,
                             metrics_only=True)
        s2 = scan_cache_stats()
        assert ms2[:-1] == ms[:-1]
        assert s2["misses"] == s1["misses"]      # no recompiles
        assert s2["hits"] > s1["hits"]
        assert s2["size"] == s1["size"]

        # growing the batch folds into the same entry: one more compiled
        # runner (the new batch size), no new shape entry
        more = [SweepCell(policy="fifo", nodes=2, cores=4, intensity=10,
                          seed=s, backend="scan") for s in range(5)]
        run_cells_scan(more, metrics_only=True)
        s3 = scan_cache_stats()
        assert len(s3["entries"]) == len(s2["entries"])
        assert s3["size"] == s2["size"] + 1
        scan_cache_clear()


@needs_jax
class TestObservability:
    CELLS = [SweepCell(policy="fifo", nodes=2, cores=4, intensity=10,
                       seed=s, backend="scan") for s in range(2)]

    def test_bucket_timings_record_chunks(self):
        scan_timings_clear()
        run_cells_scan(self.CELLS, metrics_only=True)
        recs = scan_bucket_timings()
        assert recs
        assert sum(r["cells"] for r in recs) == len(self.CELLS)
        for r in recs:
            for k in ("bucket", "bsz", "cells", "build_s", "compile_s",
                      "dispatch_s", "sync_s"):
                assert k in r
        scan_timings_clear()
        assert scan_bucket_timings() == []

    def test_analyse_scan_buckets(self):
        from benchmarks.engine_bench import analyse_scan_buckets

        recs = [
            {"bucket": "a", "bsz": 4, "cells": 4, "build_s": 0.1,
             "compile_s": 1.0, "dispatch_s": 0.0, "sync_s": 0.2},
            {"bucket": "a", "bsz": 8, "cells": 6, "build_s": 0.1,
             "compile_s": 0.0, "dispatch_s": 0.0, "sync_s": 0.2},
            {"bucket": "b", "bsz": 4, "cells": 2, "build_s": 0.0,
             "compile_s": 0.0, "dispatch_s": 0.0, "sync_s": 0.1},
        ]
        out = analyse_scan_buckets(recs)
        assert [o["bucket"] for o in out] == ["a", "b"]   # by total desc
        a = out[0]
        assert a["cells"] == 10 and a["chunks"] == 2 and a["bsz"] == 8
        assert a["dominant"] == "compile_s"
        assert a["total_s"] == pytest.approx(1.6)
        assert a["cells_per_s"] == pytest.approx(10 / 1.6)

    def test_profile_trace_dump(self, tmp_path):
        # a jax.profiler trace taken around a sweep holds the scan entry's
        # own spans on its host planes
        from jax.profiler import ProfileData

        with jax.profiler.trace(str(tmp_path)):
            run_cells_scan(self.CELLS, metrics_only=True)
        (path,) = tmp_path.rglob("*.xplane.pb")     # one trace, dumped once
        names = {ev.name for plane in ProfileData.from_file(str(path)).planes
                 for line in plane.lines for ev in line.events}
        assert {"fastpath.entry", "fastpath.fill", "fastpath.dispatch",
                "fastpath.wait", "fastpath.unpack"} <= names
