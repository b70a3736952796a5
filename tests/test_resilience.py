"""Request-lifecycle resilience: timeouts, retry backoff, admission control.

Contracts under test:

* ``TimeoutSpec`` / ``RetryPolicy`` / ``AdmissionPolicy`` value semantics
  and constructor validation;
* backoff delays are pure functions of ``(seq, attempt)`` -- deterministic
  across engines and worker counts -- and bounded by the
  ``(1-jitter)..1`` window around ``min(cap, base * 2**(attempt-1))``
  (property-tested through the hypothesis shim);
* the reference ``Cluster`` conserves requests under resilience: every
  request ends terminal (completed xor failed), ``retries_issued`` equals
  the summed per-request attempt counters, wasted work only appears once
  timeouts can cancel running attempts;
* the scan kernel reproduces the reference *exactly* on the resilience
  counters (``timed_out`` / ``shed`` / ``retries_issued``), the
  failed-request id sets and per-request attempts -- a small grid in
  tier-1 and a >= 48-cell grid in the slow set;
* ``REPRO_SCAN_CHECK=1`` names the offending bucket/cell/field on a
  non-finite output and passes cleanly over healthy resilience cells;
* ``run_sweep`` isolates faulting cells into the ``failed`` column plus
  ``meta["errors"]``, the batch dispatcher retries value-dependent
  rejections (``ScanRejected``) per item instead of losing the whole
  bucket, and every other scan error propagates to the caller.
"""

import copy
import itertools
import math

import pytest

from _hypothesis_shim import given, settings, st
from repro.core import (
    AdmissionPolicy,
    ResilienceSpec,
    RetryPolicy,
    SweepCell,
    SweepSpec,
    TimeoutSpec,
    generate_trace_burst,
    retry_jitter_u,
    run_sweep,
    simulate_cluster,
)
from repro.core.sweep import run_cells_scan

try:
    import jax  # noqa: F401
    HAVE_JAX = True
except ImportError:
    HAVE_JAX = False

needs_jax = pytest.mark.skipif(not HAVE_JAX, reason="jax not installed")


def _burst(seed=0, intensity=8, duration_s=30.0, cores=8):
    return generate_trace_burst(cores=cores, intensity=intensity, seed=seed,
                                kind="poisson", duration_s=duration_s)


def _run_ref(reqs, spec, policy="sept", **kw):
    base = dict(nodes=2, cores_per_node=4, policy=policy,
                assignment="push", warm=True, resilience=spec)
    base.update(kw)
    return simulate_cluster(copy.deepcopy(reqs), **base)


# ---------------------------------------------------------------------------
# spec value semantics
# ---------------------------------------------------------------------------
class TestTimeoutSpec:
    def test_deadline_is_multiple_of_estimate(self):
        spec = TimeoutSpec(multiple=4.0, floor_s=0.5)
        assert spec.deadline(10.0, 2.0) == 10.0 + 4.0 * 2.0

    def test_floor_guards_tiny_estimates(self):
        spec = TimeoutSpec(multiple=4.0, floor_s=0.5)
        # a 1 ms estimate must not produce a 4 ms deadline
        assert spec.deadline(0.0, 0.001) == 4.0 * 0.5

    def test_absolute_overrides_multiple(self):
        spec = TimeoutSpec(multiple=4.0, floor_s=0.5, absolute_s=30.0)
        assert spec.deadline(5.0, 100.0) == 35.0

    def test_validation(self):
        with pytest.raises(ValueError):
            TimeoutSpec(multiple=0.0)
        with pytest.raises(ValueError):
            TimeoutSpec(floor_s=-1.0)
        with pytest.raises(ValueError):
            TimeoutSpec(absolute_s=0.0)
        with pytest.raises(ValueError):
            TimeoutSpec(absolute_s=math.inf)


class TestRetryPolicy:
    def test_should_retry_respects_budget(self):
        pol = RetryPolicy(max_attempts=3)
        assert pol.should_retry("timeout", 1)
        assert pol.should_retry("timeout", 2)
        assert not pol.should_retry("timeout", 3)

    def test_should_retry_respects_causes(self):
        pol = RetryPolicy(max_attempts=3, retry_on=("timeout",))
        assert pol.should_retry("timeout", 1)
        assert not pol.should_retry("shed", 1)
        assert not pol.should_retry("kill", 1)

    def test_immediate_mode_has_zero_delay(self):
        pol = RetryPolicy(max_attempts=4, mode="immediate")
        assert all(pol.delay(seq, a) == 0.0
                   for seq in (0, 7, 991) for a in (1, 2, 3))

    def test_backoff_doubles_until_cap(self):
        pol = RetryPolicy(max_attempts=8, mode="backoff", base_delay_s=0.5,
                          cap_delay_s=4.0, jitter=0.0)
        # jitter=0 makes the schedule exactly min(cap, base * 2**(a-1))
        assert [pol.delay(0, a) for a in (1, 2, 3, 4, 5)] == \
            [0.5, 1.0, 2.0, 4.0, 4.0]

    def test_jitter_window(self):
        pol = RetryPolicy(max_attempts=4, mode="backoff", base_delay_s=1.0,
                          cap_delay_s=8.0, jitter=0.5)
        for seq in range(50):
            for a in (1, 2, 3):
                d = 1.0 * 2 ** (a - 1)
                assert (1 - 0.5) * d <= pol.delay(seq, a) <= d

    def test_delay_is_deterministic(self):
        pol = RetryPolicy(max_attempts=4, mode="backoff")
        assert [pol.delay(3, a) for a in (1, 2, 3)] == \
            [pol.delay(3, a) for a in (1, 2, 3)]

    def test_validation(self):
        with pytest.raises(ValueError):
            RetryPolicy(max_attempts=0)
        with pytest.raises(ValueError):
            RetryPolicy(max_attempts=17)
        with pytest.raises(ValueError):
            RetryPolicy(mode="fibonacci")
        with pytest.raises(ValueError):
            RetryPolicy(jitter=1.5)
        with pytest.raises(ValueError):
            RetryPolicy(retry_on=("tuesday",))


class TestAdmissionPolicy:
    def test_shed_compares_queue_work_per_free_slot(self):
        pol = AdmissionPolicy(threshold_s=2.0)
        assert not pol.shed(3.9, 2)        # 1.95 s/slot
        assert pol.shed(4.1, 2)            # 2.05 s/slot

    def test_zero_free_slots_counts_as_one(self):
        # a saturated node still sheds on the same work threshold rather
        # than dividing by zero
        pol = AdmissionPolicy(threshold_s=2.0)
        assert pol.shed(2.5, 0)
        assert not pol.shed(1.5, 0)

    def test_validation(self):
        with pytest.raises(ValueError):
            AdmissionPolicy(threshold_s=-1.0)
        with pytest.raises(ValueError):
            AdmissionPolicy(threshold_s=math.inf)


class TestResilienceSpec:
    def test_null_spec_collapses_to_none(self):
        assert ResilienceSpec.from_any(None) is None
        assert ResilienceSpec.from_any(ResilienceSpec()) is None

    def test_component_promotion(self):
        spec = ResilienceSpec.from_any(TimeoutSpec())
        assert isinstance(spec, ResilienceSpec)
        assert spec.timeout is not None and spec.retry is None
        assert ResilienceSpec.from_any(RetryPolicy()).retry is not None
        assert ResilienceSpec.from_any(
            AdmissionPolicy()).admission is not None
        with pytest.raises(TypeError):
            ResilienceSpec.from_any(object())

    def test_arrays_shapes(self):
        t4, r6, a2 = ResilienceSpec(
            timeout=TimeoutSpec(), retry=RetryPolicy(),
            admission=AdmissionPolicy()).arrays()
        assert (t4.shape, r6.shape, a2.shape) == ((4,), (6,), (2,))


# ---------------------------------------------------------------------------
# property tests (hypothesis shim: real hypothesis when installed,
# deterministic random draws otherwise)
# ---------------------------------------------------------------------------
class TestRetryProperties:
    @given(st.integers(min_value=0, max_value=10 ** 6),
           st.integers(min_value=1, max_value=15))
    @settings(max_examples=100)
    def test_jitter_u_in_unit_interval_and_deterministic(self, seq, attempt):
        u = retry_jitter_u(seq, attempt)
        assert 0.0 <= u < 1.0
        assert u == retry_jitter_u(seq, attempt)

    @given(st.integers(min_value=0, max_value=10 ** 6),
           st.integers(min_value=1, max_value=7),
           st.floats(min_value=0.01, max_value=4.0),
           st.floats(min_value=0.0, max_value=1.0))
    @settings(max_examples=60)
    def test_backoff_delay_bounds(self, seq, attempt, base, jitter):
        pol = RetryPolicy(max_attempts=8, mode="backoff", base_delay_s=base,
                          cap_delay_s=8.0, jitter=jitter)
        d = min(8.0, base * 2 ** (attempt - 1))
        lo, hi = (1 - jitter) * d, d
        got = pol.delay(seq, attempt)
        assert lo - 1e-12 <= got <= hi + 1e-12

    @given(st.floats(min_value=0.0, max_value=100.0),
           st.floats(min_value=0.0, max_value=50.0),
           st.floats(min_value=0.1, max_value=8.0),
           st.floats(min_value=0.0, max_value=4.0))
    @settings(max_examples=60)
    def test_deadline_bounds(self, now, estimate, multiple, floor):
        spec = TimeoutSpec(multiple=multiple, floor_s=floor)
        dl = spec.deadline(now, estimate)
        assert dl >= now + multiple * estimate
        assert dl >= now + multiple * floor
        assert dl == now + multiple * max(estimate, floor)


# ---------------------------------------------------------------------------
# reference-engine semantics
# ---------------------------------------------------------------------------
class TestReferenceSemantics:
    SPEC = ResilienceSpec(
        timeout=TimeoutSpec(multiple=1.5, floor_s=0.3),
        retry=RetryPolicy(max_attempts=3, mode="backoff", base_delay_s=0.2,
                          cap_delay_s=2.0, jitter=0.5),
        admission=AdmissionPolicy(threshold_s=1.0))

    def test_every_request_is_terminal(self):
        reqs = _burst(seed=3)
        res = _run_ref(reqs, self.SPEC)
        assert len(res.requests) == len(reqs)
        for r in res.requests:
            # completed xor failed: no request may be silently dropped,
            # none may be both
            assert (r.c is not None) != (r.failed is not None)

    def test_retries_issued_matches_attempt_counters(self):
        res = _run_ref(_burst(seed=3), self.SPEC)
        assert res.retries_issued == sum(r.attempts for r in res.requests)
        assert res.retries_issued > 0          # the tight deadline fires

    def test_failed_causes_are_known(self):
        res = _run_ref(_burst(seed=3), self.SPEC)
        causes = {r.failed for r in res.requests if r.failed is not None}
        assert causes and causes <= {"timeout", "shed", "kill"}

    def test_wasted_work_requires_cancellation(self):
        reqs = _burst(seed=5)
        # no timeouts -> nothing ever cancels mid-service -> no waste
        calm = _run_ref(reqs, ResilienceSpec(
            admission=AdmissionPolicy(threshold_s=50.0)))
        assert calm.timed_out == 0 and calm.wasted_work == 0.0
        hot = _run_ref(reqs, self.SPEC)
        assert hot.timed_out > 0 and hot.wasted_work > 0.0

    def test_shedding_feeds_retries(self):
        reqs = _burst(seed=7, intensity=16)
        spec = ResilienceSpec(
            retry=RetryPolicy(max_attempts=3, mode="immediate",
                              retry_on=("shed",)),
            admission=AdmissionPolicy(threshold_s=0.01))
        res = _run_ref(reqs, spec)
        assert res.shed > 0
        assert res.retries_issued > 0
        assert res.timed_out == 0              # no timeout policy active

    def test_run_is_deterministic(self):
        reqs = _burst(seed=11)
        a = _run_ref(reqs, self.SPEC)
        b = _run_ref(reqs, self.SPEC)
        sig = lambda r: {q.id: (q.c, q.failed, q.attempts)
                         for q in r.requests}
        assert sig(a) == sig(b)
        assert (a.timed_out, a.shed, a.retries_issued) == \
            (b.timed_out, b.shed, b.retries_issued)

    def test_hedging_and_resilience_is_a_documented_exclusion(self):
        from repro.core.stragglers import HedgingSpec
        with pytest.raises(ValueError, match="hedging"):
            _run_ref(_burst(seed=0), self.SPEC,
                     hedging=HedgingSpec(multiple=3.0))


# ---------------------------------------------------------------------------
# scan-vs-reference exact parity
# ---------------------------------------------------------------------------
RES_SPECS = {
    "timeout": ResilienceSpec(timeout=TimeoutSpec(multiple=3.0, floor_s=2.0)),
    "timeout+backoff": ResilienceSpec(
        timeout=TimeoutSpec(multiple=3.0, floor_s=2.0),
        retry=RetryPolicy(max_attempts=3, mode="backoff", base_delay_s=0.5,
                          cap_delay_s=4.0, jitter=0.5)),
    "timeout+immediate+shed": ResilienceSpec(
        timeout=TimeoutSpec(multiple=3.0, floor_s=2.0),
        retry=RetryPolicy(max_attempts=2, mode="immediate"),
        admission=AdmissionPolicy(threshold_s=1.0)),
    "full": ResilienceSpec(
        timeout=TimeoutSpec(multiple=3.0, floor_s=2.0),
        retry=RetryPolicy(max_attempts=3, mode="backoff", base_delay_s=0.5,
                          cap_delay_s=4.0, jitter=0.5),
        admission=AdmissionPolicy(threshold_s=2.0)),
}


def _assert_exact_parity(reqs, spec, policy):
    kw = dict(nodes=2, cores_per_node=4, policy=policy, assignment="push",
              warm=True, resilience=spec)
    ref = simulate_cluster(copy.deepcopy(reqs), backend="reference", **kw)
    scn = simulate_cluster(copy.deepcopy(reqs), backend="scan", **kw)
    for k in ("timed_out", "shed", "retries_issued"):
        assert getattr(ref, k) == getattr(scn, k), \
            f"{policy}: counter {k} ref={getattr(ref, k)} " \
            f"scan={getattr(scn, k)}"
    rf = {(r.id, r.failed) for r in ref.requests if r.c is None}
    sf = {(r.id, r.failed) for r in scn.requests if r.c is None}
    assert rf == sf, f"{policy}: failed-id sets differ"
    ra = {r.id: r.attempts for r in ref.requests}
    sa = {r.id: r.attempts for r in scn.requests}
    assert ra == sa, f"{policy}: per-request attempts differ"
    return ref


@needs_jax
class TestScanParity:
    def test_small_grid_exact_counters(self):
        # 2 seeds x 4 specs on sept: one padded-shape bucket, tier-1 sized
        exercised = 0
        for seed in (0, 7):
            reqs = _burst(seed=seed, intensity=10)
            for spec in RES_SPECS.values():
                ref = _assert_exact_parity(reqs, spec, "sept")
                exercised += ref.timed_out + ref.shed + ref.retries_issued
        assert exercised > 0   # the grid actually fired resilience events

    @pytest.mark.slow
    def test_large_grid_exact_counters(self):
        # >= 48 cells: policies x timeout multiple x retry x shed x seeds
        retries = (None,
                   RetryPolicy(max_attempts=2, mode="immediate"),
                   RetryPolicy(max_attempts=3, mode="backoff",
                               base_delay_s=0.5, cap_delay_s=4.0,
                               jitter=0.5))
        sheds = (None, AdmissionPolicy(threshold_s=2.0))
        cells = list(itertools.product(
            ("sept", "fc"), (2.0, 4.0), retries, sheds, (0, 13)))
        assert len(cells) >= 48
        exercised = 0
        for policy, tmult, retry, shed, seed in cells:
            spec = ResilienceSpec(
                timeout=TimeoutSpec(multiple=tmult, floor_s=2.0),
                retry=retry, admission=shed)
            reqs = _burst(seed=seed, intensity=10)
            ref = _assert_exact_parity(reqs, spec, policy)
            exercised += ref.timed_out + ref.shed + ref.retries_issued
        assert exercised > 0

    def test_sweep_backends_agree_on_counters(self):
        # the engines-side of "same seed => identical retry schedule":
        # a cross-checked sweep over both backends must aggregate to the
        # same exact counters per cell identity
        spec = SweepSpec(
            policies=("sept",), assignments=("push",), intensities=(40,),
            cores=(4,), nodes=(2,), duration_s=30.0, seeds=1,
            timeout_multiples=(3.0,), retry_attempts=(None, 3),
            shed_thresholds=(2.0,), timeout_floor_s=2.0,
            backends=("reference", "scan"), validate="cross-check")
        res = run_sweep(spec, workers=1)
        assert res.meta["failed"] == 0 and not res.meta["errors"]
        agg = res.aggregate()
        by = {}
        for r in agg:
            by.setdefault(r["retry_attempts"], {})[r["backend"]] = r
        for ratt, d in by.items():
            assert set(d) == {"reference", "scan"}
            for k in ("timed_out", "shed", "retries_issued", "n_failed"):
                assert d["reference"][k] == d["scan"][k], \
                    f"retry_attempts={ratt}: {k} differs across backends"

    def test_worker_count_does_not_change_results(self):
        # same seed => identical schedules regardless of pool size
        spec = SweepSpec(
            policies=("sept",), assignments=("push",), intensities=(30,),
            cores=(4,), nodes=(2,), duration_s=30.0, seeds=2,
            timeout_multiples=(3.0,), retry_attempts=(3,),
            timeout_floor_s=2.0, backends=("reference",))
        sig = lambda res: [(r.cell.label(), r.cell.seed,
                            r.metrics.get("timed_out"),
                            r.metrics.get("retries_issued"),
                            r.metrics.get("goodput"))
                           for r in res.results]
        assert sig(run_sweep(spec, workers=1)) == \
            sig(run_sweep(spec, workers=2))


# ---------------------------------------------------------------------------
# REPRO_SCAN_CHECK: opt-in finiteness validation
# ---------------------------------------------------------------------------
@needs_jax
class TestScanCheck:
    def test_names_bucket_cell_and_field(self):
        import numpy as np
        from repro.core.fastpath import _scan_check_outputs
        fields = {"finish": np.array([1.0, float("nan"), 2.0])}
        with pytest.raises(FloatingPointError) as err:
            _scan_check_outputs("n128x2", 5, 3, fields)
        msg = str(err.value)
        assert "n128x2" in msg and "cell 5" in msg
        assert "'finish'" in msg and "index 1" in msg

    def test_ignores_padding_beyond_n(self):
        import numpy as np
        from repro.core.fastpath import _scan_check_outputs
        fields = {"start": np.array([1.0, 2.0, float("inf")])}
        _scan_check_outputs("n128x2", 0, 2, fields)   # inf is padding

    def test_healthy_res_cells_pass_with_check_on(self, monkeypatch):
        monkeypatch.setenv("REPRO_SCAN_CHECK", "1")
        _assert_exact_parity(_burst(seed=2, intensity=10),
                             RES_SPECS["full"], "sept")


# ---------------------------------------------------------------------------
# sweep-engine graceful degradation (fault isolation)
# ---------------------------------------------------------------------------
class TestSweepFaultIsolation:
    SPEC = SweepSpec(
        policies=("sept", "fifo"), assignments=("push",), intensities=(20,),
        cores=(4,), nodes=(2,), duration_s=20.0, seeds=1,
        timeout_multiples=(3.0,), timeout_floor_s=2.0,
        backends=("reference",))

    def test_persistent_fault_becomes_failed_row(self):
        from repro.core.sweep import run_cell

        def runner(cell):
            if cell.policy == "sept":
                raise RuntimeError("injected persistent fault")
            return run_cell(cell)

        res = run_sweep(self.SPEC, runner=runner, workers=1)
        assert res.meta["failed"] == 1
        assert any("injected persistent fault" in e
                   for e in res.meta["errors"].values())
        rows = {r.cell.policy: r.metrics for r in res.results}
        assert rows["sept"] == {"failed": 1.0}          # poisoned cell
        assert rows["fifo"].get("failed") is None       # healthy sibling
        assert math.isfinite(rows["fifo"]["R_avg"])

    def test_transient_fault_is_retried_once(self):
        from repro.core.sweep import run_cell
        seen = set()

        def runner(cell):
            key = (cell.policy, cell.seed)
            if cell.policy == "sept" and key not in seen:
                seen.add(key)
                raise RuntimeError("injected transient fault")
            return run_cell(cell)

        res = run_sweep(self.SPEC, runner=runner, workers=1)
        # the retry absorbed the fault: no failed rows, no recorded errors
        assert res.meta["failed"] == 0 and not res.meta["errors"]
        assert all(math.isfinite(r.metrics["R_avg"]) for r in res.results)

    @needs_jax
    def test_batch_fault_falls_back_to_per_item_dispatch(self, monkeypatch):
        # a value-dependent mid-batch rejection (the named ScanRejected)
        # must degrade to per-item dispatch, not lose the whole bucket
        import repro.core.fastpath as fastpath
        real = fastpath.simulate_cluster_cells_scan

        def poisoned(items, **kw):
            if len(items) > 1:
                raise fastpath.ScanRejected("injected batch rejection")
            return real(items, **kw)

        monkeypatch.setattr(
            fastpath, "simulate_cluster_cells_scan", poisoned)
        cells = [SweepCell(policy="sept", assignment="push", nodes=2,
                           cores=4, intensity=20, duration_s=20.0,
                           timeout_multiple=3.0, timeout_floor_s=2.0,
                           retry_attempts=3, backend="scan", seed=s)
                 for s in (0, 1)]
        metrics = run_cells_scan(cells, strict=False)
        assert len(metrics) == 2
        for m in metrics:
            assert math.isfinite(m["R_avg"])
            assert m["retries_issued"] >= 0

    @staticmethod
    def _fault_batched_dispatch(monkeypatch):
        """Fail the sweep's batched scan dispatch (which skips re-validation)
        with a device-style error; single-cell calls still work, so a
        swallowed fault would show up as a quietly degraded row."""
        import repro.core.fastpath as fastpath
        real = fastpath.simulate_cluster_cells_scan

        def faulty(items, validate=True, **kw):
            if not validate:
                raise RuntimeError("injected device fault")
            return real(items, validate=validate, **kw)

        monkeypatch.setattr(fastpath, "simulate_cluster_cells_scan", faulty)

    @needs_jax
    @pytest.mark.parametrize("strict", [False, True])
    def test_device_fault_propagates_from_run_cells_scan(self, monkeypatch,
                                                          strict):
        # anything but ScanRejected (a compile refusal, a device fault) is
        # never turned into a reference run: it reaches the caller with its
        # own type and message, in strict and non-strict mode alike
        self._fault_batched_dispatch(monkeypatch)
        cells = [SweepCell(policy="sept", nodes=2, cores=4, intensity=10,
                           backend="scan", seed=s) for s in (0, 1)]
        with pytest.raises(RuntimeError, match="injected device fault"):
            run_cells_scan(cells, strict=strict)

    @needs_jax
    def test_device_fault_propagates_from_run_sweep(self, monkeypatch):
        self._fault_batched_dispatch(monkeypatch)
        spec = SweepSpec(policies=("fifo", "sept"), nodes=(2,), cores=(4,),
                         intensities=(10,), seeds=1, backends=("scan",))
        with pytest.raises(RuntimeError, match="injected device fault"):
            run_sweep(spec, workers=1)

    @needs_jax
    def test_strict_false_degrades_ineligible_cells(self):
        # pull-assignment resilience is outside the kernel's capability
        # matrix: strict=True raises, strict=False runs the reference and
        # marks the row degraded
        cell = SweepCell(policy="sept", assignment="pull", nodes=2,
                         cores=4, intensity=20, duration_s=20.0,
                         timeout_multiple=3.0, timeout_floor_s=2.0,
                         backend="scan", seed=0)
        with pytest.raises(ValueError, match="not scan-eligible"):
            run_cells_scan([cell], strict=True)
        (m,) = run_cells_scan([cell], strict=False)
        assert m["degraded"] == 1.0
        assert math.isfinite(m["R_avg"]) and m["timed_out"] >= 0
