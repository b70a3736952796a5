"""Flight recorder: unified lifecycle traces, probes, and triage.

Contracts under test:

* **trace parity** -- the canonical lifecycle stream reconstructed from the
  scan kernel's written-back request tensors matches the instrumented
  reference event loop's stream across the feature matrix (pull/push,
  dynamics, steal/duplicate hedging, resilience, cold starts), and the
  streaming chunked-scan path matches too;
* the rich reference stream's :meth:`SimTrace.canonical` projection is
  self-consistent with :func:`trace_from_result` on the same run;
* :func:`first_divergence` names the right event/field for injected
  perturbations (time drift, wrong node, missing event, attempt count,
  failure cause) and stays silent on agreeing streams;
* :func:`triage_cell` pinpoints a perturbed request end-to-end, and a
  cross-check :class:`BackendMismatchError` carries the triage report;
* probes/exporters: windowed probe series are conservation-consistent,
  the Chrome-trace export is loadable JSON with one lane per busy slot,
  ``explain`` renders a lifecycle narrative, manifests capture provenance;
* ``run_sweep(progress=...)`` fires the callback and ``ProgressReporter``
  rate-limits correctly; ``scan_timings_clear`` resets the chunk records
  and the phase totals;
* tracing is opt-in: ``trace=False`` attaches nothing and installs no
  recorder in the engines.
"""

import io
import json
import math

import pytest

from repro.core import (
    CANONICAL_KINDS,
    FlightRecorder,
    ProgressReporter,
    SimTrace,
    SweepCell,
    SweepSpec,
    TraceEvent,
    first_divergence,
    generate_burst,
    run_manifest,
    run_sweep,
    simulate_cluster,
    simulate_single_node,
    trace_from_requests,
    trace_from_result,
    triage_cell,
    write_manifest,
)
from repro.core.cluster import Cluster, ClusterConfig
from repro.core.resilience import (
    AdmissionPolicy,
    ResilienceSpec,
    RetryPolicy,
    TimeoutSpec,
)
from repro.core.simulator import REQ_OVERHEAD_S
from repro.core.stragglers import HedgingSpec

try:
    import jax  # noqa: F401
    HAVE_JAX = True
except ImportError:
    HAVE_JAX = False

needs_jax = pytest.mark.skipif(not HAVE_JAX, reason="jax not installed")

CELL = dict(nodes=3, cores_per_node=4, policy="fc")


def _twin(seed=0, cores=12, intensity=30):
    """Two identical bursts (ids differ: Request ids are global)."""
    return (generate_burst(cores=cores, intensity=intensity, seed=seed),
            generate_burst(cores=cores, intensity=intensity, seed=seed))


RES = ResilienceSpec(
    timeout=TimeoutSpec(multiple=3.0, floor_s=2.0),
    retry=RetryPolicy(max_attempts=3, mode="backoff", base_delay_s=0.5,
                      cap_delay_s=4.0, jitter=0.5),
    admission=AdmissionPolicy(threshold_s=1.0))


# ---------------------------------------------------------------------------
# unit: canonical projection, relabel, first_divergence
# ---------------------------------------------------------------------------
def _ev(t, kind, req=0, node=0, attempt=0, info=""):
    return TraceEvent(t, kind, req, node, "fn", attempt, info)


def _trace(events, **kw):
    kw.setdefault("nodes", 2)
    kw.setdefault("slots_per_node", 2)
    return SimTrace(events=list(events), **kw)


class TestCanonical:
    def test_winning_run_rules(self):
        # req 0: killed on node 0, re-dispatched on node 1 -> the winner is
        # the node-1 run; the canonical stream keeps one arrival, the
        # winning dispatch/complete pair, nothing else
        rec = FlightRecorder()
        rec.emit(1.0, "arrival", req=0)
        rec.emit(1.1, "enqueue", req=0)
        rec.emit(1.2, "dispatch", req=0, node=0)
        rec.emit(2.0, "kill", req=0, node=0)
        rec.emit(2.0, "arrival", req=0)            # retry re-arrival
        rec.emit(2.5, "dispatch", req=0, node=1, attempt=1)
        rec.emit(4.0, "complete", req=0, node=1, attempt=1)
        canon = rec.to_trace(nodes=2).canonical()
        assert canon.counts() == {"arrival": 1, "dispatch": 1, "complete": 1}
        arr, = canon.by_kind("arrival")
        assert arr.t == 1.0                        # earliest arrival wins
        disp, = canon.by_kind("dispatch")
        assert (disp.node, disp.t, disp.attempt) == (1, 2.5, 1)

    def test_duplicate_race_keeps_winner(self):
        # duplicate hedging: both copies complete; the earlier completion
        # and ITS dispatch survive the projection
        rec = FlightRecorder()
        rec.emit(0.0, "arrival", req=7)
        rec.emit(1.0, "dispatch", req=7, node=0)
        rec.emit(2.0, "dispatch", req=7, node=1)   # racing backup
        rec.emit(3.0, "complete", req=7, node=1)   # backup wins
        rec.emit(9.0, "complete", req=7, node=0)
        canon = rec.to_trace(nodes=2).canonical()
        comp, = canon.by_kind("complete")
        disp, = canon.by_kind("dispatch")
        assert comp.t == 3.0 and comp.node == 1 and disp.node == 1

    def test_fail_only_without_completion(self):
        rec = FlightRecorder()
        rec.emit(0.0, "arrival", req=1)
        rec.emit(5.0, "fail", req=1, info="timeout")
        canon = rec.to_trace().canonical()
        assert canon.counts() == {"arrival": 1, "fail": 1}

    def test_relabel(self):
        tr = _trace([_ev(0.0, "arrival", req=100), _ev(1.0, "dispatch",
                                                       req=100)])
        out = tr.relabel({100: 3})
        assert [e.req for e in out.events] == [3, 3]
        assert [e.req for e in tr.events] == [100, 100]   # original intact


class TestFirstDivergence:
    BASE = [_ev(0.0, "arrival", req=0, node=-1),
            _ev(1.0, "dispatch", req=0, node=0, attempt=1),
            _ev(2.0, "complete", req=0, node=0, attempt=1),
            _ev(0.5, "arrival", req=1, node=-1),
            _ev(float("nan"), "fail", req=1, node=0, info="timeout")]

    def _perturbed(self, **patch):
        evs = []
        for e in self.BASE:
            if e.kind == patch.get("kind") and e.req == patch.get("req", 0):
                evs.append(TraceEvent(patch.get("t", e.t), e.kind, e.req,
                                      patch.get("node", e.node), e.fn,
                                      patch.get("attempt", e.attempt),
                                      patch.get("info", e.info)))
            else:
                evs.append(e)
        return _trace(evs)

    def test_agreement_is_none(self):
        assert first_divergence(_trace(self.BASE), _trace(self.BASE)) is None

    def test_time_drift(self):
        got = self._perturbed(kind="complete", t=2.5)
        rep = first_divergence(_trace(self.BASE), got, rtol=1e-2)
        assert (rep.kind, rep.req, rep.fld) == ("complete", 0, "t")
        # within rtol the same drift is tolerated
        assert first_divergence(_trace(self.BASE), got, rtol=0.5) is None

    def test_wrong_node(self):
        # move the whole winning run (dispatch + complete) to node 1: the
        # earliest field-level divergence is the dispatch's node
        got = self._perturbed(kind="dispatch", node=1)
        got = _trace([TraceEvent(e.t, e.kind, e.req, 1, e.fn, e.attempt,
                                 e.info) if e.kind == "complete"
                      and e.req == 0 else e for e in got.events])
        rep = first_divergence(_trace(self.BASE), got)
        assert (rep.kind, rep.fld, rep.got_value) == ("dispatch", "node", 1)

    def test_missing_event(self):
        got = _trace([e for e in self.BASE if not (e.kind == "dispatch")])
        rep = first_divergence(_trace(self.BASE), got)
        assert (rep.kind, rep.fld, rep.ref_value, rep.got_value) == (
            "dispatch", "count", 1, 0)

    def test_orphaned_dispatch_collapses_to_count(self):
        # a dispatch on the wrong node does not pair with the surviving
        # completion, so the canonical projection drops it entirely: the
        # divergence surfaces as a dispatch-count gap, not a node diff
        rep = first_divergence(
            _trace(self.BASE), self._perturbed(kind="dispatch", node=1))
        assert (rep.kind, rep.fld, rep.got_value) == ("dispatch", "count", 0)

    def test_attempt_gap_and_optout(self):
        got = self._perturbed(kind="dispatch", attempt=2)
        rep = first_divergence(_trace(self.BASE), got)
        assert (rep.fld, rep.got_value) == ("attempt", 2)
        assert first_divergence(_trace(self.BASE), got,
                                compare_attempts=False) is None

    def test_fail_compares_cause_not_node(self):
        # node on a terminal failure is engine bookkeeping -> ignored
        got = self._perturbed(kind="fail", req=1, node=2)
        assert first_divergence(_trace(self.BASE), got) is None
        got = self._perturbed(kind="fail", req=1, info="shed")
        rep = first_divergence(_trace(self.BASE), got)
        assert (rep.kind, rep.fld, rep.got_value) == ("fail", "cause", "shed")

    def test_earliest_divergence_wins(self):
        # two divergences: dispatch time drift at t=1.0 and a dropped fail
        # (NaN anchor sorts last) -- the report names the earlier one
        got = self._perturbed(kind="dispatch", t=1.5)
        evs = [e for e in got.events if e.kind != "fail"]
        rep = first_divergence(_trace(self.BASE), _trace(evs), rtol=1e-2)
        assert rep.t == 1.0 and rep.kind == "dispatch" and rep.fld == "t"


# ---------------------------------------------------------------------------
# reference engine: rich stream, self-consistency, probes, exporters
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def ref_traced():
    a = generate_burst(cores=12, intensity=30, seed=0)
    res = simulate_cluster(a, backend="reference", trace=True, **CELL)
    return a, res


class TestReferenceTrace:
    def test_rich_stream_shape(self, ref_traced):
        a, res = ref_traced
        tr = res.trace
        assert tr is not None
        counts = tr.counts()
        n = len(a)
        assert counts["arrival"] == n
        assert counts["complete"] == n
        assert counts["dispatch"] == n
        assert counts["node_up"] == CELL["nodes"]
        assert counts["channel_enter"] == n        # rich-only kind present
        # time-sorted with deterministic tie-breaks
        keys = [(e.t, e.kind) for e in tr.events]
        assert all(keys[i][0] <= keys[i + 1][0] for i in range(len(keys) - 1))

    def test_hook_matches_reconstruction(self, ref_traced):
        # the instrumented stream's canonical projection must equal the
        # written-back-state reconstruction of the SAME run, exactly
        a, res = ref_traced
        rebuilt = trace_from_result(res, requests=a,
                                    slots_per_node=CELL["cores_per_node"])
        assert first_divergence(res.trace, rebuilt, rtol=1e-9) is None
        assert set(rebuilt.counts()) <= set(CANONICAL_KINDS)

    def test_trace_off_attaches_nothing(self):
        a = generate_burst(cores=12, intensity=30, seed=0)
        res = simulate_cluster(a, backend="reference", **CELL)
        assert res.trace is None
        cluster = Cluster(ClusterConfig(nodes=2, cores_per_node=2))
        assert cluster._flight is None
        assert all(n.trace is None for n in cluster.nodes)

    def test_probes_conservation(self, ref_traced):
        a, res = ref_traced
        p = res.trace.probes(bins=32)
        n = len(a)
        assert sum(p["arrivals"]) == n
        assert sum(p["completions"]) == n
        # every arrival eventually dispatches: queue drains to zero
        assert p["queue_depth"][-1] == 0
        assert p["busy"][-1] == 0
        assert p["channel_backlog"][-1] == 0
        assert max(p["busy"]) <= CELL["nodes"] * CELL["cores_per_node"]
        assert all(0.0 <= u <= 1.0 + 1e-9 for u in p["utilization"])
        assert all(q >= 0 for q in p["queue_depth"])
        assert p["active_nodes"][-1] == CELL["nodes"]
        lens = {len(v) for k, v in p.items() if isinstance(v, list)}
        assert lens == {32}

    def test_chrome_export(self, ref_traced, tmp_path):
        a, res = ref_traced
        out = tmp_path / "trace.json"
        doc = res.trace.to_chrome(out)
        loaded = json.loads(out.read_text())
        assert loaded["traceEvents"] == doc["traceEvents"]
        evs = doc["traceEvents"]
        execs = [e for e in evs if e["ph"] == "X"]
        assert len(execs) == len(a)               # one slice per winning run
        assert all(e["dur"] >= 0 for e in execs)
        # lanes stay within the per-node slot count
        assert max(e["tid"] for e in execs) <= CELL["cores_per_node"]
        names = {e["args"]["name"] for e in evs if e["ph"] == "M"}
        assert "node0" in names

    def test_to_arrays_and_explain(self, ref_traced):
        a, res = ref_traced
        cols = res.trace.to_arrays()
        assert len(cols["t"]) == len(res.trace)
        rid = a[0].id
        text = res.trace.explain(rid)
        assert f"request {rid}" in text
        assert "queued" in text and "completed" in text
        assert "no events recorded" in res.trace.explain(10**9)

    def test_single_node_rich_trace(self):
        reqs = generate_burst(cores=4, intensity=20, seed=1)
        res = simulate_single_node(reqs, cores=4, policy="fc",
                                   backend="reference", trace=True)
        counts = res.trace.counts()
        assert counts["arrival"] == len(reqs)
        assert counts["complete"] == len(reqs)
        assert "channel_enter" in counts
        res_off = simulate_single_node(reqs, cores=4, policy="fc",
                                       backend="reference")
        assert res_off.trace is None


class TestManifest:
    def test_run_manifest_fields(self):
        man = run_manifest({"custom": 1})
        assert man["custom"] == 1
        assert man["python"] and man["platform"]
        assert len(man.get("git_sha", "0" * 40)) == 40
        assert isinstance(man["env"], dict)
        assert all(k.startswith(("REPRO_", "JAX_", "XLA_"))
                   for k in man["env"])

    def test_write_manifest_with_sweep(self, tmp_path):
        spec = SweepSpec(policies=("fc",), intensities=(10,), cores=(4,),
                         seeds=1)
        result = run_sweep(spec, workers=1)
        path = tmp_path / "out" / "manifest.json"
        man = write_manifest(path, sweep=result)
        loaded = json.loads(path.read_text())
        assert loaded["sweep"]["cells"] == len(result.results)
        assert man["sweep"]["degraded"] == 0


class TestProgress:
    def test_reporter_rate_limit_and_final(self):
        buf = io.StringIO()
        clock = iter(float(i) for i in range(100))
        rep = ProgressReporter(every=2, min_interval_s=0.0, stream=buf,
                               clock=lambda: next(clock))
        for done in range(1, 11):
            rep(done, 10)
        lines = buf.getvalue().strip().splitlines()
        assert rep.lines == len(lines) == 5            # 2,4,6,8,10
        assert "[sweep] 10/10 cells (100%)" in lines[-1]
        assert "cells/s" in lines[-1] and "eta" in lines[-1]

    def test_reporter_min_interval(self):
        buf = io.StringIO()
        rep = ProgressReporter(every=1, min_interval_s=60.0, stream=buf,
                               clock=lambda: 0.0)
        for done in range(1, 5):
            rep(done, 10)
        assert rep.lines == 1           # first line, then rate-limited
        rep(10, 10)
        assert rep.lines == 2           # final line always emits

    def test_run_sweep_calls_progress(self):
        calls = []
        spec = SweepSpec(policies=("fifo", "fc"), intensities=(10,),
                         cores=(4,), seeds=1)
        run_sweep(spec, workers=1, progress=lambda d, t: calls.append((d, t)))
        assert calls == [(1, 2), (2, 2)]

    def test_run_sweep_progress_reporter(self):
        buf = io.StringIO()
        spec = SweepSpec(policies=("fifo",), intensities=(10,), cores=(4,),
                         seeds=1)
        run_sweep(spec, workers=1,
                  progress=ProgressReporter(every=1, min_interval_s=0.0,
                                            stream=buf))
        assert "[sweep] 1/1 cells" in buf.getvalue()


def test_scan_timings_clear_resets_profile_latch():
    # scan_timings_clear() resets the chunk records, the phase totals and
    # the step counters together, so a later window reads only its own
    from repro.core import fastpath
    with fastpath._phase("fill", {"build_s": 0.0}, "build_s"):
        pass
    fastpath._SCAN_TIMINGS.append({"cells": 1})
    fastpath._STEP_COUNTS["step_slots"] += 8
    assert fastpath.scan_phase_totals()["fill"]["n"] == 1
    fastpath.scan_timings_clear()
    assert fastpath.scan_bucket_timings() == []
    assert fastpath.scan_phase_totals() == {"step_slots": 0,
                                            "call_steps": 0}


# ---------------------------------------------------------------------------
# cross-engine trace parity (the observability parity surface)
# ---------------------------------------------------------------------------
PARITY_CASES = [
    ("base_pull", {}, True),
    ("push", dict(assignment="push"), True),
    ("dynamics", dict(autoscale=True, fail_at=6.0), False),
    ("steal", dict(hedging=HedgingSpec(mode="steal")), True),
    ("duplicate", dict(hedging=HedgingSpec(mode="duplicate")), True),
    ("resilience", dict(assignment="push", resilience=RES), True),
    ("cold", dict(warm=False), True),
]


@needs_jax
@pytest.mark.parametrize("label,kw,cmp_att",
                         PARITY_CASES, ids=[c[0] for c in PARITY_CASES])
def test_scan_trace_parity(label, kw, cmp_att):
    """The scan kernel's canonical lifecycle stream must match the
    instrumented reference loop event for event: same kinds and counts per
    request, nodes identical, clocks within CLUSTER_XCHECK_RTOL.  Dynamics
    cells skip the attempt compare (the kernel re-routes kill-lost calls
    without writing back a resubmission count -- documented gap)."""
    from repro.core.sweep import CLUSTER_XCHECK_RTOL

    a, b = _twin()
    ref = simulate_cluster(a, backend="reference", trace=True, **CELL, **kw)
    fast = simulate_cluster(b, backend="scan", trace=True, **CELL, **kw)
    assert fast.trace is not None
    assert fast.trace.meta.get("backend") == "scan"
    remap = {qb.id: qa.id for qa, qb in zip(a, b)}
    rep = first_divergence(ref.trace, fast.trace.relabel(remap),
                           rtol=CLUSTER_XCHECK_RTOL,
                           compare_attempts=cmp_att)
    assert rep is None, f"{label}: {rep}"


@needs_jax
def test_streamscan_trace_parity():
    """The chunked carry-handoff path reconstructs the same canonical
    stream: StreamResult.trace(order) vs the traced reference loop."""
    from repro.core.streamscan import (simulate_cluster_stream,
                                      stream_from_requests)
    from repro.core.sweep import CLUSTER_XCHECK_RTOL

    a, b = _twin()
    ref = simulate_cluster(a, backend="reference", trace=True, **CELL)
    stream, order = stream_from_requests(b, chunk=128)
    sr = simulate_cluster_stream(stream, nodes=CELL["nodes"],
                                 cores_per_node=CELL["cores_per_node"],
                                 policy=CELL["policy"], chunk=128)
    tr = sr.trace(order)
    assert tr.meta.get("backend") == "streamscan"
    idx_to_aid = {i: a[i].id for i in range(len(a))}
    rep = first_divergence(ref.trace, tr.relabel(idx_to_aid),
                           rtol=CLUSTER_XCHECK_RTOL)
    assert rep is None, str(rep)


# ---------------------------------------------------------------------------
# triage
# ---------------------------------------------------------------------------
@needs_jax
class TestTriage:
    CELL_SPEC = dict(policy="fc", nodes=2, cores=6, intensity=15, seed=0,
                     backend="scan", cross_check=False)

    def test_agreeing_cell_returns_none(self):
        assert triage_cell(SweepCell(**self.CELL_SPEC)) is None

    def test_pinpoints_perturbed_request(self, monkeypatch):
        # make_workload is called twice (reference side, then fast side);
        # slow down one call's true runtime on the FAST side only -- triage
        # must name that request's lifecycle, not just "metrics differ"
        from repro.core import sweep as sweep_mod
        real = sweep_mod.make_workload
        state = {"calls": 0, "victim": None}

        def crooked(cell):
            reqs = real(cell)
            state["calls"] += 1
            if state["calls"] == 2:
                victim = reqs[len(reqs) // 2]
                victim.p_true = victim.p_true * 40.0
                state["victim"] = len(reqs) // 2
            return reqs

        monkeypatch.setattr(sweep_mod, "make_workload", crooked)
        rep = triage_cell(SweepCell(**self.CELL_SPEC))
        assert rep is not None
        # the report names a real lifecycle event; the perturbation makes
        # the victim (or a call queued behind it) diverge in time/ordering
        assert rep.kind in CANONICAL_KINDS
        assert rep.fld in ("t", "node", "count", "attempt")

    def test_baseline_has_no_triage(self):
        cell = SweepCell(policy="baseline", nodes=1, cores=4, intensity=10,
                         seed=0)
        assert triage_cell(cell) is None

    def test_mismatch_error_carries_report(self, monkeypatch):
        from repro.core import sweep as sweep_mod
        from repro.core.flight import DivergenceReport

        fake = DivergenceReport(1.0, "dispatch", 3, "node", 0, 1)
        monkeypatch.setattr(sweep_mod, "triage_cell",
                            lambda cell, rtol=None: fake)
        err = sweep_mod._mismatch(SweepCell(**self.CELL_SPEC), 1e-2, "boom")
        assert err.report is fake
        assert "first divergence" in str(err)
        assert "kind=dispatch" in str(err)
