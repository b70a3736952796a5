"""The jnp event loop stops once every cell of a chunk is idle (CPU).

Contracts under test:

* a cell's ``start``, ``finish``, ``prio``, ``node`` and every summary
  counter are bit-identical whether it runs alone, batched with a cell twice
  as long (so it sits idle while the loop goes on), or in a bucket with a
  larger step budget -- in every feature family the loop runs: float32 and
  float64 pull, the cold count path, the container table (pull, and push
  with its same-instant continuation), kills with the autoscaler, steal and
  duplicate hedging, and resilience with retries;
* the loop ends where a fixed-length scan of the same step would stop
  changing anything: its results and final carry equal that scan's, except
  the ``stepc``/``stp`` push stamps that idle steps advance;
* a stream replays bit-identically when every chunk runs with a larger
  budget, chunk by chunk down to the final carry planes;
* an optimistic budget that falls short is still detected and the chunk
  re-run at the strict bound.
"""

from functools import partial

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from repro.core import fastpath as fp  # noqa: E402
from repro.core import streamscan  # noqa: E402
from repro.core.cluster import ClusterDynamics  # noqa: E402
from repro.core.resilience import (  # noqa: E402
    AdmissionPolicy,
    ResilienceSpec,
    RetryPolicy,
    TimeoutSpec,
)
from repro.core.stragglers import HedgingSpec, NodeSpeedProfile  # noqa: E402
from repro.core.workload import generate_burst  # noqa: E402

SLOW_NODE = NodeSpeedProfile(episodes=((0, 1.0, 300.0, 6.0),))
RETRIES = ResilienceSpec(
    timeout=TimeoutSpec(multiple=3.0, floor_s=2.0),
    retry=RetryPolicy(max_attempts=3, mode="backoff", base_delay_s=0.5,
                      cap_delay_s=4.0, jitter=0.5),
    admission=AdmissionPolicy(threshold_s=1.0))
TIGHT = dict(memory_mb=2560, container_mb=256)

# one case a family: the fleet, the policy and the feature specs of a
# cluster cell; ``intensity`` sizes its burst
FAMILIES = {
    "f32_pull": dict(nodes=2, cores=2, policy="sept"),
    "f64_pull": dict(nodes=2, cores=2, policy="fc",
                     profile=NodeSpeedProfile(speeds=(0.5, 1.0))),
    "cold_counts": dict(nodes=2, cores=2, policy="sept", warm=False),
    "cold_table": dict(nodes=2, cores=2, policy="sept", warm=False,
                       intensity=20, **TIGHT),
    "kills_autoscale": dict(
        nodes=3, cores=2, policy="sept",
        dynamics=ClusterDynamics(fail=((0, 8.0),), autoscale=True,
                                 scale_up_queue_per_slot=1.0,
                                 provision_delay_s=2.0, max_nodes=5)),
    "steal_hedging": dict(nodes=2, cores=2, policy="fc", assignment="push",
                          profile=SLOW_NODE,
                          hedging=HedgingSpec(multiple=2.0)),
    "duplicate_hedging": dict(nodes=2, cores=2, policy="fc",
                              assignment="push", profile=SLOW_NODE,
                              hedging=HedgingSpec(multiple=2.0,
                                                  mode="duplicate")),
    "retries": dict(nodes=2, cores=2, policy="fc", assignment="push",
                    resilience=RETRIES),
    "table_continuation": dict(nodes=2, cores=4, policy="fifo",
                               assignment="push", warm=False, intensity=20,
                               **TIGHT),
}


def _cell(family: str, scale: int = 1, seed: int = 0) -> fp._ScanCell:
    """The family's cell on a burst of ``scale`` times its calls."""
    kw = dict(FAMILIES[family])
    nodes, cores = kw.pop("nodes"), kw.pop("cores")
    intensity = kw.pop("intensity", 10) * scale
    memory_mb = kw.pop("memory_mb", fp.CLUSTER_MEMORY_MB)
    container_mb = kw.pop("container_mb", fp.CLUSTER_CONTAINER_MB)
    reqs = generate_burst(cores=nodes * cores, intensity=intensity,
                          seed=seed, duration_s=20.0)
    item = (reqs, nodes, cores, kw.pop("policy"),
            kw.pop("assignment", "pull"), "least_loaded",
            kw.pop("dynamics", None), kw.pop("profile", None),
            kw.pop("hedging", None), kw.pop("warm", True),
            kw.pop("resilience", None))
    assert not kw, kw
    return fp._cluster_cell(item, fp._feats_cache(), memory_mb,
                            container_mb, validate=True)


def _merged(*keys) -> tuple:
    """A bucket key that holds every cell of ``keys``: one feature mask,
    each size field the largest."""
    assert len({k[0] for k in keys}) == 1
    return (keys[0][0],) + tuple(max(f) for f in zip(*(k[1:] for k in keys)))


def _assert_same(got, want, n: int, where: str) -> None:
    """Bit-identical per-request rows ``[:n]`` and summary counters."""
    if isinstance(want, dict) or want is None:
        assert (got is None) == (want is None), where
        if want is not None:
            assert set(got) == set(want), where
            for k in want:
                _assert_same(got[k], want[k], n, f"{where}: {k}")
        return
    a, b = np.asarray(got), np.asarray(want)
    if a.ndim and max(len(a), len(b)) > n:
        a, b = a[:n], b[:n]         # per-request, padded to each bucket
    assert a.dtype == b.dtype, where
    np.testing.assert_array_equal(a, b, err_msg=where)


def _assert_rows(got: tuple, want: tuple, n: int, where: str) -> None:
    for name, a, b in zip(("start", "finish", "prio", "node", "extras"),
                          got, want):
        _assert_same(a, b, n, f"{where}: {name}")


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_cell_outputs_do_not_depend_on_the_loop_length(family):
    cell = _cell(family)
    long = _cell(family, scale=2, seed=1)
    n = len(cell.feats.t)
    assert len(long.feats.t) == 2 * n
    key = cell.bucket()
    alone = fp._run_scan_bucket(key, [cell])[0]
    batched = fp._run_scan_bucket(_merged(key, long.bucket()),
                                  [cell, long])[0]
    roomy = fp._run_scan_bucket(key[:-1] + (2 * key[-1] + 256,), [cell])[0]
    _assert_rows(batched, alone, n, f"{family}, batched with a longer cell")
    _assert_rows(roomy, alone, n, f"{family}, larger budget")
    fp.scan_cache_clear()


def _fixed_length_scan(clk, ctr, inp, *, n_steps, **static):
    """The step scanned for its whole budget in every cell, idle steps
    included: the loop without its early stop."""
    if static["dup"]:
        inp = dict(inp, **{k: jnp.tile(inp[k], (1, static["n_copies"]))
                           for k in fp._COPY_TILED})

    def cell(clk, ctr, inp):
        layout, pick, step = fp._cell_step(inp, **static)

        def body(planes, _):
            st = layout.unpack(*planes)
            nxt, rec = step(st, pick(st))
            return layout.pack(nxt), rec

        return jax.lax.scan(body, (clk, ctr), None, length=n_steps)

    return jax.vmap(cell)(clk, ctr, inp)


@pytest.mark.parametrize("family", ["cold_table", "steal_hedging",
                                    "retries"])
def test_loop_equals_a_fixed_length_scan_but_for_the_stamps(
        family, monkeypatch):
    # a short and a long cell: the loop runs until the long one's last
    # event, the fixed-length scan its whole budget
    cells = [_cell(family), _cell(family, scale=2, seed=1)]
    key = _merged(*(c.bucket() for c in cells))
    n_b = key[1]
    state_kw, step_kw = fp._runner_kwargs(key)
    inp = fp._fill_bucket_inputs(key, cells, 2)
    # the loop's final planes and raw records, in place of the summary
    monkeypatch.setattr(fp, "_cell_summary",
                        lambda clk, ctr, recs, inp, **_: ((clk, ctr), recs,
                                                          {}))
    with fp._x64_ctx(fp._use64(fp._mask_features(key[0]))):
        arrs = {k: jnp.asarray(v) for k, v in inp.items()}
        planes = jax.vmap(partial(fp._make_planes, **state_kw))(arrs)
        layout = fp._carry_layout({k: v[0] for k, v in arrs.items()},
                                  **state_kw)
        (clk, ctr), recs, info = jax.jit(
            partial(fp._event_loop, **step_kw))(*planes, arrs)
        (clk_f, ctr_f), recs_f = jax.jit(
            partial(_fixed_length_scan, **step_kw))(*planes, arrs)
    j, j_f = np.asarray(recs[0]), np.asarray(recs_f[0])
    ran = j < n_b
    assert 0 < int(info["steps"]) < step_kw["n_steps"]
    assert not ran[:, int(info["steps"]):].any()
    np.testing.assert_array_equal(j, j_f)
    for a, b in zip(recs[1:], recs_f[1:]):
        np.testing.assert_array_equal(np.asarray(a)[ran], np.asarray(b)[ran])
    for b in range(2):
        got = layout.unpack(clk[b], ctr[b])
        want = layout.unpack(clk_f[b], ctr_f[b])
        for k in want:
            if k in ("stepc", "stp"):
                # idle steps advance them, and the loop skips those
                assert int(got[k]) < int(want[k]), k
                continue
            np.testing.assert_array_equal(np.asarray(got[k]),
                                          np.asarray(want[k]), err_msg=k)


def _stream_items():
    reqs = generate_burst(cores=4, intensity=30, seed=3, duration_s=20.0)
    return reqs, dict(nodes=2, cores_per_node=2, policy="fc",
                      assignment="push", resilience=RETRIES, chunk=32)


def _logging_runner(monkeypatch, log: list, extra: int = 0):
    """Wrap the stream's runner: every chunk runs ``extra`` more budget
    steps, and its final planes go to ``log``."""
    real = streamscan._scan_runner

    def runner(key, rec=None):
        key = key[:11] + (key[11] + extra,) + key[12:]
        init_c, scan_c = real(key, rec)

        def scan(clk, ctr, arrs):
            out = scan_c(clk, ctr, arrs)
            log.append((key, tuple(np.asarray(x) for x in out[0])))
            return out

        return init_c, scan

    monkeypatch.setattr(streamscan, "_scan_runner", runner)


def test_stream_chunks_do_not_depend_on_their_budget(monkeypatch):
    reqs, kw = _stream_items()
    runs = []
    for extra in (0, 512):
        log: list = []
        _logging_runner(monkeypatch, log, extra)
        stream, _ = streamscan.stream_from_requests(reqs)
        runs.append((streamscan.simulate_cluster_stream(stream, **kw), log))
        monkeypatch.undo()
    (got, got_log), (want, want_log) = runs[1], runs[0]
    assert want.chunks > 2 and got.chunks == want.chunks
    for name in ("start", "finish", "prio", "node", "attempts", "cold",
                 "failed", "resp"):
        np.testing.assert_array_equal(getattr(got, name),
                                      getattr(want, name), err_msg=name)
    assert got.counters == want.counters
    assert len(got_log) == len(want_log)
    for (gkey, gplanes), (wkey, wplanes) in zip(got_log, want_log):
        assert gkey[11] == wkey[11] + 512
        # one cell a chunk: its loop stops at its own last event whatever
        # the budget, so even the push stamps agree
        for a, b in zip(gplanes, wplanes):
            np.testing.assert_array_equal(a, b)
    fp.scan_cache_clear()


@pytest.mark.parametrize("family", ["steal_hedging", "table_continuation"])
def test_short_optimistic_budget_is_rerun_at_the_strict_bound(
        family, monkeypatch):
    # the burst's busiest calls, a power of two of them: the padded rows
    # leave no slack, so 2n steps cannot cover the hedge fires or the
    # continuations
    cell = _cell(family)
    n = fp._pow2(len(cell.feats.t)) // 2
    cell.requests = cell.requests[-n:]
    cell.feats = fp._arrival_features(cell.requests)
    want = fp._run_scan_bucket(cell.bucket(), [cell])[0]
    keys = []
    real = fp._scan_runner

    def runner(key, rec=None):
        keys.append(key)
        return real(key, rec)

    monkeypatch.setattr(fp, "_scan_runner", runner)
    fp.scan_timings_clear()
    got = fp._run_scan_bucket(cell.bucket()[:-1] + (0,), [cell])[0]
    exec_steps = fp.scan_phase_totals()["exec_steps"]
    assert len(keys) == 2 and keys[0][11] == 0
    assert keys[1][11] > 0                        # the strict re-run
    assert exec_steps > 2 * n                     # both runs counted
    _assert_rows(got, want, n, family)
    fp.scan_cache_clear()
