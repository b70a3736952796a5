"""Streaming chunked-scan replay: bounded-memory request tensors with an
explicit carry handoff across chunk boundaries.

Every scan-backend entry point in :mod:`repro.core.fastpath` pads the *whole*
request stream into one device tensor, so trace length bounds device memory.
This module splits a long arrival stream into bounded chunks and threads the
full kernel carry -- slots, queues, estimator rings, FC count rings,
container counts, resilience/hedge watch slots -- across chunk boundaries:

* the kernel runs with ``stream=True`` (the ``stream`` carry segment), which
  gates every step on a per-chunk horizon ``t_stop`` and reads its pull
  queues through chunk-local CSR event lists instead of the dense
  per-function table;
* at each boundary the final carry planes come back to the host, every
  request still in flight (running, queued, pending re-arrival / retry
  backoff / hedge watch) is re-materialized into the next chunk's row space
  -- priorities, push-sequence (``qseq``/``qsq``) and dispatch-sequence
  (``dseq``) carries intact -- and everything else (clocks, rings, counters)
  is copied verbatim;
* precomputed static-stream features become chunk-local with cross-chunk
  prefix state: FC pull window counts stay a cumulative-count +
  ``searchsorted`` difference because every arrival still inside the sliding
  window is re-materialized as an inert *history row*, and the RECT
  previous-arrival feature needs nothing at all (the kernel carries
  ``last_t``/``prev_t``).

Peak device memory is O(chunk), independent of trace length, and the replay
is *event-for-event identical* to the single-shot scan: each chunk's first
event re-evaluates the same candidate stack the unchunked kernel would, so
boundary ties resolve with identical precedence, exact counters are
bit-identical and clocks agree to the documented cross-check tolerance
(bitwise, in practice, since every event computes from identical state).
"""

from __future__ import annotations

import math
import time
from dataclasses import dataclass
from typing import Callable, Iterable, Iterator

import numpy as np

from .estimator import DEFAULT_FC_HORIZON, DEFAULT_WINDOW
from .fastpath import (
    CLUSTER_CONTAINER_MB,
    CLUSTER_MEMORY_MB,
    POLICY_NAMES,
    _POLICY_COEF,
    _PULL_COEF,
    _PULL_COEF_DYN,
    _alloc_bucket_inputs,
    _bucket_bytes,
    _carry_layout,
    _cold_regime_ok,
    _feature_mask,
    _mask_features,
    _pow2,
    _scan_runner,
    _use64,
    _x64_ctx,
)
from .simulator import (
    OURS_BASE,
    OURS_SCALE,
    REQ_OVERHEAD_S,
    RESP_OVERHEAD_S,
    WEIGHT_CAP_S,
    container_weight,
)
from .workload import PROFILES, STRETCH_REFERENCE_S

__all__ = [
    "ArrivalStream",
    "StreamChunk",
    "StreamBudgetError",
    "StreamResult",
    "simulate_cluster_stream",
    "stream_from_requests",
    "stream_supported",
]


# ---------------------------------------------------------------------------
# stream protocol
# ---------------------------------------------------------------------------
@dataclass
class StreamChunk:
    """One slab of arrivals: client submit times (globally non-decreasing
    across the whole stream), function ids into the stream's fixed table,
    and true processing times."""

    r: np.ndarray
    fn: np.ndarray
    p: np.ndarray

    def __post_init__(self):
        self.r = np.asarray(self.r, dtype=np.float64)
        self.fn = np.asarray(self.fn, dtype=np.int64)
        self.p = np.asarray(self.p, dtype=np.float64)


@dataclass
class ArrivalStream:
    """A lazily-generated arrival stream: a fixed function-name table plus an
    iterable of :class:`StreamChunk` slabs in time order.  ``chunks`` may be
    a zero-arg factory returning a fresh iterator, which makes the stream
    re-playable (the memory-evidence runs replay the same stream twice)."""

    fns: tuple
    chunks: Iterable[StreamChunk] | Callable[[], Iterator[StreamChunk]]
    total: int | None = None

    def iter_chunks(self) -> Iterator[StreamChunk]:
        c = self.chunks
        return iter(c() if callable(c) else c)


def stream_from_requests(requests, chunk: int = 4096):
    """Wrap a materialized request list as an :class:`ArrivalStream`.

    Reproduces :func:`repro.core.fastpath._arrival_features` event ordering
    exactly (receive time ``r + REQ_OVERHEAD_S``, stable sort), so the
    returned ``order`` maps event index -> request index for cross-checking
    against the single-shot scan.  Returns ``(stream, order)``."""
    n = len(requests)
    r = np.array([q.r for q in requests], dtype=np.float64)
    order = np.argsort(r + REQ_OVERHEAD_S, kind="stable")
    fns = tuple(sorted({q.fn for q in requests}))
    fn_index = {f: i for i, f in enumerate(fns)}
    fn_ids = np.array([fn_index[requests[i].fn] for i in order],
                      dtype=np.int64)
    p = np.array([requests[i].p_true for i in order], dtype=np.float64)
    rs = r[order]

    def _gen():
        for lo in range(0, n, max(chunk, 1)):
            hi = min(lo + max(chunk, 1), n)
            yield StreamChunk(r=rs[lo:hi], fn=fn_ids[lo:hi], p=p[lo:hi])

    return ArrivalStream(fns=fns, chunks=_gen, total=n), order


class StreamBudgetError(RuntimeError):
    """A chunk failed to drain below its horizon even at the retry-doubled
    step budget cap -- a kernel budget bug, never a workload property."""


def stream_supported(
    *,
    policy: str = "fc",
    assignment: str = "pull",
    lb: str = "least_loaded",
    warm: bool = True,
    dynamics=None,
    profile=None,
    hedging=None,
    resilience=None,
) -> bool:
    """Flags-only eligibility for the chunked-stream path: the scan kernel's
    feature envelope (see :func:`~repro.core.fastpath.cluster_scan_eligible`)
    minus duplicate-mode hedging, whose racing-copy queue width has no
    incremental re-materialization (copies of one request span chunk
    boundaries asymmetrically), so those cells stay on the single-shot
    path."""
    if policy not in POLICY_NAMES:
        return False
    if assignment == "push":
        if lb not in ("least_loaded", "home"):
            return False
    elif assignment != "pull":
        return False
    dyn = dynamics is not None and not dynamics.is_static
    if resilience is not None and not resilience.is_null:
        if (assignment != "push" or not warm or dyn
                or hedging is not None
                or (profile is not None and not profile.is_uniform)):
            return False
    if hedging is not None:
        if hedging.mode != "steal":
            return False             # duplicate racing: single-shot only
        if assignment == "push" and lb != "least_loaded" and dyn:
            return False
    if dyn:
        if assignment == "push" and lb != "least_loaded":
            return False
    return True


# ---------------------------------------------------------------------------
# tie-safe rebatcher
# ---------------------------------------------------------------------------
def _batches(stream: ArrivalStream, hint):
    """Re-slice a stream into kernel batches of ~``hint`` events whose
    horizon ``t_stop`` falls strictly *between* event times: the cut point
    only ever lands where ``t[cut-1] < t[cut]``, so equal-time runs never
    straddle a boundary and the chunk horizon gate (``now >= t_stop``) can
    never split a tie the unchunked kernel would have resolved in one
    candidate-stack evaluation.  ``hint`` is either a fixed event count or
    a zero-arg callable sampled once per batch, which lets the driver
    shrink the fresh slice when carried rows already fill the compiled
    shape.  Yields ``(t, fn, p, t_stop, final)`` with ``t`` the invoker
    receive times (``r + REQ_OVERHEAD_S``)."""
    def _target() -> int:
        return max(int(hint() if callable(hint) else hint), 1)

    it = stream.iter_chunks()
    bt: list[np.ndarray] = []
    bf: list[np.ndarray] = []
    bp: list[np.ndarray] = []
    nbuf = 0
    done = False
    last_t = -np.inf
    target = _target()
    want = target
    while True:
        while not done and nbuf <= want:
            try:
                c = next(it)
            except StopIteration:
                done = True
                break
            if len(c.r) == 0:
                continue
            t = c.r + REQ_OVERHEAD_S
            if t[0] < last_t or np.any(np.diff(t) < 0):
                raise ValueError("stream arrival times must be sorted")
            last_t = float(t[-1])
            bt.append(t)
            bf.append(np.asarray(c.fn, dtype=np.int64))
            bp.append(c.p)
            nbuf += len(t)
        if nbuf == 0:
            return
        t = np.concatenate(bt)
        fn = np.concatenate(bf)
        p = np.concatenate(bp)
        if done:
            yield t, fn, p, np.inf, True
            return
        cut = min(target, nbuf - 1)
        while cut < nbuf and t[cut] == t[cut - 1]:
            cut += 1
        if cut >= nbuf:
            # the tie run reaches the buffer end: pull more before cutting
            bt, bf, bp = [t], [fn], [p]
            want = nbuf                  # force another pull
            continue
        yield t[:cut], fn[:cut], p[:cut], float(t[cut]), False
        bt, bf, bp = [t[cut:]], [fn[cut:]], [p[cut:]]
        nbuf -= cut
        target = _target()
        want = target


# ---------------------------------------------------------------------------
# numpy plane (un)packing -- the host side of _PlaneLayout
# ---------------------------------------------------------------------------
def _np_pack(layout, st: dict, fdt):
    clk = (np.concatenate([np.ravel(np.asarray(st[k], dtype=fdt))
                           for k, _, _, _ in layout.fparts])
           if layout.fparts else np.zeros(0, dtype=fdt))
    ctr = (np.concatenate([np.ravel(np.asarray(st[k])).astype(np.int32)
                           for k, _, _, _, _ in layout.iparts])
           if layout.iparts else np.zeros(0, dtype=np.int32))
    return clk, ctr


def _np_unpack(layout, clk: np.ndarray, ctr: np.ndarray) -> dict:
    st = {}
    for k, lo, hi, shape in layout.fparts:
        st[k] = np.array(clk[lo:hi]).reshape(shape)
    for k, lo, hi, shape, isbool in layout.iparts:
        v = np.array(ctr[lo:hi]).reshape(shape)
        st[k] = v.astype(bool) if isbool else v
    return st


# every carry entry indexed by *local request row* -- the handoff relocates
# these (defaults for fresh rows, old values scattered onto carried rows);
# everything else in the carry copies across the boundary verbatim
_PER_REQUEST_KEYS = (
    "pend", "fprio", "node_of", "coldq", "hedge_t", "att", "stolen", "qseq",
    "unhedge", "hedge_t2", "rearr", "rord", "xq", "rq_rt", "enq_t",
    "to_t", "rto", "eps", "ratt", "nfl", "fcz", "qsq",
)
_PRK_INF = frozenset({"hedge_t", "hedge_t2", "rearr", "to_t", "rto"})
_PRK_BOOL = frozenset({"pend", "coldq", "stolen", "unhedge", "xq", "nfl"})
_PRK_INT = frozenset({"node_of", "att", "qseq", "rord", "ratt", "fcz",
                      "qsq"})


# ---------------------------------------------------------------------------
# growable per-event accumulator (indexed by global event id)
# ---------------------------------------------------------------------------
class _Acc:
    __slots__ = ("n", "cap", "t", "fnid", "p", "cnt", "start", "finish",
                 "prio", "node", "att", "stolen", "cold", "fcz", "ratt")

    def __init__(self, cap: int = 1024):
        cap = max(int(cap), 16)
        self.n = 0
        self.cap = cap
        self.t = np.zeros(cap)
        self.fnid = np.zeros(cap, dtype=np.int64)
        self.p = np.zeros(cap)
        self.cnt = np.zeros(cap, dtype=np.int64)
        self.start = np.full(cap, np.nan)
        self.finish = np.full(cap, np.nan)
        self.prio = np.zeros(cap)
        self.node = np.zeros(cap, dtype=np.int64)
        self.att = np.zeros(cap, dtype=np.int64)
        self.stolen = np.zeros(cap, dtype=bool)
        self.cold = np.zeros(cap, dtype=bool)
        self.fcz = np.zeros(cap, dtype=np.int8)
        self.ratt = np.zeros(cap, dtype=np.int64)

    def grow(self, need: int) -> None:
        if need <= self.cap:
            return
        new = max(need, 2 * self.cap)
        for k in self.__slots__[2:]:
            old = getattr(self, k)
            arr = np.zeros(new, dtype=old.dtype)
            if old.dtype == np.float64 and k in ("start", "finish"):
                arr[:] = np.nan
            arr[: self.cap] = old
            setattr(self, k, arr)
        self.cap = new


# ---------------------------------------------------------------------------
# result container
# ---------------------------------------------------------------------------
@dataclass
class StreamResult:
    """Per-event outcome of a chunked replay, in global event order, plus the
    exact counters the single-shot scan reports.  ``failed`` is 0 for served
    events, 1 for resilience timeouts, 2 for sheds (those rows have NaN
    ``start``/``finish``/``resp``)."""

    fns: tuple
    t: np.ndarray
    fnid: np.ndarray
    p: np.ndarray
    start: np.ndarray
    finish: np.ndarray
    prio: np.ndarray
    node: np.ndarray
    attempts: np.ndarray
    cold: np.ndarray
    failed: np.ndarray
    resp: np.ndarray
    stretch: np.ndarray
    counters: dict
    nodes_used: int
    timeline: object | None
    n: int
    chunks: int
    peak_rows: int
    peak_bytes: int
    wall_s: float

    def summary(self) -> dict:
        ok = self.failed == 0
        resp = self.resp[ok]
        out = {
            "n": self.n,
            "served": int(ok.sum()),
            "chunks": self.chunks,
            "peak_rows": self.peak_rows,
            "peak_bytes": self.peak_bytes,
            "wall_s": self.wall_s,
            "rate": self.n / self.wall_s if self.wall_s > 0 else 0.0,
            "nodes_used": self.nodes_used,
        }
        if resp.size:
            out.update(mean_resp=float(resp.mean()),
                       p50=float(np.percentile(resp, 50)),
                       p99=float(np.percentile(resp, 99)),
                       mean_stretch=float(self.stretch[ok].mean()))
        out.update(self.counters)
        return out

    def write_back(self, requests, order) -> None:
        """Scatter per-event outcomes back onto ``requests`` with the exact
        :func:`~repro.core.fastpath._run_scan_cells` write-back semantics
        (``order`` from :func:`stream_from_requests`)."""
        for e, ridx in enumerate(np.asarray(order).tolist()):
            req = requests[ridx]
            req.node = f"node{int(self.node[e])}"
            req.r_prime = float(self.t[e])
            req.priority = float(self.prio[e])
            req.cold_start = bool(self.cold[e])
            if int(self.failed[e]):
                req.start = req.finish = req.c = None
                req.failed = ("timeout" if int(self.failed[e]) == 1
                              else "shed")
                req.attempts = max(int(self.ratt_minus_one(e)), 0)
                continue
            req.start = float(self.start[e])
            req.finish = float(self.finish[e])
            req.c = req.finish + RESP_OVERHEAD_S
            req.failed = None
            req.attempts = int(self.attempts[e])

    def ratt_minus_one(self, e: int) -> int:
        return int(self.attempts[e])

    def trace(self, order=None) -> "SimTrace":
        """Canonical flight-recorder stream (arrival/dispatch/complete/fail)
        reconstructed straight from the event-order output arrays — same
        schema as :func:`repro.core.flight.trace_from_result`, so a
        streamed replay is comparable event-for-event against a traced
        reference or single-shot scan run.  ``order`` (the permutation from
        ``stream_from_requests``) labels events with their original request
        index; without it the event position is the request id."""
        import math as _math

        from .flight import SimTrace, TraceEvent, _KIND_RANK

        ids = (np.asarray(order).tolist() if order is not None
               else list(range(self.n)))
        events = []
        for e in range(self.n):
            rid = int(ids[e])
            fn = self.fns[int(self.fnid[e])]
            att = max(int(self.attempts[e]), 0)
            events.append(TraceEvent(float(self.t[e]), "arrival", rid, -1,
                                     fn, 0))
            if int(self.failed[e]):
                cause = "timeout" if int(self.failed[e]) == 1 else "shed"
                events.append(TraceEvent(float("nan"), "fail", rid,
                                         int(self.node[e]), fn, att, cause))
                continue
            info = "cold" if bool(self.cold[e]) else ""
            events.append(TraceEvent(float(self.start[e]), "dispatch", rid,
                                     int(self.node[e]), fn, att, info))
            events.append(TraceEvent(float(self.finish[e]), "complete", rid,
                                     int(self.node[e]), fn, att))
        events.sort(key=lambda ev: (_math.inf if _math.isnan(ev.t) else ev.t,
                                    _KIND_RANK.get(ev.kind, 99), ev.req))
        return SimTrace(events=events, nodes=self.nodes_used,
                        meta={"backend": "streamscan", "chunks": self.chunks,
                              "canonical": True})


# ---------------------------------------------------------------------------
# the chunked replay driver
# ---------------------------------------------------------------------------
def _fn_tables(fns, nodes):
    """Per-function constants reused every chunk: channel cost (NaN for
    unprofiled names, resolved per-row from ``p``), the §V-A warm-seed
    median, and the home-routing hash."""
    from .traces import stable_hash

    nf = len(fns)
    cost = np.full(nf, np.nan)
    wseed = np.full(nf, 0.1)
    for i, f in enumerate(fns):
        if f in PROFILES:
            cost[i] = OURS_BASE + OURS_SCALE * container_weight(f, np.nan)
            wseed[i] = PROFILES[f].median_s
    home = np.array([stable_hash(f) for f in fns], dtype=np.int64) % max(
        nodes, 1)
    sref = np.array([STRETCH_REFERENCE_S.get(f) or np.nan for f in fns])
    return cost, wseed, home, sref


def _row_cost(fn_ids, p, fn_cost):
    c = fn_cost[fn_ids]
    unk = np.isnan(c)
    if unk.any():
        c = np.where(unk,
                     OURS_BASE + OURS_SCALE * np.minimum(p, WEIGHT_CAP_S),
                     c)
    return c


class _FcWindow:
    """Cross-chunk prefix state for the FC sliding-window features: every
    arrival still inside ``(t_stop - horizon, t_stop]`` with its function id
    and global event id.  Serves three consumers -- history rows for the
    pull-FC cumulative-count difference, per-arrival window counts for the
    freeze single-node static-FC ``cnt`` feature, and the running max window
    count that sizes the push-FC rings."""

    def __init__(self, horizon: float):
        self.horizon = horizon
        self.t = np.zeros(0)
        self.fn = np.zeros(0, dtype=np.int64)
        self.gid = np.zeros(0, dtype=np.int64)
        self.max_count = 0

    def counts(self, t, fn) -> np.ndarray:
        """#(fn, (t_i - horizon, t_i]] including the arrival itself, for a
        fresh batch, against buffer + batch (exactly the unchunked global
        count: anything older than the buffer is outside every window)."""
        out = np.zeros(len(t), dtype=np.int64)
        all_t = np.concatenate([self.t, t])
        all_fn = np.concatenate([self.fn, fn])
        tags = np.concatenate([np.full(len(self.t), -1),
                               np.arange(len(t))])
        for f in np.unique(fn):
            sel = all_fn == f
            tf = all_t[sel]
            tg = tags[sel]
            fresh = tg >= 0
            lo = np.searchsorted(tf, tf[fresh] - self.horizon, side="right")
            out[tg[fresh]] = np.arange(1, tf.size + 1)[fresh] - lo
        if out.size:
            self.max_count = max(self.max_count, int(out.max()))
        return out

    def push(self, t, fn, gid, t_stop: float) -> None:
        self.t = np.concatenate([self.t, t])
        self.fn = np.concatenate([self.fn, fn])
        self.gid = np.concatenate([self.gid, gid])
        if np.isfinite(t_stop):
            keep = self.t > t_stop - self.horizon
            self.t, self.fn = self.t[keep], self.fn[keep]
            self.gid = self.gid[keep]

    def hist(self, live_gids: np.ndarray):
        """Window arrivals *not* re-materialized as live rows: these become
        inert history rows (never dispatched, never queued) that keep the
        chunk-local cumulative counts window-complete."""
        if not self.gid.size:
            return self.t, self.fn, self.gid
        drop = np.isin(self.gid, live_gids)
        keep = ~drop
        return self.t[keep], self.fn[keep], self.gid[keep]


def simulate_cluster_stream(
    stream: ArrivalStream,
    *,
    nodes: int,
    cores_per_node: int = 18,
    policy: str = "fc",
    assignment: str = "pull",
    lb: str = "least_loaded",
    warm: bool = True,
    memory_mb: int = CLUSTER_MEMORY_MB,
    container_mb: int = CLUSTER_CONTAINER_MB,
    dynamics=None,
    profile=None,
    hedging=None,
    resilience=None,
    chunk: int = 8192,
    progress: Callable[[int, int, float], None] | None = None,
) -> StreamResult:
    """Replay an :class:`ArrivalStream` through the chunked scan kernel with
    O(chunk) peak device memory.  ``chunk`` is the padded-row budget per
    kernel launch: each batch's fresh slice is sized adaptively so carried
    backlog + history + fresh arrivals fill one compiled power-of-two row
    shape (see ``_fresh_target``).  Semantics are identical to
    :func:`~repro.core.fastpath.simulate_cluster_scan` on streams that fit
    both ways: exact counters bit-identical, clocks within the documented
    cross-check tolerance (bitwise in practice -- every event computes from
    identical carried state)."""
    import jax.numpy as jnp

    t_begin = time.perf_counter()
    if not stream_supported(policy=policy, assignment=assignment, lb=lb,
                            warm=warm, dynamics=dynamics, profile=profile,
                            hedging=hedging, resilience=resilience):
        raise ValueError(
            "chunked stream path requires the scan kernel's feature "
            f"envelope minus duplicate hedging (policy={policy!r}, "
            f"assignment={assignment!r}, lb={lb!r}, warm={warm}, "
            f"dynamics={dynamics!r}, hedging={hedging!r}, "
            f"resilience={resilience!r})")
    if not warm:
        # _cold_regime_ok reads only the distinct function count off the
        # request list -- the stream knows its table upfront
        class _F:
            __slots__ = ("fn",)

            def __init__(self, fn):
                self.fn = fn

        if not _cold_regime_ok([_F(f) for f in stream.fns],
                               cores_per_node, memory_mb, container_mb):
            # memory-bound single-shot cells carry a per-node container
            # table (fastpath pool_w > 0); the chunk handoff does not carry
            # one across chunk boundaries yet
            raise ValueError(
                "warm=False stream outside the ample-memory prewarm regime: "
                "memory-bound nodes need the container table, and the "
                "chunk handoff does not carry a table from one chunk to "
                "the next")
    dyn = dynamics is not None and not dynamics.is_static
    het = profile is not None and not profile.is_uniform
    hedge = hedging is not None and assignment == "push"
    res = resilience is not None and not resilience.is_null
    cold = not warm
    freeze = assignment != "pull"
    use_fc = (not freeze) and policy == "fc"
    fc_push = (freeze and policy == "fc"
               and (nodes > 1 or dyn or hedge or res))
    fc_static = freeze and policy == "fc" and not fc_push
    node_cap = (dynamics.capacity_bound(nodes)
                if dynamics is not None else nodes)
    if dyn and dynamics.fail:
        failed = {idx for idx, _ in dynamics.fail}
        if (max(failed) >= nodes or len(failed) >= nodes or nodes < 2
                or any(at < 0 for _, at in dynamics.fail)):
            raise ValueError("failure schedule outside the scan envelope")
    if profile is not None and len(profile.speeds) > node_cap:
        raise ValueError("speed profile longer than the capacity bound")

    fns = tuple(stream.fns)
    nf = len(fns)
    nodes_b = _pow2(node_cap)
    slots_b = _pow2(cores_per_node)
    f_b = _pow2(max(nf, 1))
    window = DEFAULT_WINDOW
    n_ep = _pow2(max(1, len(profile.episodes))) if het else 1
    fc_mult = 1
    if hedge:
        fc_mult = 1 + int(hedging.max_backups)
    if res:
        fc_mult = max(fc_mult, int(resilience.max_attempts))
    mask = _feature_mask(freeze=freeze, use_fc=use_fc, fc_push=fc_push,
                         cold=cold, hedge=hedge, dup=False, het=het,
                         dyn=dyn, res=res, stream=True)
    flags = _mask_features(mask)
    use64 = _use64(flags)
    fdt = np.float64 if use64 else np.float32

    fn_cost, fn_wseed, fn_home, fn_sref = _fn_tables(fns, nodes)
    seed_n = min(cores_per_node, window)
    coef = np.zeros(5)
    if not freeze:
        coef[:5 if dyn else 4] = (_PULL_COEF_DYN[policy] if dyn
                                  else _PULL_COEF[policy])
    else:
        coef[:4] = _POLICY_COEF[policy]
    killt_spec = np.full(nodes_b, np.inf)
    dynp = np.zeros(5)
    if dyn:
        d = dynamics
        for idx, at in d.fail:
            killt_spec[idx] = min(killt_spec[idx], at)
        dynp[:] = (d.autoscale_interval_s, d.scale_up_queue_per_slot,
                   d.provision_delay_s, d.failure_detect_s,
                   1.0 if d.autoscale else 0.0)
    het_arrays = profile.arrays(nodes_b, n_ep) if het else None
    res_arrays = resilience.arrays() if res else None

    fcw = _FcWindow(DEFAULT_FC_HORIZON) if policy == "fc" else None
    acc = _Acc()
    n_b = 0
    fc_ring = 1
    xtra = 0
    layout = None
    layout_key = None
    peak_rows = 0
    peak_bytes = 0
    gid_next = 0
    chunks_run = 0
    prev = None                      # boundary handoff state
    final_st = None
    max_attempts_res = int(resilience.max_attempts) if res else 1

    row_budget = _pow2(max(int(chunk), 1))
    fresh_floor = max(row_budget // 8, 1)

    def _fresh_target() -> int:
        # Adaptive batching: ``chunk`` is a padded-row budget, not a fixed
        # fresh-event count.  Size the fresh slice so history + carried
        # live rows + fresh events together fill the current compiled row
        # shape instead of straddling the next power-of-two boundary --
        # under a steady backlog a fixed fresh count pays ~2x padding on
        # every chunk.  The floor keeps forward progress through bursts
        # whose carry alone exceeds the budget (the shape then grows
        # sticky, and the budget ratchets with it).
        budget = max(row_budget, n_b)
        carried = 0
        if prev is not None:
            carried += int(prev["live"].size)   # index array, not a mask
        if use_fc and fcw is not None:
            carried += int(fcw.gid.size)   # upper bound on history rows
        return max(budget - carried, fresh_floor)

    for bt, bfn, bp, t_stop, final in _batches(stream, _fresh_target):
        n_fresh = len(bt)
        fresh_gid = np.arange(gid_next, gid_next + n_fresh, dtype=np.int64)
        fresh_cnt = (fcw.counts(bt, bfn) if fcw is not None
                     else np.zeros(n_fresh, dtype=np.int64))

        # ---- merge rows: history + carried live + fresh, gid order -------
        if prev is not None:
            lv = prev["live"]
            c_gid = prev["gid"][lv]
            c_t, c_fn = prev["t"][lv], prev["fn"][lv]
            c_p, c_cost = prev["p"][lv], prev["cost"][lv]
            c_cnt = prev["cnt"][lv]
        else:
            c_gid = np.zeros(0, dtype=np.int64)
            c_t = c_p = c_cost = np.zeros(0)
            c_fn = np.zeros(0, dtype=np.int64)
            c_cnt = np.zeros(0, dtype=np.int64)
        if use_fc and fcw is not None:
            h_t, h_fn, h_gid = fcw.hist(c_gid)
        else:
            h_t = np.zeros(0)
            h_fn = h_gid = np.zeros(0, dtype=np.int64)
        acc.grow(gid_next + n_fresh)
        acc.t[fresh_gid] = bt
        acc.fnid[fresh_gid] = bfn
        acc.p[fresh_gid] = bp
        acc.cnt[fresh_gid] = fresh_cnt
        fresh_cost = _row_cost(bfn, bp, fn_cost)

        all_gid = np.concatenate([h_gid, c_gid, fresh_gid])
        morder = np.argsort(all_gid, kind="stable")
        row_gid_rows = all_gid[morder]
        row_t = np.concatenate([h_t, c_t, bt])[morder]
        row_fn = np.concatenate([h_fn, c_fn, bfn])[morder]
        row_p = np.concatenate([np.zeros(len(h_t)), c_p, bp])[morder]
        row_cost = np.concatenate(
            [np.zeros(len(h_t)), c_cost, fresh_cost])[morder]
        row_cnt = np.concatenate(
            [np.zeros(len(h_t), dtype=np.int64), c_cnt,
             fresh_cnt])[morder]
        kind = np.concatenate(
            [np.zeros(len(h_t), dtype=np.int8),
             np.ones(len(c_gid), dtype=np.int8),
             np.full(n_fresh, 2, dtype=np.int8)])[morder]
        n_rows = len(row_t)
        is_hist = kind == 0
        ai0 = int(len(h_t) + len(c_gid))   # hist+carried all precede fresh

        # ---- sticky shape growth ----------------------------------------
        n_b = max(n_b, _pow2(max(n_rows, 1)))
        if fc_push and fcw is not None:
            need_ring = _pow2(max(fcw.max_count, 1) * fc_mult)
            if need_ring > fc_ring:
                if prev is not None:
                    prev["st"] = _grow_fc_ring(prev["st"], need_ring)
                fc_ring = need_ring
        n1 = n_b + 1
        row_gid = np.full(n1, -1, dtype=np.int64)
        row_gid[:n_rows] = row_gid_rows
        hist_mask = np.zeros(n1, dtype=bool)
        hist_mask[:n_rows] = is_hist

        # ---- per-chunk step budget --------------------------------------
        need_x = 64
        if hedge:
            need_x += n_b
        if res:
            need_x += 2 * n_b
        if dyn:
            d = dynamics
            kills = len(d.fail)
            need_x += 2 * kills * (cores_per_node + 1) + kills
            if d.autoscale:
                t_lo = float(row_t[0]) if n_rows else 0.0
                if np.isfinite(t_stop):
                    span = t_stop - t_lo
                else:
                    drain = (float(np.sum(row_p[~is_hist]))
                             / max(node_cap * cores_per_node, 1))
                    span = ((float(row_t[n_rows - 1]) if n_rows else 0.0)
                            - t_lo + drain + 2 * d.autoscale_interval_s)
                ticks = int(math.ceil(
                    max(span, 0.0) / max(d.autoscale_interval_s, 1e-6))) + 4
                grow = max(0, node_cap - nodes)
                need_x += ticks + grow * (1 + cores_per_node)
        xtra = max(xtra, _pow2(need_x))

        shape_key = (mask, n_b, nodes_b, slots_b, f_b, 1, window, fc_ring,
                     n_ep, 1, 0, xtra)
        peak_rows = max(peak_rows, n_b)

        # ---- fill inputs -------------------------------------------------
        inp = _alloc_bucket_inputs(shape_key, 1)
        inp["t"][0, :n_rows] = row_t
        inp["fnid"][0, :n_rows] = row_fn
        inp["p"][0, :n_rows] = row_p
        inp["cost"][0, :n_rows] = row_cost
        if fc_static:
            inp["cnt"][0, :n_rows] = row_cnt
        inp["coef"][0] = coef
        inp["cores"][0] = cores_per_node
        inp["nodes"][0] = nodes
        inp["t_stop"][0] = t_stop
        if freeze and lb == "home":
            inp["route"][0] = 1
            inp["home0"][0, :n_rows] = fn_home[row_fn]
        if warm and freeze:
            # pull cells never seed the estimator rings (the warm-seed
            # block in _run_scan_bucket is skipped by the pull `continue`)
            inp["ring0"][0, :, :nf, :seed_n] = fn_wseed[None, :, None]
            inp["rsum0"][0, :, :nf] = seed_n * fn_wseed
            inp["rlen0"][0, :, :nf] = seed_n
            inp["rpos0"][0, :, :nf] = seed_n % window
        if use_fc:
            onehot = np.zeros((n_rows, f_b), dtype=np.float32)
            onehot[np.arange(n_rows), row_fn] = 1.0
            inp["cumf"][0, 1:n_rows + 1] = np.cumsum(onehot, axis=0)
            inp["cumf"][0, n_rows + 1:] = inp["cumf"][0, n_rows]
        if not freeze:
            ent_fn, ent_row, qcnt0 = _csr_entries(
                prev, row_gid_rows, row_fn, kind, f_b)
            inp["fnev"][0, :len(ent_row)] = ent_row
            counts = np.bincount(ent_fn, minlength=f_b)
            inp["fnst"][0] = np.concatenate(
                ([0], np.cumsum(counts)))[:f_b]
        else:
            qcnt0 = None
        if dyn:
            inp["act0"][0, :nodes] = 0.0
            inp["killt"][0] = killt_spec
            inp["dynp"][0] = dynp
            inp["maxn"][0] = node_cap
            inp["nreq"][0] = (gid_next + n_fresh if final else 2 ** 30)
        if het:
            spd, epn, ept0, ept1, epf = het_arrays
            inp["spd"][0] = spd
            inp["epn"][0] = epn
            inp["ept0"][0] = ept0
            inp["ept1"][0] = ept1
            inp["epf"][0] = epf
        if hedge:
            inp["hmult"][0] = hedging.multiple
            inp["hfloor"][0] = hedging.floor_s
            inp["hmax"][0] = hedging.max_backups
        if res:
            t4, r6, a2 = res_arrays
            inp["rto_p"][0] = t4
            inp["rrt_p"][0] = r6
            inp["adm_p"][0] = a2
            inp["gseq"][0, :n_rows] = row_gid_rows

        # ---- layout + handoff planes ------------------------------------
        lkey = shape_key[:-1]
        if lkey != layout_key:
            import jax

            spec = {k: jax.ShapeDtypeStruct(v.shape[1:], v.dtype)
                    for k, v in _alloc_bucket_inputs(shape_key, 1).items()}
            with _x64_ctx(use64):
                layout = _carry_layout(
                    spec, n_nodes=nodes_b, n_slots=slots_b, window=window,
                    freeze=freeze, fc_push=fc_push, dyn=dyn, het=het,
                    hedge=hedge, cold=cold, dup=False, n_copies=1,
                    fc_ring=fc_ring, res=res, stream=True)
            layout_key = lkey
        planes0 = None
        if prev is not None:
            st0 = _handoff_state(
                prev, row_gid_rows, kind, n1, row_t, freeze=freeze,
                qcnt0=qcnt0, f_b=f_b, ai0=ai0, fdt=fdt)
            planes0 = _np_pack(layout, st0, fdt)

        # ---- dispatch with retry-doubled step budget --------------------
        attempt = 0
        while True:
            key = (mask, n_b, nodes_b, slots_b, f_b, 1, window, fc_ring,
                   n_ep, 1, 0, xtra, 1)
            init_c, scan_c = _scan_runner(key)
            with _x64_ctx(use64):
                arrs = {k: jnp.asarray(v) for k, v in inp.items()}
                if planes0 is None:
                    clk0, ctr0 = init_c(arrs)
                    planes0 = (np.asarray(clk0)[0], np.asarray(ctr0)[0])
                clk = jnp.asarray(planes0[0][None])
                ctr = jnp.asarray(planes0[1][None])
                (clk_f, ctr_f), recs = scan_c(clk, ctr, arrs)
            st = _np_unpack(layout, np.asarray(clk_f)[0],
                            np.asarray(ctr_f)[0])
            if _chunk_drained(st, t_stop, ai0 + n_fresh, dyn=dyn,
                              hedge=hedge, res=res, freeze=freeze):
                break
            attempt += 1
            if attempt > 8:
                raise StreamBudgetError(
                    f"chunk {chunks_run} not drained at xtra={xtra} "
                    f"(n_rows={n_rows}, t_stop={t_stop})")
            xtra = _pow2(2 * xtra if xtra else n_b)
        peak_bytes = max(peak_bytes, _bucket_bytes(shape_key, 1))

        # ---- accumulate dispatch records (last-wins in step order) ------
        j_s = np.asarray(recs[0])[0]
        es_s = np.asarray(recs[1], dtype=np.float64)[0]
        fs_s = np.asarray(recs[2], dtype=np.float64)[0]
        pj_s = np.asarray(recs[3], dtype=np.float64)[0]
        kd_s = np.asarray(recs[4])[0]
        valid = j_s < n_b
        rows_v = j_s[valid]
        g = row_gid[rows_v]
        keep = g >= 0
        gi = g[keep]
        acc.start[gi] = es_s[valid][keep]
        acc.finish[gi] = fs_s[valid][keep]
        if not freeze:
            acc.prio[gi] = pj_s[valid][keep]
            acc.node[gi] = kd_s[valid][keep]

        # ---- per-row state snapshots (live rows re-snapshot next chunk) -
        snap = (row_gid >= 0) & ~hist_mask
        gs = row_gid[snap]
        if freeze:
            acc.prio[gs] = st["fprio"][snap]
            acc.node[gs] = st["node_of"][snap]
        if cold:
            acc.cold[gs] = st["coldq"][snap]
        if hedge:
            acc.att[gs] = st["att"][snap]
            acc.stolen[gs] = st["stolen"][snap]
        if res:
            acc.ratt[gs] = st["ratt"][snap]
            acc.fcz[gs] = np.where(st["nfl"][snap],
                                   st["fcz"][snap], 0).astype(np.int8)

        # ---- liveness extraction ----------------------------------------
        live_mask, q_fn, q_gid = _extract_live(
            st, row_gid, hist_mask, n_b, freeze=freeze, dyn=dyn, res=res,
            f_b=f_b, inp=inp)
        prev = {
            "st": st, "gid": row_gid, "live": np.nonzero(live_mask)[0],
            "t": _pad_to(row_t, n1, np.inf),
            "fn": _pad_to(row_fn, n1, 0),
            "p": _pad_to(row_p, n1, 0.0),
            "cost": _pad_to(row_cost, n1, 0.0),
            "cnt": _pad_to(row_cnt, n1, 0),
            "q_fn": q_fn, "q_gid": q_gid, "n1": n1,
        }
        if fcw is not None:
            fcw.push(bt, bfn, fresh_gid, t_stop)
        gid_next += n_fresh
        chunks_run += 1
        final_st = st
        if progress is not None:
            progress(chunks_run, gid_next, time.perf_counter() - t_begin)
        if final:
            break

    n = gid_next
    wall = time.perf_counter() - t_begin
    if final_st is None:
        empty = np.zeros(0)
        counters = {"failures": 0, "backups_issued": 0, "steals_won": 0,
                    "cold_starts": 0, "evictions": 0, "timed_out": 0,
                    "shed": 0, "retries_issued": 0, "wasted_work": 0.0,
                    "n_failed": 0}
        return StreamResult(
            fns=fns, t=empty, fnid=empty.astype(np.int64), p=empty,
            start=empty, finish=empty, prio=empty,
            node=empty.astype(np.int64), attempts=empty.astype(np.int64),
            cold=empty.astype(bool), failed=empty.astype(np.int8),
            resp=empty, stretch=empty, counters=counters, nodes_used=nodes,
            timeline=None, n=0, chunks=0, peak_rows=0, peak_bytes=0,
            wall_s=wall)

    st = final_st
    counters = {
        "failures": int(st.get("nfail", 0)),
        "backups_issued": int(st.get("nbk", 0)),
        "steals_won": int(acc.stolen[:n].sum()),
        "cold_starts": int(st.get("ncold", 0)),
        "evictions": int(st.get("nevt", 0)),
        "timed_out": int(st.get("nto", 0)),
        "shed": int(st.get("nsh", 0)),
        "retries_issued": int(st.get("nrt", 0)),
        "wasted_work": float(st.get("wst", 0.0)),
        "n_failed": int(acc.fcz[:n].astype(bool).sum()),
    }
    nodes_used = int(st["prov"]) if dyn else nodes
    timeline = None
    if dyn:
        from .cluster import CapacityTimeline

        timeline = CapacityTimeline(
            activate=[float(a) for a in st["act_t"][:nodes_used]],
            deactivate=[float(killt_spec[k]) if bool(st["dead"][k])
                        else float("inf") for k in range(nodes_used)])

    failed = acc.fcz[:n].copy()
    served = failed == 0
    start = np.where(served, acc.start[:n], np.nan)
    finish = np.where(served, acc.finish[:n], np.nan)
    resp = finish + RESP_OVERHEAD_S - (acc.t[:n] - REQ_OVERHEAD_S)
    ref = fn_sref[acc.fnid[:n]]
    denom = np.maximum(np.where(np.isnan(ref), acc.p[:n], ref), 1e-9)
    stretch = resp / denom
    attempts = acc.att[:n].copy()
    if res:
        attempts = np.maximum(acc.ratt[:n] - 1, 0)
    return StreamResult(
        fns=fns, t=acc.t[:n].copy(), fnid=acc.fnid[:n].copy(),
        p=acc.p[:n].copy(), start=start, finish=finish,
        prio=acc.prio[:n].copy(), node=acc.node[:n].copy(),
        attempts=attempts, cold=acc.cold[:n].copy(), failed=failed,
        resp=resp, stretch=stretch, counters=counters,
        nodes_used=nodes_used, timeline=timeline, n=n, chunks=chunks_run,
        peak_rows=peak_rows, peak_bytes=peak_bytes, wall_s=wall)


# ---------------------------------------------------------------------------
# handoff helpers
# ---------------------------------------------------------------------------
def _pad_to(a: np.ndarray, n1: int, fill) -> np.ndarray:
    out = np.full(n1, fill, dtype=a.dtype if a.dtype != np.float64
                  else np.float64)
    out[: len(a)] = a
    return out


def _grow_fc_ring(st: dict, new_ring: int) -> dict:
    """Grow the per-(node, fn) FC arrival-time rings in place on the host:
    gather each ring oldest-first in circular order, pad with -inf (outside
    every window), and rebase the write cursor to the old length.  The
    kernel's window count sums ``ring > now - horizon``, so entry *position*
    never matters -- only the multiset of times."""
    fcr, fcp = st["fcr"], st["fcp"]
    old = fcr.shape[-1]
    idx = (fcp[..., None] + np.arange(old)) % old
    ordered = np.take_along_axis(fcr, idx, axis=-1)
    grown = np.full(fcr.shape[:-1] + (new_ring,), -np.inf, dtype=fcr.dtype)
    grown[..., :old] = ordered
    st = dict(st)
    st["fcr"] = grown
    st["fcp"] = np.full_like(fcp, old)
    return st


def _csr_entries(prev, row_gid_rows, row_fn, kind, f_b):
    """Chunk-local CSR pull-queue lists: carried queued entries first (their
    old per-function order preserved -- the pull tie-break takes the *lowest
    row index*, and gid-sorted rows preserve relative order), then every
    fresh row in arrival (gid) order.  Returns ``(entry_fn, entry_row,
    qcnt0)`` with ``qcnt0`` the per-function carried-queued counts that
    pre-validate the head window."""
    pos_of = np.searchsorted(row_gid_rows, prev["q_gid"]) if prev is not None \
        else np.zeros(0, dtype=np.int64)
    if prev is not None and len(prev["q_gid"]):
        cq_fn = prev["q_fn"]
        cq_row = pos_of
        # rank within fn = old queue order; a stable per-fn counter
        rank_c = np.zeros(len(cq_fn), dtype=np.int64)
        seen: dict = {}
        for i, f in enumerate(cq_fn.tolist()):
            rank_c[i] = seen.get(f, 0)
            seen[f] = rank_c[i] + 1
    else:
        cq_fn = np.zeros(0, dtype=np.int64)
        cq_row = rank_c = np.zeros(0, dtype=np.int64)
    fresh_rows = np.nonzero(kind == 2)[0]
    fr_fn = row_fn[fresh_rows]
    ent_fn = np.concatenate([cq_fn, fr_fn])
    ent_row = np.concatenate([cq_row, fresh_rows]).astype(np.int32)
    grp = np.concatenate([np.zeros(len(cq_fn), dtype=np.int8),
                          np.ones(len(fr_fn), dtype=np.int8)])
    rank = np.concatenate([rank_c, fresh_rows])
    order = np.lexsort((rank, grp, ent_fn))
    qcnt0 = np.bincount(cq_fn, minlength=f_b).astype(np.int32)
    return ent_fn[order], ent_row[order], qcnt0


def _handoff_state(prev, row_gid_rows, kind, n1, row_t, *, freeze, qcnt0,
                   f_b, ai0, fdt) -> dict:
    """Build the next chunk's initial carry from the previous chunk's final
    one: per-request entries relocate (defaults for fresh rows, previous
    values scattered onto the carried rows' new positions), slot back-
    pointers are value-remapped, the arrival cursor rebases to the first
    fresh row, and everything else copies verbatim."""
    st_old = prev["st"]
    old_live = prev["live"]
    carried_new = np.searchsorted(row_gid_rows, prev["gid"][old_live])
    st = {}
    for k, v in st_old.items():
        if k in _PER_REQUEST_KEYS or k in ("ai", "head", "qcnt", "idx_s"):
            continue
        st[k] = v
    for k in _PER_REQUEST_KEYS:
        if k not in st_old:
            continue
        old = st_old[k]
        if k == "enq_t":
            new = _pad_to(row_t, n1, np.inf).astype(old.dtype)
        elif k in _PRK_INF:
            new = np.full(n1, np.inf, dtype=old.dtype)
        elif k in _PRK_BOOL:
            new = np.zeros(n1, dtype=bool)
        elif k in _PRK_INT:
            new = np.zeros(n1, dtype=old.dtype)
        else:
            new = np.zeros(n1, dtype=old.dtype)
        new[carried_new] = old[old_live]
        st[k] = new
    val_map = np.zeros(prev["n1"], dtype=np.int32)
    val_map[old_live] = carried_new.astype(np.int32)
    st["idx_s"] = val_map[st_old["idx_s"]]
    st["ai"] = np.int32(ai0)
    st["head"] = np.zeros(f_b, dtype=np.int32)
    if not freeze and "qcnt" in st_old:
        st["qcnt"] = qcnt0
    return st


def _extract_live(st, row_gid, hist_mask, n_b, *, freeze, dyn, res, f_b,
                  inp):
    """Rows still in flight at the chunk horizon: running (finite slot
    finish), queued (frozen ``pend`` / CSR head window), pull re-queues
    (``xq``), pending kill re-arrivals (finite ``rearr``) and retry
    backoffs (finite ``rto``).  Returns the mask plus the queued entries'
    (fn, gid) in queue order for the next chunk's CSR build."""
    n1 = len(row_gid)
    live = np.zeros(n1, dtype=bool)
    fin = st["fin_s"]
    run_rows = st["idx_s"][np.isfinite(fin)]
    live[run_rows] = True
    q_fn_list = []
    q_gid_list = []
    if freeze:
        live |= st["pend"][:n1]
    else:
        fnev = inp["fnev"][0]
        fnst = inp["fnst"][0]
        head = st["head"]
        qcnt = st["qcnt"]
        backlog = np.nonzero(qcnt - head > 0)[0]
        for f in backlog.tolist():
            rows = fnev[fnst[f] + head[f]: fnst[f] + qcnt[f]]
            rows = rows[rows < n_b]
            live[rows] = True
            q_fn_list.append(np.full(len(rows), f, dtype=np.int64))
            q_gid_list.append(row_gid[rows])
        if dyn:
            live |= st["xq"][:n1]
    if dyn:
        live |= np.isfinite(st["rearr"][:n1])
    if res:
        live |= np.isfinite(st["rto"][:n1])
    live &= row_gid >= 0
    live &= ~hist_mask
    q_fn = (np.concatenate(q_fn_list) if q_fn_list
            else np.zeros(0, dtype=np.int64))
    q_gid = (np.concatenate(q_gid_list) if q_gid_list
             else np.zeros(0, dtype=np.int64))
    return live, q_fn, q_gid


def _chunk_drained(st, t_stop, n_arr, *, dyn, hedge, res, freeze) -> bool:
    """True when the chunk processed every event strictly below its horizon:
    all fresh arrivals consumed and no pending event candidate (completion,
    kill, re-arrival, activation, autoscaler tick, hedge deadline, timeout,
    retry) earlier than ``t_stop``.  A shortfall means the step budget ran
    out mid-chunk -- the caller re-runs the same planes at a doubled
    budget."""
    if int(st["ai"]) < n_arr:
        return False
    cands = [float(st["fin_s"].min())]
    if dyn:
        cands.append(float(st["killq"].min()))
        cands.append(float(st["rearr"].min()))
        pend = st["act_pend"]
        if pend.any():
            cands.append(float(st["act_t"][pend].min()))
        cands.append(float(st["next_tick"]))
    if hedge:
        cands.append(float(st["hedge_t"].min()))
        if "hedge_t2" in st:
            cands.append(float(st["hedge_t2"].min()))
    if res:
        cands.append(float(st["to_t"].min()))
        cands.append(float(st["rto"].min()))
    nxt = min(cands)
    if np.isinf(t_stop):
        return bool(np.isinf(nxt))
    return bool(nxt >= t_stop)
