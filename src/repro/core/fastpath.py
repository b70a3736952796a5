"""Vectorized single-node fast path (the ``"vectorized"`` / ``"scan"``
simulation backends).

The reference event loop in :mod:`.simulator` pays a heavy constant per
event: every dispatch scans the full container list, every release rebuilds
the per-function pools, and every event goes through closure-carrying heap
entries.  On a loaded node that is O(requests x containers) Python work, and
it dominates sweep wall-clock at high intensity.

This module re-implements the **ours-mode single node** (slot admission +
serialized management channel + non-preemptive 1-core execution, all five
policies) in array form:

* :class:`VectorizedBackend` -- numpy precomputation (arrival features,
  per-request channel costs) + a tight O(1)-per-event loop over counter-based
  pool / estimator state.  **Exact**: it replays the reference semantics
  decision-for-decision (same priorities, same container choices, same LRU
  eviction order, same event tie-breaking), so metrics agree to the bit --
  including cold starts, tight-memory eviction and ``warm=False`` runs.
* :class:`ScanBackend` / :func:`simulate_cells_scan` /
  :func:`simulate_cluster_cells_scan` -- an event-step loop that runs a
  whole batch of cells as one loop over padded request tensors (one event
  per step, cells vmapped, stopped once every cell is idle).  The kernel is
  **multi-node**: slot occupancy and management-channel clocks carry a node
  axis, and the per-event dispatch computes the cluster routing decision
  (pull most-free-slots, push least-loaded / home-invoker) inside the scan
  step, so an entire N-node cluster cell is one scan and a whole nodes x
  intensity x policy grid is a handful of bucketed XLA dispatches.  Capacity is **time-varying**: cells
  with a :class:`~repro.core.cluster.ClusterDynamics` carry per-node
  activation masks updated inside the step -- autoscaler ticks provision
  nodes after the configured delay, scheduled kills wipe a node and re-queue
  its lost calls after the detection delay (counted exactly like the
  reference), and push-model FC runs off bounded per-(node, fn) arrival
  count rings.  Warm cells run the *always-warm* regime -- every function
  has ``cores`` warm containers after warm-up, so the pool never cold-starts
  or evicts -- which holds for the default 32 GB node up to 10 cores (see
  :func:`scan_eligible`) and the cluster's 40 GB nodes up to ~13 (see
  :func:`cluster_scan_eligible`); ``warm=False`` cells instead carry
  per-(node, fn) free-container counts where memory is ample, and a
  per-node container table (MRU reuse, stem-cell replenish, create, LRU
  eviction, blocked queue heads) where it is not, matching the reference
  pool decision-for-decision.  Static-capacity arithmetic is float32, so
  agreement with the reference is within rounding for single nodes (~1e-6)
  and within the documented cluster tolerance for clusters (near-tie
  orderings can flip; see ``repro.core.sweep.CLUSTER_XCHECK_RTOL``);
  dynamic-capacity buckets run in float64 so failure/autoscale accounting is
  order-exact.

Compilations are cached per padded bucket shape (powers of two over requests
x nodes x slots x functions x batch; :func:`scan_cache_stats`), so repeated
``run_sweep`` calls pay one XLA compile per bucket per process.

The baseline (stock OpenWhisk) node is processor-sharing with state-dependent
rates; it stays on the reference backend (``supports`` says no and the sweep
engine falls back).
"""

from __future__ import annotations

import contextlib
import heapq
import os
import time
from collections import deque
from dataclasses import dataclass, field
from functools import partial, update_wrapper
from pathlib import Path

import numpy as np

from .request import Request
from .simulator import (
    OURS_BASE,
    OURS_COLD_EXTRA,
    OURS_PREWARM_EXTRA,
    OURS_SCALE,
    PS_KAPPA,
    REQ_OVERHEAD_S,
    RESP_OVERHEAD_S,
    SimResult,
    container_weight,
    register_backend,
)
from .containers import COLD_CREATE_S, PREWARM_INIT_S
from .estimator import DEFAULT_FC_HORIZON, DEFAULT_WINDOW
from .workload import PROFILES, SEBS_MEMORY_MB, STRETCH_REFERENCE_S

POLICY_NAMES = ("fifo", "sept", "eect", "rect", "fc")


# ---------------------------------------------------------------------------
# static arrival features (identical for both fast backends)
# ---------------------------------------------------------------------------
@dataclass
class _Arrivals:
    """Per-request features that depend only on the arrival stream."""

    order: np.ndarray      # request indices in event order
    t: np.ndarray          # invoker receive times r + REQ_OVERHEAD (sorted)
    fn_ids: np.ndarray     # function id per event
    p: np.ndarray          # true processing time per event
    chan_cost: np.ndarray  # warm-path management cost per event
    prev: np.ndarray       # RECT r-bar: previous same-fn arrival (own t first)
    count: np.ndarray      # FC #(fn, -T) including the current arrival
    fns: list[str]         # id -> function name


def _arrival_features(requests: list[Request],
                      horizon: float = DEFAULT_FC_HORIZON) -> _Arrivals:
    n = len(requests)
    r = np.array([q.r for q in requests], dtype=np.float64)
    t_all = r + REQ_OVERHEAD_S
    order = np.argsort(t_all, kind="stable")
    t = t_all[order]
    fns = sorted({q.fn for q in requests})
    fn_index = {f: i for i, f in enumerate(fns)}
    fn_ids = np.array([fn_index[requests[i].fn] for i in order], dtype=np.int64)
    p = np.array([requests[i].p_true for i in order], dtype=np.float64)
    # channel cost is a per-function constant for profiled functions; only
    # unknown (trace) names fall back to the per-request p_true proxy
    fn_cost = [OURS_BASE + OURS_SCALE * container_weight(f, float("nan"))
               if f in PROFILES else None for f in fns]
    chan_cost = np.array(
        [fn_cost[fid] if fn_cost[fid] is not None
         else OURS_BASE + OURS_SCALE * container_weight(requests[i].fn,
                                                        requests[i].p_true)
         for i, fid in zip(order, fn_ids)], dtype=np.float64)

    prev = np.empty(n, dtype=np.float64)
    count = np.empty(n, dtype=np.int64)
    for f in range(len(fns)):
        idx = np.nonzero(fn_ids == f)[0]
        tf = t[idx]
        # estimator.observe_arrival: the first call's r-bar is its own time
        prev[idx] = np.concatenate(([tf[0]], tf[:-1])) if idx.size else tf
        # (now - T, now] sliding window, current arrival included
        lo = np.searchsorted(tf, tf - horizon, side="right")
        count[idx] = np.arange(1, idx.size + 1) - lo
    return _Arrivals(order=order, t=t, fn_ids=fn_ids, p=p,
                     chan_cost=chan_cost, prev=prev, count=count, fns=fns)


# ---------------------------------------------------------------------------
# exact counter-based replica of ContainerPool (discipline="ours")
# ---------------------------------------------------------------------------
class _FastPool:
    """Bookkeeping-identical port of :class:`~repro.core.containers.
    ContainerPool` for the ours discipline, without the per-operation scans.

    Containers are (last_used, position, memory) triples grouped by function;
    ``position`` is the global insertion counter, which reproduces the
    reference's stable LRU tie-breaking (its ``sort`` is stable over list
    order, and list order is insertion order)."""

    def __init__(self, memory_mb: int, container_mb: int, cores: int,
                 fn_memory: dict | None, prewarm_count: int = 2) -> None:
        self.memory_mb = memory_mb
        self.container_mb = container_mb
        self.cores = cores
        self.fn_memory = fn_memory if fn_memory is not None else SEBS_MEMORY_MB
        self.prewarm_count = prewarm_count
        self._pos = 0
        self.mem_used = 0
        self.free: dict[str, list[list]] = {}   # fn -> [[last_used, pos, mb]]
        self.prewarm: list[list] = []           # [[last_used, pos, mb]]
        self.n_prewarm = 0
        self.cold_starts = 0
        self.evictions = 0
        self.creations = 0
        for _ in range(prewarm_count):
            if self.mem_used + container_mb <= memory_mb:
                self._add_prewarm()

    def _add_prewarm(self) -> None:
        self.prewarm.append([0.0, self._pos, self.container_mb])
        self._pos += 1
        self.n_prewarm += 1
        self.mem_used += self.container_mb

    def _size(self, fn: str) -> int:
        return int(self.fn_memory.get(fn, self.container_mb))

    def warm_up(self, fns: list[str], per_fn: int) -> None:
        for _ in range(per_fn):
            for fn in fns:
                mb = self._size(fn)
                if self.mem_used + mb <= self.memory_mb:
                    self.free.setdefault(fn, []).append([0.0, self._pos, mb])
                    self._pos += 1
                    self.mem_used += mb

    # -- acquire / release ---------------------------------------------------
    def acquire(self, fn: str, now: float):
        """Returns (startup_delay, cold_start, handle) or None; ``handle`` is
        the (fn, memory, position) triple release needs -- the container keeps
        its insertion position across busy periods, like the reference's
        containers list does."""
        # 1. warm container: most recently used, earliest-inserted on ties.
        # The free list stays sorted by last_used (releases are monotone in
        # simulation time), so the MRU is the tail; ties defer to the exact
        # (max last_used, min position) rule the reference's list scan gives.
        lst = self.free.get(fn)
        if lst:
            if len(lst) > 1 and lst[-2][0] >= lst[-1][0]:
                best = 0
                for i in range(1, len(lst)):
                    if (lst[i][0] > lst[best][0]
                            or (lst[i][0] == lst[best][0]
                                and lst[i][1] < lst[best][1])):
                        best = i
                entry = lst.pop(best)
            else:
                entry = lst.pop()
            return 0.0, False, (fn, entry[2], entry[1])
        # 2. prewarm container (first in list order)
        if self.prewarm:
            entry = self.prewarm.pop(0)
            self.n_prewarm -= 1
            self.cold_starts += 1
            while (self.n_prewarm < self.prewarm_count
                   and self.mem_used + self.container_mb <= self.memory_mb):
                self._add_prewarm()
            return PREWARM_INIT_S, True, (fn, entry[2], entry[1])
        # 3. create when memory allows
        mb = self._size(fn)
        if self.mem_used + mb <= self.memory_mb:
            self.mem_used += mb
            pos = self._pos
            self._pos += 1
            self.creations += 1
            self.cold_starts += 1
            return COLD_CREATE_S, True, (fn, mb, pos)
        # 4. evict idle non-matching containers (LRU), then create
        victims = [(e[0], e[1], None, i)
                   for i, e in enumerate(self.prewarm)]
        for f, entries in self.free.items():
            if f != fn:
                victims.extend((e[0], e[1], f, i)
                               for i, e in enumerate(entries))
        victims.sort(key=lambda v: (v[0], v[1]))
        doomed: list = []
        for lu, pos, f, _ in victims:
            if self.mem_used + mb <= self.memory_mb:
                break
            doomed.append((f, pos))
            size = (self.container_mb if f is None
                    else next(e[2] for e in self.free[f] if e[1] == pos))
            self.mem_used -= size
            self.evictions += 1
        for f, pos in doomed:
            if f is None:
                self.prewarm = [e for e in self.prewarm if e[1] != pos]
                self.n_prewarm -= 1
            else:
                self.free[f] = [e for e in self.free[f] if e[1] != pos]
        if self.mem_used + mb <= self.memory_mb:
            self.mem_used += mb
            pos = self._pos
            self._pos += 1
            self.creations += 1
            self.cold_starts += 1
            return COLD_CREATE_S, True, (fn, mb, pos)
        # 5. nothing available: head-of-line blocks
        return None

    def release(self, handle, now: float) -> None:
        fn, mb, pos = handle
        lst = self.free.setdefault(fn, [])
        lst.append([now, pos, mb])
        # _trim_ours: warm containers per function are bounded by cores
        if len(lst) > self.cores:
            lst.sort(key=lambda e: (e[0], e[1]))
            for victim in lst[: len(lst) - self.cores]:
                self.mem_used -= victim[2]
                self.evictions += 1
            del lst[: len(lst) - self.cores]

# ---------------------------------------------------------------------------
# numpy fast path: exact ours-node replay
# ---------------------------------------------------------------------------
def simulate_ours_vectorized(
    requests: list[Request],
    cores: int,
    policy: str = "fifo",
    memory_mb: int = 32 * 1024,
    container_mb: int = 128,
    warm: bool = True,
) -> SimResult:
    """Array-precomputed, O(1)-per-event replay of the reference ours node.

    Agrees with the reference backend decision-for-decision; see the module
    docstring for the argument."""
    if policy not in POLICY_NAMES:
        raise ValueError(f"unknown policy {policy!r}")
    n = len(requests)
    meta = {"mode": "ours", "policy": policy, "cores": cores,
            "backend": "vectorized"}
    if n == 0:
        return SimResult(requests=requests, cold_starts=0, evictions=0,
                         creations=0, meta=meta)

    arr = _arrival_features(requests)
    pool = _FastPool(memory_mb=memory_mb, container_mb=container_mb,
                     cores=cores, fn_memory=SEBS_MEMORY_MB)
    # estimator ring buffers; warm-up seeds min(cores, window) observations
    # of the profile median per function (experiment protocol, §V-A)
    times: list[deque] = [deque() for _ in arr.fns]
    if warm:
        pool.warm_up(arr.fns, per_fn=cores)
        seed_n = min(cores, DEFAULT_WINDOW)
        for f, fn in enumerate(arr.fns):
            w = PROFILES[fn].median_s if fn in PROFILES else 0.1
            times[f].extend([w] * seed_n)
    # Always-warm regime: when warm-up provisioned every function with
    # ``cores`` containers, acquisition is provably always a warm hit (per-fn
    # busy <= total busy < cores at dispatch) and trim/evict/cold never fire,
    # so pool bookkeeping can be skipped entirely.
    trivial_pool = warm and all(
        len(pool.free.get(fn, ())) >= cores for fn in arr.fns)

    # Python lists index ~10x faster than numpy scalars in the event loop;
    # float64 -> float via tolist() is value-preserving (both IEEE doubles)
    t_arr = arr.t.tolist()
    fn_ids = arr.fn_ids.tolist()
    p = arr.p.tolist()
    chan_cost = arr.chan_cost.tolist()
    prev = arr.prev.tolist()
    count = arr.count.tolist()
    fns = arr.fns
    start = [0.0] * n
    finish = [0.0] * n
    prio_out = [0.0] * n
    cold_out = [False] * n
    # per-fn estimate cache: sum(buf)/len(buf) is recomputed (in reference
    # summation order, for bitwise identity) only after a completion of fn
    est_cache = [sum(b) / len(b) if b else 0.0 for b in times]

    queue: list[tuple[float, int, int]] = []   # (priority, push seq, event id)
    comps: list[tuple[float, int, int, tuple]] = []  # (t, seq, event, handle)
    busy = 0
    chan_free = 0.0
    comp_seq = 0
    ai = 0
    window = DEFAULT_WINDOW

    def dispatch(now: float) -> None:
        nonlocal busy, chan_free, comp_seq
        while queue and busy < cores:
            j = queue[0][2]
            cost = chan_cost[j]
            if trivial_pool:
                handle = None
            else:
                acq = pool.acquire(fns[fn_ids[j]], now)
                if acq is None:
                    break  # head-of-line blocks; priority order is preserved
                delay, cold, handle = acq
                if cold:
                    cold_out[j] = True
                    cost += (OURS_COLD_EXTRA if delay > 1.0
                             else OURS_PREWARM_EXTRA)
            heapq.heappop(queue)
            busy += 1
            op_start = chan_free if chan_free > now else now
            chan_free = op_start + cost      # channel.occupy returns the time
            exec_start = chan_free           # the management op *finishes*
            start[j] = exec_start
            fin = exec_start + p[j]
            finish[j] = fin
            heapq.heappush(comps, (fin, comp_seq, j, handle))
            comp_seq += 1

    while True:
        next_arr = t_arr[ai] if ai < n else None
        # reference tie-break: arrival events are scheduled first, so at equal
        # times the arrival's heap sequence number is lower and it runs first
        if next_arr is not None and (not comps or next_arr <= comps[0][0]):
            e, now = ai, next_arr
            ai += 1
            if policy == "fifo":
                prio = now
            else:
                est = est_cache[fn_ids[e]]
                if policy == "sept":
                    prio = est
                elif policy == "eect":
                    prio = now + est
                elif policy == "rect":
                    prio = prev[e] + est
                else:  # fc
                    prio = count[e] * est
            prio_out[e] = prio
            heapq.heappush(queue, (prio, e, e))
            if busy < cores:
                dispatch(now)
        elif comps:
            now, _, e, handle = heapq.heappop(comps)
            f = fn_ids[e]
            buf = times[f]
            buf.append(p[e])
            if len(buf) > window:
                buf.popleft()
            est_cache[f] = sum(buf) / len(buf)
            if handle is not None:
                pool.release(handle, now)
            busy -= 1
            if queue:
                dispatch(now)
        else:
            break

    assert not queue and busy == 0, "requests left unserved"
    # write results back into the Request objects (same contract as the
    # reference backend: callers read metrics off the request list)
    order = arr.order.tolist()
    for e in range(n):
        req = requests[order[e]]
        req.node = "node0"
        req.r_prime = t_arr[e]
        req.priority = prio_out[e]
        req.cold_start = cold_out[e]
        req.start = start[e]
        req.finish = finish[e]
        req.c = finish[e] + RESP_OVERHEAD_S
    return SimResult(
        requests=requests,
        cold_starts=pool.cold_starts,
        evictions=pool.evictions,
        creations=pool.creations,
        meta=meta,
    )


class VectorizedBackend:
    """Exact array fast path for the ours-mode single node."""

    name = "vectorized"

    def supports(self, *, mode: str, policy: str, warm: bool,
                 nodes: int = 1, assignment: str = "pull",
                 autoscale: bool = False, failures: bool = False,
                 hedging: bool = False, hetero: bool = False,
                 timeouts: bool = False, retries: bool = False,
                 shedding: bool = False,
                 streaming: bool = False, trace: bool = False) -> bool:
        # trace: no rich event hooks -- the canonical lifecycle stream is
        # reconstructed from written-back request state instead
        return (mode == "ours" and policy in POLICY_NAMES and nodes <= 1
                and not autoscale and not failures
                and not hedging and not hetero
                and not timeouts and not retries and not shedding
                and not streaming and not trace)

    def simulate(
        self,
        requests: list[Request],
        cores: int,
        policy: str = "fifo",
        mode: str = "ours",
        memory_mb: int = 32 * 1024,
        container_mb: int = 128,
        warm: bool = True,
        kappa: float = PS_KAPPA,
    ) -> SimResult:
        if mode != "ours":
            raise ValueError(
                "the vectorized backend models the ours-mode node only; "
                "baseline (processor sharing) runs on backend='reference'")
        if kappa != PS_KAPPA:
            raise ValueError(
                "kappa parameterizes the baseline processor-sharing node, "
                "which the vectorized backend does not model; use "
                "backend='reference' for non-default kappa")
        return simulate_ours_vectorized(
            requests, cores, policy=policy, memory_mb=memory_mb,
            container_mb=container_mb, warm=warm)


register_backend(VectorizedBackend())


# ---------------------------------------------------------------------------
# batched event-step loop: a whole grid as one scan
# ---------------------------------------------------------------------------
# priority = a*r' + b*rbar + (c + d*count) * E[p]  -- all five policies are
# points in this 4-coefficient family, so one scan body serves the whole grid
_POLICY_COEF = {
    "fifo": (1.0, 0.0, 0.0, 0.0),
    "sept": (0.0, 0.0, 1.0, 0.0),
    "eect": (1.0, 0.0, 1.0, 0.0),
    "rect": (0.0, 1.0, 1.0, 0.0),
    "fc":   (0.0, 0.0, 0.0, 1.0),
}

# Pull-model coefficients differ in two places from the frozen-at-enqueue
# family above, both faithful to the reference Cluster semantics:
#  * fifo -- the global queue is ranked at pull time when r' is still unset,
#    so the reference degenerates to queue insertion order; ranking by the
#    (static) controller receive time is the same order without the all-equal
#    ties.
#  * eect -- "now + E[p]" shares the same `now` across every queued call, so
#    the ranking is identical to SEPT's; we drop the common term.
_PULL_COEF = {
    "fifo": (1.0, 0.0, 0.0, 0.0),
    "sept": (0.0, 0.0, 1.0, 0.0),
    "eect": (0.0, 0.0, 1.0, 0.0),
    "rect": (0.0, 1.0, 1.0, 0.0),
    "fc":   (0.0, 0.0, 0.0, 1.0),
}

# Dynamic-capacity pull cells carry a 5th coefficient on the *enqueue clock*:
# a request re-queued after its node died has a real r' (its first pull
# time), so the shared-`now` identities above no longer cancel across the
# queue -- FIFO and EECT rank fresh calls by `now` but re-queued ones by
# their recorded first-dispatch time (always earlier, exactly like the
# reference's r'-based priorities).  Heads add coef[4]*now (a shared
# constant, order-preserving), re-queued candidates add coef[4]*r'.
_PULL_COEF_DYN = {
    "fifo": (0.0, 0.0, 0.0, 0.0, 1.0),
    "sept": (0.0, 0.0, 1.0, 0.0, 0.0),
    "eect": (0.0, 0.0, 1.0, 0.0, 1.0),
    "rect": (0.0, 1.0, 1.0, 0.0, 0.0),
    "fc":   (0.0, 0.0, 0.0, 1.0, 0.0),
}


class ScanRejected(ValueError):
    """A batch the scan kernel does not model, found from the cells'
    *values* (workload, memory, dynamics schedule) rather than their static
    flags.  The one exception the sweep layer turns into a per-cell
    reference run (counted ``degraded``); every other error -- a compile
    refusal, a device fault, an out-of-memory -- propagates."""


# ClusterConfig defaults, mirrored here so scan eligibility is judged against
# the same node sizing the reference cluster uses (tests assert they agree;
# cluster.py is only imported lazily to keep this module importable alone)
CLUSTER_MEMORY_MB = 40 * 1024
CLUSTER_CONTAINER_MB = 128


def _cold_regime_ok(
    requests: list[Request],
    cores: int,
    memory_mb: int,
    container_mb: int,
    prewarm_count: int = 2,
) -> bool:
    """True when a ``warm=False`` run is inside the *ample-memory prewarm*
    regime the scan kernel models exactly.

    With no warm-up, every container is born from the prewarm pool
    (``PREWARM_INIT_S`` <= 1s, so every cold start pays exactly
    ``OURS_PREWARM_EXTRA``) and keeps the generic ``container_mb``
    reservation -- function-sized containers only ever appear via warm-up or
    the create path.  If the prewarm pool can always replenish, the create /
    evict-for-memory / head-of-line-block paths of ``ContainerPool.acquire``
    are provably unreachable, which is what lets the kernel track the pool
    as per-(node, fn) free *counts*: the MRU-vs-LRU container choice has no
    timing or accounting effect when all containers are interchangeable.

    Worst-case resident containers per node: ``prewarm_count`` prewarms +
    ``cores`` busy + ``cores`` free per function (the release trim bound),
    plus one transient during the release-then-trim and replenish windows
    each.  Ample memory means that bound times ``container_mb`` fits.
    Cells outside it run on the container table instead, see
    :func:`_pool_regime_ok`."""
    return _ample(len({r.fn for r in requests}), cores, memory_mb,
                  container_mb, prewarm_count)


def _ample(n_fns: int, cores: int, memory_mb: int, container_mb: int,
           prewarm_count: int = 2) -> bool:
    """:func:`_cold_regime_ok`'s bound on ``n_fns`` distinct functions."""
    bound = container_mb * (prewarm_count + cores * (1 + n_fns) + 2)
    return bound <= memory_mb


# stock OpenWhisk stem cells per invoker (ContainerPool.prewarm_count)
_PREWARM_COUNT = 2


def _fn_sizes(fns, container_mb: int) -> list[int]:
    """Container memory of each function, as ``ContainerPool._size`` reads
    it off the SeBS table (``container_mb`` for an unknown name)."""
    return [int(SEBS_MEMORY_MB.get(fn, container_mb)) for fn in fns]


def _pool_regime_ok(fns, cores: int, memory_mb: int, container_mb: int,
                    assignment: str) -> bool:
    """True when a ``warm=False`` cell outside the ample regime runs exactly
    on the **container table** (``pool_w > 0`` buckets): a per-node table
    of containers carrying function, memory, busy flag, ``last_used`` and
    creation order, on which the step replays every path of
    ``ContainerPool.acquire`` -- MRU warm pick, prewarm pick and a
    replenish that can fail, create, LRU eviction then create, and a
    blocked queue head -- and the release trim.

    Every node must hold any single container (``memory_mb`` at least the
    largest function or stem cell), so a blocked head always unblocks once
    its node drains.  Pull cells must moreover never block: a blocked pull
    invoker keeps its free slots, so the reference drains the global queue
    into that node's own queue, ranked by the node's estimator -- a
    node-local queue the pull step does not carry.  ``cores`` containers of
    the largest size fitting in ``memory_mb`` rules it out: a head finds
    only busy containers it cannot evict, at most ``cores - 1`` of them."""
    sizes = _fn_sizes(fns, container_mb) + [int(container_mb)]
    s_max = max(sizes)
    if memory_mb < s_max:
        return False
    if assignment == "pull":
        return cores * s_max <= memory_mb
    return True


def _pool_width(fns, cores: int, memory_mb: int, container_mb: int) -> int:
    """Rows of a node's container table: as many containers as the node's
    memory holds at the smallest size, capped at what the pool can ever
    hold (``_PREWARM_COUNT`` stem cells, ``cores`` busy and ``cores`` idle
    per function, counting the bucket's padded function axis so cells of
    one grid share a width), rounded up to a multiple of 8."""
    s_min = max(min(_fn_sizes(fns, container_mb) + [int(container_mb)]), 1)
    cap = _PREWARM_COUNT + cores * (1 + _pow2(len(fns)))
    w = max(1, min(int(memory_mb) // s_min, cap))
    return -(-w // 8) * 8


def scan_eligible(
    requests: list[Request],
    cores: int,
    policy: str = "fifo",
    mode: str = "ours",
    memory_mb: int = 32 * 1024,
    container_mb: int = 128,
    warm: bool = True,
) -> bool:
    """True when the scan backend reproduces the reference exactly (modulo
    float32): ours mode, known policy, and a container regime the kernel
    models -- either the always-warm regime where the §V-A warm-up provisions
    ``cores`` containers for *every* function (the pool never cold-starts,
    evicts or blocks), or ``warm=False``: in the ample-memory prewarm regime
    (every cold start is a prewarm hit; see :func:`_cold_regime_ok`) the
    kernel carries per-(node, fn) container counts, and on a memory-bound
    node a container table with create, LRU eviction and a blocked queue
    head (see :func:`_pool_regime_ok`)."""
    if mode != "ours" or policy not in POLICY_NAMES:
        return False
    if not warm:
        return (_cold_regime_ok(requests, cores, memory_mb, container_mb)
                or _pool_regime_ok({r.fn for r in requests}, cores,
                                   memory_mb, container_mb, "single"))
    fns = sorted({r.fn for r in requests})
    pool = _FastPool(memory_mb=memory_mb, container_mb=container_mb,
                     cores=cores, fn_memory=SEBS_MEMORY_MB)
    pool.warm_up(fns, per_fn=cores)
    return all(len(pool.free.get(fn, ())) >= cores for fn in fns)


# re-route rank sentinel: ex-queued kill losses order after every
# ex-running one (launch-sequence stamps stay far below this)
_RORD_Q = 2 ** 30


class _PlaneLayout:
    """Contiguous batch-major packing of the scan carry.

    Every float entry of the carry dict flattens into one **clocks plane**
    (``clk``, f32 for static buckets / f64 for dynamic ones) and every
    int/bool entry into one **counters plane** (``ctr``, int32), in
    sorted-key order -- so the whole per-step state is two dense tensors
    instead of ~20 scattered arrays.  That is what lets the step compile to
    a handful of fused kernels (XLA fuses the unpack/update/pack chain into
    the step body) and what makes the carry resident as two VMEM buffers on
    the Pallas path (``repro.kernels.event_step``).  The layout is a pure
    function of the carry *spec* (shapes + dtypes), so the packer
    (:func:`_make_planes`) and the kernel's unpacker derive identical
    offsets independently."""

    __slots__ = ("fparts", "iparts", "f_len", "i_len")

    def __init__(self, spec: dict):
        import jax.numpy as jnp

        self.fparts: list[tuple[str, int, int, tuple]] = []
        self.iparts: list[tuple[str, int, int, tuple, bool]] = []
        fo = io = 0
        for k in sorted(spec):
            s = spec[k]
            size = 1
            for d in s.shape:
                size *= int(d)
            if jnp.issubdtype(s.dtype, jnp.floating):
                self.fparts.append((k, fo, fo + size, tuple(s.shape)))
                fo += size
            else:
                self.iparts.append((k, io, io + size, tuple(s.shape),
                                    s.dtype == jnp.bool_))
                io += size
        self.f_len, self.i_len = fo, io

    def pack(self, st: dict):
        """Carry dict -> ``(clk, ctr)`` plane pair (bools widen to int32)."""
        import jax.numpy as jnp

        clk = jnp.concatenate([jnp.ravel(st[k]) for k, _, _, _
                               in self.fparts])
        ctr = jnp.concatenate([jnp.ravel(st[k]).astype(jnp.int32)
                               for k, _, _, _, _ in self.iparts])
        return clk, ctr

    def unpack(self, clk, ctr) -> dict:
        """``(clk, ctr)`` plane pair -> carry dict (static slices, so XLA
        sees them as zero-copy views into the planes)."""
        st = {}
        for k, lo, hi, shape in self.fparts:
            st[k] = clk[lo:hi].reshape(shape)
        for k, lo, hi, shape, isbool in self.iparts:
            v = ctr[lo:hi].reshape(shape)
            st[k] = v.astype(bool) if isbool else v
        return st


def _make_state0(inp, *, n_nodes, n_slots, window, freeze, fc_push, dyn,
                 het, hedge, cold, dup, n_copies, fc_ring, res=False,
                 stream=False, pool_w=0):
    """Initial carry dict for one cell (the ``state0`` of the event scan).

    Split out of the kernel so three consumers share one definition: the
    kernel itself (via :func:`_carry_layout` / ``jax.eval_shape`` -- the
    plane layout is derived from this function's output spec), the jitted
    plane initializer (:func:`_make_planes`, whose output buffers the scan
    runner donates back as the carry), and the Pallas kernel's static
    offset table."""
    import jax.numpy as jnp

    t_arr = inp["t"]
    nodes = inp["nodes"]
    ring0, rsum0, rlen0, rpos0 = (inp["ring0"], inp["rsum0"],
                                  inp["rlen0"], inp["rpos0"])
    n = t_arr.shape[0] - 1           # trailing +inf sentinel
    ft = t_arr.dtype
    inf = jnp.asarray(jnp.inf, dtype=ft)
    nq = n_copies * (n + 1) if dup else n + 1
    n_est = n_nodes if freeze else 1
    n_fns = ring0.shape[1]
    state0 = {
        "ai": jnp.int32(0),
        "head": jnp.zeros(n_fns, dtype=jnp.int32),
        "fin_s": jnp.full((n_nodes, n_slots), jnp.inf, dtype=ft),
        "idx_s": jnp.zeros((n_nodes, n_slots), dtype=jnp.int32),
        "busy": jnp.zeros(n_nodes, dtype=jnp.int32),
        "qn": jnp.zeros(n_nodes, dtype=jnp.int32),
        "chan": jnp.zeros(n_nodes, dtype=ft),
        "ring": ring0, "rsum": rsum0, "rlen": rlen0, "rpos": rpos0,
        "last_t": jnp.zeros((n_est, n_fns), dtype=ft),
        "prev_t": jnp.zeros((n_est, n_fns), dtype=ft),
        "narr": jnp.zeros((n_est, n_fns), dtype=jnp.int32),
    }
    if freeze:
        state0.update(
            pend=jnp.zeros(nq, dtype=bool),
            fprio=jnp.zeros(nq, dtype=ft),
            node_of=jnp.zeros(nq, dtype=jnp.int32),
        )
    if fc_push:
        state0.update(
            fcr=jnp.full((n_nodes, n_fns, fc_ring), -jnp.inf, dtype=ft),
            fcp=jnp.zeros((n_nodes, n_fns), dtype=jnp.int32),
        )
    if cold and pool_w:
        # memory-bound pools: a container table per node, rows in no
        # order (``psq`` is the creation order every tie breaks by).
        # ``pfn`` is the function (-1 a stem cell, -2 an empty row); each
        # node starts with the stem cells its memory holds, like
        # ContainerPool.__post_init__
        cmb, mem = inp["pcmb"], inp["pmem"]
        w_ids = jnp.arange(pool_w, dtype=jnp.int32)
        n_pw = jnp.clip(mem // jnp.maximum(cmb, 1), 0,
                        _PREWARM_COUNT).astype(jnp.int32)
        stem = w_ids < n_pw
        row = (n_nodes, pool_w)
        state0.update(
            pfn=jnp.broadcast_to(jnp.where(stem, -1, -2), row
                                 ).astype(jnp.int32),
            pmb=jnp.broadcast_to(jnp.where(stem, cmb, 0), row
                                 ).astype(jnp.int32),
            pbz=jnp.zeros(row, dtype=bool),
            plu=jnp.zeros(row, dtype=ft),
            psq=jnp.broadcast_to(w_ids, row),
            pnx=jnp.full(n_nodes, n_pw, dtype=jnp.int32),
            ppk=jnp.full(n_nodes, n_pw, dtype=jnp.int32),
            prow=jnp.zeros((n_nodes, n_slots), dtype=jnp.int32),
            ncold=jnp.int32(0), nevt=jnp.int32(0), ncre=jnp.int32(0),
            nblk=jnp.int32(0), coldq=jnp.zeros(n + 1, dtype=bool),
        )
        if freeze:
            # a completion's dispatch loop continues at the same instant
            # on the node in ``rdn`` (``rdt`` = +inf when none pends)
            state0.update(rdt=inf, rdn=jnp.int32(0), pdone=jnp.int32(0))
    elif cold:
        state0.update(
            # every pool starts empty in the warm=False regime (reference:
            # warm_functions=None skips warm_up); ample memory keeps the
            # prewarm pool inexhaustible, so only free-counts need carrying
            freec=jnp.zeros((n_nodes, n_fns), dtype=jnp.int32),
            ncold=jnp.int32(0), nevt=jnp.int32(0),
            coldq=jnp.zeros(n + 1, dtype=bool),
        )
    if hedge:
        state0.update(
            hedge_t=jnp.full(n + 1, jnp.inf, dtype=ft),
            att=jnp.zeros(n + 1, dtype=jnp.int32),
            nbk=jnp.int32(0),
            stolen=jnp.zeros(n + 1, dtype=bool),
            # controller estimator starts EMPTY, like the reference
            # Cluster's _estimator (nodes get the §V-A warm seed, the
            # controller does not)
            cring=jnp.zeros((n_fns, window), dtype=ft),
            crsum=jnp.zeros(n_fns, dtype=ft),
            crlen=jnp.zeros(n_fns, dtype=jnp.int32),
            crpos=jnp.zeros(n_fns, dtype=jnp.int32),
            qseq=jnp.zeros(nq, dtype=jnp.int32),
            stepc=jnp.int32(0),
            ndone=jnp.int32(0),
        )
        if dyn:
            state0.update(unhedge=jnp.zeros(n + 1, dtype=bool))
            if freeze:
                state0.update(hedge_t2=jnp.full(n + 1, jnp.inf, dtype=ft))
    if dup:
        state0.update(
            done0=jnp.zeros(n + 1, dtype=bool),
            win_start=jnp.zeros(n + 1, dtype=ft),
            win_fin=jnp.zeros(n + 1, dtype=ft),
            win_node=jnp.zeros(n + 1, dtype=jnp.int32),
            start_q=jnp.zeros(nq, dtype=ft),
        )
    if het and freeze:
        state0["sspd"] = jnp.ones((n_nodes, n_slots), dtype=ft)
    if dyn:
        state0.update(
            act_t=inp["act0"], dead=jnp.zeros(n_nodes, dtype=bool),
            killq=inp["killt"],
            act_pend=jnp.zeros(n_nodes, dtype=bool),
            rearr=jnp.full(n + 1, jnp.inf, dtype=ft),
            next_tick=jnp.where(inp["dynp"][4] > 0, inp["dynp"][0], inf),
            prov=nodes.astype(jnp.int32),
            nfail=jnp.int32(0), ndone=jnp.int32(0),
        )
        if freeze:
            state0.update(
                dseq=jnp.zeros((n_nodes, n_slots), dtype=jnp.int32),
                dcnt=jnp.int32(0),
                rord=jnp.zeros(n + 1, dtype=jnp.int32),
            )
        if not freeze:
            state0["xq"] = jnp.zeros(n + 1, dtype=bool)
            state0["rq_rt"] = jnp.zeros(n + 1, dtype=ft)
            state0["enq_t"] = t_arr          # fresh calls enqueue at receive
    if res:
        state0.update(
            # request lifecycle (timeouts / retries / shedding): active
            # timeout deadline and pending retry re-arrival per request,
            # the queued-E[p] snapshot each admission added to the shed
            # pressure gauge, submission counts, terminal-failure mask +
            # cause, per-slot exec starts (wasted-work accounting), and the
            # counters cross-checked exactly against the reference Cluster
            to_t=jnp.full(n + 1, jnp.inf, dtype=ft),
            rto=jnp.full(n + 1, jnp.inf, dtype=ft),
            eps=jnp.zeros(n + 1, dtype=ft),
            qep=jnp.zeros((), dtype=ft),
            ratt=jnp.zeros(n + 1, dtype=jnp.int32),
            nfl=jnp.zeros(n + 1, dtype=bool),
            fcz=jnp.zeros(n + 1, dtype=jnp.int32),   # 1=timeout, 2=shed
            sst=jnp.zeros((n_nodes, n_slots), dtype=ft),
            nto=jnp.int32(0), nsh=jnp.int32(0), nrt=jnp.int32(0),
            wst=jnp.zeros((), dtype=ft),
            ndn=jnp.int32(0),        # completions + terminal failures
            # queue-push sequence: a retry re-arrival re-pushes a LOW-index
            # call LATE, so push order decouples from request-index order
            # -- the reference's stable per-node PriorityQueue breaks
            # priority ties by it (same device as the hedge qseq)
            qsq=jnp.zeros(n + 1, dtype=jnp.int32),
            stp=jnp.int32(0),
            # controller estimator (deadline/shed estimates) starts EMPTY,
            # like the reference Cluster's _estimator (nodes get the §V-A
            # warm seed, the controller does not)
            zring=jnp.zeros((n_fns, window), dtype=ft),
            zrsum=jnp.zeros(n_fns, dtype=ft),
            zrlen=jnp.zeros(n_fns, dtype=jnp.int32),
            zrpos=jnp.zeros(n_fns, dtype=jnp.int32),
        )
    if stream and not freeze:
        # chunked-stream pull validity counter: ``narr`` carries the
        # *cumulative* per-function arrival count across chunk boundaries
        # (its zero-vs-nonzero state is the RECT first-arrival detector), so
        # the head-window validity test needs its own chunk-rebased counter
        # (carried queued entries preloaded by the handoff, fresh arrivals
        # incremented in-step)
        state0["qcnt"] = jnp.zeros(n_fns, dtype=jnp.int32)
    return state0


def _carry_layout(inp, **flags) -> _PlaneLayout:
    """Plane layout for a cell's carry, derived shape-only (``eval_shape``
    never materializes the state).  ``inp`` may hold concrete arrays,
    tracers or ``ShapeDtypeStruct`` leaves; float64 buckets must call this
    under ``enable_x64`` so the spec dtypes are not canonicalized down."""
    import jax

    return _PlaneLayout(jax.eval_shape(partial(_make_state0, **flags), inp))


def _make_planes(inp, **flags):
    """Per-cell initial carry as the packed ``(clk, ctr)`` plane pair.
    vmapped + jitted by the scan runner; its output buffers are donated
    straight back into the scan dispatch."""
    layout = _carry_layout(inp, **flags)
    return layout.pack(_make_state0(inp, **flags))


# -- container-table row picks: one-hot masks over a node's rows.  Creation
# order (``psq``) is unique among a node's live rows and is the order of the
# reference's container list, so it breaks every tie the way a scan of that
# list does.
def _first_row(mask, sq):
    """The earliest-created row of ``mask`` (a list scan's first hit)."""
    import jax.numpy as jnp

    return mask & (sq == jnp.min(jnp.where(mask, sq, _I32_MAX)))


def _lru_row(mask, lu, sq):
    """The least recently used row of ``mask``: smallest ``last_used``,
    earliest created on ties (a stable sort over the list)."""
    import jax.numpy as jnp

    tie = mask & (lu == jnp.min(jnp.where(mask, lu, jnp.inf)))
    return _first_row(tie, sq)


def _mru_row(mask, lu, sq):
    """The most recently used row of ``mask``, earliest created on ties
    (the reference keeps the first strictly greater ``last_used``)."""
    import jax.numpy as jnp

    tie = mask & (lu == jnp.max(jnp.where(mask, lu, -jnp.inf)))
    return _first_row(tie, sq)


def _evict_lru(cand, lu, sq, mb, used, size, mem, go):
    """ContainerPool's step 4: evict ``cand`` rows in LRU order while
    ``used + size > mem`` and a candidate is left (when ``go``).  Returns
    the evicted rows and the memory still in use; a head that still does
    not fit has evicted every candidate, as the reference does."""
    import jax
    import jax.numpy as jnp

    def cond(c):
        ev, u = c
        return go & (u + size > mem) & jnp.any(cand & ~ev)

    def body(c):
        ev, u = c
        v = _lru_row(cand & ~ev, lu, sq)
        return ev | v, u - jnp.sum(jnp.where(v, mb, 0))

    return jax.lax.while_loop(cond, body, (jnp.zeros_like(cand), used))


_I32_MAX = 2 ** 31 - 1


def _cell_step(inp, *, n_nodes, n_slots, window, freeze, use_fc, fc_push,
               dyn, het, hedge, cold, dup, n_copies, n_ep, fc_ring, horizon,
               res=False, stream=False, pool_w=0):
    """One cell's event step over a whole **cluster**: slot-occupancy and
    channel clocks carry a node axis, and the per-event dispatch includes the
    routing decision.  Returns ``(layout, pick, step)``: the cell's
    :class:`_PlaneLayout`, ``pick(st) -> (e, now, none_left, kflat)`` (the
    next event: its candidate index, its time, whether the cell has none
    left, and the slot that completes first) and ``step(st, ev) -> (st',
    record)``, which handles the event ``ev = pick(st)``.  ``inp`` is a dict
    of per-cell arrays (see ``_run_scan_bucket``; duplicate-mode buckets
    pass the copy-axis fields already tiled, see :func:`_event_loop`).
    :func:`_event_loop` vmaps both over the batch and runs them on the
    packed ``(clk, ctr)`` plane pair -- two contiguous tensors -- with the
    per-segment dict view reconstructed by static slicing, so XLA fuses the
    whole step into a handful of kernels instead of threading ~20 small
    carry arrays.

    The carry is assembled as an **ordered pipeline of feature-flagged
    segments** (see ``_CARRY_SEGMENTS``): base slots/queue/channel state,
    frozen-priority queue entries (``freeze``), per-(node, fn) push-FC
    arrival rings (``fc_push``), container free-counts (``cold``), hedge
    watches + controller ring (``hedge``), racing-copy winner state
    (``dup``), per-slot effective speeds (``het``) and capacity-dynamics
    masks (``dyn``).  Each enabled segment contributes its slice of the
    carry dict and its update inside the step below (the banner comments
    mark the segment boundaries); the compile-cache key carries the enabled
    set as a feature bitmask (:func:`_feature_mask`).

    Two static regimes share the body:

    * ``freeze=True`` -- single-node and push-assignment semantics: the
      priority is computed once at arrival from the *routed node's* estimator
      state (rings/prev-arrival are ``(n_nodes, F)``), and each event only
      dispatches on the node it touched.  ``route`` selects the push balancer
      per cell: 0 = least-loaded (min busy+queued, first on ties), 1 = home
      invoker (``home0`` carries the per-request CRC32 start index; walk
      forward to the first node with a free slot).  ``fc_push=True``
      additionally carries bounded per-(node, fn) **arrival-time count
      rings**: FC's sliding-window count depends on the dynamic routing
      history, so each routed arrival is logged in its node's ring and the
      window count is the number of logged times still inside the horizon --
      the ring is sized to the workload's worst global per-function window
      count, so it can never undercount.
    * ``freeze=False`` -- the pull model: queued calls are re-ranked at every
      pull from the *controller's* estimator (rings are ``(1, F)`` and start
      empty, exactly like the reference controller), the dispatch node is the
      one with the most free slots, and the FC window count is reconstructed
      exactly from the static arrival stream (``cumf[k, f]`` = calls of f
      among the first k arrivals, so #(f, (now-T, now]) = cumf[a] - cumf[k0]
      with k0 found by searchsorted).

      The global best-of-queue is found in O(F), not O(n): a pull-time
      priority is a per-*function* value (every queued call of f shares
      est/prev/count, and the FIFO coefficient orders a function's calls by
      arrival), so each function's queue is the contiguous tail of its static
      arrival sequence ``fn_ev[f]`` and the reference's argmin over the whole
      queue equals the argmin over the F queue *heads*, with the first-index
      tie-break preserved by taking the smallest head event index among the
      minimum-priority functions.

    ``het=True`` compiles the **heterogeneity** machinery: per-node base
    speeds plus a padded ``(node, t0, t1, slowdown)`` episode table (a
    :class:`~repro.core.stragglers.NodeSpeedProfile` in tensor form).  The
    routed node's *effective speed at dispatch time* divides both the
    management-op cost and the execution time, exactly like the reference
    ``OursNodeSim._launch``; in push mode the node estimator rings log the
    *measured* (speed-scaled) service while the controller ring keeps raw
    ``p_true``, mirroring the reference's node-vs-controller asymmetry.

    ``hedge=True`` (push/freeze only -- the pull model's late binding makes
    hedging a structural no-op) compiles **straggler hedging**: per-request
    deadline events armed at arrival from a controller-side estimator ring
    (``now + multiple x max(E[p], floor)``), which -- when the call is still
    queued and under its backup budget -- cancel it on its node and re-route
    it to the least-loaded peer with a freshly computed priority, exactly
    the reference ``Cluster._maybe_backup`` steal.  When no live peer
    exists the steal re-submits to the call's own node (the reference's
    ``min(others) if others else node`` self-steal), so single-node push
    hedging is modelled too.  ``backups_issued`` / ``steals_won`` counts
    replicate the reference bit-exactly; a dispatched call's watch is
    cleared so no-op fires do not consume scan steps.  Both flags force the
    bucket into float64 (like ``dyn``): deadline-vs-start and
    episode-boundary orderings decide integer counts that must not flip
    under float32 clock drift.

    ``dup=True`` (requires ``hedge``) switches the hedge action to
    **duplicate-mode racing copies**: the queue state grows a copy axis --
    entry ``q = c*(n+1) + j`` is copy ``c`` of request ``j``, with
    ``n_copies = 1 + max_backups`` -- and a deadline fire on a still-queued
    original issues copy ``attempts+1`` on the least-loaded live peer
    (no-op without re-arm when no peer exists, like the reference's ``if
    not others: return``).  Copies race: the first completion of any copy
    records the winner's start/finish/node (the reference ``_on_complete``
    min-c rule with first-wins ties), pops the watch, and ``steals_won``
    counts originals whose winner was a backup copy.  The original is never
    cancelled -- both runs occupy slots and feed the estimators, exactly
    like the reference.

    ``cold=True`` compiles the ``warm=False`` regimes; estimator rings
    start empty in both.  With ``pool_w == 0``, the **ample-memory prewarm
    regime** (:func:`_cold_regime_ok`): the carry tracks per-(node, fn)
    free-container counts, a dispatch with no free container is a prewarm
    cold start charging ``OURS_PREWARM_EXTRA`` on the management channel,
    and a release that would exceed the ``cores`` per-function bound counts
    an eviction -- matching ``ContainerPool`` exactly, where creations are
    provably zero and the MRU/LRU container choice has no observable
    effect.  With ``pool_w > 0``, **memory-bound nodes**
    (:func:`_pool_regime_ok`): each node carries a ``pool_w``-row container
    table (function, memory, busy, ``last_used``, creation order), and
    every dispatch replays ``ContainerPool.acquire`` on it -- the MRU warm
    container (earliest created on ties), else the earliest stem cell
    (then replenished while memory allows), else a container created at
    the function's size (``OURS_COLD_EXTRA``), evicting idle containers in
    (``last_used``, creation) order first when memory is short, else the
    head blocks: the call stays queued and the evictions stand.  A release
    trims the function's idle containers to ``cores``, LRU first.  Only a
    node's own completion can unblock it, and its dispatch loop may then
    start several calls at that instant: under ``freeze`` a continuation
    event (``rdt``, ranked before everything at its time) re-runs the
    dispatch on that node until it blocks, drains or fills.  Pull cells
    never block (the eligibility bound); ``nblk`` counts blocked attempts
    so the host can refuse a cell that did.

    ``dyn=True`` compiles the **time-varying capacity** machinery on top:
    per-node activation times and a dead mask (the cell's
    :class:`~repro.core.cluster.CapacityTimeline` in tensor form) gate
    routing, slot admission and the management-channel clocks; scheduled
    kills wipe a node's slots (and, push, its queue) and re-arrive the lost
    requests after the detection delay, counted exactly like the reference's
    ``failures``; autoscaler ticks evaluate the queue-per-slot rule inside
    the scan step and schedule provisions ``provision_delay`` ahead; a
    newly-activated node drains the global queue through repeated
    activation-dispatch events.  Event precedence at equal times is kill,
    arrival, completion, re-arrival, activation, tick (kills are scheduled
    before the burst in the reference, ticks after).  The loop's step
    budget ``n_steps`` (:func:`_event_loop`) must cover 2n plus the dynamics
    budget (see ``_ScanCell.dyn_budget``); the caller verifies the returned
    completion count.

    ``stream=True`` compiles the **chunked-stream** variant used by
    :mod:`repro.core.streamscan`: the cell goes idle at the chunk horizon
    ``t_stop`` (every event at ``now >= t_stop`` defers to the next chunk,
    whose candidate stack replays the same precedence), the final
    ``(clk, ctr)`` planes are returned so the host can hand the carry off
    into the next chunk's tensors, and three chunk-local indirections
    replace whole-stream lookups: the pull head-window validity test reads
    the chunk-rebased ``qcnt`` carry instead of the cumulative ``narr``,
    the per-function event lists arrive in CSR form (``fnev``/``fnst``,
    O(n + F) instead of the dense ``(F, kq)`` table), and the resilience
    retry-jitter hash reads the request's *global* arrival rank from
    ``gseq`` so backoff delays are bit-identical to the single-shot run.
    Dispatch records are returned raw for every mode (the host resolves
    last-wins across chunks).
    """
    import jax.numpy as jnp

    t_arr = inp["t"]
    fnid = inp["fnid"]
    p = inp["p"]
    cost = inp["cost"]
    cnt = inp["cnt"]
    home0 = inp["home0"]
    coef = inp["coef"]
    cores = inp["cores"]
    nodes = inp["nodes"]
    route = inp["route"]
    ring0, rsum0, rlen0, rpos0 = (inp["ring0"], inp["rsum0"],
                                  inp["rlen0"], inp["rpos0"])
    cumf = inp["cumf"]
    fn_ev = inp["fn_ev"]
    if stream:
        t_stop = inp["t_stop"]
        if not freeze:
            fnev_flat = inp["fnev"]      # CSR per-fn event lists
            fn_start = inp["fnst"]
        if res:
            gseq = inp["gseq"]           # global arrival ranks

    n = t_arr.shape[0] - 1           # t_arr carries a trailing +inf sentinel
    # float dtype follows the inputs: float32 for static-capacity buckets,
    # float64 for dynamic ones (dispatched under enable_x64 so that f32
    # clock drift cannot flip completion-vs-kill/arrival event orderings
    # that failure accounting depends on)
    ft = t_arr.dtype
    inf = jnp.asarray(jnp.inf, dtype=ft)
    node_ids = jnp.arange(n_nodes)
    slot_ids = jnp.arange(n_slots)
    fn_ids_ax = jnp.arange(ring0.shape[1])
    win_ids = jnp.arange(window)
    oreq_ids = jnp.arange(n + 1)     # one entry per *original* request
    pool = cold and pool_w > 0       # memory-bound container table
    pool_rd = pool and freeze        # ... with the blocked-head continuation
    if pool:
        fmb, pmem, pcmb = inp["fmb"], inp["pmem"], inp["pcmb"]
        row_ids = jnp.arange(pool_w)
    # duplicate-mode copy axis, flattened into the request axis: queue entry
    # q = c*(n+1) + j is copy c of request j, so every frozen-queue structure
    # below (pend/fprio/node_of/qseq, slot back-refs) works unchanged on the
    # widened axis; the static per-entry features arrive tiled
    nq = n_copies * (n + 1) if dup else n + 1
    req_ids = jnp.arange(nq)
    if dyn:
        interval, thr, delay, detect, auto_f = (inp["dynp"][k]
                                                for k in range(5))
    if res:
        # request-lifecycle resilience (timeouts / retries / shedding)
        # compiles only the static warm push regime -- every other combo is
        # rejected by cluster_scan_eligible / ScanBackend.supports
        assert freeze and not (dyn or hedge or dup or het or cold), \
            "res carry segment requires the static warm push regime"
        rto_p = inp["rto_p"]   # [on, multiple, floor, absolute]
        rrt_p = inp["rrt_p"]   # [max_attempts, base, cap, jitter, on_timeout,
        #                         on_shed]
        adm_p = inp["adm_p"]   # [on, threshold]

        def _res_delay(seq, a):
            # bit-identical to RetryPolicy.delay: 16-bit hash fraction for
            # the per-(request, attempt) jitter, exponential doubling via an
            # integer left-shift (exp2/power are not bit-exact), f64 ops in
            # the same order as the Python reference.  ``seq`` is the event
            # index == the reference's stable arrival rank; int64 keeps the
            # hash exact for any stream length (res buckets run under x64).
            base, cap, jit = rrt_p[1], rrt_p[2], rrt_p[3]
            u = (((seq.astype(jnp.int64) * 7919
                   + a.astype(jnp.int64) * 104729 + 12345)
                  % 65536).astype(ft)) / 65536.0
            shift = jnp.left_shift(
                jnp.ones((), jnp.int32),
                jnp.maximum(a - 1, 0)).astype(ft)
            raw = jnp.minimum(cap, base * shift)
            return raw * ((1.0 - jit) + jit * u)

        if stream:
            # the jitter hash is keyed on the reference's stable arrival
            # rank; a chunk-local row index would change the delay, so the
            # handoff supplies each row's global rank
            def _res_seq(i):
                return gseq[i]
        else:
            def _res_seq(i):
                return i

    def pick(st):
        """The cell's next event: the earliest of its candidate times."""
        t_a = t_arr[st["ai"]]
        flat = st["fin_s"].reshape(-1)
        kflat = jnp.argmin(flat)
        t_c = flat[kflat]
        if dyn:
            cand_l = [jnp.min(st["killq"]), t_a, t_c, jnp.min(st["rearr"]),
                      jnp.min(jnp.where(st["act_pend"], st["act_t"], inf)),
                      st["next_tick"]]
            if hedge:
                # hedge deadlines rank last at exact ties (measure-zero:
                # deadlines are estimate multiples)
                cand_l.append(jnp.min(st["hedge_t"]))
            cand = jnp.stack(cand_l)
        elif hedge:
            # hedge deadlines rank after completions at exact ties (a
            # measure-zero case: deadlines are estimate multiples)
            cand = jnp.stack([t_a, t_c, jnp.min(st["hedge_t"])])
        elif res:
            # timeout fires rank after completions and retry re-arrivals
            # after both; the reference heap would fire a timeout watch
            # first at a deadline == completion exact tie (lower schedule
            # seq), but deadlines are estimate multiples and re-arrivals
            # jittered backoff sums -- measure-zero, like hedge
            cand = jnp.stack([t_a, t_c, jnp.min(st["to_t"]),
                              jnp.min(st["rto"])])
        elif pool_rd:
            # the continuation of a completion's dispatch loop runs before
            # any other event at its instant, as the reference's does
            cand = jnp.stack([st["rdt"], t_a, t_c])
        else:
            cand = jnp.stack([t_a, t_c])
        # argmin takes the *first* minimum: at equal times the stack order is
        # the event precedence (kill < arrival <= completion < ... < tick)
        e = jnp.argmin(cand)
        now = cand[e]
        none_left = jnp.isinf(now)
        if stream:
            # chunk horizon: every event at or past ``t_stop`` defers to the
            # next chunk -- the carry stays exactly as it was before the
            # next chunk's first event, and the next chunk's candidate stack
            # replays the same same-instant precedence order
            none_left = none_left | (now >= t_stop)
        return e, now, none_left, kflat

    # XLA's CPU scatter runs a slow generic per-element path, so every
    # fixed-size state update below is a dense one-hot ``where`` instead of
    # an ``.at[]`` scatter -- the masks are tiny ((F,), (nodes, slots), ...)
    # and the elementwise chains fuse into a handful of kernels per step.
    # A step with ``none_left`` changes nothing but the ``stepc``/``stp``
    # push stamps, which only order pushes against each other.
    def step(st, ev):
        e, now, none_left, kflat = ev
        ai = st["ai"]
        head = st["head"]
        fin_s, idx_s = st["fin_s"], st["idx_s"]
        busy, qn, chan = st["busy"], st["qn"], st["chan"]
        ring, rsum, rlen, rpos = st["ring"], st["rsum"], st["rlen"], st["rpos"]
        last_t, prev_t, narr = st["last_t"], st["prev_t"], st["narr"]
        if freeze:
            pend, fprio, node_of = st["pend"], st["fprio"], st["node_of"]
        if res:
            to_t, rto = st["to_t"], st["rto"]
            eps, qep = st["eps"], st["qep"]
            ratt, nfl, fcz = st["ratt"], st["nfl"], st["fcz"]
            sst = st["sst"]
            nto, nsh, nrt = st["nto"], st["nsh"], st["nrt"]
            wst, ndn = st["wst"], st["ndn"]
            maxa = rrt_p[0].astype(jnp.int32)
            on_to, on_sh = rrt_p[4] > 0, rrt_p[5] > 0
        if dyn:
            act_t, dead, killq = st["act_t"], st["dead"], st["killq"]
            act_pend, rearr = st["act_pend"], st["rearr"]
        off = 1 if dyn or pool_rd else 0
        do_arr = (e == off) & ~none_left
        do_comp = (e == off + 1) & ~none_left
        if pool_rd:
            do_rd = (e == 0) & ~none_left
        if hedge:
            do_hedge = (e == (6 if dyn else 2)) & ~none_left
        if res:
            do_to = (e == 2) & ~none_left
            do_rto = (e == 3) & ~none_left
        if dyn:
            do_kill = (e == 0) & ~none_left
            do_re = (e == 3) & ~none_left
            do_act = (e == 4) & ~none_left
            do_tick = (e == 5) & ~none_left
            active = (act_t <= now) & ~dead
        else:
            active = node_ids < nodes

        # -- completion: free the slot, feed the estimator ring -------------
        kn = (kflat // n_slots).astype(jnp.int32)
        ks = kflat % n_slots
        j_done = idx_s[kn, ks]
        f_done = fnid[j_done]
        en_c = kn if freeze else 0   # which estimator observed it
        m_en = (jnp.arange(ring.shape[0]) == en_c)
        m_fd = (fn_ids_ax == f_done)
        m_cf = (m_en[:, None] & m_fd[None, :]) & do_comp     # (NE, F)
        pos = rpos[en_c, f_done]
        v = p[j_done]
        if het and freeze:
            # node estimators log the *measured* (speed-scaled) service; the
            # controller ring (pull mode / hedging below) keeps raw p_true
            v = v / st["sspd"][kn, ks]
        old = ring[en_c, f_done, pos]
        full = rlen[en_c, f_done] == window
        rsum = jnp.where(m_cf, rsum + v - jnp.where(full, old, 0.0), rsum)
        ring = jnp.where(m_cf[:, :, None] & (win_ids == pos), v, ring)
        rlen = jnp.where(m_cf & ~full, rlen + 1, rlen)
        rpos = jnp.where(m_cf, (rpos + 1) % window, rpos)
        if hedge:
            # controller-side estimator (hedging deadlines): observes every
            # completion's p_true, like the reference Cluster._on_complete
            cpos = st["crpos"][f_done]
            cfull = st["crlen"][f_done] == window
            cold_v = st["cring"][f_done, cpos]
            m_cfd = (fn_ids_ax == f_done) & do_comp
            crsum = jnp.where(m_cfd, st["crsum"] + p[j_done]
                              - jnp.where(cfull, cold_v, 0.0), st["crsum"])
            cring = jnp.where(m_cfd[:, None] & (win_ids == cpos),
                              p[j_done], st["cring"])
            crlen = jnp.where(m_cfd & ~cfull, st["crlen"] + 1, st["crlen"])
            crpos = jnp.where(m_cfd, (cpos + 1) % window, st["crpos"])
        if res:
            # completion voids the timeout watch (the reference's
            # completed-set staleness check) and feeds the controller
            # estimator ring that admission/deadline estimates read
            # (Cluster._on_complete observes p_true; nodes see the same
            # value -- het is excluded from res buckets)
            to_t = jnp.where((req_ids == j_done) & do_comp, inf, to_t)
            ndn = ndn + do_comp.astype(jnp.int32)
            zpos = st["zrpos"][f_done]
            zfull = st["zrlen"][f_done] == window
            zold = st["zring"][f_done, zpos]
            m_zfd = (fn_ids_ax == f_done) & do_comp
            zrsum = jnp.where(m_zfd, st["zrsum"] + p[j_done]
                              - jnp.where(zfull, zold, 0.0), st["zrsum"])
            zring = jnp.where(m_zfd[:, None] & (win_ids == zpos),
                              p[j_done], st["zring"])
            zrlen = jnp.where(m_zfd & ~zfull, st["zrlen"] + 1, st["zrlen"])
            zrpos = jnp.where(m_zfd, (zpos + 1) % window, st["zrpos"])
        m_kn = (node_ids == kn) & do_comp
        busy = jnp.where(m_kn, busy - 1, busy)
        fin_s = jnp.where(m_kn[:, None] & (slot_ids == ks), inf, fin_s)
        if pool:
            # -- container table, release half (ContainerPool.release +
            # _trim_ours): the slot's row goes idle at ``now``; if f now
            # has more than ``cores`` idle containers, its LRU idle row
            # (last_used, then creation order) is evicted
            m_nd = node_ids == kn
            m_rl = (m_nd[:, None] & (row_ids == st["prow"][kn, ks])[None, :]
                    & do_comp)
            pbz = jnp.where(m_rl, False, st["pbz"])
            plu = jnp.where(m_rl, now, st["plu"])
            psq = st["psq"]
            idl = (st["pfn"][kn] == f_done) & ~pbz[kn]
            trim = do_comp & (jnp.sum(idl.astype(jnp.int32)) > cores)
            m_tv = (m_nd[:, None] & _lru_row(idl, plu[kn], psq[kn])[None, :]
                    & trim)
            pfn = jnp.where(m_tv, -2, st["pfn"])
            pmb = jnp.where(m_tv, 0, st["pmb"])
            nevt = st["nevt"] + trim.astype(jnp.int32)
        elif cold:
            # -- container segment, release half (ContainerPool.release +
            # _trim_ours): the freed container re-enters its (node, fn) free
            # pool unless the fn already holds ``cores`` free ones, in which
            # case the LRU free container is evicted instead (which one is
            # unobservable here: all prewarm-born containers are identical)
            freec = st["freec"]
            rel_cap = freec[kn, f_done] >= cores
            m_rel = (((node_ids == kn)[:, None]
                      & (fn_ids_ax == f_done)[None, :])
                     & do_comp & ~rel_cap)
            freec = jnp.where(m_rel, freec + 1, freec)
            nevt = st["nevt"] + (do_comp & rel_cap).astype(jnp.int32)
        if dup:
            # -- racing-copy winner: the first completion among a request's
            # copies is the reference's min-c winner (_on_complete keeps the
            # strictly smaller c, so ties go to the earlier completion
            # event); later copies still release their slot and feed the
            # estimators but change nothing the client sees
            orig_done = (j_done % (n + 1)).astype(jnp.int32)
            take = do_comp & ~st["done0"][orig_done]
            m_win = (oreq_ids == orig_done) & take
            done0 = st["done0"] | m_win
            win_start = jnp.where(m_win, st["start_q"][j_done],
                                  st["win_start"])
            win_fin = jnp.where(m_win, now, st["win_fin"])
            win_node = jnp.where(m_win, kn.astype(jnp.int32),
                                 st["win_node"])
        if hedge:
            # -- hedge deadline fires: eligible when the call is still
            # queued on its node and under the backup budget (mirrors
            # Cluster._maybe_backup: completed/started/attempt-capped
            # fires are no-ops and do not re-arm)
            att, hedge_t, stolen = st["att"], st["hedge_t"], st["stolen"]
            if dyn and freeze:
                # second watch slot (sorted: hedge_t <= hedge_t2): the
                # reference never cancels scheduled watch fires, so a
                # queued-at-kill call keeps its old deadline pending
                # alongside the one re-armed at re-arrival
                hedge_t2 = st["hedge_t2"]
            if dup:
                # any copy's completion pops the watch (_watched.pop in
                # _on_complete): a raced request never hedges again.  In
                # dup mode ``stolen`` records *won* races -- originals whose
                # first completion was a backup copy (steals_won parity)
                hedge_t = jnp.where(m_win, inf, hedge_t)
                stolen = stolen | (m_win & (j_done >= n + 1))
            jh = jnp.argmin(hedge_t).astype(jnp.int32)
            act_able = do_hedge & pend[jh] & (att[jh] < inp["hmax"])
            if dyn:
                # a call lost *mid-execution* keeps its stale req.start
                # after the failure re-route, so every later watch fire is
                # a reference no-op (_maybe_backup's started check) -- it
                # never hedges again; queued-at-kill calls keep hedging
                act_able = act_able & ~st["unhedge"][jh]
            if dyn and freeze:
                # a fire consumes the earliest pending deadline; any later
                # one (a kill survivor) shifts down and stays armed
                m_jh = (oreq_ids == jh) & do_hedge
                hedge_t = jnp.where(m_jh, hedge_t2, hedge_t)
                hedge_t2 = jnp.where(m_jh, inf, hedge_t2)
            else:
                hedge_t = jnp.where((oreq_ids == jh) & do_hedge, inf,
                                    hedge_t)
            old_node = node_of[jh]
            peer_ok = active & (node_ids != old_node)
            if dup:
                # duplicate issue additionally needs a live peer (reference:
                # ``if not others: return`` -- a no-op *without* re-arm)
                steal_ok = act_able & jnp.any(peer_ok)
            else:
                steal_ok = act_able

        if dyn:
            ndone = st["ndone"] + do_comp.astype(jnp.int32)

            # -- kill: wipe the node, schedule the lost for re-arrival ------
            kk = jnp.argmin(killq)
            m_kk = (node_ids == kk)
            lost_slot = jnp.isfinite(fin_s[kk])              # (S,)
            m_lost = jnp.any((idx_s[kk][None, :] == req_ids[:, None])
                             & lost_slot[None, :], axis=1) & do_kill
            if freeze:
                m_lostq = pend & (node_of == kk) & do_kill
                pend = pend & ~m_lostq
                lost_any = m_lost | m_lostq
                # record the _do_fail re-route rank: ex-running keep their
                # launch sequence, ex-queued sort after them (by their
                # enqueue-time priority, resolved at re-arrival)
                rval = jnp.sum(jnp.where(
                    (idx_s[kk][None, :] == req_ids[:, None])
                    & lost_slot[None, :], st["dseq"][kk][None, :], 0),
                    axis=1).astype(jnp.int32)
                rord = jnp.where(m_lost, rval,
                                 jnp.where(m_lostq, jnp.int32(_RORD_Q),
                                           st["rord"]))
            else:
                lost_any = m_lost
            rearr = jnp.where(lost_any, now + detect, rearr)
            nfail = st["nfail"] + jnp.sum(lost_any).astype(jnp.int32)
            if hedge:
                # _do_fail on a hedged cell: the failure retry bumps
                # attempts and voids any earlier hedge credit
                # (_stolen_ids.discard); the re-arrival below re-arms the
                # watch through the insert path, like the reference's
                # _route -> _arm_straggler_watch
                att = jnp.where(lost_any, att + 1, att)
                stolen = stolen & ~lost_any
                if freeze:
                    # queued-at-kill: pending watch fires survive (the
                    # reference's loop callbacks are never cancelled).
                    # Fires landing inside the outage window [kill,
                    # re-arrival] are dead-node no-ops without re-arm, so
                    # only deadlines past it are kept (re-sorted)
                    h1k = jnp.where(hedge_t > now + detect, hedge_t, inf)
                    h2k = jnp.where(hedge_t2 > now + detect, hedge_t2, inf)
                    hedge_t = jnp.where(m_lostq, jnp.minimum(h1k, h2k),
                                        hedge_t)
                    hedge_t2 = jnp.where(m_lostq, jnp.maximum(h1k, h2k),
                                         hedge_t2)
                    # lost mid-execution: the stale req.start makes every
                    # later fire a no-op -- drop both slots outright
                    hedge_t = jnp.where(m_lost, inf, hedge_t)
                    hedge_t2 = jnp.where(m_lost, inf, hedge_t2)
                else:
                    hedge_t = jnp.where(lost_any, inf, hedge_t)
                unhedge = st["unhedge"] | m_lost
            fin_s = jnp.where((m_kk & do_kill)[:, None], inf, fin_s)
            busy = jnp.where(m_kk & do_kill, 0, busy)
            if freeze:   # pull: qn[0] is the global queue -- kills keep it
                qn = jnp.where(m_kk & do_kill, 0, qn)
            dead = dead | (m_kk & do_kill)
            killq = jnp.where(m_kk & do_kill, inf, killq)

            # -- autoscaler tick: queue-per-slot rule on the live state -----
            alldone = ndone >= inp["nreq"]
            n_alive = jnp.sum(active.astype(jnp.int32))
            queued = jnp.sum(qn).astype(jnp.float32)
            prov = st["prov"]
            fire = (do_tick & ~alldone & (prov < inp["maxn"])
                    & (queued > thr * jnp.maximum(n_alive * cores,
                                                  1).astype(jnp.float32)))
            m_new = (node_ids == prov) & fire
            act_t = jnp.where(m_new, now + delay, act_t)
            act_pend = act_pend | m_new
            prov = prov + fire.astype(jnp.int32)
            next_tick = jnp.where(
                do_tick, jnp.where(alldone, inf, now + interval),
                st["next_tick"])

            # -- re-arrival: a lost request re-enters the system ------------
            if freeze:
                # same-instant re-arrivals replay the reference _do_fail
                # order -- node.kill() returns the in-flight dict (launch
                # order) first, then the queue popped in (priority, push
                # seq) order, and _route callbacks run in that sequence;
                # the order decides least-loaded targets and FC counts
                tie = rearr <= jnp.min(rearr)
                ib31 = jnp.int32(2 ** 31 - 1)
                run_k = jnp.where(tie & (rord < _RORD_Q), rord, ib31)
                ir_run = jnp.argmin(run_k).astype(jnp.int32)
                any_run = run_k[ir_run] < ib31
                qp = jnp.where(tie & (rord >= _RORD_Q), fprio, inf)
                if hedge:
                    qk = jnp.where(qp <= jnp.min(qp), st["qseq"], ib31)
                    ir_q = jnp.argmin(qk).astype(jnp.int32)
                else:
                    ir_q = jnp.argmin(qp).astype(jnp.int32)
                ir = jnp.where(any_run, ir_run, ir_q).astype(jnp.int32)
            else:
                ir = jnp.argmin(rearr).astype(jnp.int32)
            m_ir = (req_ids == ir) & do_re
            rearr = jnp.where(m_ir, inf, rearr)
            if not freeze:
                xq = st["xq"] | m_ir     # joins the (virtual) global queue
                enq_t = jnp.where(m_ir, now, st["enq_t"])

        if res:
            # -- request-timeout fire: cancel the queued or running attempt.
            # The invariant "finite to_t => queued xor running" holds
            # because the watch is armed at admission, survives dispatch and
            # is cleared at completion / fire / re-arm, so exactly one of
            # the two branches acts per fire (Cluster._maybe_timeout)
            jt = jnp.argmin(to_t).astype(jnp.int32)
            is_q = pend[jt] & do_to
            slot_match = (idx_s == jt) & jnp.isfinite(fin_s)  # (nodes, S)
            is_run = do_to & ~is_q & jnp.any(slot_match)
            # queued: leave the node queue (scheduler.cancel) and return
            # the admission's E[p] snapshot to the shed gauge, like the
            # reference's queued-cancel -> _on_start
            pend = jnp.where((req_ids == jt) & is_q, False, pend)
            qn = jnp.where((node_ids == node_of[jt]) & is_q, qn - 1, qn)
            qep = qep - jnp.where(is_q, eps[jt], 0.0)
            # running: free the slot mid-flight (scheduler.abort) and
            # account the execution seconds bought and thrown away
            m_rc = slot_match & is_run
            rn = (jnp.argmax(slot_match.ravel()) // n_slots).astype(
                jnp.int32)
            sst_v = jnp.sum(jnp.where(m_rc, sst, 0.0))
            wst = wst + jnp.where(is_run,
                                  jnp.maximum(now - sst_v, 0.0), 0.0)
            fin_s = jnp.where(m_rc, inf, fin_s)
            busy = jnp.where((node_ids == rn) & is_run, busy - 1, busy)
            nto = nto + do_to.astype(jnp.int32)
            to_t = jnp.where((req_ids == jt) & do_to, inf, to_t)
            # retry-or-fail (Cluster._res_fail_or_retry): ``ratt`` already
            # counts this attempt, so the 1-based failed-attempt number is
            # ratt[jt] itself
            can_rt = do_to & on_to & (ratt[jt] < maxa)
            rto = jnp.where((req_ids == jt) & can_rt,
                            now + _res_delay(_res_seq(jt), ratt[jt]), rto)
            nrt = nrt + can_rt.astype(jnp.int32)
            died = do_to & ~can_rt
            nfl = nfl | ((req_ids == jt) & died)
            fcz = jnp.where((req_ids == jt) & died, 1, fcz)
            ndn = ndn + died.astype(jnp.int32)

        # -- arrival / re-arrival: route (freeze) / enqueue, observe --------
        i_orig = jnp.minimum(ai, n)
        if dyn and hedge:
            # arrivals, failure re-arrivals and hedge steals all enter the
            # queue through the same insert path (each is an exclusive
            # event type, so the selection chain below is unambiguous)
            do_ins = do_arr | do_re | steal_ok
            i_ins = jnp.where(do_arr, i_orig, jnp.where(do_re, ir, jh))
        elif dyn:
            do_ins = do_arr | do_re
            i_ins = jnp.where(do_arr, i_orig, ir)
        elif hedge:
            # a steal re-enters the system like an arrival on the target
            # node (reference: target.submit -> receive -> observe_arrival);
            # a dup issue enqueues copy ``attempts + 1`` of request jh
            do_ins = do_arr | steal_ok
            if dup:
                i_dup = ((att[jh] + 1) * (n + 1) + jh).astype(jnp.int32)
                i_ins = jnp.where(do_arr, i_orig, i_dup)
            else:
                i_ins = jnp.where(do_arr, i_orig, jh)
        elif res:
            # a retry re-arrival re-enters through the same insert path as
            # a fresh arrival (reference: loop.schedule(now + delay, _route))
            jr = jnp.argmin(rto).astype(jnp.int32)
            rto = jnp.where((req_ids == jr) & do_rto, inf, rto)
            do_ins = do_arr | do_rto
            i_ins = jnp.where(do_arr, i_orig, jr)
        else:
            do_ins = do_arr
            i_ins = i_orig
        f_i = fnid[i_ins]
        if res:
            # -- admission (Cluster._res_admit, kept in sync line-for-line):
            # count the submission, shed when the queued-E[p] backlog per
            # free slot exceeds the threshold, else snapshot the controller
            # estimate into the gauge and arm the timeout watch.  A shed
            # submission never reaches a node: everything downstream gated
            # on do_ins (node observe, FC log, queue insert, dispatch)
            # stays untouched, exactly like _route returning early.
            do_ins0 = do_ins
            ratt = jnp.where((req_ids == i_ins) & do_ins0, ratt + 1, ratt)
            att_i = ratt[i_ins]          # submissions including this one
            est_z = jnp.where(zrlen[f_i] > 0,
                              zrsum[f_i] / jnp.maximum(zrlen[f_i], 1), 0.0)
            free_tot = jnp.sum(jnp.where(active, cores - busy, 0))
            shed_now = (do_ins0 & (adm_p[0] > 0)
                        & (qep / jnp.maximum(free_tot, 1) > adm_p[1]))
            nsh = nsh + shed_now.astype(jnp.int32)
            sh_rt = shed_now & on_sh & (att_i < maxa)
            rto = jnp.where((req_ids == i_ins) & sh_rt,
                            now + _res_delay(_res_seq(i_ins), att_i), rto)
            nrt = nrt + sh_rt.astype(jnp.int32)
            sh_die = shed_now & ~sh_rt
            nfl = nfl | ((req_ids == i_ins) & sh_die)
            fcz = jnp.where((req_ids == i_ins) & sh_die, 2, fcz)
            ndn = ndn + sh_die.astype(jnp.int32)
            do_ins = do_ins0 & ~shed_now
            eps = jnp.where((req_ids == i_ins) & do_ins, est_z, eps)
            qep = qep + jnp.where(do_ins, est_z, 0.0)
            dl = jnp.where(rto_p[3] > 0, now + rto_p[3],
                           now + rto_p[1] * jnp.maximum(est_z, rto_p[2]))
            to_t = jnp.where((req_ids == i_ins) & do_ins & (rto_p[0] > 0),
                             dl, to_t)
        if freeze:
            # push least-loaded: min busy+queued over nodes, first on ties
            load = jnp.where(active, busy + qn, jnp.int32(2 ** 30))
            k_ll = jnp.argmin(load)
            if dyn:
                k_arr = k_ll         # home routing stays static-capacity
            else:
                # push home invoker: hash start, walk to the first free node
                free_n = (busy < cores) & active
                walk = (home0[i_ins] + node_ids) % jnp.maximum(nodes, 1)
                wfree = free_n[walk] & active
                k_home = jnp.where(jnp.any(wfree), walk[jnp.argmax(wfree)],
                                   home0[i_ins])
                k_arr = jnp.where(route == 1, k_home, k_ll)
            if hedge:
                # steal/copy target: least-loaded *live* peer, the slow node
                # excluded (reference: min(others, key=load), first on
                # ties); with no live peer a steal re-submits to the call's
                # own node (the reference's ``if others else node``) -- dup
                # never reaches the fallback, its steal_ok requires a peer
                load_x = jnp.where(peer_ok, busy + qn, jnp.int32(2 ** 30))
                k_tgt = jnp.where(jnp.any(peer_ok), jnp.argmin(load_x),
                                  old_node)
                k_arr = jnp.where(steal_ok, k_tgt, k_arr)
            k_arr = k_arr.astype(jnp.int32)
        else:
            k_arr = jnp.int32(0)
        en_a = k_arr if freeze else 0
        # pull re-arrivals skip the estimator: the reference re-queues them
        # without a second controller observe_arrival; push re-arrivals go
        # through node.submit -> receive and *are* re-observed
        do_obs = do_ins if freeze else do_arr
        first = narr[en_a, f_i] == 0
        prev_used = jnp.where(first, now, last_t[en_a, f_i])
        m_ea = (jnp.arange(ring.shape[0]) == en_a)
        m_af = (m_ea[:, None] & (fn_ids_ax == f_i)[None, :]) & do_obs
        prev_t = jnp.where(m_af, prev_used, prev_t)
        last_t = jnp.where(m_af, now, last_t)
        narr = jnp.where(m_af, narr + 1, narr)
        if stream and not freeze:
            # chunk-rebased head-window validity counter: counts only fresh
            # arrivals of this chunk (carried queued rows were preloaded by
            # the handoff), matching the CSR fnev row order
            qcnt = jnp.where((fn_ids_ax == f_i) & do_arr,
                             st["qcnt"] + 1, st["qcnt"])
        if hedge and not dup:
            # the stolen call leaves its old node's queue (scheduler.cancel);
            # duplicate mode races a fresh copy instead -- the original
            # stays queued on its own node
            qn = jnp.where((node_ids == old_node) & steal_ok, qn - 1, qn)
        qn = jnp.where((node_ids == k_arr) & do_ins, qn + 1, qn)
        ai = ai + do_arr.astype(jnp.int32)
        if freeze:
            if fc_push:
                # bounded per-(node, fn) arrival ring: log, then count the
                # window (the logged time itself is inside it, matching the
                # reference's observe-then-rank order)
                fcr, fcp = st["fcr"], st["fcp"]
                pos_fc = fcp[k_arr, f_i]
                m_nf = ((node_ids == k_arr)[:, None]
                        & (fn_ids_ax == f_i)[None, :]) & do_ins
                fcr = jnp.where(m_nf[:, :, None]
                                & (jnp.arange(fc_ring) == pos_fc), now, fcr)
                fcp = jnp.where(m_nf, (pos_fc + 1) % fc_ring, fcp)
                cnt_i = jnp.sum(fcr[k_arr, f_i]
                                > now - horizon).astype(jnp.float32)
            else:
                cnt_i = cnt[i_ins]
            est_i = jnp.where(rlen[en_a, f_i] > 0,
                              rsum[en_a, f_i]
                              / jnp.maximum(rlen[en_a, f_i], 1), 0.0)
            prio_i = (coef[0] * now + coef[1] * prev_used
                      + (coef[2] + coef[3] * cnt_i) * est_i)
            pend = pend.at[i_ins].set(jnp.where(do_ins, True, pend[i_ins]))
            fprio = fprio.at[i_ins].set(jnp.where(do_ins, prio_i,
                                                  fprio[i_ins]))
            node_of = node_of.at[i_ins].set(jnp.where(do_ins, k_arr,
                                                      node_of[i_ins]))
            if res:
                qsq = jnp.where((req_ids == i_ins) & do_ins, st["stp"],
                                st["qsq"])
            if hedge:
                # (re-)arm the watch from the controller estimate -- both
                # fresh arrivals and just-stolen/raced calls keep being
                # watched (the watch always tracks the *original* request)
                est_h = jnp.where(crlen[f_i] > 0,
                                  crsum[f_i] / jnp.maximum(crlen[f_i], 1),
                                  0.0)
                arm = now + inp["hmult"] * jnp.maximum(est_h, inp["hfloor"])
                w_ins = (i_ins % (n + 1)).astype(jnp.int32)
                m_w = (oreq_ids == w_ins) & do_ins
                if dyn:
                    # merge the new deadline into the sorted slot pair: a
                    # failure re-arrival may find the pre-kill deadline
                    # still pending (see the kill handler above), and both
                    # keep firing in the reference
                    lo1 = jnp.minimum(hedge_t, hedge_t2)
                    hi1 = jnp.maximum(hedge_t, hedge_t2)
                    hedge_t = jnp.where(m_w, jnp.minimum(lo1, arm), hedge_t)
                    hedge_t2 = jnp.where(
                        m_w, jnp.minimum(hi1, jnp.maximum(lo1, arm)),
                        hedge_t2)
                else:
                    hedge_t = jnp.where(m_w, arm, hedge_t)
                att = jnp.where((oreq_ids == jh) & steal_ok, att + 1, att)
                nbk = st["nbk"] + steal_ok.astype(jnp.int32)
                if dup:
                    # dup ``stolen`` (won races) is set at completion above;
                    # ndone counts first completions only -- once every
                    # request has a winner no event can change the outputs
                    ndone = st["ndone"] + take.astype(jnp.int32)
                else:
                    stolen = stolen | ((oreq_ids == jh) & steal_ok)
                    ndone = st["ndone"] + do_comp.astype(jnp.int32)
                # queue-push sequence: a steal re-pushes the call on its
                # target, so push order decouples from event-index order --
                # the reference's stable queue breaks priority ties by it
                qseq = jnp.where((req_ids == i_ins) & do_ins, st["stepc"],
                                 st["qseq"])

        # -- dispatch: one launch restores the "queued => saturated"
        # invariant (always-warm admission never blocks); a newly-activated
        # node keeps its activation event pending until it is saturated or
        # the queue drains, so multi-slot backfill costs one step per launch
        if dyn:
            ka = jnp.argmin(jnp.where(act_pend, act_t, inf)).astype(jnp.int32)
        if freeze:
            # an event only changes its own node's queue/slots
            k_d = jnp.where(do_ins, k_arr, kn)
            if dyn:
                k_d = jnp.where(do_act, ka, k_d)
            if pool_rd:
                k_d = jnp.where(do_rd, st["rdn"], k_d)
            if res:
                # a running-timeout frees a slot on the watched node and
                # backfills there (scheduler.abort -> _dispatch)
                k_d = jnp.where(do_to & is_run, rn, k_d)
            prio_vec = jnp.where(pend & (node_of == k_d), fprio, inf)
            if hedge or res:
                # exact priority ties (common under SEPT/FC: same fn, same
                # estimate) resolve by queue push order, like the
                # reference's stable per-node PriorityQueue -- hedge steals
                # and retry re-arrivals both re-push out of index order
                best = jnp.min(prio_vec)
                seq_v = qseq if hedge else qsq
                qv = jnp.where(prio_vec == best, seq_v, jnp.int32(2 ** 30))
                j = jnp.argmin(qv).astype(jnp.int32)
                has_q = best < inf
                prio_j = best
            else:
                j = jnp.argmin(prio_vec).astype(jnp.int32)
                has_q = prio_vec[j] < inf
                prio_j = prio_vec[j]
        else:
            # pull: the invoker with the most free slots pulls the global
            # best head, ranked fresh from the controller estimator --
            # O(F) over the function-queue heads (see the docstring)
            fs = jnp.where(active, cores - busy, -1)
            k_d = jnp.argmax(fs).astype(jnp.int32)
            est_f = jnp.where(rlen[0] > 0,
                              rsum[0] / jnp.maximum(rlen[0], 1), 0.0)
            if stream:
                # CSR per-function event lists: fnev is the n+1 chunk rows
                # grouped by function, fnst the per-function offsets --
                # O(n + F) memory where the dense (F, kq) table would be
                # O(F * max-calls-per-fn).  Overruns clip onto the sentinel
                # row (t = +inf) and are masked by ``valid`` anyway.
                idx_f = fnev_flat[jnp.clip(fn_start + head, 0, n)]
                valid = head < qcnt
            else:
                kmax = fn_ev.shape[1] - 1
                idx_f = jnp.take_along_axis(
                    fn_ev, jnp.minimum(head, kmax)[:, None], axis=1)[:, 0]
                valid = head < narr[0]
            if use_fc:               # FC window counts: static-stream lookup
                k0 = jnp.searchsorted(t_arr, now - horizon, side="right")
                cnt_f = (cumf[ai] - cumf[k0]).astype(jnp.float32)
                w_est = coef[2] + coef[3] * cnt_f
            else:
                w_est = coef[2]
            base_f = coef[1] * prev_t[0] + w_est * est_f
            prio_f = coef[0] * t_arr[idx_f] + base_f
            if dyn:                  # enqueue-clock term (see _PULL_COEF_DYN)
                prio_f = prio_f + coef[4] * now
            prio_f = jnp.where(valid, prio_f, inf)
            best = jnp.min(prio_f)
            # first-index tie-break over the (virtual) global queue
            j = jnp.min(jnp.where(valid & (prio_f == best), idx_f, n))
            has_q = j < n
            prio_j = best
            if dyn:
                # re-queued lost requests live outside the head windows;
                # same per-function pull formula, but their enqueue clock is
                # the recorded first-dispatch time (their reference r')
                prio_x = jnp.where(xq, coef[0] * t_arr + base_f[fnid]
                                   + coef[4] * st["rq_rt"], inf)
                j_x = jnp.argmin(prio_x).astype(jnp.int32)
                best_x = prio_x[j_x]
                # equal-priority ties resolve by global queue *append* order
                # (the reference's first-in-queue argmin): a re-queued call
                # re-enters at its re-queue time, after every fresh call
                # that was already waiting
                pick_x = (best_x < prio_j) | ((best_x == prio_j)
                                              & (st["enq_t"][j_x] < t_arr[j]))
                j = jnp.where(pick_x, j_x, j)
                prio_j = jnp.minimum(best_x, prio_j)
                has_q = prio_j < inf
        if dyn:
            allow = do_ins | do_comp | do_act
            can = allow & active[k_d] & (busy[k_d] < cores) & has_q
        elif hedge:
            # an ineligible hedge fire is a pure no-op event: no dispatch
            can = (do_ins | do_comp) & (busy[k_d] < cores) & has_q
        elif res:
            # queued-timeouts and shed inserts free no slot: no dispatch
            can = ((do_ins | do_comp | (do_to & is_run))
                   & (busy[k_d] < cores) & has_q)
        else:
            can = ~none_left & (busy[k_d] < cores) & has_q
        if pool:
            # -- container table, acquire half (ContainerPool.acquire) on
            # node k_d for call j, attempted whenever ``can`` (the
            # reference's dispatch loop reaching this head)
            f_j = fnid[j]
            size = fmb[f_j]
            fn_k, mb_k, bz_k = pfn[k_d], pmb[k_d], pbz[k_d]
            lu_k, sq_k = plu[k_d], psq[k_d]
            warm_r = (fn_k == f_j) & ~bz_k
            warm_hit = jnp.any(warm_r)
            stem_r = fn_k == -1
            has_stem = jnp.any(stem_r)
            used = jnp.sum(mb_k)
            # LRU eviction of idle rows until the function's size fits
            # (every idle row qualifies: none holds f, else a warm hit)
            go_ev = can & ~warm_hit & ~has_stem & (used + size > pmem)
            evr, used_ev = _evict_lru((fn_k != -2) & ~bz_k, lu_k, sq_k, mb_k,
                                      used, size, pmem, go_ev)
            fn_e = jnp.where(evr, -2, fn_k)
            fits = used_ev + size <= pmem
            create = ~warm_hit & ~has_stem & fits
            try_acq = can
            can = can & (warm_hit | has_stem | fits)
            empty = fn_e == -2
            r_new = empty & (jnp.cumsum(empty.astype(jnp.int32)) == 1)
            r_sel = jnp.where(warm_hit, _mru_row(warm_r, lu_k, sq_k),
                              jnp.where(has_stem, _first_row(stem_r, sq_k),
                                        r_new)) & can
            m_new = r_new & create & can
            nxt_sq = st["pnx"][k_d]
            fn_n = jnp.where(r_sel, f_j, fn_e)
            mb_n = jnp.where(m_new, size, jnp.where(evr, 0, mb_k))
            sq_n = jnp.where(m_new, nxt_sq, sq_k)
            nxt_sq = nxt_sq + m_new.any().astype(jnp.int32)
            # a stem cell taken: top the stem pool back up while memory
            # allows (the converted cell keeps its container_mb)
            n_add = jnp.where(
                has_stem & ~warm_hit & can,
                jnp.clip(jnp.minimum(
                    _PREWARM_COUNT - (jnp.sum(stem_r.astype(jnp.int32)) - 1),
                    (pmem - used) // jnp.maximum(pcmb, 1)), 0,
                    _PREWARM_COUNT), 0)
            empty = fn_n == -2
            rank = jnp.cumsum(empty.astype(jnp.int32))
            add = empty & (rank <= n_add)
            fn_n = jnp.where(add, -1, fn_n)
            mb_n = jnp.where(add, pcmb, mb_n)
            sq_n = jnp.where(add, nxt_sq + rank - 1, sq_n)
            nxt_sq = nxt_sq + n_add
            m_kr = (node_ids == k_d)[:, None] & try_acq
            pfn = jnp.where(m_kr, fn_n[None, :], pfn)
            pmb = jnp.where(m_kr, mb_n[None, :], pmb)
            pbz = jnp.where(m_kr, (bz_k | r_sel)[None, :], pbz)
            lu_n = jnp.where(r_sel, now, jnp.where(add, 0.0, lu_k))
            plu = jnp.where(m_kr, lu_n[None, :], plu)
            psq = jnp.where(m_kr, sq_n[None, :], psq)
            pnx = jnp.where((node_ids == k_d) & try_acq, nxt_sq, st["pnx"])
            ppk = jnp.maximum(st["ppk"], jnp.sum((pfn != -2).astype(
                jnp.int32), axis=1))
            nevt = nevt + jnp.where(try_acq,
                                    jnp.sum(evr.astype(jnp.int32)), 0)
            ncold = st["ncold"] + (can & ~warm_hit).astype(jnp.int32)
            ncre = st["ncre"] + (can & create).astype(jnp.int32)
            nblk = st["nblk"] + (try_acq & ~can).astype(jnp.int32)
            coldq = jnp.where((oreq_ids == j) & can, ~warm_hit, st["coldq"])
            cost_j = cost[j] + jnp.where(
                warm_hit, 0.0,
                jnp.where(has_stem, OURS_PREWARM_EXTRA, OURS_COLD_EXTRA))
            r_idx = jnp.argmax(r_sel).astype(jnp.int32)
        elif cold:
            # container acquire at dispatch (ContainerPool.acquire): a free
            # (node, fn) container is a warm hit; otherwise the prewarm pool
            # serves -- the ample-memory eligibility bound guarantees the
            # pool never creates from scratch, so every miss charges exactly
            # OURS_PREWARM_EXTRA on the management channel
            f_j = fnid[j]
            warm_hit = freec[k_d, f_j] > 0
            cost_j = cost[j] + jnp.where(warm_hit, 0.0, OURS_PREWARM_EXTRA)
            m_acq = (((node_ids == k_d)[:, None]
                      & (fn_ids_ax == f_j)[None, :]) & can & warm_hit)
            freec = jnp.where(m_acq, freec - 1, freec)
            ncold = st["ncold"] + (can & ~warm_hit).astype(jnp.int32)
            # per-request cold flag: the *original's own* dispatch decides
            # it (dup copies never set it; winner propagation does not copy
            # cold_start in the reference); last-wins across re-dispatches
            coldq = jnp.where((oreq_ids == j) & can, ~warm_hit, st["coldq"])
        else:
            cost_j = cost[j]
        if het:
            # effective speed of the routed node at dispatch time divides
            # the management cost and the execution (OursNodeSim._launch);
            # padding episodes carry node -1 / factor 1 and never match
            slow = jnp.prod(jnp.where((inp["epn"] == k_d)
                                      & (inp["ept0"] <= now)
                                      & (now < inp["ept1"]),
                                      inp["epf"], 1.0))
            eff = inp["spd"][k_d] / slow
            exec_start = jnp.maximum(now, chan[k_d]) + cost_j / eff
        else:
            exec_start = jnp.maximum(now, chan[k_d]) + cost_j
        m_kd = (node_ids == k_d)
        chan = jnp.where(m_kd & can, exec_start, chan)
        fin_j = exec_start + (p[j] / eff if het else p[j])
        slot_free = jnp.isinf(fin_s[k_d]) & (slot_ids < cores)
        s = jnp.argmax(slot_free)
        m_ds = (m_kd[:, None] & (slot_ids == s)[None, :]) & can
        fin_s = jnp.where(m_ds, fin_j, fin_s)
        idx_s = jnp.where(m_ds, j, idx_s)
        if res:
            sst = jnp.where(m_ds, exec_start, sst)
            # the dispatched call leaves the shed gauge (the reference
            # on_start hook): subtract the same stored snapshot its
            # admission added, so the +/- sequence matches bit-for-bit
            qep = qep - jnp.where(can, eps[j], 0.0)
        if dyn and freeze:
            # launch-sequence stamp: orders the in-flight half of a kill's
            # lost set (the reference in_flight dict is insertion-ordered)
            dseq = jnp.where(m_ds, st["dcnt"], st["dseq"])
            dcnt = st["dcnt"] + can.astype(jnp.int32)
        if het and freeze:
            sspd = jnp.where(m_ds, eff, st["sspd"])
        if pool:
            prow = jnp.where(m_ds, r_idx, st["prow"])
        busy = jnp.where(m_kd & can, busy + 1, busy)
        qn = jnp.where(m_kd & can, qn - 1, qn)
        if freeze:
            pend = pend.at[j].set(jnp.where(can, False, pend[j]))
            if hedge:
                # a dispatched call's watch can never act again (steal: the
                # call left the queue; dup: a started original makes fires
                # no-ops without re-arm): clear it so no-op fires do not
                # consume scan steps.  Under dup the oreq mask is all-False
                # for copy dispatches (j >= n+1), which keep the watch live.
                hedge_t = jnp.where((oreq_ids == j) & can, inf, hedge_t)
                if dyn:
                    hedge_t2 = jnp.where((oreq_ids == j) & can, inf,
                                         hedge_t2)
            if dup:
                # winner recording at completion needs the copy's own
                # exec_start, so it is carried per queue entry
                start_q = jnp.where((req_ids == j) & can, exec_start,
                                    st["start_q"])
        else:
            if dyn:
                from_x = can & pick_x
                xq = jnp.where((req_ids == j) & from_x, False, xq)
                adv = can & ~pick_x
                # the reference sets r' at node receive, i.e. the pull moment
                rq_rt = jnp.where((req_ids == j) & can, now, st["rq_rt"])
            else:
                adv = can
            head = jnp.where((fn_ids_ax == fnid[j]) & adv, head + 1, head)
        if dyn:
            # keep the activation event current while the new node can
            # still absorb queued work
            still = do_act & can & (jnp.sum(qn) > 0) & (busy[ka] < cores)
            act_pend = jnp.where((node_ids == ka) & do_act, still, act_pend)
        if pool_rd:
            # a completion's dispatch loop goes on while the node has a
            # free slot and a queue: arrivals need no continuation (a
            # queue beside a free slot means the last attempt blocked and
            # evicted every idle row, so a second head finds nothing)
            more = (can & (do_comp | do_rd) & (busy[k_d] < cores)
                    & jnp.any(pend & (node_of == k_d)))
            rdt = jnp.where(more, now, inf)
            rdn = jnp.where(more, k_d, st["rdn"])
            pdone = st["pdone"] + do_comp.astype(jnp.int32)

        # per-dispatch record: scattered into per-request arrays after the
        # scan, so the carry holds no O(n) output state (the pull carry is
        # O(F + nodes), which is what makes long streams cheap)
        out = (jnp.where(can, j, n), exec_start, fin_j, prio_j, k_d)
        nxt = {"ai": ai, "head": head, "fin_s": fin_s, "idx_s": idx_s,
               "busy": busy, "qn": qn, "chan": chan,
               "ring": ring, "rsum": rsum, "rlen": rlen, "rpos": rpos,
               "last_t": last_t, "prev_t": prev_t, "narr": narr}
        if freeze:
            nxt.update(pend=pend, fprio=fprio, node_of=node_of)
        if fc_push:
            nxt.update(fcr=fcr, fcp=fcp)
        if pool:
            nxt.update(pfn=pfn, pmb=pmb, pbz=pbz, plu=plu, psq=psq, pnx=pnx,
                       ppk=ppk, prow=prow, ncold=ncold, nevt=nevt,
                       ncre=ncre, nblk=nblk, coldq=coldq)
            if pool_rd:
                nxt.update(rdt=rdt, rdn=rdn, pdone=pdone)
        elif cold:
            nxt.update(freec=freec, ncold=ncold, nevt=nevt, coldq=coldq)
        if hedge:
            nxt.update(hedge_t=hedge_t, att=att, nbk=nbk, stolen=stolen,
                       cring=cring, crsum=crsum, crlen=crlen, crpos=crpos,
                       qseq=qseq, stepc=st["stepc"] + 1, ndone=ndone)
        if dup:
            nxt.update(done0=done0, win_start=win_start, win_fin=win_fin,
                       win_node=win_node, start_q=start_q)
        if het and freeze:
            nxt.update(sspd=sspd)
        if dyn:
            if freeze:
                nxt.update(dseq=dseq, dcnt=dcnt, rord=rord)
            nxt.update(act_t=act_t, dead=dead, killq=killq,
                       act_pend=act_pend, rearr=rearr, next_tick=next_tick,
                       prov=prov, nfail=nfail, ndone=ndone)
            if hedge:
                nxt.update(unhedge=unhedge)
                if freeze:
                    nxt.update(hedge_t2=hedge_t2)
            if not freeze:
                nxt.update(xq=xq, rq_rt=rq_rt, enq_t=enq_t)
        if res:
            nxt.update(to_t=to_t, rto=rto, eps=eps, qep=qep, ratt=ratt,
                       nfl=nfl, fcz=fcz, sst=sst, nto=nto, nsh=nsh,
                       nrt=nrt, wst=wst, ndn=ndn, qsq=qsq,
                       stp=st["stp"] + 1, zring=zring,
                       zrsum=zrsum, zrlen=zrlen, zrpos=zrpos)
        if stream and not freeze:
            nxt.update(qcnt=qcnt)
        return nxt, out

    # the loop carry is the packed (clk, ctr) plane pair; the dict view the
    # step works on is reconstructed by static slicing, which XLA folds into
    # the step body (the unpack/update/pack chain fuses away)
    layout = _carry_layout(inp, n_nodes=n_nodes, n_slots=n_slots,
                           window=window, freeze=freeze, fc_push=fc_push,
                           dyn=dyn, het=het, hedge=hedge, cold=cold,
                           dup=dup, n_copies=n_copies, fc_ring=fc_ring,
                           res=res, stream=stream, pool_w=pool_w)
    return layout, pick, step


# the per-request inputs a duplicate-mode bucket shares across its copies
_COPY_TILED = ("fnid", "p", "cost", "cnt", "home0")


def _event_loop(clk, ctr, inp, *, n_steps, **static):
    """The jnp event step of a whole chunk: one loop over the batch whose
    body is :func:`_cell_step` vmapped over the cells.  It runs at most
    ``n_steps`` steps and stops as soon as no cell of the chunk has an event
    left (each cell's own ``none_left``: every candidate +inf, or at or past
    a stream chunk's horizon).  Nothing revives an idle cell and its step is
    a no-op, so the early stop changes no result; it only leaves the
    ``stepc``/``stp`` push stamps short of the steps a longer loop runs.

    The loop is outside the vmap: a per-cell ``while_loop`` would batch
    into a ``select`` of the whole carry, record buffers included, at every
    step.  Step ``i`` writes its records into row ``i`` of ``(n_steps, B)``
    buffers preallocated at the sentinel request ``n``, so the rows of steps
    that never ran land where an idle step's would.  Returns what
    :func:`_cell_summary` returns, batched, with the loop's step count (an
    int32 scalar, the same for every cell slot) under ``"steps"`` in the
    summary dict; stream chunks return the final planes and the raw
    records."""
    import jax
    import jax.numpy as jnp

    step_inp = inp
    if static["dup"]:
        # tiled once here: the loop body would otherwise rebuild them
        step_inp = dict(inp, **{k: jnp.tile(inp[k], (1, static["n_copies"]))
                                for k in _COPY_TILED})
    cell = partial(_cell_step, **static)

    def first(clk, ctr, inp):
        layout, pick, _ = cell(inp)
        return pick(layout.unpack(clk, ctr))

    def advance(clk, ctr, ev, inp):
        layout, pick, step = cell(inp)
        nxt, rec = step(layout.unpack(clk, ctr), ev)
        clk, ctr = layout.pack(nxt)
        return clk, ctr, rec, pick(layout.unpack(clk, ctr))

    advance_b = jax.vmap(advance)
    ev = jax.vmap(first)(clk, ctr, step_inp)
    n = inp["t"].shape[1] - 1
    specs = jax.eval_shape(advance_b, clk, ctr, ev, step_inp)[2]
    recs = tuple(jnp.full((n_steps,) + s.shape, n if k == 0 else 0, s.dtype)
                 for k, s in enumerate(specs))

    def more(c):
        i, live = c[:2]
        return (i < n_steps) & live

    def body(c):
        i, _, clk, ctr, ev, recs = c
        clk, ctr, rec, ev = advance_b(clk, ctr, ev, step_inp)
        recs = tuple(jax.lax.dynamic_update_index_in_dim(r, x, i, 0)
                     for r, x in zip(recs, rec))
        # the idle test fuses into the step; the condition reads a scalar
        return i + 1, ~jnp.all(ev[2]), clk, ctr, ev, recs

    steps, _, clk, ctr, _, recs = jax.lax.while_loop(
        more, body, (jnp.int32(0), ~jnp.all(ev[2]), clk, ctr, ev, recs))
    recs = tuple(r.T for r in recs)
    out = jax.vmap(partial(_cell_summary, **static))(clk, ctr, recs, inp)
    if not static.get("stream"):
        out[-1]["steps"] = steps
    return out


def _cell_summary(clk, ctr, recs, inp, *, n_nodes, n_slots, window, freeze,
                  fc_push, dyn, het, hedge, cold, dup, n_copies, fc_ring,
                  res=False, stream=False, pool_w=0, **_):
    """One cell's results from its final ``(clk, ctr)`` planes and its
    ``(j, start, finish, prio, node)`` step records (vmapped by
    :func:`_event_loop`): per-request arrays and the counters the host
    unpacks, in the form each feature set needs."""
    import jax.numpy as jnp

    j_s, es_s, fs_s, pj_s, kd_s = recs
    if stream:
        # chunked-stream mode: the host handoff needs the final carry
        # planes (everything a summary would report lives in them) plus the
        # raw dispatch records -- last-wins resolution across re-dispatches
        # happens host-side in global chunk order for every feature set
        return (clk, ctr), recs
    n = inp["t"].shape[0] - 1
    pool = cold and pool_w > 0
    pool_rd = pool and freeze
    layout = _carry_layout(inp, n_nodes=n_nodes, n_slots=n_slots,
                           window=window, freeze=freeze, fc_push=fc_push,
                           dyn=dyn, het=het, hedge=hedge, cold=cold,
                           dup=dup, n_copies=n_copies, fc_ring=fc_ring,
                           res=res, stream=stream, pool_w=pool_w)
    state = layout.unpack(clk, ctr)
    aux = {}
    if cold:
        aux.update(ncold=state["ncold"], nevt=state["nevt"],
                   coldq=state["coldq"])
    if pool:
        aux.update(ncre=state["ncre"], ppk=state["ppk"], nblk=state["nblk"])
        if pool_rd:
            aux.update(pdone=state["pdone"])
    if hedge:
        # steal mode: every stolen call completes on its hedge target, so
        # distinct-stolen == steals won; dup mode: ``stolen`` marks
        # originals whose race was won by a backup copy (accounting parity
        # with Cluster either way).  ndone lets the caller detect an
        # exhausted optimistic step budget.
        aux.update(nbk=state["nbk"],
                   nstl=jnp.sum(state["stolen"].astype(jnp.int32)),
                   att=state["att"], ndone=state["ndone"])
    if dyn:
        # a lost request is dispatched twice; XLA scatter order over
        # duplicate indices is undefined, so the last-wins resolution
        # happens host-side in step order (see _run_scan_bucket)
        summary = {"nfail": state["nfail"], "ndone": state["ndone"],
                   "prov": state["prov"], "act_t": state["act_t"],
                   "dead": state["dead"], **aux}
        if freeze:
            summary.update(prio=state["fprio"], node=state["node_of"])
        return (j_s, es_s, fs_s, pj_s, kd_s), summary
    if res:
        # a timed-out-and-retried request is dispatched more than once, so
        # the step records resolve host-side last-wins like dyn; ``ndn``
        # lets the caller verify the step budget covered every lifecycle
        summary = {"nto": state["nto"], "nsh": state["nsh"],
                   "nrt": state["nrt"], "wst": state["wst"],
                   "nfl": state["nfl"], "fcz": state["fcz"],
                   "ratt": state["ratt"], "ndn": state["ndn"],
                   "prio": state["fprio"], "node": state["node_of"]}
        return (j_s, es_s, fs_s, pj_s, kd_s), summary
    if dup:
        # a raced request's client-visible outcome is its first-completed
        # copy (the reference run() back-copies the winner's
        # start/finish/node onto the original); copy-0 keeps the frozen
        # arrival priority, which winner propagation never overwrites
        return (state["win_start"], state["win_fin"],
                state["fprio"][:n + 1], state["win_node"], aux)
    # one batched scatter per output; can=False steps landed on sentinel n
    start = jnp.zeros(n + 1).at[j_s].set(es_s)
    finish = jnp.zeros(n + 1).at[j_s].set(fs_s)
    if freeze:
        prio = state["fprio"]        # frozen at arrival, never overwritten
        node = state["node_of"]
    else:
        prio = jnp.zeros(n + 1).at[j_s].set(pj_s)
        node = jnp.zeros(n + 1, dtype=jnp.int32).at[j_s].set(kd_s)
    return start, finish, prio, node, aux


# ---------------------------------------------------------------------------
# compilation cache keyed by padded bucket shape
# ---------------------------------------------------------------------------
# Shapes are padded to powers of two (requests, nodes, slots, functions and
# batch) so a whole sweep resolves to a handful of distinct bucket keys; each
# key holds one jitted vmapped kernel, shared across run_sweep calls, so the
# XLA compile is paid once per bucket per process.
SCAN_BATCH_MAX = 256         # default cells/chunk (auto-tuner may override)
# async dispatch window: chunks of a bucket are dispatched ahead of the host
# sync so XLA overlaps transfer and compute, but every in-flight chunk pins
# its host inputs (hedge re-dispatch needs them) and its device results, so
# the window caps peak memory
SCAN_INFLIGHT = int(os.environ.get("REPRO_SCAN_INFLIGHT", "4"))
# one-time per-(bucket-shape, backend) chunk-size measurement; disable with
# REPRO_SCAN_AUTOTUNE=0 to pin SCAN_BATCH_MAX.  Candidate chunks are capped
# by the REPRO_SCAN_MEM_MB device-footprint budget.
SCAN_AUTOTUNE = os.environ.get("REPRO_SCAN_AUTOTUNE", "1") != "0"
SCAN_MEM_MB = float(os.environ.get("REPRO_SCAN_MEM_MB", "512"))
# resident compiled runners (LRU beyond this); long sweep sessions over
# ever-changing shapes can bound their footprint via the environment
SCAN_CACHE_MAX = int(os.environ.get("REPRO_SCAN_CACHE_MAX", "32"))


@dataclass
class _CacheEntry:
    """Compiled state for one bucket *shape*, across every batch size it has
    been dispatched at.  Folding the batch axis into the entry (instead of
    the cache key) means tail chunks, auto-tune candidates and degraded-cell
    retries extend an existing entry rather than churning LRU eviction of
    other shapes' runners."""

    runners: dict = field(default_factory=dict)    # bsz -> (init_c, scan_c)
    compile_s: dict = field(default_factory=dict)  # bsz -> seconds
    hits: int = 0                 # chunk dispatches that reused a runner
    chunk: int | None = None      # auto-tuned cells/chunk (None = untuned)


_SCAN_CACHE: dict[tuple, _CacheEntry] = {}   # shape key -> entry (LRU order)
_SCAN_CACHE_STATS = {"hits": 0, "misses": 0}

# per-chunk dispatch timing records (input build vs compile vs device
# dispatch vs host sync), appended by ``_run_scan_bucket`` and surfaced by
# ``engine_bench --rows mega``; bounded so long sessions don't grow them
_SCAN_TIMINGS: list[dict] = []
_SCAN_TIMINGS_MAX = 4096
# process-wide self time of each scan-entry phase (``_phase``): name ->
# [seconds, count]; the stack holds the child seconds of each open phase
_PHASE_TOTALS: dict[str, list] = {}
_PHASE_STACK: list[float] = []
# event-step slots dispatched (padded cells and steps included), the
# arrival + completion steps of the real calls in them, and the steps the
# step actually runs (see ``_exec_steps``); for container-table buckets the
# cell-nodes dispatched (padded cells included), their table rows, and the
# peak resident containers of the real cell-nodes, summed
_STEP_COUNTS = {"step_slots": 0, "call_steps": 0, "exec_steps": 0,
                "pool_node_slots": 0, "pool_rows": 0, "pool_peak": 0}


def _pow2(x: int) -> int:
    """Smallest power of two >= x (>= 1)."""
    return 1 << max(0, int(x) - 1).bit_length()


def _bucket_tag(shape_key: tuple) -> str:
    """Human-readable stats/timing key for one bucket shape."""
    return ("mask=%#x,n=%d,nodes=%d,slots=%d,fns=%d,kq=%d,win=%d,ring=%d,"
            "ep=%d,cp=%d,pool=%d,xtra=%d" % shape_key)


def scan_cache_stats() -> dict:
    """Bucket-cache counters: ``misses`` = runner compilations in this
    process, ``hits`` = chunk dispatches that reused one, ``size`` =
    resident compiled runners across all bucket shapes, and ``entries`` =
    per-shape detail (hit count, compiled batch sizes, compile seconds and
    the auto-tuned chunk size)."""
    entries = {
        _bucket_tag(k): {
            "hits": e.hits,
            "batches": sorted(e.runners),
            "compiles": len(e.compile_s),
            "compile_s": round(sum(e.compile_s.values()), 6),
            "chunk": e.chunk,
        }
        for k, e in _SCAN_CACHE.items()
    }
    return {**_SCAN_CACHE_STATS,
            "size": sum(len(e.runners) for e in _SCAN_CACHE.values()),
            "entries": entries}


def scan_cache_clear() -> None:
    _SCAN_CACHE.clear()
    _SCAN_CACHE_STATS["hits"] = 0
    _SCAN_CACHE_STATS["misses"] = 0


def scan_bucket_timings() -> list[dict]:
    """Per-chunk dispatch timing records (most recent last).  Each record:
    ``bucket`` tag, ``bsz`` (padded batch), ``cells`` (real cells), and
    seconds split into ``build_s`` (host input fill), ``compile_s`` (XLA
    compile, zero on cache hits), ``dispatch_s`` (device call issue),
    ``wait_s`` (host blocked on the device), ``unpack_s`` (copy back,
    budget checks and per-cell unpack) and ``sync_s`` (from the block to
    the end of the budget checks, the per-cell unpack left out).  A bucket
    whose chunk size was auto-tuned additionally carries one ``cells == 0``
    record with the probe wall in ``tune_s`` and the probes' compiles in
    ``compile_s`` -- one-time setup cost.  Every key but ``sync_s`` is
    the self time of its :func:`scan_phase_totals` phase in that chunk."""
    return list(_SCAN_TIMINGS)


def scan_phase_totals() -> dict:
    """Self time and count of each scan-entry phase since the last
    :func:`scan_timings_clear`, as ``{phase: {"s": seconds, "n": count}}``,
    plus three step counters: ``step_slots`` (cells x event steps
    dispatched, padding included), ``call_steps`` (the arrival and
    completion step of every real call in them) and ``exec_steps`` (the
    event steps the step runs, :func:`_exec_steps`), and three of the
    container table: ``pool_node_slots`` (cell-nodes dispatched on it,
    padded cells and nodes included), ``pool_rows`` (their table rows) and
    ``pool_peak`` (the real cell-nodes' peak resident containers, summed).  Each phase is also a
    ``fastpath.<phase>`` span in a ``jax.profiler`` trace: ``entry`` (a
    batch entry), ``prepare`` (cell features and bucketing), ``tune``,
    ``compile``, ``fill``, ``dispatch``, ``wait`` and ``unpack``.  A
    phase's self time leaves out the phases nested in it."""
    out: dict = {name: {"s": s, "n": n}
                 for name, (s, n) in _PHASE_TOTALS.items()}
    out.update(_STEP_COUNTS)
    return out


def scan_timings_clear() -> None:
    """Reset the timing log, the phase totals and the step counters."""
    _SCAN_TIMINGS.clear()
    _PHASE_TOTALS.clear()
    for k in _STEP_COUNTS:
        _STEP_COUNTS[k] = 0


def _record_timing(rec: dict) -> None:
    if len(_SCAN_TIMINGS) >= _SCAN_TIMINGS_MAX:
        del _SCAN_TIMINGS[:_SCAN_TIMINGS_MAX // 2]
    _SCAN_TIMINGS.append(rec)


def _timing_record(tag: str, bsz: int, cells: int) -> dict:
    return {"bucket": tag, "bsz": bsz, "cells": cells, "build_s": 0.0,
            "compile_s": 0.0, "dispatch_s": 0.0, "wait_s": 0.0,
            "unpack_s": 0.0, "sync_s": 0.0}


@contextlib.contextmanager
def _phase(name: str, rec: dict | None = None, key: str | None = None,
           **args):
    """One scan-entry phase: a ``fastpath.<name>`` profiler span (with
    ``args`` as its annotation arguments) whose self time is added to the
    process-wide totals and, where the phase belongs to a chunk, to that
    chunk's timing record under ``key``.  Wrap whole loops, never one
    iteration of a per-cell loop."""
    from jax.profiler import TraceAnnotation

    with TraceAnnotation("fastpath." + name, **args):
        _PHASE_STACK.append(0.0)
        t0 = time.perf_counter()
        try:
            yield
        finally:
            dt = time.perf_counter() - t0
            own = dt - _PHASE_STACK.pop()
            if _PHASE_STACK:
                _PHASE_STACK[-1] += dt
            tot = _PHASE_TOTALS.setdefault(name, [0.0, 0])
            tot[0] += own
            tot[1] += 1
            if rec is not None:
                rec[key] += own


# The carry of ``_cell_step`` is an ordered pipeline of feature-flagged
# segments: each entry names a compile flag and the carry keys the segment
# contributes when enabled (always-on base state -- slots, queues, channel
# clocks, estimator rings -- is not listed).  Bit i of a bucket key's leading
# feature mask enables segment i, so the compile cache distinguishes exactly
# the distinct enabled-segment sets and nothing else.
_CARRY_SEGMENTS: tuple[tuple[str, tuple[str, ...]], ...] = (
    ("freeze", ("pend", "fprio", "node_of")),
    ("use_fc", ()),                       # static-stream lookup, carry-free
    ("fc_push", ("fcr", "fcp")),
    ("cold", ("freec", "ncold", "nevt", "coldq",
              # the container table of pool_w > 0 buckets
              "pfn", "pmb", "pbz", "plu", "psq", "pnx", "ppk", "prow",
              "ncre", "nblk", "rdt", "rdn", "pdone")),
    ("hedge", ("hedge_t", "att", "nbk", "stolen", "cring", "crsum", "crlen",
               "crpos", "qseq", "stepc", "ndone", "unhedge", "hedge_t2")),
    ("dup", ("done0", "win_start", "win_fin", "win_node", "start_q")),
    ("het", ("sspd",)),
    ("dyn", ("act_t", "dead", "killq", "act_pend", "rearr", "next_tick",
             "prov", "nfail", "ndone", "xq", "rq_rt", "enq_t",
             "dseq", "dcnt", "rord")),
    ("res", ("to_t", "rto", "eps", "qep", "ratt", "nfl", "fcz", "sst",
             "nto", "nsh", "nrt", "wst", "ndn", "qsq", "stp",
             "zring", "zrsum", "zrlen", "zrpos")),
    ("stream", ("qcnt",)),               # chunked-stream carry handoff
)


def _feature_mask(**flags: bool) -> int:
    """Pack kernel compile flags into the bucket key's leading bitmask
    (bit i = segment i of ``_CARRY_SEGMENTS``)."""
    mask = 0
    for bit, (name, _) in enumerate(_CARRY_SEGMENTS):
        if flags.pop(name, False):
            mask |= 1 << bit
    if flags:
        raise TypeError(f"unknown feature flags: {sorted(flags)}")
    return mask


def _mask_features(mask: int) -> dict[str, bool]:
    """Decode a bucket key's feature bitmask back into kernel flag kwargs."""
    if mask >> len(_CARRY_SEGMENTS):
        raise ValueError(f"feature mask {mask:#x} has unknown bits")
    return {name: bool(mask >> bit & 1)
            for bit, (name, _) in enumerate(_CARRY_SEGMENTS)}


def _use64(flags: dict) -> bool:
    # dynamic-capacity, heterogeneous, hedged, cold and resilience buckets
    # compute in float64 (enable_x64): failure, backup, cold-start and
    # timeout/shed accounting depend on exact completion-vs-kill/deadline
    # event orderings, which float32 channel-clock drift can flip under
    # heavy backlog
    return (flags["dyn"] or flags["het"] or flags["hedge"] or flags["cold"]
            or flags["res"])


def _x64_ctx(use64: bool):
    if use64:
        import jax
        return jax.enable_x64(True)
    return contextlib.nullcontext()


# JAX's persistent compilation cache.  Where JAX_COMPILATION_CACHE_DIR is set,
# JAX reads it and nothing here overrides it; otherwise one fixed directory
# inside the checkout (git-ignored).  The path is part of what a later
# process must find again, so it never carries a temporary name or a PID.
COMPILE_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def ensure_compile_cache() -> str | None:
    """Place JAX's persistent compilation cache before a bucket compiles and
    return the directory in use.  Called by :func:`_build_runner`, the one
    place every scan runner (single-shot and streamed) is compiled, so the
    AOT ``lower().compile()`` of a bucket shape seen by an earlier process
    loads from disk instead of compiling again."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    if (not os.environ.get("JAX_COMPILATION_CACHE_DIR")
            and jax.config.jax_compilation_cache_dir is None):
        jax.config.update("jax_compilation_cache_dir", str(COMPILE_CACHE_DIR))
        # an earlier compile in this process latched "no cache"; re-check
        compilation_cache.reset_cache()
    return jax.config.jax_compilation_cache_dir


def _alloc_bucket_inputs(shape_key: tuple, bsz: int) -> dict:
    """Zero-filled host input arrays for one bucket shape at batch ``bsz``.
    ``t`` defaults to +inf, so the untouched allocation is a valid *idle*
    bucket: the AOT lowering takes its arg specs from it and
    ``_run_scan_bucket`` fills rows in place.  An idle cell is no timing
    probe -- the Pallas step runs 2 steps per finite arrival, so none here;
    the auto-tuner measures :func:`_probe_inputs` instead."""
    (mask, n_b, nodes_b, slots_b, f_b, kq, window, fc_ring, n_ep, n_copies,
     pool_w, xtra) = shape_key
    flags = _mask_features(mask)
    freeze, use_fc = flags["freeze"], flags["use_fc"]
    dyn, het, hedge = flags["dyn"], flags["het"], flags["hedge"]
    stream = flags["stream"]
    fdt = np.float64 if _use64(flags) else np.float32
    n1 = n_b + 1
    n_est = nodes_b if freeze else 1

    inp: dict[str, np.ndarray] = {
        "t": np.full((bsz, n1), np.inf, dtype=fdt),
        "fnid": np.zeros((bsz, n1), dtype=np.int32),
        "p": np.zeros((bsz, n1), dtype=fdt),
        "cost": np.zeros((bsz, n1), dtype=fdt),
        "cnt": np.zeros((bsz, n1), dtype=fdt),
        "home0": np.zeros((bsz, n1), dtype=np.int32),
        "coef": np.zeros((bsz, 5), dtype=fdt),
        "cores": np.zeros(bsz, dtype=np.int32),
        "nodes": np.ones(bsz, dtype=np.int32),
        "route": np.zeros(bsz, dtype=np.int32),
        "ring0": np.zeros((bsz, n_est, f_b, window), dtype=fdt),
        "rsum0": np.zeros((bsz, n_est, f_b), dtype=fdt),
        "rlen0": np.zeros((bsz, n_est, f_b), dtype=np.int32),
        "rpos0": np.zeros((bsz, n_est, f_b), dtype=np.int32),
        # FC pull counts and the per-function queue sequences come from
        # the static arrival stream; freeze buckets get dummy rows (the
        # kernel never traces those branches there)
        "cumf": np.zeros((bsz, n1 if use_fc else 1, f_b), dtype=fdt),
        "fn_ev": (np.full((bsz, f_b, kq), n_b, dtype=np.int32)
                  if not freeze and not stream
                  else np.zeros((bsz, 1, 1), dtype=np.int32)),
    }
    if stream:
        # chunk horizon; +inf = run to exhaustion (the final chunk)
        inp["t_stop"] = np.full(bsz, np.inf, dtype=fdt)
        if not freeze:
            # CSR per-function event lists replace the dense fn_ev table
            inp["fnev"] = np.full((bsz, n1), n_b, dtype=np.int32)
            inp["fnst"] = np.zeros((bsz, f_b), dtype=np.int32)
        if flags["res"]:
            inp["gseq"] = np.zeros((bsz, n1), dtype=np.int32)
    if dyn:
        inp["act0"] = np.full((bsz, nodes_b), np.inf, dtype=fdt)
        inp["killt"] = np.full((bsz, nodes_b), np.inf, dtype=fdt)
        # [autoscale_interval, scale_up_threshold, provision_delay,
        #  failure_detect, autoscale_flag]
        inp["dynp"] = np.zeros((bsz, 5), dtype=fdt)
        inp["maxn"] = np.zeros(bsz, dtype=np.int32)
        inp["nreq"] = np.zeros(bsz, dtype=np.int32)
    if het:
        inp["spd"] = np.ones((bsz, nodes_b), dtype=fdt)
        inp["epn"] = np.full((bsz, n_ep), -1, dtype=np.int32)
        inp["ept0"] = np.zeros((bsz, n_ep), dtype=fdt)
        inp["ept1"] = np.zeros((bsz, n_ep), dtype=fdt)
        inp["epf"] = np.ones((bsz, n_ep), dtype=fdt)
    if hedge:
        inp["hmult"] = np.ones(bsz, dtype=fdt)
        inp["hfloor"] = np.zeros(bsz, dtype=fdt)
        inp["hmax"] = np.zeros(bsz, dtype=np.int32)
    if flags["res"]:
        # ResilienceSpec.arrays() tensor form: timeout [on, multiple,
        # floor, absolute], retry [max_attempts, base, cap, jitter,
        # on_timeout, on_shed], admission [on, threshold].  The idle
        # default (all off, max_attempts=1) never fires an event.
        inp["rto_p"] = np.zeros((bsz, 4), dtype=fdt)
        inp["rrt_p"] = np.zeros((bsz, 6), dtype=fdt)
        inp["rrt_p"][:, 0] = 1.0
        inp["adm_p"] = np.zeros((bsz, 2), dtype=fdt)
    if pool_w:
        # container table: each function's size, node and stem-cell memory
        inp["fmb"] = np.zeros((bsz, f_b), dtype=np.int32)
        inp["pmem"] = np.zeros(bsz, dtype=np.int32)
        inp["pcmb"] = np.zeros(bsz, dtype=np.int32)
    return inp


def _probe_inputs(shape_key: tuple, bsz: int) -> dict:
    """The idle allocation with finite ascending arrival times in rows
    ``[:n_b]`` of every cell: a timing probe whose every batch slot steps
    as a loaded bucket of that shape does (a step's cost does not depend on
    the values it steps over).  The Pallas kernel runs its ``2 n_b`` steps;
    on the jnp loop no call starts (the allocation has no cores), so it
    stops after the ``n_b`` arrival steps."""
    inp = _alloc_bucket_inputs(shape_key, bsz)
    n_b = shape_key[1]
    inp["t"][:, :n_b] = np.arange(n_b)
    return inp


def _exec_steps(chunk: list, bsz: int, summary: dict, pallas: bool) -> int:
    """Event steps one chunk ran, read once its results are back: on the
    Pallas kernel each cell's own arrival and completion steps (a padded
    cell none); on the jnp loop (:func:`_event_loop`) every batch slot for
    the loop's own count, ``summary["steps"]``, which stops once no cell of
    the chunk has an event left and never passes the ``n_steps`` budget (a
    chunk of padding cells runs none)."""
    if pallas:
        return 2 * sum(len(c.feats.t) for c in chunk)
    return bsz * int(summary["steps"])


def _runner_kwargs(shape_key: tuple) -> tuple[dict, dict]:
    """``(state_kw, step_kw)`` for one bucket shape: the plane packer's
    carry-shaping flags and the event-step kernel's full static kwargs."""
    (mask, n_req, n_nodes, n_slots, _, _, window, fc_ring, n_ep, n_copies,
     pool_w, xtra) = shape_key
    flags = _mask_features(mask)
    state_kw = dict(n_nodes=n_nodes, n_slots=n_slots, window=window,
                    freeze=flags["freeze"], fc_push=flags["fc_push"],
                    dyn=flags["dyn"], het=flags["het"],
                    hedge=flags["hedge"], cold=flags["cold"],
                    dup=flags["dup"], n_copies=n_copies, fc_ring=fc_ring,
                    res=flags["res"], stream=flags["stream"],
                    pool_w=pool_w)
    step_kw = dict(state_kw, use_fc=flags["use_fc"], n_ep=n_ep,
                   horizon=DEFAULT_FC_HORIZON, n_steps=2 * n_req + xtra)
    return state_kw, step_kw


def _runner_fns(shape_key: tuple):
    """The jitted ``(init, scan)`` pair of one bucket shape, not yet lowered:
    the vmapped plane packer and the event-step dispatch with the carry
    planes donated."""
    import jax

    from ..kernels import ops as _kops

    state_kw, step_kw = _runner_kwargs(shape_key)
    init_fn = jax.jit(jax.vmap(partial(_make_planes, **state_kw)))
    # named after the kernel, so the step's XLA module is
    # ``jit_event_step`` and not a bare partial's ``jit__unknown``
    step = update_wrapper(partial(_kops.event_step, **step_kw),
                          _kops.event_step)
    scan_fn = jax.jit(step, donate_argnums=(0, 1))
    return init_fn, scan_fn


def _build_runner(shape_key: tuple, bsz: int):
    """Trace + AOT-compile the ``(init, scan)`` executable pair for one
    (bucket shape, batch size), timing the compile.  ``init`` is the vmapped
    plane packer (:func:`_make_planes`); ``scan`` is the fused event-step
    dispatch (:func:`repro.kernels.ops.event_step`) jitted with the carry
    planes **donated**, so the initial-state buffers are reused as the scan
    carry instead of double-allocating large buckets.  AOT lowering (instead
    of plain ``jax.jit`` call-site tracing) is what lets the compile be
    timed separately from the dispatch.  float64 buckets lower under
    ``enable_x64`` -- eval_shape / lowering outside it would silently
    canonicalize the f64 specs back to f32."""
    import jax

    flags = _mask_features(shape_key[0])
    init_fn, scan_fn = _runner_fns(shape_key)
    ensure_compile_cache()

    import warnings

    with _x64_ctx(_use64(flags)), warnings.catch_warnings():
        # the donated planes rarely alias an output (the kernel returns
        # event records, not the final carry), but donation still lets XLA
        # recycle them for scan temporaries -- silence the advisory
        warnings.filterwarnings("ignore",
                                message="Some donated buffers were not")
        specs = {k: jax.ShapeDtypeStruct(v.shape, v.dtype)
                 for k, v in _alloc_bucket_inputs(shape_key, bsz).items()}
        t0 = time.perf_counter()
        init_c = init_fn.lower(specs).compile()
        clk, ctr = jax.eval_shape(init_fn, specs)
        scan_c = scan_fn.lower(clk, ctr, specs).compile()
        return (init_c, scan_c), time.perf_counter() - t0


def _cache_entry(shape_key: tuple) -> _CacheEntry:
    entry = _SCAN_CACHE.pop(shape_key, None)
    if entry is None:
        entry = _CacheEntry()
    _SCAN_CACHE[shape_key] = entry       # re-insert: most-recently-used last
    return entry


def _evict_runners(current: tuple) -> None:
    """Bound total resident executables: drop whole LRU entries first, then
    the oldest batch-size runner inside the current entry -- never the one
    just built."""
    cap = max(SCAN_CACHE_MAX, 1)
    while sum(len(e.runners) for e in _SCAN_CACHE.values()) > cap:
        victim = next((k for k in _SCAN_CACHE if k != current), None)
        if victim is not None:
            _SCAN_CACHE.pop(victim)
            continue
        entry = _SCAN_CACHE[current]
        if len(entry.runners) <= 1:
            break
        bsz = next(iter(entry.runners))
        entry.runners.pop(bsz)
        entry.compile_s.pop(bsz, None)


def _scan_runner(key: tuple, rec: dict | None = None):
    """AOT-compiled ``(init, scan)`` pair for one bucket shape at one chunk
    batch size: ``key = (feature_mask, n_req, n_nodes, n_slots, n_fns,
    fn_queue_cap, window, fc_ring, n_ep, n_copies, pool_w, xtra, batch)``
    (``pool_w`` the container-table rows, 0 off the table) -- the
    leading element is the :func:`_feature_mask` bitmask of enabled carry
    segments, the trailing one the padded chunk batch.  All batch sizes of
    one shape share a single LRU cache entry (see :class:`_CacheEntry`).
    A miss compiles inside a ``compile`` phase, timed into ``rec``."""
    shape_key, bsz = key[:-1], key[-1]
    entry = _cache_entry(shape_key)
    pair = entry.runners.pop(bsz, None)
    if pair is not None:
        entry.runners[bsz] = pair        # MRU within the entry as well
        entry.hits += 1
        _SCAN_CACHE_STATS["hits"] += 1
        return pair
    _SCAN_CACHE_STATS["misses"] += 1
    with _phase("compile", rec, "compile_s"):
        pair, secs = _build_runner(shape_key, bsz)
    entry.runners[bsz] = pair
    entry.compile_s[bsz] = secs
    _evict_runners(shape_key)
    return pair


def _bucket_bytes(shape_key: tuple, bsz: int) -> int:
    """Rough device footprint of one chunk at batch ``bsz``: inputs, packed
    carry planes and stacked step outputs (the x3 covers planes + XLA
    temporaries + donation slack)."""
    per_cell = sum(v.nbytes
                   for v in _alloc_bucket_inputs(shape_key, 1).values())
    n_b, xtra = shape_key[1], shape_key[-1]
    itemsize = 8 if _use64(_mask_features(shape_key[0])) else 4
    outs = (2 * n_b + xtra) * 5 * itemsize
    return (per_cell * 3 + outs) * bsz


def _bucket_chunk(shape_key: tuple, n_cells: int,
                  rec: dict | None = None) -> int:
    """Cells per dispatched chunk for this bucket: the auto-tuned value when
    one exists, else :data:`SCAN_BATCH_MAX`.  Tuning runs once per (shape,
    backend) the first time the bucket arrives with more cells than the
    default chunk, inside a ``tune`` phase timed into ``rec``, and the
    choice persists on the cache entry (visible in
    ``scan_cache_stats()["entries"]``)."""
    entry = _cache_entry(shape_key)
    if entry.chunk is not None:
        return entry.chunk
    if not SCAN_AUTOTUNE or n_cells <= SCAN_BATCH_MAX:
        return SCAN_BATCH_MAX
    with _phase("tune", rec, "tune_s"):
        entry.chunk = _autotune_chunk(shape_key, n_cells, rec)
    return entry.chunk


def _autotune_chunk(shape_key: tuple, n_cells: int,
                    rec: dict | None = None) -> int:
    """One-time chunk-size measurement for one bucket shape: time the
    :func:`_probe_inputs` bucket, which runs the full step budget like a
    loaded one, at power-of-two batch sizes under the :data:`SCAN_MEM_MB`
    footprint cap and keep the cells/sec argmax.  Candidates ascend and
    ``max`` keeps the first maximum, so exact ties resolve to the smaller
    batch; re-tuning the same resident entry is a no-op (the choice is
    cached), which is what the determinism contract promises."""
    import jax
    import jax.numpy as jnp

    flags = _mask_features(shape_key[0])
    cap = _pow2(min(n_cells, 1024))
    cands = [b for b in (128, 256, 512, 1024)
             if b <= cap and _bucket_bytes(shape_key, b) <= SCAN_MEM_MB * 2**20]
    if not cands:
        return min(SCAN_BATCH_MAX, cap)

    def _rate(bsz: int) -> float:
        init_c, scan_c = _scan_runner(shape_key + (bsz,), rec)
        inp = _probe_inputs(shape_key, bsz)
        best = np.inf
        for _ in range(3):       # min-of-3: robust to scheduler noise
            arrs = {k: jnp.asarray(v) for k, v in inp.items()}
            clk, ctr = init_c(arrs)
            t0 = time.perf_counter()
            res = scan_c(clk, ctr, arrs)
            jax.block_until_ready(res)
            best = min(best, time.perf_counter() - t0)
        return bsz / best

    with _x64_ctx(_use64(flags)):
        rates = [(b, _rate(b)) for b in cands]
    return max(rates, key=lambda kv: kv[1])[0]


@dataclass
class _ScanCell:
    """One prepared cell: features + shape parameters for bucketing."""

    requests: list
    feats: _Arrivals
    cores: int
    nodes: int
    policy: str
    assignment: str      # "single" | "pull" | "push"
    lb: str = "least_loaded"
    warm: bool = True
    dynamics: object | None = None      # ClusterDynamics | None
    profile: object | None = None       # NodeSpeedProfile | None
    hedging: object | None = None       # HedgingSpec | None
    resilience: object | None = None    # ResilienceSpec | None
    memory_mb: int = CLUSTER_MEMORY_MB
    container_mb: int = CLUSTER_CONTAINER_MB
    pool_w: int = 0      # container-table rows (0: off the table)

    @property
    def dyn(self) -> bool:
        return self.dynamics is not None and not self.dynamics.is_static

    @property
    def res(self) -> bool:
        return self.resilience is not None and not self.resilience.is_null

    @property
    def het(self) -> bool:
        return self.profile is not None and not self.profile.is_uniform

    @property
    def hedge(self) -> bool:
        # hedging only ever acts on queued-on-node calls, which the pull
        # model never has (late binding): pull cells run without the hedge
        # machinery and report backups_issued == 0, like the reference
        return self.hedging is not None and self.assignment == "push"

    @property
    def cold(self) -> bool:
        return not self.warm

    @property
    def dup(self) -> bool:
        return self.hedge and self.hedging.mode == "duplicate"

    @property
    def n_copies(self) -> int:
        # duplicate-mode queue width: the original plus one racing copy per
        # allowed backup (see the kernel's flattened copy axis)
        return 1 + int(self.hedging.max_backups) if self.dup else 1

    def node_cap(self) -> int:
        """Largest node count the cell can reach (autoscaler headroom)."""
        return (self.dynamics.capacity_bound(self.nodes)
                if self.dynamics is not None else self.nodes)

    def dyn_budget(self) -> int:
        """Upper bound on the extra scan steps capacity dynamics consume:
        kill events, lost-request re-arrivals, autoscaler ticks (bounded by
        a work-conserving makespan bound over the tick interval) and
        activation backfill dispatches."""
        if not self.dyn:
            return 0
        d = self.dynamics
        n = len(self.feats.t)
        kills = len(d.fail)
        lost = kills * self.cores
        if self.assignment == "push" and kills:
            lost += n                # queued-on-node calls are lost too
        extra = kills + lost
        if d.autoscale:
            grow = max(0, d.capacity_bound(self.nodes) - self.nodes)
            work = 0.0
            if n:
                per_req = self.feats.p + self.feats.chan_cost
                work = (float(self.feats.t[-1]) + float(per_req.sum())
                        + kills * d.failure_detect_s
                        + lost * float(per_req.max()))
            ticks = int(np.ceil(work / max(d.autoscale_interval_s, 1e-6))) + 2
            extra += ticks + grow * (1 + self.cores)
        return extra

    def hedge_budget(self) -> int:
        """*Optimistic* extra scan steps for hedging: a watch is cleared the
        moment its call dispatches, so realized deadline fires are only the
        steals plus attempt-capped no-ops -- empirically well under ``n``.
        ``_run_scan_bucket`` verifies completion (``ndone``) and re-runs a
        chunk at :meth:`hedge_budget_full` when this guess was short, so
        the bound is a performance knob, never a correctness one."""
        if not self.hedge:
            return 0
        return len(self.feats.t)

    def hedge_budget_full(self) -> int:
        """Strict upper bound on the extra scan steps hedging consumes.
        Steal mode: every arm fires at most once and arms = arrivals +
        steals <= n * (1 + max_backups).  Duplicate mode: fires are bounded
        the same way, and each issued copy additionally costs one extra
        completion event, <= n * max_backups more."""
        if not self.hedge:
            return 0
        n = len(self.feats.t)
        hmax = int(self.hedging.max_backups)
        full = n * (1 + 2 * hmax) if self.dup else n * (1 + hmax)
        if self.dyn and self.assignment == "push":
            # each queued-at-kill loss can leave one extra pending deadline
            # (the uncancelled pre-kill watch) that fires once
            full += len(self.dynamics.fail) * self.cores + n
        return full

    def res_budget(self) -> int:
        """*Optimistic* extra scan steps for resilience: realized extra
        events are timeout fires plus retry re-arrivals plus resubmission
        terminals -- ``n`` exactly when retries are off (<= one fire per
        submission), and empirically ~2 n even in a full retry storm.
        ``_run_scan_bucket`` verifies completion (``ndn``) and re-runs a
        chunk at :meth:`res_budget_full` when this guess was short, so the
        bound is a performance knob, never a correctness one."""
        if not self.res:
            return 0
        n = len(self.feats.t)
        return n if int(self.resilience.max_attempts) <= 1 else 2 * n

    def res_budget_full(self) -> int:
        """Strict upper bound on the extra scan steps resilience consumes:
        each of the <= n * max_attempts submissions costs at most one
        insert event (covered by the base arrival budget for the first) and
        one terminal event (completion or timeout fire), plus one retry
        re-arrival event per resubmission -- <= n * (2 * max_attempts - 1)
        extra, rounded up to ``2 n max_attempts``.  Sheds happen inside the
        insert event and stale watch fires never exist (the deadline slot
        is overwritten at re-arm), so no slack is needed for either."""
        if not self.res:
            return 0
        return 2 * len(self.feats.t) * int(self.resilience.max_attempts)

    def pool_budget(self) -> int:
        """*Optimistic* extra scan steps for the blocked-head continuation
        of a frozen-queue container-table cell: one per call a completion
        starts beyond its first.  ``_run_scan_bucket`` verifies completion
        (``pdone``) and re-runs a chunk at :meth:`pool_budget_full` when
        this guess was short."""
        if not self.pool_w or self.assignment == "pull":
            return 0
        return len(self.feats.t) // 4

    def pool_budget_full(self) -> int:
        """Strict bound on the continuation steps: each one either starts
        a call (at most ``n``) or ends its completion's chain (at most one
        a completion, ``n``)."""
        if not self.pool_w or self.assignment == "pull":
            return 0
        return 2 * len(self.feats.t)

    def bucket(self) -> tuple:
        freeze = self.assignment != "pull"
        dyn = self.dyn
        use_fc = not freeze and self.policy == "fc"
        # single-node static push-FC can use the precomputed global window
        # counts -- unless hedging or retries re-log re-submissions on the
        # node (and shedding withholds arrivals from it), which only the
        # live per-(node, fn) rings can track
        fc_push = (freeze and self.policy == "fc"
                   and (self.nodes > 1 or dyn or self.hedge or self.res))
        if freeze:
            kq = 1                   # fn_ev unused in frozen-priority mode
        else:                        # per-function queue capacity
            kq = _pow2(int(np.bincount(self.feats.fn_ids).max())
                       if len(self.feats.fn_ids) else 1)
        # the per-(node, fn) ring is sized to the worst *global* window
        # count, which bounds any node-local count from above; hedged cells
        # additionally re-log each steal/copy on its target node, so every
        # arrival can contribute up to 1 + max_backups entries in-window
        fc_mult = 1 + int(self.hedging.max_backups) if self.hedge else 1
        if self.res:
            # every admitted resubmission re-logs on its target node
            fc_mult = max(fc_mult, int(self.resilience.max_attempts))
        fc_ring = (_pow2(int(self.feats.count.max()) * fc_mult)
                   if fc_push and len(self.feats.count) else 1)
        n_ep = (_pow2(max(1, len(self.profile.episodes)))
                if self.het else 1)
        extra = (self.dyn_budget() + self.hedge_budget() + self.res_budget()
                 + self.pool_budget())
        xtra = _pow2(extra) if extra else 0
        mask = _feature_mask(freeze=freeze, use_fc=use_fc, fc_push=fc_push,
                             cold=self.cold, hedge=self.hedge, dup=self.dup,
                             het=self.het, dyn=dyn, res=self.res)
        return (mask, _pow2(len(self.feats.t)),
                _pow2(self.node_cap()), _pow2(self.cores),
                _pow2(len(self.feats.fns)), kq, DEFAULT_WINDOW,
                fc_ring, n_ep, self.n_copies, self.pool_w, xtra)


def _scan_check_outputs(tag: str, cell_idx: int, n: int,
                        fields: dict) -> None:
    """Opt-in (``REPRO_SCAN_CHECK=1``) numerical validation of one cell's
    carry-derived outputs, run after each chunk's host sync: every live
    entry must be finite.  A NaN/inf here means a kernel carry segment went
    numerically bad (e.g. an inf sentinel leaked through a mask); the error
    names the bucket, the cell and the offending field/event so the bad
    segment is identifiable without bisecting the sweep."""
    for name, arr in fields.items():
        a = np.asarray(arr[:n], dtype=np.float64)
        bad = ~np.isfinite(a)
        if bad.any():
            e = int(np.nonzero(bad)[0][0])
            raise FloatingPointError(
                f"REPRO_SCAN_CHECK: non-finite scan output in bucket {tag} "
                f"cell {cell_idx}: field {name!r} = {a[e]!r} at event "
                f"index {e}")


def _fill_bucket_inputs(key: tuple, chunk: list, bsz: int) -> dict:
    """Host input arrays for one chunk of a bucket: the idle allocation of
    :func:`_alloc_bucket_inputs` with rows ``[:len(chunk)]`` filled from the
    prepared cells (arrivals, fleet, policy coefficients, dynamics /
    heterogeneity / hedging / resilience parameters and the warm seed)."""
    (_, _, nodes_b, _, f_b, _, window, _, n_ep, _, pool_w, _) = key
    flags = _mask_features(key[0])
    use_fc, dyn, het = flags["use_fc"], flags["dyn"], flags["het"]
    hedge, resil = flags["hedge"], flags["res"]
    inp = _alloc_bucket_inputs(key, bsz)

    for b, cell in enumerate(chunk):
        f = cell.feats
        n = len(f.t)
        inp["t"][b, :n] = f.t
        inp["fnid"][b, :n] = f.fn_ids
        inp["p"][b, :n] = f.p
        inp["cost"][b, :n] = f.chan_cost
        inp["cnt"][b, :n] = f.count
        inp["cores"][b] = cell.cores
        inp["nodes"][b] = cell.nodes
        if pool_w:
            inp["fmb"][b, :len(f.fns)] = _fn_sizes(f.fns, cell.container_mb)
            inp["pmem"][b] = cell.memory_mb
            inp["pcmb"][b] = cell.container_mb
        if dyn:
            d = cell.dynamics
            inp["act0"][b, :cell.nodes] = 0.0
            for idx, at in d.fail:
                # duplicate kills of one node: the earliest wins, like
                # the reference's _do_fail no-op on an already-dead node
                inp["killt"][b, idx] = min(inp["killt"][b, idx], at)
            inp["dynp"][b] = (d.autoscale_interval_s,
                              d.scale_up_queue_per_slot,
                              d.provision_delay_s,
                              d.failure_detect_s,
                              1.0 if d.autoscale else 0.0)
            inp["maxn"][b] = cell.node_cap()
            inp["nreq"][b] = n
        if het:
            spd, epn, ept0, ept1, epf = cell.profile.arrays(nodes_b,
                                                            n_ep)
            inp["spd"][b] = spd
            inp["epn"][b] = epn
            inp["ept0"][b] = ept0
            inp["ept1"][b] = ept1
            inp["epf"][b] = epf
        if hedge:
            h = cell.hedging
            inp["hmult"][b] = h.multiple
            inp["hfloor"][b] = h.floor_s
            inp["hmax"][b] = h.max_backups
        if resil:
            t4, r6, a2 = cell.resilience.arrays()
            inp["rto_p"][b] = t4
            inp["rrt_p"][b] = r6
            inp["adm_p"][b] = a2
        if cell.assignment == "pull":
            if dyn:
                inp["coef"][b] = _PULL_COEF_DYN[cell.policy]
            else:
                inp["coef"][b, :4] = _PULL_COEF[cell.policy]
            if use_fc:
                onehot = np.zeros((n, f_b), dtype=np.float32)
                onehot[np.arange(n), f.fn_ids] = 1.0
                inp["cumf"][b, 1:n + 1] = np.cumsum(onehot, axis=0)
                inp["cumf"][b, n + 1:] = inp["cumf"][b, n]
            for fi in range(len(f.fns)):
                idx = np.nonzero(f.fn_ids == fi)[0]
                inp["fn_ev"][b, fi, :idx.size] = idx
            continue
        inp["coef"][b, :4] = _POLICY_COEF[cell.policy]
        if cell.assignment == "push" and cell.lb == "home":
            from .traces import stable_hash
            inp["route"][b] = 1
            hashes = np.array([stable_hash(fn) for fn in f.fns],
                              dtype=np.int64)
            inp["home0"][b, :n] = (hashes % cell.nodes)[f.fn_ids]
        # §V-A warm-up seeds every node's estimator with the profile
        # median (single-node semantics at nodes=1); autoscaled nodes
        # warm up the same way the moment they are provisioned.  The
        # warm=False regime skips the seed: the reference only seeds
        # estimators alongside container warm-up (warm_functions)
        if cell.warm:
            seed_n = min(cell.cores, window)
            for fi, fn in enumerate(f.fns):
                w = PROFILES[fn].median_s if fn in PROFILES else 0.1
                inp["ring0"][b, :, fi, :seed_n] = w
                inp["rsum0"][b, :, fi] = seed_n * w
                inp["rlen0"][b, :, fi] = seed_n
                inp["rpos0"][b, :, fi] = seed_n % window
    return inp


def _pool_extras(ex: dict, aux: dict, b: int, cell: _ScanCell) -> None:
    """A container-table cell's creations and peak residency; a blocked
    head in a pull cell is refused (its reference drains the global queue
    into the blocked node's own queue, which the pull step does not
    carry, and the eligibility bound rules it out)."""
    if int(aux["nblk"][b]) and cell.assignment == "pull":
        raise RuntimeError(
            f"a pull cell blocked a queue head {int(aux['nblk'][b])} "
            "times on the container table; _pool_regime_ok should have "
            "refused it")
    ex["creations"] = int(aux["ncre"][b])
    _STEP_COUNTS["pool_peak"] += int(np.sum(aux["ppk"][b][:cell.nodes]))


def _run_scan_bucket(key: tuple, cells: list[_ScanCell]) -> list[tuple]:
    """Dispatch one shape bucket in auto-tuned chunks (each padded to a
    power-of-two batch) and return per-cell ``(start, finish, prio, node,
    extras)`` arrays in event order; ``extras`` is ``None`` for plain
    static-capacity cells and a dict (failure/backup counters, cold-start
    flags, activation/dead vectors as applicable) otherwise.  Chunks are
    dispatched asynchronously -- up to :data:`SCAN_INFLIGHT` in flight ahead
    of the host sync -- with the carry planes donated inside the runner, so
    device work overlaps the host-side fill of the next chunk."""
    import jax
    import jax.numpy as jnp

    from ..kernels import ops as _kops

    (mask, n_b, nodes_b, slots_b, f_b, kq, window, fc_ring, n_ep, n_copies,
     pool_w, xtra) = key
    flags = _mask_features(mask)
    pallas = _kops.event_step_path(**_runner_kwargs(key)[1]) == "pallas"
    freeze, dyn, hedge = flags["freeze"], flags["dyn"], flags["hedge"]
    cold, resil = flags["cold"], flags["res"]
    pool_rd = pool_w > 0 and freeze
    check = os.environ.get("REPRO_SCAN_CHECK") == "1"
    n1 = n_b + 1
    use64 = _use64(flags)
    tag = _bucket_tag(key)
    # the auto-tuner's probes (compiles + timed runs) are a one-time cost
    # with a record of their own, apart from steady-state dispatch
    tune = dict(_timing_record(tag, 0, 0), tune_s=0.0)
    chunk_max = _bucket_chunk(key, len(cells), tune)
    if tune["tune_s"]:
        _record_timing(tune)
    out: list[tuple | None] = [None] * len(cells)
    pending: deque = deque()

    def _dispatch(inp, xtra_now: int, rec: dict, chunk: list):
        """Issue one chunk on the device and return the *un-synced* result
        tree (JAX dispatch is asynchronous, so this returns as soon as the
        work is enqueued)."""
        bsz = inp["cores"].shape[0]
        init_c, scan_c = _scan_runner((mask, n_b, nodes_b, slots_b, f_b,
                                       kq, window, fc_ring, n_ep, n_copies,
                                       pool_w, xtra_now, bsz), rec)
        _STEP_COUNTS["step_slots"] += bsz * (2 * n_b + xtra_now)
        if pool_w:
            _STEP_COUNTS["pool_node_slots"] += bsz * nodes_b
            _STEP_COUNTS["pool_rows"] += bsz * nodes_b * pool_w
        _STEP_COUNTS["call_steps"] += 2 * sum(len(c.feats.t) for c in chunk)
        with _phase("dispatch", rec, "dispatch_s"), _x64_ctx(use64):
            # float64 buckets convert inputs *inside* enable_x64 --
            # quantizing kill/arrival/deadline times through float32 first
            # would merge distinct event times and reintroduce exactly the
            # ordering flips the promotion prevents
            arrs = {k: jnp.asarray(v) for k, v in inp.items()}
            clk, ctr = init_c(arrs)
            return scan_c(clk, ctr, arrs)

    def _collect(res, rec: dict, chunk: list, bsz: int):
        """Block on one dispatched chunk, then copy its results back."""
        with _phase("wait", rec, "wait_s"):
            jax.block_until_ready(res)
        return _copy_back(res, chunk, bsz)

    def _copy_back(res, chunk: list, bsz: int):
        """One chunk's results on the host, its executed steps counted."""
        res = jax.tree_util.tree_map(np.asarray, res)
        summary = res[1] if dyn or resil else res[4]
        _STEP_COUNTS["exec_steps"] += _exec_steps(chunk, bsz, summary,
                                                  pallas)
        return res

    def _finish(lo: int, chunk: list, inp: dict, res, rec: dict) -> None:
        """Host-sync one in-flight chunk, verify hedge step budgets
        (re-running at the strict bound when the optimistic guess fell
        short) and unpack per-cell outputs into ``out``."""
        t0 = time.perf_counter()
        with _phase("wait", rec, "wait_s"):
            jax.block_until_ready(res)
        with _phase("unpack", rec, "unpack_s"):
            res = _sync(chunk, inp, res, rec)
            rec["sync_s"] += time.perf_counter() - t0
            _unpack(lo, chunk, inp, res)
        _record_timing(rec)

    def _sync(chunk: list, inp: dict, res, rec: dict):
        """Copy one chunk's results back and check its step budgets."""
        bsz = inp["cores"].shape[0]
        res = _copy_back(res, chunk, bsz)
        if hedge:
            ndone_b = (res[1] if dyn else res[4])["ndone"]
            if any(int(ndone_b[b]) != len(chunk[b].feats.t)
                   for b in range(len(chunk))):
                # the optimistic hedge step budget fell short (a cell fired
                # far more deadlines than requests): re-run the chunk at
                # the strict worst-case bound, which cannot fall short by
                # construction
                full = max(c.dyn_budget() + c.hedge_budget_full()
                           for c in chunk)
                res = _collect(_dispatch(inp, _pow2(full), rec, chunk), rec,
                               chunk, bsz)
                ndone_b = (res[1] if dyn else res[4])["ndone"]
                for b, cell in enumerate(chunk):
                    if int(ndone_b[b]) != len(cell.feats.t):
                        raise RuntimeError(
                            "hedge scan step budget exhausted at the "
                            f"strict bound ({full}); this is a kernel "
                            "budget bug")
        if resil:
            ndn_b = res[1]["ndn"]
            if any(int(ndn_b[b]) != len(chunk[b].feats.t)
                   for b in range(len(chunk))):
                # the optimistic resilience step budget fell short (a storm
                # fired far more timeouts/retries than the ~2n guess): re-run
                # the chunk at the strict worst-case bound, which cannot fall
                # short by construction -- the per-cell ndn check below then
                # only fires on a genuine kernel budget bug
                full = max(c.dyn_budget() + c.hedge_budget()
                           + c.res_budget_full() for c in chunk)
                res = _collect(_dispatch(inp, _pow2(full), rec, chunk), rec,
                               chunk, bsz)
        if pool_rd:
            pdone_b = res[4]["pdone"]
            if any(int(pdone_b[b]) != len(chunk[b].feats.t)
                   for b in range(len(chunk))):
                # the optimistic continuation budget fell short (blocked
                # heads released many calls at once): re-run the chunk at
                # the strict bound
                full = max(c.pool_budget_full() for c in chunk)
                res = _collect(_dispatch(inp, _pow2(full), rec, chunk), rec,
                               chunk, bsz)
                pdone_b = res[4]["pdone"]
                for b, cell in enumerate(chunk):
                    if int(pdone_b[b]) != len(cell.feats.t):
                        raise RuntimeError(
                            "container-table scan step budget exhausted "
                            f"at the strict bound ({full}); this is a "
                            "kernel budget bug")
        return res

    def _unpack(lo: int, chunk: list, inp: dict, res) -> None:
        """Per-cell outputs of one synced chunk into ``out``."""
        if not dyn and not resil:
            start_b, finish_b, prio_b, node_b, aux = res
            for b in range(len(chunk)):
                ex: dict | None = {}
                if hedge:
                    ex.update(backups=int(aux["nbk"][b]),
                              steals=int(aux["nstl"][b]),
                              attempts=aux["att"][b])
                if cold:
                    ex.update(cold_starts=int(aux["ncold"][b]),
                              evictions=int(aux["nevt"][b]),
                              coldq=aux["coldq"][b])
                if pool_w:
                    _pool_extras(ex, aux, b, chunk[b])
                if check:
                    _scan_check_outputs(
                        tag, lo + b, len(chunk[b].feats.t),
                        {"start": start_b[b], "finish": finish_b[b],
                         "prio": prio_b[b]})
                out[lo + b] = (np.asarray(start_b[b], dtype=np.float64),
                               np.asarray(finish_b[b], dtype=np.float64),
                               np.asarray(prio_b[b], dtype=np.float64),
                               node_b[b], ex or None)
            return
        (j_s, es_s, fs_s, pj_s, kd_s), summary = res
        es_s = np.asarray(es_s, dtype=np.float64)
        fs_s = np.asarray(fs_s, dtype=np.float64)
        pj_s = np.asarray(pj_s, dtype=np.float64)
        for b, cell in enumerate(chunk):
            n = len(cell.feats.t)
            ndone = int(summary["ndn" if resil else "ndone"][b])
            if ndone != n:
                raise RuntimeError(
                    f"scan {'resilience' if resil else 'dynamics'} step "
                    f"budget exhausted: cell resolved {ndone}/{n} requests "
                    f"(bucket xtra={xtra}); this is a kernel budget bug")
            # a re-dispatched lost/retried request appears twice in the step
            # record; numpy fancy assignment resolves duplicates last-wins
            # in step order, which is exactly the re-dispatch overriding
            # the cancelled one
            start = np.zeros(n1)
            finish = np.zeros(n1)
            start[j_s[b]] = es_s[b]
            finish[j_s[b]] = fs_s[b]
            if freeze:
                prio = summary["prio"][b].astype(np.float64)
                node = summary["node"][b]
            else:
                prio = np.zeros(n1)
                node = np.zeros(n1, dtype=np.int64)
                prio[j_s[b]] = pj_s[b]
                node[j_s[b]] = kd_s[b]
            if resil:
                extras = {
                    "timed_out": int(summary["nto"][b]),
                    "shed": int(summary["nsh"][b]),
                    "retries_issued": int(summary["nrt"][b]),
                    "wasted_work": float(summary["wst"][b]),
                    "failed_mask": summary["nfl"][b],
                    "failed_cause": summary["fcz"][b],
                    "attempts_res": summary["ratt"][b],
                }
            else:
                extras = {
                    "failures": int(summary["nfail"][b]),
                    "nodes_used": int(summary["prov"][b]),
                    "act_t": summary["act_t"][b],
                    "dead": summary["dead"][b],
                    "killt": inp["killt"][b],
                }
                if hedge:
                    extras.update(backups=int(summary["nbk"][b]),
                                  steals=int(summary["nstl"][b]),
                                  attempts=summary["att"][b])
                if cold:
                    extras.update(cold_starts=int(summary["ncold"][b]),
                                  evictions=int(summary["nevt"][b]),
                                  coldq=summary["coldq"][b])
            if check:
                _scan_check_outputs(tag, lo + b, n,
                                    {"start": start, "finish": finish,
                                     "prio": prio})
            out[lo + b] = (start, finish, prio, node, extras)

    for lo in range(0, len(cells), chunk_max):
        chunk = cells[lo:lo + chunk_max]
        bsz = _pow2(len(chunk))
        rec = _timing_record(tag, bsz, len(chunk))
        with _phase("fill", rec, "build_s"):
            inp = _fill_bucket_inputs(key, chunk, bsz)
        res = _dispatch(inp, xtra, rec, chunk)
        pending.append((lo, chunk, inp, res, rec))
        # bounded async window: every chunk is dispatched before its
        # predecessors are synced, so device work overlaps the host-side
        # fill of the next chunk without pinning the whole bucket
        while len(pending) >= max(SCAN_INFLIGHT, 1):
            _finish(*pending.popleft())
    while pending:
        _finish(*pending.popleft())
    return out


@dataclass
class ScanMetrics:
    """Metrics-only output for one scan cell: response-time / stretch
    arrays in **request order** plus the extras counters, with no Request
    objects touched.  Request-order arrays make the means bit-identical to
    the write-back path (``np.mean`` pairwise summation is order-sensitive
    in the last ulp), and not mutating the requests is what lets callers
    share one workload across every policy/fleet cell that uses it."""

    resp: np.ndarray          # response times, request order
    stretch: np.ndarray       # stretch values, request order
    max_c: float              # makespan (max completion time)
    fnids: np.ndarray         # per-request index into ``fns``
    fns: tuple                # sorted function names
    cold_starts: int = 0
    evictions: int = 0
    failures: int = 0
    backups: int = 0
    steals: int = 0
    nodes_used: int = 0
    creations: int = 0


def _cell_scan_metrics(cell: _ScanCell, finish, extras,
                       req_cache: dict) -> ScanMetrics:
    """Fold one cell's event-order finish times into request-order metric
    arrays, replicating the write-back arithmetic operation-for-operation
    (``c = finish + RESP_OVERHEAD_S``; ``resp = c - r``; ``stretch = resp /
    max(ref-or-p_true, 1e-9)``) so the results agree bitwise.  ``req_cache``
    memoizes the per-workload arrays by list identity within one batch call
    -- cells sharing a workload pay the Python-level extraction once."""
    f = cell.feats
    n = len(f.t)
    cached = req_cache.get(id(cell.requests))
    if cached is None:
        r_req = np.array([req.r for req in cell.requests], dtype=np.float64)
        den = np.array([max(STRETCH_REFERENCE_S.get(req.fn) or req.p_true,
                            1e-9) for req in cell.requests])
        cached = req_cache[id(cell.requests)] = (r_req, den)
    r_req, den = cached
    finish_req = np.empty(n, dtype=np.float64)
    finish_req[f.order] = np.asarray(finish[:n], dtype=np.float64)
    c_req = finish_req + RESP_OVERHEAD_S
    resp = c_req - r_req
    fnids = np.empty(n, dtype=np.int64)
    fnids[f.order] = f.fn_ids
    ex = extras or {}
    return ScanMetrics(
        resp=resp, stretch=resp / den, max_c=float(c_req.max()),
        fnids=fnids, fns=tuple(f.fns),
        cold_starts=ex.get("cold_starts", 0),
        evictions=ex.get("evictions", 0),
        failures=ex.get("failures", 0), backups=ex.get("backups", 0),
        steals=ex.get("steals", 0),
        nodes_used=ex.get("nodes_used", cell.nodes),
        creations=ex.get("creations", 0))


def _run_scan_cells(cells: list[_ScanCell],
                    metrics_only: bool = False) -> list:
    """Bucket, dispatch and write back a list of prepared cells (any mix of
    single-node / pull / push, static or dynamic capacity), preserving input
    order.  ``metrics_only=True`` skips the per-request write-back and
    returns :class:`ScanMetrics` rows instead of :class:`SimResult` -- the
    interactive-sweep mode, where cells share workloads and only aggregate
    metrics leave the batch."""
    with _phase("prepare"):
        buckets: dict[tuple, list[int]] = {}
        for i, cell in enumerate(cells):
            buckets.setdefault(cell.bucket(), []).append(i)
    results: list = [None] * len(cells)
    req_cache: dict = {}
    for key, idxs in buckets.items():
        arrays = _run_scan_bucket(key, [cells[i] for i in idxs])
        with _phase("unpack"):
            for i, (start, finish, prio, node, extras) in zip(idxs, arrays):
                cell = cells[i]
                if not metrics_only:
                    results[i] = _write_back(cell, start, finish, prio, node,
                                             extras)
                    continue
                if cell.res:
                    # resilience cells can terminate requests without a
                    # completion; the metrics-only fold assumes every
                    # request finished, so those cells always write back
                    raise ScanRejected(
                        "metrics_only is not supported for resilience "
                        "cells; run them through the write-back path")
                results[i] = _cell_scan_metrics(cell, finish, extras,
                                                req_cache)
    return results  # type: ignore[return-value]


def _write_back(cell: _ScanCell, start, finish, prio, node,
                extras) -> SimResult:
    """Write one cell's event-order outputs back into its requests and
    wrap them in a :class:`SimResult`."""
    f = cell.feats
    order = f.order.tolist()
    t_list = f.t.tolist()
    att = extras.get("attempts") if extras is not None else None
    coldq = extras.get("coldq") if extras is not None else None
    fmask = extras.get("failed_mask") if extras is not None else None
    fcause = extras.get("failed_cause") if extras is not None else None
    ratt = extras.get("attempts_res") if extras is not None else None
    for e, ridx in enumerate(order):
        req = cell.requests[ridx]
        req.node = f"node{int(node[e])}"
        req.r_prime = t_list[e]
        req.priority = float(prio[e])    # float32-rounded
        # warm cells never cold-start; cold cells carry the
        # original's own dispatch decision per request
        req.cold_start = bool(coldq[e]) if coldq is not None else False
        if fmask is not None and bool(fmask[e]):
            # terminal failure: the recorded start/finish belong to
            # a cancelled attempt -- the client never saw a response
            req.start = req.finish = req.c = None
            req.failed = "timeout" if int(fcause[e]) == 1 else "shed"
            req.attempts = max(int(ratt[e]) - 1, 0)
            continue
        req.start = float(start[e])
        req.finish = float(finish[e])
        req.c = req.finish + RESP_OVERHEAD_S
        req.failed = None
        if att is not None:              # hedged cell: backup count
            req.attempts = int(att[e])
        if ratt is not None:             # resubmission count
            req.attempts = max(int(ratt[e]) - 1, 0)
    meta = {"mode": "ours", "policy": cell.policy,
            "cores": cell.cores, "backend": "scan"}
    if cell.assignment != "single":
        meta["nodes"] = cell.nodes
        meta["assignment"] = cell.assignment
    failures = backups = steals = 0
    cold_starts = evictions = creations = 0
    timed_out = shed = retries_issued = 0
    wasted_work = 0.0
    nodes_used = cell.nodes
    timeline = None
    if extras is not None:
        failures = extras.get("failures", 0)
        backups = extras.get("backups", 0)
        steals = extras.get("steals", 0)
        cold_starts = extras.get("cold_starts", 0)
        evictions = extras.get("evictions", 0)
        creations = extras.get("creations", 0)
        timed_out = extras.get("timed_out", 0)
        shed = extras.get("shed", 0)
        retries_issued = extras.get("retries_issued", 0)
        wasted_work = extras.get("wasted_work", 0.0)
        if "act_t" in extras:        # dynamic-capacity cell
            from .cluster import CapacityTimeline
            nodes_used = extras["nodes_used"]
            timeline = CapacityTimeline(
                activate=[float(a) for a in extras["act_t"][:nodes_used]],
                deactivate=[float(extras["killt"][k])
                            if bool(extras["dead"][k]) else float("inf")
                            for k in range(nodes_used)])
    return SimResult(
        requests=cell.requests, cold_starts=cold_starts,
        evictions=evictions, creations=creations, failures=failures,
        backups_issued=backups, steals_won=steals, nodes_used=nodes_used,
        timeline=timeline, timed_out=timed_out, shed=shed,
        retries_issued=retries_issued, wasted_work=wasted_work, meta=meta)


def _feats_cache():
    """Per-batch-call ``_arrival_features`` memo keyed by request-list
    identity: cells sharing one workload (the metrics-only sweep mode) pay
    the numpy feature extraction once.  Scoped to a single batch call so
    recycled ``id()`` values can never alias across calls."""
    cache: dict[int, _Arrivals] = {}

    def feats(requests: list[Request]) -> _Arrivals:
        f = cache.get(id(requests))
        if f is None:
            f = cache[id(requests)] = _arrival_features(requests)
        return f

    return feats


def simulate_cells_scan(
    batch: list[tuple],
    memory_mb: int = 32 * 1024,
    container_mb: int = 128,
    validate: bool = True,
    metrics_only: bool = False,
) -> list[SimResult]:
    """Run a batch of ``(requests, cores, policy[, warm])`` ours-mode
    **single-node** scenarios through the bucketed scan path (cells vmapped,
    one XLA compile per padded bucket shape, shared across calls).

    ``warm`` defaults to ``True``; ``warm=False`` cells run the cold-start /
    eviction regime (prewarm-pool misses and per-function trim evictions
    modelled inside the step, see :func:`_cold_regime_ok`).

    Every cell must satisfy :func:`scan_eligible`; this is checked and raises
    ``ValueError`` otherwise (callers that already checked pass
    ``validate=False`` to skip the re-check).  Start/finish times are written
    back into the request objects exactly like the other backends --
    unless ``metrics_only=True``, which leaves the requests untouched and
    returns :class:`ScanMetrics` rows instead (so one workload can be
    shared across many cells)."""
    if not batch:
        return []
    with _entry_phase(batch):
        feats = _feats_cache()
        with _phase("prepare"):
            cells = [_single_cell(item, feats, memory_mb, container_mb,
                                  validate) for item in batch]
        return _run_scan_cells(cells, metrics_only=metrics_only)


def _single_cell(item: tuple, feats, memory_mb: int, container_mb: int,
                 validate: bool) -> _ScanCell:
    """One :func:`simulate_cells_scan` batch item as a prepared cell,
    checked against :func:`scan_eligible` when ``validate``."""
    requests, cores, policy = item[:3]
    warm = item[3] if len(item) > 3 else True
    if validate and not scan_eligible(requests, cores, policy,
                                      warm=warm, memory_mb=memory_mb,
                                      container_mb=container_mb):
        raise ScanRejected(
            "scan backend requires the ours regime, a known policy and "
            "(cold cells) node memory that holds any one container "
            f"(policy={policy!r}, cores={cores}, warm={warm}); use "
            "backend='vectorized' for the general exact fast path")
    f = feats(requests)
    return _ScanCell(requests=requests, feats=f, cores=cores, nodes=1,
                     policy=policy, assignment="single", warm=warm,
                     memory_mb=memory_mb, container_mb=container_mb,
                     pool_w=_cell_pool_width(f, cores, warm, memory_mb,
                                             container_mb))


def _cell_pool_width(f: _Arrivals, cores: int, warm: bool,
                     memory_mb: int, container_mb: int) -> int:
    """Container-table rows of a cell: 0 for warm cells and for cold cells
    in the ample-memory prewarm regime, which keep the count path."""
    if warm or _ample(len(f.fns), cores, memory_mb, container_mb):
        return 0
    return _pool_width(f.fns, cores, memory_mb, container_mb)


# ---------------------------------------------------------------------------
# cluster-scale scan: N-node cells, whole grids as bucketed batches
# ---------------------------------------------------------------------------
def cluster_scan_eligible(
    requests: list[Request],
    nodes: int,
    cores: int,
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
) -> bool:
    """True when the scan kernel reproduces the reference cluster within
    float32 rounding: ours mode, known policy, a container regime the kernel
    models (always-warm -- the §V-A warm-up provisions ``cores`` containers
    per function on the cluster's 40 GB nodes, so up to ~13 cores for the
    full SeBS set -- or ``warm=False``: the ample-memory prewarm regime, see
    :func:`_cold_regime_ok`, or memory-bound nodes on the container table,
    see :func:`_pool_regime_ok`, on a static uniform fleet without
    hedging), and

    * ``assignment="pull"`` -- any policy (priorities are re-ranked at pull
      time from the controller estimator, exactly like the reference), or
    * ``assignment="push"`` with ``lb`` least_loaded/home -- any policy
      including FC, whose per-node sliding-window count is modelled with
      bounded per-(node, fn) arrival-time rings.

    ``dynamics`` (a :class:`~repro.core.cluster.ClusterDynamics`) extends
    eligibility to **time-varying capacity**: autoscaling and scheduled node
    failures run inside the scan step.  Dynamic cells additionally require
    the least-loaded balancer for push (the home walk depends on the alive
    fleet size), failures confined to the initial fleet with at least one
    initial survivor, and -- for failures -- at least two initial nodes, so
    lost requests always have somewhere to go when they re-arrive.

    ``profile`` (a :class:`~repro.core.stragglers.NodeSpeedProfile`) and
    ``hedging`` (a :class:`~repro.core.stragglers.HedgingSpec`) extend
    eligibility to **heterogeneous fleets and straggler hedging**, composing
    freely with capacity dynamics: per-node effective speeds scale slot
    completion times inside the step (profile indices cover autoscaled
    nodes, like the reference's index-based ``_add_node``), steal-mode
    deadlines re-route still-queued calls to the least-loaded live peer (or
    back onto their own node when no peer exists, the reference's
    self-steal) and kills void in-flight watches, and duplicate-mode
    deadlines race copies with winner propagation.  The one remaining
    rejection: **duplicate-mode hedging under push with non-static
    capacity** -- racing copies of re-arrived lost requests have no
    reference-documented semantics, so such cells stay on the event loop.
    """
    if policy not in POLICY_NAMES or nodes < 1:
        return False
    if assignment == "push":
        if lb not in ("least_loaded", "home"):
            return False
    elif assignment != "pull":
        return False
    dyn = dynamics is not None and not dynamics.is_static
    if resilience is not None and not resilience.is_null:
        # the res carry segment models the push (frozen-priority) static
        # warm regime; resilience x pull / dynamics / hedging /
        # heterogeneity / cold-starts runs on the reference loop
        if (assignment != "push" or not warm or dyn
                or hedging is not None
                or (profile is not None and not profile.is_uniform)):
            return False
    if hedging is not None:
        if hedging.mode not in ("steal", "duplicate"):
            return False
        if hedging.mode == "duplicate" and dyn and assignment == "push":
            return False             # racing copies under churn: reference
    cap = dynamics.capacity_bound(nodes) if dynamics is not None else nodes
    if profile is not None and len(profile.speeds) > cap:
        return False                 # speeds beyond the fleet: misconfigured
    if dyn:
        if assignment == "push" and lb != "least_loaded":
            return False
        if dynamics.fail:
            failed = {idx for idx, _ in dynamics.fail}
            if (max(failed) >= nodes or len(failed) >= nodes
                    or any(at < 0 for _, at in dynamics.fail)):
                return False
    if not warm:
        if _cold_regime_ok(requests, cores, memory_mb, container_mb):
            return True
        # memory-bound nodes: the container table, on a static uniform
        # fleet without hedging
        return (not dyn and hedging is None
                and (profile is None or profile.is_uniform)
                and _pool_regime_ok({r.fn for r in requests}, cores,
                                    memory_mb, container_mb, assignment))
    fns = sorted({r.fn for r in requests})
    pool = _FastPool(memory_mb=memory_mb, container_mb=container_mb,
                     cores=cores, fn_memory=SEBS_MEMORY_MB)
    pool.warm_up(fns, per_fn=cores)
    return all(len(pool.free.get(fn, ())) >= cores for fn in fns)


def simulate_cluster_cells_scan(
    batch: list[tuple],
    memory_mb: int = CLUSTER_MEMORY_MB,
    container_mb: int = CLUSTER_CONTAINER_MB,
    validate: bool = True,
    metrics_only: bool = False,
) -> list[SimResult]:
    """Run a batch of ``(requests, nodes, cores, policy[, assignment[, lb[,
    dynamics[, profile[, hedging[, warm[, resilience]]]]]]])`` ours-mode
    cluster scenarios
    as bucketed vmapped scans -- an entire nodes x intensity x policy grid
    becomes a handful of XLA dispatches.  ``dynamics`` (a
    :class:`~repro.core.cluster.ClusterDynamics`, or ``None``) adds
    autoscaling and scheduled failures, ``profile`` (a
    :class:`~repro.core.stragglers.NodeSpeedProfile`) heterogeneous node
    speeds, ``hedging`` (a :class:`~repro.core.stragglers.HedgingSpec`)
    straggler work stealing or duplicate racing, and ``warm=False`` the
    cold-start/eviction regime -- per-(node, fn) container counts where
    memory is ample, a per-node container table (create, LRU eviction,
    blocked heads) on memory-bound nodes, each in a bucket of its own
    keyed by the table width -- all modelled inside the scan step, in any
    combination :func:`cluster_scan_eligible` accepts.

    Every cell must satisfy :func:`cluster_scan_eligible` (raises
    ``ValueError`` otherwise; ``validate=False`` skips the re-check for
    callers that already ran it).  Semantics follow the reference
    :class:`~repro.core.cluster.Cluster`; agreement is within the documented
    cluster cross-check tolerance (float32 clocks, index-order
    tie-breaking), see ``repro.core.sweep.CLUSTER_XCHECK_RTOL``; lost
    request, backup/steal and cold-start/eviction/creation counts are
    exact.
    ``metrics_only=True`` skips the per-request write-back and returns
    :class:`ScanMetrics` rows (bit-identical aggregate metrics, shareable
    workloads).
    """
    if not batch:
        return []
    with _entry_phase(batch):
        feats = _feats_cache()
        with _phase("prepare"):
            cells = [_cluster_cell(item, feats, memory_mb, container_mb,
                                   validate) for item in batch]
        return _run_scan_cells(cells, metrics_only=metrics_only)


def _entry_phase(batch: list[tuple]):
    """The ``entry`` phase of one batch; while a profiler trace is being
    taken, its span carries the batch's cell and call counts."""
    from jax.profiler import TraceAnnotation

    if not TraceAnnotation.is_enabled():
        return _phase("entry")
    return _phase("entry", cells=len(batch),
                  calls=sum(len(item[0]) for item in batch))


def _cluster_cell(item: tuple, feats, memory_mb: int, container_mb: int,
                  validate: bool) -> _ScanCell:
    """One :func:`simulate_cluster_cells_scan` batch item as a prepared
    cell, checked against :func:`cluster_scan_eligible` when ``validate``."""
    requests, nodes, cores, policy = item[:4]
    assignment = item[4] if len(item) > 4 else "pull"
    lb = item[5] if len(item) > 5 else "least_loaded"
    dynamics = item[6] if len(item) > 6 else None
    profile = item[7] if len(item) > 7 else None
    hedging = item[8] if len(item) > 8 else None
    warm = item[9] if len(item) > 9 else True
    resilience = item[10] if len(item) > 10 else None
    if validate and not cluster_scan_eligible(
            requests, nodes, cores, policy, assignment=assignment,
            lb=lb, warm=warm, memory_mb=memory_mb,
            container_mb=container_mb, dynamics=dynamics,
            profile=profile, hedging=hedging, resilience=resilience):
        raise ScanRejected(
            "scan cluster backend requires the ours regime with "
            "supported dynamics/heterogeneity/hedging/resilience and, "
            "for cold cells, ample container memory or a memory-bound "
            "pool the container table models "
            f"(policy={policy!r}, nodes={nodes}, cores={cores}, "
            f"assignment={assignment!r}, warm={warm}, "
            f"dynamics={dynamics!r}, hedging={hedging!r}, "
            f"resilience={resilience!r}); use backend='reference'")
    f = feats(requests)
    return _ScanCell(requests=requests, feats=f, cores=cores, nodes=nodes,
                     policy=policy, assignment=assignment, lb=lb, warm=warm,
                     dynamics=dynamics, profile=profile,
                     hedging=hedging, resilience=resilience,
                     memory_mb=memory_mb, container_mb=container_mb,
                     pool_w=_cell_pool_width(f, cores, warm, memory_mb,
                                             container_mb))


def simulate_cluster_scan(
    requests: list[Request],
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
) -> SimResult:
    """Single-cell convenience wrapper over
    :func:`simulate_cluster_cells_scan`."""
    return simulate_cluster_cells_scan(
        [(requests, nodes, cores_per_node, policy, assignment, lb,
          dynamics, profile, hedging, warm, resilience)],
        memory_mb=memory_mb, container_mb=container_mb)[0]


class ScanBackend:
    """Batched event-step loop variant of the ours-mode simulator.

    Supports single nodes *and* clusters: any of the five policies under the
    pull assignment or the push assignment (FC via per-(node, fn) count
    rings), time-varying capacity -- autoscaling and failure injection --
    heterogeneous node speeds (``hetero``), hedging in both steal and
    duplicate (racing-copy) modes, and the cold-start/eviction regime
    (``warm=False``) -- composable in any combination; the per-event scan
    step is an ordered pipeline of feature-flagged carry segments, so each
    combination compiles only the segments it enables.

    The one feature the scan kernel does not model is the stock baseline
    (``mode="baseline"``): processor sharing gives every in-flight call a
    state-dependent service rate that changes at each arrival/departure,
    which does not fit the fixed-slot one-core step; baseline cells run on
    ``backend='reference'``.  Per-cell restrictions that depend on *values*
    rather than flags (degenerate dynamics schedules, cold-regime memory
    bounds) live in :func:`cluster_scan_eligible`."""

    name = "scan"

    def supports(self, *, mode: str, policy: str, warm: bool,
                 nodes: int = 1, assignment: str = "pull",
                 autoscale: bool = False, failures: bool = False,
                 hedging: bool = False, hetero: bool = False,
                 timeouts: bool = False, retries: bool = False,
                 shedding: bool = False,
                 streaming: bool = False, trace: bool = False) -> bool:
        # streaming (the chunked carry-handoff path, core/streamscan.py)
        # covers the same flag matrix as the single-shot kernel, so the
        # flag never changes the answer here
        if trace:
            # no rich event hooks inside the kernel; the canonical
            # lifecycle stream comes from flight.trace_from_result
            return False
        if mode != "ours" or policy not in POLICY_NAMES:
            return False
        if assignment not in ("pull", "push"):
            return False
        if failures and nodes < 2:
            return False             # lost calls need a surviving node
        if timeouts or retries or shedding:
            # the res carry segment models the push (freeze-priority)
            # static warm regime; resilience x pull / dynamics / hedging /
            # heterogeneity / cold-starts runs on the reference loop
            if (assignment != "push" or not warm or autoscale or failures
                    or hedging or hetero):
                return False
        try:
            import jax  # noqa: F401
        except ImportError:
            return False
        return True

    def simulate(
        self,
        requests: list[Request],
        cores: int,
        policy: str = "fifo",
        mode: str = "ours",
        memory_mb: int = 32 * 1024,
        container_mb: int = 128,
        warm: bool = True,
        kappa: float = PS_KAPPA,
    ) -> SimResult:
        if mode != "ours":
            raise ValueError("scan backend requires ours mode")
        if kappa != PS_KAPPA:
            raise ValueError(
                "kappa parameterizes the baseline processor-sharing node, "
                "which the scan backend does not model; use "
                "backend='reference' for non-default kappa")
        return simulate_cells_scan(
            [(requests, cores, policy, warm)], memory_mb=memory_mb,
            container_mb=container_mb)[0]


register_backend(ScanBackend())
