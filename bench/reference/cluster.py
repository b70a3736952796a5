"""Plain reference of the cluster semantics the benchmark's cells run.

A self-contained event loop of the paper's OpenWhisk model (arXiv:2207.13168
§III-§V): pull assignment from a global queue, the node-local FIFO / SEPT /
EECT / RECT / FC policies on a last-10 runtime estimator, the serialized
container-management channel, warm and cold container pools, node kills
with failure detection, and the queue-pressure autoscaler.  It imports
nothing of the program under test; the semantics follow the program's own
reference event loop, kept here so that no later change to the program can
move the yardstick.

Every clock value passes through ``q``, the rounding of the precision the
reference computes in: the identity for float64, and a rounding to float32
or bfloat16 for the low-precision control.  Rounding after each IEEE
operation computed in float64 gives the correctly rounded result of that
operation in the narrower type, so the control is the same algorithm
computed in the narrower precision.
"""

from __future__ import annotations

import heapq
import itertools
import math
from collections import defaultdict, deque
from dataclasses import dataclass, field
from typing import Callable

import numpy as np

REQ_OVERHEAD_S = 0.008      # client -> invoker (Kafka + HTTP)
RESP_OVERHEAD_S = 0.002     # invoker -> client
OURS_BASE = 0.06            # management channel: base cost per dispatch
OURS_SCALE = 0.35           # ... plus this times the container weight
OURS_PREWARM_EXTRA = 0.35   # ... plus this for a prewarm-container init
OURS_COLD_EXTRA = 0.9       # ... or this for a container created cold
WEIGHT_CAP_S = 9.0          # cap on the weight proxy
COLD_CREATE_S = 1.8
PREWARM_INIT_S = 0.6
PREWARM_COUNT = 2
ESTIMATOR_WINDOW = 10
FC_HORIZON_S = 60.0


def rounding(dtype: str) -> Callable[[float], float]:
    """``q`` for a precision name: ``float64``, ``float32`` or ``bfloat16``."""
    if dtype == "float64":
        return float
    if dtype == "float32":
        return lambda x: float(np.float32(x))
    if dtype == "bfloat16":
        import ml_dtypes

        bf16 = ml_dtypes.bfloat16
        return lambda x: float(bf16(x))
    raise ValueError(f"unknown reference precision {dtype!r}")


@dataclass
class Call:
    fn: str
    r: float
    p: float
    id: int
    r_prime: float | None = None
    start: float | None = None
    finish: float | None = None
    c: float | None = None
    node: int = -1
    attempts: int = 0


class EventLoop:
    def __init__(self) -> None:
        self._heap: list = []
        self._seq = itertools.count()
        self.now = 0.0

    def schedule(self, t: float, cb) -> None:
        if t < self.now - 1e-12:
            t = self.now
        heapq.heappush(self._heap, (t, next(self._seq), cb))

    def run(self) -> None:
        while self._heap:
            t, _, cb = heapq.heappop(self._heap)
            self.now = t
            cb()


class Estimator:
    """Per-function last-``window`` processing times and arrival log."""

    def __init__(self, q) -> None:
        self.q = q
        self._times: dict[str, deque] = defaultdict(deque)
        self._arrivals: dict[str, deque] = defaultdict(deque)
        self._last: dict[str, float] = {}
        self._prev: dict[str, float] = {}

    def observe_completion(self, fn: str, p: float) -> None:
        buf = self._times[fn]
        buf.append(p)
        while len(buf) > ESTIMATOR_WINDOW:
            buf.popleft()

    def observe_arrival(self, fn: str, now: float) -> None:
        self._prev[fn] = self._last.get(fn, now)
        self._last[fn] = now
        self._arrivals[fn].append(now)
        self._prune(fn, now)

    def estimate(self, fn: str) -> float:
        buf = self._times.get(fn)
        if not buf:
            return 0.0
        s = 0.0
        for x in buf:
            s = self.q(s + x)
        return self.q(s / len(buf))

    def recent_count(self, fn: str, now: float) -> int:
        self._prune(fn, now)
        return len(self._arrivals.get(fn, ()))

    def _prune(self, fn: str, now: float) -> None:
        arr = self._arrivals.get(fn)
        if not arr:
            return
        cutoff = self.q(now - FC_HORIZON_S)
        while arr and arr[0] <= cutoff:
            arr.popleft()


def priority(policy: str, call: Call, est: Estimator, now: float) -> float:
    """The paper's queue orders (§IV): lower runs first."""
    q = est.q
    if policy == "fifo":
        return call.r_prime if call.r_prime is not None else now
    if policy == "sept":
        return est.estimate(call.fn)
    if policy == "eect":
        rp = call.r_prime if call.r_prime is not None else now
        return q(rp + est.estimate(call.fn))
    if policy == "rect":
        return q(est._prev.get(call.fn, 0.0) + est.estimate(call.fn))
    if policy == "fc":
        return q(est.recent_count(call.fn, now) * est.estimate(call.fn))
    raise ValueError(f"unknown policy {policy!r}")


class Container:
    __slots__ = ("fn", "mb", "busy", "last_used", "seq")

    def __init__(self, fn, mb, seq, busy=False, last_used=0.0):
        self.fn, self.mb, self.seq = fn, mb, seq
        self.busy, self.last_used = busy, last_used


class Pool:
    """A node's container pool: warm reuse, prewarm init, cold create, LRU
    eviction under memory pressure, and at most ``cores`` idle containers
    per function.  Containers keep their creation order (``seq``), which
    breaks every tie the way a scan of one list in that order would."""

    def __init__(self, memory_mb: int, container_mb: int, cores: int,
                 fn_memory: dict) -> None:
        self.memory_mb, self.container_mb = memory_mb, container_mb
        self.cores, self.fn_memory = cores, fn_memory
        self._seq = itertools.count()
        self.by_fn: dict[str, list[Container]] = defaultdict(list)
        self.prewarm: list[Container] = []
        self.used_mb = 0
        self.cold_starts = self.evictions = 0
        for _ in range(PREWARM_COUNT):
            self._add_prewarm()

    def _size(self, fn: str) -> int:
        return int(self.fn_memory.get(fn, self.container_mb))

    def _add_prewarm(self) -> bool:
        if self.used_mb + self.container_mb > self.memory_mb:
            return False
        self.prewarm.append(Container(None, self.container_mb,
                                      next(self._seq)))
        self.used_mb += self.container_mb
        return True

    def _insert(self, c: Container) -> None:
        lst = self.by_fn[c.fn]
        i = len(lst)
        while i and lst[i - 1].seq > c.seq:
            i -= 1
        lst.insert(i, c)

    def _remove(self, c: Container) -> None:
        self.by_fn[c.fn].remove(c)
        self.used_mb -= c.mb

    def warm_up(self, fns, per_fn: int) -> None:
        for _ in range(per_fn):
            for fn in fns:
                if self.used_mb + self._size(fn) <= self.memory_mb:
                    c = Container(fn, self._size(fn), next(self._seq))
                    self.by_fn[fn].append(c)
                    self.used_mb += c.mb

    def acquire(self, fn: str, now: float):
        """``(container, cold, startup_delay)`` or ``None`` when the call
        has to wait for memory."""
        best = None
        for c in self.by_fn.get(fn, ()):
            if not c.busy and (best is None or c.last_used > best.last_used):
                best = c
        if best is not None:
            best.busy, best.last_used = True, now
            return best, False, 0.0
        if self.prewarm:
            c = self.prewarm.pop(0)
            c.fn, c.busy, c.last_used = fn, True, now
            self._insert(c)
            self.cold_starts += 1
            n = len(self.prewarm)
            while n < PREWARM_COUNT and self._add_prewarm():
                n += 1
            return c, True, PREWARM_INIT_S
        size = self._size(fn)
        if self.used_mb + size > self.memory_mb:
            idle = sorted((c for lst in self.by_fn.values() for c in lst
                           if not c.busy and c.fn != fn),
                          key=lambda c: (c.last_used, c.seq))
            while idle and self.used_mb + size > self.memory_mb:
                self._remove(idle.pop(0))
                self.evictions += 1
        if self.used_mb + size <= self.memory_mb:
            c = Container(fn, size, next(self._seq), busy=True, last_used=now)
            self.by_fn[fn].append(c)
            self.used_mb += size
            self.cold_starts += 1
            return c, True, COLD_CREATE_S
        return None

    def release(self, c: Container, now: float) -> None:
        c.busy, c.last_used = False, now
        idle = [x for x in self.by_fn[c.fn] if not x.busy]
        if len(idle) > self.cores:
            idle.sort(key=lambda x: x.last_used)
            for victim in idle[:len(idle) - self.cores]:
                self._remove(victim)
                self.evictions += 1


class Node:
    """One invoker running the paper's scheduler (§IV)."""

    def __init__(self, cl: "Cluster", idx: int) -> None:
        cfg, q = cl.cfg, cl.q
        self.cl, self.idx, self.q = cl, idx, q
        self.slots = cfg["cores_per_node"]
        self.alive = True
        self.busy = 0
        self.free_at = 0.0
        self.est = Estimator(q)
        self.queue: list = []
        self._qseq = itertools.count()
        self.in_flight: dict[int, tuple] = {}
        self.pool = Pool(cfg["memory_mb"], cfg["container_mb"], self.slots,
                         cl.fn_memory)
        if cl.warm_fns:
            self.pool.warm_up(cl.warm_fns, per_fn=self.slots)
            for fn in cl.warm_fns:
                w = q(cl.medians.get(fn, 0.1))
                for _ in range(min(self.slots, ESTIMATOR_WINDOW)):
                    self.est.observe_completion(fn, w)

    def submit(self, call: Call) -> None:
        if not self.alive:
            return
        now = self.cl.loop.now
        call.node = self.idx
        call.r_prime = now
        self.est.observe_arrival(call.fn, now)
        prio = priority(self.cl.cfg["policy"], call, self.est, now)
        heapq.heappush(self.queue, (prio, next(self._qseq), call))
        for call, got in self._dispatch(now):
            self._launch(call, got)

    def _dispatch(self, now: float) -> list:
        """Start queued calls while slots are free; non-preemptive, and a
        head that finds no memory blocks the queue."""
        started = []
        while self.queue and self.busy < self.slots:
            got = self.pool.acquire(self.queue[0][2].fn, now)
            if got is None:
                break
            call = heapq.heappop(self.queue)[2]
            self.busy += 1
            started.append((call, got))
        return started

    def _launch(self, call: Call, got) -> None:
        q, cl = self.q, self.cl
        container, cold, delay = got
        self.in_flight[call.id] = got
        w = min(cl.medians.get(call.fn, call.p), WEIGHT_CAP_S)
        cost = q(OURS_BASE + q(OURS_SCALE * w))
        if cold:
            cost = q(cost + (OURS_COLD_EXTRA if delay > 1.0
                             else OURS_PREWARM_EXTRA))
        self.free_at = q(max(cl.loop.now, self.free_at) + cost)
        call.start = self.free_at
        cl.loop.schedule(q(call.start + call.p),
                         lambda: self._finish(call, got))

    def _finish(self, call: Call, got) -> None:
        if not self.alive or self.in_flight.get(call.id) is not got:
            return
        del self.in_flight[call.id]
        now = self.cl.loop.now
        call.finish = now
        call.c = self.q(now + RESP_OVERHEAD_S)
        self.est.observe_completion(call.fn, call.p)
        self.pool.release(got[0], now)
        self.busy -= 1
        started = self._dispatch(now)
        # the controller's pull round runs before this node's own backfill
        self.cl.on_complete(call)
        for c, got in started:
            self._launch(c, got)

    def kill(self) -> list[Call]:
        self.alive = False
        lost = [self.cl.calls_by_id[i] for i in self.in_flight]
        self.in_flight.clear()
        lost += [e[2] for e in sorted(self.queue)]
        self.queue.clear()
        return lost

    @property
    def free_slots(self) -> int:
        return max(0, self.slots - self.busy)


class Cluster:
    """Pull-model cluster: invokers with a free slot pull the globally best
    queued call, ranked by the policy on the controller's history."""

    def __init__(self, cfg: dict, calls: list[Call], *, warm: bool,
                 medians: dict, fn_memory: dict, q) -> None:
        self.cfg, self.q = cfg, q
        self.medians, self.fn_memory = medians, fn_memory
        self.loop = EventLoop()
        self.calls = calls
        self.calls_by_id = {c.id: c for c in calls}
        self.warm_fns = sorted({c.fn for c in calls}) if warm else None
        self.est = Estimator(q)
        self.global_queue: list[Call] = []
        self.done = 0
        self.failures = 0
        self.nodes: list[Node] = []
        self.provisioned = cfg["nodes"]
        for _ in range(cfg["nodes"]):
            self.nodes.append(Node(self, len(self.nodes)))

    def _alive(self) -> list[Node]:
        return [n for n in self.nodes if n.alive]

    def _route(self, call: Call) -> None:
        self.est.observe_arrival(call.fn, self.loop.now)
        self.global_queue.append(call)
        self.pull_round()

    def pull_round(self) -> None:
        policy = self.cfg["policy"]
        while self.global_queue:
            free = [n for n in self._alive() if n.free_slots > 0]
            if not free:
                return
            node = free[0]
            for n in free:
                if n.free_slots > node.free_slots:
                    node = n
            now = self.loop.now
            gq = self.global_queue
            best = min(range(len(gq)),
                       key=lambda i: priority(policy, gq[i], self.est, now))
            node.submit(gq.pop(best))

    def on_complete(self, call: Call) -> None:
        self.done += 1
        self.est.observe_completion(call.fn, call.p)
        self.pull_round()

    def _kill(self, idx: int) -> None:
        node = self.nodes[idx]
        if not node.alive:
            return
        lost = node.kill()
        self.failures += len(lost)
        detect = self.q(self.loop.now + self.cfg["failure_detect_s"])
        for call in lost:
            call.attempts += 1
            self.loop.schedule(detect, lambda c=call: self._requeue(c))

    def _requeue(self, call: Call) -> None:
        self.global_queue.append(call)
        self.pull_round()

    def _tick(self) -> None:
        cfg = self.cfg
        if self.done >= len(self.calls):
            return
        alive = self._alive()
        queued = len(self.global_queue) + sum(len(n.queue) for n in alive)
        slots = sum(n.slots for n in alive)
        if (queued > cfg["scale_up_queue_per_slot"] * max(slots, 1)
                and self.provisioned < cfg["max_nodes"]):
            self.provisioned += 1
            self.loop.schedule(self.q(self.loop.now
                                      + cfg["provision_delay_s"]),
                               self._provision)
        self.loop.schedule(self.q(self.loop.now + cfg["autoscale_interval_s"]),
                           self._tick)

    def _provision(self) -> None:
        self.nodes.append(Node(self, len(self.nodes)))
        self.pull_round()

    def run(self) -> None:
        # kills, arrivals, then the first tick: the order ties resolve in
        for idx, at in self.cfg.get("kills", ()):
            self.loop.schedule(self.q(at), lambda i=idx: self._kill(i))
        for call in self.calls:
            self.loop.schedule(self.q(call.r + REQ_OVERHEAD_S),
                               lambda c=call: self._route(c))
        if self.cfg.get("autoscale"):
            self.loop.schedule(self.q(self.cfg["autoscale_interval_s"]),
                               self._tick)
        self.loop.run()


@dataclass
class Outcome:
    """One reference run: per-call completion clocks (``c``, NaN where a
    call never completed), receive clocks and the exact counters."""

    c: np.ndarray
    r_prime: np.ndarray
    counters: dict = field(default_factory=dict)


def simulate(fn_names, fn_ids, r, p, cfg: dict, *, warm: bool,
             medians: dict, fn_memory: dict,
             dtype: str = "float64") -> Outcome:
    """Run the reference over one arrival list (``fn_ids`` into
    ``fn_names``, client submit times ``r``, processing times ``p``) on the
    fleet ``cfg``, computing every clock in ``dtype``."""
    q = rounding(dtype)
    calls = [Call(fn=fn_names[int(f)], r=q(float(t)), p=q(float(x)), id=i)
             for i, (f, t, x) in enumerate(zip(fn_ids, r, p))]
    cl = Cluster(cfg, calls, warm=warm, medians=medians,
                 fn_memory=fn_memory, q=q)
    cl.run()
    c = np.array([math.nan if x.c is None else x.c for x in calls])
    rp = np.array([math.nan if x.r_prime is None else x.r_prime
                   for x in calls])
    counters = {
        "failures": cl.failures,
        "cold_starts": sum(n.pool.cold_starts for n in cl.nodes),
        "evictions": sum(n.pool.evictions for n in cl.nodes),
        "nodes_used": len(cl.nodes),
    }
    return Outcome(c=c, r_prime=rp, counters=counters)
