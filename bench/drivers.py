"""How a cell drives the program: a sweep grid, one entry call a block.

A driver builds its traffic from the run's seed with the benchmark's own
generators, hands the program only generated requests, times each entry
call, keeps what the window produced, and afterwards compares a sample of
it, drawn from the seed, with the plain reference.  The program is
imported only here and only once ``run.py`` has found the chip.
"""

from __future__ import annotations

import time

import numpy as np

from .check import clock_gap
from .reference import cluster as ref
from .traffic import burst
from .window import WARMUP, WINDOW, block_seed


def _span(name: str):
    import jax

    return jax.profiler.TraceAnnotation(name)


def _medians(cfg: dict) -> dict:
    fit = burst.lognormal_fit(cfg["table_i_ms"])
    return {fn: median for fn, (median, _) in fit.items()}


def _dynamics(dyn: dict | None):
    if not dyn:
        return None
    from repro.core import ClusterDynamics

    return ClusterDynamics(
        fail=tuple((int(i), float(t)) for i, t in dyn.get("kills", ())),
        failure_detect_s=dyn["failure_detect_s"],
        autoscale=dyn["autoscale"],
        autoscale_interval_s=dyn["autoscale_interval_s"],
        scale_up_queue_per_slot=dyn["scale_up_queue_per_slot"],
        provision_delay_s=dyn["provision_delay_s"],
        max_nodes=dyn["max_nodes"])


def _ref_fleet(nodes: int, cores: int, policy: str, memory_mb: int,
               container_mb: int, dyn: dict | None) -> dict:
    cfg = dict(nodes=nodes, cores_per_node=cores, policy=policy,
               memory_mb=memory_mb, container_mb=container_mb,
               failure_detect_s=1.0)
    if dyn:
        cfg.update(failure_detect_s=dyn["failure_detect_s"],
                   autoscale=dyn["autoscale"],
                   autoscale_interval_s=dyn["autoscale_interval_s"],
                   scale_up_queue_per_slot=dyn["scale_up_queue_per_slot"],
                   provision_delay_s=dyn["provision_delay_s"],
                   max_nodes=dyn["max_nodes"],
                   kills=[(int(i), float(t))
                          for i, t in dyn.get("kills", ())])
    return cfg


class GridDriver:
    """A sweep grid through ``simulate_cluster_cells_scan(metrics_only=
    True)``: every block runs each grid point on ``seeds_per_block`` bursts,
    one burst shared by the points of an (intensity, seed)."""

    def __init__(self, cfg: dict, traffic: dict, seed: int) -> None:
        self.cfg, self.tr, self.seed = cfg, traffic, seed
        self.fns = list(cfg["table_i_ms"])
        t = traffic
        self.points = [(pol, nodes, inten) for inten in t["intensities"]
                       for nodes in t["nodes"] for pol in t["policies"]]
        self.blocks: list = []          # (bursts, rows) of each window block

    def _bursts(self, role: int, block: int) -> dict:
        t = self.tr
        out = {}
        for ii, inten in enumerate(t["intensities"]):
            for j in range(t["seeds_per_block"]):
                s = block_seed(self.seed, role, block,
                               ii * t["seeds_per_block"] + j)
                out[inten, j] = burst.generate_burst(
                    self.cfg["table_i_ms"], t["workload_cores"], inten, s,
                    t["duration_s"])
        return out

    def _batch(self, bursts: dict) -> list:
        from repro.core.request import Request

        t = self.tr
        dyn = _dynamics(t["dynamics"])
        reqs = {k: [Request(fn=self.fns[f], r=float(r), p_true=float(p))
                    for f, r, p in zip(*b)] for k, b in bursts.items()}
        return [(reqs[inten, j], nodes, t["cores_per_node"], pol, "pull",
                 "least_loaded", dyn, None, None, t["warm"])
                for j in range(t["seeds_per_block"])
                for (pol, nodes, inten) in self.points]

    def _entry(self, batch: list) -> list:
        from repro.core import simulate_cluster_cells_scan

        return simulate_cluster_cells_scan(
            batch, memory_mb=self.cfg["node_memory_mb"],
            container_mb=self.cfg["container_mb"], metrics_only=True)

    def warm_up(self) -> None:
        """One block of the window's own shapes, on warm-up seeds."""
        self._entry(self._batch(self._bursts(WARMUP, 0)))

    def block(self, k: int) -> tuple:
        """Generate and run window block ``k``: ``(calls, entry_s,
        generate_s)``.  A block runs whole; the window checks its deadline
        between blocks."""
        t0 = time.perf_counter()
        with _span("bench.generate"):
            bursts = self._bursts(WINDOW, k)
            batch = self._batch(bursts)
        t1 = time.perf_counter()
        with _span("bench.entry_call"):
            rows = self._entry(batch)
        t2 = time.perf_counter()
        self.blocks.append((bursts, rows))
        calls = sum(len(item[0]) for item in batch)
        return calls, t2 - t1, t1 - t0

    def failed_calls(self) -> int:
        return sum(int(np.count_nonzero(~np.isfinite(row.resp)))
                   for _, rows in self.blocks for row in rows)

    def release(self) -> None:
        """Drop what the program produced except the sampled rows."""
        self._sample = self._pick_sample()
        self.blocks = []

    def _pick_sample(self) -> list:
        """One window instance of every grid point, drawn from the seed:
        ``(point, burst arrays, row)``."""
        per_block = len(self.points)
        rng = np.random.default_rng([self.seed, 2])
        out = []
        for pi, point in enumerate(self.points):
            k = int(rng.integers(len(self.blocks)))
            j = int(rng.integers(self.tr["seeds_per_block"]))
            bursts, rows = self.blocks[k]
            out.append((point, bursts[point[2], j],
                        rows[j * per_block + pi]))
        return out

    def control_sample(self) -> None:
        """The sample of window block 0 without running the program, for
        the control."""
        n = len(self.points) * self.tr["seeds_per_block"]
        self.blocks = [(self._bursts(WINDOW, 0), [None] * n)]
        self.release()

    def compare(self, dtype: str = "float64",
                program: bool = True) -> dict:
        """Compared numbers over the sample.  With ``program=False`` the
        reference computed in ``dtype`` takes the program's place."""
        t = self.tr
        medians = _medians(self.cfg)
        gap, mismatches, lost, n, diverged = 0.0, 0, 0, 0, 0
        for (pol, nodes, _), (f, r, p), row in self._sample:
            fleet = _ref_fleet(nodes, t["cores_per_node"], pol,
                               self.cfg["node_memory_mb"],
                               self.cfg["container_mb"], t.get("dynamics"))
            kw = dict(warm=t["warm"], medians=medians,
                      fn_memory=self.cfg["fn_memory_mb"])
            want = ref.simulate(self.fns, f, r, p, fleet, **kw)
            if program:
                c = np.asarray(row.resp, dtype=np.float64) + r
                got = {"failures": row.failures,
                       "cold_starts": row.cold_starts,
                       "evictions": row.evictions,
                       "nodes_used": row.nodes_used}
            else:
                low = ref.simulate(self.fns, f, r, p, fleet, dtype=dtype,
                                   **kw)
                c, got = low.c, low.counters
            cell_gap = clock_gap(c, want.c)
            gap = max(gap, cell_gap)
            diverged += cell_gap > t["diverge_tol"]
            mismatches += sum(got[k] != want.counters[k] for k in got)
            lost += int(np.count_nonzero(np.isfinite(want.c)
                                         & ~np.isfinite(c)))
            n += len(r)
        return {"clock_gap": gap, "counter_mismatches": mismatches,
                "lost_calls": lost, "compared_calls": n,
                "diverged_cells_pct": 100.0 * diverged / len(self._sample)}


DRIVERS = {"grid": GridDriver}
