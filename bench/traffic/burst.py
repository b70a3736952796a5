"""The paper's §V-B load generator, kept with the benchmark.

Intensity ``v`` on ``c`` cores issues ``round(c * v / 10)`` calls of each of
the 11 SeBS functions, ``1.1 * c * v`` in all, at times uniform in a
``duration_s`` window.  Processing times are lognormal around each
function's idle median, fitted to Table I's 5th/50th/95th percentiles
with the ~10 ms Kafka overhead stripped.  The draw order follows the
program's own generator, so one integer seed gives the same burst in both.
"""

from __future__ import annotations

import math

import numpy as np

KAFKA_OVERHEAD_MS = 10.0


def lognormal_fit(table_i: dict) -> dict:
    """``{fn: (median_s, sigma)}`` from Table I ``{fn: [p5, p50, p95] ms}``:
    the median less the Kafka overhead, and the shape from the wider of
    the two tails (``p95 / p50 = exp(1.645 sigma)``)."""
    fit = {}
    for fn, (p5, med, p95) in table_i.items():
        median_s = max(med - KAFKA_OVERHEAD_MS, 1.0) / 1000.0
        up = math.log(p95 / med) / 1.645
        dn = math.log(med / p5) / 1.645
        fit[fn] = (median_s, max(up, dn, 1e-3))
    return fit


def generate_burst(table_i: dict, cores: int, intensity: int, seed,
                   duration_s: float = 60.0):
    """One burst as arrays sorted by submit time: ``(fn_ids, r, p)`` with
    ``fn_ids`` indexing ``list(table_i)``.  ``seed`` is anything
    ``numpy.random.default_rng`` takes, such as a list of integers."""
    fit = lognormal_fit(table_i)
    per_fn = max(1, round(cores * intensity / 10))
    rng = np.random.default_rng(seed)
    fn_ids, r, p = [], [], []
    for i, fn in enumerate(table_i):
        median_s, sigma = fit[fn]
        times = rng.uniform(0.0, duration_s, size=per_fn)
        procs = median_s * np.exp(sigma * rng.standard_normal(per_fn))
        fn_ids.append(np.full(per_fn, i, dtype=np.int64))
        r.append(times)
        p.append(np.maximum(procs, 1e-4))
    fn_ids, r, p = (np.concatenate(a) for a in (fn_ids, r, p))
    order = np.argsort(r, kind="stable")
    return fn_ids[order], r[order], p[order]
