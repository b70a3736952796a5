"""Runs the benchmarks: one module per paper table/figure, plus the scan rows.

Prints the harness CSV contract ``name,us_per_call,derived`` for every row.
``--quick`` shrinks seed counts / grids for smoke runs.
"""

import argparse
import inspect
import sys
import time

from . import (
    engine_bench,
    fig2_cold_starts,
    fig5_fairness,
    fig6_multinode,
    table1_functions,
    table2_completion,
    table3_response_stretch,
    trace_replay,
)
from .common import emit

MODULES = [
    ("table1", table1_functions),
    ("table2", table2_completion),
    ("table3", table3_response_stretch),
    ("fig2", fig2_cold_starts),
    ("fig5", fig5_fairness),
    ("fig6", fig6_multinode),
    ("trace", trace_replay),
    ("engine", engine_bench),
]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--only", default=None,
                    help="comma-separated module prefixes")
    ap.add_argument("--backend", default=None,
                    help="simulation backend for sweep-based modules "
                         "(reference|vectorized|scan|auto|cross-check)")
    args = ap.parse_args()
    only = set(args.only.split(",")) if args.only else None

    print("name,us_per_call,derived")
    failures = 0
    for name, mod in MODULES:
        if only and name not in only:
            continue
        t0 = time.time()
        try:
            kwargs = {"quick": args.quick}
            if (args.backend is not None
                    and "backend" in inspect.signature(mod.run).parameters):
                kwargs["backend"] = args.backend
            rows = mod.run(**kwargs)
            emit(rows)
            print(f"# {name}: {len(rows)} rows in {time.time()-t0:.0f}s",
                  file=sys.stderr)
        except Exception as e:  # noqa: BLE001
            failures += 1
            print(f"# {name} FAILED: {type(e).__name__}: {e}",
                  file=sys.stderr)
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
