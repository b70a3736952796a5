#!/usr/bin/env python3
"""Read the control of a cell: the plain reference, computed in the
precision below the one the cell states, put in the program's place.

    python bench/control.py --workload <cell> --seeds <n> [<n> ...]

For each seed it builds the window's first block at the cell's own size,
samples it as a run does, runs the reference in float64
and in the cell's ``control_precision``, and prints the compared numbers
beside the cell's limits as one JSON line.  The control has to come out
as not correct.  It needs no accelerator and does not run the program.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import check  # noqa: E402
from bench.drivers import DRIVERS  # noqa: E402


def control(cfg: dict, traffic: dict, seed: int) -> dict:
    driver = DRIVERS[traffic["driver"]](cfg, traffic, seed)
    driver.control_sample()
    numbers = driver.compare(dtype=traffic["control_precision"],
                             program=False)
    correct, shown = check.verdict(numbers, traffic["limits"])
    return {"seed": seed, "precision": traffic["control_precision"],
            "correct": correct, "compared_calls": numbers["compared_calls"],
            "check": shown}


def main(argv=None) -> int:
    from bench.run import load_cell

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    a = ap.parse_args(argv)
    _, _, cfg, traffic = load_cell(ROOT, a.workload)
    for seed in a.seeds:
        t0 = time.perf_counter()
        out = control(cfg, traffic, seed)
        out["seconds"] = time.perf_counter() - t0
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
