#!/usr/bin/env python3
"""Run one benchmark cell on the chip and print its result line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

from the root of a checkout.  The cell is looked up in ``BENCHMARK.json``;
its configuration file and its traffic file (``bench/workloads/<cell>.json``)
say what to run.  Set-up warms up every shape the window will use on a
warm-up seed; the window then runs blocks back to back until ``--seconds``
have passed; a sample of what the window produced is compared with the
plain reference once it has closed.  With ``--trace 1`` the window runs
under the JAX profiler and the cell's per-layer metrics are reported;
with ``--trace 0`` its end-to-end metrics.  The run refuses a device that
is not a TPU.  The last line of standard output is one JSON object.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402
from types import SimpleNamespace  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import check, roofline, trace_reduce  # noqa: E402
from bench.drivers import DRIVERS  # noqa: E402
from bench.window import Window  # noqa: E402


class NoDevice(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def load_cell(root: Path, name: str) -> tuple[dict, dict, dict, dict]:
    """``(spec, cell, config, traffic)`` for the cell ``name``."""
    spec = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                       f"known: {sorted(cells)}")
    cell = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    config = json.loads((root / configs[cell["config"]]["file"]).read_text())
    traffic = json.loads((root / "bench" / "workloads"
                          / f"{name}.json").read_text())
    if traffic["traffic"] != cell["traffic"]:
        raise ValueError(f"{name}: traffic file is {traffic['traffic']!r}, "
                         f"BENCHMARK.json says {cell['traffic']!r}")
    return spec, cell, config, traffic


def metrics_for(spec: dict, cell: str, per_layer: bool) -> list[dict]:
    group = spec["per_layer" if per_layer else "end_to_end"]
    return [m for m in group if "workloads" not in m or cell in m["workloads"]]


def find_devices(chips: int, require_tpu: bool):
    import jax

    devices = jax.devices()
    if require_tpu and devices[0].platform != "tpu":
        raise NoDevice(f"needs a TPU, JAX found {devices[0].platform!r}")
    if len(devices) < chips:
        raise NoDevice(f"needs {chips} chips, JAX found {len(devices)}")
    return devices[:chips]


def use_compile_cache(root: Path) -> None:
    """JAX's persistent cache in the checkout's fixed ``.jax_cache/`` (or
    where ``JAX_COMPILATION_CACHE_DIR`` says), keeping compiles of any
    length, so that only a cell's first run in a checkout compiles."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          str(root / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)


def peak_bytes(devices) -> int | None:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


class Tracer:
    """The profiler over the first ``units`` blocks of the window: long
    enough to see the steady path, short enough that the trace of a jnp
    while-loop, which records every step's operations, stays whole and is
    read within the run's time."""

    def __init__(self, log_dir: str | None, units: int) -> None:
        self.log_dir, self.units = log_dir, units
        self.active = False
        self.calls = 0

    def start(self) -> None:
        import jax

        if self.log_dir is None:
            return
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(self.log_dir, profiler_options=opts)
        self._span = jax.profiler.TraceAnnotation(trace_reduce.WINDOW_SPAN)
        self._span.__enter__()
        self.active = True

    def after(self, units_done: int, calls_done: int) -> None:
        """Called at each block boundary with the work so far."""
        if self.active and units_done >= self.units:
            self.stop(calls_done)

    def stop(self, calls_done: int) -> None:
        import jax

        if not self.active:
            return
        self._span.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self.active = False
        self.calls = calls_done


class Stalls:
    """What the host does inside the window besides the work: JAX
    compilations (a backend compile or a load from the persistent cache,
    each one compile event) and the collector's pauses."""

    COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self) -> None:
        import jax.monitoring

        self.on = False
        self.compiles = 0
        self.compile_s = 0.0
        self.gc_s = 0.0
        self._gc_t0 = None
        jax.monitoring.register_event_duration_secs_listener(self._event)
        gc.callbacks.append(self._gc)

    def _event(self, event: str, secs: float, **_) -> None:
        if self.on and event == self.COMPILE_EVENT:
            self.compiles += 1
            self.compile_s += secs

    def _gc(self, phase: str, _info: dict) -> None:
        if phase == "start":
            self._gc_t0 = time.perf_counter() if self.on else None
        elif self._gc_t0 is not None:
            self.gc_s += time.perf_counter() - self._gc_t0
            self._gc_t0 = None

    def close(self) -> None:
        import jax.monitoring

        self.on = False
        jax.monitoring.unregister_event_duration_listener(self._event)
        gc.callbacks.remove(self._gc)


def run_window(driver, seconds: float, window: Window,
               tracer: Tracer) -> None:
    deadline = time.perf_counter() + seconds
    tracer.start()
    k = 0
    while True:
        calls, entry_s, gen_s = driver.block(k)
        window.add(calls, entry_s, gen_s)
        k += 1
        tracer.after(k, window.calls)
        if time.perf_counter() >= deadline:
            tracer.stop(window.calls)
            return


def run(name: str, seed: int, seconds: float, trace: bool, *,
        root: Path = ROOT, require_tpu: bool = True,
        config: dict | None = None, traffic: dict | None = None,
        out=sys.stdout, err=sys.stderr) -> int:
    """One run of cell ``name``; ``config``/``traffic`` stand in for the
    cell's files (tests use small ones)."""
    spec, cell, cfg, tr = load_cell(root, name)
    cfg, tr = config or cfg, traffic or tr
    if not (root / "src" / "repro").is_dir():
        print(f"bench: no program under {root / 'src'}", file=err)
        return 2
    if str(root / "src") not in sys.path:
        sys.path.insert(0, str(root / "src"))
    try:
        devices = find_devices(cell["chips"], require_tpu)
    except NoDevice as e:
        print(f"bench: {e}", file=err)
        return 3
    from repro.core import (scan_bucket_timings, scan_cache_clear,
                            scan_cache_stats, scan_timings_clear)

    use_compile_cache(root)
    peaks = roofline.peaks(devices[0].device_kind) if require_tpu else None

    driver = DRIVERS[tr["driver"]](cfg, tr, seed)
    driver.warm_up()
    gc.collect()
    misses0 = scan_cache_stats()["misses"]
    scan_timings_clear()
    stalls = Stalls()
    setup_s = time.perf_counter() - T_START

    window = Window()
    tdir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    tracer = Tracer(tdir, tr["trace_units"])
    try:
        stalls.on = True
        try:
            run_window(driver, seconds, window, tracer)
        finally:
            stalls.close()
            tracer.stop(window.calls)
        runner_misses = scan_cache_stats()["misses"] - misses0
        records = scan_bucket_timings()
        mem = peak_bytes(devices)
        print(f"window: blocks={window.blocks} calls={window.calls} "
              f"entry_s={window.entry_s!r} generate_s={window.generate_s!r} "
              f"block_entry_s={window.block_entry_s!r} "
              f"jax_compiles={stalls.compiles} "
              f"compile_s={stalls.compile_s!r} "
              f"runner_misses={runner_misses} gc_s={stalls.gc_s!r}",
              file=out, flush=True)
        summary = None
        if trace:
            t0 = time.perf_counter()
            summary = trace_reduce.reduce(
                trace_reduce.load(trace_reduce.find_xplane(tdir)),
                tr["step_pattern"])
            print(f"trace: {tracer.calls} calls traced, read in "
                  f"{time.perf_counter() - t0!r} s", file=out, flush=True)
    finally:
        if tdir is not None:
            import shutil

            shutil.rmtree(tdir, ignore_errors=True)

    failed = driver.failed_calls()
    driver.release()
    scan_cache_clear()
    gc.collect()
    t0 = time.perf_counter()
    numbers = driver.compare()
    print(f"reference: {numbers['compared_calls']} calls compared in "
          f"{time.perf_counter() - t0!r} s", file=out, flush=True)
    correct, shown = check.verdict(numbers, tr["limits"])

    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": mem}
    metrics = {}
    result = {"correct": correct, "attempted": window.calls,
              "failed": failed, "metrics": metrics, "device": device}
    if trace:
        device.update(busy_s=summary.busy_s, window_s=summary.window_s)
        ctx = SimpleNamespace(
            calls=window.calls, traced_calls=tracer.calls, records=records,
            window_compiles=stalls.compiles, trace=summary,
            clock_bytes=tr["clock_bytes"], peaks=peaks, peak_bytes=mem)
        for m in metrics_for(spec, name, per_layer=True):
            reader = importlib.import_module(f"bench.metrics.{m['name']}")
            value = reader.read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        result["breakdown"] = {"device_ops": summary.device_ops,
                               "idle_gaps": summary.idle_gaps}
    else:
        e2e = {"sim_calls_per_s": window.calls_per_s, "setup_s": setup_s}
        for m in metrics_for(spec, name, per_layer=False):
            metrics[m["name"]] = {"value": e2e[m["name"]], "unit": m["unit"]}
    result["check"] = shown
    for k, v in shown.items():
        print(f"check {k} {v['value']!r} limit {v['limit']!r}", file=err)
    err.flush()
    print(json.dumps(result), file=out, flush=True)
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    a = ap.parse_args(argv)
    try:
        return run(a.workload, a.seed, a.seconds, bool(a.trace))
    except FileNotFoundError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2


if __name__ == "__main__":
    sys.exit(main())
