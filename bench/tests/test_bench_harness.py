"""The benchmark's pure parts: trace reduction, the generator, peaks,
window accounting and seeds."""

import json
from pathlib import Path

import numpy as np
import pytest

from bench import roofline, trace_reduce as T
from bench.check import clock_gap, verdict
from bench.traffic import burst
from bench.window import WARMUP, WINDOW, Window, block_seed

BENCH = Path(__file__).resolve().parents[1]
SEBS = json.loads((BENCH / "configs" / "sebs-openwhisk.json").read_text())


def _trace():
    """A window of 100 us: device busy over [10, 30] and [25, 40] (a
    module matching the step) and [70, 80]; host spans over the gaps."""
    us = 1000.0
    dev = T.Plane("/device:TPU:0", [
        T.Line("XLA Ops", [T.Event("fusion.1", 10 * us, 20 * us),
                           T.Event("fusion.2", 25 * us, 15 * us),
                           T.Event("copy.3", 70 * us, 10 * us),
                           T.Event("late", 150 * us, 5 * us)]),
        T.Line("XLA Modules", [T.Event("jit_event_step(7)", 10 * us, 30 * us),
                               T.Event("jit__make_planes(3)", 70 * us,
                                       10 * us)]),
    ])
    host = T.Plane("/host:CPU", [T.Line("python", [
        T.Event("bench.window", 0.0, 100 * us),
        T.Event("bench.generate", 0.0, 9 * us),
        T.Event("bench.entry_call", 9 * us, 91 * us),
        T.Event("not.ours", 50 * us, 5 * us),
    ])])
    return [host, dev]


def test_trace_reduce_busy_step_and_gaps():
    s = T.reduce(_trace(), "event_step")
    assert s.devices == 1
    assert s.window_s == pytest.approx(100e-6)
    assert s.busy_s == pytest.approx(40e-6)        # [10,40] and [70,80]
    assert s.step_s == pytest.approx(30e-6)
    assert s.idle_share == pytest.approx(0.6)
    assert [g[0] for g in s.idle_gaps] == ["bench.entry_call",
                                            "bench.entry_call",
                                            "bench.generate"]
    bare = [T.Plane("/host:CPU", [T.Line("python", [
        T.Event("bench.window", 0.0, 100e3),
        T.Event("np.asarray(jax.Array)", 40e3, 30e3)])]), _trace()[1]]
    assert [g[0] for g in T.reduce(bare, "event_step").idle_gaps] == [
        "host:np.asarray(jax.Array)", "host idle", "host idle"]
    assert [g[1] for g in s.idle_gaps] == pytest.approx([30e-6, 20e-6,
                                                          10e-6])
    assert s.device_ops[0] == ["fusion.1", pytest.approx(20e-6)]
    assert "late" not in dict(s.device_ops)       # outside the window


def test_trace_reduce_needs_one_window_span():
    planes = [p for p in _trace() if p.name != "/host:CPU"]
    with pytest.raises(ValueError):
        T.reduce(planes, "event_step")


def test_trace_reduce_without_device_is_all_idle():
    s = T.reduce([_trace()[0]], "event_step")
    assert s.devices == 0 and s.busy_s == 0.0 and s.step_s == 0.0


@pytest.mark.parametrize("seed", [0, 7, 2**31 + 12345, [3, 1, 4]])
def test_burst_is_deterministic_in_its_seed(seed):
    a = burst.generate_burst(SEBS["table_i_ms"], 16, 20, seed)
    b = burst.generate_burst(SEBS["table_i_ms"], 16, 20, seed)
    for x, y in zip(a, b):
        assert np.array_equal(x, y)
    c = burst.generate_burst(SEBS["table_i_ms"], 16, 20, 99)
    assert not np.array_equal(a[1], c[1])


@pytest.mark.parametrize("cores,intensity", [(16, 10), (16, 30), (72, 20),
                                             (8, 25)])
def test_burst_issues_1_1_c_v_calls_in_60_s_over_11_functions(cores,
                                                               intensity):
    f, r, p = burst.generate_burst(SEBS["table_i_ms"], cores, intensity, 5)
    assert len(SEBS["table_i_ms"]) == 11
    assert len(r) == round(1.1 * cores * intensity)
    assert np.bincount(f, minlength=11).tolist() == [
        round(cores * intensity / 10)] * 11
    assert r.min() >= 0.0 and r.max() < 60.0
    assert np.all(np.diff(r) >= 0) and np.all(p >= 1e-4)


def test_unknown_device_kind_is_an_error():
    assert roofline.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        roofline.peaks("TPU v9 imaginary")


@pytest.mark.parametrize("clock_bytes", [4, 8])
def test_compulsory_bytes_depend_on_calls_and_clock_width_only(clock_bytes):
    one = roofline.compulsory_bytes(1, clock_bytes)
    assert one == 5 * clock_bytes + 8
    assert roofline.compulsory_bytes(1000, clock_bytes) == 1000 * one
    with pytest.raises(ValueError):
        roofline.compulsory_bytes(10, 2)


def test_window_accounting_is_calls_over_entry_time():
    w = Window()
    w.add(100, 0.5, generate_s=9.0)
    w.add(300, 1.5)
    assert w.calls == 400 and w.blocks == 2
    assert w.block_entry_s == [0.5, 1.5]
    assert w.calls_per_s == pytest.approx(200.0)   # generation left out
    assert w.generate_s == 9.0
    with pytest.raises(ValueError):
        Window().calls_per_s
    with pytest.raises(ValueError):
        w.add(10, 0.0)


def test_warm_up_seed_is_never_a_window_seed():
    for run_seed in (0, 1, 2**31 - 1, 2**31 + 5, 2**40):
        warm = {tuple(block_seed(run_seed, WARMUP, 0, i)) for i in range(200)}
        window = {tuple(block_seed(run_seed, WINDOW, b, i))
                  for b in range(20) for i in range(200)}
        assert not warm & window
    with pytest.raises(ValueError):
        block_seed(-1, WINDOW, 0, 0)


def test_clock_gap_and_verdict():
    want = np.array([0.5, 10.0, 100.0, np.nan])
    assert clock_gap(want, want) == 0.0
    got = want + np.array([1e-6, 1e-5, 1e-4, 0.0])
    assert clock_gap(got, want) == pytest.approx(1e-6)
    limits = {"clock_gap": 1e-5, "counter_mismatches": 0, "lost_calls": 0}
    ok, shown = verdict({"clock_gap": 1e-6, "counter_mismatches": 0,
                         "lost_calls": 0}, limits)
    assert ok and shown["clock_gap"] == {"value": 1e-6, "limit": 1e-5}
    assert not verdict({"clock_gap": 1e-6, "counter_mismatches": 1,
                        "lost_calls": 0}, limits)[0]
    assert not verdict({"clock_gap": float("nan"), "counter_mismatches": 0,
                        "lost_calls": 0}, limits)[0]


def test_run_refuses_a_checkout_without_the_program(tmp_path):
    import shutil
    import subprocess
    import sys

    shutil.copy(BENCH.parent / "BENCHMARK.json", tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        "sebs.grid-f32", "--seed", "1", "--seconds", "1",
                        "--trace", "0"], cwd=tmp_path, capture_output=True,
                       text=True, timeout=120)
    assert p.returncode != 0 and p.stdout == ""


def test_stalls_count_compiles_only_while_on():
    import jax
    import jax.numpy as jnp

    from bench.run import Stalls

    s = Stalls()
    x = jnp.ones(5)
    jax.jit(lambda x: x + 1)(x)                     # before the window
    s.on = True
    jax.jit(lambda x: x * 3)(x)
    gc_s = s.gc_s
    import gc

    gc.collect()
    s.close()
    jax.jit(lambda x: x - 2)(x)                     # after the window
    assert s.compiles == 1 and s.compile_s > 0.0
    assert s.gc_s > gc_s
