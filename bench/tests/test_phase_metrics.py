"""Readers of the scan entry's phase totals and step counters."""

import importlib
from types import SimpleNamespace

import pytest

import repro.core

TOTALS = {"prepare": {"s": 0.02, "n": 2}, "dispatch": {"s": 0.004, "n": 8},
          "wait": {"s": 1.5, "n": 8}, "unpack": {"s": 0.06, "n": 12},
          "entry": {"s": 0.001, "n": 1}, "fill": {"s": 0.03, "n": 8},
          "step_slots": 4000, "call_steps": 1000}
SPANS = {"prepare_us_per_call": "prepare",
         "dispatch_us_per_call": "dispatch",
         "wait_us_per_call": "wait", "unpack_us_per_call": "unpack"}
ALL = (*SPANS, "pad_step_share")


def _read(name: str, calls: int = 10_000):
    reader = importlib.import_module(f"bench.metrics.{name}")
    return reader.read(SimpleNamespace(calls=calls))


@pytest.mark.parametrize("name", sorted(SPANS))
def test_span_readers_divide_self_time_by_calls(monkeypatch, name):
    monkeypatch.setattr(repro.core, "scan_phase_totals", lambda: TOTALS,
                        raising=False)
    want = TOTALS[SPANS[name]]["s"] / 10_000 * 1e6
    assert _read(name) == pytest.approx(want)
    assert _read(name, calls=0) is None


def test_pad_step_share_is_the_padded_share_of_step_slots(monkeypatch):
    monkeypatch.setattr(repro.core, "scan_phase_totals", lambda: TOTALS,
                        raising=False)
    assert _read("pad_step_share") == pytest.approx(75.0)
    monkeypatch.setattr(repro.core, "scan_phase_totals",
                        lambda: {"step_slots": 0, "call_steps": 0})
    assert _read("pad_step_share") is None


@pytest.mark.parametrize("name", ALL)
def test_readers_give_none_without_the_totals(monkeypatch, name):
    # a program that predates the phase spans has no scan_phase_totals
    monkeypatch.delattr(repro.core, "scan_phase_totals", raising=False)
    assert _read(name) is None


@pytest.mark.parametrize("name", sorted(SPANS))
def test_span_readers_give_none_without_the_phase(monkeypatch, name):
    monkeypatch.setattr(repro.core, "scan_phase_totals",
                        lambda: {"step_slots": 0, "call_steps": 0},
                        raising=False)
    assert _read(name) is None
