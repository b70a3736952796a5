"""Reader of the share of executed event steps that belong to no call."""

import importlib
from types import SimpleNamespace

import pytest

import repro.core

TOTALS = {"step_slots": 4000, "call_steps": 1000, "exec_steps": 1250}


def _read(calls: int = 10_000):
    reader = importlib.import_module("bench.metrics.exec_pad_share")
    return reader.read(SimpleNamespace(calls=calls))


def test_exec_pad_share_is_the_padded_share_of_executed_steps(monkeypatch):
    monkeypatch.setattr(repro.core, "scan_phase_totals", lambda: TOTALS,
                        raising=False)
    assert _read() == pytest.approx(20.0)
    # every executed step belongs to a call
    monkeypatch.setattr(repro.core, "scan_phase_totals",
                        lambda: {**TOTALS, "exec_steps": 1000})
    assert _read() == pytest.approx(0.0)


@pytest.mark.parametrize("totals", [
    {"step_slots": 4000, "call_steps": 1000},          # predates exec_steps
    {"step_slots": 0, "call_steps": 0, "exec_steps": 0},  # nothing ran
])
def test_exec_pad_share_gives_none_without_executed_steps(monkeypatch,
                                                          totals):
    monkeypatch.setattr(repro.core, "scan_phase_totals", lambda: totals,
                        raising=False)
    assert _read() is None


def test_exec_pad_share_gives_none_without_the_totals(monkeypatch):
    # a program that predates the phase spans has no scan_phase_totals
    monkeypatch.delattr(repro.core, "scan_phase_totals", raising=False)
    assert _read() is None
