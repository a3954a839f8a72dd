"""The readers of the program's own spans and counters: per tick from what
the window recorded, and nothing (no raise) from a program that lacks the
span or counter."""
from __future__ import annotations

import os

import pytest

import harness
from conftest import BENCH

SPAN_READERS = {
    "fleet.feed_ms_per_tick": "fleet.feed",
    "engine.emit_pull_ms_per_tick": "engine.emit_pull",
    "engine.emit_wait_ms_per_tick": "engine.emit_wait",
    "engine.emit_head_ms_per_tick": "engine.emit_head",
    "engine.emit_reset_ms_per_tick": "engine.emit_reset",
}
COUNTER_READERS = {
    "step.h2d_puts_per_tick": "transfers.h2d_count",
    "step.d2h_pulls_per_tick": "transfers.d2h_count",
}


def _read(name, spans=None, counters=None, ticks=4):
    mod = harness._load_module(os.path.join(BENCH, "metrics", name + ".py"))
    return mod.read({"spans": spans or {}, "counters": counters or {}, "ticks": ticks})


@pytest.mark.parametrize("name", sorted(SPAN_READERS))
def test_span_reader(name):
    assert _read(name, spans={SPAN_READERS[name]: 0.2}) == pytest.approx(50.0)
    assert _read(name, spans={"engine.emit": 0.2}) is None


@pytest.mark.parametrize("name", sorted(COUNTER_READERS))
def test_counter_reader(name):
    assert _read(name, counters={COUNTER_READERS[name]: 72}) == 18.0
    assert _read(name, counters={"transfers.h2d_bytes": 10}) is None


def test_tick_self_subtracts_direct_children_only():
    spans = {"fleet.tick": 0.4, "fleet.begin": 0.05, "fleet.dispatch": 0.1,
             "fleet.finish": 0.2, "fleet.deliver": 0.01,
             "engine.emit": 0.15, "engine.gather": 0.03}   # grandchildren
    assert _read("fleet.tick_self_ms_per_tick", spans=spans) == pytest.approx(10.0)
    assert _read("fleet.tick_self_ms_per_tick", spans={"engine.emit": 0.1}) is None
