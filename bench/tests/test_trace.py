"""The reduction from trace events to device numbers."""

from pathlib import Path

import pytest

from bench import devtrace
from bench.devtrace import Event

DATA = Path(__file__).with_name("data")
DEV = "/device:TPU:0"


def ev(line, name, a, b, plane=DEV):
    return Event(plane, line, name, float(a), float(b - a))


def test_busy_union_idle_and_programs_on_a_made_trace():
    events = [
        ev("python", "bench.window", 0, 1000, "/host:CPU"),
        ev("python", "bench.step", 0, 400, "/host:CPU"),
        ev("python", "bench.plan", 300, 400, "/host:CPU"),
        ev("python", "bench.sleep", 400, 1000, "/host:CPU"),
        ev("XLA Modules", "jit_batched_traverse_resume(7)", 100, 300),
        ev("XLA Modules", "jit_other(3)", 500, 600),
        ev("XLA Ops", "fusion.1", 100, 250),
        ev("XLA Ops", "sort.2", 200, 300),  # overlaps fusion.1
        ev("XLA Ops", "copy.3", 500, 600),
        ev("XLA Ops", "outside", 1200, 1300),  # after the window
    ]
    r = devtrace.reduce(events, ("batched_traverse_resume",))
    assert r["window_s"] == pytest.approx(1000e-9)
    assert r["busy_s"] == pytest.approx(300e-9)  # [100, 300) and [500, 600)
    p = r["programs"]["batched_traverse_resume"]
    assert p["dispatches"] == 1 and p["seconds"] == pytest.approx(200e-9)
    assert dict(r["device_ops"])["fusion.1"] == pytest.approx(150e-9)
    assert "outside" not in dict(r["device_ops"])
    # Gaps: [0,100) in the step; [300,500) overlaps step, plan and sleep by
    # 100 each, and the innermost span, the plan, names it; [600,1000) asleep.
    assert r["idle_by_phase"] == pytest.approx({"sleep": 400e-9, "plan": 200e-9, "step": 100e-9})
    assert r["idle_gaps"][0] == ("sleep", pytest.approx(400e-9))


def test_nothing_to_read_gives_none():
    assert devtrace.reduce([], ("x",)) is None
    host_only = [ev("python", "bench.window", 0, 10, "/host:CPU")]
    assert devtrace.reduce(host_only, ("x",)) is None


def test_recorded_chip_trace():
    """A quarter second of the serving path on a TPU v5e, as recorded."""
    events = devtrace.read_events(DATA / "trace_events.json")
    r = devtrace.reduce(events, ("batched_traverse_resume",))
    assert 0 < r["busy_s"] < r["window_s"]
    p = r["programs"]["batched_traverse_resume"]
    assert p["dispatches"] > 0 and 0 < p["seconds"] <= r["busy_s"] * 1.0001
    assert sum(s for _, s in r["idle_by_phase"].items()) == pytest.approx(
        r["window_s"] - r["busy_s"], rel=1e-6
    )
    assert len(r["device_ops"]) <= 10 and len(r["idle_gaps"]) <= 10


def test_traversal_sums_the_layout_programs_and_counts_the_first():
    events = [
        ev("python", "bench.window", 0, 1000, "/host:CPU"),
        ev("XLA Modules", "jit_step_program(1)", 100, 300),
        ev("XLA Modules", "jit_merge_program(1)", 300, 350),
        ev("XLA Modules", "jit_step_program(2)", 500, 700),
        ev("XLA Ops", "fusion.1", 100, 700),
    ]
    r = devtrace.reduce(events, ("step_program", "merge_program"))
    assert devtrace.traversal(r) == (pytest.approx(450e-9), 2)
    assert devtrace.traversal(devtrace.reduce(events, ("absent_program",))) is None
    assert devtrace.traversal(None) is None
