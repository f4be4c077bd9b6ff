"""Program phases and device scopes read from a trace."""

from pathlib import Path

import pytest

from bench import devtrace, phasetrace
from bench.phasetrace import Event

DATA = Path(__file__).with_name("data")
DEV = "/device:TPU:0"
HOST = "/host:CPU"
PROGRAM = ("batched_traverse_resume",)
PHASE_METRICS = {f"{p}_idle_ms" for p in phasetrace.STEP_PHASES}
SCOPE_METRICS = {f"{s}_device_ms" for s in phasetrace.SCOPES}


def ev(line, name, a, b, plane=DEV, scope=""):
    return Event(plane, line, name, float(a), float(b - a), scope)


def host(name, a, b):
    return ev("python", name, a, b, HOST)


def test_a_program_phase_names_its_gap():
    events = [
        host("bench.window", 0, 1000),
        host("bench.step", 0, 400),
        host("repro.admit", 0, 100),
        host("repro.plan", 20, 80),
        host("repro.launch", 100, 150),
        host("repro.fetch", 200, 400),
        host("bench.sleep", 400, 1000),
        ev("XLA Modules", "jit_batched_traverse_resume(1)", 100, 200),
        ev("XLA Ops", "%fusion.1 = s32[8] fusion()", 100, 200, scope="gather"),
    ]
    # By the harness's spans alone, one gap runs from the fetch into the
    # sleep and takes the sleep's name whole; the admit's is only "step".
    assert devtrace.reduce(events, PROGRAM)["idle_by_phase"] == pytest.approx(
        {"sleep": 800e-9, "step": 100e-9}
    )
    r = phasetrace.reduce(events, PROGRAM)
    assert r["idle_by_phase"] == pytest.approx(
        {"sleep": 600e-9, "fetch": 200e-9, "plan": 60e-9, "admit": 40e-9}
    )
    # A plan counts under the phase that planned.
    assert r["idle_by_owner"] == pytest.approx({"fetch": 200e-9, "admit": 100e-9})
    assert r["step_idle_s"] == r["step_idle_named_s"] == pytest.approx(300e-9)
    assert r["idle_gaps"][:2] == [("sleep", pytest.approx(600e-9)), ("fetch", pytest.approx(200e-9))]
    got = phasetrace.per_dispatch(r)
    assert got["fetch_idle_ms"] == pytest.approx(200e-6)
    assert got["admit_idle_ms"] == pytest.approx(100e-6)
    assert got["plan_ahead_idle_ms"] == 0.0


def test_scopes_sum_leaf_ops_and_leave_out_an_enclosing_while(tmp_path):
    events = [
        host("bench.window", 0, 1000),
        ev("XLA Modules", "jit_batched_traverse_resume(1)", 100, 300),
        ev("XLA Ops", "%while.5 = (s32[16]) while()", 100, 300),
        ev("XLA Ops", "%fusion.1 = s32[8] fusion()", 100, 180, scope="gather"),
        ev("XLA Ops", "%fusion.2 = s32[8] fusion()", 180, 250, scope="accumulate"),
        ev("XLA Ops", "%sort.3 = s32[8] sort()", 250, 290, scope="topk"),
        ev("XLA Ops", "%copy.4 = s32[8] copy()", 290, 300),
        ev("XLA Modules", "jit_other(2)", 500, 600),
        ev("XLA Ops", "%fusion.9 = s32[8] fusion()", 500, 600, scope="gather"),  # another program
    ]
    phasetrace.write_events(events, tmp_path / "events.json")
    assert phasetrace.read_events(tmp_path / "events.json") == events
    r = phasetrace.reduce(events, PROGRAM)
    assert r["scopes"] == pytest.approx(
        {"gather": 80e-9, "accumulate": 70e-9, "topk": 40e-9, "other": 10e-9}
    )
    ops = dict(r["device_ops"])
    assert ops["accumulate/%fusion.2 = s32[8] fusion()"] == pytest.approx(70e-9)
    assert ops["%while.5 = (s32[16]) while()"] == pytest.approx(200e-9)
    # devtrace's own numbers are untouched.
    assert r["busy_s"] == pytest.approx(300e-9) and r["programs"] == devtrace.reduce(events, PROGRAM)["programs"]
    got = phasetrace.per_dispatch(r)
    assert set(got) == SCOPE_METRICS and got["gather_device_ms"] == pytest.approx(80e-6)


def test_a_trace_without_program_spans_or_scopes_reads_nothing():
    """The earlier recording (`trace_events.json`) has neither: devtrace's numbers, nothing added."""
    events = phasetrace.read_events(DATA / "trace_events.json")
    r = phasetrace.reduce(events, PROGRAM)
    assert r == devtrace.reduce(events, PROGRAM)
    assert phasetrace.per_dispatch(r) == {}
    assert phasetrace.per_dispatch(None) == {}
    assert phasetrace.reduce([], PROGRAM) is None


HLO = """\
HloModule jit_f, entry_computation_layout={()->s32[8]}

%region_1.5 (a: s32[], b: s32[]) -> s32[] {
  %a = s32[] parameter(0), metadata={op_name="scatter-add"}
  %b = s32[] parameter(1), metadata={op_name="scatter-add"}
  ROOT %add.30 = s32[] add(%a, %b), metadata={op_name="accumulate/add" stack_frame_id=59}
}

%fused_computation.12 (p0: s32[8], p1: s32[8], p2: s32[8]) -> s32[8] {
  %p0 = s32[8]{0} parameter(0)
  %p1 = s32[8]{0} parameter(1)
  %reshape.247 = s32[8]{0} reshape(%p1), metadata={op_name="jit(f)/gather/select_n"}
  %p2 = s32[8]{0} parameter(2)
  ROOT %scatter.5 = s32[8]{0} scatter(%p0, %reshape.247, %p2), to_apply=%region_1.5
}

%fused_computation.20 (p0: s32[8], p1: s32[8], p2: s32[8]) -> s32[8] {
  %p0.1 = s32[8]{0} parameter(0)
  %p1.1 = s32[8]{0} parameter(1)
  %p2.1 = s32[8]{0} parameter(2)
  ROOT %fusion.60 = s32[8]{0} fusion(%p0.1, %p1.1, %p2.1), kind=kCustom, calls=%fused_computation.12
}

ENTRY %main.9 (x: s32[8]) -> s32[8] {
  %x = s32[8]{0} parameter(0)
  %fusion.72 = s32[8]{0} fusion(%x), kind=kLoop, calls=%fused_computation.12, metadata={op_name="jit(f)/while/body/jit(g)/gather/gather"}
  %fusion.75 = s32[8]{0} fusion(%x, %x, %x), kind=kCustom, calls=%fused_computation.20
  %copy.3 = s32[8]{0} copy(%fusion.75)
  ROOT %sort.22 = s32[8]{0} sort(%copy.3), dimensions={0}, to_apply=%region_1.5, metadata={op_name="jit(f)/topk/sort"}
}
"""


def test_hlo_scopes_follow_op_names_fusion_roots_and_combiners():
    got = phasetrace.hlo_scopes(HLO)
    assert got["fusion.72"] == "gather"  # its own op_name wins over its root's
    # XLA left the rebuilt scatter no metadata: its combiner's scope, through
    # two levels of fusion.
    assert got["scatter.5"] == got["fusion.60"] == got["fusion.75"] == "accumulate"
    assert got["sort.22"] == "topk"
    assert "copy.3" not in got and "x" not in got
    assert phasetrace.op_scope("jit(f)/topk/jit(g)/gather/x") == "gather"
    assert phasetrace.op_scope("jit(f)/while/body/add") == ""


def test_recorded_chip_trace_with_phases_and_scopes():
    """A quarter second of ``web-isn.safe`` on a TPU v5e with phases and scopes."""
    events = phasetrace.read_events(DATA / "trace_events_phases.json")
    r = phasetrace.reduce(events, PROGRAM)
    prog = r["programs"]["batched_traverse_resume"]
    scoped = sum(r["scopes"].get(s, 0.0) for s in phasetrace.SCOPES)
    assert all(r["scopes"].get(s, 0.0) > 0 for s in phasetrace.SCOPES)
    assert scoped <= prog["seconds"] * 1.0001
    assert max(phasetrace.SCOPES, key=lambda s: r["scopes"][s]) == "gather"
    assert sum(r["idle_by_phase"].values()) == pytest.approx(r["window_s"] - r["busy_s"], rel=1e-6)
    assert sum(r["idle_by_owner"].values()) <= r["window_s"] - r["busy_s"]
    assert r["step_idle_named_s"] >= 0.9 * r["step_idle_s"] > 0
    assert set(phasetrace.per_dispatch(r)) == SCOPE_METRICS | PHASE_METRICS


@pytest.mark.parametrize("name", sorted(SCOPE_METRICS | PHASE_METRICS))
def test_each_phase_and_scope_reader_reads_the_recorded_chip_trace(name):
    from types import SimpleNamespace

    from bench.spec import metric_reader

    r = phasetrace.reduce(phasetrace.read_events(DATA / "trace_events_phases.json"), PROGRAM)
    got = metric_reader(name)(SimpleNamespace(trace=r))
    assert got == phasetrace.per_dispatch(r)[name] and got >= 0
    assert metric_reader(name)(SimpleNamespace(trace=None)) is None
