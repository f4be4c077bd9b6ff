"""A whole run of a tiny cell on the CPU, with the look for a chip skipped.

The served answers must agree with the reference under both guarantees;
the timed path broken underneath, or the reference at 4-bit impacts in its
place, must come out not correct; the last line carries the contract's keys.
"""

import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from bench import control, gen, run
from bench.reference import Answer, Reference
from bench.spec import load_cell
from bench.tests.conftest import REPO, cpu_devices

SEED = 2**31 + 77


def _run(root, name, seconds=2.0, trace=False, grace_s=60.0):
    cell = load_cell(name, root / "BENCHMARK.json", root / "traffic")
    return run.run_cell(
        cell, SEED, seconds, trace, cpu_devices, root / ".cache", grace_s, log=lambda *_: None
    )


@pytest.mark.parametrize("name", ["tiny.safe", "tiny.sla", "tinyk.safe"])
def test_served_answers_agree_with_the_reference(tiny_root, name):
    out = _run(tiny_root, name)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert list(out) == ["correct", "attempted", "failed", "metrics", "device", "checks"]
    assert set(out["metrics"]) == {"qps", "p50_ms", "p95_ms", "setup_s"}
    assert set(out["device"]) == {"platform", "kind", "count", "memory_peak_bytes"}
    assert all(set(c) == {"value", "limit"} for c in out["checks"].values())


def test_traced_run_reports_per_layer_metrics(tiny_root):
    out = _run(tiny_root, "tiny.safe", seconds=3.0, trace=True)
    assert out["correct"]
    # The CPU has no device plane: the device readers find nothing and are left out.
    assert set(out["metrics"]) == {"plan_ms", "step_ms"}
    assert list(out)[-1] == "checks"


def _broken(fault):
    real = system_inflight().batched_traverse_resume

    def step(dix, blk, rest, order, bounds, budget, maxr, carry, **kw):
        if fault == "unchanged":
            return carry
        out = real(dix, blk, rest, order, bounds, budget, maxr, carry, **kw)
        n = out.i.shape[0]
        if fault == "half_left_out":
            # Every other lane advances its cursor unscored.
            keep = np.arange(n) % 2 == 0

            def pick(a, b):
                a, b = np.asarray(a), np.asarray(b)
                return np.where(keep.reshape((-1,) + (1,) * (a.ndim - 1)), a, b)

            return out._replace(state=jax.tree.map(pick, out.state, carry.state))
        if fault == "altered":
            ids = np.asarray(out.state.ids).copy()
            ids[ids >= 0] += 1
            return out._replace(state=out.state._replace(ids=ids))
        raise ValueError(fault)

    return step


def system_inflight():
    import repro.serving.inflight as inflight

    return inflight


@pytest.mark.parametrize("fault", ["unchanged", "half_left_out", "altered"])
@pytest.mark.parametrize("name", ["tiny.safe", "tiny.sla"])
def test_broken_timed_path_is_not_correct(tiny_root, monkeypatch, name, fault):
    monkeypatch.setattr(system_inflight(), "batched_traverse_resume", _broken(fault))
    out = _run(tiny_root, name, grace_s=1.0)
    assert not out["correct"] and out["failed"] > 0
    key = "unanswered" if fault == "unchanged" else "wrong_answers"
    assert out["checks"][key]["value"] > out["checks"][key]["limit"]


@pytest.mark.parametrize("name", ["tiny.safe", "tiny.sla", "tinyk.safe"])
def test_precision_control_is_not_correct(tiny_root, name):
    cell = load_cell(name, tiny_root / "BENCHMARK.json", tiny_root / "traffic")
    r = control.control_reading(cell, SEED, 2.0)
    assert r["of"] > 0 and r["wrong_answers"] > r["of"] // 2


def test_certified_check_holds_a_budget_exit_to_its_ranges():
    from bench.tests.conftest import tiny_config, tiny_traffic

    cfg = tiny_config()
    c = gen.make_corpus(cfg, 1)
    order, ends = gen.arrange(c)
    ref = Reference(c, order, ends, 10, 8, 0.4, 0.9)
    for q in gen.make_queries(c, tiny_traffic(), 20, 1):
        full = ref.topk(ref.scores(q))
        first = ref.expected(q, Answer(full[0], full[1], 1, "budget"), "certified")
        assert ref.check(q, Answer(*first, 1, "budget"), "certified")
        # The same partial answer may not claim to be safe, nor pass as rank-safe.
        if not np.array_equal(first[0], full[0]):
            assert not ref.check(q, Answer(*first, 1, "safe"), "certified")
            assert not ref.check(q, Answer(*first, 1, "budget"), "rank_safe")
        assert ref.check(q, Answer(*full, ref.n_ranges, "exhausted"), "rank_safe")


def test_refuses_without_a_tpu():
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "passage-isn.safe", "--seed", "1", "--seconds", "1"],
        cwd=REPO, capture_output=True, text=True, env=dict(os.environ, JAX_PLATFORMS="cpu"),
    )
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert not any(line.startswith("{") for line in p.stdout.splitlines())


def test_every_metric_has_a_reader():
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    from bench.spec import metric_reader

    for m in spec["end_to_end"] + spec["per_layer"]:
        assert callable(metric_reader(m["name"]))


def test_a_stall_in_the_window_is_named(tiny_root, monkeypatch):
    import time

    real = system_inflight().batched_traverse_resume
    calls = []

    def slow(*a, **kw):
        calls.append(1)
        if len(calls) == 30:  # past the lead-in's steps, inside the window
            time.sleep(0.5)
        return real(*a, **kw)

    lines = []
    monkeypatch.setattr(system_inflight(), "batched_traverse_resume", slow)
    cell = load_cell("tiny.safe", tiny_root / "BENCHMARK.json", tiny_root / "traffic")
    run.run_cell(cell, SEED, 2.0, False, cpu_devices, tiny_root / ".cache", 60.0, log=lines.append)
    (host,) = [x for x in lines if x.startswith("host:")]
    assert len(calls) > 30, host
    stalls = json.loads(host.split("ms: ", 1)[1])
    assert stalls[0]["phase"] == "step" and stalls[0]["wall_ms"] >= 300
    assert stalls[0]["thread_ms"] < 0.5 * stalls[0]["wall_ms"]  # slept, not computed
