"""Observability substrate (repro.obs): metrics, traces, export, report.

Three contracts pinned here, per ISSUE 8 / DESIGN.md §13:

  * **results neutrality** — serving with full instrumentation (metrics +
    tracing at sample rate 1.0, durable JSONL sink) is *bitwise identical*
    to serving with the no-op handle: same doc ids, scores, and exit
    reasons on both the micro-batch and in-flight paths;
  * **exit-reason conservation** — telemetry exit counters sum to the
    number of queries served and match the returned per-query reasons as
    a multiset, at every layer (Engine, BatchEngine, both servers,
    ShardedEngine per shard);
  * **substrate unit behaviour** — log2 histogram bucketing/percentiles,
    deterministic trace sampling, torn-tail JSONL recovery, Prometheus
    exposition shape, and the report CLI's summary math.
"""

from __future__ import annotations

import json

import numpy as np
import pytest

from differential import assert_exit_reason_conservation
from repro.core.clustered_index import build_index
from repro.core.range_daat import Engine, exit_reason
from repro.data.synth import make_corpus, make_query_log
from repro.obs import (
    N_BUCKETS,
    FakeClock,
    Instrumentation,
    MetricsRegistry,
    NOOP,
    Tracer,
    TraceSink,
    json_snapshot,
    prometheus_text,
    read_traces,
    render,
    summarize,
)
from repro.obs.metrics import BUCKET_EDGES, bucket_index
from repro.obs.trace import sampled
from repro.serving import (
    BatchEngine,
    BucketSpec,
    InflightServer,
    MicroBatchServer,
    ShardedEngine,
    SlaBudgeter,
    result_exit_reason,
)


def _small_setup(seed: int, n_ranges: int, k: int = 5, n_queries: int = 12):
    corpus = make_corpus(
        n_docs=900, n_terms=700, n_topics=4, mean_doc_len=50, seed=seed
    )
    idx = build_index(corpus, n_ranges=n_ranges, strategy="clustered")
    eng = Engine(idx, k=k)
    log = make_query_log(corpus, n_queries=n_queries, seed=seed + 1)
    return eng, [log.terms[i] for i in range(log.n_queries)]


# --------------------------------------------------------------------------
# metrics substrate
# --------------------------------------------------------------------------


def test_bucket_index_edges():
    assert bucket_index(-3.0) == 0
    assert bucket_index(0.0) == 0
    assert bucket_index(0.9) == 0
    assert bucket_index(1.0) == 1
    assert bucket_index(1.9) == 1  # int() floors into [1, 2)
    assert bucket_index(2.0) == 2
    assert bucket_index(3.0) == 2
    assert bucket_index(4.0) == 3
    assert bucket_index(2.0**62) == N_BUCKETS - 1
    assert bucket_index(float(2**200)) == N_BUCKETS - 1  # overflow clamps
    assert BUCKET_EDGES[-1] == float("inf")


def test_counter_gauge_label_series():
    reg = MetricsRegistry()
    c = reg.counter("served")
    c.inc(reason="safe")
    c.inc(2.0, reason="budget")
    c.inc(reason="safe")
    assert c.value(reason="safe") == 2.0
    assert c.value(reason="budget") == 2.0
    assert c.value(reason="down") == 0.0
    assert c.total() == 4.0
    g = reg.gauge("alpha")
    g.set(1.5)
    g.set(2.5)  # last write wins
    assert g.value() == 2.5
    # get-or-create is idempotent per name; kind mismatch is an error.
    assert reg.counter("served") is c
    with pytest.raises(TypeError):
        reg.gauge("served")


def test_histogram_percentiles_one_octave():
    reg = MetricsRegistry()
    h = reg.histogram("latency_ms")
    values = [0.4, 1.5, 3.0, 3.0, 6.0, 12.0, 100.0, 900.0]
    for v in values:
        h.observe(v)
    assert h.count() == len(values)
    assert h.mean() == pytest.approx(float(np.mean(values)))
    # Log2 buckets: each percentile lands within one octave of the truth.
    for p in (50.0, 95.0, 99.0):
        got = h.percentile(p)
        true = float(np.percentile(values, p))
        assert true / 2.0 <= got <= true * 2.0 + 1.0, (p, got, true)
    snap = h.snapshot()["samples"][""]
    assert snap["count"] == len(values)
    assert sum(snap["buckets"].values()) == len(values)


def test_histogram_overflow_bucket_reports_floor():
    h = MetricsRegistry().histogram("huge")
    h.observe(float(2**100))
    assert h.percentile(99.0) == 2.0 ** (N_BUCKETS - 2)


# --------------------------------------------------------------------------
# tracing substrate
# --------------------------------------------------------------------------


def test_sampling_is_deterministic_and_calibrated():
    assert all(sampled(r, 1.0) for r in range(100))
    assert not any(sampled(r, 0.0) for r in range(100))
    hits = [sampled(r, 0.25) for r in range(4000)]
    assert hits == [sampled(r, 0.25) for r in range(4000)]  # run-stable
    assert 0.15 < np.mean(hits) < 0.35


def test_tracer_ring_and_sink_roundtrip(tmp_path):
    path = str(tmp_path / "trace.jsonl")
    tr = Tracer(sample_rate=1.0, ring=4, sink=TraceSink(path))
    for rid in range(6):
        tr.begin(rid)
        t = tr.get(rid)
        t.span("queue", 0.0, 0.001, depth=rid)
        t.attrs["exit_reason"] = "safe"
        tr.end(rid)
    tr.close()
    assert len(tr.ring) == 4  # bounded window
    assert tr.started == 6 and tr.finished == 6
    recs = read_traces(path)
    assert [r["rid"] for r in recs] == list(range(6))  # sink keeps all
    assert recs[0]["spans"][0]["name"] == "queue"
    assert recs[0]["exit_reason"] == "safe"


def test_read_traces_skips_torn_tail_only(tmp_path):
    path = str(tmp_path / "trace.jsonl")
    sink = TraceSink(path)
    tr = Tracer(sample_rate=1.0, sink=sink)
    for rid in range(3):
        tr.begin(rid)
        tr.end(rid)
    tr.close()
    with open(path, "a", encoding="utf-8") as f:
        f.write('{"rid": 3, "torn')  # crash mid-append
    assert [r["rid"] for r in read_traces(path)] == [0, 1, 2]
    # The next sink append repairs the tail before writing.
    tr2 = Tracer(sample_rate=1.0, sink=TraceSink(path))
    tr2.begin(7)
    tr2.end(7)
    tr2.close()
    assert [r["rid"] for r in read_traces(path)] == [0, 1, 2, 7]
    # Mid-file corruption is an error, not a silent skip.
    lines = open(path, encoding="utf-8").read().splitlines()
    lines.insert(1, "{broken")
    with open(path, "w", encoding="utf-8") as f:
        f.write("\n".join(lines) + "\n")
    with pytest.raises(ValueError):
        read_traces(path)


def test_fake_clock_is_shared_and_deterministic():
    clock = FakeClock(dt=0.5, start=10.0)
    assert clock() == 10.5
    assert clock() == 11.0
    clock.advance(4.0)
    assert clock() == 15.5


# --------------------------------------------------------------------------
# export + report
# --------------------------------------------------------------------------


def test_prometheus_exposition_shape():
    reg = MetricsRegistry()
    reg.counter("served", "queries served").inc(3, server="micro", reason="safe")
    reg.gauge("alpha").set(1.25)
    h = reg.histogram("latency_ms")
    for v in (0.5, 3.0, 70.0):
        h.observe(v, server="micro")
    text = prometheus_text(reg)
    assert '# TYPE served counter' in text
    assert 'served_total{reason="safe",server="micro"} 3' in text
    assert "alpha 1.25" in text
    assert '# TYPE latency_ms histogram' in text
    assert 'latency_ms_bucket{server="micro",le="+Inf"} 3' in text
    assert 'latency_ms_count{server="micro"} 3' in text
    # Cumulative buckets: every le line is monotone nondecreasing.
    les = [
        (float(ln.split('le="')[1].split('"')[0].replace("+Inf", "inf")),
         int(ln.rsplit(" ", 1)[1]))
        for ln in text.splitlines() if ln.startswith("latency_ms_bucket")
    ]
    assert les == sorted(les) and les[-1][1] == 3
    json.loads(json_snapshot(reg))  # exposition twin is valid JSON


def test_report_summary_math():
    recs = []
    for i in range(10):
        lat = 2.0 + i  # 2..11 ms
        recs.append({
            "rid": i,
            "exit_reason": "safe" if i % 2 == 0 else "budget",
            "latency_ms": lat,
            "sla_ms": 8.0,
            "quanta": 1 + i % 3,
            "fidelity_bound": 0 if i < 8 else 5,
            "exact": i < 8,
            "spans": [
                {"name": "queue", "t0_ms": 0.0, "dur_ms": 1.0},
                {"name": "service", "t0_ms": 1.0, "dur_ms": lat - 1.0},
            ],
        })
    s = summarize(recs, sla_ms=8.0)
    assert s["queries"] == 10
    assert s["sla"]["judged"] == 10
    assert s["sla"]["met"] == 7  # latencies 2..8 of 2..11
    assert s["sla"]["compliance"] == pytest.approx(0.7)
    assert s["exit_reasons"] == {"budget": 5, "safe": 5}
    assert s["queue_wait_ms"]["p50"] == pytest.approx(1.0)
    assert 0.0 < s["queue_share"] < 0.5
    assert s["fidelity_bound"]["nonzero"] == 2
    assert s["inexact"] == 2
    text = render(s)
    assert "compliance" in text and "exit reasons" in text


def test_report_cli(tmp_path, capsys):
    from repro.obs.__main__ import main

    path = str(tmp_path / "t.jsonl")
    tr = Tracer(sample_rate=1.0, sink=TraceSink(path))
    for rid in range(4):
        tr.begin(rid)
        t = tr.get(rid)
        t.span("queue", 0.0, 0.001)
        t.attrs.update(exit_reason="safe", latency_ms=3.0)
        tr.end(rid)
    tr.close()
    assert main(["report", path, "--sla-ms", "10"]) == 0
    out = capsys.readouterr().out
    assert "queries" in out and "4" in out
    assert main(["report", path, "--json"]) == 0
    assert json.loads(capsys.readouterr().out)["queries"] == 4
    assert main(["report", str(tmp_path / "missing.jsonl")]) == 1


# --------------------------------------------------------------------------
# results neutrality: instrumentation changes nothing served
# --------------------------------------------------------------------------


def _served_observables(served):
    return [
        (
            s.rid,
            np.asarray(s.result.doc_ids).tolist(),
            np.asarray(s.result.scores).tolist(),
            result_exit_reason(s.result),
        )
        for s in sorted(served, key=lambda s: s.rid)
    ]


@pytest.mark.parametrize("mode", ["micro", "inflight"])
def test_instrumented_serving_is_bitwise_identical(tmp_path, mode):
    eng, queries = _small_setup(seed=3, n_ranges=6)
    budgets = [None, 800]  # unbounded and budget-exit paths both pinned

    def serve(obs, cap):
        beng = BatchEngine(eng, BucketSpec(max_batch=4))
        bud = SlaBudgeter(sla_ms=float("inf"), obs=obs)
        if cap is not None:
            bud.budgets = lambda n, plans=None: np.full(n, cap, np.int32)
        if mode == "micro":
            srv = MicroBatchServer(beng, bud, max_batch=4, obs=obs)
            for q in queries:
                srv.submit(q)
            served = []
            while srv.pending:
                served.extend(srv.drain_once())
            return served
        srv = InflightServer(beng, bud, n_slots=4, quantum=2, obs=obs)
        for q in queries:
            srv.submit(q)
        return srv.run_until_idle()

    for cap in budgets:
        path = str(tmp_path / f"{mode}-{cap}.jsonl")
        # profile=True: the dispatch profiler's extra sync points are
        # timing-only, so identity must hold with it attached too (§14).
        obs = Instrumentation.make(
            sample_rate=1.0, trace_path=path, profile=True
        )
        instrumented = serve(obs, cap)
        obs.close()
        baseline = serve(NOOP, cap)
        assert _served_observables(instrumented) == _served_observables(baseline)
        # Full-rate tracing saw every query exactly once.
        recs = read_traces(path)
        assert sorted(r["rid"] for r in recs) == sorted(
            s.rid for s in baseline
        )
        for r in recs:
            assert r["exit_reason"] in ("safe", "budget", "exhausted", "down")
            assert any(sp["name"] == "queue" for sp in r["spans"])
            assert any(
                sp["name"] in ("service", "dispatch") for sp in r["spans"]
            )


# --------------------------------------------------------------------------
# exit-reason conservation at every layer
# --------------------------------------------------------------------------


def test_engine_exit_reason_conservation():
    eng, queries = _small_setup(seed=5, n_ranges=6)
    obs = Instrumentation()
    eng_i = Engine(eng.index, k=5, obs=obs)
    reasons = []
    for i, q in enumerate(queries):
        plan = eng_i.plan(q)
        kw = {"budget_postings": 500} if i % 2 else {}
        res = eng_i.traverse(plan, **kw)
        reasons.append(exit_reason(bool(res.exit_safe), bool(res.exit_budget)))
    assert_exit_reason_conservation(obs, "engine_queries", reasons)
    assert obs.metrics.histogram("engine_postings").count() == len(queries)


def test_batch_engine_exit_reason_conservation():
    eng, queries = _small_setup(seed=6, n_ranges=6)
    obs = Instrumentation()
    beng = BatchEngine(Engine(eng.index, k=5), BucketSpec(max_batch=4), obs=obs)
    plans = beng.plan_many(queries)
    caps = [400 if i % 3 == 0 else None for i in range(len(plans))]
    results = beng.run_batch(
        plans, budget_postings=[c or 2**31 - 1 for c in caps]
    )
    assert_exit_reason_conservation(
        obs, "batch_engine_queries", [r.exit_reason for r in results]
    )


@pytest.mark.parametrize("mode", ["micro", "inflight"])
def test_server_exit_reason_conservation(mode):
    eng, queries = _small_setup(seed=7, n_ranges=6)
    obs = Instrumentation.make(sample_rate=1.0)
    beng = BatchEngine(eng, BucketSpec(max_batch=4))
    bud = SlaBudgeter(sla_ms=float("inf"), obs=obs)
    if mode == "micro":
        srv = MicroBatchServer(beng, bud, max_batch=4, obs=obs)
        for q in queries:
            srv.submit(q)
        served = []
        while srv.pending:
            served.extend(srv.drain_once())
        label = "micro"
    else:
        srv = InflightServer(beng, bud, n_slots=4, quantum=2, obs=obs)
        for q in queries:
            srv.submit(q)
        served = srv.run_until_idle()
        label = "inflight"
    assert_exit_reason_conservation(
        obs,
        "served_queries",
        [result_exit_reason(s.result) for s in served],
        server=label,
    )
    sub = obs.metrics.counter("submitted")
    assert sub.value(server=label) == len(queries)


def test_sharded_exit_reason_conservation_per_shard():
    eng, queries = _small_setup(seed=8, n_ranges=8)
    obs = Instrumentation()
    se = ShardedEngine(
        Engine(eng.index, k=5), n_shards=2, use_mesh=False, obs=obs
    )
    per_shard: dict[int, list[str]] = {0: [], 1: []}
    merged = []
    for q in queries:
        r = se.traverse(se.engine.plan(q))
        for s, reason in enumerate(r.shard_exit_reasons):
            per_shard[s].append(reason)
        merged.append(result_exit_reason(r))
    for s, reasons in per_shard.items():
        assert_exit_reason_conservation(
            obs, "shard_exits", reasons, context=f"shard {s}", shard=s
        )
    # The merged counter sums to queries served, one count per query.
    total = obs.metrics.counter("sharded_queries").total()
    assert total == len(queries) == len(merged)
    assert obs.metrics.histogram("fidelity_bound").count() == len(queries)


# --------------------------------------------------------------------------
# control plane instrumentation
# --------------------------------------------------------------------------


def test_control_plane_health_and_serving_telemetry():
    from repro.control import ControlPlane

    eng, queries = _small_setup(seed=9, n_ranges=8)
    obs = Instrumentation.make(sample_rate=1.0)
    plane = ControlPlane(eng, n_shards=2, use_mesh=False, obs=obs)
    served = plane.replay(queries, batch_size=4)
    assert len(served) == len(queries)
    plane.mark_down(1)
    down = plane.replay(queries[:4], batch_size=4)
    plane.mark_up(1)
    assert len(down) == 4
    ht = obs.metrics.counter("health_transitions")
    assert ht.value(event="down", shard=1) == 1
    assert ht.value(event="up", shard=1) == 1
    assert_exit_reason_conservation(
        obs,
        "served_queries",
        [result_exit_reason(s.result) for s in served + down],
        server="micro",
    )
    # Down-shard serving surfaced inexactness in the fidelity telemetry.
    assert obs.metrics.counter("sharded_exact").value(exact=False) >= 1


# --------------------------------------------------------------------------
# ISSUE 9 / DESIGN.md §14: help catalog, profiler, SLOs, detectors, ops loop
# --------------------------------------------------------------------------


def test_every_registered_metric_carries_help():
    """Drive every serving layer through one handle: no empty help strings."""
    from repro.control import ControlPlane
    from repro.obs.slo import SloTracker, default_serving_slos

    eng, queries = _small_setup(seed=10, n_ranges=8)
    obs = Instrumentation.make(sample_rate=1.0, profile=True)
    plane = ControlPlane(eng, n_shards=2, use_mesh=False, obs=obs)
    plane.replay(queries[:8], batch_size=4)
    srv = InflightServer(
        BatchEngine(eng, BucketSpec(max_batch=4)),
        SlaBudgeter(sla_ms=float("inf"), obs=obs),
        n_slots=4,
        obs=obs,
    )
    for q in queries[:4]:
        srv.submit(q)
    srv.run_until_idle()
    tracker = SloTracker(obs, default_serving_slos(sla_ms=5.0))
    tracker.sample(now=0.0)
    tracker.evaluate(now=1.0)
    assert obs.metrics.missing_help() == []
    text = prometheus_text(obs.metrics)
    assert "# HELP latency_ms" in text
    assert "# HELP served_queries" in text
    assert "# HELP profiler_dispatches" in text


def test_unlimited_budget_sentinel_stays_out_of_histogram():
    """INT32_MAX admissions count separately; finite budgets histogram."""
    eng, queries = _small_setup(seed=13, n_ranges=6)

    obs = Instrumentation.make(sample_rate=1.0)
    srv = InflightServer(
        BatchEngine(eng, BucketSpec(max_batch=4)),
        SlaBudgeter(sla_ms=float("inf"), obs=obs),
        n_slots=4,
        obs=obs,
    )
    for q in queries:
        srv.submit(q)
    srv.run_until_idle()
    assert obs.metrics.histogram("budget_postings").count(server="inflight") == 0
    unl = obs.metrics.counter("unlimited_admissions").value(server="inflight")
    adm = obs.metrics.counter("admissions").value(server="inflight")
    assert unl == adm == len(queries)

    obs2 = Instrumentation.make(sample_rate=1.0)
    bud = SlaBudgeter(sla_ms=float("inf"), obs=obs2)
    bud.budgets = lambda n, plans=None: np.full(n, 800, np.int32)
    srv2 = MicroBatchServer(
        BatchEngine(eng, BucketSpec(max_batch=4)), bud, max_batch=4, obs=obs2
    )
    for q in queries:
        srv2.submit(q)
    while srv2.pending:
        srv2.drain_once()
    h2 = obs2.metrics.histogram("budget_postings")
    assert h2.count(server="micro") == len(queries)
    assert h2.percentile(50.0, server="micro") <= 1024.0  # real budgets, not 2^31
    assert obs2.metrics.counter("unlimited_admissions").value(server="micro") == 0


def test_cdf_below_bucket_edges():
    from repro.obs.slo import cdf_below

    buckets = [0] * N_BUCKETS
    buckets[bucket_index(5.0)] = 8  # [4, 8)
    assert cdf_below(buckets, 8.0) == pytest.approx(8.0)  # edge is exact
    assert cdf_below(buckets, 4.0) == pytest.approx(0.0)
    assert cdf_below(buckets, 6.0) == pytest.approx(4.0)  # interpolated
    assert cdf_below(buckets, -1.0) == 0.0
    over = [0] * N_BUCKETS
    over[N_BUCKETS - 1] = 3  # overflow bucket
    assert cdf_below(over, 1e18) == 0.0  # no interpolable mass
    assert cdf_below(over, float("inf")) == pytest.approx(3.0)


def test_slo_tracker_windowed_burn_hand_computed():
    """Two windows, hand-placed events: burn = (1 - a) / (1 - objective)."""
    from repro.obs.slo import HistogramBelow, SloSpec, SloTracker

    obs = Instrumentation()
    tracker = SloTracker(
        obs,
        [SloSpec("lat", 0.9, HistogramBelow("latency_ms", 8.0))],
        windows={"10s": 10.0, "100s": 100.0},
    )
    tracker.sample(now=0.0)
    for _ in range(6):
        obs.observe("latency_ms", 3.0)  # good: whole bucket under 8.0
    tracker.sample(now=50.0)
    for _ in range(4):
        obs.observe("latency_ms", 100.0)  # bad
    tracker.sample(now=100.0)
    rep = tracker.evaluate(now=100.0)["lat"]
    w10, w100 = rep["windows"]["10s"], rep["windows"]["100s"]
    # 10s window sees only the 4 bad events: attainment 0, burn 1/0.1.
    assert w10["total"] == pytest.approx(4.0)
    assert w10["attainment"] == pytest.approx(0.0)
    assert w10["burn"] == pytest.approx(10.0)
    # 100s window sees all 10: attainment 0.6, burn 0.4/0.1.
    assert w100["total"] == pytest.approx(10.0)
    assert w100["attainment"] == pytest.approx(0.6)
    assert w100["burn"] == pytest.approx(4.0)
    assert rep["budget_remaining"] == 0.0  # long burn 4.0 >= 1
    # Evaluate mirrored the report into slo_* gauges.
    g = obs.metrics.gauge("slo_burn_rate")
    assert g.value(slo="lat", window="10s") == pytest.approx(10.0)
    assert obs.metrics.gauge("slo_state").value(slo="lat") == 0  # ok


def test_ewma_detector_fire_clear_hysteresis():
    from repro.obs.detect import EwmaDetector

    det = EwmaDetector(
        "sig", patience=3, clear_patience=2, min_samples=4, direction="above"
    )
    clock = FakeClock(dt=1.0)
    for _ in range(6):  # warm-up + settled baseline
        assert det.update(10.0, clock()) is None
    assert det.mean == pytest.approx(10.0)
    got = [det.update(100.0, clock()) for _ in range(3)]
    assert got[0] is None and got[1] is None  # patience absorbs two spikes
    assert got[2] is not None and got[2].state == "fire"
    assert det.firing
    assert det.mean == pytest.approx(10.0)  # baseline frozen, not chasing
    back = [det.update(10.0, clock()) for _ in range(2)]
    assert back[0] is None
    assert back[1] is not None and back[1].state == "clear"
    assert not det.firing
    # A lone spike after clearing neither fires nor shifts the baseline much.
    assert det.update(100.0, clock()) is None
    assert det.mean < 20.0


def test_threshold_detector_and_monitor_emit_to_sink(tmp_path):
    from repro.obs.detect import DriftMonitor, ThresholdDetector

    path = str(tmp_path / "t.jsonl")
    obs = Instrumentation.make(
        sample_rate=1.0, trace_path=path, clock=FakeClock(dt=1.0)
    )
    mon = DriftMonitor(obs)
    sig = {"v": 0.5}
    mon.add(
        ThresholdDetector("skew", 2.0, patience=2, clear_patience=1),
        lambda reg: sig["v"],
    )
    seen = []
    mon.subscribe(lambda ev: seen.append((ev.detector, ev.state)))
    assert mon.poll() == []
    sig["v"] = 3.0
    assert mon.poll() == []  # patience
    fired = mon.poll()
    assert [e.state for e in fired] == ["fire"]
    assert mon.firing() == ["skew"]
    sig["v"] = 1.0
    assert [e.state for e in mon.poll()] == ["clear"]
    assert seen == [("skew", "fire"), ("skew", "clear")]
    assert obs.metrics.counter("alerts").value(detector="skew", state="fire") == 1
    obs.close()
    recs = read_traces(path)
    alerts = [r for r in recs if r.get("kind") == "alert"]
    assert [a["state"] for a in alerts] == ["fire", "clear"]
    # Alert records do not pollute the query-report math.
    assert summarize(recs)["queries"] == 0
    assert summarize(recs)["alerts"] == 2


def test_profiler_compile_recompile_classification():
    from repro.obs.profiler import Profiler

    obs = Instrumentation()
    prof = Profiler(obs)
    prof.record_dispatch("s", (4, 32), cache_before=0, cache_after=1)  # compile
    prof.record_dispatch("s", (4, 32), cache_before=1, cache_after=1)  # warm hit
    prof.record_dispatch("s", (4, 32), cache_before=1, cache_after=2)  # RECOMPILE
    prof.record_dispatch("s", (8, 32), cache_before=2, cache_after=3)  # compile
    prof.record_dispatch("s", (8, 64))  # no introspection: novelty fallback
    prof.record_dispatch("s", (8, 64))  # seen + no introspection: nothing
    snap = prof.snapshot()["s"]
    assert snap["dispatches"] == 6
    assert snap["compiles"] == 3
    assert snap["recompiles"] == 1
    assert prof.recompiles() == 1
    c = obs.metrics.counter("profiler_recompiles")
    assert c.value(site="s") == 1


def test_profiler_tracks_bucket_ladder_without_recompiles():
    """Across the pow2 ladder: one compile per program, zero recompiles."""
    # k=7 is unique to this test, so the module-level jit cache has no
    # warm entries for these programs and every first-seen shape compiles.
    eng, queries = _small_setup(seed=12, n_ranges=6, k=7)
    obs = Instrumentation.make(sample_rate=1.0, profile=True)
    beng = BatchEngine(eng, BucketSpec(max_batch=4), obs=obs)
    plans = beng.plan_many(queries)
    for chunk in (plans[:1], plans[:3], plans):  # batch buckets 1, 4, 4x3
        beng.run_batch(chunk)
    snap = obs.profiler.snapshot()["batch_engine"]
    assert snap["recompiles"] == 0
    assert snap["dispatches"] == beng.batches_run
    assert {tuple(s) for s in snap["shapes"]} == beng.compiled_shapes
    assert snap["compiles"] == len(beng.compiled_shapes)
    assert snap["device_ms"] > 0.0
    assert snap["hbm_total_bytes"] > 0
    # A second pass over warm programs adds dispatches, never compiles.
    beng.run_batch(plans)
    snap2 = obs.profiler.snapshot()["batch_engine"]
    assert snap2["dispatches"] > snap["dispatches"]
    assert snap2["compiles"] == snap["compiles"]
    assert snap2["recompiles"] == 0


def test_planted_shard_skew_arms_reshard_via_alert(tmp_path):
    """Detector -> ControlPlane arming, end-to-end through real serving."""
    from repro.control import ControlPlane
    from repro.obs.detect import DriftMonitor, ShardSkewProbe, ThresholdDetector

    eng, queries = _small_setup(seed=11, n_ranges=8, n_queries=24)
    path = str(tmp_path / "trace.jsonl")
    obs = Instrumentation.make(sample_rate=1.0, trace_path=path)
    plane = ControlPlane(
        eng, n_shards=2, use_mesh=False, obs=obs, reshard_trigger=1.02
    )
    # Plant the skew: dry-run the log on an uninstrumented twin engine,
    # then replay the single most shard-skewed query so one shard eats
    # the workload on every consecutive drain.
    twin = ShardedEngine(Engine(eng.index, k=5), n_shards=2, use_mesh=False)
    ratio = []
    for q in queries:
        p = np.asarray(
            twin.traverse(twin.engine.plan(q)).shard_postings, np.float64
        )
        ratio.append(p.max() * 2.0 / max(p.sum(), 1.0))
    hot = queries[int(np.argmax(ratio))]
    assert max(ratio) >= 1.5  # the plant is a real, strong skew

    monitor = DriftMonitor(obs)
    monitor.add(
        ThresholdDetector("shard_skew", 1.3, patience=2), ShardSkewProbe(2)
    )
    plane.enable_operations(monitor=monitor)
    for _ in range(16):
        plane.submit(hot)
        plane.drain_once()
    while plane.pending or plane.reshard_task is not None:
        plane.drain_once()

    fires = obs.metrics.counter("alerts").value(
        detector="shard_skew", state="fire"
    )
    assert fires >= 1
    # The sustained alert armed the planner's reshard path.
    assert plane.reshards_completed >= 1
    assert obs.metrics.counter("reshard_started").total() >= 1
    obs.close()
    alerts = [r for r in read_traces(path) if r.get("kind") == "alert"]
    assert any(
        a["detector"] == "shard_skew" and a["state"] == "fire" for a in alerts
    )


def test_burn_rate_alert_marks_plane_degraded():
    """Impossible latency SLO -> fast burn -> degraded-SLO plane state."""
    from repro.control import ControlPlane
    from repro.obs.detect import DriftMonitor, ThresholdDetector, gauge_probe
    from repro.obs.slo import SloTracker, default_serving_slos

    eng, queries = _small_setup(seed=14, n_ranges=8)
    obs = Instrumentation.make(sample_rate=1.0)
    plane = ControlPlane(eng, n_shards=2, use_mesh=False, obs=obs)
    tracker = SloTracker(obs, default_serving_slos(sla_ms=1e-4))
    monitor = DriftMonitor(obs)
    monitor.add(
        ThresholdDetector("slo_fast_burn", 14.4, patience=2),
        gauge_probe("slo_burn_rate", slo="latency_sla", window="5m"),
    )
    plane.enable_operations(slos=tracker, monitor=monitor)
    plane.replay(queries, batch_size=4)
    assert plane.stats()["degraded_slo"] is True
    assert "slo_fast_burn" in monitor.firing()
    assert obs.metrics.gauge("plane_degraded_slo").value() == 1.0
    assert obs.metrics.gauge("slo_state").value(slo="latency_sla") == 2


def test_slo_cli(tmp_path, capsys):
    from repro.obs.__main__ import main

    path = str(tmp_path / "t.jsonl")
    tr = Tracer(sample_rate=1.0, sink=TraceSink(path))
    clock = FakeClock(dt=0.01)
    for rid in range(8):
        tr.begin(rid)
        t = tr.get(rid)
        t0 = clock()
        lat = 2.0 if rid < 6 else 50.0
        t.span("service", t0, t0 + lat / 1e3)
        t.attrs.update(
            exit_reason="safe", latency_ms=lat, sla_ms=10.0, exact=True
        )
        tr.end(rid)
    tr.close()
    assert main(["slo", path]) == 0
    out = capsys.readouterr().out
    assert "latency_sla" in out and "burn" in out
    assert main(["slo", path, "--json", "--windows", "w=1"]) == 0
    rep = json.loads(capsys.readouterr().out)
    assert rep["queries"] == 8
    assert rep["sla_ms"] == 10.0  # recovered from the recorded attribute
    assert rep["slos"]["latency_sla"]["attainment"] == pytest.approx(0.75)
    assert main(["slo", str(tmp_path / "missing.jsonl")]) == 1


def test_watch_cli(tmp_path, capsys):
    from repro.obs import write_snapshot
    from repro.obs.__main__ import main

    obs = Instrumentation.make(sample_rate=1.0)
    obs.count("served_queries", 5, server="micro", reason="safe")
    obs.observe("latency_ms", 3.0, server="micro")
    obs.gauge("queue_depth", 2.0, server="micro")
    snap = str(tmp_path / "snap.json")
    write_snapshot(
        snap,
        obs.metrics,
        alerts=[{"detector": "skew", "state": "fire", "value": 2.5, "t_ms": 1.0}],
        t=12.5,
    )
    assert main(["watch", snap, "--once"]) == 0
    out = capsys.readouterr().out
    assert "served_queries" in out and "latency_ms" in out
    assert "skew" in out  # the alert tail rendered
    assert main(["watch", str(tmp_path / "missing.json"), "--once"]) == 1


# --------------------------------------------------------------------------
# phases: named parts of a server step on the profiler trace's clock
# --------------------------------------------------------------------------

STEP_PHASES = ["admit", "launch", "plan_ahead", "fetch", "retire"]


class _FakeAnnotation:
    """Stands in for ``jax.profiler.TraceAnnotation``: logs opens and closes."""

    def __init__(self, log, name, **args):
        self.log, self.name, self.args = log, name, args

    def __enter__(self):
        self.log.append(("open", self.name, self.args))
        return self

    def __exit__(self, *exc):
        self.log.append(("close", self.name, self.args))


def _phase_tree(log):
    """Top-level phases in order, each with the phases nested in it."""
    top, stack = [], []
    for kind, name, args in log:
        if kind == "open":
            node = (name, args, [])
            (stack[-1][2] if stack else top).append(node)
            stack.append(node)
        else:
            assert stack and stack[-1][0] == name, "phases close innermost first"
            stack.pop()
    assert not stack
    return top


def test_noop_phase_is_one_shared_null_context():
    from repro.obs import NoopInstrumentation

    first = NOOP.phase("admit", server="inflight", step=1)
    assert NOOP.phase("fetch", step=2) is first
    assert NoopInstrumentation().phase("retire") is first
    with NOOP.phase("plan", step=3):
        pass
    assert NOOP.snapshot() == {}


def test_enabled_handle_records_phase_ms_per_phase():
    obs = Instrumentation(clock=FakeClock(dt=0.5))
    with obs.phase("launch", server="inflight", step=1) as launch:
        with obs.phase("upload", server="inflight", step=1):
            pass
    with obs.phase("fetch", server="inflight", step=1):
        pass
    h = obs.metrics.histogram("phase_ms")
    for phase in ("launch", "upload", "fetch"):
        assert h.count(server="inflight", phase=phase) == 1
    # Every clock reading advances 0.5 s: upload spans one tick, launch three.
    assert h.mean(server="inflight", phase="upload") == pytest.approx(500.0)
    assert launch.ms == pytest.approx(1500.0)
    assert "# HELP phase_ms" in prometheus_text(obs.metrics)
    assert obs.metrics.missing_help() == []


@pytest.mark.parametrize("enabled", [False, True])
def test_annotator_sees_every_step_phase_in_order(enabled):
    from repro.obs import NoopInstrumentation

    eng, queries = _small_setup(seed=6, n_ranges=6)
    log = []

    def annotate(name, **args):
        return _FakeAnnotation(log, name, **args)

    obs = (
        Instrumentation.make(sample_rate=1.0, profile=True, annotate=annotate)
        if enabled
        else NoopInstrumentation(annotate=annotate)
    )
    srv = InflightServer(
        BatchEngine(eng, BucketSpec(max_batch=4)),
        SlaBudgeter(sla_ms=float("inf")),
        n_slots=4,
        quantum=2,
        obs=obs,
    )
    served = srv.replay(queries)
    assert len(served) == len(queries)

    tree = _phase_tree(log)
    assert all(name.startswith("repro.") for _, name, _ in log)
    steps = [tree[i : i + 5] for i in range(0, len(tree), 5)]
    assert len(steps) == srv.steps_run
    n_plans = 0
    for n, phases in enumerate(steps, start=1):
        assert [name[len("repro."):] for name, _, _ in phases] == STEP_PHASES
        for name, args, inner in phases:
            assert args == {"server": "inflight", "step": n}
            kids = [c[0] for c in inner]
            if name == "repro.launch":
                assert kids == ["repro.upload", "repro.dispatch"]
            elif name in ("repro.admit", "repro.plan_ahead"):
                assert set(kids) <= {"repro.plan"}
                assert all(c[1]["step"] == n for c in inner)
                n_plans += len(kids)
            else:
                assert kids == []
    assert n_plans == len(queries)  # every plan is named, inside one of the two
    if enabled:
        h = obs.metrics.histogram("phase_ms")
        assert h.count(server="inflight", phase="plan") == len(queries)
        for phase in STEP_PHASES:
            assert h.count(server="inflight", phase=phase) == srv.steps_run
        snap = obs.profiler.snapshot()["inflight"]
        assert snap["dispatches"] == srv.steps_run


def test_obs_imports_no_jax():
    import os
    import subprocess
    import sys
    from pathlib import Path

    src = Path(__file__).resolve().parents[1] / "src"
    code = "import sys, repro.obs; print(sorted(m for m in sys.modules if m.split('.')[0] == 'jax'))"
    env = dict(os.environ, PYTHONPATH=str(src))
    out = subprocess.run(
        [sys.executable, "-c", code], env=env, capture_output=True, text=True, check=True
    )
    assert out.stdout.strip() == "[]"
