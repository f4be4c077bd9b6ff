"""In-flight (slot-swapping) serving loop: bitwise-resume + saturation.

The tentpole invariant, pinned tier-1: a query served across N slot quanta
via ``batched_traverse_resume`` — including host<->device carry round-trips
between quanta, mid-flight slot swaps, and budget exits — is *bitwise
identical* to the same query served by one ``device_traverse`` call: same
doc ids, scores, work counters, and exit reason. ``InflightServer`` keeps
the carry on the device; its every dispatch receives, bit for bit, the
carry the host round trip would have uploaded.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.clustered_index import build_index
from repro.core.range_daat import (
    Engine,
    TraverseCarry,
    batched_init_carry,
    batched_traverse_resume,
    carry_done,
)
from repro.data.synth import make_corpus, make_query_log
from repro.serving import (
    BatchEngine,
    BucketSpec,
    DoubleBuffer,
    InflightServer,
    MicroBatchServer,
    SlaBudgeter,
    SlotTable,
    stack_plans,
)

INT32_MAX = 2**31 - 1


def _small_setup(seed: int, n_ranges: int, k: int = 5, n_queries: int = 12):
    corpus = make_corpus(
        n_docs=900, n_terms=700, n_topics=4, mean_doc_len=50, seed=seed
    )
    idx = build_index(corpus, n_ranges=n_ranges, strategy="clustered")
    eng = Engine(idx, k=k)
    log = make_query_log(corpus, n_queries=n_queries, seed=seed + 1)
    return eng, [log.terms[i] for i in range(log.n_queries)]


def _to_device(carry):
    return jax.tree_util.tree_map(jnp.asarray, carry)


def _to_host(carry):
    return jax.tree_util.tree_map(lambda x: np.array(x), carry)


def _assert_result_matches_single(eng, plan, result, **traverse_kw):
    single = eng.traverse(plan, **traverse_kw)
    sids, svals = eng.topk_docs(single.state)
    assert result.doc_ids.tolist() == sids.tolist()
    assert result.scores.tolist() == svals.tolist()
    assert result.exit_safe == bool(single.exit_safe)
    assert result.exit_budget == bool(single.exit_budget)
    assert result.ranges_processed == int(single.ranges_processed)
    assert result.postings == int(np.asarray(single.state.postings))
    assert result.blocks == int(np.asarray(single.state.blocks))


class FixedBudgeter(SlaBudgeter):
    """Deterministic budgets: every admission gets the same postings cap."""

    def __init__(self, cap: int = INT32_MAX):
        super().__init__(sla_ms=float("inf"))
        self.cap = cap
        self.given: list[int] = []

    def budgets(self, n, plans=None):
        self.given.extend([self.cap] * n)
        return np.full(n, self.cap, dtype=np.int32)


# Deterministic clock shared with the observability substrate, so tests and
# instrumentation agree on what a fake second is (DESIGN.md §13).
from repro.obs import FakeClock  # noqa: E402


# ----------------------------------------------------- core resume invariant


@pytest.mark.parametrize("quantum", [1, 2, 3])
def test_quantum_stepped_resume_matches_single_traverse(quantum):
    """N-quanta resume (host round-trip each step) == one device_traverse."""
    eng, queries = _small_setup(seed=0, n_ranges=6, n_queries=8)
    plans = [eng.plan(q) for q in queries]
    R = eng.index.n_ranges
    width = max(p.blk_tab.shape[1] for p in plans)
    bp = stack_plans(plans, width, batch=len(plans))

    rng = np.random.default_rng(3)
    budgets = rng.choice([120, 700, INT32_MAX], size=len(plans)).astype(np.int64)
    maxr = rng.choice([1, 3, INT32_MAX], size=len(plans)).astype(np.int64)

    carry = batched_init_carry(len(plans), eng.k)
    for _ in range(200):
        out = batched_traverse_resume(
            eng.dix, bp.blk_tab, bp.rest_tab, bp.order, bp.ordered_bounds,
            jnp.asarray(np.clip(budgets, 0, INT32_MAX).astype(np.int32)),
            jnp.asarray(np.clip(maxr, 0, INT32_MAX).astype(np.int32)),
            _to_device(carry), s_pad=eng.s_pad, k=eng.k, quantum=quantum,
        )
        carry = _to_host(out)
        if carry_done(carry, R).all():
            break
    assert carry_done(carry, R).all()

    for i, p in enumerate(plans):
        single = eng.traverse(
            p, budget_postings=int(budgets[i]), max_ranges=int(maxr[i])
        )
        assert carry.state.vals[i].tolist() == np.asarray(single.state.vals).tolist()
        assert carry.state.ids[i].tolist() == np.asarray(single.state.ids).tolist()
        assert int(carry.i[i]) == int(single.ranges_processed)
        assert bool(carry.exit_safe[i]) == bool(single.exit_safe)
        assert bool(carry.exit_budget[i]) == bool(single.exit_budget)
        assert int(carry.state.postings[i]) == int(np.asarray(single.state.postings))
        assert int(carry.state.blocks[i]) == int(np.asarray(single.state.blocks))


def test_carry_roundtrip_is_bitwise():
    """host->device->host round-trip preserves every carry leaf exactly."""
    eng, queries = _small_setup(seed=2, n_ranges=4, n_queries=4)
    plans = [eng.plan(q) for q in queries]
    width = max(p.blk_tab.shape[1] for p in plans)
    bp = stack_plans(plans, width, batch=len(plans))
    b = jnp.full(len(plans), INT32_MAX, jnp.int32)

    carry = batched_init_carry(len(plans), eng.k)
    out = batched_traverse_resume(
        eng.dix, bp.blk_tab, bp.rest_tab, bp.order, bp.ordered_bounds,
        b, b, _to_device(carry), s_pad=eng.s_pad, k=eng.k, quantum=2,
    )
    host = _to_host(out)
    back = _to_host(_to_device(host))
    for leaf_a, leaf_b in zip(
        jax.tree_util.tree_leaves(host), jax.tree_util.tree_leaves(back)
    ):
        assert leaf_a.dtype == leaf_b.dtype
        assert np.array_equal(leaf_a, leaf_b)


def test_parked_lanes_do_no_work():
    """A parked (vacant) lane's carry is inert across any number of quanta."""
    eng, queries = _small_setup(seed=4, n_ranges=4, n_queries=4)
    plans = [eng.plan(q) for q in queries]
    width = max(p.blk_tab.shape[1] for p in plans)
    bp = stack_plans(plans[:2], width, batch=4)  # lanes 2,3 are dummies
    b = jnp.full(4, INT32_MAX, jnp.int32)

    carry = batched_init_carry(4, eng.k, parked=True)
    # Un-park only the two real lanes.
    carry.exit_budget[:2] = False
    for _ in range(10):
        carry = _to_host(batched_traverse_resume(
            eng.dix, bp.blk_tab, bp.rest_tab, bp.order, bp.ordered_bounds,
            b, b, _to_device(carry), s_pad=eng.s_pad, k=eng.k, quantum=1,
        ))
    for lane in (2, 3):
        assert int(carry.i[lane]) == 0
        assert int(carry.state.postings[lane]) == 0
        assert np.all(carry.state.ids[lane] == -1)


# --------------------------------------------------------- slot-table staging


def test_slot_table_write_clear_grow():
    eng, queries = _small_setup(seed=6, n_ranges=4, n_queries=3)
    plans = [eng.plan(q) for q in queries]
    R = eng.index.n_ranges
    w = max(p.blk_tab.shape[1] for p in plans)
    tab = SlotTable(3, R, w)
    tab.write_lane(0, plans[0], budget=500)
    assert tab.valid[0] and tab.budget[0] == 500
    assert np.array_equal(tab.order[0], plans[0].order_host)
    tab.clear_lane(0)
    assert not tab.valid[0] and tab.budget[0] == 0
    assert np.all(tab.blk[0] == -1) and np.all(tab.bounds[0] == 0)

    tab.write_lane(1, plans[1], budget=7, max_ranges=2)
    grown = tab.grow_width(2 * w)
    assert grown.width == 2 * w
    assert np.array_equal(grown.blk[1, :, :w], tab.blk[1])
    assert np.all(grown.blk[1, :, w:] == -1)  # new columns are padding
    assert grown.budget[1] == 7 and grown.maxr[1] == 2 and grown.valid[1]

    with pytest.raises(ValueError):
        tab.grow_width(w // 2)
    with pytest.raises(ValueError):
        SlotTable(0, R, w)


def test_double_buffer_swap_carries_live_state():
    eng, queries = _small_setup(seed=6, n_ranges=4, n_queries=2)
    plan = eng.plan(queries[0])
    w = plan.blk_tab.shape[1]
    buf = DoubleBuffer(2, eng.index.n_ranges, w)
    buf.back.write_lane(0, plan, budget=123)
    buf.swap()
    # The admission went live, and the new back mirrors the front.
    assert buf.front.valid[0] and buf.front.budget[0] == 123
    assert buf.back.valid[0] and buf.back.budget[0] == 123
    buf.back.clear_lane(0)
    assert buf.front.valid[0]  # in-flight table untouched by back writes
    buf.swap()
    assert not buf.front.valid[0]


# ------------------------------------------------------------ server parity


def test_inflight_server_bitwise_parity_unbounded():
    eng, queries = _small_setup(seed=8, n_ranges=4, n_queries=12)
    beng = BatchEngine(eng, BucketSpec(max_batch=8))
    srv = InflightServer(beng, SlaBudgeter(sla_ms=float("inf")), n_slots=4)
    served = srv.replay(queries)
    assert sorted(s.rid for s in served) == list(range(len(queries)))
    for s in served:
        _assert_result_matches_single(eng, eng.plan(queries[s.rid]), s.result)


def test_inflight_server_bitwise_parity_budgeted():
    """Admission-time budgets behave exactly like device_traverse budgets."""
    eng, queries = _small_setup(seed=9, n_ranges=6, n_queries=10)
    beng = BatchEngine(eng, BucketSpec(max_batch=8))
    budgeter = FixedBudgeter(cap=100)
    srv = InflightServer(beng, budgeter, n_slots=4, quantum=2)
    served = srv.replay(queries)
    assert len(budgeter.given) == len(queries)
    for s in served:
        _assert_result_matches_single(
            eng, eng.plan(queries[s.rid]), s.result, budget_postings=100
        )
    assert any(s.result.exit_reason == "budget" for s in served)


def test_slot_swap_happens_mid_flight():
    """Queries admit into freed slots while others are still in flight."""
    eng, queries = _small_setup(seed=10, n_ranges=6, n_queries=10)
    beng = BatchEngine(eng, BucketSpec(max_batch=4))
    srv = InflightServer(beng, SlaBudgeter(sla_ms=float("inf")), n_slots=2)
    for q in queries:
        srv.submit(q)
    swapped = False
    served = []
    while srv.pending or srv.active:
        done = srv.step()
        if done and srv.active > 0:
            swapped = True  # a slot retired while its batchmate kept flying
        served.extend(done)
    assert swapped
    assert srv.admissions == len(queries) > srv.n_slots
    # One persistent program: slot swaps never recompile.
    assert len(srv.compiled_shapes) == 1
    for s in served:
        _assert_result_matches_single(eng, eng.plan(queries[s.rid]), s.result)


# --------------------------------------------------------------- saturation


def test_saturation_bitwise_both_servers():
    """Offered load >> capacity: every query's result stays bitwise-exact."""
    eng, queries = _small_setup(seed=12, n_ranges=4, n_queries=24)
    cap = 600

    beng = BatchEngine(eng, BucketSpec(max_batch=4))
    micro = MicroBatchServer(beng, FixedBudgeter(cap=cap), max_batch=4)
    for q in queries:  # burst far beyond one batch
        micro.submit(q)
    served_m = []
    while micro.pending:
        served_m.extend(micro.drain_once())

    infl = InflightServer(
        BatchEngine(eng, BucketSpec(max_batch=4)), FixedBudgeter(cap=cap),
        n_slots=4,
    )
    served_i = infl.replay(queries)

    for served in (served_m, served_i):
        assert sorted(s.rid for s in served) == list(range(len(queries)))
        for s in served:
            _assert_result_matches_single(
                eng, eng.plan(queries[s.rid]), s.result, budget_postings=cap
            )


def test_saturation_queue_bounded_under_tightening():
    """Sustained arrivals: the budgeter tightens and the queue stays bounded."""
    eng, queries = _small_setup(seed=14, n_ranges=4, n_queries=12)
    beng = BatchEngine(eng, BucketSpec(max_batch=8))
    clock = FakeClock(dt=0.010)  # every reading +10ms: e2e latencies blow up
    budgeter = SlaBudgeter(sla_ms=5.0, rate=100.0)
    srv = MicroBatchServer(beng, budgeter, max_batch=8, clock=clock)

    depths = []
    qi = 0
    for _ in range(12):  # arrivals every round, service every round
        for _ in range(4):
            srv.submit(queries[qi % len(queries)])
            qi += 1
        srv.drain_once()
        depths.append(srv.pending)
    # Service rate (8/round) beats arrivals (4/round): depth bounded, and
    # the overload drove Eq. (7) to tighten rather than relax.
    assert max(depths) <= 8
    assert depths[-1] == 0
    assert budgeter.policy.alpha > 1.0

    infl = InflightServer(
        BatchEngine(eng, BucketSpec(max_batch=8)),
        SlaBudgeter(sla_ms=5.0, rate=100.0), n_slots=8,
        clock=FakeClock(dt=0.010),
    )
    depths = []
    qi = 0
    for _ in range(16):
        for _ in range(4):
            infl.submit(queries[qi % len(queries)])
            qi += 1
        infl.step()
        depths.append(infl.pending + infl.active)
    infl.run_until_idle()
    assert max(depths) <= 8 + 4 * 16  # never exceeds total offered
    assert infl.budgeter.policy.alpha > 1.0
    assert infl.pending == 0 and infl.active == 0


def test_latency_attribution_monotone_with_queue_position():
    """Identical queries arriving at one instant, FIFO service: attributed
    latency is non-decreasing with queue position (both servers)."""
    eng, queries = _small_setup(seed=16, n_ranges=4, n_queries=2)
    q = queries[0]

    beng = BatchEngine(eng, BucketSpec(max_batch=4))
    clock = FakeClock(dt=0.0)  # frozen during the arrival burst
    micro = MicroBatchServer(
        beng, SlaBudgeter(sla_ms=float("inf")), max_batch=4, clock=clock
    )
    for _ in range(12):
        micro.submit(q)
    clock.dt = 0.001  # time moves once service starts
    served = []
    while micro.pending:
        served.extend(micro.drain_once())
    lat = [s.latency_ms for s in sorted(served, key=lambda s: s.rid)]
    assert all(b >= a for a, b in zip(lat, lat[1:])), lat
    assert lat[-1] > lat[0]  # deeper queue position paid real queue wait

    clock = FakeClock(dt=0.0)
    infl = InflightServer(
        BatchEngine(eng, BucketSpec(max_batch=4)),
        SlaBudgeter(sla_ms=float("inf")), n_slots=4, clock=clock,
    )
    for _ in range(12):
        infl.submit(q)
    clock.dt = 0.001
    served = infl.run_until_idle()
    lat = [s.latency_ms for s in sorted(served, key=lambda s: s.rid)]
    assert all(b >= a for a, b in zip(lat, lat[1:])), lat
    assert lat[-1] > lat[0]


# ------------------------------------------- queue-aware Reactive feedback


def test_microbatch_overload_feeds_end_to_end_latency_to_policy():
    """Queue wait counts: device-fast batches behind a deep queue must
    still register as SLA misses and tighten budgets (Eq. 7)."""
    eng, queries = _small_setup(seed=18, n_ranges=4, n_queries=12)
    beng = BatchEngine(eng, BucketSpec(max_batch=2))
    clock = FakeClock(dt=0.010)
    # Each dispatch reads the clock twice -> batch_ms == 10 < sla == 50.
    # But a 12-deep queue drained 2 at a time means most queries wait far
    # longer than 50ms end-to-end.
    budgeter = SlaBudgeter(sla_ms=50.0, rate=1e6)
    srv = MicroBatchServer(beng, budgeter, max_batch=2, clock=clock)
    for q in queries:
        srv.submit(q)
    served = []
    while srv.pending:
        served.extend(srv.drain_once())

    assert all(s.latency_ms > 10.0 for s in served[2:])
    assert any(s.latency_ms > 50.0 for s in served)
    # Pre-fix behaviour: policy only ever saw batch_ms=10 (< sla) and alpha
    # would *relax* below 1. Queue-aware feedback must tighten it instead.
    assert budgeter.policy.alpha > 1.0


def test_budgeter_latencies_override_device_time():
    fast_device = SlaBudgeter(sla_ms=50.0)
    fast_device.observe(
        elapsed_ms=10.0, total_postings=1000, n=2, latencies_ms=[120.0, 130.0]
    )
    assert fast_device.policy.alpha > 1.0  # two e2e misses despite fast device

    rate_only = SlaBudgeter(sla_ms=50.0)
    a0 = rate_only.policy.alpha
    rate_only.observe(
        elapsed_ms=10.0, total_postings=1000, n=2, latencies_ms=[]
    )
    assert rate_only.policy.alpha == a0  # empty list: rate EWMA only


@pytest.mark.parametrize("cap", [INT32_MAX, 100])
def test_phases_on_and_off_serve_bitwise_equal(cap):
    """An annotating, enabled, profiled handle changes no result bit."""
    import contextlib

    from repro.obs import NOOP, Instrumentation

    eng, queries = _small_setup(seed=21, n_ranges=6, n_queries=12)
    opened = []

    def annotate(name, **args):
        opened.append(name)
        return contextlib.nullcontext()

    def serve(obs):
        srv = InflightServer(
            BatchEngine(eng, BucketSpec(max_batch=4)),
            FixedBudgeter(cap=cap),
            n_slots=4,
            quantum=2,
            obs=obs,
        )
        return srv, sorted(srv.replay(queries), key=lambda s: s.rid)

    obs = Instrumentation.make(sample_rate=1.0, profile=True, annotate=annotate)
    srv_on, on = serve(obs)
    srv_off, off = serve(NOOP)
    assert opened and srv_on.steps_run == srv_off.steps_run
    assert [s.rid for s in on] == [s.rid for s in off] == list(range(len(queries)))
    for a, b in zip(on, off):
        assert a.quanta == b.quanta
        ra, rb = a.result, b.result
        assert np.asarray(ra.doc_ids).tobytes() == np.asarray(rb.doc_ids).tobytes()
        assert np.asarray(ra.scores).tobytes() == np.asarray(rb.scores).tobytes()
        assert (ra.exit_safe, ra.exit_budget, ra.ranges_processed) == (
            rb.exit_safe, rb.exit_budget, rb.ranges_processed
        )
        assert (ra.postings, ra.blocks) == (rb.postings, rb.blocks)


@pytest.mark.parametrize(
    "impl,docs_format", [("xla", "int32"), ("xla", "packed"), ("pallas", "int32")]
)
def test_traversal_ops_carry_their_layer_scopes(impl, docs_format):
    """The compiled traversal names its gather, accumulate and top-k work."""
    import re

    corpus = make_corpus(n_docs=900, n_terms=700, n_topics=4, mean_doc_len=50, seed=3)
    idx = build_index(corpus, n_ranges=6, strategy="clustered")
    eng = Engine(idx, k=5, impl=impl, docs_format=docs_format)
    plan = eng.plan(make_query_log(corpus, n_queries=2, seed=4).terms[0])
    table = SlotTable(4, idx.n_ranges, plan.blk_tab.shape[1])
    table.write_lane(0, plan)
    text = (
        batched_traverse_resume.lower(
            eng.dix,
            *table.device_arrays(),
            _to_device(batched_init_carry(4, eng.k)),
            s_pad=eng.s_pad,
            k=eng.k,
            quantum=2,
            impl=impl,
            docs_format=docs_format,
        )
        .compile()
        .as_text()
    )
    parts = {p for name in re.findall(r'op_name="([^"]*)"', text) for p in name.split("/")}
    assert {"gather", "accumulate", "topk"} <= parts


@pytest.mark.parametrize("quantum", [1, 2])
def test_gather_fill_is_scored_postings_over_gathered_lanes(quantum):
    """``gather_fill`` observes, per dispatch, 100 x the postings the
    dispatch scored over slots x quantum x width x 128 gathered lanes."""
    from repro.core.clustered_index import BLOCK
    from repro.obs import Instrumentation

    eng, queries = _small_setup(seed=23, n_ranges=6, n_queries=10)
    width = max(eng.plan(q).blk_tab.shape[1] for q in queries)
    obs = Instrumentation.make()
    srv = InflightServer(
        BatchEngine(eng, BucketSpec(max_batch=4, min_width=width)),
        FixedBudgeter(), n_slots=4, quantum=quantum, obs=obs,
    )
    served = srv.replay(queries)
    assert srv.buffers.front.width == width  # no width growth mid-run
    hist = obs.metrics.histogram("gather_fill")
    assert hist.count(server="inflight") == srv.steps_run
    lanes = 4 * quantum * width * BLOCK
    scored = hist.mean(server="inflight") * srv.steps_run * lanes / 100.0
    assert scored == pytest.approx(sum(s.result.postings for s in served))
    assert 0.0 < hist.mean(server="inflight") < 100.0


# ------------------------------------------------------ device-resident carry


class CyclingBudgeter(FixedBudgeter):
    """Admission ``i`` gets ``caps[i % len(caps)]``: budget and safe exits mixed."""

    def __init__(self, caps):
        super().__init__()
        self.caps = list(caps)

    def budgets(self, n, plans=None):
        out = [self.caps[(len(self.given) + j) % len(self.caps)] for j in range(n)]
        self.given.extend(out)
        return np.asarray(out, dtype=np.int32)


def _setup_k(k: int):
    """An engine whose ranges are wide enough for a top-``k`` heap, and 12 queries."""
    if k <= 10:
        return _small_setup(seed=31, n_ranges=6, k=k, n_queries=12)
    corpus = make_corpus(n_docs=8400, n_terms=900, n_topics=4, mean_doc_len=20, seed=31)
    idx = build_index(corpus, n_ranges=8, strategy="clustered")
    eng = Engine(idx, k=k)
    log = make_query_log(corpus, n_queries=12, seed=32)
    return eng, [log.terms[i] for i in range(log.n_queries)]


def _host_lane(k: int, parked: bool) -> dict:
    """The values a host round trip writes into a fresh or parked lane."""
    return {
        "i": np.int32(0), "vals": np.zeros(k, np.int32), "ids": np.full(k, -1, np.int32),
        "postings": np.int32(0), "blocks": np.int32(0),
        "exit_safe": False, "exit_budget": parked,
    }


def _named(carry) -> dict:
    return {
        "i": carry.i, "vals": carry.state.vals, "ids": carry.state.ids,
        "postings": carry.state.postings, "blocks": carry.state.blocks,
        "exit_safe": carry.exit_safe, "exit_budget": carry.exit_budget,
    }


def _spy_dispatches(monkeypatch, srv):
    """Record, per traversal dispatch, its carry argument, the slots' rids
    and the host mirror then, and its output (host copies and the objects)."""
    from repro.serving import inflight

    real = inflight.batched_traverse_resume
    calls = []

    def spy(*args, **kw):
        carry = args[7]
        out = real(*args, **kw)
        calls.append(dict(
            arg=carry, out=out, rid=srv.slot_rid.copy(),
            host_in=_to_host(carry), mirror=_to_host(srv.carry), host_out=_to_host(out),
        ))
        return out

    monkeypatch.setattr(inflight, "batched_traverse_resume", spy)
    return calls


def _round_trip_inputs(calls, n_slots: int, k: int):
    """Per dispatch, the carry the host round trip would upload: the previous
    output fetched, lanes holding a new query reset fresh, vacant lanes parked;
    and each dispatch's (fresh, parked) lane counts."""
    prev = _named(batched_init_carry(n_slots, k, parked=True))
    prev_rid = np.full(n_slots, -1)
    expected, changes = [], []
    for c in calls:
        exp = {name: np.array(v) for name, v in prev.items()}
        fresh = parked = 0
        for lane in range(n_slots):
            rid = c["rid"][lane]
            if rid >= 0 and rid == prev_rid[lane]:
                continue
            if rid >= 0:
                fresh += 1
            elif prev_rid[lane] >= 0:
                parked += 1
            for name, v in _host_lane(k, parked=rid < 0).items():
                exp[name][lane] = v
        expected.append(exp)
        changes.append((fresh, parked))
        prev, prev_rid = _named(c["host_out"]), c["rid"]
    return expected, changes


@pytest.mark.parametrize("k", [10, 1000])
@pytest.mark.parametrize("quantum", [1, 2])
def test_every_dispatch_gets_the_round_trip_carry_bitwise(monkeypatch, quantum, k):
    """The device-resident carry a dispatch receives equals, leaf for leaf and
    bit for bit, what fetching the last output and resetting the admitted and
    parked lanes on the host would have uploaded; unchanged steps pass the
    last output itself, with no upload."""
    eng, queries = _setup_k(k)
    caps = [INT32_MAX, 90, INT32_MAX, 400]
    srv = InflightServer(
        BatchEngine(eng, BucketSpec(max_batch=4)), CyclingBudgeter(caps),
        n_slots=3, quantum=quantum,
    )
    calls = _spy_dispatches(monkeypatch, srv)
    served = srv.replay(queries)

    expected, changes = _round_trip_inputs(calls, srv.n_slots, eng.k)
    assert len(calls) == srv.steps_run
    for n, (c, exp, ch) in enumerate(zip(calls, expected, changes)):
        got, mirror = _named(c["host_in"]), _named(c["mirror"])
        for name, want in exp.items():
            assert got[name].dtype == want.dtype == mirror[name].dtype, name
            assert got[name].tobytes() == want.tobytes(), (n, name)
            assert mirror[name].tobytes() == want.tobytes(), (n, name)
        if n and ch == (0, 0):
            assert c["arg"] is calls[n - 1]["out"]  # resident: not uploaded
        elif n:
            assert c["arg"] is not calls[n - 1]["out"]  # the reset's output

    # The replay swapped slots mid-flight, ended queries on their budget
    # and not, and had steps with no lane change.
    assert any(f and c["rid"][c["rid"] >= 0].size > f for c, (f, _) in zip(calls, changes))
    reasons = {s.result.exit_reason for s in served}
    assert "budget" in reasons and reasons - {"budget"}
    assert any(ch == (0, 0) for ch in changes[1:])
    for s in served:
        _assert_result_matches_single(
            eng, eng.plan(queries[s.rid]), s.result,
            budget_postings=caps[s.rid % len(caps)],
        )


@pytest.mark.parametrize("k", [10, 1000])
def test_pack_carry_round_trips_through_host(k):
    """``_pack_carry`` then the host unpack returns every leaf bit for bit,
    both exit flags set included, from one ``[n, 2k+5]`` int32 array."""
    from repro.serving.inflight import _carry_to_host, _pack_carry

    n = 5
    rng = np.random.default_rng(k)
    like = batched_init_carry(n, k)
    host = jax.tree_util.tree_map(
        lambda x: rng.integers(0, 2, x.shape).astype(bool) if x.dtype == bool
        else rng.integers(-(2**31), 2**31 - 1, x.shape, dtype=np.int64).astype(np.int32),
        like,
    )
    host.exit_safe[:2] = True
    host.exit_budget[:2] = True
    packed = _pack_carry(_to_device(host))
    assert packed.shape == (n, 2 * k + 5) and packed.dtype == jnp.int32
    back = _carry_to_host(packed, like)
    assert jax.tree_util.tree_structure(back) == jax.tree_util.tree_structure(host)
    for a, b in zip(jax.tree_util.tree_leaves(host), jax.tree_util.tree_leaves(back)):
        assert a.dtype == b.dtype and a.shape == b.shape
        assert a.tobytes() == b.tobytes()
    assert back.exit_safe[:2].all() and back.exit_budget[:2].all()


@pytest.mark.parametrize("quantum", [1, 2])
def test_carry_uploaded_once_and_fetched_once_a_step(monkeypatch, quantum):
    """One whole-carry upload a server's life; each step packs its carry
    once and brings it to the host in one array, inside ``_carry_to_host``."""
    from repro.obs import Instrumentation
    from repro.serving import inflight

    counts = {"up": 0, "pack": 0}
    fetched = []
    real_up, real_pack, real_fetch = (
        inflight._carry_to_device, inflight._pack_carry, inflight._carry_to_host
    )

    def up(carry):
        counts["up"] += 1
        return real_up(carry)

    def pack(carry):
        counts["pack"] += 1
        return real_pack(carry)

    def fetch(packed, like):
        fetched.append(packed)
        return real_fetch(packed, like)

    monkeypatch.setattr(inflight, "_carry_to_device", up)
    monkeypatch.setattr(inflight, "_pack_carry", pack)
    monkeypatch.setattr(inflight, "_carry_to_host", fetch)

    eng, queries = _small_setup(seed=33, n_ranges=6, n_queries=10)
    obs = Instrumentation.make()
    srv = InflightServer(
        BatchEngine(eng, BucketSpec(max_batch=4)), FixedBudgeter(),
        n_slots=4, quantum=quantum, obs=obs,
    )
    srv.replay(queries)
    assert srv.steps_run > 1
    assert counts["up"] == 1
    assert obs.metrics.counter("carry_uploads").value(server="inflight") == 1
    assert counts["pack"] == len(fetched) == srv.steps_run
    for packed in fetched:
        assert isinstance(packed, jax.Array)
        assert packed.shape == (srv.n_slots, 2 * eng.k + 5)
    for leaf in jax.tree_util.tree_leaves(srv.carry):
        assert type(leaf) is np.ndarray  # the mirror holds no device buffer


@pytest.mark.parametrize("quantum", [1, 2])
def test_carry_reset_counters_follow_lane_changes(monkeypatch, quantum):
    """``carry_resets`` counts the dispatches with an admitted or parked lane,
    and ``carry_lanes_reset`` the lanes of each kind those resets wrote."""
    from repro.obs import Instrumentation

    eng, queries = _small_setup(seed=34, n_ranges=6, n_queries=12)
    obs = Instrumentation.make()
    srv = InflightServer(
        BatchEngine(eng, BucketSpec(max_batch=4)), CyclingBudgeter([INT32_MAX, 80]),
        n_slots=3, quantum=quantum, obs=obs,
    )
    calls = _spy_dispatches(monkeypatch, srv)
    srv.replay(queries)

    _, changes = _round_trip_inputs(calls, srv.n_slots, eng.k)
    changed = [ch for ch in changes if ch != (0, 0)]
    assert 0 < len(changed) < srv.steps_run
    resets = obs.metrics.counter("carry_resets").value(server="inflight")
    lanes = obs.metrics.counter("carry_lanes_reset")
    assert resets == len(changed)
    assert lanes.value(server="inflight", kind="fresh") == sum(f for f, _ in changes)
    assert lanes.value(server="inflight", kind="fresh") == srv.admissions == len(queries)
    assert lanes.value(server="inflight", kind="parked") == sum(p for _, p in changes) > 0
