"""End-to-end anytime serving driver (the paper's operating mode).

Two engines over the same cluster-skipping index:

  * ``--mode host`` — the paper's host-driven loop: one device step per
    range, wall-clock polled between ranges, Reactive (§6.4) alpha feedback
    per query;
  * ``--mode batch`` — the production path: a micro-batching request loop
    over the vmapped ``BatchEngine``. The SLA cannot be polled mid-dispatch,
    so ``SlaBudgeter`` compiles it into per-query postings budgets (EWMA
    throughput x Reactive alpha, see repro/serving/README.md);
  * ``--mode sharded`` — the batch loop over a range-sharded index
    (``--shards`` devices, DESIGN.md §4): one (batch x shard) dispatch per
    micro-batch, ``ShardedSlaBudgeter`` splitting the SLA into per-shard
    postings budgets. Falls back to the single-device vmap path when the
    runtime exposes fewer devices than shards (set
    XLA_FLAGS=--xla_force_host_platform_device_count=N for a CPU mesh);
  * ``--mode control`` — the full control plane (DESIGN.md §9): the same
    sharded serving under a ``ControlPlane`` with ``--replicas`` replica
    groups, BoundSum-aware budget allocation, a mid-stream shard outage
    (served degraded through the fidelity bound, then recovered), and a
    live reshard cutover with serving uninterrupted;
  * ``--mode inflight`` — the slot-swapping continuous loop (DESIGN.md
    §11): ``--batch-size`` slots stepped ``--quantum`` ranges per
    dispatch, exited queries replaced mid-flight from the queue. Same
    queue-wait-inclusive latency attribution as the batch mode, so the
    two modes' P99s compare directly.

All report percentile latencies, queries/sec, SLA compliance, and
effectiveness (RBO vs exhaustive). ``--trace out.jsonl`` records a
per-query trace (any mode, sample rate 1.0, DESIGN.md §13) for
``python -m repro.obs report out.jsonl`` / ``... slo out.jsonl``.
``--metrics snap.json`` exports the metrics registry (plus SLO/alert
state and the dispatch profiler, DESIGN.md §14) for
``python -m repro.obs watch snap.json``; in control mode the snapshot
refreshes every drain, so a concurrent ``watch`` follows the run live.

    PYTHONPATH=src python examples/serve_anytime.py
        [--mode host|batch|sharded|control|inflight] [--sla-ms 15]
        [--queries 300] [--batch-size 16] [--quantum 1] [--shards 2]
        [--replicas 1] [--trace out.jsonl] [--metrics snap.json]
"""

import argparse
import time

import numpy as np

from repro.compile_cache import use_compile_cache
from repro.core import Engine, arrange, build_index
from repro.core.anytime import Reactive, run_query_anytime
from repro.core.metrics import rbo
from repro.core.oracle import exhaustive_topk
from repro.data.synth import make_corpus, make_query_log
from repro.obs import NOOP, Instrumentation, write_snapshot
from repro.obs.detect import DriftMonitor, default_serving_detectors
from repro.obs.slo import SloTracker, default_serving_slos
from repro.serving import (
    BatchEngine,
    BucketSpec,
    InflightServer,
    MicroBatchServer,
    ShardedBatchEngine,
    ShardedEngine,
    ShardedSlaBudgeter,
    SlaBudgeter,
)


def build(args):
    corpus = make_corpus(n_docs=10_000, n_terms=8000, n_topics=16,
                         mean_doc_len=150, seed=0)
    log = make_query_log(corpus, n_queries=args.queries, seed=2)
    arr = arrange(corpus, n_ranges=16, strategy="clustered_bp", bp_rounds=4)
    index = build_index(corpus, arrangement=arr)
    return corpus, log, index, Engine(index, k=args.k)


def calibrate(engine, index, log, args):
    """Warmup + derive the SLA from this machine's exhaustive distribution."""
    base, rates, oracle = [], [], {}
    for i in range(min(64, log.n_queries)):
        plan = engine.plan(log.terms[i])
        res = run_query_anytime(engine, plan, policy=None)
        base.append(res.elapsed_ms)
        if res.elapsed_ms > 0:
            rates.append(res.postings / res.elapsed_ms)
        oracle[i] = exhaustive_topk(index, log.terms[i], args.k)[0].tolist()
    exh_p99 = float(np.percentile(base, 99))
    return exh_p99, oracle, float(np.median(rates))


def report(times, quality, sla, wall, n, extra=""):
    t = np.asarray(times)
    print(f"\nServed {n} queries in {wall:.1f}s ({n/wall:.1f} q/s){extra}")
    print(f"  P50 {np.percentile(t,50):6.2f} ms   P95 {np.percentile(t,95):6.2f} "
          f"ms   P99 {np.percentile(t,99):6.2f} ms")
    miss = (t > sla).mean() * 100
    print(f"  SLA misses: {miss:.2f}% (target <= 1%)")
    print(f"  mean RBO(0.8) vs exhaustive: {np.mean(quality):.4f}")
    print("  P99 SLA", "MET" if np.percentile(t, 99) <= sla else "MISSED")


def serve_host(engine, log, sla_arg, oracle, exh_p99, obs=NOOP):
    # Default SLA: 25% of this machine's host-driven exhaustive P99.
    sla = sla_arg or exh_p99 * 0.25
    print(f"SLA: P99 <= {sla:.2f} ms (exhaustive P99 was {exh_p99:.2f} ms)")
    policy = Reactive(alpha=1.0, beta=1.2, q=0.01)
    times, quality = [], []
    t0 = time.perf_counter()
    for i in range(log.n_queries):
        plan = engine.plan(log.terms[i])
        t_q = obs.clock() if obs.enabled else 0.0
        res = run_query_anytime(engine, plan, policy=policy, budget_ms=sla)
        times.append(res.elapsed_ms)
        if obs.enabled:
            # The host loop has no server in front of it, so it emits the
            # one-span trace itself (queue wait is zero by construction).
            obs.trace_begin(i)
            obs.trace_span(i, "service", t_q, obs.clock())
            obs.trace_attr(i, server="host", latency_ms=res.elapsed_ms,
                           exit_reason=res.exit_reason, sla_ms=sla)
            obs.trace_end(i)
        if i in oracle:
            quality.append(rbo(res.doc_ids.tolist(), oracle[i], phi=0.8))
    wall = time.perf_counter() - t0
    report(times, quality, sla, wall, log.n_queries,
           extra=f"   final alpha = {policy.alpha:.2f}")


def serve_batch(engine, log, sla_arg, oracle, batch_size, rate0, exh_p99,
                n_shards=None, obs=NOOP):
    spec = BucketSpec(max_batch=batch_size)
    if n_shards:
        seng = ShardedEngine(engine, n_shards, obs=obs)
        beng = ShardedBatchEngine(seng, spec)
        path = "shard_map mesh" if seng.mesh is not None else "vmap (1 device)"
        print(f"sharded: {seng.n_shards} range shards, {path}, "
              f"mass={seng.mass.tolist()}")
        mk_budgeter = lambda **kw: ShardedSlaBudgeter(n_shards=seng.n_shards, **kw)
    else:
        beng = BatchEngine(engine, spec)
        mk_budgeter = SlaBudgeter
    # Pre-compile every (batch_bucket, width) program the whole log can
    # produce before any timing (planning is host-side and cheap).
    widths = {beng.spec.width_bucket(engine.plan(log.terms[i]).blk_tab.shape[1])
              for i in range(log.n_queries)}
    beng.warmup(sorted(widths))

    # Default SLA: half of the *batched* unbudgeted P99 — a micro-batch
    # serializes its lanes on this 1-core container, so the host-loop
    # distribution understates what one dispatch costs.
    probe_n = min(4 * batch_size, log.n_queries)
    probe = MicroBatchServer(
        beng, mk_budgeter(sla_ms=float("inf"), rate=rate0), max_batch=batch_size
    )
    lat = [s.latency_ms for s in
           probe.replay([log.terms[i] for i in range(probe_n)],
                        batch_size=batch_size)]
    sla = sla_arg or float(np.percentile(lat, 99)) * 0.5
    print(f"SLA: P99 <= {sla:.2f} ms (unbudgeted batch P99 was "
          f"{np.percentile(lat, 99):.2f} ms; host exhaustive P99 "
          f"{exh_p99:.2f} ms)")

    budgeter = mk_budgeter(
        sla_ms=sla, policy=Reactive(alpha=1.0, beta=1.5, q=0.01), rate=rate0,
        obs=obs,
    )
    server = MicroBatchServer(beng, budgeter, max_batch=batch_size, obs=obs)
    # Let the budgeter see one real batch before timing; remember the rid
    # watermark so the timed replay's rids map back to query-log positions.
    server.replay([log.terms[i] for i in range(min(batch_size, log.n_queries))])
    rid0 = server._next_rid

    times, quality = [], []
    t0 = time.perf_counter()
    served = server.replay(
        [log.terms[i] for i in range(log.n_queries)], batch_size=batch_size
    )
    wall = time.perf_counter() - t0
    for s in served:
        times.append(s.latency_ms)
        qi = s.rid - rid0
        if qi in oracle:
            ids = s.result.doc_ids[np.lexsort((s.result.doc_ids, -s.result.scores))]
            quality.append(rbo(ids.tolist(), oracle[qi], phi=0.8))
    report(times, quality, sla, wall, log.n_queries,
           extra=(f"   batch={batch_size}, programs="
                  f"{sorted(beng.compiled_shapes)}, "
                  f"final alpha = {budgeter.policy.alpha:.2f}"))


def serve_inflight(engine, log, sla_arg, oracle, args, rate0, exh_p99,
                   obs=NOOP):
    """Slot-swapping continuous loop at saturating offered load."""
    spec = BucketSpec(max_batch=args.batch_size)
    beng = BatchEngine(engine, spec)
    queries = [log.terms[i] for i in range(log.n_queries)]

    # Warm the (n_slots, width) programs the log can produce.
    warm = InflightServer(
        beng, SlaBudgeter(sla_ms=float("inf"), rate=rate0),
        n_slots=args.batch_size, quantum=args.quantum,
    )
    lat = [s.latency_ms for s in
           warm.replay(queries[: min(4 * args.batch_size, log.n_queries)])]
    sla = sla_arg or float(np.percentile(lat, 99)) * 0.5
    print(f"SLA: P99 <= {sla:.2f} ms (unbudgeted in-flight P99 was "
          f"{np.percentile(lat, 99):.2f} ms; host exhaustive P99 "
          f"{exh_p99:.2f} ms)")

    budgeter = SlaBudgeter(
        sla_ms=sla, policy=Reactive(alpha=1.0, beta=1.5, q=0.01), rate=rate0,
        obs=obs,
    )
    server = InflightServer(
        beng, budgeter, n_slots=args.batch_size, quantum=args.quantum, obs=obs
    )
    times, quality = [], []
    t0 = time.perf_counter()
    served = server.replay(queries)
    wall = time.perf_counter() - t0
    for s in served:
        times.append(s.latency_ms)
        if s.rid in oracle:
            ids = s.result.doc_ids[np.lexsort((s.result.doc_ids, -s.result.scores))]
            quality.append(rbo(ids.tolist(), oracle[s.rid], phi=0.8))
    report(times, quality, sla, wall, log.n_queries,
           extra=(f"   slots={args.batch_size}, quantum={args.quantum}, "
                  f"steps={server.steps_run}, programs="
                  f"{sorted(server.compiled_shapes)}, "
                  f"final alpha = {budgeter.policy.alpha:.2f}"))


def serve_control(engine, log, sla_arg, oracle, args, obs=NOOP,
                  metrics_path=None):
    """Control-plane demo: outage + recovery + live reshard, one stream."""
    from repro.control import ControlPlane

    plane = ControlPlane(
        engine, n_shards=args.shards, n_replicas=args.replicas,
        sla_ms=sla_arg or float("inf"),
        spec=BucketSpec(max_batch=args.batch_size),
        obs=obs,
    )
    tracker = monitor = None
    alerts_seen = []
    if obs.enabled:
        # Operations loop (DESIGN.md §14): SLO burn-rate accounting plus
        # drift/skew detectors polled every drain, alerts feeding back into
        # the plane (skew arms maybe_reshard, burn marks degraded-SLO).
        tracker = SloTracker(obs, default_serving_slos(
            sla_ms=sla_arg, fidelity_ceiling=None))
        monitor = DriftMonitor(obs)
        default_serving_detectors(monitor, n_shards=args.shards,
                                  server="control")
        monitor.subscribe(lambda ev: alerts_seen.append(ev.to_dict()))
        plane.enable_operations(slos=tracker, monitor=monitor)
    st = plane.stats()
    print(f"control plane: {args.shards} shards x {args.replicas} replicas, "
          f"cuts={st['cuts']}, replica_mesh={st['replica_mesh']}, "
          f"budget mode={plane.budgeter.mode}")
    queries = [log.terms[i] for i in range(log.n_queries)]
    third = max(args.batch_size, log.n_queries // 3)
    # Pre-compile every (batch_bucket, width) program before any timing,
    # same discipline as serve_batch — percentiles measure serving, not XLA.
    widths = {plane.bengine.spec.width_bucket(
        engine.plan(log.terms[i]).blk_tab.shape[1])
        for i in range(log.n_queries)}
    plane.bengine.warmup(sorted(widths))

    times, quality, degraded = [], [], 0

    def consume(served):
        nonlocal degraded
        for s in served:
            times.append(s.latency_ms)
            r = s.result
            if "down" in r.shard_exit_reasons and not r.exact:
                degraded += 1
            qi = s.rid
            if qi in oracle:
                ids = r.doc_ids[np.lexsort((r.doc_ids, -r.scores))]
                quality.append(rbo(ids.tolist(), oracle[qi], phi=0.8))

    t0 = time.perf_counter()
    # Phase 1: healthy serving.
    consume(plane.replay(queries[:third], batch_size=args.batch_size))
    # Phase 2: shard 0 dies mid-stream; every query still answers.
    plane.mark_down(0)
    consume(plane.replay(queries[third : 2 * third],
                         batch_size=args.batch_size))
    print(f"  outage window: shard 0 down, {degraded} queries served "
          f"degraded (exact=False, bounded fidelity loss)")
    plane.mark_up(0)
    # Phase 3: live reshard while the rest of the log streams through.
    task = plane.start_reshard(plane.planner.propose()) \
        if plane.planner.should_reshard() else None
    if task is None and args.shards > 1:
        # Demo fallback: nudge the first boundary by one range (a single
        # shard has no interior boundary to move — nothing to demo).
        cuts = plane.cuts.copy()
        cuts[1] = cuts[1] - 1 if cuts[1] > 1 else cuts[1] + 1
        if cuts[1] < cuts[2] and not np.array_equal(cuts, plane.cuts):
            task = plane.start_reshard(cuts)
    def refresh_metrics():
        if metrics_path and obs.enabled:
            write_snapshot(
                metrics_path, obs.metrics,
                slo=tracker.evaluate() if tracker is not None else None,
                alerts=alerts_seen[-32:],
                profiler=(obs.profiler.snapshot()
                          if obs.profiler is not None else None),
                t=obs.clock(),
            )

    qi = 2 * third
    while qi < len(queries) or plane.reshard_task is not None:
        for q in queries[qi : qi + args.batch_size]:
            plane.submit(q)
        qi += args.batch_size
        consume(plane.drain_once())
        refresh_metrics()
    while plane.pending:
        consume(plane.drain_once())
        refresh_metrics()
    wall = time.perf_counter() - t0
    if task is not None:
        print(f"  live reshard -> cuts={plane.cuts.tolist()} in "
              f"{task.steps_done} steps; "
              f"{plane.queries_served_during_reshard} queries served "
              f"mid-cutover (serving never paused)")
    sla = sla_arg or float("inf")
    report(times, quality, sla, wall, len(times),
           extra=f"   degraded={degraded}, "
                 f"reshards={plane.reshards_completed}")
    if plane.stats().get("degraded_slo"):
        print("  SLO state: degraded (burn-rate alert firing)")
    return {"slo": tracker.evaluate() if tracker is not None else None,
            "alerts": alerts_seen}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--mode",
                    choices=("host", "batch", "sharded", "control", "inflight"),
                    default="batch")
    ap.add_argument("--quantum", type=int, default=1,
                    help="ranges per dispatch per slot for --mode inflight")
    ap.add_argument("--shards", type=int, default=2,
                    help="range shards for --mode sharded/control")
    ap.add_argument("--replicas", type=int, default=1,
                    help="replica groups for --mode control")
    ap.add_argument("--sla-ms", type=float, default=None,
                    help="P99 budget; default: host mode = 25%% of the "
                         "host-driven exhaustive P99, batch mode = 50%% of "
                         "the unbudgeted batched P99")
    ap.add_argument("--queries", type=int, default=300)
    ap.add_argument("--k", type=int, default=10)
    ap.add_argument("--batch-size", type=int, default=16)
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="record a per-query JSONL trace (sample rate 1.0) "
                         "for `python -m repro.obs report PATH`")
    ap.add_argument("--metrics", default=None, metavar="PATH",
                    help="write a registry+SLO+profiler snapshot (JSON) "
                         "for `python -m repro.obs watch PATH`")
    args = ap.parse_args()

    use_compile_cache()
    obs = (Instrumentation.make(sample_rate=1.0, trace_path=args.trace,
                                profile=bool(args.metrics))
           if args.trace or args.metrics else NOOP)
    _, log, index, engine = build(args)
    exh_p99, oracle, rate0 = calibrate(engine, index, log, args)
    extras = {}
    if args.mode == "host":
        serve_host(engine, log, args.sla_ms, oracle, exh_p99, obs=obs)
    elif args.mode == "control":
        extras = serve_control(engine, log, args.sla_ms, oracle, args,
                               obs=obs, metrics_path=args.metrics) or {}
    elif args.mode == "inflight":
        serve_inflight(engine, log, args.sla_ms, oracle, args, rate0, exh_p99,
                       obs=obs)
    else:
        serve_batch(engine, log, args.sla_ms, oracle, args.batch_size,
                    rate0, exh_p99,
                    n_shards=args.shards if args.mode == "sharded" else None,
                    obs=obs)
    if obs.enabled:
        if args.metrics:
            write_snapshot(
                args.metrics, obs.metrics,
                slo=extras.get("slo"), alerts=extras.get("alerts"),
                profiler=(obs.profiler.snapshot()
                          if obs.profiler is not None else None),
                t=obs.clock(),
            )
            print(f"\nmetrics snapshot -> {args.metrics}  "
                  f"(view: python -m repro.obs watch {args.metrics} --once)")
        obs.close()
        if args.trace:
            print(f"\ntrace: {obs.tracer.finished} records -> {args.trace}  "
                  f"(summarize: python -m repro.obs report {args.trace})")


if __name__ == "__main__":
    main()
