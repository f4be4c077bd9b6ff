"""Smoke run of the anytime serving path on a TPU, through its user entry points.

    python chip_smoke.py              # one chip: index, in-flight serving, Pallas
    python chip_smoke.py --chips 4    # four chips: mesh shards and replica groups

One process, no children. It builds an index from a seed with the repo's
own index build, serves a query log through ``BatchEngine`` + ``InflightServer``
(the path of ``examples/serve_anytime.py --mode inflight``) and checks every
answer bitwise against the exhaustive oracle (``core.oracle``), tie order
included. With ``--chips 4`` it runs only the multi-chip phase:
``ShardedEngine`` over a 4-device mesh and a 2x2 ``ControlPlane``, each
compared bitwise with its one-device or one-replica twin and the oracle.

Any failed check exits non-zero. Without a TPU it exits non-zero before any
work. Lines before the last are information for a reader, not a
benchmark; the last line is one JSON object naming the device.
"""

from __future__ import annotations

import argparse
import collections
import json
import os
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

K = 10
N_RANGES = 64
N_QUERIES = 64
N_SLOTS = 16
QUANTUM = 2
SEED = 0


class SmokeFailure(Exception):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)
    print(f"  ok: {what}")


def check_device(n_chips: int):
    """Print the runtime's devices; refuse anything but n_chips TPUs."""
    import jax

    print(f"jax {jax.__version__}")
    devices = jax.devices()
    for d in devices:
        print(f"  device {d.id}: platform={d.platform} kind={d.device_kind}")
    print(f"  count={len(devices)}")
    if devices[0].platform != "tpu":
        hint = os.environ.get("JAX_PLATFORMS")
        note = f" (JAX_PLATFORMS={hint!r} is set)" if hint else ""
        raise SmokeFailure(f"no TPU: JAX found {devices[0].platform}{note}")
    if len(devices) < n_chips:
        raise SmokeFailure(f"need {n_chips} TPU devices, found {len(devices)}")
    return devices


class CompileClock:
    """Sums XLA backend-compile seconds and counts persistent-cache hits.

    A program read back from the persistent cache records its read time
    under the same compile event, so a warm rerun shows fewer seconds.
    """

    def __init__(self):
        import jax

        self.seconds = 0.0
        self.cache_hits = 0

        def on_duration(name, secs, **_):
            if name == "/jax/core/compile/backend_compile_duration":
                self.seconds += secs

        def on_event(name, **_):
            if name == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)


def build(n_docs: int):
    """Corpus -> clustered arrangement -> index, all from the seed."""
    from repro.core import arrange, build_index
    from repro.data.synth import make_corpus, make_query_log

    t0 = time.perf_counter()
    corpus = make_corpus(
        n_docs=n_docs, n_terms=50_000, mean_doc_len=150, seed=SEED
    )
    t1 = time.perf_counter()
    arr = arrange(corpus, n_ranges=N_RANGES, strategy="clustered", seed=SEED)
    t2 = time.perf_counter()
    index = build_index(corpus, arrangement=arr)
    t3 = time.perf_counter()
    log = make_query_log(corpus, n_queries=N_QUERIES, seed=SEED + 1)
    print(
        f"index: {corpus.n_docs} docs, {corpus.n_terms} terms, "
        f"{index.n_ranges} ranges, {index.nnz} postings, {index.n_blocks} blocks "
        f"(host build: corpus {t1 - t0:.1f}s, k-means {t2 - t1:.1f}s, "
        f"build_index {t3 - t2:.1f}s)"
    )
    print(
        "  note: a chip-sized shard of the configs/anytime_ir.py deployment "
        "holds 256M postings; building it inside a run waits for a faster "
        "index build (ROADMAP Reach 1)"
    )
    queries = [log.terms[i] for i in range(log.n_queries)]
    return index, queries


def oracle_topk(index, queries):
    from repro.core.oracle import exhaustive_topk

    return [exhaustive_topk(index, q, K) for q in queries]


def result_key(r) -> tuple:
    """Every field of a served result, as plain Python values."""
    return tuple(
        v.tolist() if isinstance(v, np.ndarray) else v for v in r
    )


def serve_inflight(engine, queries, budgeter):
    """One replay through BatchEngine + InflightServer; results by rid."""
    from repro.serving import BatchEngine, BucketSpec, InflightServer

    server = InflightServer(
        BatchEngine(engine, BucketSpec(max_batch=N_SLOTS)),
        budgeter,
        n_slots=N_SLOTS,
        quantum=QUANTUM,
    )
    t0 = time.perf_counter()
    served = server.replay(queries)
    wall = time.perf_counter() - t0
    check(len(served) == len(queries), f"{len(served)}/{len(queries)} queries answered")
    by_rid = {s.rid: s for s in served}
    return [by_rid[i] for i in range(len(queries))], wall


def phase_one_chip(index, queries, clock, device):
    from repro.core import Engine
    from repro.core.anytime import Reactive
    from repro.kernels.range_scorer.kernel import pallas_interpret
    from repro.serving import SlaBudgeter

    oracle = oracle_topk(index, queries)
    unlimited = lambda: SlaBudgeter(sla_ms=float("inf"))  # noqa: E731

    print("serve: XLA scorer, unlimited budget, in-flight slots")
    t0, c0 = time.perf_counter(), clock.seconds
    xla = Engine(index, k=K, impl="xla", impact_dtype="int8")
    print(f"  device bytes: {index.device_bytes(impact_dtype='int8')['total']}")
    first, _ = serve_inflight(xla, queries, unlimited())
    print(
        f"  first replay {time.perf_counter() - t0:.1f}s, "
        f"of which XLA compile {clock.seconds - c0:.1f}s"
    )
    hits = sum(
        s.result.doc_ids.tolist() == oid.tolist()
        and s.result.scores.tolist() == osc.tolist()
        for s, (oid, osc) in zip(first, oracle)
    )
    check(hits == len(queries), f"{hits}/{len(queries)} top-{K} bitwise equal to the oracle")
    served, wall = serve_inflight(xla, queries, unlimited())
    check(
        [result_key(s.result) for s in served] == [result_key(s.result) for s in first],
        "second replay identical to the first",
    )
    lat = np.asarray([s.latency_ms for s in served])
    postings = sum(s.result.postings for s in served)
    print(
        f"  smoke info, not a benchmark: {len(queries) / wall:.1f} q/s, "
        f"p50 {np.percentile(lat, 50):.2f} ms, p99 {np.percentile(lat, 99):.2f} ms "
        f"(all {len(queries)} queries submitted at once)"
    )

    print("serve: XLA scorer, finite SLA (SlaBudgeter)")
    sla = 0.25 * float(np.percentile(lat, 50))
    rate0 = postings / (wall * 1e3 * N_SLOTS)
    budgeter = SlaBudgeter(
        sla_ms=sla, policy=Reactive(alpha=1.0, beta=1.5, q=0.01), rate=rate0
    )
    budgeted, _ = serve_inflight(xla, queries, budgeter)
    reasons = collections.Counter(s.result.exit_reason for s in budgeted)
    print(f"  sla {sla:.3f} ms, exit reasons {dict(sorted(reasons.items()))}")
    bounds, outside = [], []
    for s, q, (oid, osc) in zip(budgeted, queries, oracle):
        r = s.result
        rest = xla.plan(q).bounds_host[r.ranges_processed:]
        fb = int(rest.max()) if r.exit_budget and rest.size else 0
        bounds.append(fb)
        # The anytime guarantee: an exact top-k doc the answer lacks scores
        # at most the larger of the answer's k-th score and the bound.
        kth = int(r.scores[-1]) if len(r.scores) == K else 0
        got = set(r.doc_ids.tolist())
        if any(sc > max(kth, fb) for d, sc in zip(oid.tolist(), osc.tolist()) if d not in got):
            outside.append(s.rid)
    fbs = np.asarray(bounds)
    print(
        f"  fidelity bounds: {int((fbs > 0).sum())} queries inexact, "
        f"largest bound {int(fbs.max())}"
    )
    check(not outside, f"every missing doc scores within its fidelity bound {outside}")

    mode = "interpreted" if pallas_interpret() else "compiled"
    for docs_format in ("int32", "packed"):
        print(f"serve: {mode} Pallas scorer, docs_format={docs_format}")
        t0, c0 = time.perf_counter(), clock.seconds
        pallas = Engine(
            index, k=K, impl="pallas", impact_dtype="int8", docs_format=docs_format
        )
        got, _ = serve_inflight(pallas, queries, unlimited())
        same = sum(
            result_key(a.result) == result_key(b.result) for a, b in zip(got, first)
        )
        print(
            f"  replay {time.perf_counter() - t0:.1f}s, "
            f"of which XLA compile {clock.seconds - c0:.1f}s"
        )
        check(same == len(queries), f"{same}/{len(queries)} results bitwise equal to the XLA run")

    stats = device.memory_stats() or {}
    print(f"  peak_bytes_in_use {stats.get('peak_bytes_in_use', 'not reported')}")


def check_placement(dix, n_devices: int, n_shards: int, what: str) -> None:
    """Every device holds exactly one shard of every index leaf."""
    for name, leaf in dix._asdict().items():
        if leaf is None:
            continue
        rows = []
        for sh in leaf.addressable_shards:
            if sh.data.shape[0] != 1:
                raise SmokeFailure(f"{what}.{name}: {sh.device} holds {sh.data.shape[0]} shards")
            rows.append((sh.device, sh.index[0].start or 0))
        devices = {d for d, _ in rows}
        counts = collections.Counter(r for _, r in rows)
        if len(rows) != n_devices or len(devices) != n_devices or set(counts) != set(range(n_shards)):
            raise SmokeFailure(f"{what}.{name}: placement {rows}")
    check(True, f"{what}: each of {n_devices} devices holds one shard of {n_shards}")


def phase_four_chips(index, queries, clock):
    from repro.control import ControlPlane
    from repro.core import Engine
    from repro.serving import BucketSpec, ShardedBatchEngine, ShardedEngine

    oracle = oracle_topk(index, queries)
    engine = Engine(index, k=K, impl="xla", impact_dtype="int8")
    spec = BucketSpec(max_batch=N_SLOTS)
    plans = [engine.plan(q) for q in queries]

    print("sharded: ShardedEngine(n_shards=4) on a 4-device mesh vs one device")
    t0, c0 = time.perf_counter(), clock.seconds
    mesh_se = ShardedEngine(engine, 4, use_mesh=True)
    check(mesh_se.mesh is not None, "mesh path selected")
    check_placement(mesh_se.dix, 4, 4, "ShardedEngine index")
    on_mesh = ShardedBatchEngine(mesh_se, spec).run_batch(plans)
    one_dev = ShardedBatchEngine(ShardedEngine(engine, 4, use_mesh=False), spec).run_batch(plans)
    print(
        f"  {time.perf_counter() - t0:.1f}s, of which XLA compile "
        f"{clock.seconds - c0:.1f}s"
    )
    same = sum(result_key(a) == result_key(b) for a, b in zip(on_mesh, one_dev))
    check(same == len(queries), f"{same}/{len(queries)} mesh results bitwise equal to one device")
    hits = sum(
        r.doc_ids.tolist() == oid.tolist() and r.scores.tolist() == osc.tolist()
        for r, (oid, osc) in zip(on_mesh, oracle)
    )
    check(hits == len(queries), f"{hits}/{len(queries)} mesh top-{K} bitwise equal to the oracle")

    print("control plane: 2 shards x 2 replicas vs 1 replica")
    t0, c0 = time.perf_counter(), clock.seconds
    group = ControlPlane(engine, n_shards=2, n_replicas=2, spec=spec)
    check(group.replicas.group_mesh is not None, "2x2 replica mesh selected")
    check_placement(group.replicas.group_dix, 4, 2, "replica group index")
    single = ControlPlane(engine, n_shards=2, n_replicas=1, spec=spec)
    a = {s.rid: s.result for s in group.replay(queries, batch_size=N_SLOTS)}
    b = {s.rid: s.result for s in single.replay(queries, batch_size=N_SLOTS)}
    print(
        f"  {time.perf_counter() - t0:.1f}s, of which XLA compile "
        f"{clock.seconds - c0:.1f}s; replica-mesh dispatches {group.replicas.dispatches}"
    )
    check(group.replicas.dispatches > 0, "queries went through the replica mesh")
    check(len(a) == len(b) == len(queries), f"{len(a)}/{len(queries)} queries answered")
    same = sum(result_key(a[i]) == result_key(b[i]) for i in range(len(queries)))
    check(same == len(queries), f"{same}/{len(queries)} replica results bitwise equal to one replica")
    hits = sum(
        a[i].doc_ids.tolist() == oid.tolist() and a[i].scores.tolist() == osc.tolist()
        for i, (oid, osc) in enumerate(oracle)
    )
    check(hits == len(queries), f"{hits}/{len(queries)} replica top-{K} bitwise equal to the oracle")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: the one-chip serving path; 4: only the multi-chip phase")
    ap.add_argument("--docs", type=int, default=200_000,
                    help="corpus size (default about 20M postings)")
    args = ap.parse_args(argv)
    try:
        devices = check_device(args.chips)
        from repro.compile_cache import use_compile_cache

        print(f"compile cache: {use_compile_cache()}")
        clock = CompileClock()
        index, queries = build(args.docs)
        if args.chips == 4:
            phase_four_chips(index, queries, clock)
        else:
            phase_one_chip(index, queries, clock, devices[0])
        print(
            f"XLA compile seconds in total: {clock.seconds:.1f} "
            f"(persistent-cache hits: {clock.cache_hits})"
        )
    except SmokeFailure as e:
        print(f"FAILED: {e}", file=sys.stderr)
        return 1
    d = devices[0]
    print(json.dumps(
        {"ok": True, "device": {"platform": d.platform, "kind": d.device_kind, "count": args.chips}}
    ))
    return 0


if __name__ == "__main__":
    sys.exit(main())
