"""Knee sweep of one cell: a ladder of offered rates in one process.

    python3 bench/sweep.py --workload passage-isn.safe --seeds 11 12 13 --seconds 10 --rates 8,16,24,32

Each seed's index is built (or loaded) once; every rate gets a fresh server
and its own queries, and the cell's traffic mix with only ``rate_qps``
changed. For each seed and rate it prints one JSON line: offered and
completed q/s, p50, p95 and p99 of due-to-answer latency, the backlog
(submitted, not yet answered) averaged over the second and the last
quarter of the window, and the loop's stalls. A rate keeps up when at least
95% of what it offered is answered in the window and its backlog does not
grow from the one quarter to the other; a seed's knee is the highest rate
that keeps up with every rate below it keeping up too. The last line gives
each seed's knee and their lowest. The rate a traffic file fixes is written
there by hand from this sweep.
"""

import sys
import time
from pathlib import Path

sys.path[:1] = [str(Path(__file__).resolve().parent.parent), str(Path(__file__).resolve().parent.parent / "src")]

import argparse  # noqa: E402
import json  # noqa: E402

import numpy as np  # noqa: E402

from bench import gen, loop, system  # noqa: E402
from bench.spec import ROOT, load_cell  # noqa: E402
from repro.obs import NOOP  # noqa: E402


def _quarter_backlog(backlog, seconds, q):
    b = [n for t, n in backlog if q * seconds / 4 <= t < (q + 1) * seconds / 4]
    return float(np.mean(b)) if b else 0.0


def _keeps_up(r: dict) -> bool:
    return r["completed_qps"] >= 0.95 * r["offered_qps"] and r["backlog_q4"] <= 1.25 * r["backlog_q2"] + 2


def _sweep_seed(cell, seed: int, rates: list, args, clock, devices) -> float:
    cfg, inputs, layout = cell.config, cell.inputs, cell.layout
    corpus = inputs.make_corpus(cfg, seed)
    order, ends = inputs.arrange(corpus)
    index, how, t_index = system.get_index(
        cell, corpus, order, ends, seed, ROOT, ROOT / ".cache" / "bench" / "index"
    )
    print(f"seed {seed}: index {how} in {t_index:.1f}s: {index.nnz} postings")
    engine = layout.make_engine(index, cfg, devices)
    mixes = []
    for j, rate in enumerate(rates):
        traffic = dict(cell.traffic, rate_qps=rate)
        if args.sla_ms is not None:
            traffic["sla_ms"] = args.sla_ms
        due = gen.make_schedule(traffic, args.seconds, seed + j)
        mixes.append((traffic, due, inputs.make_queries(corpus, traffic, len(due), seed + j)))
    past = inputs.make_queries(corpus, cell.traffic, int(cell.traffic["lead_in_queries"]), seed, lead_in=True)
    seen = np.concatenate([past] + [q for _, _, q in mixes])
    layout.warm(engine, cfg, seen)
    knee = 0.0
    for rate, (traffic, due, queries) in zip(rates, mixes):
        spans = system.Spans()
        server = layout.make_server(engine, cfg, traffic, spans, NOOP)
        layout.lead_in(server, engine, seen)
        spans.clear()
        t0 = time.perf_counter()
        d = loop.drive(server, queries, due, args.seconds, spans, clock, grace_s=5.0)
        lat = (np.where(np.isfinite(d.answered_s), d.answered_s, np.inf) - due) * 1e3
        reasons = [sq.result.exit_reason for sq in d.served if sq is not None]
        r = {
            "seed": seed,
            "offered_qps": len(due) / args.seconds,
            "completed_qps": int((d.answered_s < args.seconds).sum()) / args.seconds,
            "p50_ms": float(np.percentile(lat, 50)),
            "p95_ms": float(np.percentile(lat, 95)),
            "p99_ms": float(np.percentile(lat, 99)),
            "backlog_q2": _quarter_backlog(d.backlog, args.seconds, 1),
            "backlog_q4": _quarter_backlog(d.backlog, args.seconds, 3),
            "budget_exits": sum(r == "budget" for r in reasons) / max(1, len(reasons)),
            "mean_step_ms": 1e3 * float(np.mean(spans.step)) if spans.step else None,
            "steps": len(spans.step),
            "compiles_in_window": d.compiles_in_window,
            "stalls": [[round(x.at_s, 3), x.phase, round(x.wall_ms, 1), round(x.thread_ms, 1), round(x.gc_ms, 1)] for x in d.stalls],
            "drain_s": time.perf_counter() - t0 - args.seconds,
        }
        print(json.dumps(r), flush=True)
        if _keeps_up(r) and knee == max([0.0] + [x for x in rates if x < rate]):
            knee = rate
    return knee


def main(argv=None, device_check=system.check_device) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--rates", required=True, help="comma-separated offered q/s")
    ap.add_argument("--sla-ms", type=float, default=None, help="override the mix's SLA")
    args = ap.parse_args(argv)
    rates = [float(r) for r in args.rates.split(",")]
    cell = load_cell(args.workload)
    try:
        devices = device_check(cell.chips)
    except system.NoChip as e:
        print(f"refused: {e}", file=sys.stderr)
        return 2
    system.use_compile_cache(ROOT / ".cache" / "bench" / "jax")
    clock = system.CompileClock()
    knees = {}
    for seed in args.seeds:
        knees[seed] = _sweep_seed(cell, seed, rates, args, clock, devices)
    print(json.dumps({"knees": knees, "lowest": min(knees.values())}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
