"""Run one benchmark cell once and print its result as the last line.

    python3 bench/run.py --workload passage-isn.safe --seed 7 --seconds 35 --trace 0

Set-up (counted in ``setup_s``, from process start to the first due query):
the corpus and the queries from ``--seed``, the program's index for that
corpus (built by the configuration's ``inputs`` module, or loaded from
``.cache/bench/index`` when this seed and these sources built it before),
the upload to the cell's chips, and the compile of every program shape the
traffic reaches (read from the compile cache in ``.cache/bench/jax`` after
the first run). Then the open loop drives the server for ``--seconds``,
waits for late answers, and checks every answer against the configuration's
plain reference under its guarantee. What is built, served and compared
with is the configuration's choice (``bench/spec.py``).

With ``--trace 1`` a profiler trace of a few seconds in the middle of the
window gives the device numbers, the server names its step's phases in it,
and the result carries the per-layer metrics instead of the end-to-end
ones. Without a TPU, or with fewer chips than the cell asks for, it exits
non-zero and prints no result.
"""

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

_ROOT = Path(__file__).resolve().parent.parent
# Run as a script, the interpreter puts bench/ first on the path; the
# harness imports itself as the package ``bench`` from the checkout root.
sys.path[:1] = [str(_ROOT), str(_ROOT / "src")]

import numpy as np  # noqa: E402

from bench import gen, loop, phasetrace, system  # noqa: E402
from bench.peaks import peak  # noqa: E402
from bench.reference import Answer  # noqa: E402
from bench.spec import ROOT, Cell, load_cell, metric_reader  # noqa: E402

CACHE = ROOT / ".cache" / "bench"


@dataclasses.dataclass
class Run:
    """What the metric readers see of one run."""

    cell: Cell
    seconds: float
    setup_s: float
    latency_ms: np.ndarray  # due -> answer, every query due in the window
    completed_in_window: int
    exit_reasons: list  # of every answered query
    recall: tuple  # (found, wanted) over answered queries
    plan_s: list
    step_self_s: list
    trace: dict | None
    postings_traced: int
    peaks: dict


def _answer(sq) -> Answer:
    r = sq.result
    return Answer(r.doc_ids, r.scores, int(r.ranges_processed), r.exit_reason)


def run_cell(
    cell: Cell,
    seed: int,
    seconds: float,
    trace: bool,
    device_check=system.check_device,
    cache_dir: Path = CACHE,
    grace_s: float = loop.GRACE_S,
    log=print,
) -> dict:
    cfg, traffic, inputs, layout = cell.config, cell.traffic, cell.inputs, cell.layout
    devices = device_check(cell.chips)
    system.use_compile_cache(Path(cache_dir) / "jax")
    clock = system.CompileClock()

    t = time.perf_counter()
    corpus = inputs.make_corpus(cfg, seed)
    order, ends = inputs.arrange(corpus)
    due = gen.make_schedule(traffic, seconds, seed)
    queries = inputs.make_queries(corpus, traffic, len(due), seed)
    t_gen = time.perf_counter() - t

    index, how, t_index = system.get_index(
        cell, corpus, order, ends, seed, ROOT, Path(cache_dir) / "index", log
    )
    t = time.perf_counter()
    engine = layout.make_engine(index, cfg, devices)
    t_upload = time.perf_counter() - t
    c0, n0 = clock.seconds, clock.compiles
    t = time.perf_counter()
    past = inputs.make_queries(corpus, traffic, int(traffic["lead_in_queries"]), seed, lead_in=True)
    seen = np.concatenate([past, queries])
    layout.warm(engine, cfg, seen, log)
    import jax

    from repro.obs import NOOP, NoopInstrumentation

    # Traced, the server names its step's phases in the profiler's trace;
    # untraced, it runs with no instrumentation at all.
    obs = NoopInstrumentation(annotate=jax.profiler.TraceAnnotation) if trace else NOOP
    spans = system.Spans(annotate=trace)
    server = layout.make_server(engine, cfg, traffic, spans, obs)
    layout.lead_in(server, engine, seen, log)
    # The traversal's ops carry their scopes only in the compiled program's
    # text, which a TPU trace does not hold: read it once, before the window.
    op_scopes = phasetrace.hlo_scopes(layout.program_hlo(server)) if trace else None
    spans.clear()
    t_warm = time.perf_counter() - t
    # What set-up left behind is the serving process's long-lived state, as
    # a server holds it after start-up: out of the collector's way, so that
    # a full collection in the window walks only what the window allocates.
    gc.collect()
    gc.freeze()
    log(f"gc: {gc.get_freeze_count()} objects of set-up frozen")
    log(
        f"index: {corpus.n_docs} docs, {corpus.n_terms} terms, {index.n_ranges} ranges, "
        f"{index.nnz} postings ({cfg['nnz_loc']} stated), {index.n_blocks} blocks, "
        f"s_pad {engine.s_pad}, k {engine.k}"
    )
    setup_s = time.perf_counter() - T_PROCESS
    log(
        f"setup: {setup_s:.3f}s; inputs {t_gen:.3f}s, index {how} {t_index:.3f}s, "
        f"upload {t_upload:.3f}s, warm {t_warm:.3f}s of which compile "
        f"{clock.seconds - c0:.3f}s ({clock.compiles - n0} compiles, "
        f"{clock.cache_hits} persistent-cache hits)"
    )

    tw = None
    if trace:
        tdir = Path(cache_dir) / "trace" / cell.name
        shutil.rmtree(tdir, ignore_errors=True)
        span = min(3.0, 0.3 * seconds)
        tw = loop.TraceWindow(start_s=0.4 * seconds, seconds=span, directory=str(tdir))
    d = loop.drive(server, queries, due, seconds, spans, clock, tw, grace_s)
    gc.unfreeze()
    mem_peak = max(int((dv.memory_stats() or {}).get("peak_bytes_in_use", 0)) for dv in devices)

    red = None
    postings_traced = 0
    if tw is not None and tw.started is not None:
        red = phasetrace.reduce(phasetrace.load_events(tw.directory, op_scopes), layout.PROGRAMS)
        ends_s = np.asarray(d.step_end_s)
        steps = np.asarray(d.postings)
        postings_traced = int(steps[(ends_s >= tw.started) & (ends_s <= tw.stopped)].sum())
        shutil.rmtree(tw.directory, ignore_errors=True)
    del server, engine

    # The reference, once the window has closed and the device is read.
    t = time.perf_counter()
    ref = cell.reference.make_reference(corpus, order, ends, cfg)
    wrong, found, wanted, reasons = 0, 0, 0, []
    for j, sq in enumerate(d.served):
        if sq is None:
            continue
        a = _answer(sq)
        reasons.append(a.exit_reason)
        wrong += not ref.check(queries[j], a, cell.guarantee)
        if cell.guarantee == "certified":
            f, w = ref.recall(queries[j], a)
            found, wanted = found + f, wanted + w
    t_ref = time.perf_counter() - t

    n = len(due)
    answered = np.isfinite(d.answered_s)
    unanswered = int((~answered).sum())
    deadline = seconds + grace_s
    latency_ms = (np.where(answered, d.answered_s, deadline) - due) * 1e3
    late_ms = (d.submitted_s - due)[np.isfinite(d.submitted_s)] * 1e3
    log(
        f"window: {n} due, {int((d.answered_s < seconds).sum())} answered in the window, "
        f"{unanswered} never; generator late p99 {np.percentile(late_ms, 99) if late_ms.size else 0:.3f} ms; "
        f"{len(d.postings)} steps, {d.compiles_in_window} compiles in the window; "
        f"peak device memory {mem_peak} bytes; reference {t_ref:.3f}s"
    )
    if d.compiles_in_window:
        log(f"WARNING: {d.compiles_in_window} programs compiled inside the window")
    g = d.gc
    log(
        f"host: longest step {d.longest_step_ms:.3f} ms; gc {sum(g.by_gen)} collections "
        f"(by generation {g.by_gen}), {1e3 * g.total_s:.3f} ms in all, longest {1e3 * g.longest_s:.3f} ms; "
        f"stalls over {1e3 * loop.STALL_S:.0f} ms: "
        + (json.dumps([dataclasses.asdict(x) for x in d.stalls]) if d.stalls else "none")
    )
    if red is not None:
        log(
            f"trace: window {red['window_s']:.6f}s, busy {red['busy_s']:.6f}s, "
            f"idle by host phase {json.dumps(red['idle_by_phase'])}"
        )

    # A traced run reads its spans up to the trace's end: stopping the
    # profiler stalls the loop, and what follows is a catch-up burst.
    cutoff = d.t0 + tw.stopped if tw is not None and tw.stopped is not None else np.inf
    kind = devices[0].device_kind
    run = Run(
        cell=cell,
        seconds=float(seconds),
        setup_s=setup_s,
        latency_ms=latency_ms,
        completed_in_window=int((d.answered_s < seconds).sum()),
        exit_reasons=reasons,
        recall=(found, wanted),
        plan_s=[a for a, t in zip(spans.plan, spans.plan_start) if t < cutoff],
        step_self_s=[a - b for a, b, t in zip(spans.step, spans.step_plan, spans.step_start) if t < cutoff],
        trace=red,
        postings_traced=postings_traced,
        peaks=peak(kind) if devices[0].platform == "tpu" else {},
    )
    metrics = {}
    for m in cell.per_layer if trace else cell.end_to_end:
        v = metric_reader(m["name"])(run)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}

    checks = {
        "wrong_answers": {"value": wrong, "limit": 0},
        "unanswered": {"value": unanswered, "limit": 0},
    }
    out = {
        "correct": all(c["value"] <= c["limit"] for c in checks.values()),
        "attempted": n,
        "failed": wrong + unanswered,
        "metrics": metrics,
        "device": {
            "platform": devices[0].platform,
            "kind": kind,
            "count": len(jax.devices()),
            "memory_peak_bytes": mem_peak,
        },
    }
    if red is not None:
        out["device"]["busy_s"] = red["busy_s"]
        out["device"]["window_s"] = red["window_s"]
        out["breakdown"] = {
            "device_ops": [[k, v] for k, v in red["device_ops"]],
            "idle_gaps": [[k, v] for k, v in red["idle_gaps"]],
        }
    out["checks"] = checks
    return out


def main(argv=None, device_check=system.check_device) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        cell = load_cell(args.workload)
        out = run_cell(cell, args.seed, args.seconds, bool(args.trace), device_check)
    except system.NoChip as e:
        print(f"refused: {e}", file=sys.stderr)
        return 2
    for name, c in out["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})", file=sys.stderr)
    sys.stdout.flush()
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
