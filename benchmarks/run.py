"""Benchmark entry point: one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV per the repo contract, where
us_per_call is the benchmark's headline per-query latency (microseconds)
where latency is meaningful, and ``derived`` carries the headline claim
metric. Full rows land in benchmarks/results/*.json for EXPERIMENTS.md,
and the per-run headline summary lands in a top-level ``BENCH_<id>.json``
(id = ``$BENCH_ID``, else the git short sha, else a timestamp) — the
perf-trajectory artifact CI uploads per commit. Because that artifact is
gitignored and expires with CI retention, every run also appends a compact
record to the git-tracked ``benchmarks/trajectory.jsonl``.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time
import traceback

MODULES = [
    ("T2_index_space", "benchmarks.bench_index_space"),
    ("T3_saat_reorder", "benchmarks.bench_saat_reorder"),
    ("F5_safe_daat", "benchmarks.bench_safe_daat"),
    ("T4_range_selection", "benchmarks.bench_range_selection"),
    ("F7_tradeoff", "benchmarks.bench_tradeoff"),
    ("T5_sla", "benchmarks.bench_sla"),
    ("F8_alpha", "benchmarks.bench_alpha"),
    ("T6_reactive", "benchmarks.bench_reactive"),
    ("T7_partitions", "benchmarks.bench_partitions"),
    ("F11_scaling", "benchmarks.bench_scaling"),
    ("S1_batch_serving", "benchmarks.bench_batch_serving"),
    ("S2_sharded_serving", "benchmarks.bench_sharded_serving"),
    ("S3_index_io", "benchmarks.bench_index_io"),
    ("S4_control_plane", "benchmarks.bench_control_plane"),
    ("S5_incremental", "benchmarks.bench_incremental"),
    ("S6_inflight", "benchmarks.bench_inflight"),
    ("T8_failures", "benchmarks.bench_failures"),
    ("Q_quantization", "benchmarks.bench_quantization"),
    ("S7_packed", "benchmarks.bench_packed_postings"),
]


def _headline(name: str, rows) -> tuple[float, str]:
    """(us_per_call, derived) summaries per benchmark."""
    try:
        if name == "T2_index_space":
            clustered = next(
                r for r in rows
                if r["index_type"] == "Clustered" and r["ordering"] == "Reordered"
            )
            return 0.0, f"clustered_overhead={clustered['overhead_vs_default']}x"
        if name == "T3_saat_reorder":
            r = rows[0]
            return (
                r["reordered_p50"] * 1e3,
                f"lines_ratio={r['lines_ratio']}x_speedup_p50={r['speedup_p50']}x",
            )
        if name == "F5_safe_daat":
            r = next(x for x in rows if x["k"] == 10 and "Clustered" in x["mode"])
            d = next(x for x in rows if x["k"] == 10 and "Default" in x["mode"])
            return r["p50"] * 1e3, f"clustered_vs_default_p50={d['p50']/max(r['p50'],1e-9):.2f}x"
        if name == "T4_range_selection":
            r10 = next(x for x in rows if x["ranges"] == 10)
            return 0.0, f"rbo10_bndsum={r10['rbo_BndSum']}_oracle={r10['rbo_Oracle']}"
        if name == "F7_tradeoff":
            r = next(x for x in rows if x["system"] == "BndSum" and x["setting"] == "n=10" and x["k"] == 10)
            return r["p50_ms"] * 1e3, f"rbo={r['rbo']}"
        if name == "T5_sla":
            r = next(x for x in rows if x["system"] == "Predictive-a1")
            return r["p99"] * 1e3, f"sla_met={r['sla_met']}_rbo={r['rbo']}"
        if name == "F8_alpha":
            r = next(x for x in rows if x["alpha"] == 2.0 and x["sla_frac_of_p99"] == 0.1)
            return r["p99"] * 1e3, f"sla_met={r['sla_met']}_rbo={r['rbo']}"
        if name == "T6_reactive":
            r = next(x for x in rows if x["system"] == "Reactive-b1.2")
            return r["p99"] * 1e3, f"miss_pct={r['miss_pct']}_rbo={r['rbo']}"
        if name == "T7_partitions":
            r = rows[-1]
            return 0.0, f"p99_range_pct={r['p99_range_pct']}%"
        if name == "F11_scaling":
            r = next(x for x in rows if x["batch"] == 32 and x["budget"] == "unlimited")
            return 1e6 / max(r["qps"], 1e-9), f"speedup_b32={r['speedup_vs_b1']}x"
        if name == "S1_batch_serving":
            r = next(
                x for x in rows if x["engine"] == "batch-32"
                and x["budget"] == "unlimited"
            )
            return (
                1e6 / max(r["qps"], 1e-9),
                f"qps_b32={r['qps']}_speedup={r['speedup_vs_seq_host']}x",
            )
        if name == "S2_sharded_serving":
            r = next(
                x for x in rows if x["engine"] == "sharded-4"
                and x["budget"] == "unlimited"
            )
            return (
                1e6 / max(r["qps"], 1e-9),
                f"qps_4shard={r['qps']}_path={r['path'].split()[0]}"
                f"_vs_batch={r['speedup_vs_batch']}x",
            )
        if name == "S3_index_io":
            r8 = next(x for x in rows if x["impact_dtype"] == "int8")
            return (
                r8["load_ms_eager"] * 1e3,
                f"disk_mb={r8['disk_mb']}"
                f"_hbm_impacts={r8['hbm_impacts_ratio_vs_int32']}x"
                f"_parity={r8['parity_bitwise']}",
            )
        if name == "S4_control_plane":
            r2 = next(x for x in rows if x["mode"] == "replicas-2")
            live = next(x for x in rows if x["mode"] == "reshard-live")
            return (
                1e6 / max(r2["qps"], 1e-9),
                f"qps_1rep={next(x for x in rows if x['mode'] == 'replicas-1')['qps']}"
                f"_2rep={r2['qps']}_reshard_qps={live['qps_during']}"
                f"_served_during={live['served_during']}",
            )
        if name == "S5_incremental":
            app = next(x for x in rows if x["op"] == "append+publish")
            reb = next(x for x in rows if x["op"] == "rebuild+publish")
            deep = next(
                x for x in rows if x["op"] == "reopen-chain"
                and x["chain_length"] == max(
                    y["chain_length"] for y in rows if y["op"] == "reopen-chain"
                )
            )
            comp = next(x for x in rows if x["op"] == "reopen-compacted")
            return (
                app["ms"] * 1e3,
                f"append={app['ms']}ms_rebuild={reb['ms']}ms_"
                f"speedup={reb['speedup_vs_rebuild']}x_"
                f"reopen{deep['chain_length']}={deep['ms']}ms_"
                f"compacted={comp['ms']}ms_parity={comp['parity_bitwise']}",
            )
        if name == "S6_inflight":
            r = next(
                x for x in rows if x["server"].startswith("inflight")
                and x["budget"] == "unlimited"
            )
            return (
                1e6 / max(r["qps"], 1e-9),
                f"qps={r['qps']}_vs_micro={r['qps_vs_microbatch']}x"
                f"_p99={r['p99_vs_microbatch']}x",
            )
        if name == "S7_packed":
            pk = next(x for x in rows if x["docs_format"] == "packed")
            return (
                1e6 / max(pk["qps"], 1e-9),
                f"docid_hbm_ratio={pk['docid_hbm_ratio_vs_int32']}x"
                f"_qps_vs_int32={pk['qps_vs_int32']}x"
                f"_parity={pk['parity_bitwise']}",
            )
        if name == "Q_quantization":
            r8 = next(x for x in rows if x["bits"] == 8)
            r4 = next(x for x in rows if x["bits"] == 4)
            return 0.0, f"rbo8bit={r8['rbo_vs_float']}_rbo4bit={r4['rbo_vs_float']}"
        if name == "T8_failures":
            r = rows[-1]
            if r.get("summary"):
                return 0.0, (
                    f"depth_low={r['mean_avg_depth_low_rbo']}"
                    f"_high={r['mean_avg_depth_high_rbo']}"
                )
    except (StopIteration, KeyError, IndexError):
        pass
    return 0.0, "see_json"


def _bench_id() -> str:
    """Stable id for this run's BENCH_<id>.json: env, git sha, or time."""
    env = os.environ.get("BENCH_ID")
    if env:
        return env
    try:
        proc = subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"],
            capture_output=True, text=True, timeout=10,
            cwd=os.path.dirname(os.path.abspath(__file__)),
        )
        # A nonzero exit (not a repo, detached worktree garbage) can still
        # print to stdout under some git versions — never trust it then.
        sha = proc.stdout.strip() if proc.returncode == 0 else ""
        if sha:
            return sha
    except (OSError, subprocess.SubprocessError):
        pass
    return time.strftime("%Y%m%d-%H%M%S")


TRAJECTORY = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "trajectory.jsonl")


def _obs_compact(metrics: dict | None) -> dict:
    """Compact per-module observability facts for the trajectory log.

    One small dict per module that exported an OBS_SNAPSHOT: the
    instrumented-vs-noop overhead, total profiler compile/recompile
    counts, and the registered metric names — the inputs perf_gate.py
    checks for q/s regressions and metric-schema drift.
    """
    out: dict = {}
    for name, snap in sorted((metrics or {}).items()):
        rec: dict = {}
        overhead = (snap.get("overhead") or {}).get("overhead_pct")
        if overhead is not None:
            rec["overhead_pct"] = overhead
        prof = snap.get("profiler") or {}
        if prof:
            rec["profiler"] = {
                "compiles": sum(s.get("compiles", 0) for s in prof.values()),
                "recompiles": sum(
                    s.get("recompiles", 0) for s in prof.values()
                ),
            }
        reg = snap.get("registry") or {}
        if reg:
            rec["metric_names"] = sorted(reg)
        if rec:
            out[name] = rec
    return out


def _static_findings(root: str) -> dict | None:
    """Static-hazard finding counts for this run's trajectory record.

    Sourced from ``repro.analysis`` (DESIGN.md §15) so perf_gate can fail
    a run whose finding count *rose* against history — the lint ratchet's
    CI twin. Analyzer unavailable (trimmed checkout) -> record nothing.
    """
    try:
        from repro.analysis import count_findings
    except ImportError:
        return None
    try:
        return count_findings(os.path.join(root, "src", "repro"))
    except (OSError, SyntaxError):
        return None


def append_trajectory(
    rid: str,
    headlines: dict,
    failures: list,
    metrics: dict | None = None,
    static_findings: dict | None = None,
) -> str:
    """Append one compact run record to the git-tracked trajectory log.

    ``BENCH_<id>.json`` is gitignored and CI only keeps it as an expiring
    artifact, which is why seven PRs of bench runs accumulated nothing.
    This JSONL file is tracked: every run (CI small presets included)
    appends one line — id, time, and the headline string per benchmark,
    no bulky per-row payloads — so the perf trajectory survives in-repo.
    A run with the same id (re-run of one commit) replaces its entry.
    """
    entry = {
        "id": rid,
        "unix_time": int(time.time()),
        "small": bool(os.environ.get("REPRO_BENCH_SMALL")),
        "headlines": {
            name: h["derived"] for name, h in sorted(headlines.items())
        },
        "us_per_call": {
            name: h["us_per_call"] for name, h in sorted(headlines.items())
        },
        "failures": failures,
    }
    obs = _obs_compact(metrics)
    if obs:
        entry["obs"] = obs
    if static_findings is not None:
        entry["static_findings"] = static_findings
    lines = []
    if os.path.exists(TRAJECTORY):
        with open(TRAJECTORY, encoding="utf-8") as f:
            lines = [ln for ln in f.read().splitlines() if ln.strip()]
        lines = [ln for ln in lines if json.loads(ln).get("id") != rid]
    lines.append(json.dumps(entry, sort_keys=True))
    with open(TRAJECTORY, "w", encoding="utf-8") as f:
        f.write("\n".join(lines) + "\n")
    return TRAJECTORY


def write_headline_file(
    headlines: dict, failures: list, metrics: dict | None = None
) -> str:
    """Write the top-level BENCH_<id>.json perf-trajectory snapshot."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    rid = _bench_id()
    path = os.path.join(root, f"BENCH_{rid}.json")
    payload = {
        "id": rid,
        "unix_time": int(time.time()),
        "headlines": headlines,
        "failures": failures,
    }
    if metrics:
        payload["metrics"] = metrics
    findings = _static_findings(root)
    if findings is not None:
        payload["static_findings"] = findings
    with open(path, "w", encoding="utf-8") as f:
        json.dump(payload, f, indent=1, sort_keys=True)
    append_trajectory(
        rid, headlines, failures, metrics, static_findings=findings
    )
    return path


def main() -> None:
    import importlib

    from repro.compile_cache import use_compile_cache

    use_compile_cache()
    only = sys.argv[1:] if len(sys.argv) > 1 else None
    print("name,us_per_call,derived")
    failures = []
    headlines = {}
    metrics = {}
    for name, module in MODULES:
        if only and not any(o in name for o in only):
            continue
        t0 = time.time()
        try:
            mod = importlib.import_module(module)
            rows = mod.run()
            us, derived = _headline(name, rows)
            print(f"{name},{us:.1f},{derived}", flush=True)
            headlines[name] = {"us_per_call": round(us, 1), "derived": derived}
            # Bench modules that instrument a run export an OBS_SNAPSHOT
            # (metrics-registry dump + derived numbers); fold it into the
            # BENCH_<id>.json so overhead claims ship with the run record.
            snap = getattr(mod, "OBS_SNAPSHOT", None)
            if snap:
                metrics[name] = snap
        except Exception as e:  # noqa: BLE001
            traceback.print_exc()
            print(f"{name},nan,FAILED:{type(e).__name__}", flush=True)
            failures.append(name)
        sys.stderr.write(f"# {name} took {time.time()-t0:.1f}s\n")
    if headlines or failures:
        path = write_headline_file(headlines, failures, metrics)
        sys.stderr.write(f"# headline trajectory -> {path}\n")
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
