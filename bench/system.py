"""The system under test, as the benchmark drives it.

Everything the program contributes to a run goes through here: its index
build (``build_index``) or a stored copy of it (``repro.index_io``), its
engine (``core.range_daat.Engine`` at the default scorer and docid format),
its serving loop (``InflightServer`` over ``BatchEngine``) and budgeter
(``SlaBudgeter`` with the ``Reactive`` policy). The benchmark adds only
spans around its calls into the program.
"""

from __future__ import annotations

import contextlib
import hashlib
import os
import shutil
import time
from pathlib import Path

import numpy as np

from bench.gen import Corpus

__all__ = [
    "CompileClock",
    "NoChip",
    "check_device",
    "get_index",
    "index_key",
    "lead_in",
    "make_engine",
    "make_server",
    "source_hash",
    "use_compile_cache",
    "warm",
]

# Sources that decide what an index holds, the program's and the harness's
# own (the corpus and the build's arguments): a change to any of them must
# never be served an index its parent built.
INDEX_SOURCES = ("src/repro/core", "src/repro/data", "src/repro/index_io", "bench/gen.py", "bench/system.py")
KEEP_INDEXES = 16  # stored indexes kept per configuration: two sets of six seeds


class NoChip(RuntimeError):
    pass


def check_device(n_chips: int):
    """The TPU devices of this machine; refuses anything else."""
    import jax

    devices = jax.devices()
    kinds = sorted({f"{d.platform}:{d.device_kind}" for d in devices})
    print(f"devices: {len(devices)} x {kinds} (jax {jax.__version__})")
    if devices[0].platform != "tpu":
        hint = os.environ.get("JAX_PLATFORMS")
        note = f", JAX_PLATFORMS={hint!r}" if hint else ""
        raise NoChip(f"no TPU: JAX found {devices[0].platform} ({devices[0].device_kind}{note})")
    if len(devices) < n_chips:
        raise NoChip(f"the cell needs {n_chips} TPU chips, JAX found {len(devices)}")
    return devices[:n_chips]


class CompileClock:
    """XLA compile seconds and count, and persistent-cache hits."""

    def __init__(self):
        import jax

        self.seconds = 0.0
        self.compiles = 0
        self.cache_hits = 0

        def on_duration(name, secs, **_):
            if name == "/jax/core/compile/backend_compile_duration":
                self.seconds += secs
                self.compiles += 1

        def on_event(name, **_):
            if name == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)


def use_compile_cache(path: Path) -> None:
    """JAX's persistent compilation cache at ``path``, every program in it.

    The path is fixed inside the checkout: it is part of every cache key,
    so only the first run of a cell in a checkout compiles.
    """
    import jax

    jax.config.update("jax_compilation_cache_dir", str(path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def source_hash(root: Path) -> str:
    h = hashlib.sha1()
    for sub in INDEX_SOURCES:
        top = root / sub
        for p in [top] if top.is_file() else sorted(top.rglob("*.py")):
            h.update(str(p.relative_to(root)).encode())
            h.update(p.read_bytes())
    return h.hexdigest()


def index_key(config_file: Path, seed: int, root: Path) -> str:
    h = hashlib.sha1(config_file.read_bytes())
    h.update(str(int(seed)).encode())
    h.update(source_hash(root).encode())
    return h.hexdigest()[:20]


def _program_corpus(c: Corpus):
    from repro.data.synth import Corpus as ProgramCorpus

    return ProgramCorpus(
        n_docs=c.n_docs,
        n_terms=c.n_terms,
        doc_ptr=c.doc_ptr,
        doc_terms=c.doc_terms,
        doc_tfs=c.doc_tfs,
        doc_topic=c.doc_topic,
        n_topics=c.n_topics,
    )


def _build(cfg: dict, corpus: Corpus, order: np.ndarray, ends: np.ndarray):
    from repro.core import build_index
    from repro.core.bm25 import BM25Params
    from repro.core.reorder import Arrangement

    return build_index(
        _program_corpus(corpus),
        arrangement=Arrangement(order, ends, "clustered"),
        bits=int(cfg["impact_bits"]),
        params=BM25Params(k1=float(cfg["bm25_k1"]), b=float(cfg["bm25_b"])),
    )


def get_index(cfg, config_file, corpus, order, ends, seed, root, cache_dir, log=print):
    """The program's index for this corpus: stored copy if there is one, else built.

    Returns ``(index, "built" | "loaded", seconds)``. A stored copy that does
    not load is reported and replaced by a build.
    """
    from repro import index_io

    path = Path(cache_dir) / f"{cfg['name']}-{index_key(Path(config_file), seed, root)}"
    t0 = time.perf_counter()
    if path.is_dir():
        try:
            index = index_io.load_index(str(path))
            os.utime(path)
            return index, "loaded", time.perf_counter() - t0
        except Exception as e:  # noqa: BLE001 - any unreadable copy is rebuilt
            log(f"index cache: {path.name} did not load ({type(e).__name__}: {e}); building")
            shutil.rmtree(path, ignore_errors=True)
    index = _build(cfg, corpus, order, ends)
    seconds = time.perf_counter() - t0
    path.parent.mkdir(parents=True, exist_ok=True)
    index_io.save_index(index, str(path), impact_dtype=cfg["impact_dtype"], overwrite=True)
    stored = sorted(path.parent.glob(f"{cfg['name']}-*"), key=lambda p: p.stat().st_mtime)
    for old in stored[:-KEEP_INDEXES]:
        shutil.rmtree(old, ignore_errors=True)
    return index, "built", seconds


def make_engine(index, cfg: dict):
    from repro.core import Engine

    return Engine(index, k=int(cfg["k"]), impact_dtype=cfg["impact_dtype"])


class Spans:
    """Harness spans: plan durations and step wall times, in seconds.

    With ``annotate`` the same phases are written into the profiler's trace
    as ``TraceAnnotation``s, so idle gaps on the device can be named by what
    the host was doing.
    """

    def __init__(self, annotate: bool = False):
        self.annotate = annotate
        self.plan: list[float] = []
        self.step: list[float] = []
        self.step_plan: list[float] = []  # planning inside each step
        self.plan_start: list[float] = []  # perf_counter at each span's start
        self.step_start: list[float] = []

    def clear(self) -> None:
        self.__init__(self.annotate)

    def phase(self, name: str):
        if not self.annotate:
            return contextlib.nullcontext()
        import jax

        return jax.profiler.TraceAnnotation(f"bench.{name}")


def make_server(engine, cfg: dict, traffic: dict, spans: Spans):
    """A fresh ``InflightServer`` with the cell's budgeter, planning timed."""
    from repro.core.anytime import Reactive
    from repro.serving import BatchEngine, BucketSpec, InflightServer, SlaBudgeter

    class TimedBatchEngine(BatchEngine):
        def plan(self, q_terms):
            with spans.phase("plan"):
                t0 = time.perf_counter()
                p = super().plan(q_terms)
                spans.plan.append(time.perf_counter() - t0)
                spans.plan_start.append(t0)
            return p

    sla = traffic.get("sla_ms")
    if sla is None:
        budgeter = SlaBudgeter(sla_ms=float("inf"))
    else:
        budgeter = SlaBudgeter(sla_ms=float(sla), policy=Reactive(**traffic.get("policy", {})))
    n_slots = int(cfg["n_slots"])
    return InflightServer(
        TimedBatchEngine(engine, BucketSpec(max_batch=n_slots)),
        budgeter,
        n_slots=n_slots,
        quantum=int(cfg["quantum"]),
    )


def plan_widths(index, queries: np.ndarray) -> np.ndarray:
    """Block-table width of each query's plan: its widest range's block count."""
    nb = np.zeros((index.n_terms + 1, index.n_ranges), np.int64)
    term = np.repeat(np.arange(index.n_terms), np.diff(index.tr_ptr))
    nb[term, index.tr_range] = index.tr_blk_end - index.tr_blk_start
    q = np.where(queries >= 0, queries, index.n_terms)  # padding -> zero row
    return nb[q].sum(axis=1).max(axis=1)


def _serve_until_idle(server) -> bool:
    """Step until every submitted query is answered; False if one never is.

    A query takes at most one step per range; a program that never
    finishes it is left to the window's check to catch.
    """
    for _ in range(2 * server.n_ranges + 2):
        if not (server.pending or server.active):
            return True
        server.step()
    return not (server.pending or server.active)


def warm(engine, cfg: dict, queries: np.ndarray, log=print) -> list[int]:
    """Compile the plan conversion of every width bucket the traffic reaches.

    A plan's block table is padded to its width bucket and converted on the
    device, one program per bucket; one query of each bucket is planned here
    so that none compiles in the window. The traversal program compiles in
    ``lead_in``, at the one width the window runs at.
    """
    from repro.serving import BucketSpec

    spec = BucketSpec(max_batch=int(cfg["n_slots"]))
    widths = plan_widths(engine.index, queries)
    buckets = np.asarray([spec.width_bucket(int(w)) for w in widths])
    ladder = sorted(set(buckets.tolist()))
    for w in ladder:
        engine.plan(queries[int(np.argmax(buckets == w))])
    log(f"warm: plan widths {ladder} (plans {int(widths.min())}..{int(widths.max())} blocks)")
    return ladder


def lead_in(server, engine, queries: np.ndarray, log=print) -> int:
    """Bring a fresh server to the width a long-running one has.

    The slot table only widens, to the widest plan served so far, and every
    step pays for the table's width. So a long-running server sits at the
    width of the widest plan its traffic has sent. ``queries`` are the
    mix's lead-in (the server's past) and the window's own; the widest of
    them is served here, through the server's own path, so the window runs
    at one width and compiles nothing.
    """
    widths = plan_widths(engine.index, queries)
    server.submit(queries[int(np.argmax(widths))])
    if not _serve_until_idle(server):
        log("lead-in: the widest query was not answered")
    width = server.buffers.front.width
    log(f"lead-in: widest of {len(queries)} plans {int(widths.max())} blocks, slot table width {width}")
    return width
