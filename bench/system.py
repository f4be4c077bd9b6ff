"""What every run shares around the system under test.

The look for the chip, the compile cache and its clock, the program's index
(built by the configuration's ``inputs`` module, or a stored copy of it read
back through ``repro.index_io``), and the harness's own spans. The engine
and the server come from the configuration's ``layout`` module
(``bench/layouts``); the benchmark adds only spans around its calls into
the program.
"""

from __future__ import annotations

import contextlib
import hashlib
import os
import shutil
import time
from pathlib import Path

__all__ = [
    "CompileClock",
    "NoChip",
    "Spans",
    "check_device",
    "get_index",
    "index_key",
    "source_hash",
    "use_compile_cache",
]

# Sources that decide what an index holds, the program's and the harness's
# own (the corpus and the build's arguments): a change to any of them must
# never be served an index its parent built. A configuration's own
# ``inputs``, ``reference`` and ``layout`` modules are hashed beside them.
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


def index_key(config_file: Path, seed: int, root: Path, modules=()) -> str:
    """Key of a stored index: configuration, seed, index sources and ``modules``' files."""
    h = hashlib.sha1(config_file.read_bytes())
    h.update(str(int(seed)).encode())
    h.update(source_hash(root).encode())
    for p in modules:
        h.update(Path(p).name.encode())
        h.update(Path(p).read_bytes())
    return h.hexdigest()[:20]


def get_index(cell, corpus, order, ends, seed, root, cache_dir, log=print):
    """The program's index for this corpus: stored copy if there is one, else built.

    The cell's ``inputs`` module builds it. Returns ``(index, "built" |
    "loaded", seconds)``. A stored copy that does not load is reported and
    replaced by a build.
    """
    from repro import index_io

    cfg = cell.config
    key = index_key(Path(cell.config_file), seed, root, cell.module_files)
    path = Path(cache_dir) / f"{cfg['name']}-{key}"
    t0 = time.perf_counter()
    if path.is_dir():
        try:
            index = index_io.load_index(str(path))
            os.utime(path)
            return index, "loaded", time.perf_counter() - t0
        except Exception as e:  # noqa: BLE001 - any unreadable copy is rebuilt
            log(f"index cache: {path.name} did not load ({type(e).__name__}: {e}); building")
            shutil.rmtree(path, ignore_errors=True)
    index = cell.inputs.build_index(cfg, corpus, order, ends)
    seconds = time.perf_counter() - t0
    path.parent.mkdir(parents=True, exist_ok=True)
    index_io.save_index(index, str(path), impact_dtype=cfg["impact_dtype"], overwrite=True)
    stored = sorted(path.parent.glob(f"{cfg['name']}-*"), key=lambda p: p.stat().st_mtime)
    for old in stored[:-KEEP_INDEXES]:
        shutil.rmtree(old, ignore_errors=True)
    return index, "built", seconds


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
