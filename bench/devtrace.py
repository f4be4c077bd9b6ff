"""From a ``jax.profiler`` trace to device numbers.

``bench/phasetrace.load_events`` flattens an ``.xplane.pb`` into plain
events; ``reduce`` turns them into the numbers the per-layer metrics read:

* the traced window: the host span ``bench.window`` the loop writes;
* busy time: the union of the device's operations (the ``XLA Ops`` line of
  each ``/device:`` plane) clipped to the window, averaged over devices;
* per-program device time and dispatch count: the ``XLA Modules`` events
  whose name holds the jitted function's name (``traversal`` sums them over
  a layout's programs);
* the device operations that took most time;
* the idle gaps (the window less the busy union), each named by the
  ``bench.*`` host span that overlaps it most, the innermost on a tie.
"""

from __future__ import annotations

import bisect
import dataclasses
import json
from collections import defaultdict
from pathlib import Path

__all__ = ["Event", "read_events", "reduce", "traversal"]

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PREFIX = "bench."
WINDOW = "bench.window"
OP_NAME_CHARS = 100


@dataclasses.dataclass(frozen=True)
class Event:
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


def read_events(path: Path) -> list[Event]:
    """Events kept as JSON rows ``[plane, line, name, start_ns, dur_ns]``."""
    return [Event(*row) for row in json.loads(Path(path).read_text())]


def _union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    merged: list[list[float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def _clip(e: Event, lo: float, hi: float) -> tuple[float, float] | None:
    a, b = max(e.start_ns, lo), min(e.end_ns, hi)
    return (a, b) if b > a else None


def reduce(events: list[Event], programs: tuple[str, ...] = ()) -> dict | None:
    """Device numbers of the traced window; None when there is nothing to read."""
    wins = [e for e in events if e.name == WINDOW]
    devices = sorted({e.plane for e in events if e.plane.startswith("/device:")})
    if not wins or not devices:
        return None
    win = max(wins, key=lambda e: e.dur_ns)
    lo, hi = win.start_ns, win.end_ns

    busy_ns = 0.0
    unions = []
    op_time: dict[str, float] = defaultdict(float)
    prog_ns = {p: 0.0 for p in programs}
    prog_n = {p: 0 for p in programs}
    for plane in devices:
        ops = []
        for e in events:
            if e.plane != plane:
                continue
            iv = _clip(e, lo, hi)
            if e.line == OPS_LINE:
                if iv:
                    ops.append(iv)
                    op_time[e.name] += (iv[1] - iv[0]) * 1e-9
            elif e.line == MODULES_LINE:
                for p in programs:
                    if p in e.name and iv:
                        prog_ns[p] += iv[1] - iv[0]
                        prog_n[p] += 1
        u = _union(ops)
        unions.append(u)
        busy_ns += sum(b - a for a, b in u)
    n_dev = len(devices)

    host = sorted(
        (e for e in events if e.name.startswith(HOST_PREFIX) and e.name != WINDOW),
        key=lambda e: e.start_ns,
    )
    starts = [h.start_ns for h in host]
    longest = max((h.dur_ns for h in host), default=0.0)
    gaps = []
    for u in unions[:1]:  # gaps are named on the first device
        edges = [lo] + [x for iv in u for x in iv] + [hi]
        for a, b in zip(edges[::2], edges[1::2]):
            if b <= a:
                continue
            best, label = (0.0, 0.0), "other"
            j = bisect.bisect_left(starts, b) - 1
            while j >= 0 and starts[j] >= a - longest:
                h = host[j]
                ov = min(b, h.end_ns) - max(a, h.start_ns)
                if ov > 0 and (ov, -h.dur_ns) > best:
                    best, label = (ov, -h.dur_ns), h.name[len(HOST_PREFIX):]
                j -= 1
            gaps.append((label, (b - a) * 1e-9))
    idle_by_phase: dict[str, float] = defaultdict(float)
    for label, s in gaps:
        idle_by_phase[label] += s

    window_s = (hi - lo) * 1e-9
    return {
        "window_s": window_s,
        "busy_s": busy_ns * 1e-9 / n_dev,
        "devices": n_dev,
        "programs": {p: {"seconds": prog_ns[p] * 1e-9 / n_dev, "dispatches": prog_n[p] // n_dev} for p in programs},
        "device_ops": sorted(op_time.items(), key=lambda kv: -kv[1])[:10],
        "idle_gaps": sorted(gaps, key=lambda g: -g[1])[:10],
        "idle_by_phase": dict(sorted(idle_by_phase.items(), key=lambda kv: -kv[1])),
        "window_ns": (lo, hi),
    }


def traversal(red: dict | None) -> tuple[float, int] | None:
    """(device seconds, dispatches) of the traversal in the traced window, or None.

    ``red`` was reduced over a layout's ``PROGRAMS``: their seconds are
    summed, and the first, dispatched once a server step, counts the
    dispatches.
    """
    progs = list((red or {}).get("programs", {}).values())
    if not progs or not progs[0]["dispatches"]:
        return None
    seconds = sum(p["seconds"] for p in progs)
    return (seconds, progs[0]["dispatches"]) if seconds > 0 else None
