"""Program phases and device scopes in a ``jax.profiler`` trace.

``devtrace`` times the device per jitted program and names idle gaps by the
harness's own ``bench.*`` spans. The program names its own work too:

* ``InflightServer.step``, given a handle with an annotator, writes the host
  spans ``repro.admit``, ``repro.launch`` (with ``repro.upload`` and
  ``repro.dispatch`` in it), ``repro.plan_ahead``, ``repro.fetch`` and
  ``repro.retire``, and ``repro.plan`` around every plan inside ``admit`` or
  ``plan_ahead``;
* the traversal's device ops carry the named scopes ``gather``,
  ``accumulate`` and ``topk`` in their HLO ``op_name``. A TPU trace names
  each op by its HLO instruction and carries no ``op_name``, so
  ``hlo_scopes`` maps instruction names to scopes from the compiled
  program's HLO text (``traverse_hlo``), read once in set-up.

This module reads both. ``load_events`` keeps the device events, the
``bench.*`` and ``repro.*`` host spans, and each op's scope. ``reduce`` keeps devtrace's window,
busy time and per-program numbers, prefixes each of ``device_ops`` with its
scope, and, where the trace holds scopes and phases, adds or replaces:

* ``scopes``: device seconds per scope over the traversal program's leaf ops
  (control flow such as a ``while`` that encloses other ops is left out,
  since its body's ops are counted themselves; an op of no scope counts
  under ``other``). A fusion counts under its root's scope;
* ``idle_gaps`` and ``idle_by_phase`` (in place of devtrace's): the idle
  time split at host-span edges, each piece named by the innermost span over
  it, ``bench.*`` or ``repro.*``;
* ``idle_by_owner``: idle seconds by the outermost ``repro.*`` phase over
  them, so a ``plan`` counts under the phase that planned;
* ``step_idle_s`` and ``step_idle_named_s``: idle seconds inside
  ``bench.step``, and the part of them under a ``repro.*`` phase.

``per_dispatch`` turns these into milliseconds per traversal dispatch
(``devtrace.traversal``).
"""

from __future__ import annotations

import bisect
import dataclasses
import glob
import json
import re
from collections import defaultdict
from pathlib import Path

from bench import devtrace

__all__ = [
    "Event",
    "hlo_scopes",
    "load_events",
    "op_scope",
    "per_dispatch",
    "read_events",
    "reduce",
    "traverse_hlo",
    "write_events",
]

SCOPES = ("gather", "accumulate", "topk")
STEP_PHASES = ("admit", "launch", "plan_ahead", "fetch", "retire")
PROGRAM_PREFIX = "repro."
HOST_PREFIXES = (devtrace.HOST_PREFIX, PROGRAM_PREFIX)
STEP = devtrace.HOST_PREFIX + "step"
_HLO_INSTR = re.compile(r"^\s*(ROOT\s+)?%?([\w.\-]+) = (.*)$")
_HLO_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+) .*\{\s*$")
_HLO_OP_NAME = re.compile(r'\bop_name="([^"]*)"')
_HLO_CALLS = re.compile(r"\b(?:calls|to_apply)=%?([\w.\-]+)")


@dataclasses.dataclass(frozen=True)
class Event(devtrace.Event):
    scope: str = ""  # device ops: the op's named scope, "" if none


def op_scope(op_name: str) -> str:
    """The innermost of ``SCOPES`` in an HLO ``op_name`` path, or ""."""
    for part in reversed(op_name.split("/")):
        if part in SCOPES:
            return part
    return ""


def hlo_scopes(hlo_text: str) -> dict[str, str]:
    """Scope of every instruction of a compiled HLO module that has one.

    An instruction takes the scope of its own ``op_name``. One without (a
    fusion, or an op a compiler pass rebuilt, as XLA rebuilds a batched
    scatter with no metadata) takes the scope of the root of the
    computation it calls: a fusion its fused root's, a scatter or a reduce
    its combiner's. So a fusion counts under its root's scope.
    """
    instrs: dict[str, tuple[str, list[str]]] = {}
    roots: dict[str, str] = {}
    comp = None
    for line in hlo_text.splitlines():
        if m := _HLO_INSTR.match(line):
            root, name, rest = m.groups()
            op = _HLO_OP_NAME.search(rest)
            instrs[name] = (op.group(1) if op else "", _HLO_CALLS.findall(rest))
            if root and comp:
                roots[comp] = name
        elif m := _HLO_COMPUTATION.match(line):
            comp = m.group(1)

    memo: dict[str, str] = {}

    def scope(name: str) -> str:
        if name not in memo:
            memo[name] = ""  # a cycle ends here
            op_name, calls = instrs.get(name, ("", []))
            found = op_scope(op_name)
            for c in calls:
                found = found or (scope(roots[c]) if c in roots else "")
            memo[name] = found
        return memo[name]

    return {name: sc for name in instrs if (sc := scope(name))}


def traverse_hlo(server) -> str:
    """Compiled HLO text of the traversal program at an in-flight server's shape.

    The same arguments as ``InflightServer.step`` gives it, so the compile
    (a hit in the persistent cache) names its instructions as the served
    program's.
    """
    import jax
    import jax.numpy as jnp

    from repro.core.range_daat import batched_traverse_resume

    eng = server.engine
    carry = jax.tree_util.tree_map(jnp.asarray, server.carry)
    lowered = batched_traverse_resume.lower(
        eng.dix, *server.buffers.front.device_arrays(), carry,
        s_pad=eng.s_pad, k=eng.k, quantum=server.quantum, impl=eng.impl, docs_format=eng.docs_format,
    )
    return lowered.compile().as_text()


def _instruction(op_event_name: str) -> str:
    """``fusion.72`` of ``%fusion.72 = s32[...] fusion(...)``."""
    return op_event_name.split(" ", 1)[0].lstrip("%")


def load_events(trace_dir: str, op_scopes: dict[str, str] | None = None) -> list[Event]:
    """Device events with their scopes, and the ``bench.*`` and ``repro.*`` host spans.

    ``op_scopes`` (from ``hlo_scopes``) gives each device op its scope.
    """
    op_scopes = op_scopes or {}
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True))
    if not paths:
        return []
    pd = ProfileData.from_file(paths[-1])
    out = []
    for plane in pd.planes:
        device = plane.name.startswith("/device:")
        for line in plane.lines:
            if device and line.name not in (devtrace.OPS_LINE, devtrace.MODULES_LINE):
                continue
            ops = device and line.name == devtrace.OPS_LINE
            for ev in line.events:
                if not (device or ev.name.startswith(HOST_PREFIXES)):
                    continue
                name, scope = ev.name, ""
                if ops:
                    name = name[: devtrace.OP_NAME_CHARS]
                    scope = op_scopes.get(_instruction(name), "")
                out.append(Event(plane.name, line.name, name, ev.start_ns, ev.duration_ns, scope))
    return out


def read_events(path: Path) -> list[Event]:
    """Events kept as JSON rows ``[plane, line, name, start_ns, dur_ns(, scope)]``."""
    return [Event(*row) for row in json.loads(Path(path).read_text())]


def write_events(events: list[Event], path: Path) -> None:
    rows = [[e.plane, e.line, e.name, e.start_ns, e.dur_ns, getattr(e, "scope", "")] for e in events]
    Path(path).write_text(json.dumps(rows))


def _leaf_ops(ops: list[Event]) -> list[Event]:
    """Ops that enclose no other op (a ``while`` encloses its body's ops)."""
    ops = sorted(ops, key=lambda e: (e.start_ns, -e.dur_ns))
    return [e for e, nxt in zip(ops, ops[1:] + [None]) if nxt is None or nxt.end_ns > e.end_ns]


def _scopes(events, devices, programs, lo, hi) -> tuple[dict, dict]:
    """Device seconds per scope (leaf ops of the programs) and per scoped op name."""
    scope_s: dict[str, float] = defaultdict(float)
    op_s: dict[str, float] = defaultdict(float)
    for plane in devices:
        mine = [e for e in events if e.plane == plane]
        mods = sorted(
            (e.start_ns, e.end_ns)
            for e in mine
            if e.line == devtrace.MODULES_LINE and any(p in e.name for p in programs)
        )
        starts = [a for a, _ in mods]
        ops = [e for e in mine if e.line == devtrace.OPS_LINE]
        for e in ops:
            iv = devtrace._clip(e, lo, hi)
            if iv:
                op_s[f"{e.scope}/{e.name}" if e.scope else e.name] += (iv[1] - iv[0]) * 1e-9
        for e in _leaf_ops(ops):
            iv = devtrace._clip(e, lo, hi)
            j = bisect.bisect_right(starts, e.start_ns) - 1
            if iv and j >= 0 and e.end_ns <= mods[j][1]:
                scope_s[e.scope or "other"] += (iv[1] - iv[0]) * 1e-9
    n = len(devices)
    return {k: v / n for k, v in scope_s.items()}, op_s


def _idle_pieces(events, first_device, lo, hi):
    """Idle intervals of the first device, split at host-span edges.

    Yields ``(seconds, innermost span name, outermost repro.* name or None,
    inside bench.step)`` for every piece.
    """
    ops = [iv for e in events if e.plane == first_device and e.line == devtrace.OPS_LINE
           if (iv := devtrace._clip(e, lo, hi))]
    busy = devtrace._union(ops)
    host = sorted(
        (e for e in events if e.name.startswith(HOST_PREFIXES) and e.name != devtrace.WINDOW),
        key=lambda e: e.start_ns,
    )
    starts = [h.start_ns for h in host]
    longest = max((h.dur_ns for h in host), default=0.0)
    edges = [lo] + [x for iv in busy for x in iv] + [hi]
    for a, b in zip(edges[::2], edges[1::2]):
        if b <= a:
            continue
        over = []
        j = bisect.bisect_left(starts, b) - 1
        while j >= 0 and starts[j] >= a - longest:
            if host[j].end_ns > a:
                over.append(host[j])
            j -= 1
        cuts = sorted({a, b} | {x for h in over for x in (h.start_ns, h.end_ns) if a < x < b})
        for x, y in zip(cuts, cuts[1:]):
            cover = [h for h in over if h.start_ns <= x and h.end_ns >= y]
            inner = min(cover, key=lambda h: h.dur_ns).name.split(".", 1)[1] if cover else "other"
            prog = [h for h in cover if h.name.startswith(PROGRAM_PREFIX)]
            owner = max(prog, key=lambda h: h.dur_ns).name[len(PROGRAM_PREFIX):] if prog else None
            yield (y - x) * 1e-9, inner, owner, any(h.name == STEP for h in cover)


def reduce(events: list, programs: tuple[str, ...]) -> dict | None:
    """devtrace's numbers with phases and scopes added; None when there is nothing to read."""
    red = devtrace.reduce(events, programs)
    if red is None:
        return None
    lo, hi = red["window_ns"]
    devices = sorted({e.plane for e in events if e.plane.startswith("/device:")})
    scopes, op_s = _scopes(events, devices, programs, lo, hi)
    if any(k != "other" for k in scopes):
        red["scopes"] = scopes
        red["device_ops"] = sorted(op_s.items(), key=lambda kv: -kv[1])[:10]
    pieces = list(_idle_pieces(events, devices[0], lo, hi))
    if any(owner for _, _, owner, _ in pieces):
        by_phase: dict[str, float] = defaultdict(float)
        by_owner: dict[str, float] = defaultdict(float)
        for s, inner, owner, _ in pieces:
            by_phase[inner] += s
            if owner:
                by_owner[owner] += s
        red["idle_gaps"] = sorted(((inner, s) for s, inner, _, _ in pieces), key=lambda g: -g[1])[:10]
        red["idle_by_phase"] = dict(sorted(by_phase.items(), key=lambda kv: -kv[1]))
        red["idle_by_owner"] = dict(sorted(by_owner.items(), key=lambda kv: -kv[1]))
        red["step_idle_s"] = sum(s for s, _, _, step in pieces if step)
        red["step_idle_named_s"] = sum(s for s, _, owner, step in pieces if step and owner)
    return red


def per_dispatch(red: dict | None) -> dict[str, float]:
    """Milliseconds per traversal dispatch: ``<scope>_device_ms`` and ``<phase>_idle_ms``.

    Empty where the trace holds no scoped op and no ``repro.*`` phase, as a
    program without them gives.
    """
    trav = devtrace.traversal(red)
    if trav is None:
        return {}
    n = trav[1]
    out = {}
    if "scopes" in red:
        out.update({f"{s}_device_ms": 1e3 * red["scopes"].get(s, 0.0) / n for s in SCOPES})
    if "idle_by_owner" in red:
        out.update({f"{p}_idle_ms": 1e3 * red["idle_by_owner"].get(p, 0.0) / n for p in STEP_PHASES})
    return out
