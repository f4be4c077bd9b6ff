"""The ``Instrumentation`` handle every serving layer threads through.

One object bundles the three observability substrates — a
``MetricsRegistry``, an optional ``Tracer``, and the injected clock — so a
constructor signature stays one keyword: ``MicroBatchServer(...,
obs=...)``. No globals anywhere: layers receive the handle explicitly and
share time series by sharing the handle (metric registration is
idempotent by name).

The default is ``NOOP``, a shared do-nothing instance whose every method
returns immediately and whose ``enabled`` flag is False — hot loops guard
their per-item instrumentation blocks with ``if obs.enabled`` so an
uninstrumented server pays one attribute read per batch, nothing per
query. The acceptance bar (ISSUE 8) is < 5% q/s overhead with full
instrumentation and *zero* result drift: nothing in this module touches
budgets, plans, or device inputs, so instrumented results are bitwise
identical by construction (pinned in tests/test_obs.py).

Phases (``Instrumentation.phase``) name the parts of a server step on the
device trace's clock: a handle built with an ``annotate`` callable (for
example ``jax.profiler.TraceAnnotation``, passed in so that this package
imports no jax) opens ``annotate(f"repro.{name}", **args)`` around each
phase. Phases depend on the annotator, not on ``enabled``: a traced run can
name its phases without every counter and per-query trace on the hot path.
An enabled handle also times each phase into ``phase_ms``. On ``NOOP``
``phase`` returns one shared null context, so call sites need no guard.
"""

from __future__ import annotations

import contextlib

from repro.obs.catalog import help_for
from repro.obs.clock import DEFAULT_CLOCK
from repro.obs.metrics import MetricsRegistry
from repro.obs.profiler import Profiler
from repro.obs.trace import Tracer, TraceSink

__all__ = ["Instrumentation", "NoopInstrumentation", "NOOP"]

# What ``phase`` returns on a handle that neither annotates nor times:
# one reusable context, so a disabled phase allocates and formats nothing.
_NULL_PHASE = contextlib.nullcontext()


class _Phase:
    """One phase of a step: annotated, timed, or both (see ``phase``)."""

    __slots__ = ("_obs", "_name", "_args", "_ann", "t0", "t1")

    def __init__(self, obs: "Instrumentation", name: str, args: dict):
        self._obs = obs
        self._name = name
        self._args = args
        self._ann = None
        self.t0 = self.t1 = 0.0

    @property
    def ms(self) -> float:
        """Milliseconds between enter and exit (enabled handles only)."""
        return (self.t1 - self.t0) * 1e3

    def __enter__(self):
        obs = self._obs
        if obs.annotate is not None:
            self._ann = obs.annotate(f"repro.{self._name}", **self._args)
            self._ann.__enter__()
        if obs.enabled:
            self.t0 = obs.clock()
        return self

    def __exit__(self, *exc):
        obs = self._obs
        if obs.enabled:
            self.t1 = obs.clock()
            obs.observe(
                "phase_ms", self.ms,
                server=self._args.get("server", ""), phase=self._name,
            )
        if self._ann is not None:
            self._ann.__exit__(*exc)
        return False


class Instrumentation:
    """Live metrics + tracing + clock bundle (+ optional profiler)."""

    enabled = True

    def __init__(
        self,
        metrics: MetricsRegistry | None = None,
        tracer: Tracer | None = None,
        clock=DEFAULT_CLOCK,
        profiler: Profiler | None = None,
        annotate=None,
    ):
        self.metrics = metrics if metrics is not None else MetricsRegistry()
        self.tracer = tracer
        self.clock = clock
        self.profiler = profiler
        self.annotate = annotate

    @classmethod
    def make(
        cls,
        sample_rate: float = 1.0,
        trace_path: str | None = None,
        ring: int = 1024,
        clock=DEFAULT_CLOCK,
        profile: bool = False,
        annotate=None,
    ) -> "Instrumentation":
        """Convenience constructor: metrics + a tracer (+ JSONL sink).

        ``profile=True`` attaches a :class:`~repro.obs.profiler.Profiler`;
        the serving dispatch sites pick it up via ``obs.profiler`` and add
        compile tracking + the host/device/transfer time split.
        ``annotate`` names each ``phase`` in a profiler trace (see
        :meth:`phase`).
        """
        sink = TraceSink(trace_path) if trace_path else None
        obs = cls(
            MetricsRegistry(),
            Tracer(sample_rate=sample_rate, ring=ring, sink=sink),
            clock=clock,
            annotate=annotate,
        )
        if profile:
            obs.profiler = Profiler(obs)
        return obs

    # -------------------------------------------------------------- metrics
    def count(self, name: str, value: float = 1.0, **labels) -> None:
        self.metrics.counter(name, help_for(name)).inc(value, **labels)

    def gauge(self, name: str, value: float, **labels) -> None:
        self.metrics.gauge(name, help_for(name)).set(value, **labels)

    def observe(self, name: str, value: float, **labels) -> None:
        self.metrics.histogram(name, help_for(name)).observe(value, **labels)

    # --------------------------------------------------------------- phases
    def phase(self, name: str, **args):
        """Context manager around one phase of a step.

        With an annotator it opens ``annotate(f"repro.{name}", **args)``;
        on an enabled handle it also observes the phase's duration into
        ``phase_ms{server, phase}`` from the handle's clock and keeps it as
        ``t0``/``t1``/``ms`` on the returned object. A handle that does
        neither (``NOOP``) returns one shared null context.
        """
        if self.annotate is None and not self.enabled:
            return _NULL_PHASE
        return _Phase(self, name, args)

    # -------------------------------------------------------------- tracing
    def trace_begin(self, rid: int) -> None:
        if self.tracer is not None:
            self.tracer.begin(rid)

    def trace_span(
        self, rid: int, name: str, t0: float, t1: float, **attrs
    ) -> None:
        if self.tracer is not None:
            tr = self.tracer.get(rid)
            if tr is not None:
                tr.span(name, t0, t1, **attrs)

    def trace_attr(self, rid: int, **attrs) -> None:
        if self.tracer is not None:
            tr = self.tracer.get(rid)
            if tr is not None:
                tr.attrs.update(attrs)

    def trace_end(self, rid: int) -> None:
        if self.tracer is not None:
            self.tracer.end(rid)

    # ------------------------------------------------------------ lifecycle
    def snapshot(self) -> dict:
        return self.metrics.snapshot()

    def close(self) -> None:
        if self.tracer is not None:
            self.tracer.close()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()


class NoopInstrumentation(Instrumentation):
    """Shared default: every hook is a no-op, ``enabled`` is False.

    Keeps the ``clock`` attribute (servers resolve their clock through the
    handle) and a metrics registry that is never written, so generic code
    can snapshot it and get ``{}``. ``annotate`` gives a handle whose only
    effect is to name phases in a profiler trace.
    """

    enabled = False

    def __init__(self, annotate=None):
        super().__init__(MetricsRegistry(), None, DEFAULT_CLOCK, annotate=annotate)

    def count(self, name, value=1.0, **labels):
        pass

    def gauge(self, name, value, **labels):
        pass

    def observe(self, name, value, **labels):
        pass

    def trace_begin(self, rid):
        pass

    def trace_span(self, rid, name, t0, t1, **attrs):
        pass

    def trace_attr(self, rid, **attrs):
        pass

    def trace_end(self, rid):
        pass


NOOP = NoopInstrumentation()
