"""Structured span tracing with cross-process correlation ids.

A :class:`Span` is a named interval with a ``trace_id`` correlator, a
``component`` (which side of the system emitted it: "worker",
"controller", "bench"), and free-form attributes. Spans append to an
in-memory ring (for same-process assertions) and, when a sink is attached,
stream as JSONL — one JSON object per line, the shape tests and benches
read back with :func:`load_spans`.

Cross-process correlation does not need a propagation header: for the one
lifecycle that spans processes — an elastic rescale — the membership epoch
IS the shared id. The controller's actuator learns the new epoch from
``bump_epoch``; every worker adopts the same epoch from its re-register.
:func:`rescale_trace_id` turns it into the common ``trace_id``, and
:func:`rescale_timeline` stitches both sides' spans into the
phase-attributed recovery breakdown (drain -> checkpoint -> warm_compile ->
restore -> first_step) that ``bench_rescale.py`` commits as
``RESCALE_TIMELINE.json``.

One clock with the profiler: every context-managed span
(:meth:`Tracer.span`) also opens a ``jax.profiler.TraceAnnotation`` of the
same name, so that while a profiler session runs the span is on a host line
of the ``.xplane.pb``, beside the device's operations and on their clock.
This package stays stdlib-only and never imports JAX: the annotation is
looked up in ``sys.modules``, so a process that has not imported JAX (the
controller) records to the ring alone, and with no session active an
annotation is a flag check.

What JAX compiles is on the same record: inside :meth:`Tracer.compile_spans`
every program JAX traces, lowers or compiles on a thread where the tracer has
a span open becomes a span under that span (``jax_trace``, ``jax_lower``,
``xla_compile``), from ``jax.monitoring``'s events, found in ``sys.modules``
by the same rule.
"""

from __future__ import annotations

import contextlib
import json
import sys
import threading
import time
from dataclasses import dataclass, field
from typing import (Any, Callable, ContextManager, Dict, Iterable, Iterator,
                    List, Optional, TextIO, Union)

__all__ = [
    "Span",
    "Tracer",
    "get_tracer",
    "rescale_trace_id",
    "rescale_timeline",
    "load_spans",
    "RESCALE_PHASES",
]

#: The rescale lifecycle's phase vocabulary, in causal order. The e2e test
#: and the bench assert all of these appear under one rescale trace id.
#: ``preempt_drain`` is the advance-notice window (notice arrival through
#: doomed-rank shard evacuation — degenerate-but-present on rescales no
#: notice triggered, keeping the completeness gate unconditional),
#: ``replan`` is the layout search (planner argmin over candidate meshes —
#: degenerate-but-present on data-only resizes) and ``reshard`` is the
#: device_put window that moves restored state onto the new mesh layout.
RESCALE_PHASES = ("preempt_drain", "drain", "checkpoint", "replan",
                  "warm_compile", "restore", "reshard", "first_step")


def rescale_trace_id(epoch: int) -> str:
    """The shared rescale correlator: both sides observe the same membership
    epoch (bump_epoch reply on the controller, register/sync reply on the
    worker), so both stamp the same id without talking to each other."""
    return f"rescale-e{int(epoch):06d}"


@dataclass
class Span:
    """One named interval. ``start``/``end`` are epoch seconds (wall clock:
    spans from different processes must land on one timeline); a
    context-managed span measures its length on ``time.perf_counter`` and
    sets ``end = start + length``, so a step of the wall clock cannot turn
    a 0.3 ms span negative."""

    name: str
    start: float
    end: float
    trace_id: str = ""
    component: str = ""
    attrs: Dict[str, Any] = field(default_factory=dict)
    #: the span that caused this one: the enclosing :meth:`Tracer.span` of
    #: the same thread (None at the top, and for recorded intervals given
    #: none)
    parent: Optional["Span"] = field(default=None, repr=False, compare=False)
    #: cleared inside the ``with`` by a caller that finds the work was not
    #: there (the wait that met the end of its input): the span then stays
    #: out of the ring, so that it cannot pass for a short item
    keep: bool = field(default=True, repr=False, compare=False)

    @property
    def seconds(self) -> float:
        return self.end - self.start

    def to_dict(self) -> dict:
        d = {
            "kind": "span",
            "name": self.name,
            "start": round(self.start, 6),
            "end": round(self.end, 6),
            "seconds": round(self.seconds, 6),
            "trace_id": self.trace_id,
            "component": self.component,
        }
        if self.attrs:
            d["attrs"] = self.attrs
        if self.parent is not None:
            d["parent"] = self.parent.name
        return d


def profiler_annotation() -> Optional[Callable[[str], ContextManager]]:
    """``jax.profiler.TraceAnnotation`` where this process has imported JAX,
    else None. Never imports JAX itself."""
    jax = sys.modules.get("jax")
    return getattr(getattr(jax, "profiler", None), "TraceAnnotation", None)


#: JAX's compile events, by the span each becomes (:meth:`Tracer.compile_spans`)
_COMPILE_SPANS = {
    "/jax/core/compile/jaxpr_trace_duration": "jax_trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "jax_lower",
    "/jax/core/compile/backend_compile_duration": "xla_compile",
}

#: the persistent compile cache's answer, fired inside a backend compile
_CACHE_ANSWERS = {"/jax/compilation_cache/cache_hits": "hit",
                  "/jax/compilation_cache/cache_misses": "miss"}


class _CompileListener:
    """``jax.monitoring``'s compile events as one tracer's spans.

    JAX stamps a compile event twice, on ``time.time()`` (the clock of
    ``Span.start``): a scalar at its start and a time span at its end. An
    event that ends on a thread where the tracer has a span open is recorded
    under that span, with ``fun`` (JAX's ``fun_name``) and, on
    ``xla_compile``, ``cache``: ``hit`` or ``miss`` where the persistent
    cache answered on that thread inside the interval, else ``off``. An
    event inside another on the same thread (a jitted function called while
    a trace runs) is already in the outer one's interval and is not recorded
    again, so a thread's compile spans never overlap and their seconds add.
    """

    def __init__(self, tracer: "Tracer"):
        self.tracer = tracer
        self._local = threading.local()  # depth of open events, cache answer

    def register(self, monitoring) -> None:
        monitoring.register_scalar_listener(self.on_start)
        monitoring.register_event_listener(self.on_answer)
        monitoring.register_event_time_span_listener(self.on_end)

    def unregister(self, monitoring) -> None:
        monitoring.unregister_scalar_listener(self.on_start)
        monitoring.unregister_event_listener(self.on_answer)
        monitoring.unregister_event_time_span_listener(self.on_end)

    def on_start(self, event: str, value: float, **kwargs: Any) -> None:
        name = _COMPILE_SPANS.get(event)
        if name is None:
            return
        local = self._local
        local.depth = getattr(local, "depth", 0) + 1
        if name == "xla_compile":
            local.cache = "off"

    def on_answer(self, event: str, **kwargs: Any) -> None:
        answer = _CACHE_ANSWERS.get(event)
        if answer is not None:
            self._local.cache = answer

    def on_end(self, event: str, start: float, end: float,
               fun_name: str = "", **kwargs: Any) -> None:
        name = _COMPILE_SPANS.get(event)
        if name is None:
            return
        local = self._local
        # an end whose start came before the listener was there counts as
        # outermost: the floor is 0
        local.depth = max(getattr(local, "depth", 0) - 1, 0)
        parent = self.tracer.current()
        if local.depth or parent is None:
            return
        attrs = {"fun": fun_name}
        if name == "xla_compile":
            attrs["cache"] = getattr(local, "cache", "off")
        self.tracer.record(name, start, end, parent=parent, **attrs)


class _OpenSpan:
    """The context manager behind :meth:`Tracer.span`."""

    __slots__ = ("tracer", "span", "mirror", "t0")

    def __init__(self, tracer: "Tracer", span: Span):
        self.tracer = tracer
        self.span = span

    def __enter__(self) -> Span:
        tracer, span = self.tracer, self.span
        stack = tracer._open_spans()
        if stack:
            span.parent = stack[-1]
        stack.append(span)
        annotation = tracer.annotation or profiler_annotation()
        self.mirror = annotation(span.name) if annotation else None
        if self.mirror is not None:
            self.mirror.__enter__()
        span.start = time.time()
        self.t0 = time.perf_counter()
        return span

    def __exit__(self, exc_type, exc, tb) -> bool:
        seconds = time.perf_counter() - self.t0
        span = self.span
        if self.mirror is not None:
            self.mirror.__exit__(exc_type, exc, tb)
        self.tracer._open_spans().pop()
        if exc_type is not None:  # a failed phase is still a phase
            span.attrs["error"] = exc_type.__name__
        span.end = span.start + seconds
        if span.keep:
            self.tracer._append(span)
        return False


class Tracer:
    """Span recorder: bounded in-memory ring + optional JSONL sink.

    Thread-safe (worker main loop, pump thread, warm-compile thread and the
    scrape handler all record concurrently); the critical section is a list
    append — sink writes happen outside the lock.
    """

    def __init__(self, component: str = "", sink: Optional[TextIO] = None,
                 window: int = 50_000,
                 annotation: Optional[Callable[[str], ContextManager]] = None):
        self.component = component
        self.sink = sink
        self.window = window
        #: what :meth:`span` mirrors through: ``name -> context manager``.
        #: None (every caller but the tests) is :func:`profiler_annotation`.
        self.annotation = annotation
        self.spans: List[Span] = []
        self._lock = threading.Lock()
        self._local = threading.local()
        #: JAX's compile events while a :meth:`compile_spans` is open, and
        #: how many are open
        self._compile_listener: Optional[_CompileListener] = None
        self._compile_users = 0

    def _open_spans(self) -> List[Span]:
        """This thread's stack of open context-managed spans."""
        try:
            return self._local.stack
        except AttributeError:
            stack = self._local.stack = []
            return stack

    def current(self) -> Optional[Span]:
        """The calling thread's innermost open :meth:`span`, or None."""
        stack = getattr(self._local, "stack", None)
        return stack[-1] if stack else None

    # -- recording -------------------------------------------------------------

    def record(self, name: str, start: float, end: float, trace_id: str = "",
               component: str = "", parent: Optional[Span] = None,
               **attrs: Any) -> Span:
        """Record an interval measured by the caller (after-the-fact spans:
        the drain interval is only attributable once the new epoch is
        known). Zero-length intervals are clamped to a microsecond so phase
        durations are strictly positive — "this phase happened" must never
        round down to "it took no time". A microsecond, not a nanosecond:
        these are epoch-seconds floats (~2e9), where double precision eats
        anything under ~2.4e-7 and a 1e-9 clamp silently rounds back to
        zero length.

        A recorded interval is not mirrored into the profiler's trace: it is
        already over when it is known, and an annotation can only be opened
        now. Its parent is ``parent`` (``current()`` gives the calling
        thread's innermost open span), None by default."""
        return self._append(Span(
            name=name, start=start, end=end, trace_id=trace_id,
            component=component or self.component, attrs=dict(attrs),
            parent=parent))

    def _append(self, span: Span) -> Span:
        if span.end <= span.start:
            span.end = span.start + 1e-6
        sink = self.sink
        with self._lock:
            self.spans.append(span)
            if len(self.spans) > self.window:
                del self.spans[: len(self.spans) - self.window]
        if sink is not None:
            try:
                sink.write(json.dumps(span.to_dict()) + "\n")
                sink.flush()
            except (OSError, ValueError):  # edl: noqa[EDL005] a torn/closed sink must not kill the training loop; the in-memory ring still has the span
                pass
        return span

    def span(self, name: str, trace_id: str = "", component: str = "",
             **attrs: Any) -> ContextManager[Span]:
        """Context-managed span; records on exit (also on exception, with
        ``error`` attached — a failed phase is still a phase). The ``with``
        yields the :class:`Span`, so the caller can add what it learns
        inside (``span.attrs["task"] = ...``) and read ``span.seconds``
        afterwards. ``parent`` is the enclosing span of the same thread, and
        the span is mirrored into the profiler's trace (module docstring):
        this is the one place a program span opens an annotation."""
        return _OpenSpan(self, Span(
            name=name, start=0.0, end=0.0, trace_id=trace_id,
            component=component or self.component, attrs=attrs))

    def event(self, name: str, trace_id: str = "", **attrs: Any) -> Span:
        """Point-in-time marker (epoch observation, decision taken)."""
        now = time.time()
        return self.record(name, now, now, trace_id=trace_id, **attrs)

    @contextlib.contextmanager
    def compile_spans(self) -> Iterator[None]:
        """While the ``with`` lasts, every program JAX traces, lowers or
        compiles on a thread where this tracer has a span open is recorded
        as a span under that span: ``jax_trace``, ``jax_lower`` or
        ``xla_compile`` (:class:`_CompileListener` says which events, and
        how nested ones fold into the outermost). A compile on a thread where
        this tracer has nothing open is not recorded. The outermost ``with``
        on a tracer registers the listeners with ``jax.monitoring`` and
        removes them as it ends, also on an exception; in a process without
        JAX it does nothing. The listeners run on compiles only: a step that
        compiles nothing pays nothing."""
        monitoring = getattr(sys.modules.get("jax"), "monitoring", None)
        if monitoring is None:
            yield
            return
        with self._lock:
            if self._compile_listener is None:
                self._compile_listener = _CompileListener(self)
                self._compile_listener.register(monitoring)
            self._compile_users += 1
        try:
            yield
        finally:
            with self._lock:
                self._compile_users -= 1
                if not self._compile_users:
                    self._compile_listener.unregister(monitoring)
                    self._compile_listener = None

    # -- reading ---------------------------------------------------------------

    def find(self, trace_id: Optional[str] = None,
             name: Optional[str] = None) -> List[Span]:
        with self._lock:
            spans = list(self.spans)
        return [s for s in spans
                if (trace_id is None or s.trace_id == trace_id)
                and (name is None or s.name == name)]

    def to_jsonl(self) -> str:
        with self._lock:
            spans = list(self.spans)
        return "".join(json.dumps(s.to_dict()) + "\n" for s in spans)


#: Process-wide default tracer, mirroring the metrics registry's role: every
#: layer records into one stream so a single export carries the whole story.
_TRACER = Tracer()


def get_tracer() -> Tracer:
    return _TRACER


def set_tracer(tracer: Tracer) -> Tracer:
    global _TRACER
    prev = _TRACER
    _TRACER = tracer
    return prev


# -- cross-process stitching ---------------------------------------------------


def load_spans(path: str) -> List[dict]:
    """Read a JSONL event stream, keeping span records only. Tolerates
    interleaved non-span lines (profiler records, collector samples) — in a
    pod all streams may share one stdout."""
    out: List[dict] = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            try:
                rec = json.loads(line)
            except ValueError:
                continue  # foreign line in a shared stream
            if isinstance(rec, dict) and rec.get("kind") == "span":
                out.append(rec)
    return out


def _as_dict(span: Union[Span, dict]) -> dict:
    return span.to_dict() if isinstance(span, Span) else span


def rescale_timeline(spans: Iterable[Union[Span, dict]],
                     trace_id: Optional[str] = None) -> Dict[str, dict]:
    """Stitch spans (from any number of processes) into per-trace phase
    breakdowns.

    Returns ``{trace_id: {"phases": {name: {...}}, "components": [...],
    "wall_seconds": ..., "span_count": n}}``. A phase recorded more than
    once under one trace (both sides timing "restore") keeps the longest
    observation — its ``attrs`` ride along — and counts the repeats. ``wall_seconds`` is last end minus
    first start across the whole trace — the number recovery budgets are
    written against; per-phase seconds attribute it (phases may overlap:
    warm_compile runs concurrent with restore by design, so the sum of
    phases can exceed the wall).

    Every recorded phase appears in ``phases`` — nothing is filtered against
    ``RESCALE_PHASES`` here — and names outside that vocabulary are
    additionally listed under ``unknown_phases`` so a misspelled or
    unregistered phase surfaces in the timeline instead of silently failing
    downstream completeness gates (which iterate ``RESCALE_PHASES`` and
    would otherwise never look at the stray name).
    """
    by_trace: Dict[str, List[dict]] = {}
    for s in spans:
        d = _as_dict(s)
        tid = d.get("trace_id", "")
        if not tid or (trace_id is not None and tid != trace_id):
            continue
        by_trace.setdefault(tid, []).append(d)
    out: Dict[str, dict] = {}
    for tid, recs in sorted(by_trace.items()):
        phases: Dict[str, dict] = {}
        for d in sorted(recs, key=lambda r: (r.get("start", 0.0), r.get("name", ""))):
            name = d.get("name", "")
            seconds = float(d.get("seconds",
                                  d.get("end", 0.0) - d.get("start", 0.0)))
            cur = phases.get(name)
            if cur is None:
                phases[name] = {
                    "seconds": seconds,
                    "start": d.get("start", 0.0),
                    "end": d.get("end", 0.0),
                    "component": d.get("component", ""),
                    "attrs": dict(d.get("attrs") or {}),
                    "count": 1,
                }
            else:
                cur["count"] += 1
                if seconds > cur["seconds"]:
                    cur.update(seconds=seconds, start=d.get("start", 0.0),
                               end=d.get("end", 0.0),
                               component=d.get("component", ""),
                               attrs=dict(d.get("attrs") or {}))
        starts = [d.get("start", 0.0) for d in recs]
        ends = [d.get("end", 0.0) for d in recs]
        out[tid] = {
            "phases": phases,
            "unknown_phases": sorted(
                n for n in phases if n not in RESCALE_PHASES),
            "components": sorted({d.get("component", "") for d in recs} - {""}),
            "wall_seconds": (max(ends) - min(starts)) if recs else 0.0,
            "span_count": len(recs),
        }
    return out
