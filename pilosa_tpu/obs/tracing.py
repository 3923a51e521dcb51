"""Tracing: one span primitive (``start_span``), a vendor-neutral
Tracer/Span with a global singleton, and trace-id propagation.

Reference: tracing/tracing.go (Tracer :32, Span :45, GlobalTracer :23,
StartSpanFromContext, InjectHTTPHeaders/ExtractHTTPHeaders for
cross-node propagation). SimpleTracer records spans in memory; the OTLP
exporter (obs/otlp.py) implements the same two-method interface.
"""

from __future__ import annotations

import contextvars
import itertools
import os
import random
import threading
import time
from dataclasses import dataclass, field
from typing import Protocol

from pilosa_tpu.obs import profile as _profile

TRACE_HEADER = "X-Pilosa-Trace-Id"

#: the active trace correlation id, carried across node boundaries via
#: TRACE_HEADER (reference InjectHTTPHeaders/ExtractHTTPHeaders,
#: tracing.go:37-49 + the http client's span injection).
_current_trace: contextvars.ContextVar[str | None] = \
    contextvars.ContextVar("pilosa_trace", default=None)
_trace_seq = itertools.count(1)
#: "q" + pid-hex: the letter keeps an id from reading as a number
#: ("69e-3" is a float to the profiler's stat parser, which would file a
#: span's ``trace_id`` as 0.069).
_trace_prefix = f"q{os.getpid():x}"


def current_trace_id() -> str | None:
    return _current_trace.get()


def new_trace_id() -> str:
    """Mint a fresh trace id (the scheme spans use: q + pid-hex + seq)."""
    return f"{_trace_prefix}-{next(_trace_seq)}"


def set_current_trace(trace_id: str | None):
    """Returns a token for contextvars reset."""
    return _current_trace.set(trace_id)


def reset_current_trace(token) -> None:
    _current_trace.reset(token)


def inject_http_headers(headers: dict) -> dict:
    """Attach the active trace id to outgoing node-to-node requests."""
    tid = _current_trace.get()
    if tid:
        headers[TRACE_HEADER] = tid
    return headers


def extract_http_headers(headers) -> str | None:
    """Read a propagated trace id from incoming request headers."""
    return headers.get(TRACE_HEADER)


class Span(Protocol):
    def finish(self) -> None: ...
    def set_tag(self, key: str, value) -> None: ...


class Tracer(Protocol):
    def start_span(self, operation: str, parent_id: str | None = None) -> Span: ...


class _NopSpan:
    def finish(self) -> None:
        pass

    def set_tag(self, key, value) -> None:
        pass


class NopTracer:
    """Reference NopTracer (tracing.go:52)."""

    def start_span(self, operation: str, parent_id: str | None = None):
        return _NopSpan()


@dataclass
class RecordedSpan:
    operation: str
    start: float
    parent_id: str | None = None
    end: float | None = None
    tags: dict = field(default_factory=dict)
    span_id: str = ""

    def finish(self) -> None:
        self.end = time.perf_counter()

    def set_tag(self, key, value) -> None:
        self.tags[key] = value

    @property
    def duration(self) -> float | None:
        return None if self.end is None else self.end - self.start


class SimpleTracer:
    """In-memory recording tracer (test + debugging backend)."""

    def __init__(self, max_spans: int = 10_000):
        self.spans: list[RecordedSpan] = []
        self.max_spans = max_spans
        self._lock = threading.Lock()
        self._next = 0

    def start_span(self, operation: str, parent_id: str | None = None):
        span = RecordedSpan(operation=operation, start=time.perf_counter(),
                            parent_id=parent_id)
        with self._lock:
            self._next += 1
            span.span_id = str(self._next)
            if len(self.spans) < self.max_spans:
                self.spans.append(span)
        return span


_global: Tracer = NopTracer()


def set_tracer(t: Tracer) -> None:
    global _global
    _global = t


def get_tracer() -> Tracer:
    return _global


#: the four counters a span name gets in the registry, in ledger order.
SPAN_FIELDS = ("count", "wallSeconds", "cpuSeconds", "selfCpuSeconds")

#: The thread-CPU clock is read for ONE span tree in this many, drawn at
#: the tree's outermost span, and the counters hold that tree's CPU times
#: this number: an estimate of the whole. On the chip's host a read of
#: that clock is a 5.9 us system call into a sandboxed kernel that ticks
#: every 10 ms, against 0.09 us for the wall clock: four fifths of a
#: span's cost, and 10 % of ``qps`` when every request paid it (PERF.md,
#: PR 28). The kernel's clock being a 100 Hz sampler itself, sampling
#: requests costs noise, not truth. Wall time is read on every span.
CPU_SAMPLE_EVERY = 16
_draw = random.Random().random

_tls = threading.local()
_annotation_cls = None


class LayerSpan:
    """One timed interval on one thread: THE span primitive. Every layer
    boundary of the served read path and of the residency pipeline opens
    one (``with start_span(name, stats=...)``) and three readers see it:

    * the device trace: a ``jax.profiler.TraceAnnotation`` of the same
      name, carrying the request's ``trace_id``, lies on the host plane
      of the ``.xplane.pb`` on the device trace's clock whenever a
      profiler session is open (a flag check when none is);
    * the counters: each span adds its count, inclusive wall seconds,
      inclusive thread-CPU seconds and SELF thread-CPU seconds (its own
      less its children's; both estimated from one tree in
      CPU_SAMPLE_EVERY) to the ledger of the outermost span open on
      its thread (found through ``_tls.top``, the innermost open span),
      which folds it into its registry as
      ``span.<name>.count|wallSeconds|cpuSeconds|selfCpuSeconds`` in one
      lock acquisition when it closes; and to the active QueryProfile
      (``spans`` in ``?profile=true`` and ``/debug/queries``);
    * the global Tracer, with its parent's id, so ``--trace-endpoint``
      exports the whole tree of a request.

    Wall beside thread-CPU: under one interpreter lock a span's wall time
    is mostly the wait for the lock; self CPU says where the interpreter
    worked, wall less CPU where a thread waited."""

    __slots__ = ("name", "trace_id", "parent", "stats", "profiles", "wall",
                 "end", "_parent_id", "_rec", "_ann", "_token", "_t0", "_c0",
                 "_child_cpu", "_root", "_ledger", "_cpu_weight")

    def __init__(self, name: str, parent_id: str | None, stats, profiles):
        self.name = name
        self.stats = stats
        self.profiles = profiles
        self._parent_id = parent_id
        self._rec = None

    def set_tag(self, key: str, value) -> None:
        if self._rec is not None:
            self._rec.set_tag(key, value)

    def join_trace(self, trace_id: str) -> None:  # analysis: ignore[contextvar-hygiene]
        """Adopt a propagated trace id learned after the span opened (the
        HTTP handler reads the header inside ``http.request``)."""
        # -- the span's first token is reset by __exit__, which restores
        # the value from before the span whatever was set since.
        self.trace_id = trace_id
        token = _current_trace.set(trace_id)
        if self._token is None:
            self._token = token
        if self._ann is not None:
            self._ann.set_metadata(trace_id=trace_id)
        self.set_tag("trace.id", trace_id)

    def __enter__(self) -> "LayerSpan":  # analysis: ignore[contextvar-hygiene]
        # -- the token is reset by __exit__: ``with`` is the finally.
        parent = self.parent = getattr(_tls, "top", None)
        _tls.top = self
        tid = _current_trace.get()
        self._token = None
        if tid is None:
            # A span the coalescer's flusher runs for a batch has no
            # request context of its own: it adopts the first entry's.
            tid = next((p.trace_id for p in self.profiles or ()
                        if p is not None), None) or new_trace_id()
            self._token = _current_trace.set(tid)
        self.trace_id = tid
        if parent is not None:
            # The outermost span names the registry; children inherit it.
            self.stats = parent.stats
            self._root = parent._root
            self._cpu_weight = parent._cpu_weight
        else:
            self._root = self
            self._ledger = {}
            self._cpu_weight = CPU_SAMPLE_EVERY \
                if _draw() * CPU_SAMPLE_EVERY < 1.0 else 0
        tracer = _global
        if type(tracer) is not NopTracer:
            parent_id = self._parent_id
            if parent_id is None and parent is not None:
                parent_id = getattr(parent._rec, "span_id", None)
            self._rec = tracer.start_span(self.name, parent_id)
            self._rec.set_tag("trace.id", tid)
        self._child_cpu = 0.0
        ann = _annotation_cls or _annotation()
        if ann.is_enabled():        # a profiler session is open
            self._ann = ann(self.name, trace_id=tid)
            self._ann.__enter__()
        else:
            self._ann = None
        self._t0 = time.perf_counter()
        if self._cpu_weight:
            self._c0 = time.thread_time()
        return self

    def __exit__(self, *exc) -> None:
        weight = self._cpu_weight
        parent = self.parent
        if weight:
            cpu = time.thread_time() - self._c0
            self_cpu = cpu - self._child_cpu
            if parent is not None:
                parent._child_cpu += cpu
        else:
            cpu = self_cpu = None
        self.end = time.perf_counter()
        wall = self.wall = self.end - self._t0
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
        _tls.top = parent
        name = self.name
        ledger = self._root._ledger
        e = ledger.get(name)
        if e is None:
            e = ledger[name] = [0, 0.0, 0.0, 0.0]
        e[0] += 1
        e[1] += wall
        if weight:
            e[2] += cpu * weight
            e[3] += self_cpu * weight
        profiles = self.profiles
        if profiles is None:
            prof = _profile.current()
            if prof is not None:
                prof.add_span(name, wall, cpu, self_cpu)
        else:
            for prof in profiles:
                if prof is not None:
                    prof.add_span(name, wall, cpu, self_cpu)
        if self._rec is not None:
            self._rec.finish()
        if parent is None:
            fold = getattr(self.stats, "count_many", None)
            if fold is not None:
                fold(_counters_of(ledger, weight))
            self._root = None       # a root points at itself: no cycle left
        if self._token is not None:
            _current_trace.reset(self._token)


#: span name -> its four counter names (built once a name).
_counter_names: dict[str, tuple] = {}


def _counters_of(ledger: dict, with_cpu) -> dict:
    """A root's ledger as {counter name: increment}; a tree whose CPU was
    not read adds nothing to the two CPU counters."""
    out = {}
    for name, e in ledger.items():
        keys = _counter_names.get(name)
        if keys is None:
            keys = _counter_names[name] = tuple(
                f"span.{name}.{field}" for field in SPAN_FIELDS)
        out[keys[0]] = e[0]
        out[keys[1]] = e[1]
        if with_cpu:
            out[keys[2]] = e[2]
            out[keys[3]] = e[3]
    return out


def _annotation():
    """jax.profiler.TraceAnnotation, imported on the first span: the
    client-side users of this module (loadgen) never load jax."""
    global _annotation_cls
    if _annotation_cls is None:
        from jax.profiler import TraceAnnotation
        _annotation_cls = TraceAnnotation
    return _annotation_cls


def start_span(operation: str, parent_id: str | None = None, *,
               stats=None, profiles=None) -> LayerSpan:
    """``with start_span("plan.prepare", stats=self.stats): ...`` — the
    StartSpanFromContext analog, and the one timing primitive of the
    tree (see LayerSpan). Spans join the active cross-node trace (starting
    one if absent) and tag themselves with its id, so a query's spans
    correlate across every node it touched.

    ``stats`` is the registry the thread's OUTERMOST span folds into
    (children inherit their parent's); ``profiles`` charges the given
    QueryProfiles by reference instead of the active one (the
    coalescer's flusher thread, which has no query context)."""
    return LayerSpan(operation, parent_id, stats, profiles)
