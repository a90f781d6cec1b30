"""Span-based request tracing with cross-wire context propagation.

PR 1's metrics answer "how slow is this element on average"; they
cannot answer "where did *this* slow request spend its time" across
client → query wire → server pipeline → serving engine. This module is
the per-request complement: explicit span contexts (``trace_id`` /
``span_id`` / ``parent_id``), a lock-protected bounded span store with
**tail-based retention** (the slowest-N completed traces are always
kept alongside a ring of recent ones — tail-latency forensics wants
exactly the traces a uniform sample would evict), and the same
zero-overhead-when-disabled flag discipline as the metrics registry.

Context travels three ways:

  * **in-process** on ``Buffer.meta[CTX_META_KEY]`` — the source stamps
    a root span, every instrumented element chain opens a child
    (obs/instrument.py), sinks close the root;
  * **cross-thread** via a ``contextvars`` current-span slot set while
    an instrumented chain or a ``with start_span(...)`` body runs, so
    engine ``submit()`` calls made inside a traced chain join the
    trace without plumbing;
  * **cross-wire** as a ``trace`` field in query message meta
    (query/protocol.py) — the server adopts the remote parent, so one
    trace id spans both processes.

Span names are literal ``<layer>.<operation>`` lowercase dotted
strings (layer in {pipeline, query, serving, device}), linted by
scripts/check_metric_names.py alongside the metric names.

Exposition: ``GET /debug/traces`` (summaries, ``?min_ms=`` filter),
``GET /debug/traces/<trace_id>`` (full span tree) and
``GET /debug/pipeline`` (live topology + per-element span stats, the
DOT-dump analog) on the obs exporter. ``nns-launch --trace`` and
``PipelineTracer`` consume the same store. Stdlib only, but for
``phase``, which writes through ``jax.profiler.TraceAnnotation`` where
jax can be imported (looked up on first use, never at import).
"""

from __future__ import annotations

import contextvars
import logging
import os
import threading
import time
from collections import OrderedDict
from collections import deque as _deque
from typing import Any, Dict, List, NamedTuple, Optional, Sequence, Tuple

__all__ = [
    "Span", "SpanContext", "SpanStore", "CTX_META_KEY", "ROOT_META_KEY",
    "TRACE_META_KEY", "ctx_from_wire", "current_context", "disable",
    "enable", "enabled", "element_stats", "element_stats_report",
    "live_pipelines", "phase", "pipeline_topology", "register_pipeline",
    "stamp_buffer", "start_span", "store", "Tick", "WAIT_CAUSES",
    "classify_wait", "wait_cause", "wait_record", "witness_stats",
]

#: Buffer.meta key carrying the in-process parent SpanContext
CTX_META_KEY = "trace_ctx"
#: Buffer.meta key carrying the root Span a sink must close
ROOT_META_KEY = "trace_root"
#: wire meta key carrying {"tid": trace_id, "sid": span_id}
TRACE_META_KEY = "trace"


def _new_id() -> str:
    return os.urandom(8).hex()


class SpanContext:
    """Immutable (trace_id, span_id, parent_id) triple. ``parent_id``
    is None for a locally-rooted span; a remote parent (adopted off the
    wire) is a plain SpanContext whose ids came from the peer."""

    __slots__ = ("trace_id", "span_id", "parent_id")

    def __init__(self, trace_id: str, span_id: str,
                 parent_id: Optional[str] = None):
        self.trace_id = trace_id
        self.span_id = span_id
        self.parent_id = parent_id

    def to_wire(self) -> Dict[str, str]:
        """The meta["trace"] payload: trace id + this span as the
        remote parent. parent_id is a local concern and stays home."""
        return {"tid": self.trace_id, "sid": self.span_id}

    def __repr__(self) -> str:
        return (f"SpanContext({self.trace_id}, {self.span_id}, "
                f"parent={self.parent_id})")


def ctx_from_wire(d: Any) -> Optional[SpanContext]:
    """Adopt a remote parent from a wire ``trace`` field; None for a
    missing or malformed field (a peer must never crash the receiver
    with a bad trace blob)."""
    if not isinstance(d, dict):
        return None
    tid, sid = d.get("tid"), d.get("sid")
    if not isinstance(tid, str) or not isinstance(sid, str):
        return None
    return SpanContext(tid, sid)


#: current span context for the running thread of control — set while
#: an instrumented element chain or a ``with start_span(...)`` body
#: runs, read by send_message (wire injection) and LMEngine.submit
_current: "contextvars.ContextVar[Optional[SpanContext]]" = \
    contextvars.ContextVar("nnstpu_current_span", default=None)


def current_context() -> Optional[SpanContext]:
    return _current.get()


def _set_current(ctx: Optional[SpanContext]):
    return _current.set(ctx)


def _reset_current(token) -> None:
    _current.reset(token)


class Span:
    """One timed operation. Created by ``SpanStore.start_span``; calling
    ``end()`` (idempotent) records it into the store. Usable as a
    context manager: exceptions set ``error=True`` before ending."""

    __slots__ = ("name", "context", "start_ns", "end_ns", "wall",
                 "attrs", "tid", "_store", "_token")
    recording = True

    def __init__(self, store: "SpanStore", name: str, context: SpanContext,
                 attrs: Optional[Dict[str, Any]] = None):
        self._store = store
        self.name = name
        self.context = context
        self.attrs = dict(attrs) if attrs else {}
        self.start_ns = time.monotonic_ns()
        self.wall = time.time()
        self.end_ns: Optional[int] = None
        # creating thread: the Perfetto exporter lays host spans out in
        # one lane per pipeline thread
        self.tid = threading.get_ident()
        self._token = None

    def set_attribute(self, key: str, value: Any) -> None:
        self.attrs[key] = value

    def end(self) -> None:
        if self.end_ns is not None:
            return  # idempotent: tee'd buffers may reach two sinks
        self.end_ns = time.monotonic_ns()
        self._store._record(self)

    @property
    def duration_ns(self) -> int:
        return (self.end_ns or time.monotonic_ns()) - self.start_ns

    def __enter__(self) -> "Span":
        self._token = _set_current(self.context)
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        if self._token is not None:
            _reset_current(self._token)
            self._token = None
        if exc_type is not None:
            self.attrs["error"] = True
        self.end()


class _NoopSpan:
    """Returned when tracing is disabled: every operation is a no-op
    and ``context`` is None, so callers never stamp wire meta or buffer
    meta from it. One shared instance — zero allocation when off."""

    __slots__ = ()
    recording = False
    context = None
    name = ""
    attrs: Dict[str, Any] = {}
    duration_ns = 0

    def set_attribute(self, key: str, value: Any) -> None:
        pass

    def end(self) -> None:
        pass

    def __enter__(self) -> "_NoopSpan":
        return self

    def __exit__(self, *exc: Any) -> None:
        pass


NOOP_SPAN = _NoopSpan()


class _Trace:
    """Span accumulator for one trace id (store-internal; guarded by
    the store lock)."""

    __slots__ = ("spans", "start_ns", "end_ns", "root_name",
                 "duration_ns", "completed", "wall")

    def __init__(self) -> None:
        self.spans: List[Span] = []
        self.start_ns: Optional[int] = None
        self.end_ns: Optional[int] = None
        self.root_name: Optional[str] = None
        self.duration_ns: int = 0
        self.completed = False
        self.wall: Optional[float] = None


class SpanStore:
    """Thread-safe bounded trace store with tail-based retention.

    Capacity is ``max_traces`` recent traces PLUS up to ``keep_slowest``
    protected slots: when the ring wraps, the oldest trace NOT in the
    slowest-N set is evicted, so the worst tail survives arbitrarily
    long runs. A trace is *completed* when a locally-rooted span
    (parent_id None) ends; its duration ranks it. Remote-parented
    server-side traces complete on the client side in two-process
    deployments — in-proc tests see both halves in one store.
    """

    def __init__(self, max_traces: int = 256, keep_slowest: int = 16,
                 max_spans_per_trace: int = 512, enabled: bool = False,
                 sample_every: int = 1):
        self._lock = threading.Lock()
        self._traces: "OrderedDict[str, _Trace]" = OrderedDict()  # guarded-by: _lock
        self._slow: Dict[str, int] = {}  # trace_id->duration_ns # guarded-by: _lock
        self.max_traces = int(max_traces)
        self.keep_slowest = int(keep_slowest)
        self.max_spans_per_trace = int(max_spans_per_trace)
        self.sample_every = max(int(sample_every), 1)
        self._sample_n = 0
        self._enabled = bool(enabled)
        self._dropped_spans = 0
        # -- fleet span export (obs/fleet.py) --------------------------- #
        # Off until a fleet pusher flips it on: zero cost for plain
        # single-process tracing (one attribute read in _record against
        # an empty set). Traces are *marked* exportable when their id
        # crosses the query wire (or an engine opts a request in);
        # spans of marked traces queue — bounded, drop-oldest — for the
        # pusher to drain into the aggregator.
        self._export_on = False
        self._export_tids: "OrderedDict[str, None]" = OrderedDict()
        self._export_max_tids = 4096
        self._export_pending: "deque" = _deque(maxlen=2048)
        self._export_dropped = 0

    # -- enable/disable ------------------------------------------------ #
    @property
    def is_enabled(self) -> bool:
        return self._enabled

    def enable(self) -> None:
        self._enabled = True

    def disable(self) -> None:
        self._enabled = False

    def reset(self) -> None:
        with self._lock:
            self._traces.clear()
            self._slow.clear()
            self._sample_n = 0
            self._dropped_spans = 0
            self._export_tids.clear()
            self._export_pending.clear()
            self._export_dropped = 0

    # -- recording ----------------------------------------------------- #
    def start_span(self, name: str,
                   parent: Optional[SpanContext] = None,
                   attrs: Optional[Dict[str, Any]] = None):
        """Open a span; the single flag check is the whole disabled
        cost. ``parent=None`` roots a new trace."""
        if not self._enabled:
            return NOOP_SPAN
        if parent is not None:
            ctx = SpanContext(parent.trace_id, _new_id(), parent.span_id)
        else:
            ctx = SpanContext(_new_id(), _new_id(), None)
        return Span(self, name, ctx, attrs)

    def should_sample(self) -> bool:
        """Head sampling for buffer-rate roots: admit 1 of every
        ``sample_every`` new traces (tail retention still keeps the
        slowest of the admitted ones)."""
        if not self._enabled:
            return False
        if self.sample_every <= 1:
            return True
        with self._lock:
            self._sample_n += 1
            return self._sample_n % self.sample_every == 1

    def _record(self, span: Span) -> None:
        tid = span.context.trace_id
        with self._lock:
            tr = self._traces.get(tid)
            if tr is None:
                tr = _Trace()
                self._traces[tid] = tr
            if len(tr.spans) >= self.max_spans_per_trace:
                self._dropped_spans += 1
            else:
                tr.spans.append(span)
                if self._export_on and tid in self._export_tids:
                    if len(self._export_pending) == \
                            self._export_pending.maxlen:
                        self._export_dropped += 1
                    self._export_pending.append(_span_to_wire(span))
            if tr.start_ns is None or span.start_ns < tr.start_ns:
                tr.start_ns = span.start_ns
                tr.wall = span.wall
            if tr.end_ns is None or span.end_ns > tr.end_ns:
                tr.end_ns = span.end_ns
            if span.context.parent_id is None:
                tr.completed = True
                tr.root_name = span.name
                tr.duration_ns = span.end_ns - span.start_ns
                self._rank_slow(tid, tr.duration_ns)
            self._evict_locked()

    def _rank_slow(self, tid: str, duration_ns: int) -> None:  # guarded-by: _lock
        # maintain the protected slowest-N set (store lock held)
        prev = self._slow.get(tid)
        if prev is not None:
            if duration_ns > prev:
                self._slow[tid] = duration_ns
            return
        if len(self._slow) < self.keep_slowest:
            self._slow[tid] = duration_ns
            return
        fastest = min(self._slow, key=self._slow.get)
        if duration_ns > self._slow[fastest]:
            del self._slow[fastest]
            self._slow[tid] = duration_ns

    def _evict_locked(self) -> None:
        budget = self.max_traces + len(self._slow)
        while len(self._traces) > budget:
            victim = None
            for tid in self._traces:  # oldest-first insertion order
                if tid not in self._slow:
                    victim = tid
                    break
            if victim is None:
                return  # everything is protected; nothing to drop
            del self._traces[victim]

    # -- queries -------------------------------------------------------- #
    def summaries(self, min_ms: float = 0.0) -> List[Dict[str, Any]]:
        """Trace list, slowest first; ``min_ms`` filters on duration
        (completed traces only when a threshold is set — an open trace
        has no defensible duration yet)."""
        out = []
        with self._lock:
            items = list(self._traces.items())
        for tid, tr in items:
            dur_ms = tr.duration_ns / 1e6 if tr.completed else None
            if min_ms > 0.0 and (dur_ms is None or dur_ms < min_ms):
                continue
            out.append({
                "trace_id": tid,
                "root": tr.root_name,
                "completed": tr.completed,
                "duration_ms": dur_ms,
                "spans": len(tr.spans),
                "slowest_retained": tid in self._slow,
                "wall": tr.wall,
            })
        out.sort(key=lambda s: s["duration_ms"] or 0.0, reverse=True)
        return out

    def spans_of(self, trace_id: str) -> Optional[List[Span]]:
        with self._lock:
            tr = self._traces.get(trace_id)
            return list(tr.spans) if tr is not None else None

    def tree(self, trace_id: str) -> Optional[Dict[str, Any]]:
        """Full span tree for one trace: spans nest under their local
        parents; spans whose parent is remote (or unrecorded) surface
        as roots — exactly the view a cross-process half contributes."""
        spans = self.spans_of(trace_id)
        if spans is None:
            return None
        t0 = min(s.start_ns for s in spans) if spans else 0

        def node(s: Span) -> Dict[str, Any]:
            return {
                "span_id": s.context.span_id,
                "parent_id": s.context.parent_id,
                "name": s.name,
                "start_us": (s.start_ns - t0) / 1e3,
                "duration_us": (s.end_ns - s.start_ns) / 1e3,
                "attrs": s.attrs,
                "children": [],
            }

        by_id = {s.context.span_id: node(s) for s in spans}
        roots: List[Dict[str, Any]] = []
        for n in by_id.values():
            parent = by_id.get(n["parent_id"])
            if parent is not None:
                parent["children"].append(n)
            else:
                roots.append(n)
        for n in by_id.values():
            n["children"].sort(key=lambda c: c["start_us"])
        roots.sort(key=lambda c: c["start_us"])
        return {"trace_id": trace_id, "spans": len(spans), "tree": roots}

    def element_stats(self) -> Dict[str, Dict[str, float]]:
        """Per-element stats over recorded ``pipeline.element`` spans:
        {element: {n, mean_us, max_us}} — the span-store view the
        /debug/pipeline endpoint and ``nns-launch --trace`` render."""
        agg: Dict[str, List[float]] = {}
        with self._lock:
            traces = list(self._traces.values())
        for tr in traces:
            for s in tr.spans:
                if s.name != "pipeline.element":
                    continue
                el = str(s.attrs.get("element", "?"))
                agg.setdefault(el, []).append(
                    (s.end_ns - s.start_ns) / 1e3)
        return {
            el: {"n": len(v), "mean_us": sum(v) / len(v), "max_us": max(v)}
            for el, v in agg.items()
        }

    def snapshot_spans(self, max_spans: int = 20000) -> List[Span]:
        """Flat snapshot of recorded spans across all retained traces
        (completed spans only), for timeline exporters (obs/profile.py's
        Perfetto view). Bounded: retention already caps traces, this
        caps the flattened view."""
        out: List[Span] = []
        with self._lock:
            for tr in self._traces.values():
                for s in tr.spans:
                    if s.end_ns is not None:
                        out.append(s)
                        if len(out) >= max_spans:
                            return out
        return out

    def add_span(self, name: str, trace_id: str, parent_id: Optional[str],
                 start_ns: int, end_ns: int,
                 attrs: Optional[Dict[str, Any]] = None,
                 wall: Optional[float] = None) -> Optional[SpanContext]:
        """Insert one already-timed span into an existing trace — the
        diag layer's entry for synthetic attribution spans (sched queue
        wait / batch run) whose endpoints were measured outside a
        ``with start_span(...)`` body. Timestamps are local monotonic
        ns; the span records immediately (bypassing ``end()``, which
        would re-stamp ``end_ns``). Returns the new span's context, or
        None when the store is disabled."""
        if not self._enabled:
            return None
        ctx = SpanContext(str(trace_id), _new_id(), parent_id or None)
        span = Span.__new__(Span)
        span._store = self
        span.name = str(name)
        span.context = ctx
        span.attrs = dict(attrs) if attrs else {}
        span.start_ns = int(start_ns)
        span.end_ns = max(int(end_ns), int(start_ns))
        span.wall = float(wall) if wall is not None else (
            time.time() - (time.monotonic_ns() - span.start_ns) / 1e9)
        span.tid = threading.get_ident()
        span._token = None
        self._record(span)
        return ctx

    # -- fleet span export/ingest (obs/fleet.py) ------------------------ #
    def set_export(self, on: bool) -> None:
        """Flip fleet span export. Off (the default) keeps _record's
        extra cost at one attribute read; turning off also drops any
        queued exports and marks."""
        with self._lock:
            self._export_on = bool(on)
            if not on:
                self._export_tids.clear()
                self._export_pending.clear()

    def mark_export(self, trace_id: Optional[str]) -> None:
        """Mark one trace's spans for fleet export — called where a
        trace id crosses the query wire (send injection / remote-parent
        adoption) and by a serving engine opting a request in. LRU-
        bounded; a no-op unless a fleet pusher enabled export."""
        if not self._export_on or not trace_id:
            return
        with self._lock:
            self._export_tids[trace_id] = None
            self._export_tids.move_to_end(trace_id)
            while len(self._export_tids) > self._export_max_tids:
                self._export_tids.popitem(last=False)

    def drain_export(self, max_n: int = 512) -> List[Dict[str, Any]]:
        """Pop up to ``max_n`` queued wire-format span dicts (oldest
        first) — the fleet pusher's per-push batch."""
        out: List[Dict[str, Any]] = []
        with self._lock:
            while self._export_pending and len(out) < int(max_n):
                out.append(self._export_pending.popleft())
        return out

    def requeue_export(self, spans: List[Dict[str, Any]]) -> None:
        """Put a drained batch back at the FRONT of the export queue —
        the pusher's failure path, so a briefly unreachable aggregator
        doesn't silently lose the spans it drained. Overflow evicts the
        newest queued entries (the requeued batch is older) and counts
        them as export drops."""
        if not spans:
            return
        with self._lock:
            if not self._export_on:
                return
            free = self._export_pending.maxlen - len(self._export_pending)
            overflow = len(spans) - free
            if overflow > 0:
                self._export_dropped += overflow
            for s in reversed(spans):
                self._export_pending.appendleft(s)

    def ingest_remote(self, spans: List[Dict[str, Any]],
                      instance: str) -> int:
        """Insert pushed wire-format spans from ``instance`` into this
        store so /debug/traces/<id> renders the cross-host tree.
        Remote timestamps arrive wall-clock-derived (monotonic clocks
        do not travel between hosts) and are rebased here into the
        local monotonic domain — local spans carry ``monotonic_ns``
        starts, and a trace holding both halves (aggregator tracing its
        own side of the same request) must not mix clock domains in
        tree() offsets or trace start/end rollups. Malformed entries
        are skipped, never raised — a peer must not 500 the aggregator.
        Returns the count actually ingested. Works on a disabled store:
        the aggregator exposes fleet traces without recording its own."""
        # one anchor per batch: local monotonic "now" minus wall "now";
        # remote wall ns + offset lands in the local monotonic domain
        # (to the accuracy of inter-host clock sync, the best we have)
        offset_ns = time.monotonic_ns() - int(time.time() * 1e9)
        n = 0
        for d in spans:
            try:
                ctx = SpanContext(str(d["tid"]), str(d["sid"]),
                                  d.get("par") or None)
                span = Span.__new__(Span)
                span._store = self
                span.name = str(d["name"])
                span.context = ctx
                span.attrs = dict(d.get("attrs") or {})
                span.attrs.setdefault("instance", instance)
                span.wall = float(d["wall"])
                span.start_ns = int(span.wall * 1e9) + offset_ns
                span.end_ns = span.start_ns + max(int(d["dur_ns"]), 0)
                span.tid = 0  # remote thread idents are meaningless here
                span._token = None
            except Exception:
                # the docstring's "never raised" is load-bearing: any
                # malformed field shape (not just the anticipated
                # KeyError/TypeError/ValueError) must skip the entry,
                # not 500 the aggregator
                continue
            # bypass Span.end(): end_ns is already set, record directly
            tid = span.context.trace_id
            with self._lock:
                tr = self._traces.get(tid)
                if tr is None:
                    tr = _Trace()
                    self._traces[tid] = tr
                if len(tr.spans) >= self.max_spans_per_trace:
                    self._dropped_spans += 1
                else:
                    tr.spans.append(span)
                if tr.start_ns is None or span.start_ns < tr.start_ns:
                    tr.start_ns = span.start_ns
                    tr.wall = span.wall
                if tr.end_ns is None or span.end_ns > tr.end_ns:
                    tr.end_ns = span.end_ns
                if span.context.parent_id is None:
                    tr.completed = True
                    tr.root_name = span.name
                    tr.duration_ns = span.end_ns - span.start_ns
                    self._rank_slow(tid, tr.duration_ns)
                self._evict_locked()
            n += 1
        return n


def _span_to_wire(span: Span) -> Dict[str, Any]:
    """Wire-format dict for one completed span: wall-clock start +
    duration (monotonic ns never leave the host), ids, name, attrs."""
    return {
        "tid": span.context.trace_id,
        "sid": span.context.span_id,
        "par": span.context.parent_id,
        "name": span.name,
        "wall": span.wall,
        "dur_ns": (span.end_ns or span.start_ns) - span.start_ns,
        "attrs": span.attrs,
    }


# --------------------------------------------------------------------------- #
# Process-global store + helpers
# --------------------------------------------------------------------------- #

#: disabled by default — mirror of the metrics registry: tracing costs
#: one flag check until NNSTPU_TRACE=1 or enable() turns it on
_STORE = SpanStore(enabled=os.environ.get("NNSTPU_TRACE", "") == "1")


def store() -> SpanStore:
    return _STORE


def enabled() -> bool:
    return _STORE._enabled


def enable(sample_every: Optional[int] = None) -> None:
    """Turn span recording on. Like metrics, call BEFORE building
    pipelines/starting them: element chains decide at Pipeline.start
    whether to open spans at all."""
    if sample_every is not None:
        _STORE.sample_every = max(int(sample_every), 1)
    _STORE.enable()


def disable() -> None:
    _STORE.disable()


def start_span(name: str, parent: Optional[SpanContext] = None,
               attrs: Optional[Dict[str, Any]] = None):
    return _STORE.start_span(name, parent=parent, attrs=attrs)


#: ``jax.profiler.TraceAnnotation`` once a phase has looked for it; False
#: where jax cannot be imported
_ANNOTATION: Any = None


def _annotation() -> Any:
    global _ANNOTATION
    if _ANNOTATION is None:
        try:
            from jax.profiler import TraceAnnotation
            _ANNOTATION = TraceAnnotation
        except ImportError:
            _ANNOTATION = False
    return _ANNOTATION


# -- the wait witness -------------------------------------------------------- #

#: the witness's sleep between two looks at an armed wait
WITNESS_TICK_NS = 5_000_000
#: a look that woke more than this past the wake it asked for: the host
#: stood still over the look's interval, which is then held time
WITNESS_LATE_NS = 10_000_000
#: looks kept a wait (a minute of them); a wait that outlasts them is
#: judged from its last kept look to its end as that look saw it
WITNESS_MAX_TICKS = 12_000
#: the four disjoint parts of a wait's time (``classify_wait``); the
#: engine's ``stats`` counts each as ``wait_<cause>_s``
WAIT_CAUSES = ("unready", "unreturned", "held", "delivered_late")


class Tick(NamedTuple):
    """One look at an armed wait. Its interval runs from the look before
    it, or from the wait's start, to ``end_ns``."""
    end_ns: int     # when the look was made (``monotonic_ns``)
    late_ns: int    # how far past the wake it asked for the witness woke
    cpu_ns: int     # the process's CPU time over the witness's sleep
    ready: bool     # the awaited arrays were all ready
    behind: bool    # the arrays queued behind them were all ready


def classify_wait(start_ns: int, end_ns: int,
                  ticks: Sequence[Tick]) -> Dict[str, Any]:
    """Split the wait ``[start_ns, end_ns]`` by what ``ticks`` saw (those
    that end past ``end_ns`` are left out) into four disjoint parts, in
    seconds, that sum to the wait:

    * ``held_s``: the intervals of looks that woke more than
      ``WITNESS_LATE_NS`` late. Nothing is judged there; ``held_cpu_s`` is the process's CPU
      time over them: none where the process was descheduled, about their
      wall where another thread held the interpreter;
    * ``unreturned_s``: from the first look that saw the awaited arrays
      ready to the end, less held time. The arrays turned ready up to a
      look before, so it is a lower bound;
    * ``delivered_late_s``: the time before that look, less held time,
      where the arrays queued behind were first seen ready in the same
      look, within two looks after it or before it, with no held look
      in the wait up to the later sighting: a chunk behind takes a
      chunk's device time after the one it waits for, unless the first
      one's completion came late; and a completion that comes late after
      the host stood still is the hold's (the runtime's threads stood
      still with it);
    * ``unready_s``: the rest: the device running, or a completion not
      delivered, with nothing to tell which.

    ``lag_s`` is from the awaited arrays' first sighting to the arrays
    behind's, None where either was not seen."""
    ticks = [t for t in ticks if t.end_ns <= end_ns]
    held = held_cpu = held_after = 0
    first = behind = None
    prev = start_ns
    for i, t in enumerate(ticks):
        if t.late_ns > WITNESS_LATE_NS:
            held += t.end_ns - prev
            held_cpu += t.cpu_ns
            if first is not None:
                held_after += t.end_ns - prev
        prev = t.end_ns
        if first is None and t.ready:
            first = i
        if behind is None and t.behind:
            behind = i
    unreturned = 0 if first is None \
        else end_ns - ticks[first].end_ns - held_after
    unready = end_ns - start_ns - held - unreturned
    late = False
    if behind is not None and (first is None or behind <= first + 2):
        hi = behind if first is None else max(first, behind)
        late = not any(t.late_ns > WITNESS_LATE_NS for t in ticks[:hi + 1])
    return {"unready_s": 0.0 if late else unready / 1e9,
            "unreturned_s": unreturned / 1e9, "held_s": held / 1e9,
            "delivered_late_s": unready / 1e9 if late else 0.0,
            "held_cpu_s": held_cpu / 1e9,
            "lag_s": None if first is None or behind is None
            else (ticks[behind].end_ns - ticks[first].end_ns) / 1e9}


def wait_record() -> Dict[str, Any]:
    """The ``wait`` entry of a step record before its first wait: the
    parts of ``classify_wait`` summed over the iteration's waits, and the
    longest wait with its ``lag_s``."""
    return {"unready_s": 0.0, "unreturned_s": 0.0, "held_s": 0.0,
            "delivered_late_s": 0.0, "held_cpu_s": 0.0, "longest_s": 0.0,
            "longest_lag_s": None}


def wait_cause(record: Dict[str, Any]) -> Optional[str]:
    """The largest part of a ``wait_record``, held time named by whether
    the process ran through it; None where nothing was waited for."""
    cause = max(WAIT_CAUSES, key=lambda c: record[f"{c}_s"])
    if record[f"{cause}_s"] <= 0.0:
        return None
    if cause == "held":
        return "held, process busy" \
            if record["held_cpu_s"] >= record["held_s"] / 2 \
            else "held, descheduled"
    return cause.replace("_", " ")


def _ready(arrays: Sequence[Any]) -> bool:
    """Whether every array is ready; a host array or a None (no
    ``is_ready``) is."""
    try:
        return all(a.is_ready() for a in arrays if hasattr(a, "is_ready"))
    except RuntimeError:        # an array deleted under the look
        return False


class _Watch:
    """One armed wait: the arrays awaited and those behind, and the looks
    the witness made at them."""

    __slots__ = ("start_ns", "awaited", "behind", "ticks", "open")

    def __init__(self, start_ns: int, awaited: Sequence[Any],
                 behind: Optional[Sequence[Any]]) -> None:
        self.start_ns = start_ns
        self.awaited = awaited
        self.behind = behind
        self.ticks: List[Tick] = []
        self.open = True


class _Witness:
    """The process's one witness thread, started by the first armed wait.
    While a wait is armed it sleeps ``WITNESS_TICK_NS`` at a time and adds
    a ``Tick`` to every armed wait; with none armed it blocks on an event.
    It calls nothing but ``is_ready()``, the monotonic clock and the
    process CPU clock, never blocks on an array, and holds no lock across
    a look: arming and disarming take none while it is awake. A thread
    that died (an exception a look did not expect, logged once) is started
    again by the next armed wait."""

    def __init__(self) -> None:
        self._open: List[_Watch] = []
        self._armed = threading.Event()
        self._start_lock = threading.Lock()
        self._thread: Optional[threading.Thread] = None
        self._stopping = False
        self._failed = False    # a thread of it died, and it was logged
        self.ticks = 0          # looks made, over the process's life
        self.busy_ns = 0        # wall spent looking, wake to next sleep

    def arm(self, start_ns: int, awaited: Sequence[Any],
            behind: Optional[Sequence[Any]]) -> _Watch:
        w = _Watch(start_ns, awaited, behind)
        self._open.append(w)
        if self._thread is None or not self._thread.is_alive():
            with self._start_lock:
                if self._thread is None or not self._thread.is_alive():
                    self._thread = threading.Thread(
                        target=self._run, name="nns-wait-witness",
                        daemon=True)
                    self._thread.start()
        # the witness clears the event only before it looks at _open again
        if not self._armed.is_set():
            self._armed.set()
        return w

    def disarm(self, w: _Watch) -> List[Tick]:
        """Close ``w``; the looks it got."""
        w.open = False
        try:
            self._open.remove(w)
        except ValueError:
            pass
        return list(w.ticks)

    def stop(self, timeout: float = 1.0) -> None:
        """End the witness thread; the next armed wait starts another."""
        t = self._thread
        if t is None:
            return
        self._stopping = True
        self._armed.set()
        t.join(timeout)
        self._thread = None
        self._stopping = False

    def _run(self) -> None:
        try:
            self._look()
        except Exception:
            if not self._failed:
                self._failed = True
                _log.exception("wait witness died; the next armed wait "
                               "starts it again")

    def _look(self) -> None:
        tick = WITNESS_TICK_NS
        sched = cpu = None
        while not self._stopping:
            watches = tuple(self._open)
            if not watches:
                self._armed.clear()
                if not self._open and not self._stopping:
                    self._armed.wait()
                sched = None
                continue
            if sched is None:
                sched = min(w.start_ns for w in watches) + tick
                cpu = time.process_time_ns()
            now = time.monotonic_ns()
            if sched > now:
                time.sleep((sched - now) / 1e9)
            woke = time.monotonic_ns()
            cpu0, cpu = cpu, time.process_time_ns()
            for w in tuple(self._open):
                if w.open and len(w.ticks) < WITNESS_MAX_TICKS:
                    w.ticks.append(Tick(
                        woke, woke - sched, cpu - cpu0, _ready(w.awaited),
                        w.behind is not None and _ready(w.behind)))
            done = time.monotonic_ns()
            self.ticks += 1
            self.busy_ns += done - woke
            sched = done + tick


_WITNESS = _Witness()
_log = logging.getLogger("nns_tpu.obs")


def witness_stats() -> Dict[str, Any]:
    """The witness's own cost, for an operator: its looks over the
    process's life (``ticks``), the wall they took, wake to next sleep
    (``busy_s``), and whether its thread runs (``alive``)."""
    t = _WITNESS._thread
    return {"ticks": _WITNESS.ticks, "busy_s": _WITNESS.busy_ns / 1e9,
            "alive": t is not None and t.is_alive()}


class phase:
    """One host phase of an engine iteration, timed once and written
    three ways. ``with phase(stats, "serving.decode_wait", parent=step)``
    reads ``time.monotonic_ns()`` on entry and on exit (``start_ns`` /
    ``end_ns``: callers that report the same interval elsewhere take
    these stamps and read no clock of their own) and

    * always adds the elapsed seconds to ``stats["decode_wait_s"]`` (the
      name without its layer, plus ``_s``);
    * always brackets the body with a ``jax.profiler.TraceAnnotation``:
      a ``Span``'s stamps are ``monotonic_ns`` and a profiler trace's are
      relative to its session, so only an event written through the
      profiler lies on the device ops' timeline. It costs a few hundred
      nanoseconds while no session is live and appears in the
      ``.xplane.pb`` of whichever session is;
    * while tracing is enabled records a ``Span``: a phase without a
      ``parent`` roots a trace of its own (thinned by ``sample_every``
      like any buffer-rate root), one with a parent is its child, and
      records only if the parent did. Tracing off makes no ``Span``.

    A phase given ``awaited`` (the arrays a wait blocks on) arms the
    process's witness for its length, with ``behind`` (the arrays of the
    program queued behind them, or None): the witness looks at them every
    5 ms. On exit the waiting thread adds its own last look (the awaited
    arrays ready, and whether those behind are), ``classify_wait`` splits
    the phase's time, and each part is added to
    ``stats["wait_<cause>_s"]`` and, where ``record`` is a
    ``wait_record``, to it.
    """

    __slots__ = ("name", "start_ns", "end_ns", "_stats", "_key", "_parent",
                 "_note", "_span", "_awaited", "_behind", "_record",
                 "_watch")

    def __init__(self, stats: Dict[str, Any], name: str,
                 parent: Optional["phase"] = None,
                 awaited: Optional[Sequence[Any]] = None,
                 behind: Optional[Sequence[Any]] = None,
                 record: Optional[Dict[str, Any]] = None) -> None:
        self.name = name
        self._stats = stats
        self._key = name.partition(".")[2] + "_s"
        self._parent = parent
        self._note = None
        self._span: Optional[Span] = None
        self.start_ns = self.end_ns = 0
        self._awaited = awaited
        self._behind = behind
        self._record = record
        self._watch: Optional[_Watch] = None

    def set_attribute(self, key: str, value: Any) -> None:
        """On the ``Span``, where one is being recorded."""
        if self._span is not None:
            self._span.attrs[key] = value

    def __enter__(self) -> "phase":
        if _STORE._enabled:
            parent = self._parent
            if parent is None:
                if _STORE.should_sample():
                    self._span = _STORE.start_span(self.name)
            elif parent._span is not None:
                self._span = _STORE.start_span(
                    self.name, parent=parent._span.context)
        self.start_ns = time.monotonic_ns()
        note = _annotation()
        if note:
            self._note = note(self.name)
            self._note.__enter__()
        if self._awaited is not None:
            self._watch = _WITNESS.arm(self.start_ns, self._awaited,
                                       self._behind)
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        if self._note is not None:
            self._note.__exit__(exc_type, exc, tb)
        self.end_ns = time.monotonic_ns()
        self._stats[self._key] += (self.end_ns - self.start_ns) / 1e9
        if self._watch is not None:
            self._judge(exc_type is None)
        if self._span is not None:
            if exc_type is not None:
                self._span.attrs["error"] = True
            self._span.end()

    def _judge(self, returned: bool) -> None:
        w = self._watch
        ticks = _WITNESS.disarm(w)
        if returned:
            ticks.append(Tick(self.end_ns, 0, 0, True, w.behind is not None
                              and _ready(w.behind)))
        wait = classify_wait(self.start_ns, self.end_ns, ticks)
        st = self._stats
        for key, part in _WAIT_KEYS:
            st[key] += wait[part]
        rec = self._record
        if rec is not None:
            for _, part in _WAIT_KEYS:
                rec[part] += wait[part]
            rec["held_cpu_s"] += wait["held_cpu_s"]
            if self.seconds > rec["longest_s"]:
                rec["longest_s"] = self.seconds
                rec["longest_lag_s"] = wait["lag_s"]

    @property
    def seconds(self) -> float:
        return (self.end_ns - self.start_ns) / 1e9


_WAIT_KEYS = tuple((f"wait_{c}_s", f"{c}_s") for c in WAIT_CAUSES)


def stamp_buffer(buf: Any, span_store: SpanStore, source: str):
    """Root a new trace on a source-created buffer (obs/instrument.py
    source wrapper). A buffer that already carries a context — e.g. a
    serversrc inbox frame adopted off the wire — is left alone: the
    existing trace owns it."""
    if CTX_META_KEY in buf.meta:
        return None
    if not span_store.should_sample():
        return None
    root = span_store.start_span("pipeline.buffer", attrs={
        "source": source, "pts": buf.pts, "offset": buf.offset})
    if root.recording:
        buf.meta[CTX_META_KEY] = root.context
        buf.meta[ROOT_META_KEY] = root
    return root


# -- live pipeline topology (the DOT-dump analog) --------------------------- #

import weakref  # noqa: E402 — grouped with its single consumer

_live_pipelines: "weakref.WeakSet" = weakref.WeakSet()


def register_pipeline(pipeline: Any) -> None:
    """Called from the Pipeline.start instrumentation hook — a WeakSet
    add, so a collected pipeline never lingers in /debug/pipeline."""
    _live_pipelines.add(pipeline)


def live_pipelines() -> List[Any]:
    return list(_live_pipelines)


def pipeline_topology(pipeline: Any) -> Dict[str, Any]:
    """Elements + directed links of one pipeline, duck-typed off the
    graph model (element name/kind, src pad → peer element)."""
    elements = []
    for el in pipeline.elements.values():
        links = []
        for pad in el.src_pads:
            if pad.peer is not None:
                links.append(pad.peer.element.name)
        elements.append({
            "name": el.name,
            "kind": getattr(el, "ELEMENT_NAME", type(el).__name__),
            "is_source": el.is_source,
            "is_sink": el.is_sink,
            "links": links,
        })
    return {"name": pipeline.name, "running": pipeline.running,
            "elements": elements}


def element_stats(span_store: Optional[SpanStore] = None
                  ) -> Dict[str, Dict[str, float]]:
    return (span_store or _STORE).element_stats()


def element_stats_report(span_store: Optional[SpanStore] = None) -> str:
    """Text table of per-element span stats, slowest mean first — the
    shared renderer behind ``nns-launch --trace`` and
    ``PipelineTracer.span_report``."""
    stats = element_stats(span_store)
    lines = [f"{'element':<24}{'spans':>8}{'mean(us)':>12}{'max(us)':>12}"]
    for el, t in sorted(stats.items(),
                        key=lambda kv: kv[1]["mean_us"], reverse=True):
        lines.append(f"{el:<24}{t['n']:>8}{t['mean_us']:>12.1f}"
                     f"{t['max_us']:>12.1f}")
    return "\n".join(lines)
