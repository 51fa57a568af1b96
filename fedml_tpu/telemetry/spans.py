"""Zero-dependency structured tracer — the host-side half of the profiling
story (the device half is ``jax.profiler`` via ``utils/profiling.trace``).

A span is a named wall-clock interval with attributes::

    with span("round", round=3):
        with span("broadcast", round=3):
            ...

Spans are thread-safe and nestable; each thread keeps its own nesting stack
(parent attribution), and the recording buffer is shared so one trace file
covers the server FSM thread, the client actor threads, and timer threads.

The export format is Chrome trace events (the ``traceEvents`` JSON that
Perfetto / ``chrome://tracing`` load natively), with complete ("X") events
in epoch-anchored microseconds: wall clock at the tracer's construction plus
a monotonic delta. That is NOT the jax profiler's clock — a ``--telemetry_dir``
trace and a ``--profile_dir`` device trace share no timebase and line up only
as well as one reads both clocks at one moment. To put the spans on the
profiler's own clock, set :attr:`Tracer.annotate`: every context-manager span
then also enters the annotation the hook returns
(``utils/profiling.span_annotation`` gives ``jax.profiler.TraceAnnotation``
named ``fedml.<span>``), so a profile holds the host spans on its host plane
beside the device ops.

Cross-thread spans (a federated "round" begins on the broadcast path and
ends in a receive handler on another thread) use the explicit handle API::

    s = tracer.start_span("round", round=r)   # on the broadcast thread
    ...
    s.end()                                   # on the handler thread

Listeners subscribe to finished spans (``tracer.add_listener``) — the
client health registry feeds on ``local_train`` spans this way."""

from __future__ import annotations

import json
import os
import threading
import time
from typing import Any, Callable, Dict, List, Optional

# Bounded recording: a month-long run must not grow the event buffer without
# limit. Past the cap, new events are dropped and counted.
DEFAULT_MAX_EVENTS = 1_000_000


class SpanEvent:
    """One finished span: name, epoch-anchored start (us), duration (us),
    recording thread id, and user attributes."""

    __slots__ = ("name", "ts_us", "dur_us", "pid", "tid", "attrs")

    def __init__(self, name: str, ts_us: float, dur_us: float, pid: int, tid: int, attrs: Dict[str, Any]):
        self.name = name
        self.ts_us = ts_us
        self.dur_us = dur_us
        self.pid = pid
        self.tid = tid
        self.attrs = attrs

    def to_chrome(self) -> dict:
        return {
            "name": self.name,
            "ph": "X",
            "ts": self.ts_us,
            "dur": self.dur_us,
            "pid": self.pid,
            "tid": self.tid,
            "cat": "fedml_tpu",
            "args": self.attrs,
        }

    def __repr__(self):  # debugging aid, not part of the wire format
        return (
            f"SpanEvent({self.name!r}, dur={self.dur_us / 1e3:.3f}ms, "
            f"attrs={self.attrs})"
        )


# One lock for every span's end(): what it guards is a flag's test-and-set,
# so spans never wait on each other for longer than that — and a span need
# not allocate a lock of its own (the train loop opens ten a round).
_END_LOCK = threading.Lock()

# The recording process, kept current across a fork: asked once, not per span.
_pid = os.getpid()


def _refresh_pid() -> None:
    global _pid
    _pid = os.getpid()


os.register_at_fork(after_in_child=_refresh_pid)


class Span:
    """A live span handle. Created by ``Tracer.start_span`` / ``Tracer.span``;
    ``end()`` is idempotent and may be called from any thread."""

    __slots__ = (
        "_tracer", "name", "attrs", "_t0_perf", "_done", "_annotation", "dur_us",
    )

    def __init__(self, tracer: "Tracer", name: str, attrs: Dict[str, Any]):
        self._tracer = tracer
        self.name = name
        self.attrs = attrs
        self._t0_perf = time.perf_counter_ns()
        self._done = False
        self._annotation = None
        self.dur_us: Optional[float] = None  # set by end()

    def set_attr(self, key: str, value: Any) -> "Span":
        self.attrs[key] = value
        return self

    def end(self) -> Optional[SpanEvent]:
        # atomic test-and-set: end() may race from two threads (e.g. a
        # timeout path vs the handler that completes the round) and must
        # record exactly once
        with _END_LOCK:
            if self._done:
                return None
            self._done = True
        t0, tracer = self._t0_perf, self._tracer
        self.dur_us = dur_us = (time.perf_counter_ns() - t0) / 1e3
        ev = SpanEvent(
            self.name,
            tracer._epoch_us + (t0 - tracer._anchor_ns) / 1e3,
            dur_us,
            _pid,
            threading.get_ident(),
            self.attrs,
        )
        tracer._record(ev)
        return ev

    def __enter__(self) -> "Span":
        tracer = self._tracer
        tracer._push(self)
        if tracer.annotate is not None:
            self._annotation = tracer.annotate(self.name, self.attrs.get("round"))
            self._annotation.__enter__()
        return self

    def __exit__(self, *exc) -> None:
        if self._annotation is not None:
            self._annotation.__exit__(*exc)
        self._tracer._pop(self)
        self.end()


class Tracer:
    """Thread-safe span recorder with a bounded buffer and span listeners."""

    def __init__(self, max_events: int = DEFAULT_MAX_EVENTS):
        self._events: List[SpanEvent] = []
        self._lock = threading.Lock()
        # replaced, never mutated (add/remove build a new tuple under the
        # lock), so _record reads it without copying
        self._listeners: tuple = ()
        self._local = threading.local()
        self.max_events = int(max_events)
        self.dropped = 0
        # epoch anchor: ts = wall clock at init + monotonic delta since,
        # so timestamps are comparable across processes (not with the jax
        # profiler's trace: see ``annotate``) but never jump with NTP
        # adjustments mid-run
        self._epoch_us = time.time() * 1e6
        self._anchor_ns = time.perf_counter_ns()
        self.process_label: Optional[str] = None
        # Optional mirror of every context-manager span onto another clock:
        # ``annotate(name, round) -> context manager``, entered and left with
        # the span on the span's own thread (handle spans from start_span
        # may end on another thread and are not mirrored).
        self.annotate: Optional[Callable[[str, Any], Any]] = None

    # -- time --
    def _now_us(self) -> float:
        return self._epoch_us + (time.perf_counter_ns() - self._anchor_ns) / 1e3

    def now_us(self) -> float:
        """This tracer's epoch-anchored clock (us) — the timebase every
        recorded event uses, and the one the cross-process trace context
        (telemetry/wire.py) stamps into outbound messages so recv-side
        deltas are comparable across processes."""
        return self._now_us()

    # -- nesting stack (per thread, parent attribution) --
    def _stack(self) -> List[Span]:
        st = getattr(self._local, "stack", None)
        if st is None:
            st = self._local.stack = []
        return st

    def _push(self, s: Span) -> None:
        st = self._stack()
        if st:
            s.attrs.setdefault("parent", st[-1].name)
        s.attrs.setdefault("depth", len(st))
        st.append(s)

    def _pop(self, s: Span) -> None:
        st = self._stack()
        if st and st[-1] is s:
            st.pop()
        elif s in st:  # mis-nested exit — drop it and everything above
            del st[st.index(s):]

    def current_span(self) -> Optional[Span]:
        """The innermost open context-manager span on the calling thread
        (None outside any ``with span(...)``) — parent attribution for
        the outbound trace context."""
        st = getattr(self._local, "stack", None)
        return st[-1] if st else None

    # -- recording --
    def record_event(
        self, name: str, ts_us: float, dur_us: float = 0.0, **attrs
    ) -> SpanEvent:
        """Record a pre-timed event directly (no Span handle) — the comm
        template uses this for ``wire_recv`` markers whose start is the
        message arrival, not a span entry."""
        ev = SpanEvent(
            str(name),
            float(ts_us),
            float(dur_us),
            _pid,
            threading.get_ident(),
            attrs,
        )
        self._record(ev)
        return ev

    def record_child_event(self, name: str, dur_s: float, **attrs) -> SpanEvent:
        """Record an interval that ENDS NOW on the calling thread and was
        timed by someone else (jax's compile events reach their listener
        only once over: analysis/sentinel.py), attributed as ``_push``
        attributes a context-manager span: ``parent``, ``depth`` and ``round``
        of the innermost open span. Outside any span it carries none of the
        three, so that it is never read as a depth-0 span of the loop."""
        st = getattr(self._local, "stack", None)
        if st:
            attrs["parent"] = st[-1].name
            attrs["depth"] = len(st)
            if "round" in st[-1].attrs:
                attrs["round"] = st[-1].attrs["round"]
        dur_us = float(dur_s) * 1e6
        return self.record_event(name, self._now_us() - dur_us, dur_us, **attrs)

    def _record(self, ev: SpanEvent) -> None:
        with self._lock:
            if len(self._events) < self.max_events:
                self._events.append(ev)
            else:
                self.dropped += 1
            listeners = self._listeners
        for fn in listeners:
            try:
                fn(ev)
            except Exception:  # noqa: BLE001 — a listener must never break training
                import logging

                logging.exception("telemetry span listener failed")

    # -- public API --
    def span(self, name: str, **attrs) -> Span:
        """Context-manager span (nested via the calling thread's stack)."""
        return Span(self, name, attrs)

    def start_span(self, name: str, **attrs) -> Span:
        """Explicit-handle span for intervals that end on another thread
        (no nesting-stack participation)."""
        return Span(self, name, attrs)

    def add_listener(self, fn: Callable[[SpanEvent], None]) -> None:
        with self._lock:
            if fn not in self._listeners:
                self._listeners = self._listeners + (fn,)

    def remove_listener(self, fn: Callable[[SpanEvent], None]) -> None:
        with self._lock:
            if fn in self._listeners:
                self._listeners = tuple(f for f in self._listeners if f != fn)

    def listeners(self) -> List[Callable[[SpanEvent], None]]:
        """Snapshot of the subscribed listeners (the supported read
        accessor — consumers must not reach into the private list)."""
        with self._lock:
            return list(self._listeners)

    def events(self) -> List[SpanEvent]:
        with self._lock:
            return list(self._events)

    def reset(self) -> None:
        with self._lock:
            self._events.clear()
            self.dropped = 0

    # -- export --
    def to_chrome_trace(self) -> dict:
        """Chrome trace-event JSON object (Perfetto / chrome://tracing)."""
        events = [ev.to_chrome() for ev in self.events()]
        # thread/process name metadata makes the Perfetto track labels human
        meta = []
        pid = os.getpid()
        label = self.process_label or f"fedml_tpu pid {pid}"
        meta.append(
            {
                "name": "process_name",
                "ph": "M",
                "pid": pid,
                "tid": 0,
                "args": {"name": label},
            }
        )
        for tid in sorted({e["tid"] for e in events}):
            meta.append(
                {
                    "name": "thread_name",
                    "ph": "M",
                    "pid": pid,
                    "tid": tid,
                    "args": {"name": f"thread-{tid}"},
                }
            )
        return {
            "traceEvents": meta + events,
            "displayTimeUnit": "ms",
            "otherData": {"dropped_events": self.dropped},
        }

    def write_chrome_trace(self, path: str) -> str:
        """Write the trace JSON; returns the path written. Creates parent
        directories, so call sites can pass the CLI flag straight through."""
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        tmp = f"{path}.tmp.{os.getpid()}"
        with open(tmp, "w") as f:
            json.dump(self.to_chrome_trace(), f)
        os.replace(tmp, path)
        return path


_GLOBAL = Tracer()

from fedml_tpu.telemetry.scope import current_scope  # noqa: E402 — import
# placed after Tracer so scope.py's lazy constructor can import it; scope
# itself imports nothing from telemetry at module level (no cycle)


def get_tracer() -> Tracer:
    """The tracer for the calling thread: the active
    :class:`fedml_tpu.telemetry.scope.TelemetryScope`'s tracer when one is
    installed (multi-tenant serving — each session's threads record into
    their own trace), else the process-wide default every single-run path
    records into."""
    sc = current_scope()
    return sc.tracer if sc is not None else _GLOBAL


def get_global_tracer() -> Tracer:
    """The process-wide tracer, regardless of any active scope."""
    return _GLOBAL


def span(name: str, **attrs) -> Span:
    """``with span("round", round=n): ...`` on the calling thread's tracer
    (scope-aware, see :func:`get_tracer`)."""
    return get_tracer().span(name, **attrs)
