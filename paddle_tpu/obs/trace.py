"""Structured span tracer — the timing spine of the obs subsystem.

One thread-safe tracer serves every layer (decode dispatch wrappers,
serving engine request timelines, bundle entries, the legacy profiler
facade): ``with span("decode.chunk", batch=8):`` records a nested,
monotonic-clock span into a bounded ring buffer. Spans measure HOST
intervals around device dispatches (what the host pays per dispatch, the
cost the fused programs exist to spread over many tokens); the
device-side FLOPs and bytes of the dispatched program ride in as span
attributes from ``obs.cost`` (compiled-program cost telemetry).

Every active span, and every :func:`phase`, also opens the same-named
``jax.profiler.TraceAnnotation``: a no-op unless a profiler session is
live (whoever started it), and then a host event on the device trace's
own clock — so a profiler trace of a serving run shows which host phase
each device-idle gap falls under, with no merge step.

Clock discipline: all timestamps are ``time.monotonic_ns()`` — the same
clock family the serving engine and ``distributed/elastic.py`` use for
latency math, so a span's interval can never jump on an NTP step and
serving timeline spans (built from the engine's monotonic stamps) land
on the SAME axis as dispatch spans in one exported trace.

Disabled (the default — ``FLAGS_obs_enabled`` / ``PADDLE_TPU_OBS=1``),
``span()`` returns a shared no-op context manager: the per-call cost is
one enabled check, guarded by an overhead test in tests/test_obs.py.

Exporters: ``export_chrome_trace`` (chrome://tracing / Perfetto
loadable) and ``export_jsonl`` (one span dict per line — the
``tools/trace_report.py`` input; chrome JSON is accepted there too).
"""

from __future__ import annotations

import collections
import json
import os
import threading
import time
from typing import Callable, Dict, List, Optional

from jax.profiler import TraceAnnotation

__all__ = ["Span", "Tracer", "tracer", "span", "phase", "obs_enabled",
           "DeviceTimeline", "LONG_INTERVAL_S"]


def obs_enabled() -> bool:
    """The obs master switch: ``FLAGS_obs_enabled`` (settable at runtime
    via ``set_flags``/``FLAGS_obs_enabled=1``) or the ``PADDLE_TPU_OBS``
    environment variable. Read live — tests and benches toggle it around
    measurement windows."""
    try:
        from paddle_tpu.flags import flags
        if flags.obs_enabled:
            return True
    except Exception:
        pass
    return os.environ.get("PADDLE_TPU_OBS", "").strip().lower() in (
        "1", "true", "yes", "on")


class Span:
    """One recorded interval. ``parent_id`` encodes nesting (same-thread
    enclosing span); ``seq`` is the tracer-wide admission order (marks /
    windowed counting); ``attrs`` carries site metadata and the attached
    compiled-program cost record."""

    __slots__ = ("name", "span_id", "parent_id", "start_ns", "end_ns",
                 "tid", "attrs", "seq", "kind")

    def __init__(self, name, span_id, parent_id, start_ns, end_ns, tid,
                 attrs, seq, kind="span"):
        self.name = name
        self.span_id = span_id
        self.parent_id = parent_id
        self.start_ns = start_ns
        self.end_ns = end_ns
        self.tid = tid
        self.attrs = attrs
        self.seq = seq
        self.kind = kind              # "span" | "event" (instant)

    @property
    def dur_ms(self) -> float:
        return (self.end_ns - self.start_ns) / 1e6

    def ok(self) -> bool:
        """True unless the spanned body raised (error spans are excluded
        from dispatch-count accounting — a failed dispatch never ran)."""
        return "error" not in self.attrs

    def as_dict(self) -> dict:
        return {"name": self.name, "span_id": self.span_id,
                "parent_id": self.parent_id, "start_ns": self.start_ns,
                "end_ns": self.end_ns, "dur_ms": self.dur_ms,
                "tid": self.tid, "kind": self.kind, "attrs": self.attrs}

    def as_chrome(self) -> dict:
        ev = {"name": self.name, "pid": os.getpid(), "tid": self.tid,
              "ts": self.start_ns / 1e3, "cat": self.kind,
              "args": dict(self.attrs)}
        if self.kind == "event":
            ev.update(ph="i", s="t")
        else:
            ev.update(ph="X", dur=(self.end_ns - self.start_ns) / 1e3)
        return ev


class _ActiveSpan:
    """The context manager handed out by ``Tracer.span`` when enabled.
    Records on exit; ``annotate()`` attaches attrs mid-flight (the cost
    telemetry hook)."""

    __slots__ = ("_tracer", "name", "attrs", "_start", "_parent",
                 "span_id", "_ann")

    def __init__(self, tracer_, name, attrs):
        self._tracer = tracer_
        self.name = name
        self.attrs = attrs

    def annotate(self, **attrs) -> None:
        self.attrs.update(attrs)

    def __enter__(self):
        t = self._tracer
        self.span_id = t._next_id()
        stack = t._stack()
        self._parent = stack[-1] if stack else None
        stack.append(self.span_id)
        self._ann = TraceAnnotation(self.name)
        self._ann.__enter__()
        self._start = time.monotonic_ns()
        return self

    def __exit__(self, etype, exc, tb):
        end = time.monotonic_ns()
        self._ann.__exit__(None, None, None)
        stack = self._tracer._stack()
        if stack and stack[-1] == self.span_id:
            stack.pop()
        if etype is not None:
            self.attrs["error"] = f"{etype.__name__}: {str(exc)[:200]}"
        self._tracer._record(Span(
            self.name, self.span_id, self._parent, self._start, end,
            threading.get_ident() & 0xFFFF, self.attrs,
            self._tracer._next_seq()))
        return False


class _NullSpan:
    """Shared no-op for the disabled path — zero allocation per call."""

    __slots__ = ()

    def annotate(self, **attrs) -> None:
        pass

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL = _NullSpan()


class Tracer:
    """Thread-safe bounded span recorder.

    ``enabled``: ``None`` follows the global obs switch
    (:func:`obs_enabled`); a callable is consulted per call (the legacy
    profiler facade plugs its own recording state in here). The buffer
    is a ring: the newest ``capacity`` spans win, and ``dropped`` counts
    what the ring evicted so reports never silently claim completeness.
    ``mark()``/``spans_since(mark)`` give windowed views keyed by a
    monotonic admission counter — how the benches count dispatch spans
    for exactly the timed window."""

    def __init__(self, capacity: Optional[int] = None,
                 enabled: Optional[Callable[[], bool]] = None):
        self._cap = capacity
        self._enabled = enabled
        self._lock = threading.Lock()
        self._spans: List[Span] = []
        self._ids = 0
        self._seq = 0
        self.dropped = 0
        self._local = threading.local()

    # -- internals ----------------------------------------------------------
    def _capacity(self) -> int:
        if self._cap is not None:
            return self._cap
        try:
            from paddle_tpu.flags import flags
            return int(flags.obs_buffer_size)
        except Exception:
            return 8192

    def _stack(self) -> list:
        st = getattr(self._local, "stack", None)
        if st is None:
            st = self._local.stack = []
        return st

    def _next_id(self) -> int:
        with self._lock:
            self._ids += 1
            return self._ids

    def _next_seq(self) -> int:
        with self._lock:
            self._seq += 1
            return self._seq

    def _record(self, sp: Span) -> None:
        with self._lock:
            self._spans.append(sp)
            cap = self._capacity()
            if len(self._spans) > cap:
                drop = len(self._spans) - cap
                del self._spans[:drop]
                self.dropped += drop

    def enabled(self) -> bool:
        return self._enabled() if self._enabled is not None \
            else obs_enabled()

    # -- recording API ------------------------------------------------------
    def span(self, name: str, **attrs):
        """Context manager timing a nested interval. No-op (shared
        singleton, no allocation) when disabled."""
        if not self.enabled():
            return _NULL
        return _ActiveSpan(self, name, attrs)

    def event(self, name: str, **attrs) -> None:
        """Instant event (Chrome 'i' phase) — serving request phase
        markers (queued/admitted/finished) and resilience events."""
        if not self.enabled():
            return
        now = time.monotonic_ns()
        self._record(Span(name, self._next_id(), None, now, now,
                          threading.get_ident() & 0xFFFF, attrs,
                          self._next_seq(), kind="event"))

    def add_span(self, name: str, start_ns: int, end_ns: int,
                 **attrs) -> None:
        """Retroactive span from caller-supplied ``time.monotonic_ns``
        stamps — the serving engine builds each request's lifetime span
        (submit -> finish) this way at finish time."""
        if not self.enabled():
            return
        self._record(Span(name, self._next_id(), None, int(start_ns),
                          int(end_ns), threading.get_ident() & 0xFFFF,
                          attrs, self._next_seq()))

    # -- views --------------------------------------------------------------
    def spans(self) -> List[Span]:
        with self._lock:
            return list(self._spans)

    def mark(self) -> int:
        """Current admission counter; pair with :meth:`spans_since`."""
        with self._lock:
            return self._seq

    def spans_since(self, mark: int) -> List[Span]:
        with self._lock:
            return [s for s in self._spans if s.seq > mark]

    def counts(self, since: int = 0, ok_only: bool = True
               ) -> Dict[str, int]:
        """Span count per name admitted after ``since`` (a ``mark()``
        value). ``ok_only`` drops error spans — the dispatch-accounting
        comparison counts only dispatches that ran."""
        out: Dict[str, int] = {}
        for s in self.spans_since(since):
            if s.kind != "span" or (ok_only and not s.ok()):
                continue
            out[s.name] = out.get(s.name, 0) + 1
        return out

    def clear(self) -> None:
        with self._lock:
            self._spans.clear()
            self.dropped = 0

    def drain(self) -> List[Span]:
        with self._lock:
            out, self._spans = self._spans, []
            return out

    # -- exporters ----------------------------------------------------------
    def chrome_events(self, since: int = 0) -> List[dict]:
        return [s.as_chrome() for s in self.spans_since(since)]

    def export_chrome_trace(self, path: str, since: int = 0,
                            extra_events: Optional[List[dict]] = None
                            ) -> str:
        """Write a chrome://tracing-loadable JSON trace; returns the
        path. Crash-safe write (atomic rename) — a trace artifact is
        evidence, and half a JSON is none."""
        from paddle_tpu.runtime.resilience import atomic_write_bytes
        events = self.chrome_events(since) + list(extra_events or [])
        atomic_write_bytes(path, json.dumps(
            {"traceEvents": events, "displayTimeUnit": "ms"}).encode())
        return path

    def export_jsonl(self, path: str, since: int = 0) -> str:
        from paddle_tpu.runtime.resilience import atomic_write_bytes
        lines = "".join(json.dumps(s.as_dict()) + "\n"
                        for s in self.spans_since(since))
        atomic_write_bytes(path, lines.encode())
        return path


tracer = Tracer()


def span(name: str, **attrs):
    """``with obs.span("decode.chunk", batch=8):`` on the global tracer."""
    return tracer.span(name, **attrs)


class phase:
    """``with obs.phase("serving.step.admit", hist):`` — one always-on
    phase of a loop. Opens the profiler annotation ``name``, adds the
    elapsed ``time.monotonic()`` interval to ``hist`` (any object with
    ``observe(seconds)``) on exit, raised or not, and, with obs enabled,
    records the nested span in the ring as :func:`span` does. Disabled
    cost: one annotation enter/exit with no session live, two clock
    reads and one observe. ``t0`` and ``t1`` are those two readings: a
    caller that marks something else at the same boundary (the engine's
    :class:`DeviceTimeline`) takes them and reads no clock of its own."""

    __slots__ = ("name", "hist", "_ann", "_span", "t0", "t1")

    def __init__(self, name: str, hist):
        self.name = name
        self.hist = hist

    def __enter__(self):
        # the span opens the annotation itself when obs is enabled
        self._span = tracer.span(self.name)
        self._ann = (TraceAnnotation(self.name) if self._span is _NULL
                     else _NULL)
        self._ann.__enter__()
        self._span.__enter__()
        self.t0 = time.monotonic()
        return self

    def __exit__(self, etype, exc, tb):
        self.t1 = time.monotonic()
        self.hist.observe(self.t1 - self.t0)
        self._span.__exit__(etype, exc, tb)
        self._ann.__exit__(None, None, None)
        return False


# an interval of one part of a DeviceTimeline that lasts this long is a
# stall: no chunk, prefill or host phase of any served configuration
# comes near it (the longest prefill measured is 0.45 s)
LONG_INTERVAL_S = 1.0
_LONG_KEPT = 32


class DeviceTimeline:
    """The device's timeline as the HOST knows it: at every instant one
    part is open, and each closed stretch of ``clock()`` seconds is added
    to that part's counter (``counters[part].inc(seconds)``).

    The host knows two things and marks them where they happen. An
    enqueue has returned: ``fed(kind)`` — the device has work from now
    on (``fed.<kind>``). A blocking read behind everything enqueued has
    returned: ``drained()`` — the device is empty until the next
    ``fed``, and the time goes to where the host is, ``host(where)``:
    ``starved.<where>``, or ``no_work`` while ``where`` is None (nothing
    submitted is unfinished, so nobody is kept waiting). An enqueue with
    no blocking read after it simply stays fed until the next read, and
    ``fed`` while fed closes that interval and opens its own kind. The
    timeline cannot see a device that ran dry before the read returned,
    gaps between the ops of a program, or the launch of one: a device
    trace can; what it gives is every run's, traced or not.

    Every boundary is one clock reading (``now``: a reading the caller
    already took at that boundary), so the parts tile wall time from
    construction: the counters' sum after ``flush()`` is the seconds
    since then. An interval of ``LONG_INTERVAL_S`` or more in any part
    but ``no_work`` (an idle server is not stalled, and its idle
    stretches would push the real ones out) is kept in ``long`` — the
    newest 32, ``{serial, part, seconds}``, ``serial`` the count of
    intervals closed so far — and logged once at WARNING on ``log``.
    ``intervals`` counts the closed intervals by part."""

    __slots__ = ("_counters", "_clock", "_log", "_lock", "_fed", "_host",
                 "_t_open", "_t_acc", "serial", "intervals", "long")

    def __init__(self, counters, host: Optional[str] = None, log=None,
                 clock: Callable[[], float] = time.monotonic):
        self._counters = counters
        self._clock = clock
        self._log = log
        self._lock = threading.Lock()
        self._fed: Optional[str] = None     # kind of the newest enqueue
        self._host = host                   # where the host is; None: idle
        # the open interval began at _t_open; its seconds up to _t_acc
        # are in the counter already (flush moves _t_acc alone, so a
        # scrape in the middle of a stall does not cut it in two)
        self._t_open = self._t_acc = clock()
        self.serial = 0
        self.intervals: Dict[str, int] = collections.Counter()
        self.long: collections.deque = collections.deque(maxlen=_LONG_KEPT)

    @property
    def part(self) -> str:
        if self._fed is not None:
            return "fed." + self._fed
        return "no_work" if self._host is None else "starved." + self._host

    def _flush(self, now: float) -> None:
        # a reading taken before another thread's flush is not after it
        if now > self._t_acc:
            self._counters[self.part].inc(now - self._t_acc)
            self._t_acc = now

    def _close(self, now: Optional[float]) -> None:
        now = self._clock() if now is None else now
        part = self.part
        self._flush(now)
        self.serial += 1
        self.intervals[part] += 1
        seconds = now - self._t_open
        if seconds >= LONG_INTERVAL_S and part != "no_work":
            self.long.append({"serial": self.serial, "part": part,
                              "seconds": seconds})
            if self._log is not None:
                self._log.warning(
                    "device timeline: %.3f s in %s (interval %d)",
                    seconds, part, self.serial)
        self._t_open = max(now, self._t_acc)

    def fed(self, kind: str, now: Optional[float] = None) -> None:
        """An enqueue of ``kind`` has returned."""
        with self._lock:
            self._close(now)
            self._fed = kind

    def drained(self, now: Optional[float] = None) -> None:
        """A blocking read behind everything enqueued has returned."""
        with self._lock:
            if self._fed is not None:
                self._close(now)
                self._fed = None

    def host(self, where: Optional[str],
             now: Optional[float] = None) -> None:
        """The host is ``where`` from now on (None: nothing submitted is
        unfinished). Splits an interval only while the device is empty:
        a fed device is fed wherever the host is."""
        with self._lock:
            if where != self._host:
                if self._fed is None:
                    self._close(now)
                self._host = where

    def flush(self, now: Optional[float] = None) -> None:
        """Bring the open part's counter up to ``now``."""
        with self._lock:
            self._flush(self._clock() if now is None else now)
