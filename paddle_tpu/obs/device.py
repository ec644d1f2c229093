"""Device-time span attribution via merged ``jax.profiler`` traces.

The obs spine measures HOST intervals around device dispatches
(``trace.py``) and MODELED cost (``cost.py`` — analytical FLOPs from
``cost_analysis``). Both are proxies: the host interval includes
dispatch and fetch overhead, and the cost model says what the program
*should* cost, not what the device *spent*. This module closes the gap
with measured device time, the number Pope et al.'s efficient-scaling
analysis actually needs per dispatch:

- a :class:`DeviceTraceSession` wraps an obs evidence window in
  ``jax.profiler.start_trace``/``stop_trace`` and, for its duration,
  plugs a span hook into the tracer so every active obs span also opens
  a ``jax.profiler.TraceAnnotation("obs#<span_id>")`` — the profiler
  timeline then carries one host region per obs span;
- on ``stop()`` the exported profiler trace (the ``*.trace.json.gz``
  chrome-format file the profiler writes next to its xplane protobuf)
  is parsed, device-op events (``hlo_op`` args, or any event on a
  ``/device:*`` process) are attributed to the ``obs#`` region they
  overlap most, and the summed durations are merged back onto the
  owning spans as ``device_ms`` / ``device_occupancy`` attrs;
- the session reports **attribution coverage** — attributed device time
  over total captured device time — so a merge that lost ops (spans
  evicted from the ring, work outside any span) is visible instead of
  silently undercounting.

Everything here degrades to "no device attribution" on failure —
profiler unavailable, trace unparseable, zero captured ops — and never
breaks the measured window. Strictly an evidence mode
(``FLAGS_obs_device_trace`` / ``PADDLE_TPU_OBS_DEVICE=1``): a profiler
session is far too heavy for the default serving hot path.
"""

from __future__ import annotations

import bisect
import glob
import gzip
import json
import os
import tempfile
import threading
from typing import Any, Dict, List, Optional, Tuple

from paddle_tpu.obs import trace as _trace

__all__ = ["DeviceTraceSession", "device_trace_enabled",
           "merge_device_events"]

_ACTIVE_LOCK = threading.Lock()
_ACTIVE: Optional["DeviceTraceSession"] = None


def device_trace_enabled() -> bool:
    """``FLAGS_obs_device_trace`` or ``PADDLE_TPU_OBS_DEVICE=1`` — the
    evidence-mode switch the benches consult (always AND-ed with the obs
    master switch; without spans there is nothing to merge onto)."""
    try:
        from paddle_tpu.flags import flags
        if flags.obs_device_trace:
            return True
    except Exception:
        pass
    return os.environ.get("PADDLE_TPU_OBS_DEVICE", "").strip().lower() \
        in ("1", "true", "yes", "on")


def _load_profile_trace(log_dir: str) -> Optional[dict]:
    """Newest chrome-format trace the profiler wrote under ``log_dir``
    (``plugins/profile/<run>/*.trace.json.gz``), parsed, or None."""
    paths = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.trace.json.gz")))
    if not paths:
        return None
    try:
        with gzip.open(paths[-1], "rt") as f:
            return json.load(f)
    except Exception:
        return None


def _split_events(data: dict) -> Tuple[List[dict], List[dict]]:
    """Partition a profiler chrome trace into (obs annotation regions,
    device-op events). Device ops are events carrying an ``hlo_op`` arg
    (how XLA labels executed thunks/ops on every backend) or any
    complete event on a process the profiler named ``/device:*`` (the
    TPU device timeline)."""
    device_pids = set()
    for e in data.get("traceEvents", []):
        if e.get("ph") == "M" and e.get("name") == "process_name":
            name = (e.get("args") or {}).get("name", "")
            if str(name).startswith("/device:"):
                device_pids.add(e.get("pid"))
    annotations, device_events = [], []
    for e in data.get("traceEvents", []):
        if e.get("ph") != "X":
            continue
        name = str(e.get("name", ""))
        if name.startswith("obs#"):
            annotations.append(e)
        elif ("hlo_op" in (e.get("args") or {})
                or e.get("pid") in device_pids):
            device_events.append(e)
    return annotations, device_events


def merge_device_events(annotations: List[dict],
                        device_events: List[dict]) -> dict:
    """Attribute each device-op event to the ``obs#<span_id>`` region it
    overlaps most (innermost wins on ties — nested spans shadow their
    parents, matching the tracer's parent/child semantics). All times
    are profiler-timeline microseconds, so no cross-clock alignment is
    needed. Returns::

        {"attributed_us": {span_id: us}, "device_total_us": float,
         "attributed_total_us": float, "coverage": float,
         "device_ops": int}
    """
    windows = []                       # (start, end, dur, span_id)
    for a in annotations:
        try:
            sid = int(str(a["name"]).split("#", 1)[1])
        except (ValueError, KeyError, IndexError):
            continue
        s = float(a.get("ts", 0.0))
        d = float(a.get("dur", 0.0))
        windows.append((s, s + d, d, sid))
    windows.sort()
    starts = [w[0] for w in windows]
    max_dur = max((w[2] for w in windows), default=0.0)
    attributed: Dict[int, float] = {}
    total = attributed_total = 0.0
    n_ops = 0
    for e in device_events:
        s = float(e.get("ts", 0.0))
        d = float(e.get("dur", 0.0))
        if d <= 0:
            continue
        n_ops += 1
        total += d
        best_sid, best_ov, best_len = None, 0.0, 0.0
        # only windows starting before this op ends can overlap it, and
        # none starting more than max_dur before it begins still can
        hi = bisect.bisect_right(starts, s + d)
        for i in range(hi - 1, -1, -1):
            ws, we, wd, sid = windows[i]
            if ws < s - max_dur:
                break
            ov = min(we, s + d) - max(ws, s)
            if ov > best_ov or (ov == best_ov and ov > 0
                                and wd < best_len):
                best_sid, best_ov, best_len = sid, ov, wd
        if best_sid is not None and best_ov > 0:
            attributed[best_sid] = attributed.get(best_sid, 0.0) + d
            attributed_total += d
    return {"attributed_us": attributed, "device_total_us": total,
            "attributed_total_us": attributed_total,
            "coverage": (attributed_total / total) if total else 0.0,
            "device_ops": n_ops}


class DeviceTraceSession:
    """One profiler capture merged back onto the obs spans it covers.

    Usage (what the benches do around their timed windows)::

        sess = DeviceTraceSession().start()
        ... obs-instrumented work ...
        summary = sess.stop()

    After ``stop()``, every obs span recorded during the session whose
    annotation captured device ops carries ``attrs["device_ms"]`` (sum
    of its device-op durations) and ``attrs["device_occupancy"]``
    (device_ms over the span's host interval — >1.0 is legal when ops
    run on several device threads/cores in parallel). ``summary`` (also
    ``self.summary``) reports per-site totals and the coverage check::

        {"active": True, "merged_spans": n, "coverage": 0.97,
         "device_total_ms": ..., "attributed_ms": ...,
         "by_site": {"decode.chunk": {"device_ms": ..., "spans": n,
                                      "device_ms_mean": ...}, ...}}

    Sessions don't nest (the profiler is process-global): starting while
    another session is active yields an inactive session. Obs disabled
    likewise yields an inactive session — there are no spans to merge.
    """

    def __init__(self, log_dir: Optional[str] = None):
        self._log_dir = log_dir
        self._own_dir = log_dir is None
        self._mark: Optional[int] = None
        self.active = False
        self.summary: dict = {"active": False}

    def __enter__(self) -> "DeviceTraceSession":
        return self.start()

    def __exit__(self, *exc) -> bool:
        self.stop()
        return False

    def start(self) -> "DeviceTraceSession":
        global _ACTIVE
        if not _trace.obs_enabled():
            return self
        with _ACTIVE_LOCK:
            if _ACTIVE is not None:
                return self
            _ACTIVE = self
        try:
            import jax.profiler
            if self._own_dir:
                self._log_dir = tempfile.mkdtemp(prefix="obs_devtrace_")
            self._mark = _trace.tracer.mark()
            jax.profiler.start_trace(self._log_dir)
        except Exception:
            with _ACTIVE_LOCK:
                _ACTIVE = None
            return self
        self.active = True

        def _annotate(name, span_id):
            return jax.profiler.TraceAnnotation(f"obs#{span_id}")

        _trace.set_span_hook(_annotate)
        return self

    def stop(self) -> dict:
        global _ACTIVE
        if not self.active:
            return self.summary
        self.active = False
        _trace.set_span_hook(None)
        try:
            import jax.profiler
            jax.profiler.stop_trace()
        except Exception:
            with _ACTIVE_LOCK:
                _ACTIVE = None
            return self.summary
        with _ACTIVE_LOCK:
            _ACTIVE = None
        data = _load_profile_trace(self._log_dir)
        if data is not None:
            self.summary = self._merge(data)
        if self._own_dir:
            import shutil
            shutil.rmtree(self._log_dir, ignore_errors=True)
        return self.summary

    def _merge(self, data: dict) -> dict:
        annotations, device_events = _split_events(data)
        merged = merge_device_events(annotations, device_events)
        spans = {s.span_id: s
                 for s in _trace.tracer.spans_since(self._mark or 0)}
        by_site: Dict[str, dict] = {}
        merged_spans = 0
        for sid, us in merged["attributed_us"].items():
            sp = spans.get(sid)
            if sp is None:           # evicted from the ring before merge
                continue
            ms = us / 1e3
            sp.attrs["device_ms"] = round(ms, 6)
            if sp.dur_ms > 0:
                sp.attrs["device_occupancy"] = round(ms / sp.dur_ms, 4)
            agg = by_site.setdefault(sp.name,
                                     {"device_ms": 0.0, "spans": 0})
            agg["device_ms"] += ms
            agg["spans"] += 1
            merged_spans += 1
        for agg in by_site.values():
            agg["device_ms"] = round(agg["device_ms"], 6)
            agg["device_ms_mean"] = round(
                agg["device_ms"] / agg["spans"], 6)
        return {"active": True, "merged_spans": merged_spans,
                "coverage": round(merged["coverage"], 4),
                "device_total_ms": round(
                    merged["device_total_us"] / 1e3, 6),
                "attributed_ms": round(
                    merged["attributed_total_us"] / 1e3, 6),
                "device_ops": merged["device_ops"],
                "by_site": dict(sorted(by_site.items()))}
