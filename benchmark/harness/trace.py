"""The one reduction from a profiler trace to numbers.

``load_xplane`` turns the ``.xplane.pb`` the JAX profiler writes into plain
event lists (``jax.profiler.ProfileData``, nothing else); everything after
that works on those lists, so the arithmetic is checked in
``benchmark/tests`` against a small trace recorded on the chip and kept as
JSON (``tests/data/``).

An event is ``[name, start_ns, duration_ns, detail]``; ``detail`` is the
long name the compiler recorded for an op (its HLO text / source op name),
empty where there is none. Per device: the ``XLA Modules`` line (one event
per run of a compiled program) and the ``XLA Ops`` line (one event per
operation, nested under control flow). Host: the annotations the
benchmark's own loop wrote (``jax.profiler.TraceAnnotation``, names starting
``bench.``).
"""

from __future__ import annotations

import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
MODULES_LINE, OPS_LINE = "XLA Modules", "XLA Ops"
HOST_PREFIX = "bench."
WINDOW_SPAN = "bench.trace_window"
_DETAIL_STATS = ("long_name", "hlo_op", "tf_op", "name", "kernel_details",
                 "hlo_category", "source")


class TraceError(RuntimeError):
    """The trace does not hold what a reader was asked for."""


def find_xplane(trace_dir: str) -> str:
    hits = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not hits:
        raise TraceError(f"the profiler wrote no .xplane.pb under "
                         f"{trace_dir}/plugins/profile/")
    return hits[-1]


def _detail(ev) -> str:
    parts = []
    for k, v in ev.stats:
        if k in _DETAIL_STATS and isinstance(v, str) and v:
            parts.append(v)
    return " | ".join(parts)[:200]


def _op_name(name: str) -> tuple:
    """(short name, rest): on the TPU the op event's name is the whole HLO
    line, ``%decode_attention.37 = bf16[...] custom-call(...)``; the short
    name is the instruction's own, the rest goes to the detail."""
    head, sep, rest = name.partition(" = ")
    return head.lstrip("%"), (rest[:200] if sep else "")


def load_xplane(path: str, cpu_rehearsal: bool = False) -> dict:
    """``{"devices": {id: {"modules": [...], "ops": [...]}}, "host": [...]}``
    from one ``.xplane.pb``. ``cpu_rehearsal``: where the trace holds no TPU
    plane, the XLA:CPU client's op events (those that name an HLO module)
    stand in as device 0's ops, with no modules line - so that a rehearsal
    walks the same code; its numbers mean nothing."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    out = {"devices": {}, "host": []}
    cpu_ops = []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev = {"modules": [], "ops": []}
            for line in plane.lines:
                key = {MODULES_LINE: "modules", OPS_LINE: "ops"}.get(line.name)
                if key is None:
                    continue
                for ev in line.events:
                    if key == "ops":
                        name, rest = _op_name(ev.name)
                        det = rest or _detail(ev)
                    else:
                        name, det = ev.name, ""
                    dev[key].append([name, float(ev.start_ns),
                                     float(ev.duration_ns), det])
            out["devices"][int(m.group(1))] = dev
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(HOST_PREFIX):
                        out["host"].append([ev.name, float(ev.start_ns),
                                            float(ev.duration_ns), ""])
                    elif cpu_rehearsal and any(
                            k == "hlo_module" for k, _ in ev.stats):
                        cpu_ops.append([ev.name, float(ev.start_ns),
                                        float(ev.duration_ns), _detail(ev)])
    if cpu_rehearsal and not out["devices"]:
        out["devices"][0] = {"modules": [], "ops": cpu_ops}
    return out


def describe(path: str, limit: int = 6) -> list:
    """What is in a trace, for looking at one by hand: every plane and line
    with its event count and first few events (name, stats)."""
    from jax.profiler import ProfileData
    rows = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            evs = list(line.events)
            rows.append({"plane": plane.name, "line": line.name,
                         "events": len(evs),
                         "first": [{"name": e.name, "start_ns": e.start_ns,
                                    "dur_ns": e.duration_ns,
                                    "stats": {k: (v if not isinstance(v, str)
                                                  else v[:200])
                                              for k, v in e.stats}}
                                   for e in evs[:limit]]})
    return rows


# ---------------------------------------------------------------------------
# reduction (pure functions over event lists)
# ---------------------------------------------------------------------------

def window(trace: dict) -> tuple:
    """(start_ns, end_ns) of the traced stretch: the benchmark's own
    ``bench.trace_window`` annotation, which it opens after the profiler
    has started and closes before it stops."""
    spans = [e for e in trace["host"] if e[0] == WINDOW_SPAN]
    if len(spans) != 1:
        raise TraceError(f"expected one {WINDOW_SPAN} annotation in the "
                         f"trace, found {len(spans)}")
    _, t0, dur, _ = spans[0]
    return t0, t0 + dur


def _clip(events, t0, t1):
    out = []
    for name, s, d, det in events:
        a, b = max(s, t0), min(s + d, t1)
        if b > a:
            out.append((name, a, b - a, det))
    return out


def union_intervals(events) -> list:
    """Merged [start, end] intervals covered by any event."""
    iv = sorted((s, s + d) for _, s, d, _ in events if d > 0)
    out = []
    for a, b in iv:
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1][1] = b
        else:
            out.append([a, b])
    return out


def busy_seconds(trace: dict) -> dict:
    """Seconds in which an operation ran, per device, inside the window
    (union of the ``XLA Ops`` intervals), their mean over the devices, and
    the window's length."""
    t0, t1 = window(trace)
    per = {}
    for dev, lines in trace["devices"].items():
        iv = union_intervals(_clip(lines["ops"], t0, t1))
        per[dev] = sum(b - a for a, b in iv) / 1e9
    if not per:
        raise TraceError("the trace holds no device plane")
    return {"per_device": per, "busy_s": sum(per.values()) / len(per),
            "window_s": (t1 - t0) / 1e9}


def module_runs(trace: dict, pattern: str, device=None) -> list:
    """Durations (ns) of the runs of the compiled programs whose module
    name matches ``pattern``, started inside the window, on one device (the
    lowest-numbered unless given)."""
    t0, t1 = window(trace)
    dev = min(trace["devices"]) if device is None else device
    rx = re.compile(pattern)
    return [d for name, s, d, _ in trace["devices"][dev]["modules"]
            if rx.search(name) and t0 <= s and s + d <= t1]


def op_events(trace: dict, pattern: str, device=None) -> list:
    """The op events whose name or detail matches ``pattern``, wholly inside
    the window, on one device."""
    t0, t1 = window(trace)
    dev = min(trace["devices"]) if device is None else device
    rx = re.compile(pattern)
    return [(name, s, d, det) for name, s, d, det
            in trace["devices"][dev]["ops"]
            if t0 <= s and s + d <= t1
            and (rx.search(name) or rx.search(det))]


def ops_inside_modules(trace: dict, op_pattern: str, module_pattern: str,
                       device=None) -> tuple:
    """(summed ns of matching ops that lie inside a matching module run,
    number of those module runs): a kernel's time per run of its program."""
    t0, t1 = window(trace)
    dev = min(trace["devices"]) if device is None else device
    mrx = re.compile(module_pattern)
    runs = sorted((s, s + d) for name, s, d, _
                  in trace["devices"][dev]["modules"]
                  if mrx.search(name) and t0 <= s and s + d <= t1)
    total, i = 0.0, 0
    for _, s, d, _ in sorted(op_events(trace, op_pattern, dev),
                             key=lambda e: e[1]):
        while i < len(runs) and runs[i][1] < s:
            i += 1
        if i < len(runs) and runs[i][0] <= s and s + d <= runs[i][1]:
            total += d
    return total, len(runs)


def kernel_ns_per_run(trace: dict, op_pattern: str, module_pattern: str) -> float:
    """``ops_inside_modules`` as time per run; a kernel or a program that
    was asked for and is not in the trace is an error, never a 0."""
    total_ns, runs = ops_inside_modules(trace, op_pattern, module_pattern)
    if runs == 0:
        raise TraceError(f"no run of a module matching {module_pattern!r} "
                         f"lies inside the traced window")
    if total_ns <= 0:
        raise TraceError(f"no op matching {op_pattern!r} inside the runs of "
                         f"{module_pattern!r}: the kernel is not in this "
                         f"program")
    return total_ns / runs


def self_times(events) -> list:
    """(name, self_ns) per event of one line: its duration less what the
    events nested inside it cover (control flow holds its body's ops)."""
    evs = sorted(events, key=lambda e: (e[1], -e[2]))
    out, stack = [], []          # stack of [name, end, self]
    for name, s, d, _ in evs:
        while stack and stack[-1][1] <= s:
            top = stack.pop()
            out.append((top[0], top[2]))
        if stack:
            stack[-1][2] -= min(d, stack[-1][1] - s)
        stack.append([name, s + d, d])
    out.extend((n, t) for n, _, t in stack)
    return out


_SUFFIX = re.compile(r"[.\-_]?\d+$")


def top_device_ops(trace: dict, n: int = 10) -> list:
    """[[name, seconds], ...]: the device operations that took most self
    time inside the window on the lowest device, by the trace's names with
    their numeric suffix dropped (``fusion.123`` -> ``fusion``)."""
    t0, t1 = window(trace)
    dev = min(trace["devices"])
    acc = {}
    for name, t in self_times(_clip(trace["devices"][dev]["ops"], t0, t1)):
        key = _SUFFIX.sub("", name.lstrip("%")) or name
        acc[key] = acc.get(key, 0.0) + t
    top = sorted(acc.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v / 1e9] for k, v in top]


def idle_gaps(trace: dict, n: int = 10) -> list:
    """[[what the host was doing, seconds], ...]: the device's idle time
    inside the window, attributed gap by gap to the benchmark's host
    annotation that covers most of the gap (the innermost where several
    do), summed by annotation name, longest first."""
    t0, t1 = window(trace)
    dev = min(trace["devices"])
    busy = union_intervals(_clip(trace["devices"][dev]["ops"], t0, t1))
    gaps, cur = [], t0
    for a, b in busy:
        if a > cur:
            gaps.append((cur, a))
        cur = max(cur, b)
    if t1 > cur:
        gaps.append((cur, t1))
    host = [(name, s, s + d, d) for name, s, d, _ in trace["host"]
            if name != WINDOW_SPAN and d > 0]
    acc = {}
    for a, b in gaps:
        best, best_key = "unattributed", (0.0, 0.0)
        for name, s, e, d in host:
            ov = min(b, e) - max(a, s)
            if ov > 0 and (ov, -d) > best_key:
                best, best_key = name, (ov, -d)
        acc[best] = acc.get(best, 0.0) + (b - a)
    top = sorted(acc.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v / 1e9] for k, v in top]
