"""Render an obs trace into per-phase / per-request summary tables.

Input: a Chrome-trace JSON (``{"traceEvents": [...]}`` — what
``obs.tracer.export_chrome_trace`` and the obs-enabled benches write)
or an obs JSONL file (one span dict per line, from ``export_jsonl``).

Output: two text tables —

- **phases**: per span name, the count / total / mean / p50 / max
  duration, with attached cost-telemetry columns (per-dispatch GFLOPs
  from the span attrs) when present;
- **requests**: one row per ``serving.request`` lifetime span (queue
  delay, service latency, chunks, slot, ladder level) — the
  iteration-level serving view; a completeness line flags any request
  id whose queued/admitted/finished phase events don't all appear.

``--json`` additionally emits the summary as one machine-readable JSON
line on stdout (for logs / CI greps). Exit code 1 on an
empty or unreadable trace — a smoke gate, not just a pretty-printer.

Usage:
    python tools/trace_report.py obs_trace_serve.json
    python tools/trace_report.py /tmp/spans.jsonl --json
"""

from __future__ import annotations

import argparse
import json
import sys
from collections import defaultdict


def _load(path: str):
    """Returns (spans, events): span dicts with name/dur_ms/attrs, and
    instant phase events with name/attrs."""
    with open(path) as f:
        head = f.read(1)
        f.seek(0)
        if head == "{":
            data = json.load(f)
            spans, events = [], []
            for e in data.get("traceEvents", []):
                if e.get("ph") == "X":
                    spans.append({"name": e["name"],
                                  "dur_ms": e.get("dur", 0) / 1e3,
                                  "attrs": e.get("args", {})})
                elif e.get("ph") == "i":
                    events.append({"name": e["name"],
                                   "attrs": e.get("args", {})})
            return spans, events
        spans, events = [], []
        for line in f:
            line = line.strip()
            if not line:
                continue
            d = json.loads(line)
            if d.get("kind") == "event":
                events.append(d)
            else:
                spans.append(d)
        return spans, events


def _pct(vals, q):
    s = sorted(vals)
    if not s:
        return 0.0
    k = (len(s) - 1) * q / 100.0
    lo, hi = int(k), min(int(k) + 1, len(s) - 1)
    return s[lo] + (s[hi] - s[lo]) * (k - lo)


def phase_table(spans):
    per = defaultdict(list)
    flops = {}
    errors = defaultdict(int)
    for s in spans:
        per[s["name"]].append(s["dur_ms"])
        a = s.get("attrs") or {}
        if "flops" in a:
            flops[s["name"]] = float(a["flops"])
        if "error" in a:
            errors[s["name"]] += 1
    rows = []
    for name, durs in sorted(per.items(), key=lambda kv: -sum(kv[1])):
        row = {
            "phase": name, "count": len(durs),
            "total_ms": round(sum(durs), 3),
            "mean_ms": round(sum(durs) / len(durs), 3),
            "p50_ms": round(_pct(durs, 50), 3),
            "max_ms": round(max(durs), 3),
            "errors": errors.get(name, 0),
            "gflops_per_dispatch": (round(flops[name] / 1e9, 6)
                                    if name in flops else None),
        }
        rows.append(row)
    return rows


def request_table(spans, events):
    rows = []
    for s in spans:
        if s["name"] != "serving.request":
            continue
        a = s.get("attrs") or {}
        rows.append({
            "request": a.get("request"),
            "queue_delay_ms": round(
                float(a.get("queue_delay_s", 0.0)) * 1e3, 3),
            "latency_ms": round(s["dur_ms"], 3),
            "chunks": a.get("chunks"), "tokens": a.get("tokens"),
            "slot": a.get("slot"), "level": a.get("level"),
        })
    rows.sort(key=lambda r: (r["request"] is None, r["request"]))
    # completeness: every queued request id must also be admitted+finished
    seen = defaultdict(set)
    for e in events:
        name = e["name"]
        if name.startswith("serving.request."):
            rid = (e.get("attrs") or {}).get("request")
            if rid is not None:
                seen[rid].add(name.rsplit(".", 1)[1])
    incomplete = sorted(rid for rid, phases in seen.items()
                        if not {"queued", "admitted",
                                "finished"} <= phases)
    return rows, {"timeline_requests": len(seen),
                  "incomplete": incomplete}


def _print_table(rows, cols, title):
    print(f"\n== {title} ==")
    if not rows:
        print("(empty)")
        return
    widths = {c: max(len(c), *(len(str(r.get(c, ""))) for r in rows))
              for c in cols}
    line = "  ".join(f"{c:>{widths[c]}}" for c in cols)
    print(line)
    print("-" * len(line))
    for r in rows:
        print("  ".join(f"{str(r.get(c, '') if r.get(c) is not None else '-'):>{widths[c]}}"
                        for c in cols))


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("trace", help="chrome-trace JSON or obs JSONL file")
    ap.add_argument("--json", action="store_true",
                    help="also print the summary as one JSON line")
    args = ap.parse_args(argv)
    try:
        spans, events = _load(args.trace)
    except Exception as e:
        print(f"trace_report: cannot read {args.trace}: {e}",
              file=sys.stderr)
        return 1
    if not spans and not events:
        print(f"trace_report: {args.trace} holds no spans or events",
              file=sys.stderr)
        return 1
    phases = phase_table(spans)
    requests, completeness = request_table(spans, events)
    cols = ["phase", "count", "total_ms", "mean_ms", "p50_ms", "max_ms",
            "errors", "gflops_per_dispatch"]
    _print_table(phases, cols,
                 f"phases ({len(spans)} spans, {len(events)} events)")
    if requests or completeness["timeline_requests"]:
        _print_table(requests, ["request", "queue_delay_ms", "latency_ms",
                                "chunks", "tokens", "slot", "level"],
                     "serving requests")
        if completeness["incomplete"]:
            print(f"INCOMPLETE timelines (missing queued/admitted/"
                  f"finished): {completeness['incomplete']}")
        else:
            print(f"timeline completeness: "
                  f"{completeness['timeline_requests']} request(s), "
                  f"all queued->admitted->finished")
    if args.json:
        print(json.dumps({"trace": args.trace, "phases": phases,
                          "requests": requests,
                          "completeness": completeness}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
