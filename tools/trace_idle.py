#!/usr/bin/env python3
"""Where the device idled in a kept benchmark trace: between programs or
inside them.

    python3 tools/trace_idle.py <dir of benchmark/run.py --keep-trace>

Reads ``events.json.gz`` (the traced window's first second: per device the
``XLA Modules`` and ``XLA Ops`` events, ``[name, start_ns, duration_ns,
detail]``) and prints one JSON object: the stretch covered, the union of
the module runs, the union of the ops, the idle time BETWEEN module runs
(no program on the device: what ``ServingEngine``'s ``starved.*`` parts
can own, plus what the host does not know — a program's launch, the return
of a read) and INSIDE them (gaps between the ops of a running program),
and the longest gaps between runs with the programs on either side.
"""

from __future__ import annotations

import gzip
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from benchmark.harness.trace import union_intervals as _union  # noqa: E402


def reduce(dev: dict, top: int = 8) -> dict:
    mods = sorted(dev["modules"], key=lambda e: e[1])
    if not mods:
        return {"modules": 0}
    t0, t1 = mods[0][1], max(s + d for _, s, d, _ in mods)
    runs = _union(mods)
    ops = _union([e for e in dev["ops"] if t0 <= e[1] and e[1] + e[2] <= t1])
    in_runs = sum(b - a for a, b in runs)
    busy = sum(b - a for a, b in ops)
    gaps = []
    for prev, nxt in zip(mods, mods[1:]):
        gap = nxt[1] - (prev[1] + prev[2])
        if gap > 0:
            gaps.append((gap, prev[0], nxt[0]))
    by_pair: dict = {}
    for gap, a, b in gaps:
        key = f"{a.split('(')[0]} -> {b.split('(')[0]}"
        n, s = by_pair.get(key, (0, 0.0))
        by_pair[key] = (n + 1, s + gap)
    ms = 1e-6
    return {
        "modules": len(mods), "stretch_ms": (t1 - t0) * ms,
        "in_programs_ms": in_runs * ms, "ops_busy_ms": busy * ms,
        "idle_between_programs_ms": (t1 - t0 - in_runs) * ms,
        "idle_inside_programs_ms": (in_runs - busy) * ms,
        "idle_share_pct": 100.0 * (t1 - t0 - busy) / (t1 - t0),
        "gaps_between_programs_ms": {
            k: {"n": n, "sum_ms": s * ms, "mean_ms": s * ms / n}
            for k, (n, s) in sorted(by_pair.items(),
                                    key=lambda kv: -kv[1][1])[:top]}}


def main(argv) -> int:
    if len(argv) != 2:
        print(__doc__, file=sys.stderr)
        return 2
    with gzip.open(os.path.join(argv[1], "events.json.gz"), "rt") as f:
        cut = json.load(f)
    print(json.dumps({dev: reduce(lines)
                      for dev, lines in cut["devices"].items()}, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
