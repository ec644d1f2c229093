"""Share of its roofline the expanded attention of the admission prefills
of a model with multi-head latent attention reaches, in percent: the least
time of the attention the traced prefills NEEDED over the summed device
time of the flash forward kernel's calls in the traced window.

A call computes one layer of a BUCKET: its output's shape in the trace,
``(rows x heads, positions, query/key width)``, says which bucket and how
many rows. What was needed is less — causal attention over each row's true
length, nothing of the padded tail, nothing of the values' zero padding to
the query/key width — and the engine counts it at admission from each
row's true length (``ServingEngine._count_prefill``; ``prefill_by_bucket``
in its ``metrics()``: rows, positions and causal pairs by bucket). The
runner reads the engine at the window's two ends, and the traced stretch is
the window's last seconds, so the need of a traced call is the need a row
of the WINDOW's prefills of its bucket, counted not guessed. FLOPs and
bytes as ``mla_flops.mla_prefill_attention`` reckons them; least time by
``flops.least_time_s`` over the traced calls' needs together. A traced
stretch that held no admission prefill, or an engine without the counts
(no ``prefill_by_bucket`` in its metrics), reads None. Args: ``kernel`` (regex on the op's
name)."""

import re

from benchmark.adapters.axk1 import at_depth
from benchmark.flops import least_time_s
from benchmark.harness.trace import op_events
from benchmark.mla_flops import mla_prefill_attention

_SHAPE = re.compile(r"\[(\d+),(\d+),(\d+)\]")
_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}
_COUNTS = ("rows", "positions", "causal_pairs")


def _window_by_bucket(ctx) -> dict:
    """bucket -> the window's difference of the engine's counts."""
    eng = ctx.get("engine") or {}
    after = (eng.get("after") or {}).get("prefill_by_bucket") or {}
    before = (eng.get("before") or {}).get("prefill_by_bucket") or {}
    return {int(b): {k: v[k] - before.get(b, {}).get(k, 0)
                     for k in _COUNTS} for b, v in after.items()}


def read(ctx, *, kernel: str):
    trace = ctx.get("trace")
    if trace is None:
        return None
    section = ctx["section"]
    a = at_depth(ctx["arch"], section["num_hidden_layers"])
    heads = int(a["num_attention_heads"])
    qk = int(a["qk_nope_head_dim"]) + int(a["qk_rope_head_dim"])
    window = _window_by_bucket(ctx)
    calls = []
    for name, _, d, det in op_events(trace, kernel):
        shapes = [tuple(map(int, m)) for m in
                  _SHAPE.findall(det) + _SHAPE.findall(name)]
        mine = [s for s in shapes if s[2] == qk]
        if d > 0 and mine:
            calls.append((d, mine[0]))
    if not calls or not window:
        return None
    flops = nbytes = 0.0
    for _, (bh, positions, _) in calls:     # one call a layer a prefill
        need = window.get(positions)
        if not need or need["rows"] <= 0:
            return None
        share = bh / heads / need["rows"]
        n = mla_prefill_attention(
            causal_pairs=share * need["causal_pairs"],
            positions=share * need["positions"], heads=heads,
            qk_head_dim=qk, v_head_dim=int(a["v_head_dim"]),
            bytes_per_el=_BYTES[section["dtype"]])
        flops += n["flops"]
        nbytes += n["bytes"]
    busy_s = sum(d for d, _ in calls) / 1e9
    least = least_time_s(flops, nbytes, ctx["peaks"])
    ctx.setdefault("notes", []).append(
        f"mla_prefill_roofline: {least['bound']}-bound, least "
        f"{least['seconds'] * 1e3:.4f} ms of {busy_s * 1e3:.4f} ms in "
        f"{len(calls)} traced calls over buckets of "
        f"{sorted({s[1] for _, s in calls})} positions ({flops:.4g} FLOPs "
        f"needed, by the engine's counts of the window's prefills)")
    return 100.0 * least["seconds"] / busy_s
