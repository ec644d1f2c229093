"""Share of its roofline the attention kernel of the EVA admission
prefills (``eva_prefill_attention``: causal attention inside each aligned
window and over the summaries of the windows before, one softmax) reaches,
in percent: the least time of the attention the traced prefills NEEDED
over the summed device time of the kernel's calls in the traced window.

A call computes a BUCKET: its output's shape in the trace, ``(rows x
heads, positions, head_dim)``, says which and how many rows. What was
needed is less — the causal half of each window a prompt reaches, the
summaries its queries see, nothing of the padded tail — and the engine
counts it at admission from each row's true length
(``ServingEngine._count_eva_prefill``; ``eva_prefill_by_bucket`` in its
``metrics()``: rows, positions, (query, key) and (query, summary) pairs
by bucket). The runner reads the engine at the window's two ends, and the
traced stretch is the window's last seconds, so the need of a traced call
is the need a row a layer of the WINDOW's prefills of its bucket, counted
not guessed; every traced call's prefill is among them. FLOPs and bytes
from the counts as ``eva_flops.eva_prefill_attention`` reckons them; least
time by ``flops.least_time_s`` over the traced calls' needs together.
Which bound held and the counts go to ``ctx["notes"]``. A traced window
that held no admission prefill, a program without the kernel or an engine
without the counts (the parent of PR 36) reads None. Args: ``kernel``
(regex on the op's name)."""

import re

from benchmark.flops import least_time_s
from benchmark.harness.trace import op_events

_SHAPE = re.compile(r"\[(\d+),(\d+),(\d+)\]")
_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}
_COUNTS = ("rows", "positions", "local_pairs", "summary_pairs")


def _by_call_length(ctx, W: int) -> dict:
    """positions a kernel call computes -> the window's difference of the
    engine's counts over the buckets that call serves (a bucket is padded
    to whole windows of ``W``)."""
    eng = ctx.get("engine") or {}
    after = (eng.get("after") or {}).get("eva_prefill_by_bucket") or {}
    before = (eng.get("before") or {}).get("eva_prefill_by_bucket") or {}
    out = {}
    for b, v in after.items():
        acc = out.setdefault(-(-int(b) // W) * W, dict.fromkeys(_COUNTS, 0))
        for k in _COUNTS:
            acc[k] += v[k] - before.get(b, {}).get(k, 0)
    return out


def read(ctx, *, kernel: str):
    trace = ctx.get("trace")
    if trace is None or "window_size" not in ctx.get("arch", {}):
        return None
    arch, section = ctx["arch"], ctx["section"]
    heads, D = int(arch["num_attention_heads"]), int(arch["head_dim"])
    calls = [e for e in op_events(trace, kernel) if e[2] > 0]
    shapes = [_SHAPE.search(e[3]) or _SHAPE.search(e[0]) for e in calls]
    window = _by_call_length(ctx, int(arch["window_size"]))
    if not calls or None in shapes or not window:
        return None
    el = heads * D * _BYTES[section["dtype"]]
    flops = nbytes = 0.0
    buckets = set()
    for m in shapes:                        # one call a layer a prefill
        rows, positions = int(m.group(1)) // heads, int(m.group(2))
        need = window.get(positions)
        if not need or need["rows"] <= 0:
            return None
        share = rows / need["rows"]
        flops += share * 4.0 * D * heads * (need["local_pairs"]
                                            + need["summary_pairs"])
        nbytes += share * el * (4 * need["positions"] + 2 * (
            need["positions"] // int(arch["chunk_size"])))
        buckets.add(positions)
    busy_s = sum(e[2] for e in calls) / 1e9
    least = least_time_s(flops, nbytes, ctx["peaks"])
    ctx.setdefault("notes", []).append(
        f"eva_prefill_roofline: {least['bound']}-bound, least "
        f"{least['seconds'] * 1e3:.4f} ms of {busy_s * 1e3:.4f} ms in "
        f"{len(calls)} traced calls over buckets of {sorted(buckets)} "
        f"positions ({flops:.4g} FLOPs needed, by the engine's counts of "
        f"the window's prefills)")
    return 100.0 * least["seconds"] / busy_s
