"""Share of its roofline a whole decode step of a model with routed
feed-forwards reaches, in percent: the least time the chip could take for
one step of the batch over the chunk program's device time a step.

Least time = max(bytes / ``peaks["hbm_bytes_per_s"]``, operations /
``peaks["bf16_flops"]``) with the bytes and operations of
``moe_flops.moe_decode_step``: the section's non-expert weights, one
expert's weights per held expert the step's routing touched (the window's
difference of the engine's ``moe_experts_touched_total`` over its chunk
steps), the head's slice, and the live cache positions BY LAYER KIND (the
differences of ``live_kv_positions_total`` and
``live_window_positions_total`` a chunk x the engine's bytes a position of
each kind). It counts the work the model and its routing need, so it reads
the same whatever implements the experts. The engine counts a row's
position at the START of a chunk, as ``loop_step_roofline`` says.

Device time a step = the median run of the chunk program in the traced
window over ``chunk_size``. The counters span the whole window and the
device time its traced last seconds: the cells that report this are closed
loops, stationary over the window. Which bound held and the bytes go to
``ctx["notes"]``. An engine without the counters (a program before PR 33)
or a trace without the chunk program reads None. Args: ``module`` (regex on
the chunk program's name)."""

from benchmark.adapters.afmoe import at_depth
from benchmark.flops import least_time_s
from benchmark.harness.stats import median
from benchmark.harness.trace import module_runs
from benchmark.moe_flops import moe_decode_step
from benchmark.readers.occupancy_delta import read as occupancy

_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def step_need(ctx):
    """``moe_decode_step`` of the window's mean step, or None where the
    engine lacks the counters."""
    eng = ctx.get("engine")
    if not eng or "moe_experts_touched_total" not in eng["after"]:
        return None
    m0, m1 = eng["before"], eng["after"]
    chunks = m1["chunk_dispatches"] - m0["chunk_dispatches"]
    if chunks <= 0:
        return None
    section = ctx["section"]
    steps = chunks * float(section["chunk_size"])
    occupied = occupancy(ctx)            # percent of the slots, or None
    rows = int(section["num_slots"]) * (
        1.0 if occupied is None else occupied / 100.0)

    def per(key, n):
        return (m1[key] - m0[key]) / n
    return moe_decode_step(
        arch=at_depth(ctx["arch"], section["num_hidden_layers"]),
        rows=rows,
        experts_touched=per("moe_experts_touched_total", steps),
        pairs_held=per("moe_pairs_held_total", steps),
        live_full=per("live_kv_positions_total", chunks),
        live_window=per("live_window_positions_total", chunks),
        bytes_full=float(m1["cache_bytes_per_position_full"]),
        bytes_window=float(m1["cache_bytes_per_position_window"]),
        bytes_per_el=_BYTES[section["dtype"]])


def read(ctx, *, module: str):
    trace = ctx.get("trace")
    need = step_need(ctx) if trace is not None else None
    runs = module_runs(trace, module) if need else None
    if not runs:
        return None
    per_step_s = median(runs) / 1e9 / float(ctx["section"]["chunk_size"])
    least = least_time_s(need["flops"], need["bytes"], ctx["peaks"])
    ctx.setdefault("notes", []).append(
        f"moe_step_roofline: {least['bound']}-bound, least "
        f"{least['seconds'] * 1e3:.4f} ms of {per_step_s * 1e3:.4f} ms a "
        f"step ({need['weight_bytes']:.4g} bytes of weights, of them "
        f"{need['expert_bytes']:.4g} of touched experts, + "
        f"{need['kv_bytes']:.4g} of KV, {need['flops']:.4g} FLOPs)")
    return 100.0 * least["seconds"] / per_step_s
