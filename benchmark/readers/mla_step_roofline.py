"""Share of its roofline a whole decode step of a model with multi-head
latent attention and routed feed-forwards reaches, in percent: the least
time the chip could take for one step of the batch over the chunk
program's device time a step.

Least time = max(bytes / ``peaks["hbm_bytes_per_s"]``, operations /
``peaks["bf16_flops"]``) with the bytes and operations of
``mla_flops.mla_decode_step``: the section's non-expert weights, one
expert's weights per held expert the step's routing touched (the window's
difference of the engine's ``moe_experts_touched_total`` over its chunk
steps), the head's slice, and the latent positions the step read
(``live_latent_positions_total`` over the chunk steps) x a position's
stored bytes in one layer. It counts the work the model and its routing
need, so it reads the same whatever implements them. ``moe_step_roofline``
is this reading for AFMoE's GQA cache.

Device time a step = the median run of the chunk program in the traced
window over ``chunk_size``. Which bound held and the bytes go to
``ctx["notes"]``. An engine without the counters (a program without latent attention)
or a trace without the chunk program reads None. Args: ``module`` (regex
on the chunk program's name)."""

from benchmark.adapters.axk1 import at_depth
from benchmark.flops import least_time_s
from benchmark.harness.stats import median
from benchmark.harness.trace import module_runs
from benchmark.mla_flops import mla_decode_step
from benchmark.readers.mla_decode_roofline import latent_per_step
from benchmark.readers.occupancy_delta import read as occupancy

_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def step_need(ctx):
    """``mla_decode_step`` of the window's mean step, or None where the
    engine lacks the counters."""
    eng = ctx.get("engine")
    live = latent_per_step(ctx)
    if live is None or "moe_experts_touched_total" not in eng["after"]:
        return None
    m0, m1 = eng["before"], eng["after"]
    section = ctx["section"]
    steps = (m1["chunk_dispatches"] - m0["chunk_dispatches"]) \
        * float(section["chunk_size"])
    occupied = occupancy(ctx)
    rows = int(section["num_slots"]) * (
        1.0 if occupied is None else occupied / 100.0)

    def per(key):
        return (m1[key] - m0[key]) / steps
    return mla_decode_step(
        arch=at_depth(ctx["arch"], section["num_hidden_layers"]),
        rows=rows, experts_touched=per("moe_experts_touched_total"),
        pairs_held=per("moe_pairs_held_total"), live_latent=live,
        bytes_per_position=m1["cache_bytes_per_position"]
        / m1["cache_layers"],
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
        f"mla_step_roofline: {least['bound']}-bound, least "
        f"{least['seconds'] * 1e3:.4f} ms of {per_step_s * 1e3:.4f} ms a "
        f"step ({need['weight_bytes']:.4g} bytes of weights, of them "
        f"{need['expert_bytes']:.4g} of touched experts, + "
        f"{need['latent_bytes']:.4g} of latent, {need['flops']:.4g} FLOPs)")
    return 100.0 * least["seconds"] / per_step_s
