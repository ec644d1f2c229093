"""Share of its roofline a whole decode step of an EVA model reaches, in
percent: the least time the chip could take for one step of the batch over
the chunk program's device time a step.

Least time = max(bytes / ``peaks["hbm_bytes_per_s"]``, operations /
``peaks["bf16_flops"]``) with the bytes and operations of
``eva_flops.eva_decode_step``: the section's weights (the head of all
``num_pred_heads`` vocabularies with them), the live window rows and
visible summaries a chunk (``eva_attn_roofline.live_per_chunk``) x the
engine's bytes a row of each leaf, and the rows a step writes; rows = the
window's mean occupied slots. It counts the work the model needs, so it
reads the same whatever implements the step. The engine counts a row's
entries at the START of a chunk, as ``eva_attn_roofline`` says.

Device time a step = the median run of the chunk program in the traced
window over ``chunk_size``. The counters span the whole window and the
device time its traced last seconds: the cell that reports this is a closed
loop, stationary over the window. Which bound held and the bytes go to
``ctx["notes"]``. An engine without the counters or a trace without the
chunk program reads None. Args: ``module`` (regex on the chunk program's
name)."""

from benchmark.eva_flops import eva_decode_step
from benchmark.flops import least_time_s
from benchmark.harness.stats import median
from benchmark.harness.trace import module_runs
from benchmark.readers.eva_attn_roofline import live_per_chunk
from benchmark.readers.occupancy_delta import read as occupancy

_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def read(ctx, *, module: str):
    trace = ctx.get("trace")
    live = live_per_chunk(ctx) if trace is not None else None
    runs = module_runs(trace, module) if live else None
    if not runs:
        return None
    arch, section = ctx["arch"], ctx["section"]
    m1 = ctx["engine"]["after"]
    occupied = occupancy(ctx)            # percent of the slots, or None
    rows = int(section["num_slots"]) * (
        1.0 if occupied is None else occupied / 100.0)
    need = eva_decode_step(
        layers=int(section["num_hidden_layers"]),
        hidden=int(arch["hidden_size"]), ffn=int(arch["intermediate_size"]),
        heads=int(arch["num_attention_heads"]),
        head_dim=int(arch["head_dim"]),
        head_rows=int(arch["num_pred_heads"]) * int(arch["vocab_size"]),
        chunk=int(arch["chunk_size"]), rows=rows,
        live_window=live[0], live_summary=live[1],
        bytes_window_row=float(m1["cache_bytes_per_position_window"]),
        bytes_summary_row=float(m1["cache_bytes_per_position_summary"]),
        bytes_per_el=_BYTES[section["dtype"]])
    per_step_s = median(runs) / 1e9 / float(section["chunk_size"])
    least = least_time_s(need["flops"], need["bytes"], ctx["peaks"])
    ctx.setdefault("notes", []).append(
        f"eva_step_roofline: {least['bound']}-bound, least "
        f"{least['seconds'] * 1e3:.4f} ms of {per_step_s * 1e3:.4f} ms a "
        f"step ({need['weight_bytes']:.4g} bytes of weights + "
        f"{need['leaf_bytes']:.4g} of live leaves + "
        f"{need['written_bytes']:.4g} written, {need['flops']:.4g} FLOPs)")
    return 100.0 * least["seconds"] / per_step_s
