"""Share of its roofline a whole decode step reaches, in percent: the least
time the chip could take for one step of the batch over the chunk program's
device time a step.

Least time = max(bytes / ``peaks["hbm_bytes_per_s"]``, operations /
``peaks["bf16_flops"]``) with the bytes and operations of
``loop_flops.looped_decode_step``: the section's layer weights once per
pass (``arch["total_ut_steps"]``) + the head + the live cache positions a
chunk (the window's difference of ``live_kv_positions_total`` over that of
``chunk_dispatches``) x the engine's ``cache_bytes_per_position``; rows =
the window's mean occupied slots. It counts the work the model needs, not
what today's attention reads (XLA's masked attention streams every buffer
whole), so it reads the same whatever implements the step. The engine
counts a row's position at the START of a chunk (every step of the chunk
reads at least that), and a row that finishes inside a chunk is counted
for all of it: with outputs of 128-608 tokens that over-counts a few
percent of the KV, itself a quarter of the bytes.

Device time a step = the median run of the chunk program in the traced
window (``XLA Modules`` line) over ``chunk_size``. The counters span the
whole window and the device time its traced last seconds: the cells that
report this are closed loops, stationary over the window. Which bound held
and the bytes go to ``ctx["notes"]``. An engine without the counters (a
program before PR 28) or a trace without the chunk program reads None.
Args: ``module`` (regex on the chunk program's name), ``norms`` (RMSNorm
weights a block: 4 in a sandwich block)."""

from benchmark.flops import least_time_s
from benchmark.harness.stats import median
from benchmark.harness.trace import module_runs
from benchmark.loop_flops import looped_decode_step
from benchmark.readers.occupancy_delta import read as occupancy

_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def read(ctx, *, module: str, norms: int):
    trace, eng = ctx.get("trace"), ctx.get("engine")
    if trace is None or not eng:
        return None
    m0, m1 = eng["before"], eng["after"]
    if "cache_bytes_per_position" not in m1:
        return None
    chunks = m1["chunk_dispatches"] - m0["chunk_dispatches"]
    runs = module_runs(trace, module)
    if chunks <= 0 or not runs:
        return None
    arch, section = ctx["arch"], ctx["section"]
    passes = int(arch["total_ut_steps"])
    per_step_s = median(runs) / 1e9 / float(section["chunk_size"])
    live = (m1["live_kv_positions_total"]
            - m0["live_kv_positions_total"]) / chunks
    occupied = occupancy(ctx)            # percent of the slots, or None
    rows = int(section["num_slots"]) * (
        1.0 if occupied is None else occupied / 100.0)
    need = looped_decode_step(
        layers=int(section["num_hidden_layers"]),
        loop_steps=passes, hidden=int(arch["hidden_size"]),
        ffn=int(arch["intermediate_size"]),
        heads=int(arch["num_attention_heads"]),
        kv_heads=int(arch["num_key_value_heads"]),
        head_dim=int(arch["head_dim"]), vocab=int(arch["vocab_size"]),
        rows=rows, live_positions=live,
        kv_bytes_per_position=float(m1["cache_bytes_per_position"]),
        norms=int(norms), bytes_per_el=_BYTES[section["dtype"]])
    least = least_time_s(need["flops"], need["bytes"], ctx["peaks"])
    ctx.setdefault("notes", []).append(
        f"loop_step_roofline: {least['bound']}-bound, least "
        f"{least['seconds'] * 1e3:.4f} ms of {per_step_s * 1e3:.4f} ms a "
        f"step ({need['weight_bytes']:.4g} bytes of weights over "
        f"{passes} passes + {need['kv_bytes']:.4g} of KV at "
        f"{live:.1f} live positions a chunk, {need['flops']:.4g} FLOPs)")
    return 100.0 * least["seconds"] / per_step_s
