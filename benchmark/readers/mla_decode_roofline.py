"""Share of its roofline the latent decode attention kernel
(``mla_decode_attention``) reaches in a decode step of a model with
multi-head latent attention, in percent: the least time the chip could take
for the absorbed attention of one step over the kernel's summed device time
a step.

Least time = max(live latent positions a step x the bytes a position is
stored in / ``peaks["hbm_bytes_per_s"]``, 2 x heads x (2 kv_lora_rank +
qk_rope_head_dim) operations a live position / ``peaks["bf16_flops"]``)
(``mla_flops.mla_decode_attention``), from the window's difference of the
engine's ``live_latent_positions_total`` (the positions the steps read,
over the occupied rows, the chunks' steps and the latent layers) over its
chunk steps, and a position's stored bytes in one layer
(``cache_bytes_per_position`` over ``cache_layers``).

Kernel time = the kernel's op events inside the runs of the chunk program
in the traced window, per run, over ``chunk_size``. The counters span the
whole window and the device time its traced last seconds: the cell is a
closed loop, stationary over the window. Bound and bytes go to
``ctx["notes"]``. An engine without the counter (a program without latent attention)
reads None; with it, a kernel that is not in the trace fails the run.
Args: ``kernel``, ``module`` (regexes)."""

from benchmark.adapters.axk1 import at_depth
from benchmark.flops import least_time_s
from benchmark.harness.trace import kernel_ns_per_run
from benchmark.mla_flops import mla_decode_attention


def latent_per_step(ctx):
    """Latent positions a decode step read over the window (over rows and
    layers), or None where the engine lacks the counter or ran no chunk."""
    eng = ctx.get("engine")
    if not eng or "live_latent_positions_total" not in eng["after"]:
        return None
    m0, m1 = eng["before"], eng["after"]
    steps = (m1["chunk_dispatches"] - m0["chunk_dispatches"]) \
        * float(ctx["section"]["chunk_size"])
    live = m1["live_latent_positions_total"] \
        - m0["live_latent_positions_total"]
    if steps <= 0 or live <= 0:
        return None
    return live / steps


def read(ctx, *, kernel: str, module: str):
    live = latent_per_step(ctx) if ctx.get("trace") is not None else None
    if live is None:
        return None
    m1 = ctx["engine"]["after"]
    section = ctx["section"]
    a = at_depth(ctx["arch"], section["num_hidden_layers"])
    need = mla_decode_attention(
        live_positions=live, heads=int(a["num_attention_heads"]),
        kv_lora_rank=int(a["kv_lora_rank"]),
        rope_dim=int(a["qk_rope_head_dim"]),
        bytes_per_position=m1["cache_bytes_per_position"]
        / m1["cache_layers"])
    per_step_s = (kernel_ns_per_run(ctx["trace"], kernel, module) / 1e9
                  / float(section["chunk_size"]))
    least = least_time_s(need["flops"], need["bytes"], ctx["peaks"])
    ctx.setdefault("notes", []).append(
        f"mla_decode_roofline: {least['bound']}-bound, least "
        f"{least['seconds'] * 1e3:.4f} ms of {per_step_s * 1e3:.4f} ms a "
        f"step ({live:.1f} latent positions a step over the layers, "
        f"{need['bytes']:.4g} bytes, {need['flops']:.4g} FLOPs)")
    return 100.0 * least["seconds"] / per_step_s
