"""Share of its roofline the ``decode_attention`` kernel reaches in a decode
step, in percent: the least time the chip could take to stream the live KV
of one step over the kernel's summed device time per step.

Least time = live positions per chunk (the window's difference of the
engine's ``live_kv_positions_total`` over its difference of
``chunk_dispatches``) x layers x 2 (K and V) x KV heads x head size x bytes
of the section's dtype / ``peaks["hbm_bytes_per_s"]``. The engine counts a
row's position at the START of a chunk, which every step of the chunk must
at least read (step t reads t + 1 more), so for a row that lives through
the chunk the bytes are a lower bound. Not so for a row that FINISHES inside
the chunk: it is counted for all ``chunk_size`` steps, though after its last
token a kernel that skipped finished rows would read nothing of it (today's
kernel streams every row's whole cache, finished or not). With budgets of
32-256 tokens a request rides 2-16 chunks and over-counts about half of its
last one, a few percent of its bytes (less what the uncounted ``t + 1`` of
the live rows gives back); at a share of 10 % that is inside the rounding,
but a kernel near 100 % needs the count taken per step, and a reading over
100 would mean this and not a fast kernel.

Kernel time = the kernel's op events inside the runs of the chunk program
in the traced window, per run, over ``chunk_size``. The counters span the
whole window and the kernel time its traced last seconds: the cells that
report this are closed loops, which are stationary over the window.
Memory-bound by construction (a decode step's attention does 2 FLOPs a
byte); bound and bytes go to ``ctx["notes"]``. A kernel that is not in the
trace fails the run; an engine without the counter reads None."""

from benchmark.harness.trace import kernel_ns_per_run

_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def read(ctx, *, kernel: str, module: str):
    trace, eng = ctx.get("trace"), ctx.get("engine")
    if trace is None or not eng:
        return None
    m0, m1 = eng["before"], eng["after"]
    if "live_kv_positions_total" not in m1:
        return None
    chunks = m1["chunk_dispatches"] - m0["chunk_dispatches"]
    if chunks <= 0:
        return None
    arch, section = ctx["arch"], ctx["section"]
    per_step_s = (kernel_ns_per_run(trace, kernel, module) / 1e9
                  / float(section["chunk_size"]))
    live = (m1["live_kv_positions_total"]
            - m0["live_kv_positions_total"]) / chunks
    nbytes = (live * int(section["num_hidden_layers"]) * 2
              * int(arch["num_key_value_heads"]) * int(arch["head_dim"])
              * _BYTES[section["dtype"]])
    least_s = nbytes / ctx["peaks"]["hbm_bytes_per_s"]
    ctx.setdefault("notes", []).append(
        f"decode_attn_roofline: memory-bound, least {least_s * 1e3:.4f} ms "
        f"of {per_step_s * 1e3:.4f} ms a step ({live:.1f} live positions a "
        f"chunk, {nbytes:.4g} bytes a step)")
    return 100.0 * least_s / per_step_s
