"""Share of its roofline the ``decode_attention`` kernel reaches in a decode
step of a model whose cache buffers have two lengths, in percent: the least
time the chip could take to stream the live KV of one step over the
kernel's summed device time per step.

Least time = (live positions a chunk in the full-length caches x their K
and V bytes a position + live positions in the rolling buffers x theirs) /
``peaks["hbm_bytes_per_s"]``, from the engine's
``live_kv_positions_total`` / ``live_window_positions_total`` differences
over the window's chunks and its ``cache_bytes_per_position_full`` /
``_window`` (the accepted ``decode_attn_roofline`` multiplies one live count
by the layers, which over-counts a windowed layer once a row passes its
window). A row's position is counted at the START of a chunk and a row
that finishes inside a chunk for all of it: ``decode_attn_roofline`` says
what that does to a reading near 100 %.

Kernel time = the kernel's op events inside the runs of the chunk program
in the traced window, per run, over ``chunk_size``. Memory-bound by
construction; bound and bytes go to ``ctx["notes"]``. A kernel that is not
in the trace fails the run; an engine without the counters reads None."""

from benchmark.harness.trace import kernel_ns_per_run


def read(ctx, *, kernel: str, module: str):
    trace, eng = ctx.get("trace"), ctx.get("engine")
    if trace is None or not eng:
        return None
    m0, m1 = eng["before"], eng["after"]
    if "live_window_positions_total" not in m1:
        return None
    chunks = m1["chunk_dispatches"] - m0["chunk_dispatches"]
    if chunks <= 0:
        return None
    per_step_s = (kernel_ns_per_run(trace, kernel, module) / 1e9
                  / float(ctx["section"]["chunk_size"]))
    full = (m1["live_kv_positions_total"]
            - m0["live_kv_positions_total"]) / chunks
    win = (m1["live_window_positions_total"]
           - m0["live_window_positions_total"]) / chunks
    nbytes = (full * m1["cache_bytes_per_position_full"]
              + win * m1["cache_bytes_per_position_window"])
    least_s = nbytes / ctx["peaks"]["hbm_bytes_per_s"]
    ctx.setdefault("notes", []).append(
        f"window_attn_roofline: memory-bound, least {least_s * 1e3:.4f} ms "
        f"of {per_step_s * 1e3:.4f} ms a step ({full:.1f} live positions a "
        f"chunk in the full caches, {win:.1f} in the rolling buffers, "
        f"{nbytes:.4g} bytes a step)")
    return 100.0 * least_s / per_step_s
