"""Share of its roofline the two-leaf decode attention kernel
(``decode_attention_pair``) reaches in a decode step of an EVA model, in
percent: the least time the chip could take to stream the live entries of
both leaves of one step over the kernel's summed device time per step.

Least time = (live window rows a chunk x a window row's K and V bytes over
the layers + visible summaries a chunk x a summary's) /
``peaks["hbm_bytes_per_s"]``, from the engine's
``live_window_positions_total`` / ``live_summary_positions_total``
differences over the window's chunks and its
``cache_bytes_per_position_window`` / ``_summary``. A row's live entries
are counted at the START of a chunk (its window leaf gains a row a step,
up to 15 more by the chunk's end, about 1 % of 1700) and a row that
finishes inside a chunk for all of it.

Kernel time = the kernel's op events inside the runs of the chunk program
in the traced window, per run, over ``chunk_size``. Memory-bound by
construction; bound and bytes go to ``ctx["notes"]``. A kernel that is not
in the trace fails the run; an engine without the counters (a program
before PR 36) reads None. Args: ``kernel``, ``module`` (regexes)."""

from benchmark.harness.trace import kernel_ns_per_run


def live_per_chunk(ctx):
    """(window rows, summaries) live a chunk over the window, or None
    where the engine lacks the counters or dispatched no chunk."""
    eng = ctx.get("engine")
    if not eng or "live_summary_positions_total" not in eng["after"]:
        return None
    m0, m1 = eng["before"], eng["after"]
    chunks = m1["chunk_dispatches"] - m0["chunk_dispatches"]
    if chunks <= 0:
        return None
    return tuple((m1[k] - m0[k]) / chunks for k in (
        "live_window_positions_total", "live_summary_positions_total"))


def read(ctx, *, kernel: str, module: str):
    live = live_per_chunk(ctx) if ctx.get("trace") is not None else None
    if live is None:
        return None
    m1 = ctx["engine"]["after"]
    per_step_s = (kernel_ns_per_run(ctx["trace"], kernel, module) / 1e9
                  / float(ctx["section"]["chunk_size"]))
    win, summ = live
    nbytes = (win * m1["cache_bytes_per_position_window"]
              + summ * m1["cache_bytes_per_position_summary"])
    least_s = nbytes / ctx["peaks"]["hbm_bytes_per_s"]
    ctx.setdefault("notes", []).append(
        f"eva_attn_roofline: memory-bound, least {least_s * 1e3:.4f} ms of "
        f"{per_step_s * 1e3:.4f} ms a step ({win:.1f} live window rows a "
        f"chunk, {summ:.1f} visible summaries, {nbytes:.4g} bytes a step)")
    return 100.0 * least_s / per_step_s
