"""Share of the entries a decode step attends over that are chunk
summaries, in percent: the window's difference of the engine's
``live_summary_positions_total`` over that of it and
``live_window_positions_total`` together. What the traffic asks of the
mechanism: near 0 the rows sit in their first window and the model is a
plain MHA decoder; at a mean context of 11 k positions about 700 of 1700
entries are summaries. An engine without the counters (a program before
PR 36) reads None."""

from benchmark.readers.eva_attn_roofline import live_per_chunk


def read(ctx):
    live = live_per_chunk(ctx)
    if live is None or sum(live) <= 0:
        return None
    return 100.0 * live[1] / sum(live)
