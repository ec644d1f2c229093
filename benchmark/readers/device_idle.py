"""Share of the traced window in which no operation ran on the device, in
percent: 1 - (union of the op intervals / window), averaged over the chips
used."""

from benchmark.harness.trace import busy_seconds


def read(ctx):
    trace = ctx.get("trace")
    if trace is None:
        return None
    b = busy_seconds(trace)
    return 100.0 * (1.0 - b["busy_s"] / b["window_s"])
