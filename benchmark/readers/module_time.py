"""Device time of one run of a compiled program, median over the runs in
the traced window, from the trace's ``XLA Modules`` line, in ms. Args:
``pattern`` (regex on the module name, found by looking at one trace by
hand), ``divide_by`` (a key of the configuration's section, e.g.
``chunk_size`` to turn a chunk into a step; optional)."""

from benchmark.harness.stats import median
from benchmark.harness.trace import module_runs


def read(ctx, *, pattern: str, divide_by: str = None):
    trace = ctx.get("trace")
    if trace is None:
        return None
    runs = module_runs(trace, pattern)
    if not runs:
        return None
    per = float(ctx["section"][divide_by]) if divide_by else 1.0
    return median(runs) / 1e6 / per
