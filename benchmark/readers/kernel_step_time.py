"""Summed device time of a named kernel per step of the program that holds
it, in ms: the kernel's op events inside the runs of ``module`` in the
traced window, over (runs x ``steps_per_run``). Args: ``kernel`` (regex on
the op's name or detail), ``module`` (regex on the module name),
``steps_per_run`` (a key of the configuration's section, e.g.
``chunk_size``; optional). A kernel that is asked for and is not in the
trace fails the run: it never reads 0."""

from benchmark.harness.trace import kernel_ns_per_run


def read(ctx, *, kernel: str, module: str, steps_per_run: str = None):
    trace = ctx.get("trace")
    if trace is None:
        return None
    per = float(ctx["section"][steps_per_run]) if steps_per_run else 1.0
    return kernel_ns_per_run(trace, kernel, module) / 1e6 / per
