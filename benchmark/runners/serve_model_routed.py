"""``runners/serve_model_ctx.py`` with the reference check read without the
routing's near-ties: the adapter's ``check_against_reference`` in the place
of ``serve_model._check_against_reference`` for this process's run.

In a model whose feed-forward routes each token to the top K of many
experts, the program (bf16 activations) and the float32 reference choose
differently wherever the K-th and the next score lie closer than their
roundings differ; with random weights a held expert flipped that way moves
a position's logits by about half a standard deviation, as far as storing
the activations one precision lower does (``adapters/axk1.py``, PERF.md
section 6). The adapter's check hands the reference the program's
own choices and judges each against the scores the reference selected on,
within a margin set for rounding; the limits are the adapter's, set for
rounding. The loop, the traffic and the reduction are ``serve_model``'s.
(A ``benchmark`` PR folds this into ``serve_model``: PERF.md section 7.)
"""

from __future__ import annotations

from benchmark.harness import common, resolve
from benchmark.runners import serve_model, serve_model_ctx


def run(cell: dict, args, devices, t_start: float, watch) -> dict:
    adapter = resolve.load_module("adapters", cell["config_file"]["adapter"],
                                  args.root)

    def check(*a):
        """The adapter's check, with the device's peak bytes as the
        allocator reports them before and after it in the check's line:
        the run's ``memory_peak_bytes`` is the window's own only where it
        lies above both."""
        before = common.memory_peak_bytes(devices)
        out = adapter.check_against_reference(*a)
        return {**out, "memory_peak_bytes_before": before,
                "memory_peak_bytes_after": common.memory_peak_bytes(devices)}
    plain = serve_model._check_against_reference
    serve_model._check_against_reference = check
    try:
        return serve_model_ctx.run(cell, args, devices, t_start, watch)
    finally:
        serve_model._check_against_reference = plain
