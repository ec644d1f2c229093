"""``runners/serve_model.py`` with the reference check's prompt lengths
taken from the cell's traffic mix (``check_prompt_lens``).

``serve_model``'s check prompts are 64 and 100 tokens, the Llama runner's:
below a model's window nothing the window forces is compared. A mix whose
model behaves differently past a position names the lengths the check has
to reach; this runner sets them as ``serve_model.CHECK_PROMPTS`` — the name
its ``run`` reads when called — for this process and calls it. The loop,
the gates and the reduction are ``serve_model``'s, imported, not copied.
(A ``benchmark`` PR folds this into ``serve_model``: PERF.md section 7.)
"""

from __future__ import annotations

from benchmark.runners import serve_model


def run(cell: dict, args, devices, t_start: float, watch) -> dict:
    lens = cell["mix"].get("check_prompt_lens")
    if lens:
        serve_model.CHECK_PROMPTS = tuple(int(n) for n in lens)
    return serve_model.run(cell, args, devices, t_start, watch)
