"""Held experts with at least one token, a routed layer a decode step: the
window's difference of the engine's ``moe_experts_touched_total``
(``serving.moe.experts_touched``: summed over the chunks' steps and routed
layers) over its chunk steps and the routed layers of the section. What the
routing asked of a step, whatever implements the experts: each touched
expert is 56.6 MB of weights to read at the published widths. An engine
without the counter (a program before PR 33) reads None."""

from benchmark.adapters.afmoe import at_depth


def read(ctx):
    eng = ctx.get("engine")
    if not eng or "moe_experts_touched_total" not in eng["after"]:
        return None
    m0, m1 = eng["before"], eng["after"]
    section = ctx["section"]
    steps = (m1["chunk_dispatches"] - m0["chunk_dispatches"]) \
        * int(section["chunk_size"])
    a = at_depth(ctx["arch"], section["num_hidden_layers"])
    routed = a["num_hidden_layers"] - a["num_dense_layers"]
    if steps <= 0 or routed <= 0:
        return None
    return (m1["moe_experts_touched_total"]
            - m0["moe_experts_touched_total"]) / (steps * routed)
