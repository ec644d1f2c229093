"""Share of its roofline the flash-attention kernels reach in a training
step, in percent: the least time the chip could take for one step's
attention (the larger of FLOPs over the bf16 peak and bytes over the HBM
peak, both from the cell's fixed batch, sequence, heads and head size by
``benchmark/flops.py``) over the kernels' summed device time per step.
Args: ``kernels`` (regex on the op's name or detail), ``module`` (regex of
the step program). Which bound held goes to ``ctx["notes"]``. Kernels that
are not in the trace fail the run."""

from benchmark.flops import flash_attention_step, least_time_s
from benchmark.harness.trace import kernel_ns_per_run


def read(ctx, *, kernels: str, module: str):
    trace = ctx.get("trace")
    if trace is None:
        return None
    per_step_s = kernel_ns_per_run(trace, kernels, module) / 1e9
    arch, mix = ctx["arch"], ctx["mix"]
    need = flash_attention_step(
        batch=int(mix["batch"]), seq=int(mix["seq"]),
        heads=int(arch["num_attention_heads"]),
        kv_heads=int(arch["num_key_value_heads"]),
        head_dim=int(arch["head_dim"]),
        layers=int(ctx["section"]["num_hidden_layers"]))
    least = least_time_s(need["flops"], need["bytes"], ctx["peaks"])
    ctx.setdefault("notes", []).append(
        f"flash_roofline: {least['bound']}-bound, least "
        f"{least['seconds'] * 1e3:.4f} ms of {per_step_s * 1e3:.4f} ms a "
        f"step ({need['flops']:.4g} FLOPs, {need['bytes']:.4g} bytes)")
    return 100.0 * least["seconds"] / per_step_s
