"""Share of its roofline one call of the banded flash forward kernel
reaches, in percent: the least time of the call
(``moe_flops.banded_flash_fwd`` through ``flops.least_time_s``) over the
median device time of the kernel's calls inside the admission prefills of
the traced window.

The decoder runs the banded kernel only where the band cuts the square:
in the prefill bucket longer than the window, which with buckets of powers
of two up to ``max_len`` = 2 x window is the ``max_len`` bucket alone, so
every call has one shape (batch 1, ``max_len`` queries and keys, the
query heads, the configuration's window). Which bound held goes to
``ctx["notes"]``. A traced window that held no such prefill, or a program
without the kernel, reads None. Args: ``kernel`` (regex on the op's name)."""

from benchmark.adapters.afmoe import at_depth
from benchmark.flops import least_time_s
from benchmark.harness.stats import median
from benchmark.harness.trace import op_events
from benchmark.moe_flops import banded_flash_fwd


def read(ctx, *, kernel: str):
    trace = ctx.get("trace")
    if trace is None:
        return None
    calls = [d for _, _, d, _ in op_events(trace, kernel) if d > 0]
    if not calls:
        return None
    section = ctx["section"]
    a = at_depth(ctx["arch"], section["num_hidden_layers"])
    need = banded_flash_fwd(
        seq=int(section["max_len"]), window=int(a["sliding_window"]),
        heads=int(a["num_attention_heads"]), head_dim=int(a["head_dim"]))
    least = least_time_s(need["flops"], need["bytes"], ctx["peaks"])
    per_call_s = median(calls) / 1e9
    ctx.setdefault("notes", []).append(
        f"flash_fwd_band_roofline: {least['bound']}-bound, least "
        f"{least['seconds'] * 1e3:.4f} ms of {per_call_s * 1e3:.4f} ms a "
        f"call ({len(calls)} calls in the traced window, "
        f"{need['flops']:.4g} FLOPs, {need['bytes']:.4g} bytes)")
    return 100.0 * least["seconds"] / per_call_s
