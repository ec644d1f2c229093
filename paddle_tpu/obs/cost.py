"""Compiled-program cost telemetry.

The accounting discipline of Pope et al. (2022, "Efficiently Scaling
Transformer Inference"): a serving number without its FLOPs/bytes
denominator is not evidence. XLA already knows both for every compiled
program — ``compiled.cost_analysis()`` (model FLOPs, bytes accessed)
and ``compiled.memory_analysis()`` (argument/output/temp bytes) — so
the dispatch wrappers attach them to the owning span and every bench
record can report tokens/s AND model-FLOPs-utilisation per dispatch.

The analysis is derived ONCE per (site, input-signature) via
``jitted.lower(...).compile()`` and cached here: the AOT lowering path
may recompile the program (it does not always share the jit dispatch
cache), so this is strictly obs-gated, amortized to one extra compile
per site, and any failure degrades to "no cost attached" — telemetry
never breaks the dispatch it measures. jax.export-deserialized bundle
entries expose no analysis hooks; bundle dispatch spans carry timing
only (documented in README).

Always on beside it: which dispatch site compiled. ``dispatch_site``
names the site for the length of one dispatch and one ``jax.monitoring``
listener (:func:`watch_compiles`) credits every backend compile to the
site it fell inside, or to ``other`` — the ``obs.compiles.<site>``
counters of the global registry, read back by :func:`compile_counts`.
"""

from __future__ import annotations

import re
import threading
from typing import Any, Dict, Optional, Tuple

from paddle_tpu.obs.metrics import metrics

__all__ = ["dispatch_cost", "site_costs", "clear_cost_cache",
           "program_census", "device_peak_flops", "mfu",
           "dispatch_site", "watch_compiles", "compile_counts"]

_CACHE: Dict[Tuple, Optional[dict]] = {}
_BY_SITE: Dict[str, dict] = {}      # latest successful analysis per site
_LOCK = threading.Lock()


_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_COMPILES = "obs.compiles."
_SITE = threading.local()
_WATCHING = False


class dispatch_site:
    """``with dispatch_site("decode.chunk"):`` around one dispatch: a
    backend compile inside it (same thread) is credited to the site."""

    __slots__ = ("site", "_prev")

    def __init__(self, site: str):
        self.site = site

    def __enter__(self):
        self._prev = getattr(_SITE, "site", None)
        _SITE.site = self.site
        return self

    def __exit__(self, *exc):
        _SITE.site = self._prev
        return False


def _on_compile(event, duration, **kw):
    if event == _COMPILE_EVENT:
        metrics.counter(
            _COMPILES + (getattr(_SITE, "site", None) or "other"),
            "backend compiles inside this dispatch site").inc()


def watch_compiles() -> None:
    """Register the compile listener, once per process."""
    global _WATCHING
    with _LOCK:
        if _WATCHING:
            return
        _WATCHING = True
    import jax.monitoring
    jax.monitoring.register_event_duration_secs_listener(_on_compile)


def compile_counts() -> Dict[str, int]:
    """``{site: backend compiles so far}`` in this process."""
    return {n[len(_COMPILES):]: int(metrics.get(n).value)
            for n in metrics.names() if n.startswith(_COMPILES)}


def _sig(args, kwargs) -> Tuple:
    """Hashable shape/dtype signature of a dispatch's inputs — static
    kwargs (ints/strs/bools/None) hash as themselves."""
    import jax

    def leaf(x):
        if hasattr(x, "shape") and hasattr(x, "dtype"):
            return (tuple(x.shape), str(x.dtype))
        return x
    flat, _ = jax.tree_util.tree_flatten((args, kwargs))
    return tuple(leaf(x) for x in flat)


# the path element before /pallas_call is the call's name=, inside any
# jvp(...)/transpose(...) wrapping; a call without a name has none
_KERNEL_RE = re.compile(
    r'op_name="[^"]*/(?:\w+\()*([\w.]+)\)*/pallas_call')
_COLLECTIVE_RE = re.compile(
    r"\b(all-reduce|all-gather|reduce-scatter|collective-permute|"
    r"all-to-all)(?:-start)?\(")


def program_census(compiled) -> dict:
    """What a compiled program contains, read off its HLO text: Pallas
    kernels (``tpu_custom_call``s, counted by the ``name=`` of their
    ``pallas_call``) and collectives by kind. In interpret mode (off the
    TPU) a kernel is ordinary HLO, so the kernel count is 0 there."""
    kernels: Dict[str, int] = {}
    collectives: Dict[str, int] = {}
    for line in compiled.as_text().splitlines():
        if 'custom_call_target="tpu_custom_call"' in line:
            m = _KERNEL_RE.search(line)
            name = m.group(1) if m else "unnamed"
            kernels[name] = kernels.get(name, 0) + 1
        m = _COLLECTIVE_RE.search(line)
        if m:
            collectives[m.group(1)] = collectives.get(m.group(1), 0) + 1
    return {"kernels": kernels, "collectives": collectives}


def dispatch_cost(site: str, jitted, args=(), kwargs=None,
                  num_devices: int = 1) -> Optional[dict]:
    """FLOPs/bytes/peak-bytes record for the program ``jitted`` compiles
    at these arguments, or ``None`` when the backend can't say. Cached
    per (site, signature); safe to call per dispatch once obs is on.

    ``num_devices``: mesh size at a SHARDED dispatch site (GSPMD). XLA's
    ``cost_analysis()`` on a partitioned module reports PER-PARTITION
    numbers (verified on this jax: a tp=4 matmul reports global/4 plus
    the collective), so the recorded ``flops`` are already per-device —
    the honest MFU numerator against the per-device peak. The record
    carries ``num_devices`` and the derived ``flops_global`` so nothing
    has to guess which scope a number is in; callers must NOT divide
    again (that would double-count the partitioning)."""
    kwargs = kwargs or {}
    try:
        key = (site, _sig(args, kwargs))
    except Exception:
        return None
    with _LOCK:
        if key in _CACHE:
            return _CACHE[key]
    out: Optional[dict] = None
    try:
        compiled = jitted.lower(*args, **kwargs).compile()
        cost = compiled.cost_analysis()
        if isinstance(cost, (list, tuple)):
            cost = cost[0] if cost else {}
        cost = cost or {}
        out = {}
        if cost.get("flops", -1) and float(cost.get("flops", -1)) > 0:
            out["flops"] = float(cost["flops"])
        ba = cost.get("bytes accessed", cost.get("bytes_accessed"))
        if ba is not None and float(ba) > 0:
            out["bytes_accessed"] = float(ba)
        try:
            mem = compiled.memory_analysis()
            for field, k in (("temp_size_in_bytes", "temp_bytes"),
                             ("argument_size_in_bytes", "argument_bytes"),
                             ("output_size_in_bytes", "output_bytes"),
                             ("alias_size_in_bytes", "alias_bytes")):
                v = getattr(mem, field, None)
                if v is not None:
                    out[k] = int(v)
            if "temp_bytes" in out:
                # outputs that alias a donated argument (a chunk's KV
                # carry) are counted among the outputs and take no new
                # memory
                out["peak_bytes"] = (out["temp_bytes"]
                                     + out.get("output_bytes", 0)
                                     - out.get("alias_bytes", 0))
        except Exception:
            pass
        # the bytes-moved-per-dispatch record (the weight-bandwidth
        # evidence quantized decode is judged by): XLA's "bytes
        # accessed" when the backend reports it, else the
        # argument+output buffer sizes from memory_analysis — both read
        # the program's ACTUAL operand dtypes, so an int8-weight or
        # int8-KV dispatch reports its shrunken byte stream, not a
        # notional fp32 one
        if "bytes_accessed" in out:
            out["bytes_per_dispatch"] = out["bytes_accessed"]
        elif "argument_bytes" in out or "output_bytes" in out:
            out["bytes_per_dispatch"] = (out.get("argument_bytes", 0)
                                         + out.get("output_bytes", 0))
        if out:
            out.update(program_census(compiled))
        if out and int(num_devices) > 1:
            out["num_devices"] = int(num_devices)
            if "flops" in out:
                out["flops_global"] = out["flops"] * int(num_devices)
        if not out:
            out = None
    except Exception:
        out = None
    with _LOCK:
        _CACHE[key] = out
        if out is not None:
            _BY_SITE[site] = dict(out)
    return out


def site_costs() -> Dict[str, dict]:
    """Latest successful cost record per dispatch site — the bench
    ``obs`` block's per-dispatch FLOPs source."""
    with _LOCK:
        return {k: dict(v) for k, v in _BY_SITE.items()}


def clear_cost_cache() -> None:
    with _LOCK:
        _CACHE.clear()
        _BY_SITE.clear()


# bf16 peak FLOP/s of one chip, keyed by jax's ``device_kind`` (Google
# Cloud TPU documentation: v5e 197, v5p 459, v4 275 TFLOP/s). The one
# peaks table: bench.py reads it through ``device_peak_flops``.
DEVICE_PEAK_FLOPS = {
    "TPU v5 lite": 197e12,
    "TPU v5e": 197e12,
    "TPU v5p": 459e12,
    "TPU v4": 275e12,
}


def device_peak_flops() -> Optional[float]:
    """bf16 peak FLOP/s of device 0. None off the TPU: a utilisation is
    a device metric and the CPU harness reports none. A TPU kind that
    is not in the table is an error, never a default."""
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        return None
    kind = str(dev.device_kind)
    if kind not in DEVICE_PEAK_FLOPS:
        raise KeyError(f"no peak FLOP/s on record for device_kind "
                       f"{kind!r}; add it to obs.cost.DEVICE_PEAK_FLOPS "
                       f"with its source")
    return DEVICE_PEAK_FLOPS[kind]


def mfu(flops: float, seconds: float,
        peak: Optional[float] = None) -> Optional[float]:
    """Model-FLOPs-utilisation fraction for ``flops`` of work done in
    ``seconds`` of wall time; None where there is no device peak (off
    the TPU)."""
    if peak is None:
        peak = device_peak_flops()
    if peak is None:
        return None
    if seconds <= 0 or flops <= 0:
        return 0.0
    return flops / seconds / peak
