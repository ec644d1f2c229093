"""Global flag registry — env-overridable, introspectable runtime switches.

TPU-native analog of the reference's gflags-compatible flag registry
(paddle/common/flags.h:373 ``PHI_DEFINE_EXPORTED_*``, paddle/common/flags.cc —
147 exported flags, surfaced to Python via ``get_flags``/``set_flags``).

Flags are declared at import time with a default, a type, and a docstring.
``FLAGS_<name>`` environment variables override the default at first read.
"""

from __future__ import annotations

import os
import threading
from typing import Any, Callable, Dict, Optional

__all__ = ["define_flag", "get_flags", "set_flags", "flags"]

_TRUE = {"1", "true", "yes", "on"}
_FALSE = {"0", "false", "no", "off", ""}


def _parse_bool(s: str) -> bool:
    v = s.strip().lower()
    if v in _TRUE:
        return True
    if v in _FALSE:
        return False
    raise ValueError(f"cannot parse boolean flag value {s!r}")


class _Flag:
    __slots__ = ("name", "default", "type", "help", "_value", "_read_env")

    def __init__(self, name: str, default: Any, type_: Callable, help_: str):
        self.name = name
        self.default = default
        self.type = type_
        self.help = help_
        self._value = default
        self._read_env = False

    def get(self) -> Any:
        if not self._read_env:
            env = os.environ.get("FLAGS_" + self.name)
            if env is not None:
                if self.type is bool:
                    self._value = _parse_bool(env)
                else:
                    self._value = self.type(env)
            self._read_env = True
        return self._value

    def set(self, value: Any) -> None:
        if self.type is bool and isinstance(value, str):
            value = _parse_bool(value)
        else:
            value = self.type(value)
        self._value = value
        self._read_env = True


class _FlagRegistry:
    def __init__(self) -> None:
        self._flags: Dict[str, _Flag] = {}
        self._lock = threading.Lock()

    def define(self, name: str, default: Any, help_: str, type_: Optional[Callable] = None):
        if type_ is None:
            type_ = type(default)
        with self._lock:
            if name in self._flags:
                raise KeyError(f"flag {name!r} already defined")
            self._flags[name] = _Flag(name, default, type_, help_)

    def __getattr__(self, name: str) -> Any:
        try:
            return self._flags[name].get()
        except KeyError:
            raise AttributeError(f"undefined flag {name!r}")

    def __setattr__(self, name: str, value: Any) -> None:
        # ``flags.x = v`` (and a test's ``monkeypatch.setattr(flags, "x",
        # v)``, teardown included) SETS the flag: stored as a plain
        # attribute it would shadow the registry for the rest of the
        # process, and no later ``set_flags`` could reach a reader
        if name.startswith("_"):
            object.__setattr__(self, name, value)
            return
        try:
            self._flags[name].set(value)
        except KeyError:
            raise AttributeError(f"undefined flag {name!r}")

    def get(self, name: str) -> Any:
        return self._flags[name].get()

    def set(self, name: str, value: Any) -> None:
        self._flags[name].set(value)

    def names(self):
        return sorted(self._flags)

    def describe(self, name: str) -> str:
        f = self._flags[name]
        return f"{f.name} (default={f.default!r}): {f.help}"


flags = _FlagRegistry()


def define_flag(name: str, default: Any, help_: str = "", type_: Optional[Callable] = None) -> None:
    flags.define(name, default, help_, type_)


def get_flags(names) -> Dict[str, Any]:
    if isinstance(names, str):
        names = [names]
    return {n: flags.get(n) for n in names}


def set_flags(d: Dict[str, Any]) -> None:
    for k, v in d.items():
        flags.set(k, v)


# ---------------------------------------------------------------------------
# Core flag inventory (analog of paddle/common/flags.cc switchboard).
# ---------------------------------------------------------------------------
define_flag("check_nan_inf", False, "scan every op output for NaN/Inf and raise")
define_flag("check_nan_inf_skip_ops", "",
            "comma-separated op names exempt from the NaN/Inf scan "
            "(op_type skip list, fluid/eager/nan_inf_utils.h analog — e.g. "
            "softmax_with_cross_entropy produces benign -inf internally)")
define_flag("deterministic", False, "prefer deterministic kernels / reductions")
define_flag("eager_jit_ops", True, "cache-and-jit each eager op call (vs. raw dispatch)")
define_flag("benchmark", False, "print per-step timing")
define_flag("log_level", 0, "verbosity level for framework logging (VLOG analog)")
define_flag("use_fused_attention", True, "use Pallas flash attention when available")
define_flag("use_fused_group_norm", True,
            "route GroupNorm (and the fused GroupNorm+SiLU entry) through "
            "the Pallas kernel (ops/pallas/group_norm.py): one HBM pass "
            "per direction vs XLA's 4-5 — the round-4 UNet profile showed "
            "normalization dominating the step")
define_flag("use_fused_rms_norm", True,
            "route rms_norm through the fused Pallas kernel when eligible")
define_flag("flash_attention_min_seq", 512,
            "min KV seq length to route through the Pallas flash kernel "
            "(below this XLA's fused sdpa wins — measured end-to-end on "
            "v5e round 3: BERT-base B=16 S=512 train step 83.2 ms with "
            "sdpa vs 75.4 ms with flash; S=1024 flash fwd 0.37 ms vs sdpa "
            "1.20 ms per layer, and sdpa OOMs at S=2048)")
define_flag("use_fused_lm_ce", True,
            "route large-vocab LM losses through the chunked-vocab fused "
            "head+CE (ops/fused_ce.py) instead of materializing (T, V) "
            "logits")
define_flag("use_ring_attention", True,
            "use ring (context-parallel) attention when the mesh has a sep>1 axis")
define_flag("use_decode_attention", True,
            "route single-token GQA cache attention through the Pallas "
            "decode kernel (ops/pallas/decode_attention.py); MHA (no "
            "head sharing) stays on XLA, which is faster there")
define_flag("decode_quant", "",
            "default decode dtype recipe for LlamaDecoder when neither "
            "quant= nor weight_dtype= is passed: '' (fp32/bf16, the "
            "default), 'int8w' (per-channel absmax int8 weights, dequant "
            "fused into the matmuls) or 'int8wk' (int8w + int8 KV cache "
            "with per-row absmax scales, dequant-on-load in the scan "
            "body); the PADDLE_TPU_DECODE_QUANT environment variable is "
            "an equivalent switch")
define_flag("decode_attention_interpret", False,
            "route eligible decode attention through the Pallas decode "
            "kernel, and the decode step's per-row token-row write "
            "through kv_row_write, in INTERPRET mode when not on a TPU "
            "backend (off-TPU the kernels are normally skipped for the "
            "faster XLA forms); the CPU-harness parity evidence for the "
            "kernel-routed chunked decode path — never a production "
            "switch")
define_flag("decode_fallback", False,
            "serve LlamaDecoder.generate / nn.generation.generate_tokens "
            "through the per-token host loop (one dispatch + one host sync "
            "per token) instead of the one-dispatch fused scan decode — a "
            "debugging escape hatch; the PADDLE_TPU_DECODE_FALLBACK=1 "
            "environment variable is an equivalent switch")
define_flag("decode_speculative_tokens", 4,
            "default number of draft tokens proposed per speculative "
            "verify step (K) when LlamaDecoder.generate is given a "
            "draft_model without an explicit num_speculative_tokens; the "
            "target scores all K+1 positions in one batched forward "
            "inside the one-dispatch decode program")
define_flag("resilience_retries", 3,
            "transient-backend-error retries per device dispatch in "
            "runtime/resilience.resilient_call (UNAVAILABLE / "
            "DEADLINE_EXCEEDED / ABORTED, plus RESOURCE_EXHAUSTED during "
            "setup); 0 disables retrying")
define_flag("resilience_backoff_s", 0.5,
            "base exponential-backoff delay (seconds) between "
            "resilient_call retries: attempt i sleeps base * 2**(i-1)")
define_flag("resilience_deadline_s", 0.0,
            "total wall-clock budget (seconds) a resilient_call may "
            "spend retrying before the last transient error propagates; "
            "0 means no deadline")
define_flag("resilience_auto_degrade", True,
            "step the decode ladder down automatically on dispatch "
            "failure (fused speculative -> fused plain -> per-token "
            "fallback), recording a typed DegradationEvent per step; "
            "off = the first level's error propagates (the pre-round-8 "
            "behavior, where only the manual decode_fallback flag could "
            "change the path)")
define_flag("fused_ce_logits_budget_mb", 1536,
            "transient f32 logits budget (MB) for the chunked fused "
            "lm-head CE; the vocab chunk is the largest multiple of 1024 "
            "whose (tokens, chunk) f32 block fits")
define_flag("train_rng_impl", "rbg",
            "PRNG implementation for the per-step traced key in compiled "
            "training steps (dropout & co.). 'rbg' uses the TPU hardware "
            "RNG path — threefry mask generation alone cost ~36 ms/step on "
            "the 183M-param dropout-0.1 GPT config (v5e); 'threefry2x32' "
            "restores the jax default (cross-backend reproducible streams)")
define_flag("decompose_fused_ops", False,
            "trace-time decomposition mode (passes.decompose_fused): "
            "every fused/Pallas-routed op runs its canonical lax "
            "composition so passes and exporters see base primitives "
            "only (reference: paddle/fluid/primitive/composite/)")
define_flag("to_static_max_cond_paths", 16,
            "path budget for capturing data-dependent Python bools into "
            "lax.cond inside to_static (jit/cond_capture.py): each "
            "captured bool doubles the leaf-path count; beyond the budget "
            "the call graph-breaks to eager as in round 3")
define_flag("to_static_max_while_iters", 8,
            "iteration bound for capturing a `while tensor:` loop inside "
            "to_static (jit/cond_capture.py): the same bool site forking "
            "once per iteration is unrolled up to this many times into "
            "the lax.cond fold (differentiable); a loop that exceeds the "
            "bound at runtime raises instead of silently truncating")
define_flag("to_static_max_specializations", 4,
            "per-specialization budget for guard-specializing a function "
            "that graph-broke on a non-bool concretization "
            "(jit/conc_capture.py): each distinct set of concretized "
            "values gets its own compiled program with runtime guards; "
            "beyond the budget the call stays permanently eager")
define_flag("to_static_guard_miss_limit", 8,
            "consecutive guard misses before a guard-specialized "
            "function stops trying compiled programs (each trial costs "
            "one wasted execution) and settles on permanent eager")
define_flag("to_static_max_guard_elems", 64,
            "largest concretized array (elements) that may be baked into "
            "a guard-specialized program; larger concretizations make "
            "the function permanently eager")
define_flag("obs_enabled", False,
            "master switch for the unified observability spine "
            "(paddle_tpu/obs): span tracing at decode/serving/bundle "
            "dispatch sites, obs metrics counters, compiled-program "
            "cost telemetry. The PADDLE_TPU_OBS=1 environment variable "
            "is an equivalent switch; off (default) the instrumented "
            "paths pay one boolean check per call")
define_flag("obs_buffer_size", 8192,
            "ring-buffer capacity (spans) of the global obs tracer; the "
            "newest spans win and Tracer.dropped counts evictions")
define_flag("obs_export_port", 0,
            "TCP port for the live telemetry exporter (obs/exporter.py): "
            "/metrics (Prometheus text), /statusz (JSON status), /tracez "
            "(recent completed spans). 0 (default) = no exporter; the "
            "PADDLE_TPU_OBS_PORT environment variable is an equivalent "
            "switch. ServingEngine.start_exporter() and bench.py --serve "
            "honor it")
define_flag("obs_flight_recorder", True,
            "on DecodeFailedError / an exhausted degradation ladder, "
            "atomically dump the last FLAGS_obs_flight_spans spans + the "
            "resilience timeline + a metrics snapshot to a postmortem "
            "JSON (obs/flight.py) so a dead run stays debuggable; only "
            "active while obs is enabled")
define_flag("obs_flight_spans", 256,
            "how many of the newest tracer spans a flight-recorder "
            "postmortem dump carries")
define_flag("obs_flight_dir", "",
            "directory for flight-recorder postmortem dumps (empty = "
            "current working directory)")
define_flag("obs_cost_analysis", True,
            "attach XLA cost_analysis/memory_analysis records "
            "(FLOPs, bytes, peak bytes) to dispatch spans; derived once "
            "per (site, input signature) via an AOT lower+compile — "
            "turn off to trace timing only")
define_flag("serving_prefix_cache_bytes", 0,
            "byte budget for the serving engine's content-hashed prefix "
            "cache (serving/prefix_cache.py): admission consults a "
            "device-resident, ref-counted KV slab store keyed by the "
            "prompt's block-boundary content hashes — a full-prefix hit "
            "admits with ZERO prefill dispatches (one row-scatter), a "
            "partial hit prefills only the uncached suffix. 0 (default) "
            "= disabled; the PADDLE_TPU_PREFIX_CACHE_BYTES environment "
            "variable is an equivalent switch. Least-recently-used "
            "unpinned slabs evict when the budget is exceeded")
define_flag("serving_prefix_block_tokens", 64,
            "prefix-cache hash granularity: prompts are content-hashed "
            "at every multiple of this many tokens (plus the full "
            "length), so two prompts sharing a prefix but diverging in "
            "their suffixes still match at the longest common block "
            "boundary")
define_flag("default_dtype", "float32", "default floating point dtype")
define_flag("allocator_stats", False, "track live tensor bytes (allocator stats analog)")
define_flag("profiler_dir", "", "directory for profiler trace output")
define_flag("comm_timeout_s", 1800.0, "collective watchdog timeout seconds")
define_flag("enable_auto_parallel_align_mode", False, "deterministic data order for parallel-strategy alignment checks")
