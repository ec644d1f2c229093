"""AOT predictor bundles — serving with zero model Python.

Round-4 answer to VERDICT item 3. Reference capability:
paddle/fluid/inference/api/analysis_predictor.h +
paddle_analysis_config.h — a configurable predictor loaded from an
exported artifact: named inputs/outputs, device/dtype config, MULTIPLE
entry functions (prefill + decode), shape buckets.

TPU-native design: each entry point is a ``jax.export`` StableHLO module
with the parameters BAKED IN as constants (the serving process never
imports model code or loads a separate weights file — one artifact, no
pickle, no Python execution on load). Static shapes are the deployment
contract; a bundle carries one compiled entry per declared shape bucket,
exactly like TensorRT optimization profiles.

Bundle layout (a directory):
    bundle.json                      # metadata: kind, io names, buckets,
                                     #   cache shapes/dtype, dtypes
    predict_<bucket>.aot             # plain forward entries
    prefill_b{B}_s{S}.aot            # LM prefill entries
    decode_b{B}_n{N}.aot             # LM greedy scan-decode entries

``AotPredictor`` loads a bundle and serves `run` / `generate` from the
deserialized executables only.
"""

from __future__ import annotations

import json
import os
from typing import Dict, List, Optional, Sequence

import numpy as np

__all__ = ["export_predict_bundle", "export_decoder_bundle", "AotPredictor"]

_META = "bundle.json"


def _save_exp(fn, args, path, donate_argnums=(), meta=None):
    """Export one entry module (crash-safe write) and return its sha256
    for the bundle manifest. ``meta`` embeds an entry self-description
    in the .aot file itself (``aot.read_meta``) so a stray entry stays
    identifiable away from bundle.json."""
    from paddle_tpu.inference.aot import save_compiled
    return save_compiled(fn, args, path, donate_argnums=donate_argnums,
                         meta=meta)


def _load_exp(path, expected_sha256=None):
    from paddle_tpu.inference.aot import load_compiled
    return load_compiled(path, expected_sha256=expected_sha256)


def _write_meta(out_dir: str, meta: dict) -> None:
    """bundle.json write: temp + atomic rename, so a killed exporter
    leaves either the previous metadata or the new one — never a torn
    JSON that would poison every later load."""
    from paddle_tpu.runtime.resilience import atomic_write_bytes
    atomic_write_bytes(os.path.join(out_dir, _META),
                       json.dumps(meta, indent=2).encode())


def export_predict_bundle(layer, example_inputs: Sequence[np.ndarray],
                          out_dir: str,
                          input_names: Optional[List[str]] = None,
                          output_names: Optional[List[str]] = None,
                          extra_batch_sizes: Sequence[int] = ()) -> None:
    """Export a plain forward model as an AOT bundle.

    ``example_inputs`` fixes the primary shape bucket; each entry of
    ``extra_batch_sizes`` adds another bucket with the leading dim
    replaced. Parameters are baked into the modules at export time (the
    exporting process runs the model Python once per bucket; the serving
    process runs none)."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.framework.tensor import Tensor

    if hasattr(layer, "eval"):
        layer.eval()

    def fwd(*arrs):
        from paddle_tpu.autograd import tape
        with tape.no_grad():
            out = layer(*[Tensor(a) for a in arrs])
        outs = out if isinstance(out, (list, tuple)) else [out]
        return tuple(o._value if isinstance(o, Tensor) else jnp.asarray(o)
                     for o in outs)

    os.makedirs(out_dir, exist_ok=True)
    examples = [jnp.asarray(a) for a in example_inputs]
    buckets = []
    manifest = {}
    shapes_list = [tuple(tuple(a.shape) for a in examples)]
    for b in extra_batch_sizes:
        shapes_list.append(tuple((int(b),) + tuple(a.shape[1:])
                                 for a in examples))
    for shapes in shapes_list:
        args = [jnp.zeros(s, a.dtype) for s, a in zip(shapes, examples)]
        tag = "predict_" + "_".join(
            "x".join(map(str, s)) for s in shapes)
        manifest[tag + ".aot"] = _save_exp(
            fwd, args, os.path.join(out_dir, tag + ".aot"))
        buckets.append({"file": tag + ".aot",
                        "shapes": [list(s) for s in shapes],
                        "dtypes": [str(a.dtype) for a in examples]})
    outs0 = jax.eval_shape(fwd, *examples)
    n_out = len(outs0)
    meta = {
        "kind": "predict",
        "inputs": input_names or [f"x{i}" for i in range(len(examples))],
        "outputs": output_names or [f"out_{i}" for i in range(n_out)],
        "buckets": buckets,
        "manifest": manifest,
    }
    # Identify which outputs are batch-major BY CONSTRUCTION (abstract
    # re-trace at a different batch: an output is batch-major iff its
    # leading dim tracks the input batch), so the padded-bucket run()
    # path never trims a non-batch output whose leading dim happens to
    # equal the padded batch (ADVICE r5).
    try:
        B0 = examples[0].shape[0]
        alt = B0 + 1
        outs1 = jax.eval_shape(fwd, *[
            jax.ShapeDtypeStruct((alt,) + tuple(a.shape[1:]), a.dtype)
            for a in examples])
        meta["output_batch_major"] = [
            bool(len(s0.shape) and len(s1.shape)
                 and s0.shape[0] == B0 and s1.shape[0] == alt)
            for s0, s1 in zip(outs0, outs1)]
    except Exception:
        # batch-polymorphic retrace unsupported (e.g. batch-baked model):
        # leave batch axes unknown -> run() serves exact shapes only
        pass
    _write_meta(out_dir, meta)


def export_decoder_bundle(decoder, out_dir: str,
                          prompt_lens: Sequence[int],
                          decode_steps: Sequence[int],
                          batch_sizes: Sequence[int] = (1,),
                          do_sample: bool = False,
                          temperature: float = 1.0,
                          top_k: Optional[int] = None,
                          top_p: Optional[float] = None,
                          draft_model=None,
                          num_speculative_tokens: Optional[int] = None,
                          plain_fallback: bool = True,
                          chunk_sizes: Sequence[int] = ()) -> None:
    """Export a ``LlamaDecoder`` as prefill + fused scan-decode AOT
    entries (the compiled-decode serving artifact the reference ships via
    its generation ops + AnalysisPredictor). One prefill module per
    (B, S) bucket, one decode module per (B, N) bucket; KV-cache buffers
    are donated so serving decodes in place.

    Decode entries run the SAME one-dispatch fused loop the in-process
    decoder uses: the eos id, the jax.random key AND the temperature are
    runtime inputs (one entry serves any eos — pass eos=-1 for "none" —
    any seed and any temperature); ``do_sample``/``top_k``/``top_p``
    change program structure, are baked at export and recorded in the
    bundle metadata (``decode_mode``; the export-time ``temperature``
    is recorded as ``default_temperature`` for callers that don't pass
    one).

    With ``draft_model`` (a LlamaForCausalLM or ``'skip:N'``; see
    ``LlamaDecoder.generate``) the decode entries are SPECULATIVE: the
    bundle additionally carries ``draft_prefill_b{B}_s{S}.aot`` entries
    and draft cache metadata, each decode entry takes both cache pairs
    and returns (tokens, rounds, accepted), and ``decode_mode``
    records the speculation statics. For speculative buckets ``N`` is
    the OUTPUT BUFFER size (serves max_new_tokens <= N); plain buckets
    keep the scan-steps meaning (serves max_new_tokens <= N + 1).

    ``plain_fallback`` (default on, speculative bundles only) also
    exports a plain fused decode entry per bucket — the serve-side
    degradation ladder's lower rung: when the speculative entry keeps
    failing dispatch at serve time, AotPredictor steps down to the plain
    entry automatically (bit-exact for greedy bundles) instead of
    failing the request.

    ``chunk_sizes`` additionally exports the CONTINUOUS-BATCHING serving
    entries (``decode_mode.chunked``): per batch bucket, one
    ``decode_chunk_b{B}_t{T}.aot`` running T steps of the re-enterable
    fused loop — the chunk size is a compile-time static; the whole loop
    carry (next-token logits, both cache buffers, per-row positions /
    RNG keys / done mask / eos ids / temperatures) is runtime inputs and
    outputs — plus one batch-1 ``admit_prefill_s{S}.aot`` per prompt
    bucket (right-padded prompt + runtime true length, returning the
    true last position's logits) for slot admission. A chunk size of 1
    is always included as the serve-side degradation rung. Serve with
    ``paddle_tpu.serving.ServingEngine(AotPredictor(dir), ...)`` — the
    same scheduler as in-process serving, zero model Python."""
    import jax
    import jax.numpy as jnp

    cfg = decoder.cfg
    if cfg.latent:
        from paddle_tpu.inference.generate import LatentCacheError
        raise LatentCacheError(
            "a decoder bundle records one K / V buffer shape for all "
            "layers; a model with latent attention caches a latent and a "
            "rope key of two widths: serve it in process")
    if cfg.has_windows:
        from paddle_tpu.inference.generate import WindowedModelError
        raise WindowedModelError(
            "a decoder bundle records ONE cache buffer shape for all "
            "layers and its serving process rebuilds the carry from it; a "
            "model with windowed layers holds buffers of several lengths "
            "or kinds: serve it in process")
    os.makedirs(out_dir, exist_ok=True)
    p = decoder.params
    # a mesh-built decoder exports PARTITIONED entries: the example args
    # below are committed to their carry placements so jax.export bakes
    # the GSPMD program (sharded weight constants included), and the
    # topology + partition rules are recorded in decode_mode.mesh — the
    # load side refuses a different mesh instead of crashing mid-serve
    srd = getattr(decoder, "sharding", None)
    hm = getattr(decoder, "_head_major", False)

    def sput(x, field=None):
        if srd is None:
            return x
        if field is None:
            return srd.put(x, ())           # replicated on the mesh
        return srd.put_state_field(field, x, hm)

    eng, K = None, None
    if draft_model is not None:
        if srd is not None:
            from paddle_tpu.inference.sharding import SpeculativeMeshError
            raise SpeculativeMeshError(
                "speculative bundles cannot be exported from a mesh-built "
                "decoder (speculative decode is refused on a mesh)")
        from paddle_tpu.flags import flags
        eng = decoder._spec_engine(draft_model)
        K = int(num_speculative_tokens if num_speculative_tokens is not None
                else flags.decode_speculative_tokens)
        if K < 1:
            raise ValueError(f"num_speculative_tokens must be >= 1, got {K}")
        worst = max(prompt_lens) + max(decode_steps) + K
        if worst > decoder.max_len:
            raise ValueError(
                f"speculative buckets can overshoot the cache by up to "
                f"K={K} slots: prompt {max(prompt_lens)} + buffer "
                f"{max(decode_steps)} + {K} exceeds max_len "
                f"{decoder.max_len}")
    elif num_speculative_tokens is not None:
        raise ValueError("num_speculative_tokens requires a draft_model")
    prefills, dprefills, decodes = [], [], []
    chunks, admits = [], []
    csizes = sorted({int(t) for t in chunk_sizes} | {1}) if chunk_sizes \
        else []
    caches, dcaches = {}, {}
    manifest = {}

    def _cache_meta(kc):
        """What the serving process rebuilds the carry from: ``kc`` is one
        buffer per layer (``_empty_cache``), all of one shape."""
        from paddle_tpu.quantization.kv_cache import is_quantized_kv
        meta = {"n_buffers": len(kc), "layout": "per_layer"}
        buf = kc[0]
        if is_quantized_kv(buf):
            # int8 KV carry (the int8wk recipe): the serving process
            # rebuilds {"q": int8, "s": f32 scale} buffers from this
            meta.update(
                shape=list(buf["q"].shape),
                dtype=str(buf["q"].dtype),
                quant={"kv": str(buf["q"].dtype),
                       "scale_shape": list(buf["s"].shape),
                       "scale_dtype": str(buf["s"].dtype)})
        else:
            meta.update(shape=list(buf.shape), dtype=str(buf.dtype))
        return meta

    for B in batch_sizes:
        kc, vc = decoder._empty_cache(int(B))
        caches[str(int(B))] = _cache_meta(kc)
        if eng is not None:
            dkc, dvc = decoder._empty_cache(int(B), eng["cfg"])
            dcaches[str(int(B))] = _cache_meta(dkc)
        for S in prompt_lens:
            ids = sput(jnp.zeros((int(B), int(S)), jnp.int32))

            def prefill(ids, kc, vc):
                return decoder._prefill(p, ids, kc, vc)

            tag = f"prefill_b{B}_s{S}"
            manifest[tag + ".aot"] = _save_exp(
                prefill, (ids, kc, vc),
                os.path.join(out_dir, tag + ".aot"),
                donate_argnums=(1, 2))
            prefills.append({"file": tag + ".aot", "batch": int(B),
                             "seq": int(S)})
            if eng is not None:
                def dprefill(ids, dkc, dvc):
                    return eng["prefill"](eng["params"], ids, dkc, dvc)

                dtag = f"draft_prefill_b{B}_s{S}"
                manifest[dtag + ".aot"] = _save_exp(
                    dprefill, (ids, dkc, dvc),
                    os.path.join(out_dir, dtag + ".aot"),
                    donate_argnums=(1, 2))
                dprefills.append({"file": dtag + ".aot", "batch": int(B),
                                  "seq": int(S)})
        logits_sds = jax.eval_shape(
            lambda ids, kc, vc: decoder._prefill(p, ids, kc, vc),
            jnp.zeros((int(B), int(prompt_lens[0])), jnp.int32), kc, vc)[0]
        for N in decode_steps:
            logits0 = sput(jnp.zeros(logits_sds.shape, logits_sds.dtype),
                           "logits")
            pos0 = sput(jnp.asarray(0, jnp.int32))
            key0 = sput(jax.random.PRNGKey(0))
            done0 = sput(jnp.zeros((int(B),), jnp.bool_), "done")
            eos0 = sput(jnp.asarray(-1, jnp.int32))
            temp0 = sput(jnp.asarray(float(temperature), jnp.float32))
            tag = f"decode_b{B}_n{N}"
            if eng is None:
                def decode(logits, kc, vc, pos, key, done, eos, temp,
                           N=int(N)):
                    return decoder._fused_decode(
                        p, logits, kc, vc, pos, key, done, eos, temp,
                        steps=N, do_sample=bool(do_sample), use_eos=True,
                        top_k=None if top_k is None else int(top_k),
                        top_p=None if top_p is None else float(top_p))

                manifest[tag + ".aot"] = _save_exp(
                    decode,
                    (logits0, kc, vc, pos0, key0, done0, eos0, temp0),
                    os.path.join(out_dir, tag + ".aot"),
                    donate_argnums=(1, 2))
                decodes.append({"file": tag + ".aot", "batch": int(B),
                                "steps": int(N)})
            else:
                def decode(logits, kc, vc, dkc, dvc, pos, key, done, eos,
                           temp, N=int(N)):
                    return eng["decode"](
                        p, eng["params"], logits, kc, vc, dkc, dvc, pos,
                        key, done, eos, temp, max_new=N, K=K,
                        do_sample=bool(do_sample), use_eos=True,
                        top_k=None if top_k is None else int(top_k),
                        top_p=None if top_p is None else float(top_p))

                manifest[tag + ".aot"] = _save_exp(
                    decode,
                    (logits0, kc, vc, dkc, dvc, pos0, key0, done0,
                     eos0, temp0),
                    os.path.join(out_dir, tag + ".aot"),
                    donate_argnums=(1, 2, 3, 4))
                decodes.append({"file": tag + ".aot", "batch": int(B),
                                "steps": int(N), "speculative": True})
                if plain_fallback and N >= 1:
                    # the ladder's lower rung: a plain fused entry with
                    # the SAME serve capacity (N tokens) as the
                    # speculative buffer above it
                    def pdecode(logits, kc, vc, pos, key, done, eos,
                                temp, N=int(N)):
                        return decoder._fused_decode(
                            p, logits, kc, vc, pos, key, done, eos, temp,
                            steps=N - 1, do_sample=bool(do_sample),
                            use_eos=True,
                            top_k=None if top_k is None else int(top_k),
                            top_p=None if top_p is None else float(top_p))

                    ptag = f"decode_plain_b{B}_n{N}"
                    manifest[ptag + ".aot"] = _save_exp(
                        pdecode,
                        (logits0, kc, vc, pos0, key0, done0, eos0, temp0),
                        os.path.join(out_dir, ptag + ".aot"),
                        donate_argnums=(1, 2))
                    decodes.append({"file": ptag + ".aot",
                                    "batch": int(B), "steps": int(N) - 1})
        for T in csizes:
            # continuous-batching chunk entry: T loop steps per dispatch,
            # whole carry in/out (ServingEngine re-enters it between
            # admissions); T=1 doubles as the per-token degradation rung
            def cdecode(logits, kc, vc, pos, keys, done, eos, temp,
                        T=int(T)):
                # the chunk program with no adapter index and no ring;
                # the entry returns the seven values it always has (eos
                # and temp come back as they went in)
                return decoder._ring_chunk_decode(
                    p, logits, kc, vc, pos, keys, done, eos, temp,
                    *((None,) * 10),
                    steps=T, do_sample=bool(do_sample),
                    top_k=None if top_k is None else int(top_k),
                    top_p=None if top_p is None else float(top_p))[:7]

            logits0 = sput(jnp.zeros(logits_sds.shape, logits_sds.dtype),
                           "logits")
            ctag = f"decode_chunk_b{B}_t{T}"
            manifest[ctag + ".aot"] = _save_exp(
                cdecode,
                (logits0, kc, vc,
                 sput(jnp.zeros((int(B),), jnp.int32), "pos"),
                 sput(jnp.zeros((int(B), 2), jnp.uint32), "keys"),
                 sput(jnp.zeros((int(B),), jnp.bool_), "done"),
                 sput(jnp.full((int(B),), -1, jnp.int32), "eos"),
                 sput(jnp.ones((int(B),), jnp.float32), "temp")),
                os.path.join(out_dir, ctag + ".aot"),
                donate_argnums=(1, 2),
                # the entry self-describes its statics: this chunk
                # program has NO ring-admission prologue and NO
                # speculative verify loop — what the serving engine's
                # typed demotions point at
                meta={"entry": "decode_chunk", "batch": int(B),
                      "chunk": int(T), "admit_ring": False,
                      "spec_chunk": False})
            chunks.append({"file": ctag + ".aot", "batch": int(B),
                           "chunk": int(T)})
    if csizes:
        # batch-1 admission prefills: right-padded prompt bucket + the
        # runtime true length; the returned row state is what the engine
        # scatters into a freed slot of the batch carry
        kc1, vc1 = decoder._empty_cache(1)
        caches["1"] = _cache_meta(kc1)
        for S in prompt_lens:
            # true_len/pos0 are PER-ROW (1,) runtime inputs: pos0 > 0 is
            # the prefix-cache suffix prefill (the caches arrive
            # preloaded with the cached prefix's KV rows [0, pos0)) — the
            # SAME bucketed entry serves cold and cached-suffix admission
            def aprefill(ids, kc, vc, true_len, pos0):
                return decoder._admit_prefill(p, ids, kc, vc, true_len,
                                              pos0)

            atag = f"admit_prefill_s{S}"
            manifest[atag + ".aot"] = _save_exp(
                aprefill,
                (sput(jnp.zeros((1, int(S)), jnp.int32)), kc1, vc1,
                 sput(jnp.ones((1,), jnp.int32)),
                 sput(jnp.zeros((1,), jnp.int32))),
                os.path.join(out_dir, atag + ".aot"),
                meta={"entry": "admit_prefill", "batch": 1,
                      "seq": int(S), "admit_pos0": True})
            admits.append({"file": atag + ".aot", "batch": 1,
                           "seq": int(S)})
    # the fused-decode serving contract: key/done/eos/temperature are
    # runtime inputs; do_sample/top_k/top_p (and the speculation statics)
    # were baked at export
    mode = {"do_sample": bool(do_sample),
            "temperature": "runtime",
            "default_temperature": float(temperature),
            "top_k": None if top_k is None else int(top_k),
            "top_p": None if top_p is None else float(top_p),
            # the dtype recipe baked into every entry (weights are
            # StableHLO constants; the KV carry dtype is structural):
            # load-side serving cross-checks an explicit quant ask
            # against this and refuses mismatches typed
            "quant": {
                "recipe": getattr(decoder, "quant", None) or "none",
                "weights": ("int8" if getattr(decoder, "weight_dtype",
                                              None) == "int8"
                            else str(jnp.dtype(cfg.dtype))),
                "kv_cache": ("int8" if getattr(decoder, "quant_kv", False)
                             else str(jnp.dtype(cfg.dtype))),
            }}
    if eng is not None:
        mode["speculative"] = {
            "num_speculative_tokens": K,
            "draft": (draft_model if isinstance(draft_model, str)
                      else "model"),
            "draft_layers": eng["cfg"].num_hidden_layers,
        }
    if csizes:
        # continuous-batching contract: chunk size is a static (one
        # entry per size); the loop carry — logits, caches, per-row
        # pos/keys/done/eos/temperature — is runtime inputs AND outputs
        mode["chunked"] = {"chunk_sizes": csizes,
                           "state_inputs": ["logits", "kc", "vc", "pos",
                                            "keys", "done", "eos",
                                            "temp"],
                           # admit entries take per-row (1,) true_len +
                           # pos0 — the prefix-cache suffix-prefill
                           # contract; absent on pre-prefix bundles,
                           # whose partial hits the engine demotes to
                           # misses
                           "admit_pos0": True,
                           # bundle entries carry neither the device
                           # admission-ring prologue nor a speculative
                           # chunk program: ServingEngine demotes bundle
                           # serving to host-scatter admission, and
                           # refuses draft_model= over a bundle typed
                           # (pointing at these statics) instead of
                           # crashing on a missing entry mid-serve
                           "admit_ring": False,
                           "spec_chunk": False}
    if srd is not None:
        # the mesh contract: entries are partitioned programs for THIS
        # topology (jax.export refuses other device counts outright);
        # AotPredictor/_BundleBackend refuse a different mesh typed, at
        # load, and rebuild the carry placements from these rules
        mode["mesh"] = srd.describe()
    meta = {
        "kind": "llama_decoder",
        "inputs": ["input_ids"],
        "outputs": ["tokens"],
        # int8 weight-only decoders export with the quantized params baked
        # into the modules (the PTQ -> serving chain, VERDICT r5 item 6)
        "weight_dtype": decoder.weight_dtype or "none",
        "max_len": decoder.max_len,
        "vocab_size": cfg.vocab_size,
        "logits_dtype": str(logits_sds.dtype),
        "caches": caches,
        "prefill_buckets": prefills,
        "decode_buckets": decodes,
        "decode_mode": mode,
        # per-file sha256 of the intended bytes (computed BEFORE the
        # write hit disk): AotPredictor verifies each entry at load and
        # refuses corrupt modules with a typed CorruptBundleError
        "manifest": manifest,
    }
    if eng is not None:
        meta["draft_caches"] = dcaches
        meta["draft_prefill_buckets"] = dprefills
    if csizes:
        meta["chunk_buckets"] = chunks
        meta["admit_prefill_buckets"] = admits
    _write_meta(out_dir, meta)


class AotPredictor:
    """Serve an AOT bundle: no model Python, no re-tracing, no pickle.

    ``run`` serves plain-forward bundles by named inputs/outputs;
    ``generate`` serves llama_decoder bundles (prefill at the (B, S)
    bucket, greedy decode at the smallest (B, N>=max_new_tokens) bucket,
    trimmed to the requested length).

    Ergonomics (round-5 VERDICT item 8, AnalysisConfig capability):
    - a smaller batch than any exported bucket pads up to the NEAREST
      bucket and trims the outputs (TensorRT-profile style), instead of
      exact-shape-or-error;
    - ``warmup=True`` executes every entry once with zeros at load time,
      so the first request pays no deserialization/transfer latency;
    - ``cast_inputs=True`` coerces feeds to the bucket dtype;
    - ``memory_report()`` sizes the artifact and the serving buffers."""

    def __init__(self, bundle_dir: str, device: Optional[str] = None,
                 warmup: bool = False, cast_inputs: bool = True,
                 allow_bucket_padding: bool = True):
        """``allow_bucket_padding``: serve smaller batches by zero-padding
        to the nearest bucket. CAVEAT: only sound when the model treats
        batch rows independently (the overwhelmingly common case); a graph
        with cross-batch-coupled outputs (e.g. a batch-mean output) would
        silently fold the pad rows in — disable padding for such models
        (Config.set_bucket_padding(False)) to get the strict
        exact-shape-or-error behavior back."""
        with open(os.path.join(bundle_dir, _META)) as f:
            self.meta = json.load(f)
        self._dir = bundle_dir
        self._entries: Dict[str, object] = {}
        self.device = device
        self.cast_inputs = cast_inputs
        self.allow_bucket_padding = allow_bucket_padding
        # mesh-exported bundles: rebuild the recorded sharding (raises a
        # typed MeshMismatchError when this process cannot host the
        # topology — "refuse at load", never a mid-serve device crash);
        # serving state and fed arrays are then committed to the mesh
        self._sharding = None
        mesh_rec = (self.meta.get("decode_mode") or {}).get("mesh")
        if mesh_rec is not None:
            from paddle_tpu.inference.sharding import DecodeSharding
            self._sharding = DecodeSharding.from_describe(mesh_rec)
        self.padded_calls = 0      # observability: nearest-bucket serves
        self.last_spec_stats = None  # speculative bundles: last generate's
        #                              round/acceptance totals
        self.last_resilience = None  # retry/degradation record of the
        #                              last generate (also on the result)
        self._events = []
        if warmup:
            self.warmup()

    # -- common ------------------------------------------------------------
    @property
    def quant_recipe(self) -> Optional[str]:
        """The dtype recipe this bundle was exported with (``None`` =
        unquantized, else 'int8w'/'int8wk'). Read from
        ``decode_mode.quant``; legacy bundles fall back to the
        ``weight_dtype`` metadata (int8 weights = 'int8w')."""
        mode = self.meta.get("decode_mode") or {}
        q = mode.get("quant")
        if q is not None:
            r = q.get("recipe")
            return None if r in (None, "none") else r
        return ("int8w" if self.meta.get("weight_dtype") == "int8"
                else None)

    def get_input_names(self) -> List[str]:
        return list(self.meta["inputs"])

    def get_output_names(self) -> List[str]:
        return list(self.meta["outputs"])

    def _entry(self, fname):
        fn = self._entries.get(fname)
        if fn is None:
            # verify-on-load: bundles carrying a manifest get each entry's
            # on-disk bytes checked against the export-time sha256 — a
            # bit-flipped weight constant raises CorruptBundleError here
            # instead of silently serving wrong numerics. Pre-manifest
            # bundles load unchecked (legacy contract).
            expected = (self.meta.get("manifest") or {}).get(fname)
            fn = _load_exp(os.path.join(self._dir, fname),
                           expected_sha256=expected)
            self._entries[fname] = fn
        return fn

    def _run_entry(self, fname, site, *args):
        """Execute one exported module under the resilience contract:
        the fault-injection hook fires first, then transient backend
        errors retry with backoff; retry events accumulate on the
        in-flight generate/run record.

        With obs enabled (paddle_tpu/obs) each executed entry records a
        dispatch span named after its fault site (the entry file in the
        attrs) and bumps ``dispatches.<site>`` — timing only: a
        jax.export-deserialized module exposes no cost_analysis hooks,
        so bundle spans carry no FLOPs record (the in-process decoder's
        spans do)."""
        import paddle_tpu.obs as obs
        from paddle_tpu.runtime.resilience import (fault_injector,
                                                   resilient_call)

        def attempt():
            fault_injector.on_call(site)
            if not obs.enabled():
                return self._entry(fname)(*args)
            with obs.span(site, kind="dispatch", entry=fname):
                out = self._entry(fname)(*args)
            obs.metrics.counter(
                "dispatches." + site,
                "bundle entries executed at this site").inc()
            return out

        return resilient_call(attempt, site=site,
                              on_event=self._events.append)

    # -- config/ops surface ------------------------------------------------
    def warmup(self) -> None:
        """Execute every exported entry once with zeros: pays module
        deserialization + first-dispatch cost at LOAD time instead of on
        the first real request (AnalysisConfig warmup analog)."""
        import jax.numpy as jnp
        if self.meta["kind"] == "predict":
            for b in self.meta["buckets"]:
                args = [jnp.zeros(tuple(s), jnp.dtype(d))
                        for s, d in zip(b["shapes"], b["dtypes"])]
                self._entry(b["file"])(*args)
            return
        # EVERY decode bucket warms once (each is its own module); the
        # prefill feeding it re-runs per decode bucket because its cache
        # outputs are donated into the decode call. Prefill buckets with
        # no same-batch decode still warm on their own.
        decode_by_batch: Dict[int, list] = {}
        for dc in self.meta["decode_buckets"]:
            decode_by_batch.setdefault(dc["batch"], []).append(dc)
        for pf in self.meta["prefill_buckets"]:
            B = pf["batch"]
            decs = decode_by_batch.get(B, [None]) \
                if pf is self._first_prefill(B) else [None]
            for dc in decs:
                ids = jnp.zeros((B, pf["seq"]), jnp.int32)
                kc, vc = self._make_cache(B)
                logits, kc, vc = self._entry(pf["file"])(ids, kc, vc)
                if dc is None:
                    continue
                draft_caches = None
                if dc.get("speculative"):
                    dpf = next(b for b in self.meta["draft_prefill_buckets"]
                               if b["batch"] == B and b["seq"] == pf["seq"])
                    dkc, dvc = self._make_cache(B, "draft_caches")
                    _, dkc, dvc = self._entry(dpf["file"])(ids, dkc, dvc)
                    draft_caches = (dkc, dvc)
                self._entry(dc["file"])(*self._decode_args(
                    logits, kc, vc, pf["seq"], B, None, 0,
                    draft_caches=draft_caches))

    def _first_prefill(self, B: int):
        return next((b for b in self.meta["prefill_buckets"]
                     if b["batch"] == B), None)

    def memory_report(self) -> Dict[str, object]:
        """Artifact + serving-buffer sizes: per-entry bytes on disk (the
        baked-weight modules ARE the deployment payload) and the KV-cache
        bytes a generate() call allocates per batch bucket."""
        entries = {}
        total = 0
        for f in os.listdir(self._dir):
            if f.endswith(".aot"):
                sz = os.path.getsize(os.path.join(self._dir, f))
                entries[f] = sz
                total += sz
        report = {"entries_bytes": entries, "artifact_bytes": total}
        if self.meta["kind"] == "llama_decoder":
            caches = {}
            for b, cm in self.meta["caches"].items():
                per = int(np.prod(cm["shape"])) * cm["n_buffers"] \
                    * np.dtype(cm["dtype"]).itemsize
                q = cm.get("quant")
                if q is not None:        # + the int8 carry's f32 scales
                    per += int(np.prod(q["scale_shape"])) \
                        * cm["n_buffers"] \
                        * np.dtype(q["scale_dtype"]).itemsize
                caches[b] = 2 * per                      # K and V
            report["kv_cache_bytes_per_batch"] = caches
        return report

    def _cast(self, arr, dtype):
        a = np.asarray(arr)
        if self.cast_inputs and str(a.dtype) != dtype:
            a = a.astype(np.dtype(dtype))
        return a

    # -- plain forward -----------------------------------------------------
    def run(self, feeds: Dict[str, np.ndarray]) -> Dict[str, np.ndarray]:
        if self.meta["kind"] != "predict":
            raise ValueError(f"bundle kind {self.meta['kind']!r} has no "
                             "plain-forward entry; use generate()")
        names = self.meta["inputs"]
        args = [np.asarray(feeds[n]) for n in names]
        shapes = tuple(tuple(a.shape) for a in args)
        self._events = []
        for b in self.meta["buckets"]:
            if tuple(tuple(s) for s in b["shapes"]) == shapes:
                args = [self._cast(a, d) for a, d in zip(args, b["dtypes"])]
                outs = self._run_entry(b["file"], "bundle.predict", *args)
                outs = outs if isinstance(outs, (list, tuple)) else [outs]
                return {n: np.asarray(o)
                        for n, o in zip(self.meta["outputs"], outs)}
        # nearest-bucket batch padding: every input must share ONE leading
        # batch dim; same trailing dims as the bucket; smallest bucket
        # batch that fits; outputs trimmed back to the fed batch
        B = shapes[0][0] if shapes and shapes[0] else None
        same_batch = (self.allow_bucket_padding and B is not None
                      and all(s and s[0] == B for s in shapes))
        cands = []
        for b in self.meta["buckets"]:
            bs = [tuple(s) for s in b["shapes"]]
            if (same_batch
                    and all(len(s) == len(g) and s[1:] == g[1:]
                            for s, g in zip(bs, shapes))
                    and all(s[0] == bs[0][0] for s in bs)
                    and bs[0][0] > B):
                cands.append((bs[0][0], b))
        if cands:
            nb, b = min(cands, key=lambda t: t[0])
            self.padded_calls += 1
            padded = []
            for a, d in zip(args, b["dtypes"]):
                a = self._cast(a, d)
                pad = np.zeros((nb - a.shape[0],) + a.shape[1:], a.dtype)
                padded.append(np.concatenate([a, pad], axis=0))
            outs = self._run_entry(b["file"], "bundle.predict", *padded)
            outs = outs if isinstance(outs, (list, tuple)) else [outs]
            # trim ONLY the outputs the exporter identified as batch-major
            # (abstract re-trace at a second batch size); a non-batch
            # output whose leading dim coincidentally equals the padded
            # batch must pass through untouched (ADVICE r5)
            bm = self.meta.get("output_batch_major")
            if bm is None:
                # legacy bundle without batch-axis metadata: padding could
                # silently truncate a non-batch output — refuse, per the
                # strict exact-shape contract
                raise ValueError(
                    f"no exact shape bucket for inputs {shapes} and this "
                    "bundle predates output batch-axis metadata; re-export "
                    "it to enable padded serving (exported buckets: "
                    f"{[b['shapes'] for b in self.meta['buckets']]})")
            return {n: (np.asarray(o)[:B] if is_bm else np.asarray(o))
                    for n, o, is_bm in zip(self.meta["outputs"], outs, bm)}
        raise ValueError(
            f"no shape bucket for inputs {shapes}; exported buckets: "
            f"{[b['shapes'] for b in self.meta['buckets']]}")

    # -- LM decode ---------------------------------------------------------
    def _head_major(self) -> bool:
        """Cache row layout from the recorded shapes: head-major rows are
        ``(B, KV, max_len, D)`` (max_len second-to-last), token-major
        ``(B, max_len, KV, D)``."""
        caches = self.meta.get("caches") or {}
        for cm in caches.values():
            shape = cm["shape"]
            return len(shape) >= 2 and shape[-2] == self.meta["max_len"]
        return False

    def _make_cache(self, B: int, which: str = "caches"):
        import jax.numpy as jnp
        cm = self.meta[which][str(B)]
        dt = jnp.dtype(cm["dtype"])
        shape = tuple(cm["shape"])
        quant = cm.get("quant")

        def z():
            if quant is not None:
                # int8wk carry: int8 rows + their scale buffer (never
                # mesh-exported — int8wk is refused on a mesh at build)
                return {"q": jnp.zeros(shape, dt),
                        "s": jnp.zeros(tuple(quant["scale_shape"]),
                                       jnp.dtype(quant["scale_dtype"]))}
            buf = jnp.zeros(shape, dt)
            if self._sharding is None:
                return buf
            return self._sharding.put_state_field("kc", buf,
                                                  self._head_major())

        if cm["layout"] == "stacked":
            # a bundle exported before the carry became one buffer per
            # layer: its programs take one array stacked over layers (the
            # recorded shape is that array's), and still serve
            return z(), z()
        kc = tuple(z() for _ in range(cm["n_buffers"]))
        vc = tuple(z() for _ in range(cm["n_buffers"]))
        return kc, vc

    def _decode_temp(self, temperature):
        """Resolve the decode temperature against the bundle contract:
        runtime-temperature bundles serve any value (export-time value as
        the default); legacy static bundles reject a mismatching ask."""
        mode = self.meta.get("decode_mode") or {}
        if mode.get("temperature") == "runtime":
            if temperature is None:
                return float(mode.get("default_temperature", 1.0))
            return float(temperature)
        if temperature is not None and mode and \
                float(temperature) != float(mode.get("temperature", 1.0)):
            raise ValueError(
                f"this bundle predates runtime-temperature decode entries "
                f"(baked temperature={mode.get('temperature')}); re-export "
                f"it to serve temperature={temperature}")
        return None        # static bundles take no temperature input

    def _decode_args(self, logits, kc, vc, pos, nb, eos_token_id, seed,
                     temperature=None, draft_caches=None):
        """Positional inputs for a decode entry. Fused-decode bundles
        (``decode_mode`` in the metadata) take (logits, caches, pos, key,
        done, eos[, temperature]) — eos=-1 means "no eos"; speculative
        bundles insert the draft cache pair after the target's; legacy
        greedy bundles take the original 4 inputs."""
        import jax.numpy as jnp

        pos = jnp.asarray(pos, jnp.int32)
        if self.meta.get("decode_mode") is None:
            return (logits, kc, vc, pos)
        import jax
        key = jax.random.PRNGKey(seed)
        done = jnp.zeros((nb,), jnp.bool_)
        eos = jnp.asarray(-1 if eos_token_id is None else int(eos_token_id),
                          jnp.int32)
        if self._sharding is not None:
            # partitioned entries call with committed mesh arrays only
            pos = self._sharding.put(pos, ())
            key = self._sharding.put(key, ())
            eos = self._sharding.put(eos, ())
            done = self._sharding.put_state_field("done", done,
                                                  self._head_major())
        args = (logits, kc, vc)
        if draft_caches is not None:
            args = args + tuple(draft_caches)
        args = args + (pos, key, done, eos)
        t = self._decode_temp(temperature)
        if t is not None:
            t = jnp.asarray(t, jnp.float32)
            if self._sharding is not None:
                t = self._sharding.put(t, ())
            args = args + (t,)
        return args

    def generate(self, input_ids, max_new_tokens: int,
                 eos_token_id: Optional[int] = None,
                 do_sample: bool = False,
                 temperature: Optional[float] = None,
                 seed: int = 0, quant: Optional[str] = None) -> np.ndarray:
        """Serve a decode: the whole token loop is ONE exported fused
        module execution. Eos id (``None`` or negative = no eos), seed
        and — on current bundles — temperature are runtime inputs;
        ``do_sample``/``top_k``/``top_p`` were fixed at export and a
        mismatching request is a contract violation. ``quant`` is a
        cross-check against the recipe baked into the bundle
        (``decode_mode.quant``): an unquantized bundle refuses a
        quantized ask typed (``QuantMismatchError``) and vice versa —
        ``None`` serves whatever was exported. Speculative bundles
        (``decode_mode.speculative``) additionally run the exported
        draft prefill and record the round/acceptance totals in
        ``last_spec_stats``."""
        if self.meta["kind"] != "llama_decoder":
            raise ValueError(f"bundle kind {self.meta['kind']!r} cannot "
                             "generate; use run()")
        if quant is not None:
            from paddle_tpu.quantization.kv_cache import (
                QuantMismatchError, canonical_quant)
            want, have = canonical_quant(quant), self.quant_recipe
            if want != have:
                raise QuantMismatchError(
                    f"this bundle was exported with quant recipe "
                    f"{have or 'none'!r} (weights are baked StableHLO "
                    f"constants); the ask for {want or 'none'!r} cannot "
                    f"be served — re-export the decoder with the "
                    f"matching quant=")
        import jax.numpy as jnp

        from paddle_tpu.inference.generate import _normalize_eos
        eos_token_id = _normalize_eos(eos_token_id)

        mode = self.meta.get("decode_mode")
        if mode is None:
            if do_sample or eos_token_id is not None:
                raise ValueError(
                    "this bundle predates fused-decode entries and serves "
                    "greedy-without-eos only; re-export it for "
                    "sampling/eos support")
        elif bool(do_sample) != bool(mode["do_sample"]):
            raise ValueError(
                f"bundle decode entries were exported with do_sample="
                f"{mode['do_sample']} (temperature={mode['temperature']}, "
                f"top_k={mode['top_k']}, top_p={mode['top_p']}); "
                f"requested do_sample={do_sample}")
        spec = (mode or {}).get("speculative")

        ids = np.asarray(input_ids)
        B, S = ids.shape
        # admission hook for batch-conditional faults (OOM above batch B)
        from paddle_tpu.runtime.resilience import fault_injector
        fault_injector.on_call("bundle.generate", batch=B)
        if S + max_new_tokens > self.meta["max_len"]:
            raise ValueError(
                f"prompt {S} + {max_new_tokens} new tokens exceeds the "
                f"bundle's max_len {self.meta['max_len']}")
        # exact batch bucket, else the smallest exported batch that fits
        # (prompt rows padded with zeros, outputs trimmed back; decode
        # rows are independent, so padding is always sound here)
        min_b = B if self.allow_bucket_padding else None
        batches = sorted({b["batch"] for b in self.meta["prefill_buckets"]
                          if b["seq"] == S
                          and (b["batch"] == B
                               or (min_b is not None
                                   and b["batch"] >= min_b))})
        if not batches:
            have = [(b["batch"], b["seq"])
                    for b in self.meta["prefill_buckets"]]
            raise ValueError(
                f"no prefill bucket for (B={B}, S={S}); exported: {have}")
        nb = batches[0]
        pf = next(b for b in self.meta["prefill_buckets"]
                  if b["batch"] == nb and b["seq"] == S)

        # bucket capacity: plain entries decode steps+1 tokens (scan steps
        # + the last pick); speculative entries' ``steps`` IS the output
        # buffer size
        def cap(b):
            return b["steps"] + (0 if b.get("speculative") else 1)

        want_spec = spec is not None
        cands = [b for b in self.meta["decode_buckets"]
                 if b["batch"] == nb and cap(b) >= max_new_tokens
                 and bool(b.get("speculative")) == want_spec]
        if not cands:
            have = [(b["batch"], cap(b))
                    for b in self.meta["decode_buckets"]]
            raise ValueError(
                f"no decode bucket with B={nb}, "
                f"capacity>={max_new_tokens}; exported (batch, capacity): "
                f"{have}")
        dc = min(cands, key=cap)

        fed = ids
        if nb != B:
            self.padded_calls += 1
            fed = np.concatenate(
                [ids, np.zeros((nb - B, S), ids.dtype)], axis=0)
        fed_d = jnp.asarray(fed, jnp.int32)
        if self._sharding is not None:
            fed_d = self._sharding.put(fed_d, ())

        def run_level(dcb):
            """One serve attempt at one decode bucket, from fresh caches
            (a failed higher rung may have consumed its donated
            buffers)."""
            use_spec = bool(dcb.get("speculative"))
            kc, vc = self._make_cache(nb)
            logits, kc, vc = self._run_entry(pf["file"], "bundle.prefill",
                                             fed_d, kc, vc)
            draft_caches = None
            if use_spec:
                dpf = next(b for b in self.meta["draft_prefill_buckets"]
                           if b["batch"] == nb and b["seq"] == S)
                dkc, dvc = self._make_cache(nb, "draft_caches")
                _, dkc, dvc = self._run_entry(
                    dpf["file"], "bundle.draft_prefill", fed_d, dkc, dvc)
                draft_caches = (dkc, dvc)
            site = "bundle.spec_decode" if use_spec else "bundle.decode"
            out = self._run_entry(dcb["file"], site,
                                  *self._decode_args(
                                      logits, kc, vc, S, nb, eos_token_id,
                                      seed, temperature=temperature,
                                      draft_caches=draft_caches))
            return out, use_spec

        # serve-side degradation ladder: the speculative bucket steps
        # down to a plain fused bucket of the same batch/capacity when
        # the bundle exported one (export_decoder_bundle plain_fallback)
        ladder = [("speculative" if want_spec else "fused", dc)]
        if want_spec:
            plain = [b for b in self.meta["decode_buckets"]
                     if b["batch"] == nb and not b.get("speculative")
                     and cap(b) >= max_new_tokens]
            if plain:
                ladder.append(("fused", min(plain, key=cap)))

        from paddle_tpu.flags import flags as _flags
        from paddle_tpu.runtime.resilience import (
            DecodeFailedError, DegradationEvent, GenerateResult,
            classify_error, record_event)
        self._events = []
        self.last_resilience = None
        degradations = []
        out, use_spec, level = None, False, None
        for li, (name, dcb) in enumerate(ladder):
            try:
                out, use_spec = run_level(dcb)
                level = name
                break
            except Exception as e:
                if classify_error(e) != "transient":
                    raise
                if (li == len(ladder) - 1
                        or not _flags.resilience_auto_degrade):
                    import paddle_tpu.obs as obs
                    obs.record_crash(
                        "bundle.ladder_exhausted", error=e,
                        extra={"site": "bundle.generate",
                               "failed_level": name,
                               "bundle_dir": self._dir})
                    raise DecodeFailedError(
                        f"bundle decode failed at ladder level {name!r} "
                        f"with no further fallback: {str(e)[:300]}",
                        events=list(self._events), last_error=e) from e
                ev = DegradationEvent(
                    site="bundle.generate", from_level=name,
                    to_level=ladder[li + 1][0],
                    error_class=type(e).__name__, error=str(e)[:300])
                record_event(ev)
                self._events.append(ev)
                degradations.append(ev)
        if use_spec:
            toks, sr, sa = out
            r, a = int(sr), int(sa)
            self.last_spec_stats = {
                "rounds": r, "accepted_drafts": a,
                "acceptance_len_mean": (a / r) if r else float(
                    spec["num_speculative_tokens"]),
                "num_speculative_tokens": spec["num_speculative_tokens"],
            }
        else:
            toks = out
            self.last_spec_stats = None
        toks = np.asarray(toks)[:B, :max_new_tokens]
        if eos_token_id is not None:
            from paddle_tpu.inference.generate import _trim_after_eos
            toks = _trim_after_eos(toks, int(eos_token_id))
        self.last_resilience = {
            "level": level,
            "requested_level": ladder[0][0],
            "retries": sum(1 for e in self._events
                           if getattr(e, "kind", "") == "retry"),
            "degradations": [e.as_dict() for e in degradations],
            "events": [e.as_dict() for e in self._events],
        }
        return GenerateResult.wrap(
            np.concatenate([ids, toks.astype(ids.dtype)], axis=1),
            self.last_resilience)
