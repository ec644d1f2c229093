"""KV-cache autoregressive decoding for LlamaForCausalLM.

Capability analog of the reference's decode stack —
paddle/phi/kernels/fusion/gpu/block_multi_head_attention_kernel.cu
(block-table KV cache attention) and the fused generation ops — in the
TPU-native form: a PURE functional forward with a statically-shaped KV
cache — head-major ``(B, KV, max_len, D)``, GQA and MHA alike (the
decode-kernel layout); one buffer per cache layer (a weight layer,
times the passes of a looped model:
``LlamaConfig.num_cache_layers``), so that a step writes its token rows
into each buffer in place (one array stacked over layers cost a copy of
the whole cache out of and back into the stack every step, 35 % of a
serving step on the v5e with GQA and 58 % with MHA: PERF.md section 6,
PR 27) —
so prefill and every decode step are each
ONE cached-compile XLA program (no recompiles across steps; static shapes
are what the MXU wants). Block tables are unnecessary: XLA owns memory, and
a padded dense cache + position mask is the layout it tiles best.

Decode attention: GQA and MHA (``rep = 1``) route through the Pallas
decode-attention kernel (ops/pallas/decode_attention.py — no repeated-KV
materialization, only a row's live positions read) where the routing
takes it; else XLA's masked dense read over the whole buffer. The Pallas
flash kernel covers chunked prefill (bottom-right-aligned causal,
sq != sk).

Positions may be a traced scalar (the classic lockstep decode) OR a
per-row ``(B,)`` vector: speculative decoding accepts a variable number
of draft tokens per row per round, so each row owns its cache write
offset, causal mask bound, and rope phase (``_cache_write`` vmaps the
dynamic-update-slice over the batch in that case; one token a row on one
TPU is ``_cache_update``'s ``kv_row_write`` kernel call).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM

__all__ = ["LlamaDecoder", "DecodeState", "LoopedDraftError",
           "WindowedModelError", "LatentCacheError"]


class LoopedDraftError(ValueError):
    """``draft_model='skip:N'`` over a looped model: the layer-skip view
    drafts with the first N layers of depth, and a model that runs its
    layers ``total_ut_steps`` times has no such prefix of depth."""


class WindowedModelError(ValueError):
    """A path that reads or writes cache positions by their index (the
    speculative verify, a prefill that starts past position 0) was asked
    of a model with windowed layers, whose rolling buffers keep a
    position at ``position % window``."""


class LatentCacheError(ValueError):
    """A path that holds keys and values per KV head (the prefix cache's
    slabs, an int8 cache, the speculative verify, a decoder bundle, the
    mesh's partition rules) was asked of a model with multi-head latent
    attention, whose cache layer is a compressed latent and one rotated
    key part (models/axk1.py)."""


class ConsumedStateError(RuntimeError):
    """A dispatch was handed buffers an earlier dispatch had been given
    to keep: the chunk program takes its carry's KV caches and the ring
    prefill takes the admission ring (donated, so the program writes
    them in place), and what a caller still holds of them afterwards is
    deleted. Go on from the state the call returned."""


def _consumed(tree) -> bool:
    """Whether the buffers of ``tree`` went into a donating dispatch.
    One call donates a whole argument, so its first leaf speaks for the
    rest."""
    leaves = jax.tree_util.tree_leaves(tree)
    return bool(leaves) and isinstance(leaves[0], jax.Array) \
        and not isinstance(leaves[0], jax.core.Tracer) \
        and leaves[0].is_deleted()


@dataclasses.dataclass
class DecodeState:
    """The exported/re-enterable carry of the fused decode loop.

    Everything the loop needs to resume is a plain array (exportable as
    AOT entry inputs, scatter-updatable row by row by the serving
    engine's admission path): next-token ``logits``, both KV-cache
    buffers, PER-ROW cache positions, PER-ROW raw uint32 RNG keys (each
    row's sample stream depends only on its own key — admitting a new
    request into a neighbouring row can't shift it), the done mask and
    per-row eos ids (``-1`` = no eos for that row) and temperatures.
    ``decode_chunk`` advances the state by T tokens in ONE dispatch;
    chaining chunks is bit-exact with run-to-completion for greedy.
    The chunk program CONSUMES the state it is given — ``kc`` and ``vc``
    are donated, so the caches are written in place and never copied —
    and ``consumed`` says so afterwards; the state to go on from is the
    one the call returned.

    A SPECULATIVE carry (``init_decode_state(draft_model=...)``)
    additionally holds the draft model's KV caches (``dkc``/``dvc``), a
    per-row pending token ``tok`` (the last emitted-but-not-yet-cached
    token; ``-1`` = "no pending token, pick from ``logits``" — the state
    of a freshly admitted row) and per-row CUMULATIVE acceptance stats
    (``spec_rounds``/``spec_accepted``, reset at admission). In that mode
    ``logits`` are the verify logits of the pending token's position —
    finite (the serving engine's corruption guard still works) but NOT
    pick-ready; the ``tok`` sentinel governs the next pick. ``nv`` is an
    OUTPUT of a speculative chunk: the per-row count of valid tokens in
    the returned ``(B, T+K)`` buffer — ``T..T+K`` of them, the per-row
    overflow being the accepted draft tail of the chunk's last round.
    """

    logits: Any           # (B, V) f32 — logits the next pick samples from
    kc: Any               # target KV caches: a tuple of one 4-D buffer
    vc: Any               #   per layer (see _empty_cache)
    pos: Any              # (B,) i32 — per-row next cache write position
    keys: Any             # (B, 2) u32 — per-row RNG keys
    done: Any             # (B,) bool — frozen rows (eos hit / slot free)
    eos: Any              # (B,) i32 — per-row eos id, -1 = none
    temp: Any             # (B,) f32 — per-row sampling temperature
    dkc: Any = None       # draft caches (speculative chunks)
    dvc: Any = None
    tok: Any = None       # (B,) i32 — pending token, -1 = pick from logits
    spec_rounds: Any = None    # (B,) i32 — cumulative verify rounds
    spec_accepted: Any = None  # (B,) i32 — cumulative accepted drafts
    nv: Any = None        # (B,) i32 — valid tokens in the last chunk's buf
    adapter_idx: Any = None    # (B,) i32 — per-row LoRA adapter index into
    #                            the stacked (N+1, ...) delta arrays;
    #                            0 = base-only (None = no adapters at all,
    #                            keeping non-LoRA traces identical)
    spec_on: Any = None   # (B,) bool — per-row speculative enable: False
    #                       rows decode verify-free (a=0, target pick) in
    #                       the SAME speculative chunk program (None = all
    #                       rows speculate, the pre-multiplex behaviour)
    spec: Any = None      # host-side: {"ekey", "K"} engine routing meta
    moe: Any = None       # (3,) i32, an OUTPUT of a chunk over a model with
    #                       routed feed-forwards: token-expert pairs that
    #                       landed on held experts and held experts touched,
    #                       both summed over the chunk's steps and routed
    #                       layers, and the largest count one expert took
    #                       in one step (None for every other model)
    steps_done: int = 0   # host-side: loop steps executed so far

    @property
    def consumed(self) -> bool:
        """True once a chunk dispatch has taken this state's caches."""
        return _consumed(self.kc)


def _rope_at(x, pos, cfg, p):
    """Rotate (B, S, H, D) by positions ``pos + [0..S)``: a dynamic slice
    of the tables precomputed at init from the training-path frequency
    function (_rope_tables), so decode can never diverge from training if
    rope scaling changes — and no per-step exp/pow work. ``pos`` may be a
    scalar or a per-row (B,) vector (speculative rows advance unevenly)."""
    S = x.shape[1]
    d2 = p["rope.cos"].shape[1]
    if jnp.ndim(pos) == 1:
        idx = pos[:, None] + jnp.arange(S)                  # (B, S)
        cos = jnp.take(p["rope.cos"], idx, axis=0).astype(x.dtype)
        sin = jnp.take(p["rope.sin"], idx, axis=0).astype(x.dtype)
        cos, sin = cos[:, :, None, :], sin[:, :, None, :]
    else:
        cos = jax.lax.dynamic_slice(p["rope.cos"], (pos, 0),
                                    (S, d2)).astype(x.dtype)
        sin = jax.lax.dynamic_slice(p["rope.sin"], (pos, 0),
                                    (S, d2)).astype(x.dtype)
        cos, sin = cos[None, :, None, :], sin[None, :, None, :]
    if cfg.rope_interleaved:
        # pairs (2i, 2i + 1) rotate together, and come out as halves
        x1, x2 = x[..., 0::2], x[..., 1::2]
    else:
        x1, x2 = x[..., :d2], x[..., d2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _mm(x, p, name, sharded=False, aidx=None):
    """x @ weight, transparently using the int8 weight-only path when the
    decoder quantized this matrix (weight stays int8 in HBM — half the
    weight bandwidth, which bounds small-batch decode; reference analog:
    weight_only_linear, paddle/phi/kernels/fusion/gpu/). On TPU the
    dequant happens INSIDE the Pallas matmul tile (ops/pallas/int8_matmul)
    — XLA's astype-then-dot materializes the bf16 weight and loses the
    bandwidth win (measured slower than bf16). Under a mesh (``sharded``)
    the Pallas tile is skipped: the hand-written kernel has no GSPMD
    partitioning rule, so the dequant-matmul falls back to the XLA form,
    which shards like any dot.

    ``aidx`` (B,) i32 multiplexes per-row LoRA deltas when the params
    carry stacked ``lora.{name}.A`` (N+1, d_in, r) / ``.B`` (N+1, r,
    d_out) arrays: each row gathers ITS adapter's pair and adds
    ``(x @ A[idx]) @ B[idx]`` to the base product — row 0 is all-zero, so
    base rows pay only the rank-r epsilon and every tenant mix stays one
    dispatch. The delta applies identically over the int8 base (fp16/fp32
    adapters over a quantized trunk: the AWQ observation that the weight
    STREAM is the decode cost — rank-r stacks barely add to it)."""
    q = p.get(name + ":int8")
    if q is not None:
        scale = p[name + ":scale"]
        lead = x.shape[:-1]
        x2 = x.reshape((-1, x.shape[-1]))
        from paddle_tpu.ops.pallas import int8_matmul as i8
        if (not sharded and jax.default_backend() == "tpu"
                and i8.supported(x2, q)):
            out = i8.int8_matmul(x2, q, scale)
        else:
            out = (x2 @ q.astype(x.dtype)) * scale.astype(x.dtype)
        out = out.reshape(lead + (q.shape[1],))
    else:
        out = x @ p[name]
    if aidx is not None:
        A = p.get("lora." + name + ".A")
        if A is not None:
            Bm = p["lora." + name + ".B"]
            Ai = jnp.take(A, aidx, axis=0)          # (B, d_in, r)
            Bi = jnp.take(Bm, aidx, axis=0)         # (B, r, d_out)
            xa = x.astype(Ai.dtype)
            if x.ndim == 3:                         # (B, S, d_in)
                d = jnp.einsum("bsd,bdr->bsr", xa, Ai)
                d = jnp.einsum("bsr,bro->bso", d, Bi)
            else:                                   # (B, d_in)
                d = jnp.einsum("bd,bdr->br", xa, Ai)
                d = jnp.einsum("br,bro->bo", d, Bi)
            out = out + d.astype(out.dtype)
    return out


def _decode_kernels(pos, sharded) -> bool:
    """Whether a decode step's attention over the cache may go through the
    Pallas kernels (``decode_attention``, ``decode_attention_pair``,
    ``eva_chunk_pool``), as far as the call itself says — each kernel's
    ``supported`` then answers for the shapes: ``pos`` a scalar or one
    position a row, one device (a mesh takes the XLA forms, which shard by
    propagation), ``flags.use_decode_attention``, and a TPU backend (or
    ``flags.decode_attention_interpret``, for the CPU tests). One
    predicate for ``_kv_attention`` and ``_eva_attention``."""
    from paddle_tpu.flags import flags as _flags
    from paddle_tpu.ops.pallas import _routing
    return bool(jnp.ndim(pos) <= 1 and not sharded
                and _flags.use_decode_attention and _routing.kernel_backend())


def _cache_update(kbuf, vbuf, kt, vt, pos, head_major, sharded=False):
    """Write a layer's new keys ``kt`` and values ``vt`` into its K and V
    cache buffers at [pos, pos+S) -> ``(kbuf, vbuf)``. One write, whose
    form follows what the call can observe (an explicit predicate, no
    fallback after an error): ONE token a row at per-row positions on one
    device — the serving chunk's decode step — is one ``kv_row_write``
    kernel call for both buffers (ops/pallas/kv_row_write.py), where the
    kernel takes the buffers and the backend is a TPU (or
    ``flags.decode_attention_interpret``, for the CPU tests); XLA's own
    lowering of that write on the v5e is a serial loop over the rows,
    2.8-3.7 us a row a buffer whatever the bytes, 3.5 ms of a 17.2 ms
    step at 96 rows x 10 buffers where the kernel's 5 calls take 0.3
    (PERF.md section 6, PR 34). Everything else — a scalar ``pos`` (prefills, the lockstep
    step), ``S > 1`` at per-row positions (the speculative verify's
    uneven advance), a mesh, a quantized cache's ``(..., 1)`` scale leaf
    (its int8 leaf takes the kernel) — is ``_cache_write`` a buffer."""
    from paddle_tpu.ops.pallas import _routing
    from paddle_tpu.ops.pallas import kv_row_write as _kw
    from paddle_tpu.quantization.kv_cache import (is_quantized_kv,
                                                  quantize_kv_rows)
    rows = jnp.ndim(pos) == 1 and not sharded and _routing.kernel_backend()
    if rows and is_quantized_kv(kbuf):
        qk, qv = quantize_kv_rows(kt), quantize_kv_rows(vt)
        if _kw.supported(kbuf["q"], qk["q"], head_major):
            kq, vq = _kw.kv_row_write(kbuf["q"], vbuf["q"], qk["q"],
                                      qv["q"], pos, head_major=head_major)
            return ({"q": kq, "s": _cache_write(kbuf["s"], qk["s"], pos,
                                                head_major)},
                    {"q": vq, "s": _cache_write(vbuf["s"], qv["s"], pos,
                                                head_major)})
    elif (rows and _kw.supported(kbuf, kt, head_major)
          and _kw.supported(vbuf, vt, head_major)):
        return _kw.kv_row_write(kbuf, vbuf, kt, vt, pos,
                                head_major=head_major)
    return (_cache_write(kbuf, kt, pos, head_major, sharded),
            _cache_write(vbuf, vt, pos, head_major, sharded))


def _cache_write(buf, t, pos, head_major, sharded=False):
    """Write t into ONE layer's cache buffer at [pos, pos+S). Scalar pos:
    a single dynamic-update-slice. Per-row (B,) pos: the same DUS vmapped
    over the batch (ONE scatter with sorted, unique indices, which the
    v5e compiler expands into a loop over the rows — each row lands at
    its own offset, the speculative-decode requirement). A quantized buffer
    (``int8wk``) quantizes the incoming rows by per-row absmax and
    updates the int8 and scale leaves with the SAME index math (the
    scale keeps a last dim of 1, so ranks line up).

    ``sharded`` may be the live ``DecodeSharding`` (not just a bool): the
    per-row branch then lowers through ``shard_map`` — dp splits the
    batch, tp splits the head axis, and the per-row DUS touches only its
    own row's shard, so the LOCAL body is exactly the single-device body
    and no collective is ever needed. That is the trusted sharded
    lowering of the speculative uneven cache advance (the former
    ``SpeculativeMeshError``); axes the guard drops (non-dividing dims)
    replicate, and the body still computes identical values per replica."""
    from paddle_tpu.quantization.kv_cache import (is_quantized_kv,
                                                  quantize_kv_rows)
    if is_quantized_kv(buf):
        qt = quantize_kv_rows(t)
        return {"q": _cache_write(buf["q"], qt["q"], pos, head_major,
                                  sharded),
                "s": _cache_write(buf["s"], qt["s"], pos, head_major,
                                  sharded)}
    if jnp.ndim(pos) == 1:
        if head_major:     # buf (B, KV, L, D), t (B, KV, S, D)
            f = lambda c, u, p0: jax.lax.dynamic_update_slice(  # noqa: E731
                c, u, (0, p0, 0))
        else:              # buf (B, L, KV, D), t (B, S, KV, D)
            f = lambda c, u, p0: jax.lax.dynamic_update_slice(  # noqa: E731
                c, u, (p0, 0, 0))
        upd = jax.vmap(f)
        srd = sharded if (sharded and not isinstance(sharded, bool)) \
            else None
        if srd is not None:
            ent = srd.state_entries("kc", buf.ndim, head_major)
            bspec = srd.guarded(buf.shape, ent)
            tspec = srd.guarded(t.shape, ent)
            pspec = srd.guarded(pos.shape, srd.state_entries("pos", 1))
            return jax.shard_map(
                upd, mesh=srd.jax_mesh, in_specs=(bspec, tspec, pspec),
                out_specs=bspec, check_vma=False)(buf, t, pos)
        return upd(buf, t, pos)
    at = (0, 0, pos, 0) if head_major else (0, pos, 0, 0)
    return jax.lax.dynamic_update_slice(buf, t, at)


def _row_scatter(dst, src, idx):
    """Scatter whole batch rows ``src[j] -> dst[idx[j]]`` on the leading
    (batch) axis of every per-layer cache buffer, recursing over the
    per-layer tuple and quantized ``{"q", "s"}`` leaves. ``idx`` entries
    >= dst's batch size DROP (``mode="drop"``) — the admission-ring
    convention maps empty ring rows to that sentinel (NEVER pass raw -1:
    negative scatter indices wrap). Used both to stage admission-prefill
    rows into the ring and to splice ring rows into the live carry inside
    the chunk program."""
    from paddle_tpu.quantization.kv_cache import is_quantized_kv
    if is_quantized_kv(dst):
        return {"q": _row_scatter(dst["q"], src["q"], idx),
                "s": _row_scatter(dst["s"], src["s"], idx)}
    if isinstance(dst, tuple):
        return tuple(_row_scatter(d, s, idx) for d, s in zip(dst, src))
    return dst.at[idx].set(src, mode="drop")


def _rms(x, w, eps):
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), -1, keepdims=True)
    return (x.astype(jnp.float32) * jax.lax.rsqrt(var + eps)
            ).astype(x.dtype) * w


def _stream_read(h, cfg):
    """The residual stream as a sub-layer's norm reads it: a float32
    stream (``cfg.fp32_skip_add``, models/evabyte.py) in the compute
    dtype, any other as it is."""
    return h.astype(jnp.dtype(cfg.dtype)) if cfg.fp32_skip_add else h


def _prefill_rows(t, true_len, L: int, head_major: bool):
    """The rows a prefill from position 0 leaves in a rolling buffer of
    ``L`` positions, out of its ``S > L`` fresh rows ``t``: slot ``s``
    holds the last position ``p < true_len`` with ``p % L == s``. The
    padded tail past ``true_len`` is left out (in a buffer that wraps it
    would land on live positions); where ``true_len < L`` the slots from
    it on hold an arbitrary row, masked until decode writes them."""
    S = t.shape[2] if head_major else t.shape[1]
    n = (jnp.full((t.shape[0],), S, jnp.int32) if true_len is None
         else true_len)[:, None]
    s = jnp.arange(L)[None, :]
    idx = jnp.clip(s + L * ((n - 1 - s) // L), 0, S - 1)       # (B, L)
    if head_major:
        return jnp.take_along_axis(t, idx[:, None, :, None], axis=2)
    return jnp.take_along_axis(t, idx[:, :, None, None], axis=1)


@functools.partial(jax.jit, static_argnames=("window", "interpret",
                                             "scale"))
def _flash_prefill(q, k, v, *, window, interpret, scale=None):
    """The flash forward of ``_fresh_attention`` as a function of its own:
    a prefill calls it once a cache layer with the same shapes, and an
    inner ``jit`` is traced and lowered ONCE a program — as 12 separate
    ``pallas_call``s Mistral's seven admission buckets took 1.8 s each
    longer to trace and lower than the kernel-free programs they replace,
    12.6 s of every serving run's set-up with every program found in the
    compile cache (PERF.md section 6, PR 37); XLA inlines the calls, so
    the compiled program is the same. ``interpret`` is part of the
    trace's key only: the kernel asks ``_routing.use_interpret()`` itself,
    and a test that patches it must not be served the other mode's
    trace. ``scale``: the softmax's, ``head_dim ** -0.5`` if None."""
    del interpret
    from paddle_tpu.ops.pallas import flash_attention as _fa
    return _fa.flash_attention_fn(q, k, v, causal=True, window=window,
                                  scale=scale)


def _fresh_attention(q, k, v, window, sharded):
    """Causal attention of a prefill from position 0 over its own fresh
    keys (B, S, KV, D), under a band of ``window`` positions where the
    layer has one: blockwise through the flash forward kernel, which
    neither computes nor fetches blocks outside the band and never holds
    an (S, S) score matrix; XLA's masked attention over the same S keys
    where the kernel does not take the shape, the program runs under a
    mesh, or the backend is not the kernels' (``kernel_backend``: one
    rule for windowed and plain layers). K and V are repeated to H heads
    first (a grouped index map in the kernel would spare that: ROADMAP
    S3)."""
    from paddle_tpu.ops.pallas import _routing
    from paddle_tpu.ops.pallas import flash_attention as _fa
    B, S, H, D = q.shape
    rep = H // k.shape[2]
    if rep > 1:
        k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
    if window is not None and window >= S:
        window = None                       # the band is all of the past
    if (not sharded and _routing.kernel_backend()
            and _fa.supported(q.shape, k.shape, True, window)):
        out = _flash_prefill(q, k, v, window=window,
                             interpret=_routing.use_interpret())
        return out.reshape(B, S, H * D)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(
        jnp.float32(D)).astype(q.dtype)
    d = jnp.arange(S)[:, None] - jnp.arange(S)[None, :]
    mask = d >= 0 if window is None else jnp.logical_and(d >= 0, d < window)
    scores = jnp.where(mask, scores.astype(jnp.float32), -jnp.inf)
    attn = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", attn, v).reshape(B, S, H * D)


def _kv_attention(cfg, li: int, ci: int, q, k, v, kc, vc, pos, max_len,
                  sharded, true_len, from_zero=False):
    """Attention of layer ``li`` through cache layer ``ci``'s one buffer
    of keys and one of values by position: write the fresh rows, then
    attend — over what, the call's own facts decide (``_block_forward``
    says how a layer's kind enters). q (B, S, H, D), k, v (B, S, KV, D),
    rotated -> (out (B, S, H * D), kc, vc).

    ``S > 1`` FROM POSITION 0 (``from_zero``, a trace-time fact of the
    entry point, never a test of the traced ``pos``; a model with
    windowed layers has no other ``S > 1`` forward) attends over the S
    FRESH keys it brought (``_fresh_attention``): every buffer row from S
    on is empty and masked, so scores against the whole buffer — (B, H, S,
    max_len) in float32, 8 x the keys at bucket 256 of ``max_len`` 2048 —
    are the same sum at several times the bytes (PERF.md section 6, PR
    37). Everything else attends over the BUFFER: ``S > 1`` at ``pos >
    0`` (the prefix-cache suffix prefill, whose keys before ``pos`` live
    only there; the speculative verify), every ``S == 1`` step, and a
    quantized cache's prefill, which reads back the dequantized rows a
    decode step will read."""
    from paddle_tpu.quantization.kv_cache import (dequantize_kv,
                                                  is_quantized_kv)
    B, S, H, D = q.shape
    KV = k.shape[2]
    rolling = cfg.has_windows
    quant_kv = is_quantized_kv(kc[ci])
    fresh = S > 1 and (rolling or (from_zero and not quant_kv))
    L = cfg.cache_len(ci, max_len)

    rep = H // KV
    # (B, KV, L, D) tiles feed the Pallas kernel, GQA and MHA (rep = 1)
    # alike: the layout is the config's one predicate
    head_major = cfg.cache_head_major
    kt = jnp.swapaxes(k, 1, 2) if head_major else k
    vt = jnp.swapaxes(v, 1, 2) if head_major else v
    # each CACHE layer owns its buffer: the token rows are written into
    # buffer ci in place and attention reads that array. (From a carry
    # stacked over layers XLA copied the whole layer out and back in here,
    # every layer of every step. Measured on the v5e at 7B widths, PR 27:
    # a serving step 23.3 -> 15.3 ms with 12 GQA layers x 16 slots, 23.6
    # -> 9.9 ms with 8 MHA layers x 8 slots. PERF.md section 6.)
    if fresh:
        if S > L:
            kt = _prefill_rows(kt, true_len, L, head_major)
            vt = _prefill_rows(vt, true_len, L, head_major)
        at = 0
    else:
        at = pos % L if rolling else pos
    kc_l, vc_l = _cache_update(kc[ci], vc[ci], kt, vt, at, head_major,
                               sharded)
    kc = kc[:ci] + (kc_l,) + kc[ci + 1:]
    vc = vc[:ci] + (vc_l,) + vc[ci + 1:]

    from paddle_tpu.ops.pallas import decode_attention as _da
    use_kernel = (head_major and S == 1 and _decode_kernels(pos, sharded)
                  and _da.supported(q[:, 0],
                                    kc_l["q"] if quant_kv else kc_l))
    # per-row qpos: scalar pos broadcasts as (1,1,S,1), vector as (B,1,S,1)
    qpos = (jnp.reshape(pos, (-1, 1, 1, 1))
            + jnp.arange(S)[None, None, :, None])

    def live_rows():
        # the buffer's rows a decode step attends over: all up to its own
        # position, which in a rolling buffer that has wrapped is every row
        return jnp.minimum(pos + 1, L) if rolling else pos + 1

    if fresh:
        out = _fresh_attention(q, k, v, cfg.layer_window(li), sharded)
    elif use_kernel:
        # one-kernel cache attention, GQA or MHA (block_multi_head_attention
        # capability): no repeated-KV materialization, online softmax,
        # cache blocks past a row's valid prefix neither fetched nor
        # computed; ``pos`` may be per-row (the chunked serving path,
        # where rows sit at different cache offsets). Int8 caches
        # (int8wk) stream int8 tiles and dequant in VMEM against their
        # per-row scales. Measured on the v5e at Mistral-7B widths (12
        # layers, 16 slots, max_len 2048, rows at 32-2047 live positions,
        # 475 on average): 0.97 ms of an 11.3 ms serving step, where
        # the live KV's bytes need 0.47 ms at 819 GB/s (PERF.md section
        # 6, PR 29; 5.0 ms of a 15.3 ms step while the kernel streamed
        # all of max_len, one KV head a grid step: ledger, PR 28).
        if quant_kv:
            out = _da.decode_attention(
                q[:, 0], kc_l["q"], vc_l["q"], live_rows(),
                k_scale=kc_l["s"], v_scale=vc_l["s"]).reshape(B, S, H * D)
        else:
            out = _da.decode_attention(q[:, 0], kc_l, vc_l,
                                       live_rows()).reshape(B, S, H * D)
    elif head_major:
        kk = jnp.repeat(dequantize_kv(kc_l, q.dtype), rep, axis=1)
        vv = jnp.repeat(dequantize_kv(vc_l, q.dtype), rep, axis=1)
        scores = jnp.einsum("bqhd,bhkd->bhqk", q, kk) / jnp.sqrt(
            jnp.float32(D)).astype(q.dtype)
        kpos = jnp.arange(L)[None, None, None, :]
        mask = (kpos < jnp.minimum(qpos + 1, L) if rolling
                else kpos <= qpos)                # bottom-right causal
        scores = jnp.where(mask, scores.astype(jnp.float32), -jnp.inf)
        attn = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
        out = jnp.einsum("bhqk,bhkd->bqhd", attn, vv).reshape(B, S, H * D)
    else:
        kk = dequantize_kv(kc_l, q.dtype)         # (B, max_len, KV, D)
        vv = dequantize_kv(vc_l, q.dtype)
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, kk) / jnp.sqrt(
            jnp.float32(D)).astype(q.dtype)
        kpos = jnp.arange(L)[None, None, None, :]
        mask = (kpos < jnp.minimum(qpos + 1, L) if rolling
                else kpos <= qpos)                # bottom-right causal
        scores = jnp.where(mask, scores.astype(jnp.float32), -jnp.inf)
        attn = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
        out = jnp.einsum("bhqk,bkhd->bqhd", attn, vv).reshape(B, S, H * D)
    return out, kc, vc


def _eva_attention(p, cfg, li: int, ci: int, q, k, v, kc, vc, pos, max_len,
                   sharded, true_len):
    """EVA attention (ops/eva.py) of layer ``li`` through cache layer
    ``ci``'s TWO leaves, both head-major: buffer ``2 * ci`` the WINDOW
    leaf, ``W = cfg.cache_len(2 * ci, max_len)`` exact positions, and
    buffer ``2 * ci + 1`` the SUMMARY leaf, one entry a chunk of ``C``
    positions. q, k, v (B, S, H, D), rotated. -> (out (B, S, H * D), kc,
    vc).

    A decode step (``S == 1``; ``pos`` a scalar or per row) follows one
    rule, from ``pos`` alone: write the token's row at ``pos % W`` — the
    window leaf is RESET at a window's end, not rolled —, pool the chunk
    the row lies in from the window leaf into summary ``pos // C`` (an
    incomplete chunk's entry is written too and written over by every
    later row of the chunk; no query sees a chunk of its own window, so
    only the complete one is ever read: at ``pos % C == C - 1``), and
    attend in one softmax over ``pos % W + 1`` window rows and ``(pos //
    W) * (W / C)`` summaries: through ``decode_attention_pair`` where the
    routing takes it (``_decode_kernels``, the one-leaf kernel's
    predicate too), else XLA's masked form. No branch on a row's position.

    ``S > 1`` is a prefill from position 0 (``cfg.has_windows``): causal
    attention inside each aligned window and attention over the summaries
    of the windows before under one softmax, through the
    ``eva_prefill_attention`` kernel (ops/pallas/eva_attention.py), or
    ``ops/eva.py``'s masked form where the kernel does not take the
    shape or the program runs under a mesh. It FILLS both
    leaves: every chunk's summary, and the rows of the window that holds
    position ``true_len - 1`` ((B,), None = S)."""
    from paddle_tpu.ops import eva as _eva
    from paddle_tpu.ops.pallas import decode_attention as _da
    from paddle_tpu.ops.pallas import eva_attention as _ea
    B, S, H, D = q.shape
    C = cfg.chunk_size
    W = cfg.cache_len(2 * ci, max_len)
    pre = f"model.layers.{li}.self_attn."
    mu, phi = p[pre + "adaptive_mu_k"], p[pre + "adaptive_phi"]
    wi, si = 2 * ci, 2 * ci + 1

    def put(kc, vc, bi, kb, vb):
        return (kc[:bi] + (kb,) + kc[bi + 1:],
                vc[:bi] + (vb,) + vc[bi + 1:])

    if S > 1:
        # head-major from here on: the layout of the kernel and of both
        # leaves, so each of q, k, v is transposed once
        qt, kt, vt = (jnp.swapaxes(x, 1, 2) for x in (q, k, v))
        Sw = -(-S // W) * W                 # whole windows, zeros after S
        pad = ((0, 0), (0, 0), (0, Sw - S), (0, 0))
        # (the barrier: the pooling and the kernel read ONE head-major
        # copy of k and of v; without it the compiler also wrote a float32
        # one for the pooling, 537 MB each at 32 x 32768 x 128)
        kp, vp = jax.lax.optimization_barrier(
            (jnp.pad(kt, pad), jnp.pad(vt, pad)))
        ks, vs = _eva.chunk_summaries(kp, vp, mu, phi, C)
        if not sharded and _ea.supported(Sw, W, W // C):
            out = _ea.eva_prefill_attention(
                *(x.reshape(B * H, -1, D)
                  for x in (jnp.pad(qt, pad), kp, vp, ks, vs)),
                window=W, per=W // C)
            out = jnp.swapaxes(out.reshape(B, H, Sw, D)[:, :, :S], 1, 2)
        else:
            out = _eva.eva_attention(q, k, v, ks, vs, W, C)
        # the window leaf: the rows of the window position true_len - 1
        # lies in (slots past it hold later padding or an earlier
        # window's rows, masked until decode writes them)
        if S > W:
            n = (jnp.full((B,), S, jnp.int32) if true_len is None
                 else true_len)[:, None]
            idx = jnp.clip((n - 1) // W * W + jnp.arange(W)[None, :], 0,
                           S - 1)[:, None, :, None]
            kt = jnp.take_along_axis(kt, idx, axis=2)
            vt = jnp.take_along_axis(vt, idx, axis=2)
        kc, vc = put(kc, vc, wi, *_cache_update(kc[wi], vc[wi], kt, vt, 0,
                                               True, sharded))
        kc, vc = put(kc, vc, si, *_cache_update(kc[si], vc[si], ks, vs, 0,
                                               True, sharded))
        return out.reshape(B, S, H * D), kc, vc

    at = pos % W
    kw, vw = _cache_update(kc[wi], vc[wi], jnp.swapaxes(k, 1, 2),
                           jnp.swapaxes(v, 1, 2), at, True, sharded)
    kc, vc = put(kc, vc, wi, kw, vw)
    # the chunk the new row lies in, whole, from the window leaf: through
    # the pooling kernel where the decode kernel below runs (XLA's gather
    # of a row's chunk wants the leaf transposed and copies it whole,
    # every layer of every step: PERF.md section 6, PR 36), else gathered
    start = at // C * C
    kernels = _decode_kernels(pos, sharded)
    if kernels and _ea.pool_supported(kw, C):
        ks, vs = _ea.eva_chunk_pool(kw, vw, mu, phi, start, chunk=C)
    else:
        if jnp.ndim(pos) == 1:
            cut = jax.vmap(lambda c, s0: jax.lax.dynamic_slice(
                c, (0, s0, 0), (H, C, D)))
            kch, vch = cut(kw, start), cut(vw, start)
        else:
            kch = jax.lax.dynamic_slice(kw, (0, 0, start, 0), (B, H, C, D))
            vch = jax.lax.dynamic_slice(vw, (0, 0, start, 0), (B, H, C, D))
        ks, vs = _eva.chunk_summaries(kch, vch, mu, phi, C)
    ksb, vsb = _cache_update(kc[si], vc[si], ks, vs, pos // C, True, sharded)
    kc, vc = put(kc, vc, si, ksb, vsb)
    n_win, n_sum = at + 1, pos // W * (W // C)
    if kernels and _da.supported_pair(q[:, 0], kw, ksb):
        out = _da.decode_attention_pair(q[:, 0], kw, vw, n_win, ksb, vsb,
                                        n_sum)
        return out.reshape(B, S, H * D), kc, vc
    sc = jnp.float32(D) ** -0.5

    def scores(kb, n):
        s_ = jnp.einsum("bhd,bhld->bhl", q[:, 0], kb).astype(jnp.float32)
        live = jnp.arange(kb.shape[2])[None, :] < jnp.reshape(n, (-1, 1))
        return jnp.where(live[:, None, :], s_ * sc, -jnp.inf)
    pr = jax.nn.softmax(jnp.concatenate(
        [scores(kw, n_win), scores(ksb, n_sum)], axis=-1), axis=-1
    ).astype(q.dtype)
    out = (jnp.einsum("bhl,bhld->bhd", pr[..., :kw.shape[2]], vw)
           + jnp.einsum("bhl,bhld->bhd", pr[..., kw.shape[2]:], vsb))
    return out.reshape(B, S, H * D), kc, vc


# bytes of one (B, S, heads, width) tensor of a latent prefill's expanded
# attention past which its heads go in groups (_latent_attention)
_HEAD_GROUP_BYTES = 128 << 20


def _head_group(bytes_per_head: int, heads: int) -> int:
    """Heads a group: all where a tensor of all of them fits
    ``_HEAD_GROUP_BYTES``, else ``heads`` halved until it does (or is
    odd)."""
    g = heads
    while g % 2 == 0 and g * bytes_per_head > _HEAD_GROUP_BYTES:
        g //= 2
    return g


def _latent_attention(p, cfg, li: int, ci: int, x, kc, vc, pos, sharded,
                      from_zero):
    """Multi-head latent attention (models/axk1.py) of layer ``li`` over
    its normed input ``x`` (B, S, hidden), through cache layer ``ci``'s
    two leaves, head-major with one "head": buffer ``ci`` of ``kc`` the
    normed latent ``c_kv`` (B, 1, L, kv_lora_rank), of ``vc`` the rotated
    key part ``k_pe`` shared by every head (B, 1, L, rope_cache_width:
    ``qk_rope_head_dim`` values, zeros to a lane tile). -> (out (B, S,
    heads * v_head_dim), kc, vc).

    Two forms of one function, chosen by what the call is:

    - ``S > 1`` FROM POSITION 0 (``from_zero``) is EXPANDED: per-head keys
      ``[c_kv W_UK ; k_pe]`` and values ``c_kv W_UV`` of the S fresh
      positions, causal attention over them through the flash forward
      kernel where it routes (values zero-padded to the key width, the
      padding's output dropped), else XLA's masked form;
    - everything else (a decode step; ``S > 1`` at ``pos > 0``) is
      ABSORBED: each head's query is taken into latent space, ``q~ = W_UK
      q_nope``, scored against the latent and the rope key over the
      buffer, and the weighted latent brought back through ``W_UV``; a
      decode step through the ``mla_decode_attention`` kernel
      (ops/pallas/mla_decode_attention.py: each live latent block read
      once for all heads) where the routing takes it, else XLA's masked
      form over the buffer.

    The softmax scale is the config's (YaRN's ``mscale`` squared)."""
    from paddle_tpu.ops.pallas import _routing
    from paddle_tpu.ops.pallas import flash_attention as _fa
    from paddle_tpu.ops.pallas import mla_decode_attention as _mla
    B, S, _ = x.shape
    H, dn, dr, dv = (cfg.num_attention_heads, cfg.qk_nope_head_dim,
                     cfg.qk_rope_head_dim, cfg.v_head_dim)
    Q, R, eps = cfg.q_lora_rank, cfg.kv_lora_rank, cfg.rms_norm_eps
    scale = cfg.softmax_scale
    pre = f"model.layers.{li}.self_attn."
    # one matmul for the query's bottleneck and the latent with its key
    a = _mm(x, p, pre + "qa_kva.weight", sharded)
    q = _mm(_rms(a[..., :Q], p[pre + "q_a_layernorm.weight"], eps), p,
            pre + "q_b_proj.weight", sharded).reshape(B, S, H, dn + dr)
    ckv = _rms(a[..., Q:Q + R], p[pre + "kv_a_layernorm.weight"], eps)
    kpe = _rope_at(a[..., None, Q + R:], pos, cfg, p)        # (B, S, 1, dr)
    qn, qp = q[..., :dn], _rope_at(q[..., dn:], pos, cfg, p)
    wkv = p[pre + "kv_b_proj.weight"].reshape(R, H, dn + dv)
    pad = cfg.rope_cache_width - dr
    fresh = S > 1 and from_zero
    kc_l, vc_l = _cache_update(
        kc[ci], vc[ci], ckv[:, None],
        jnp.pad(jnp.swapaxes(kpe, 1, 2), ((0, 0),) * 3 + ((0, pad),)),
        0 if fresh else pos, True, sharded)
    kc = kc[:ci] + (kc_l,) + kc[ci + 1:]
    vc = vc[:ci] + (vc_l,) + vc[ci + 1:]
    if fresh:
        qf = jnp.concatenate([qn, qp], -1)
        # a length the flash forward does not tile (a prompt prefilled
        # unbucketed) runs it over zeros to whole 512-position blocks: the
        # appended keys lie after every true query, so the causal mask
        # hides them, and the appended queries' rows are dropped
        Sp = S if _fa.supported(qf.shape, qf.shape, True) \
            else -(-S // 512) * 512
        flash = (not sharded and _routing.kernel_backend()
                 and _fa.supported((B, Sp, H, dn + dr), (B, Sp, H, dn + dr),
                                   True))
        tail = ((0, 0), (0, Sp - S), (0, 0), (0, 0))

        def grow(t):
            return jnp.pad(t, tail) if Sp > S else t
        outs = []
        # heads in groups whose (S, heads, width) tensors stay under
        # _HEAD_GROUP_BYTES: each group's expanded keys and values, the
        # flash call's copies of them and its float32 log-sum-exp (padded
        # to 128 lanes) are alive for that group alone
        G = _head_group(B * Sp * (dn + dr) * x.dtype.itemsize, H)
        for g0 in range(0, H, G):
            kv = jnp.einsum("bsr,rhd->bshd", ckv, wkv[:, g0:g0 + G])
            k = jnp.concatenate([kv[..., :dn],
                                 jnp.broadcast_to(kpe, (B, S, G, dr))], -1)
            v, q_g = kv[..., dn:], qf[:, :, g0:g0 + G]
            if flash:
                vp = jnp.pad(grow(v), ((0, 0),) * 3 + ((0, dn + dr - dv),))
                o = _flash_prefill(grow(q_g), grow(k), vp, window=None,
                                   scale=scale,
                                   interpret=_routing.use_interpret())
                outs.append((o[:, :S] if Sp > S else o)[..., :dv])
                continue
            sc = jnp.einsum("bqhd,bkhd->bhqk", q_g, k,
                            preferred_element_type=jnp.float32) * scale
            sc = jnp.where(jnp.tril(jnp.ones((S, S), bool)), sc, -jnp.inf)
            pr = jax.nn.softmax(sc, axis=-1).astype(x.dtype)
            outs.append(jnp.einsum("bhqk,bkhd->bqhd", pr, v))
        out = outs[0] if len(outs) == 1 else jnp.concatenate(outs, 2)
        return out.reshape(B, S, H * dv), kc, vc
    qa = jnp.einsum("bshd,rhd->bshr", qn, wkv[..., :dn])     # (B, S, H, R)
    qp = jnp.pad(qp, ((0, 0),) * 3 + ((0, pad),))
    if (S == 1 and _decode_kernels(pos, sharded)
            and _mla.supported(qa[:, 0], kc_l, vc_l)):
        o = _mla.mla_decode_attention(qa[:, 0], qp[:, 0], kc_l, vc_l,
                                      pos + 1, scale=scale)[:, None]
    else:
        sc = (jnp.einsum("bshr,blr->bhsl", qa, kc_l[:, 0],
                         preferred_element_type=jnp.float32)
              + jnp.einsum("bshd,bld->bhsl", qp, vc_l[:, 0],
                           preferred_element_type=jnp.float32)) * scale
        qpos = (jnp.reshape(pos, (-1, 1, 1, 1))
                + jnp.arange(S)[None, None, :, None])
        kpos = jnp.arange(kc_l.shape[2])[None, None, None, :]
        sc = jnp.where(kpos <= qpos, sc, -jnp.inf)
        pr = jax.nn.softmax(sc, axis=-1).astype(x.dtype)
        o = jnp.einsum("bhsl,blr->bshr", pr, kc_l[:, 0])
    out = jnp.einsum("bshr,rhd->bshd", o, wkv[..., dn:])
    return out.reshape(B, S, H * dv), kc, vc


def _moe_reduce(c):
    """(n, 3) routing counts -> (3,): pairs on held experts and held
    experts touched add up, the largest count one expert took is a
    maximum."""
    return jnp.concatenate([jnp.sum(c[:, :2], 0), jnp.max(c[:, 2:], 0)])


def _block_forward(p, cfg: LlamaConfig, li: int, ci: int, h, kc, vc, pos,
                   max_len, sharded=False, aidx=None, true_len=None,
                   live=None, moe_stats=None, from_zero=False):
    """One decoder block over h (B, S, H), weights of layer ``li``,
    writing K/V into CACHE layer ``ci`` at [pos, pos+S); attention reads
    that whole buffer masked to < pos+S with causal alignment to the
    bottom-right (query i attends to <= pos+i) — or, where the forward
    starts at position 0 by construction (``from_zero``, trace-time
    static) with ``S > 1``, the S fresh keys alone, which are all the
    buffer then holds (``_kv_attention``). ``ci`` is ``li`` for a
    model that makes one pass over its layers; a looped model's pass
    ``t`` keeps its own keys and values in ``t * num_hidden_layers +
    li``. Both are trace-time integers. Where the parameters hold
    ``input_layernorm_2`` / ``post_attention_layernorm_2`` (a sandwich
    block, models/ouro.py) each sub-layer's output is normed before it
    joins the residual stream.
    ``pos``: scalar or per-row (B,) vector. ``sharded`` (trace-time
    static): the decoder runs under a GSPMD mesh — hand-written Pallas
    kernels (no partitioning rules) give way to the XLA forms, which
    shard via sharding propagation. ``aidx`` (B,) i32 routes per-row LoRA
    deltas through every projection (see ``_mm``).

    What the layer is, the config says at trace time and the parameters
    by their presence. ``cfg.layer_rope(li)``: whether queries and keys
    are rotated. ``cfg.layer_window(li)``: a windowed layer's cache
    buffer is ROLLING — ``cfg.cache_len(ci, max_len)`` positions, a
    position kept at ``pos % length``; keys are rotated before they are
    cached and softmax does not care for order, so a decode step attends
    over the buffer's first ``min(pos + 1, length)`` rows as it does over
    a plain one. In a model with any windowed layer (``cfg.has_windows``)
    ``S > 1`` is a prefill FROM POSITION 0 whatever the entry says: every
    layer attends over its own fresh keys (``_fresh_attention``) and
    writes the last ``min(true_len, length)`` of them (``true_len`` (B,),
    None = S).
    ``cfg.eva``: the layer's attention is EVA over a window leaf and a
    summary leaf (``_eva_attention``), buffers ``2 * ci`` and ``2 * ci +
    1`` of the carry. ``cfg.latent``: it is multi-head latent attention
    (``_latent_attention``), buffer ``ci`` of ``kc`` the latent and of
    ``vc`` the rope key. ``cfg.fp32_skip_add``: ``h`` is
    float32 and each sub-layer reads it in the compute dtype.
    ``self_attn.q_norm`` / ``k_norm``: per-head RMSNorm on q and k. A
    fourth column block of the fused q|k|v matrix: an output gate,
    ``(o * sigmoid(x Wg)) Wo``. ``mlp.router``: a routed feed-forward
    over this chip's held experts plus a shared expert
    (``ops/moe.py:routed_ffn``), ``live`` (B, S) bool the rows that reach
    an expert, its three counts and the chosen experts appended to
    ``moe_stats``."""
    B, S, _ = h.shape
    H, KV, D = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    pre = f"model.layers.{li}."
    eps = cfg.rms_norm_eps

    x = _rms(_stream_read(h, cfg), p[pre + "input_layernorm.weight"], eps)
    gate = None
    if cfg.latent:
        out, kc, vc = _latent_attention(p, cfg, li, ci, x, kc, vc, pos,
                                        sharded, from_zero)
    else:
        qkv = _mm(x, p, pre + "self_attn.qkv.weight", sharded, aidx)
        q = qkv[..., :H * D].reshape(B, S, H, D)
        k = qkv[..., H * D:H * D + KV * D].reshape(B, S, KV, D)
        if qkv.shape[-1] > (H + 2 * KV) * D:
            v = qkv[..., (H + KV) * D:(H + 2 * KV) * D].reshape(B, S, KV, D)
            gate = qkv[..., (H + 2 * KV) * D:]
        else:
            v = qkv[..., H * D + KV * D:].reshape(B, S, KV, D)
        qn = p.get(pre + "self_attn.q_norm.weight")
        if qn is not None:
            q = _rms(q, qn, eps)
            k = _rms(k, p[pre + "self_attn.k_norm.weight"], eps)
        if cfg.layer_rope(li):
            q = _rope_at(q, pos, cfg, p)
            k = _rope_at(k, pos, cfg, p)
        if cfg.eva:
            out, kc, vc = _eva_attention(p, cfg, li, ci, q, k, v, kc, vc,
                                         pos, max_len, sharded, true_len)
        else:
            out, kc, vc = _kv_attention(cfg, li, ci, q, k, v, kc, vc, pos,
                                        max_len, sharded, true_len,
                                        from_zero)
    if gate is not None:
        out = out * jax.nn.sigmoid(gate)
    att = _mm(out, p, pre + "self_attn.o_proj.weight", sharded, aidx)
    w2 = p.get(pre + "input_layernorm_2.weight")
    h = h + (att if w2 is None else _rms(att, w2, eps))

    x = _rms(_stream_read(h, cfg),
             p[pre + "post_attention_layernorm.weight"], eps)
    router = p.get(pre + "mlp.router.weight")
    ffn = pre + ("mlp." if router is None else "mlp.shared_experts.")
    gu = _mm(x, p, ffn + "gate_up.weight", sharded, aidx)
    F_ = gu.shape[-1] // 2
    a = jax.nn.silu(gu[..., :F_]) * gu[..., F_:]
    mlp = _mm(a, p, ffn + "down_proj.weight", sharded, aidx)
    if router is not None:
        from paddle_tpu.ops.moe import routed_ffn
        routed, counts, chosen = routed_ffn(
            x.reshape(B * S, -1), router, p[pre + "mlp.expert_bias"],
            p[pre + "mlp.experts_gate_up"], p[pre + "mlp.experts_down"],
            top_k=cfg.num_experts_per_tok, route_norm=cfg.route_norm,
            route_scale=cfg.route_scale, expert_offset=cfg.expert_offset,
            live=None if live is None else live.reshape(B * S),
            sharded=bool(sharded), n_group=cfg.n_group,
            topk_group=cfg.topk_group)
        mlp = mlp + routed.reshape(B, S, -1)
        if moe_stats is not None:
            moe_stats.append((counts, chosen))
    w2 = p.get(pre + "post_attention_layernorm_2.weight")
    h = h + (mlp if w2 is None else _rms(mlp, w2, eps))
    if cfg.fp32_skip_add and S > 1:
        # a prefill's float32 stream is WRITTEN at the end of each block:
        # left to itself the compiler keeps every block's two bf16
        # addends instead and re-adds them in each reader, 537 MB more a
        # layer at 32768 x 4096 (AOT, PERF.md section 6, PR 36)
        h = jax.lax.optimization_barrier(h)
    return h, kc, vc


def _forward_cached(p, cfg: LlamaConfig, ids, kc, vc, pos, max_len,
                    return_all: bool = False, sharded: bool = False,
                    aidx=None, true_len=None, live=None, moe_stats=None,
                    from_zero: bool = False):
    """ids (B, S) -> logits (B, V) plus the updated caches: of the LAST
    position, or, for right-padded rows of ``true_len`` (B,) tokens each
    (the admission prefills), of position ``true_len - 1`` — the row is
    gathered out of the residual stream BEFORE the last final norm and
    the head, so neither runs over the S - 1 positions nobody samples
    from (the (B, S, V) logits: 0.42 GB in bfloat16 at bucket 2048 of a
    102 400-row head). ``return_all=True`` gives all S positions (B, S,
    V): the speculative verify alone, which scores every drafted
    position in one batched forward. ``pos``: scalar or per-row (B,)
    vector. ``aidx`` (B,) i32: per-row LoRA adapter index (projections
    only — the head stays base).

    ``from_zero`` (trace-time static, set by the entry point): this
    forward starts at position 0 BY CONSTRUCTION — the solo and draft
    prefills, which pass the literal 0, and the ring admission, whose
    traced ``pos`` the engine always fills with zeros: the entry knows,
    the tracer does not. With ``S > 1`` its attention is over its own S
    fresh keys instead of the cache buffer (``_kv_attention``). Entries
    that may start anywhere (``admit_prefill``'s per-row offsets, the
    speculative verify, every step) leave it False and attend over the
    buffer.

    The layer list is run ``cfg.total_ut_steps`` times (once for every
    model but a looped one), each pass over its own cache layers and
    closed by the final norm, whose output feeds the next pass. The
    passes are written out at trace time, so every cache index is a
    Python integer: a buffer indexed by a traced pass number would be
    copied whole out of and back into the carry every step (PERF.md
    section 6, PRs 27 and 28)."""
    h = p["model.embed_tokens.weight"][ids]
    if cfg.embedding_scale != 1.0:
        h = h * jnp.asarray(cfg.embedding_scale, h.dtype)
    if cfg.fp32_skip_add:
        h = h.astype(jnp.float32)        # the residual stream's dtype
    L = cfg.num_hidden_layers
    for t in range(cfg.total_ut_steps):
        for li in range(L):
            h, kc, vc = _block_forward(p, cfg, li, t * L + li, h, kc, vc,
                                       pos, max_len, sharded, aidx,
                                       true_len, live, moe_stats, from_zero)
        if (t == cfg.total_ut_steps - 1 and true_len is not None
                and not return_all):
            # only the last pass's norm: an earlier pass's output feeds
            # the next pass whole
            h = jnp.take_along_axis(h, (true_len - 1)[:, None, None],
                                    axis=1)                 # (B, 1, hidden)
        h = _rms(_stream_read(h, cfg), p["model.norm.weight"],
                 cfg.rms_norm_eps)
    hh = h if return_all else h[:, -1]
    if "head:int8" in p:
        logits = _mm(hh, p, "head", sharded).astype(jnp.float32)
    else:
        head = (p["model.embed_tokens.weight"].T if cfg.tie_word_embeddings
                else p["lm_head.weight"])
        logits = (hh @ head).astype(jnp.float32)
    if logits.shape[-1] > cfg.vocab_size:
        # a head of several vocabularies (head h predicts token t + 1 +
        # h, models/evabyte.py): the next token is picked from the first
        logits = logits[..., :cfg.vocab_size]
    return logits, kc, vc


def _build_params(model: LlamaForCausalLM, max_len: int,
                  weight_dtype: Optional[str]):
    """Snapshot + decode-shape a model's weights: fused qkv / gate_up
    matmuls, optional int8 weight-only quantization, precomputed rope
    tables for the whole cache window. Shared by the target decoder and
    any separate-weights draft model (speculative decoding)."""
    raw = {name: t.value for name, t in model.state_dict().items()}
    # fuse qkv and gate/up per layer (one matmul each; fewer kernels). An
    # output gate rides the q|k|v matmul as a fourth block; a routed
    # layer's gate|up is its shared expert's, and its experts' stacks
    # (already gate|up and down, models/afmoe.py) pass through by
    # reference: a second copy of them would not fit beside the first
    for li in range(model.config.num_hidden_layers):
        pre = f"model.layers.{li}."
        if model.config.latent:
            # latent attention: the query's bottleneck and the latent
            # with its rope key in one matmul
            raw[pre + "self_attn.qa_kva.weight"] = jnp.concatenate(
                [raw.pop(pre + "self_attn.q_a_proj.weight"),
                 raw.pop(pre + "self_attn.kv_a_proj_with_mqa.weight")],
                axis=1)
        else:
            qkv = [raw.pop(pre + "self_attn.q_proj.weight"),
                   raw.pop(pre + "self_attn.k_proj.weight"),
                   raw.pop(pre + "self_attn.v_proj.weight")]
            if pre + "self_attn.gate_proj.weight" in raw:
                qkv.append(raw.pop(pre + "self_attn.gate_proj.weight"))
            raw[pre + "self_attn.qkv.weight"] = jnp.concatenate(qkv,
                                                                axis=1)
        ffn = pre + ("mlp." if pre + "mlp.gate_proj.weight" in raw
                     else "mlp.shared_experts.")
        raw[ffn + "gate_up.weight"] = jnp.concatenate(
            [raw.pop(ffn + "gate_proj.weight"),
             raw.pop(ffn + "up_proj.weight")], axis=1)
    if model.config.norm_add_unit_offset:
        # a norm that multiplies by 1 + w: folded here, so that the
        # programs' RMSNorm is the one form
        for name in [n for n in raw if n.endswith("norm.weight")]:
            raw[name] = raw[name] + jnp.ones((), raw[name].dtype)
    p = {}
    for name, v in raw.items():
        if (weight_dtype == "int8" and v.ndim == 2
                and ("self_attn." in name or "mlp." in name)
                and "mlp.router." not in name):
            from paddle_tpu.quantization import weight_quantize
            from paddle_tpu.framework.tensor import Tensor
            q, scale = weight_quantize(Tensor(v))
            p[name + ":int8"] = q.value
            p[name + ":scale"] = scale.value
            continue
        p[name] = v
    # the lm head (tied: transposed embedding) is the single biggest
    # matrix in the step — quantize a dedicated copy of it too
    if weight_dtype == "int8":
        from paddle_tpu.quantization import weight_quantize
        from paddle_tpu.framework.tensor import Tensor
        head = (p["model.embed_tokens.weight"].T
                if model.config.tie_word_embeddings
                else p.pop("lm_head.weight"))
        q, scale = weight_quantize(Tensor(head))
        p["head:int8"] = q.value
        p["head:scale"] = scale.value
    # precomputed rope tables for the whole cache window
    cos, sin = model.config.rope_tables(max_len,
                                        jnp.dtype(model.config.dtype))
    p["rope.cos"], p["rope.sin"] = cos, sin
    return p


def _spec_round(p, dp, cfg, dcfg, tok, pos, key, done, kc, vc, dkc, dvc,
                eos_id, temperature, max_len, *, K: int, do_sample: bool,
                use_eos: bool, top_k, top_p, sharded=False):
    """One draft-propose / target-verify / accept round (Leviathan et
    al., arXiv:2211.17192) as a pure trace-level function, so the SAME
    code runs inside the fused while-loop program AND as the per-round
    fallback's jitted step (that identity is what makes fused-vs-fallback
    token parity bit-exact).

    ``pos`` is PER-ROW (B,): acceptance is data-dependent, so rows
    advance by different amounts and each owns its cache offset. The
    draft runs K+1 single-token forwards from its own cache (the +1
    keeps the draft cache complete when every proposal is accepted); the
    target scores all K+1 positions in ONE batched cached forward.
    Acceptance: greedy = exact match against the target argmax;
    sampling = the rejection rule u < min(1, p(d)/q(d)) over the
    FILTERED (temperature/top-k/top-p) target/draft distributions, with
    the first rejection resampled from norm(max(p - q, 0)) — preserving
    the target distribution exactly. Rows that were done (eos) flush eos
    at the full K+1 rate so the output buffer fills like the non-
    speculative program's.

    Returns (emit (B, K+1), accepted (B,), next_tok (B,), key, done,
    kc, vc, dkc, dvc): emit slot j < a holds the accepted draft
    d_{j+1}, slot a the target's correction/bonus token; slots > a are
    padding the caller drops. Cache rows past each row's committed
    length are stale but masked, and the next round overwrites them
    before they could ever unmask.
    """
    B = tok.shape[0]
    if do_sample:
        key, sub = jax.random.split(key)
        rk = jax.random.split(sub, 3)
        dkeys = jax.random.split(rk[0], K)      # draft proposal keys
        u = jax.random.uniform(rk[1], (B, K))   # acceptance uniforms
        ckey = rk[2]                            # correction/bonus key

    def dbody(carry, j):
        cur, dkc, dvc = carry
        lg, dkc, dvc = _forward_cached(dp, dcfg, cur[:, None], dkc, dvc,
                                       pos + j, max_len, sharded=sharded)
        if do_sample:
            kj = jax.lax.dynamic_index_in_dim(
                dkeys, jnp.minimum(j, K - 1), keepdims=False)
            flt = _filter_logits(lg, temperature, top_k, top_p)
            nxt = jax.random.categorical(kj, flt,
                                         axis=-1).astype(jnp.int32)
            return (nxt, dkc, dvc), (nxt, flt)
        nxt = jnp.argmax(lg, -1).astype(jnp.int32)
        return (nxt, dkc, dvc), nxt

    (_, dkc, dvc), ys = jax.lax.scan(dbody, (tok, dkc, dvc),
                                     jnp.arange(K + 1))
    props = jnp.moveaxis((ys[0] if do_sample else ys)[:K], 0, 1)  # (B, K)
    seq = jnp.concatenate([tok[:, None], props], axis=1)       # (B, K+1)
    all_lg, kc, vc = _forward_cached(p, cfg, seq, kc, vc, pos, max_len,
                                     return_all=True,
                                     sharded=sharded)          # (B,K+1,V)
    if do_sample:
        pprob = jax.nn.softmax(
            _filter_logits(all_lg, temperature, top_k, top_p), axis=-1)
        qprob = jax.nn.softmax(jnp.moveaxis(ys[1][:K], 0, 1), axis=-1)
        pd = jnp.take_along_axis(pprob[:, :K], props[..., None],
                                 axis=-1)[..., 0]
        qd = jnp.take_along_axis(qprob, props[..., None], axis=-1)[..., 0]
        accept = u * qd < pd       # u < min(1, p/q) without the divide
        a = jnp.sum(jnp.cumprod(accept.astype(jnp.int32), axis=1), axis=1)
        pa = jnp.take_along_axis(pprob, a[:, None, None], axis=1)[:, 0]
        qa = jnp.take_along_axis(
            qprob, jnp.minimum(a, K - 1)[:, None, None], axis=1)[:, 0]
        resid = jnp.maximum(pa - qa, 0.0)
        rs = jnp.sum(resid, axis=-1, keepdims=True)
        # all-accepted rows draw the bonus token from p_K itself; a
        # degenerate all-zero residual (p <= q everywhere) falls back to p
        resid = jnp.where(rs > 0, resid / jnp.where(rs > 0, rs, 1.0), pa)
        dist = jnp.where((a == K)[:, None], pa, resid)
        corr = jax.random.categorical(ckey, jnp.log(dist),
                                      axis=-1).astype(jnp.int32)
    else:
        tgt = jnp.argmax(all_lg, -1).astype(jnp.int32)         # (B, K+1)
        match = props == tgt[:, :K]
        a = jnp.sum(jnp.cumprod(match.astype(jnp.int32), axis=1), axis=1)
        corr = jnp.take_along_axis(tgt, a[:, None], axis=1)[:, 0]
    jidx = jnp.arange(K + 1)[None, :]
    ext = jnp.concatenate([props, jnp.zeros((B, 1), jnp.int32)], axis=1)
    emit = jnp.where(jidx < a[:, None], ext,
                     jnp.where(jidx == a[:, None], corr[:, None], 0))
    if use_eos:
        a = jnp.where(done, K, a)    # finished rows flush eos full-rate
        emit = jnp.where(done[:, None], eos_id, emit)
        valid = jidx <= a[:, None]
        hit = jnp.logical_and(emit == eos_id, valid)
        after = (jnp.cumsum(hit.astype(jnp.int32), axis=1)
                 - hit.astype(jnp.int32)) > 0
        emit = jnp.where(jnp.logical_and(after, valid), eos_id, emit)
        done = jnp.logical_or(done, jnp.any(hit, axis=1))
    tok_next = jnp.take_along_axis(emit, a[:, None], axis=1)[:, 0]
    return emit, a, tok_next, key, done, kc, vc, dkc, dvc


def _spec_round_rows(p, dp, cfg, dcfg, tok, pos, keys, done, kc, vc, dkc,
                     dvc, eos, temp, max_len, *, K: int, do_sample: bool,
                     top_k, top_p, sharded=False, aidx=None, spec_on=None):
    """``_spec_round`` under the CHUNKED-SERVING carry contract: PER-ROW
    RNG keys (each row splits its OWN (2,) raw uint32 key per round, so
    its sample stream is invariant to batch neighbours — the admission
    contract ``ring_chunk_decode`` already honours), per-row eos ids (``-1``
    = none; rows already done flush their eos fill at the full K+1 rate)
    and per-row temperatures. Same Leviathan accept/reject math as
    ``_spec_round`` — greedy rounds are bit-identical, which is what the
    chunk-slicing-invariance tests ride on.

    ``aidx`` routes per-row LoRA deltas through the TARGET forwards only
    (verify + the committed pick); the draft stays base — a mismatched
    draft can only cost acceptance length, never correctness, because
    every emitted token is accept/reject-verified against the adapter-
    routed target. ``spec_on`` (B,) bool demotes False rows to verify-
    free decode INSIDE the same program: their acceptance is forced to 0
    BEFORE the correction draw and the correction distribution is the
    target's own position-0 law (``pa``), so a sampled spec-off row draws
    from exactly the filtered target distribution and a greedy spec-off
    row emits exactly the plain-decode argmax.

    Returns ``(emit (B, K+1), a (B,), tok_next (B,), lg_a (B, V), keys,
    done, kc, vc, dkc, dvc)``; ``lg_a`` is the verify logits at each
    row's accepted position — the freshest finite logits the carry can
    hold (NOT pick-ready: ``tok_next`` is the pending pick)."""
    B = tok.shape[0]
    fill = jnp.where(eos >= 0, eos, 0)
    if do_sample:
        kk = jax.vmap(jax.random.split)(keys)               # (B, 2, 2)
        keys_next, sub = kk[:, 0], kk[:, 1]
        rk = jax.vmap(lambda k: jax.random.split(k, 3))(sub)
        dkeys = jax.vmap(lambda k: jax.random.split(k, K))(rk[:, 0])
        u = jax.vmap(lambda k: jax.random.uniform(k, (K,)))(rk[:, 1])
        ckey = rk[:, 2]                                     # (B, 2)
    else:
        keys_next = keys

    def dbody(carry, j):
        cur, dkc, dvc = carry
        lg, dkc, dvc = _forward_cached(dp, dcfg, cur[:, None], dkc, dvc,
                                       pos + j, max_len, sharded=sharded)
        if do_sample:
            kj = jax.lax.dynamic_index_in_dim(
                dkeys, jnp.minimum(j, K - 1), axis=1, keepdims=False)
            flt = _filter_logits(lg, temp[:, None], top_k, top_p)
            nxt = jax.vmap(jax.random.categorical)(
                kj, flt).astype(jnp.int32)
            return (nxt, dkc, dvc), (nxt, flt)
        nxt = jnp.argmax(lg, -1).astype(jnp.int32)
        return (nxt, dkc, dvc), nxt

    (_, dkc, dvc), ys = jax.lax.scan(dbody, (tok, dkc, dvc),
                                     jnp.arange(K + 1))
    props = jnp.moveaxis((ys[0] if do_sample else ys)[:K], 0, 1)  # (B, K)
    seq = jnp.concatenate([tok[:, None], props], axis=1)       # (B, K+1)
    all_lg, kc, vc = _forward_cached(p, cfg, seq, kc, vc, pos, max_len,
                                     return_all=True,
                                     sharded=sharded, aidx=aidx)  # B,K+1,V
    if do_sample:
        pprob = jax.nn.softmax(
            _filter_logits(all_lg, temp[:, None, None], top_k, top_p),
            axis=-1)
        qprob = jax.nn.softmax(jnp.moveaxis(ys[1][:K], 0, 1), axis=-1)
        pd = jnp.take_along_axis(pprob[:, :K], props[..., None],
                                 axis=-1)[..., 0]
        qd = jnp.take_along_axis(qprob, props[..., None], axis=-1)[..., 0]
        accept = u * qd < pd
        a = jnp.sum(jnp.cumprod(accept.astype(jnp.int32), axis=1), axis=1)
        if spec_on is not None:
            a = jnp.where(spec_on, a, 0)   # BEFORE the pa/qa gathers: the
            #   spec-off correction must come from the position-0 law
        pa = jnp.take_along_axis(pprob, a[:, None, None], axis=1)[:, 0]
        qa = jnp.take_along_axis(
            qprob, jnp.minimum(a, K - 1)[:, None, None], axis=1)[:, 0]
        resid = jnp.maximum(pa - qa, 0.0)
        rs = jnp.sum(resid, axis=-1, keepdims=True)
        resid = jnp.where(rs > 0, resid / jnp.where(rs > 0, rs, 1.0), pa)
        dist = jnp.where((a == K)[:, None], pa, resid)
        if spec_on is not None:
            # spec-off rows sample the target distribution itself, not
            # the rejection residual — the verify-free decode law
            dist = jnp.where(spec_on[:, None], dist, pa)
        corr = jax.vmap(jax.random.categorical)(
            ckey, jnp.log(dist)).astype(jnp.int32)
    else:
        tgt = jnp.argmax(all_lg, -1).astype(jnp.int32)         # (B, K+1)
        match = props == tgt[:, :K]
        a = jnp.sum(jnp.cumprod(match.astype(jnp.int32), axis=1), axis=1)
        if spec_on is not None:
            a = jnp.where(spec_on, a, 0)
        corr = jnp.take_along_axis(tgt, a[:, None], axis=1)[:, 0]
    jidx = jnp.arange(K + 1)[None, :]
    ext = jnp.concatenate([props, jnp.zeros((B, 1), jnp.int32)], axis=1)
    emit = jnp.where(jidx < a[:, None], ext,
                     jnp.where(jidx == a[:, None], corr[:, None], 0))
    a = jnp.where(done, K, a)        # finished rows flush fill full-rate
    emit = jnp.where(done[:, None], fill[:, None], emit)
    valid = jidx <= a[:, None]
    hit = jnp.logical_and(emit == eos[:, None], valid)  # -1 never matches
    after = (jnp.cumsum(hit.astype(jnp.int32), axis=1)
             - hit.astype(jnp.int32)) > 0
    emit = jnp.where(jnp.logical_and(after, valid), fill[:, None], emit)
    done = jnp.logical_or(done, jnp.any(hit, axis=1))
    tok_next = jnp.take_along_axis(emit, a[:, None], axis=1)[:, 0]
    lg_a = jnp.take_along_axis(all_lg, a[:, None, None], axis=1)[:, 0]
    return emit, a, tok_next, lg_a, keys_next, done, kc, vc, dkc, dvc


class LlamaDecoder:
    """Compile-once greedy/sampling decoder with a static KV cache.

    Two executables per generate: ``prefill`` (fixed prompt length, pad to
    reuse) and ``fused_decode`` — the ENTIRE token loop (argmax or
    temperature/top-k/top-p sampling, per-step key splits, per-row eos
    freezing) as one ``lax.scan`` program, so a ``generate`` of N tokens
    is 2 device dispatches regardless of mode, with zero retraces across
    calls/seeds/eos ids/temperatures (temperature is a runtime input).
    With a ``draft_model`` (a smaller LlamaForCausalLM or a ``'skip:N'``
    layer-skip view of the target), ``generate`` runs SPECULATIVE
    decoding: the draft proposes K tokens per round from its own cache,
    the target verifies all K+1 positions in one batched forward, and
    accept/reject + per-row cache advance + eos freezing all live inside
    one ``lax.while_loop`` program — prefill(target) + prefill(draft) +
    ONE decode dispatch. ``dispatch_count`` counts executions so both
    one-dispatch properties are assertable in tests; the per-token
    ``step`` / per-round speculative fallback remain behind the
    ``decode_fallback`` flag.

    Resilience (runtime/resilience.py): every device dispatch retries
    transient backend errors (UNAVAILABLE and friends) with exponential
    backoff, and ``generate`` walks a DEGRADATION LADDER — fused
    speculative -> fused plain -> per-token fallback — stepping down
    automatically when a level keeps failing (``FLAGS_resilience_*``).
    Each retry/degradation is a typed event; the record rides on the
    returned array (``GenerateResult.resilience``) and on
    ``self.last_resilience``.
    """

    def __init__(self, model: LlamaForCausalLM, max_len: int = 512,
                 weight_dtype: Optional[str] = None, mesh=None,
                 partition_rules=None, quant: Optional[str] = None):
        """``quant`` picks the decode dtype recipe
        (quantization/kv_cache.resolve_decode_quant; default also via
        ``FLAGS_decode_quant`` / ``PADDLE_TPU_DECODE_QUANT``):

        - ``"int8w"`` — per-output-channel absmax int8 weight-only
          quantization of the decoder/MLP matmuls (embedding and norms
          stay in the activation dtype); the legacy
          ``weight_dtype="int8"`` argument is an alias. On TPU the
          dequant runs inside the Pallas matmul tile
          (ops/pallas/int8_matmul), so the quantized matrices stream
          int8 from HBM — halving the weight bandwidth that bounds
          small-batch decode (reference weight_only_linear capability).
        - ``"int8wk"`` — int8w PLUS an int8 KV cache: every written K/V
          row quantizes by per-row absmax (scales live beside the int8
          rows in the ``DecodeState`` carry) and dequantizes on load
          inside the scan body's attention — or inside the Pallas
          decode-attention tile — so neither the weights nor the cache
          ever materialize an fp copy in HBM. Refused typed on a mesh
          (``QuantizedKVMeshError``); ``int8w`` serves on a mesh via
          the XLA dequant form.

        Decode steps are kernel-count-sensitive (the scan body runs ~1ms
        of tiny ops on a 134M model): q/k/v and gate/up are concatenated
        at init into single fused matmuls (q_proj|k_proj|v_proj ->
        'self_attn.qkv', gate|up -> 'mlp.gate_up'), and the rope tables
        are precomputed once for max_len instead of per step.

        ``mesh``: a ``ProcessMesh`` / ``jax.sharding.Mesh`` /
        ``"dp:2,tp:4"`` spec — the decoder then runs TENSOR-PARALLEL over
        the ``tp`` axis and batch-parallel over ``dp``
        (inference/sharding.DecodeSharding): params are sharded by regex
        partition rules (``partition_rules`` overrides
        ``DEFAULT_DECODE_RULES``), the ``DecodeState`` carry — KV caches
        on the head axis, per-row pos/keys/done on dp — lives sharded on
        device across chunk re-entry, and every jitted entry pins its
        carry outputs to the same placements (sharding-preserving jit).
        Greedy and per-row-keyed sampled TOKENS are bit-exact with the
        single-device path — including SPECULATIVE decode, whose per-row
        uneven cache advance lowers through ``shard_map``
        (``_cache_update``); only speculative BUNDLE EXPORT from a
        mesh-built decoder still refuses typed
        (``SpeculativeMeshError``)."""
        from paddle_tpu.quantization.kv_cache import resolve_decode_quant
        self.quant = resolve_decode_quant(quant, weight_dtype)
        # legacy surface (bundle meta, draft-param reuse): any quantized
        # recipe quantizes the weights int8
        self.weight_dtype = "int8" if self.quant else None
        self.quant_kv = self.quant == "int8wk"
        self.cfg = model.config
        self.max_len = max_len
        self.sharding = None
        if mesh is not None:
            from paddle_tpu.inference.sharding import DecodeSharding
            self.sharding = (mesh if isinstance(mesh, DecodeSharding)
                             else DecodeSharding(mesh,
                                                 rules=partition_rules))
        elif partition_rules is not None:
            raise ValueError("partition_rules requires a mesh")
        if self.quant_kv and self.sharding is not None:
            from paddle_tpu.inference.sharding import QuantizedKVMeshError
            raise QuantizedKVMeshError(
                "quant='int8wk' does not run on a mesh yet: the int8 KV "
                "carry's scale buffers have no partition rules; use "
                "quant='int8w' (weight-only) on a mesh, or drop mesh=")
        if self.cfg.latent and (self.quant or self.sharding is not None):
            raise LatentCacheError(
                "a model with latent attention holds a latent and a rope "
                "key a cache layer: an int8 cache, int8 weights beside "
                "the latent's up-projection and the mesh's partition "
                "rules are all of keys and values per head — build the "
                "decoder without quant= and mesh=")
        if self.quant_kv and self.cfg.eva:
            raise WindowedModelError(
                "quant='int8wk' keeps an int8 K / V buffer by position; a "
                "model whose cache layer holds a window leaf and a summary "
                "leaf has no quantized form of either: use quant='int8w'")
        self.params = _build_params(model, max_len, self.weight_dtype)
        if self.sharding is not None:
            self.params = self.sharding.shard_params(self.params)
        cfg = self.cfg
        # trace-time static the closures below capture: the LIVE
        # DecodeSharding when the programs run under GSPMD (falsy
        # off-mesh — every `if not sharded` check still reads naturally,
        # and _cache_update can reach the mesh for its shard_map
        # lowering)
        shd = self.sharding if self.sharding is not None else False
        self._head_major = cfg.cache_head_major
        self.trace_count = 0     # python side effect: bumps only on (re)trace
        self.dispatch_count = 0  # one per device program execution
        self._spec_engines = {}  # draft-model state for speculative decode
        self.last_spec_stats = None
        self.last_resilience = None  # retry/degradation record of the last
        #                              generate (also on the result array)
        self._events = []        # typed events of the in-flight generate
        pin = self._pin

        routed = cfg.routed

        def prefill(p, ids, kc, vc):
            self.trace_count += 1
            logits, kc, vc = _forward_cached(p, cfg, ids, kc, vc, 0,
                                             max_len, sharded=shd,
                                             from_zero=True)
            return pin(logits=logits, kc=kc, vc=vc)

        def step(p, ids, kc, vc, pos):
            self.trace_count += 1
            logits, kc, vc = _forward_cached(p, cfg, ids, kc, vc, pos,
                                             max_len, sharded=shd)
            return pin(logits=logits, kc=kc, vc=vc)

        def fused_decode(p, logits0, kc, vc, pos0, key0, done0, eos_id,
                         temperature, steps: int, do_sample: bool,
                         use_eos: bool, top_k, top_p):
            """The whole token loop — sampling and EOS handling included —
            as ONE device program (lax.scan): N tokens cost a single host
            dispatch in EVERY decode mode. The jax.random key
            threads through the carry and splits once per step (identical
            stream to the per-token fallback); ``done0`` rows that hit
            ``eos_id`` freeze to eos, and the host trims post-eos columns
            after the fact (``_trim_after_eos``). Temperature is a RUNTIME
            scalar input (one compiled program / one AOT entry serves any
            temperature); top-k/top-p change program structure and stay
            static."""
            self.trace_count += 1

            def pick(logits, key, done):
                if do_sample:
                    key, sub = jax.random.split(key)
                    tok = _sample_from(logits, sub, temperature, top_k,
                                       top_p).astype(jnp.int32)
                else:
                    tok = jnp.argmax(logits, -1).astype(jnp.int32)
                if use_eos:
                    tok = jnp.where(done, eos_id, tok)
                    done = jnp.logical_or(done, tok == eos_id)
                return tok, key, done

            def body(carry, _):
                logits, kc, vc, pos, key, done = carry
                tok, key, done = pick(logits, key, done)
                logits, kc, vc = _forward_cached(p, cfg, tok[:, None], kc,
                                                 vc, pos, max_len,
                                                 sharded=shd)
                return (logits, kc, vc, pos + 1, key, done), tok

            (logits, _, _, _, key, done), toks = jax.lax.scan(
                body, (logits0, kc, vc, pos0, key0, done0), None,
                length=steps)
            last, _, _ = pick(logits, key, done)
            return jnp.concatenate([jnp.moveaxis(toks, 0, 1),
                                    last[:, None]], axis=1)

        def admit_rows(p, ids, kc, vc, true_len, pos0, aidx,
                       from_zero=False):
            """The traced body both admission entries share: forward the
            right-padded rows at their cache offsets; each row's logits
            are those of position ``true_len - 1`` of its bucket, the
            one row the head runs over (``_forward_cached``)."""
            # the padded tail reaches no routed expert, and is not
            # written into a rolling buffer
            live = (jnp.arange(ids.shape[1])[None, :] < true_len[:, None]
                    if routed else None)
            return _forward_cached(
                p, cfg, ids, kc, vc, pos0, max_len, sharded=shd, aidx=aidx,
                true_len=true_len, live=live, from_zero=from_zero)

        def admit_prefill(p, ids, kc, vc, true_len, pos0, aidx=None):
            """Length-bucketed admission prefill: ``ids`` is a batch of
            requests right-padded to one prompt bucket (one compiled
            program per (batch, bucket), not per distinct prompt length).
            ``true_len`` and ``pos0`` are PER-ROW ``(B,)`` vectors: each
            row's tokens land in the cache at ``[pos0, pos0+S)`` and its
            returned logits are those of position ``true_len - 1`` of the
            bucket — causal masking makes the padded tail invisible to
            them, and decode overwrites the tail's cache rows before they
            could ever unmask — so the admitted row decodes bit-exactly
            like an unpadded solo generate. ``pos0 > 0`` is the prefix-
            cache SUFFIX prefill (serving/prefix_cache.py): ``kc``/``vc``
            arrive preloaded with the cached prefix's KV rows ``[0,
            pos0)`` and only the uncached suffix is computed; several
            same-bucket admissions batch into one dispatch (per-row
            offsets keep their prefixes independent). ``aidx`` (B,) i32
            or None: each admitted row's prompt prefills through ITS
            adapter's deltas, so the cached prefix KV matches what a
            dense per-tenant model would have produced.

            Its attention is over the cache BUFFER (all ``max_len`` rows,
            masked past ``pos0 + i``): a row's keys before ``pos0`` are
            in the buffer and nowhere else, and ``pos0`` is a traced
            vector, so this entry cannot know a start of 0. A cold
            admission that should not pay for the empty rows goes through
            ``ring_admit_prefill``."""
            self.trace_count += 1
            logits, kc, vc = admit_rows(p, ids, kc, vc, true_len, pos0,
                                        aidx)
            return pin(logits=logits, kc=kc, vc=vc)

        def ring_admit_prefill(p, ids, kc, vc, true_len, pos0,
                               ring_logits, ring_kc, ring_vc, ring_idx,
                               aidx=None):
            """``admit_prefill`` that STAGES its results into the
            device-resident admission ring instead of returning them to
            host: the freshly prefilled rows scatter into ring rows
            ``ring_idx`` (host-chosen free slots) inside the SAME
            dispatch, and the next chunk program splices them into the
            live carry mid-chunk. Admission thus costs exactly its one
            counted prefill dispatch — the host-side ``_admit_row``
            scatter round-trip is gone. The three ring buffers are
            DONATED: the scatter writes the admitted rows in place and
            the returned ring is the one passed in, which the caller
            must not touch again (``kc``/``vc``, the empty pair the
            rows prefill from, are not).

            Every ring admission is COLD: the engine stages a request
            here from position 0 (``_admit_group_ring`` packs ``(req,
            0)``; a prefix-cache suffix goes through ``admit_prefill``),
            so ``pos0`` is all zeros by construction and ``kc``/``vc``
            hold nothing. The entry says so (``from_zero``) and the rows
            attend over their own S fresh keys, not over ``max_len``
            rows of which ``max_len - S`` are empty: ``flash_fwd`` once a
            layer on one device, XLA's (S, S) masked form under a mesh
            and where the kernel declines; a quantized cache (``int8wk``)
            keeps the buffer (``_kv_attention``)."""
            self.trace_count += 1
            logits, kc, vc = admit_rows(p, ids, kc, vc, true_len, pos0,
                                        aidx, from_zero=True)
            ring_logits = ring_logits.at[ring_idx].set(logits,
                                                       mode="drop")
            ring_kc = _row_scatter(ring_kc, kc, ring_idx)
            ring_vc = _row_scatter(ring_vc, vc, ring_idx)
            return pin(logits=ring_logits, kc=ring_kc, vc=ring_vc)

        def ring_chunk_decode(p, logits0, kc, vc, pos0, keys0, done0,
                              eos0, temp0, aidx0, ring_logits, ring_kc,
                              ring_vc, ring_slot, ring_pos, ring_keys,
                              ring_eos, ring_temp, ring_aidx,
                              steps: int, do_sample: bool,
                              top_k, top_p):
            """T steps of the fused token loop as ONE re-enterable
            dispatch — the decoder's only chunk program: the carry comes
            in and goes back out as plain arrays (DecodeState), so a
            serving engine can admit new requests into freed rows
            BETWEEN chunks instead of holding dead slots until the
            slowest row finishes (Orca-style iteration-level batching).
            Per-row everything: positions (rows admitted at different
            times sit at different cache offsets), eos ids (-1 = none),
            temperatures, and RNG keys — each row splits its OWN key per
            step, so a row's sample stream is invariant to its batch
            neighbours. Greedy chunks chained over N steps are bit-exact
            with the run-to-completion fused path (same
            pick-then-forward stream).

            ``kc`` and ``vc`` are DONATED: the returned caches are
            the ones passed in, written in place, and the caller's
            handles to them are dead after the call. The ring operands
            are only read — the ring outlives the chunk and the next
            admission writes into it.

            The DEVICE-SIDE slot-refill prologue: before the T-step
            scan, ring rows staged by ``ring_admit_prefill`` scatter
            into the carry at their destination slots (``ring_slot``;
            empty ring rows carry the B sentinel and drop). Admitting
            mid-stream therefore never adds a dispatch boundary — steady
            state is ONE fused dispatch per chunk per replica regardless
            of admission rate. With every ring operand ``None``
            (``LlamaDecoder.decode_chunk``, bundle entries, engines that
            admit by host scatter) the prologue is not traced. Because
            admission can rewrite per-row eos/temp, BOTH are part of the
            returned carry. ``aidx0``/``ring_aidx`` (B,) i32 or None:
            per-row LoRA adapter indices — part of the returned carry
            for the same reason (admission rewrites a freed slot's
            tenant)."""
            self.trace_count += 1
            B = logits0.shape[0]
            logits, pos, keys, done = logits0, pos0, keys0, done0
            eos, temp, aidx = eos0, temp0, aidx0
            if ring_slot is not None:
                tgt = jnp.where(ring_slot >= 0, ring_slot, B)
                logits = logits.at[tgt].set(ring_logits, mode="drop")
                kc = _row_scatter(kc, ring_kc, tgt)
                vc = _row_scatter(vc, ring_vc, tgt)
                pos = pos.at[tgt].set(ring_pos, mode="drop")
                keys = keys.at[tgt].set(ring_keys, mode="drop")
                done = done.at[tgt].set(False, mode="drop")
                eos = eos.at[tgt].set(ring_eos, mode="drop")
                temp = temp.at[tgt].set(ring_temp, mode="drop")
                if aidx is not None:
                    aidx = aidx.at[tgt].set(ring_aidx, mode="drop")

            def pick(logits, keys, done):
                if do_sample:
                    kk = jax.vmap(jax.random.split)(keys)       # (B,2,2)
                    keys, subs = kk[:, 0], kk[:, 1]
                    flt = _filter_logits(logits, temp[:, None],
                                         top_k, top_p)
                    tok = jax.vmap(jax.random.categorical)(
                        subs, flt).astype(jnp.int32)
                else:
                    tok = jnp.argmax(logits, -1).astype(jnp.int32)
                tok = jnp.where(done, jnp.where(eos >= 0, eos, 0), tok)
                done = jnp.logical_or(done, tok == eos)
                return tok, keys, done

            def body(carry, _):
                logits, kc, vc, pos, keys, done = carry
                tok, keys, done = pick(logits, keys, done)
                # (a routed model: a frozen row reaches no expert, and
                # what the routing did this step leaves the scan beside
                # the tokens)
                counts = [] if routed else None
                logits, kc, vc = _forward_cached(
                    p, cfg, tok[:, None], kc, vc, pos, max_len,
                    sharded=shd, aidx=aidx, moe_stats=counts,
                    live=jnp.logical_not(done)[:, None] if routed else None)
                if routed:
                    tok = (tok, _moe_reduce(jnp.stack(
                        [c for c, _ in counts])))
                # rows past their budget keep stepping until the chunk
                # boundary; clamping pins their (discarded) writes to the
                # last cache slot instead of running off the buffer
                pos = jnp.minimum(pos + 1, max_len - 1)
                return (logits, kc, vc, pos, keys, done), tok

            (logits, kc, vc, pos, keys, done), toks = jax.lax.scan(
                body, (logits, kc, vc, pos, keys, done), None,
                length=steps)
            moe = None
            if routed:
                toks, c = toks                          # c (steps, 3)
                moe = _moe_reduce(c)
            # the re-entry contract: the carry leaves this program with
            # the SAME placements it arrived with (sharding-preserving
            # jit) — chaining chunks never gathers the state to host
            carry = pin(logits=logits, kc=kc, vc=vc, pos=pos, keys=keys,
                        done=done, eos=eos, temp=temp, adapter_idx=aidx)
            return (jnp.moveaxis(toks, 0, 1),) + carry + (moe,)

        self._prefill = self._counted(jax.jit(prefill), "decode.prefill")
        self._step = self._counted(jax.jit(step), "decode.step")
        self._fused_decode = self._counted(jax.jit(
            fused_decode,
            static_argnames=("steps", "do_sample", "use_eos", "top_k",
                             "top_p")), "decode.fused")
        # one jitted chunk program under two fault sites: the serving
        # degradation ladder's per-token rung must stay dispatchable when
        # a plan is killing "decode.chunk". The program is GIVEN its
        # carry's caches (kc, vc donated): its output carry aliases them
        # and no chunk begins by copying the whole cache. The ring
        # operands stay the caller's: the ring outlives the chunk
        carry = (2, 3)
        chunk = jax.jit(ring_chunk_decode, static_argnames=(
            "steps", "do_sample", "top_k", "top_p"), donate_argnums=carry)
        self._ring_chunk_decode = self._counted(chunk, "decode.chunk",
                                                consumes=carry)
        self._ring_chunk_step = self._counted(chunk, "decode.chunk_step",
                                              consumes=carry)
        # both admission entries dispatch under one site: the serving
        # ladder, fault plans and the obs span-vs-dispatch accounting see
        # ONE logical site per role. The ring entry is given the ring
        # (ring_logits, ring_kc, ring_vc donated) and writes the admitted
        # rows into it in place; its kc, vc are the empty pair every
        # admission shares and stay the caller's
        self._admit_prefill = self._counted(jax.jit(admit_prefill),
                                            "decode.admit_prefill")
        ring = (6, 7, 8)
        self._ring_admit_prefill = self._counted(
            jax.jit(ring_admit_prefill, donate_argnums=ring),
            "decode.admit_prefill", consumes=ring)

    def _pin(self, **fields) -> tuple:
        """Sharding-preserving jit: the named carry fields a program
        returns keep the placements the carry arrived with, so re-entry
        never decays to replicated. Off-mesh the values pass through."""
        if self.sharding is None:
            return tuple(fields.values())
        return self.sharding.constrain_carry(self._head_major, **fields)

    def _counted(self, jitted, site="decode.dispatch", consumes=()):
        """Count dispatches AND guard each one: the fault-injection hook
        fires first (an injected failure is a dispatch that never ran, so
        counters stay parity-comparable with the no-fault run), then the
        execution retries transient backend errors with backoff
        (resilient_call; FLAGS_resilience_retries/backoff_s). Retry
        events land in the in-flight generate's record.

        ``consumes``: the positions ``jitted`` donates. An attempt that
        finds one of them already gone — the caller reused a consumed
        state, or this is the retry of a dispatch that failed after it
        had taken them — raises ``ConsumedStateError`` (fatal: nothing
        is left to retry) where the runtime would say "buffer has been
        deleted or donated".

        Observability (paddle_tpu/obs, FLAGS_obs_enabled): each executed
        dispatch records a span named after its fault site with the
        compiled program's cost_analysis/memory_analysis attached (one
        AOT lower+compile per site/signature, cached), and bumps the
        ``dispatches.<site>`` obs counter — so a trace's per-site span
        count is directly comparable with ``dispatch_count`` and the
        serving engine's asserted accounting. A dispatch that raises
        records an error span, which the accounting comparison excludes
        (the failed attempt never ran). Disabled: one boolean check.

        Always on: the dispatch runs under ``obs.dispatch_site(site)``,
        so a backend compile inside it counts under
        ``obs.compiles.<site>`` (an operator sees which site
        recompiled)."""
        import paddle_tpu.obs as obs
        from paddle_tpu.flags import flags as _flags
        from paddle_tpu.runtime.resilience import (fault_injector,
                                                   resilient_call)
        obs.watch_compiles()

        def attempt(args, kwargs):
            for i in consumes:
                if _consumed(args[i]):
                    raise ConsumedStateError(
                        f"{site}: argument {i} was given to an earlier "
                        f"dispatch, which consumed it (its buffers are "
                        f"donated); go on from the state that dispatch "
                        f"returned, or build a fresh one")
            fault_injector.on_call(site)
            self.dispatch_count += 1
            with obs.dispatch_site(site):
                return run(args, kwargs)

        def run(args, kwargs):
            if not obs.enabled():
                return jitted(*args, **kwargs)
            with obs.span(site, kind="dispatch") as sp:
                out = jitted(*args, **kwargs)
                if _flags.obs_cost_analysis:
                    cost = obs.dispatch_cost(
                        site, jitted, args, kwargs,
                        num_devices=(self.sharding.size if self.sharding
                                     else 1))
                    if cost:
                        sp.annotate(**cost)
            obs.metrics.counter(
                "dispatches." + site,
                "device dispatches executed at this site").inc()
            return out

        def call(*args, **kwargs):
            return resilient_call(attempt, args, kwargs, site=site,
                                  on_event=self._events.append)
        call._jitted = jitted    # for tests that lower the program itself
        return call

    def _empty_cache(self, B, cfg: Optional[LlamaConfig] = None):
        """Zeroed K and V caches for ``B`` rows: per cache a tuple of
        ``cfg.num_cache_layers`` buffers — one per weight layer per pass
        over the layers, so ``num_hidden_layers`` of them for every model
        but a looped one — head-major ``(B, KV, L, D)``
        (``LlamaConfig.cache_head_major``), ``L`` being ``max_len`` or,
        for a windowed layer, its window. An EVA config
        (``cfg.eva``) gets ``cfg.cache_leaves`` = 2 buffers a cache layer,
        its window leaf and then its summary leaf: the leaves ride in
        ``kc`` / ``vc``. A latent config (``cfg.latent``) gets in ``kc``
        its layers' latents ``(B, 1, L, kv_lora_rank)`` and in ``vc``
        their rope keys ``(B, 1, L, rope_cache_width)``."""
        cfg = self.cfg if cfg is None else cfg
        dt = jnp.dtype(cfg.dtype)
        head_major = cfg.cache_head_major

        def z(ci, width=None):
            # each buffer at its own length: a windowed layer's is
            # rolling and holds its window, an EVA layer holds a window
            # leaf and a summary leaf (the config's cache_len)
            L = cfg.cache_len(ci, self.max_len)
            if width is not None:
                # a latent layer's leaf: one "head" of its own width
                return jnp.zeros((B, 1, L, width), dt)
            if head_major:
                shape = (B, cfg.num_key_value_heads, L, cfg.head_dim)
            else:
                shape = (B, L, cfg.num_key_value_heads, cfg.head_dim)
            if self.quant_kv:
                # int8 rows + per-row scale buffer (never on a mesh:
                # int8wk is refused typed at init)
                from paddle_tpu.quantization.kv_cache import quant_kv_zeros
                return quant_kv_zeros(shape, jnp)
            buf = jnp.zeros(shape, dt)
            if self.sharding is None:
                return buf
            # caches are BORN on the mesh — batch rows over dp, heads
            # over tp — and every downstream program pins them there:
            # the carry never exists gathered, not even at init
            return self.sharding.put_state_field("kc", buf, head_major)

        if cfg.latent:
            n = cfg.num_cache_layers
            return (tuple(z(ci, cfg.kv_lora_rank) for ci in range(n)),
                    tuple(z(ci, cfg.rope_cache_width) for ci in range(n)))
        zeros = lambda: tuple(z(ci) for ci in range(  # noqa: E731
            cfg.num_cache_layers * cfg.cache_leaves))
        return zeros(), zeros()

    def moe_ffn_kernel_layers(self, batch: int) -> int:
        """Routed layers whose experts' feed-forward a decode step of
        ``batch`` rows runs through the grouped-FFN kernel, over the
        passes (``ops/moe.py:kernel_route`` at the step's ``batch x
        top_k`` sorted rows: a trace-time fact of the shapes, the backend
        and the mesh); 0 for a model without routed layers."""
        from paddle_tpu.ops.moe import kernel_route
        cfg, p = self.cfg, self.params
        n = 0
        for li in range(cfg.num_hidden_layers):
            pre = f"model.layers.{li}.mlp."
            if pre + "router.weight" in p:
                gu, dn = p[pre + "experts_gate_up"], p[pre + "experts_down"]
                n += kernel_route(batch * cfg.num_experts_per_tok, gu, dn,
                                  gu.dtype, self.sharding is not None)
        return n * cfg.total_ut_steps

    # -- chunked resumable decode -----------------------------------------
    def init_decode_state(self, input_ids, eos_token_id=None,
                          temperature: float = 1.0, seed: int = 0,
                          draft_model=None,
                          num_speculative_tokens: Optional[int] = None,
                          draft_quant: Optional[str] = None,
                          adapter_idx=None,
                          speculative=None) -> DecodeState:
        """Prefill (one dispatch) and build the exportable loop carry for
        ``decode_chunk``. Whole-batch entry: every row starts from the
        same prompt tensor; the serving engine instead assembles mixed
        states row by row via its admission path. Per-row keys are
        ``split(PRNGKey(seed), B)`` — row i's sampled stream depends only
        on ``keys[i]``, never on its neighbours.

        With ``draft_model`` the carry is SPECULATIVE: it additionally
        holds the draft's prefilled caches (one extra counted dispatch),
        the per-row pending-token sentinel ``tok=-1`` and zeroed
        cumulative acceptance stats — ``decode_chunk`` then advances it
        by draft/verify/accept rounds instead of single steps.

        ``adapter_idx`` (B,) ints: per-row LoRA adapter routing (the
        params must carry ``lora.*`` stacks — see serving/lora); the
        PREFILL runs adapter-routed too, so each row's cached prompt KV
        matches its dense-merged tenant model. ``speculative`` (B,)
        bools (speculative carries only): rows set False decode
        verify-free inside the same speculative chunk program."""
        import jax.random as jrandom

        ids = jnp.asarray(np.asarray(input_ids))
        B, S = ids.shape
        aidx = None
        if adapter_idx is not None:
            aidx = jnp.asarray(np.asarray(adapter_idx), jnp.int32)
        kc, vc = self._empty_cache(B)
        if aidx is None:
            logits, kc, vc = self._prefill(self.params, ids, kc, vc)
        else:
            # adapter-routed prefill: the bucketed admission program with
            # every row at its full length (per-row aidx is its contract)
            logits, kc, vc = self._admit_prefill(
                self.params, ids, kc, vc,
                jnp.full((B,), S, jnp.int32), jnp.zeros((B,), jnp.int32),
                aidx)
        eos_n = _normalize_eos(eos_token_id)
        kw = {"adapter_idx": aidx}
        if speculative is not None and draft_model is None:
            raise ValueError("speculative=(B,) row mask requires a "
                             "draft_model")
        if draft_model is not None:
            from paddle_tpu.flags import flags
            K = int(num_speculative_tokens
                    if num_speculative_tokens is not None
                    else flags.decode_speculative_tokens)
            if K < 1:
                raise ValueError(
                    f"num_speculative_tokens must be >= 1, got {K}")
            eng = self._spec_engine(draft_model, draft_quant)
            dkc, dvc = self._empty_cache(B, eng["cfg"])
            _, dkc, dvc = eng["prefill"](eng["params"], ids, dkc, dvc)
            kw.update(dkc=dkc, dvc=dvc,
                      tok=jnp.full((B,), -1, jnp.int32),
                      spec_rounds=jnp.zeros((B,), jnp.int32),
                      spec_accepted=jnp.zeros((B,), jnp.int32),
                      spec={"ekey": eng["ekey"], "K": K})
            if speculative is not None:
                kw["spec_on"] = jnp.asarray(np.asarray(speculative),
                                            jnp.bool_)
        elif num_speculative_tokens is not None:
            raise ValueError("num_speculative_tokens requires a "
                             "draft_model")
        elif draft_quant is not None:
            raise ValueError("draft_quant requires a draft_model")
        state = DecodeState(
            logits=logits, kc=kc, vc=vc,
            pos=jnp.full((B,), S, jnp.int32),
            keys=jnp.asarray(jrandom.split(jrandom.PRNGKey(seed), B),
                             jnp.uint32),
            done=jnp.zeros((B,), jnp.bool_),
            eos=jnp.full((B,), -1 if eos_n is None else int(eos_n),
                         jnp.int32),
            temp=jnp.full((B,), float(temperature), jnp.float32), **kw)
        if self.sharding is not None:
            # per-row fields join the mesh (batch over dp); logits and
            # caches already came out of the prefill pinned
            state = self.sharding.put_state(state, self._head_major)
        return state

    def decode_chunk(self, state: DecodeState, num_tokens: int,
                     do_sample: bool = False, top_k: Optional[int] = None,
                     top_p: Optional[float] = None,
                     K: Optional[int] = None):
        """Advance the loop carry by ``num_tokens`` steps in ONE device
        dispatch; returns ``(tokens (B, num_tokens), new_state)``.
        ``state`` is CONSUMED: its caches are donated to the program and
        come back, written in place, in ``new_state``; decoding the old
        state again raises ``ConsumedStateError`` (to branch twice from
        one prompt, build the state twice). A speculative carry is not
        consumed. Chaining chunks totalling N steps emits the same
        greedy tokens, bit-exactly, as one run-to-completion
        ``generate`` of N — the property continuous batching rides on (a
        request's output can't depend on how admission sliced its decode
        into dispatches).

        A SPECULATIVE carry (``init_decode_state(draft_model=...)``)
        routes to the chunked speculative program instead:
        ``num_tokens`` counts verify ROUNDS (each committing 1..K+1
        tokens), the returned token buffer is
        ``(B, num_tokens*(K+1)+1)`` and the new state's ``nv`` holds
        each row's valid count, at least ``num_tokens`` (slice
        ``toks[i, :nv[i]]``; everything past ``num_tokens`` is
        acceptance overflow — the per-dispatch token yield that IS the
        speculative dispatch reduction). ``K`` overrides the carry's
        draft length for THIS chunk only (the adaptive-K serving hook:
        K is a static, so each distinct value compiles once and the
        engine steers between cached programs; greedy output stays
        bit-exact for any K schedule)."""
        if state.dkc is not None:
            eng = self._spec_engines[state.spec["ekey"]]
            K = int(state.spec["K"]) if K is None else int(K)
            (toks, nv, logits, kc, vc, dkc, dvc, pos, keys, done, eos,
             temp, tok, sr, sa, aidx, son) = eng["chunk"](
                self.params, eng["params"], state.logits, state.kc,
                state.vc, state.dkc, state.dvc, state.pos, state.keys,
                state.done, state.eos, state.temp, state.tok,
                state.spec_rounds, state.spec_accepted,
                state.adapter_idx, state.spec_on,
                None, None, None, None, None,      # no admission ring
                None, None, None, None, None, None, None,
                steps=int(num_tokens), K=K, do_sample=bool(do_sample),
                top_k=None if top_k is None else int(top_k),
                top_p=None if top_p is None else float(top_p))
            return toks, dataclasses.replace(
                state, logits=logits, kc=kc, vc=vc, dkc=dkc, dvc=dvc,
                pos=pos, keys=keys, done=done, eos=eos, temp=temp,
                tok=tok, spec_rounds=sr, spec_accepted=sa, nv=nv,
                adapter_idx=aidx, spec_on=son,
                steps_done=state.steps_done + int(num_tokens))
        return self._advance(
            self._ring_chunk_decode, state, num_tokens,
            do_sample=bool(do_sample),
            top_k=None if top_k is None else int(top_k),
            top_p=None if top_p is None else float(top_p))

    def _advance(self, entry, state: DecodeState, steps: int, ring=None,
                 **statics):
        """One dispatch of the chunk program over a plain carry, which
        it consumes (``state.kc`` / ``state.vc`` are donated; the other
        fields stay readable). ``entry`` is ``_ring_chunk_decode`` or
        its per-token-site twin ``_ring_chunk_step``; ``ring`` the
        program's nine ring operands (``ServingEngine``'s staged
        admissions; read, not consumed), ``None`` for none."""
        (toks, logits, kc, vc, pos, keys, done, eos, temp, aidx,
         moe) = entry(
            self.params, state.logits, state.kc, state.vc, state.pos,
            state.keys, state.done, state.eos, state.temp,
            state.adapter_idx, *((None,) * 9 if ring is None else ring),
            steps=int(steps), **statics)
        return toks, dataclasses.replace(
            state, logits=logits, kc=kc, vc=vc, pos=pos, keys=keys,
            done=done, eos=eos, temp=temp, adapter_idx=aidx, moe=moe,
            steps_done=state.steps_done + int(steps))

    def _generate_chunked(self, ids, max_new, eos_norm, do_sample,
                          temperature, top_k, top_p, seed, chunk_size):
        """Chunked resumable decode: prefill + ceil(max_new/T) chunk
        dispatches. Greedy is bit-exact with the one-dispatch fused path
        (identical pick/forward stream); sampling draws from PER-ROW key
        streams — distribution-preserving and row-independent (the
        admission contract), but a different stream than the fused
        path's single shared key. Retry/degradation events of EVERY
        chunk dispatch accumulate into the one generate record."""
        T = int(chunk_size)
        if T < 1:
            raise ValueError(f"chunk_size must be >= 1, got {T}")
        state = self.init_decode_state(ids, eos_token_id=eos_norm,
                                       temperature=temperature, seed=seed)
        out, got = [], 0
        while got < max_new:
            toks, state = self.decode_chunk(
                state, min(T, max_new - got), do_sample=do_sample,
                top_k=top_k, top_p=top_p)
            out.append(np.asarray(toks))
            got += out[-1].shape[1]
            if eos_norm is not None and bool(np.asarray(state.done).all()):
                break
        return np.concatenate(out, axis=1)

    def _generate_chunked_spec(self, ids, max_new, eos_norm, do_sample,
                               temperature, top_k, top_p, seed,
                               draft_model, draft_quant, K, chunk_size):
        """Chunked SPECULATIVE decode: prefill(target) + prefill(draft)
        + roughly ``ceil(max_new/(T*(1+a)))`` chunk dispatches at
        acceptance ``a`` — each dispatch runs T verify rounds and
        commits a per-row variable ``>= T`` tokens (``decode_chunk``'s
        ``nv`` contract), so the speculative K-fold dispatch reduction
        composes with chunk re-entry. Greedy tokens are bit-exact with
        the one-dispatch fused speculative path for every ``chunk_size``
        slicing (the chunk-slicing-invariance contract); sampling draws
        from PER-ROW key streams like the plain chunked path.
        Acceptance stats accumulate per row in the CARRY across chunk
        re-entries, so ``last_spec_stats`` reports the CUMULATIVE
        request totals — never stale, never last-chunk-only."""
        T = int(chunk_size)
        if T < 1:
            raise ValueError(f"chunk_size must be >= 1, got {T}")
        state = self.init_decode_state(
            ids, eos_token_id=eos_norm, temperature=temperature,
            seed=seed, draft_model=draft_model, num_speculative_tokens=K,
            draft_quant=draft_quant)
        B = ids.shape[0]
        buf = np.zeros((B, max_new), np.int32)
        got = np.zeros((B,), np.int64)
        while True:
            toks, state = self.decode_chunk(
                state, T, do_sample=do_sample, top_k=top_k, top_p=top_p)
            toks_h, nv_h = np.asarray(toks), np.asarray(state.nv)
            for b in range(B):
                n = min(int(nv_h[b]), int(max_new - got[b]))
                if n > 0:
                    buf[b, got[b]:got[b] + n] = toks_h[b, :n]
                    got[b] += n
            if bool((got >= max_new).all()):
                break
            done_h = np.asarray(state.done)
            if eos_norm is not None and bool(done_h.all()):
                # like the fused path's buffer, post-eos columns hold
                # the eos fill (the trim contract both paths share)
                for b in range(B):
                    buf[b, got[b]:] = int(eos_norm)
                break
            full = got >= max_new
            if bool(full.any()):
                # budget-filled rows freeze (like the engine retiring a
                # slot): they stop accumulating stat counters while
                # their batch neighbours finish
                state = dataclasses.replace(
                    state, done=jnp.logical_or(state.done,
                                               jnp.asarray(full)))
        self._record_spec_stats(
            int(np.asarray(state.spec_rounds).sum()),
            int(np.asarray(state.spec_accepted).sum()), K)
        return buf

    # -- speculative decoding ---------------------------------------------
    def _spec_engine(self, draft_model, draft_quant: Optional[str] = None):
        """Prepare (and cache) the draft side of speculative decoding.
        ``draft_model``: a LlamaForCausalLM with the same vocab (its
        weights are snapshotted exactly like the target's), or 'skip:N'
        — a layer-skip view that reuses the TARGET's first N layers plus
        its final norm/head as the draft, zero extra weights.
        ``draft_quant``: 'int8w' quantizes the DRAFT's weights only —
        the target keeps its own dtype, the verify pass stays exact, so
        a wrong draft only costs acceptance length, never correctness."""
        import dataclasses
        cfg, max_len = self.cfg, self.max_len
        if cfg.latent:
            raise LatentCacheError(
                "speculative decoding drafts through a second cache and "
                "verifies over the target's keys and values per head; a "
                "model with latent attention has neither: decode without "
                "draft_model=")
        if cfg.has_windows:
            raise WindowedModelError(
                "speculative decoding verifies several positions in one "
                "forward over the cache, which a model with windowed "
                "layers does not do: its S > 1 forward is a prefill from "
                "position 0 over fresh keys; decode without draft_model=")
        if draft_quant not in (None, "int8w"):
            raise ValueError(
                f"draft_quant must be None or 'int8w', got {draft_quant!r}")
        if isinstance(draft_model, str):
            if not draft_model.startswith("skip:"):
                raise ValueError(
                    "draft_model must be a LlamaForCausalLM or 'skip:N' "
                    f"(layer-skip view of the target), got {draft_model!r}")
            if draft_quant is not None:
                raise ValueError(
                    "draft_quant does not compose with 'skip:N' drafts: "
                    "the layer-skip view reuses the TARGET's params, so "
                    "quantize the target (quant='int8w') instead")
            if cfg.total_ut_steps > 1:
                raise LoopedDraftError(
                    f"draft_model={draft_model!r} needs a model that "
                    f"makes one pass over its layers: this one runs them "
                    f"total_ut_steps={cfg.total_ut_steps} times, so its "
                    f"first N layers are not a prefix of its depth; pass "
                    f"a separate draft model")
            n = int(draft_model.split(":", 1)[1])
            if not 0 < n < cfg.num_hidden_layers:
                raise ValueError(
                    f"'skip:{n}' needs 0 < N < num_hidden_layers "
                    f"({cfg.num_hidden_layers})")
            ekey = ("skip", n)
        else:
            ekey = ("model", id(draft_model), draft_quant)
        eng = self._spec_engines.get(ekey)
        if eng is not None:
            return eng
        shd = self.sharding if self.sharding is not None else False
        pin = self._pin
        if isinstance(draft_model, str):
            dcfg = dataclasses.replace(cfg, num_hidden_layers=n)
            dp = self.params
        else:
            dcfg = draft_model.config
            if dcfg.vocab_size != cfg.vocab_size:
                raise ValueError(
                    f"draft vocab_size {dcfg.vocab_size} != target "
                    f"vocab_size {cfg.vocab_size}")
            dp = _build_params(draft_model, max_len,
                               "int8" if draft_quant else self.weight_dtype)
            if self.sharding is not None:
                dp = self.sharding.shard_params(dp)

        def draft_prefill(dp_, ids, dkc, dvc):
            self.trace_count += 1
            return _forward_cached(dp_, dcfg, ids, dkc, dvc, 0, max_len,
                                   sharded=shd, from_zero=True)

        def spec_round(p, dp_, tok, pos, key, done, kc, vc, dkc, dvc,
                       eos_id, temperature, K: int, do_sample: bool,
                       use_eos: bool, top_k, top_p):
            self.trace_count += 1
            return _spec_round(p, dp_, cfg, dcfg, tok, pos, key, done, kc,
                               vc, dkc, dvc, eos_id, temperature, max_len,
                               K=K, do_sample=do_sample, use_eos=use_eos,
                               top_k=top_k, top_p=top_p, sharded=shd)

        def spec_decode(p, dp_, logits0, kc, vc, dkc, dvc, pos0, key0,
                        done0, eos_id, temperature, max_new: int, K: int,
                        do_sample: bool, use_eos: bool, top_k, top_p):
            """Speculative decode as ONE device program: a lax.while_loop
            of draft-propose/verify/accept rounds, each round committing
            a variable 1..K+1 tokens per row (scattered into the output
            buffer at per-row offsets), until every row has its
            ``max_new`` tokens. Also returns (rounds, accepted) totals
            over live rows for acceptance-length reporting."""
            self.trace_count += 1
            B = logits0.shape[0]
            if do_sample:
                key0, sub0 = jax.random.split(key0)
                tok0 = _sample_from(logits0, sub0, temperature, top_k,
                                    top_p).astype(jnp.int32)
            else:
                tok0 = jnp.argmax(logits0, -1).astype(jnp.int32)
            done = done0
            if use_eos:
                tok0 = jnp.where(done, eos_id, tok0)
                done = jnp.logical_or(done, tok0 == eos_id)
            buf = jnp.zeros((B, max_new), jnp.int32).at[:, 0].set(tok0)
            pos = jnp.broadcast_to(pos0, (B,)).astype(jnp.int32)
            rows = jnp.arange(B)[:, None]
            jidx = jnp.arange(K + 1)[None, :]

            def cond(c):
                return jnp.any(c[1] - pos0 + 1 < max_new)

            def body(c):
                buf, pos, tok, key, done, kc, vc, dkc, dvc, sr, sa = c
                active = (pos - pos0 + 1) < max_new
                live = jnp.logical_and(active, jnp.logical_not(done))
                (emit, a, tok2, key, done2, kc, vc, dkc,
                 dvc) = _spec_round(p, dp_, cfg, dcfg, tok, pos, key,
                                    done, kc, vc, dkc, dvc, eos_id,
                                    temperature, max_len, K=K,
                                    do_sample=do_sample, use_eos=use_eos,
                                    top_k=top_k, top_p=top_p, sharded=shd)
                sr = sr + jnp.sum(live.astype(jnp.int32))
                sa = sa + jnp.sum(jnp.where(live, a, 0).astype(jnp.int32))
                idx = (pos - pos0 + 1)[:, None] + jidx
                valid = jnp.logical_and(jidx <= a[:, None],
                                        active[:, None])
                idx = jnp.where(valid, idx, max_new)  # OOB -> dropped
                buf = buf.at[rows, idx].set(emit, mode="drop")
                pos = jnp.where(active, pos + a + 1, pos)
                tok = jnp.where(active, tok2, tok)
                done = jnp.where(active, done2, done)
                return (buf, pos, tok, key, done, kc, vc, dkc, dvc,
                        sr, sa)

            z = jnp.asarray(0, jnp.int32)
            out = jax.lax.while_loop(
                cond, body,
                (buf, pos, tok0, key0, done, kc, vc, dkc, dvc, z, z))
            return out[0], out[9], out[10]

        def spec_chunk(p, dp_, logits0, kc, vc, dkc, dvc, pos0, keys0,
                       done0, eos0, temp0, tok0, sr0, sa0, aidx0, son0,
                       ring_logits, ring_kc, ring_vc, ring_dkc, ring_dvc,
                       ring_slot, ring_pos, ring_keys, ring_eos,
                       ring_temp, ring_aidx, ring_son,
                       steps: int, K: int, do_sample: bool,
                       top_k, top_p):
            """CHUNKED speculative decode: exactly ``steps=T``
            draft/verify/accept rounds (``_spec_round_rows`` — per-row
            keys/eos/temps, the serving carry contract) as one
            re-enterable dispatch. A plain chunk buys T tokens per row
            for T forwards; here the SAME T sequential rounds commit a
            variable 1..K+1 tokens per row each — ~``T*(1+a)`` tokens
            per dispatch at acceptance ``a``, which IS the K-fold
            dispatch reduction, kept intact across chunk boundaries.
            The output buffer is ``(B, T*(K+1)+1)`` (fresh-pick column
            plus T rounds) with a per-row valid count ``nv`` in
            ``[T, T*(K+1)+1]`` (harvest slices ``buf[i, :nv[i]]``;
            nothing is thrown away). Chunk-slicing
            invariance holds because the per-row ROUND sequence is
            continuous across chunk boundaries — no round is re-run, no
            committed token is dropped, so every T slicing replays the
            fused path's exact stream (greedy AND per-row-keyed
            sampled). The carry's pending token ``tok`` (-1 = pick
            fresh from ``logits``, the state of an admitted row) is
            what makes re-entry exact: unlike the plain chunk, the last
            committed token of a round is not yet in the caches when
            the chunk ends. Acceptance stats accumulate PER ROW in the
            carry (``sr``/``sa``), reset by admission — chunk re-entry
            can neither lose rounds nor double-report them. The ring
            prologue is the same device-side slot refill as the plain
            ring chunk (plus the draft caches and spec-field resets).
            ``aidx0``/``son0`` (+ their ring columns): per-row LoRA
            adapter routing and per-row speculative enable — both ride
            the carry so admission can retarget a freed slot's tenant or
            demote it to verify-free decode without a new program."""
            self.trace_count += 1
            T = int(steps)
            B = logits0.shape[0]
            logits, pos, keys, done = logits0, pos0, keys0, done0
            eos, temp, tok, sr, sa = eos0, temp0, tok0, sr0, sa0
            aidx, son = aidx0, son0
            if ring_slot is not None:
                tgt = jnp.where(ring_slot >= 0, ring_slot, B)
                logits = logits.at[tgt].set(ring_logits, mode="drop")
                kc = _row_scatter(kc, ring_kc, tgt)
                vc = _row_scatter(vc, ring_vc, tgt)
                dkc = _row_scatter(dkc, ring_dkc, tgt)
                dvc = _row_scatter(dvc, ring_dvc, tgt)
                pos = pos.at[tgt].set(ring_pos, mode="drop")
                keys = keys.at[tgt].set(ring_keys, mode="drop")
                done = done.at[tgt].set(False, mode="drop")
                eos = eos.at[tgt].set(ring_eos, mode="drop")
                temp = temp.at[tgt].set(ring_temp, mode="drop")
                tok = tok.at[tgt].set(-1, mode="drop")
                sr = sr.at[tgt].set(0, mode="drop")
                sa = sa.at[tgt].set(0, mode="drop")
                if aidx is not None:
                    aidx = aidx.at[tgt].set(ring_aidx, mode="drop")
                if son is not None:
                    son = son.at[tgt].set(ring_son, mode="drop")
            fill = jnp.where(eos >= 0, eos, 0)
            need = tok < 0           # no pending token: fresh pick
            if do_sample:
                kk = jax.vmap(jax.random.split)(keys)
                flt = _filter_logits(logits, temp[:, None], top_k, top_p)
                cand = jax.vmap(jax.random.categorical)(
                    kk[:, 1], flt).astype(jnp.int32)
                # only picked rows consume their key split
                keys = jnp.where(need[:, None], kk[:, 0], keys)
            else:
                cand = jnp.argmax(logits, -1).astype(jnp.int32)
            cand = jnp.where(done, fill, cand)
            done = jnp.where(need, jnp.logical_or(done, cand == eos),
                             done)
            tok = jnp.where(need, cand, tok)
            # fresh pick (1) + T rounds of at most K+1 commits each
            W = T * (K + 1) + 1
            buf = jnp.zeros((B, W), jnp.int32)
            buf = buf.at[:, 0].set(jnp.where(need, tok, 0))
            cnt = jnp.where(need, 1, 0).astype(jnp.int32)
            rows = jnp.arange(B)[:, None]
            jidx = jnp.arange(K + 1)[None, :]

            def body(_, c):
                (buf, cnt, logits, tok, pos, keys, done, kc, vc, dkc,
                 dvc, sr, sa) = c
                live = jnp.logical_not(done)
                if son is not None:
                    # spec-off rows advance 1/round verify-free: their
                    # rounds never enter the acceptance stats
                    live = jnp.logical_and(live, son)
                (emit, a, tok2, lg2, keys2, done2, kc, vc, dkc,
                 dvc) = _spec_round_rows(
                    p, dp_, cfg, dcfg, tok, pos, keys, done, kc, vc,
                    dkc, dvc, eos, temp, max_len, K=K,
                    do_sample=do_sample, top_k=top_k, top_p=top_p,
                    sharded=shd, aidx=aidx, spec_on=son)
                idx = cnt[:, None] + jidx
                valid = jidx <= a[:, None]
                idx = jnp.where(valid, idx, W)         # OOB -> dropped
                buf = buf.at[rows, idx].set(emit, mode="drop")
                sr = sr + jnp.where(live, 1, 0).astype(jnp.int32)
                sa = sa + jnp.where(live, a, 0).astype(jnp.int32)
                cnt = cnt + a + 1
                # rows past their budget keep their (discarded) writes
                # clamped where a full round still fits the cache
                pos = jnp.minimum(pos + a + 1, max_len - K - 1)
                return (buf, cnt, lg2, tok2, pos, keys2, done2, kc, vc,
                        dkc, dvc, sr, sa)

            (buf, cnt, logits, tok, pos, keys, done, kc, vc, dkc, dvc,
             sr, sa) = jax.lax.fori_loop(
                0, T, body, (buf, cnt, logits, tok, pos, keys, done, kc,
                             vc, dkc, dvc, sr, sa))
            return (buf, cnt) + pin(
                logits=logits, kc=kc, vc=vc, dkc=dkc, dvc=dvc, pos=pos,
                keys=keys, done=done, eos=eos, temp=temp, tok=tok,
                spec_rounds=sr, spec_accepted=sa, adapter_idx=aidx,
                spec_on=son)

        def spec_demote(p, logits0, kc, vc, tok, pos, aidx=None):
            """One-time speculative->chunked demotion of a live carry:
            the pending token (the one speculative re-entry would have
            verified) is committed to the target caches with a single
            masked forward, yielding PICK-READY logits and pos+1 — after
            which the plain chunk program serves the state and the draft
            caches are dropped. Rows with no pending token (tok < 0)
            keep their logits; their placeholder write at ``pos`` is
            overwritten by the next real write at the same offset before
            attention could unmask it."""
            self.trace_count += 1
            need = tok >= 0
            t = jnp.where(need, tok, 0)
            lg, kc, vc = _forward_cached(p, cfg, t[:, None], kc, vc, pos,
                                         max_len, sharded=shd, aidx=aidx)
            logits = jnp.where(need[:, None], lg, logits0)
            pos = jnp.where(need, jnp.minimum(pos + 1, max_len - 1), pos)
            return pin(logits=logits, kc=kc, vc=vc, pos=pos)

        def ring_draft_prefill(dp_, ids, dkc, dvc, ring_dkc, ring_dvc,
                               ring_idx):
            """Draft-side admission prefill, staged straight into the
            ring's draft caches (one counted dispatch per admission
            group — the speculative analog of ``ring_admit_prefill``)."""
            self.trace_count += 1
            _, dkc, dvc = _forward_cached(dp_, dcfg, ids, dkc, dvc, 0,
                                          max_len, sharded=shd,
                                          from_zero=True)
            ring_dkc = _row_scatter(ring_dkc, dkc, ring_idx)
            ring_dvc = _row_scatter(ring_dvc, dvc, ring_idx)
            return pin(dkc=ring_dkc, dvc=ring_dvc)

        eng = {
            "cfg": dcfg, "params": dp, "ekey": ekey,
            "prefill": self._counted(jax.jit(draft_prefill),
                                     "spec.prefill"),
            "round": self._counted(jax.jit(spec_round, static_argnames=(
                "K", "do_sample", "use_eos", "top_k", "top_p")),
                "spec.round"),
            "decode": self._counted(jax.jit(spec_decode, static_argnames=(
                "max_new", "K", "do_sample", "use_eos", "top_k",
                "top_p")), "spec.decode"),
            # chunked speculative decode dispatches under the SAME fault
            # site as the plain chunk: to the serving ladder and fault
            # plans there is one "the chunk dispatch" site, whatever
            # program backs it
            "chunk": self._counted(jax.jit(spec_chunk, static_argnames=(
                "steps", "K", "do_sample", "top_k", "top_p")),
                "decode.chunk"),
            "demote": self._counted(jax.jit(spec_demote),
                                    "decode.spec_demote"),
            "ring_prefill": self._counted(jax.jit(ring_draft_prefill),
                                          "spec.prefill"),
        }
        self._spec_engines[ekey] = eng
        return eng

    def generate(self, input_ids, max_new_tokens: int = 32,
                 eos_token_id: Optional[int] = None,
                 do_sample: bool = False, temperature: float = 1.0,
                 top_k: Optional[int] = None, top_p: Optional[float] = None,
                 seed: int = 0, draft_model=None,
                 num_speculative_tokens: Optional[int] = None,
                 draft_quant: Optional[str] = None,
                 chunk_size: Optional[int] = None) -> np.ndarray:
        """Decode. input_ids: (B, S) ints. Returns (B, S + new).

        Greedy by default; ``do_sample=True`` draws from the
        temperature/top-k/top-p-filtered distribution (the reference
        generation-op sampling surface). EVERY mode — greedy, greedy+eos,
        sampled, sampled+eos — runs the whole token loop as one fused
        device dispatch (``fused_decode``). With ``draft_model`` (a
        smaller LlamaForCausalLM or ``'skip:N'``) the loop runs
        SPECULATIVELY: ``num_speculative_tokens`` (default
        ``flags.decode_speculative_tokens``) draft proposals per target
        verify, still one decode dispatch after the two prefills, with
        the target distribution preserved exactly (greedy: exact-match
        accept; sampling: Leviathan rejection rule).
        ``draft_quant='int8w'`` additionally quantizes the DRAFT
        model's weights to int8 (target untouched — the verify pass
        stays exact, so a worse draft only costs acceptance length). ``eos_token_id``
        accepts ``None`` or any negative id (the bundles' ``-1``
        convention) as "no eos". Set the ``decode_fallback`` flag or
        ``PADDLE_TPU_DECODE_FALLBACK=1`` to debug against the per-token
        (or per-speculative-round) host loop, which emits the same
        tokens for a fixed seed.

        ``chunk_size=T`` runs the SAME fused loop as a chain of
        re-enterable T-step dispatches (``init_decode_state`` /
        ``decode_chunk`` — the continuous-batching serving substrate,
        ``paddle_tpu/serving``): greedy output is bit-exact with the
        one-dispatch path; sampling switches to per-row key streams
        (``split(PRNGKey(seed), B)``) so each row's draw is independent
        of its batch neighbours — distribution-preserving, different
        stream. The resilience record accumulates the retry/degradation
        events of every chunk dispatch of the call.

        Dispatch failures walk the degradation ladder automatically
        (``FLAGS_resilience_auto_degrade``): speculative falls back to
        fused plain decode (chunked likewise), fused to the per-token
        loop. Greedy levels
        are bit-exact with each other, so degraded greedy output ==
        the no-fault output; sampled levels preserve the distribution
        but consume the RNG stream differently. The returned array
        carries the retry/degradation record (``.resilience``); a run
        whose every rung fails raises a typed ``DecodeFailedError``.
        """
        from paddle_tpu.flags import flags as _flags
        from paddle_tpu.runtime.resilience import (
            DecodeFailedError, DegradationEvent, GenerateResult,
            classify_error, record_event)

        eos_token_id = _normalize_eos(eos_token_id)
        ids = jnp.asarray(np.asarray(input_ids))
        B, S = ids.shape
        # admission hook: batch-conditional faults (the injected
        # OOM-above-batch-B class) fire here, BEFORE any device work —
        # steady-state RESOURCE_EXHAUSTED is fatal and propagates typed
        from paddle_tpu.runtime.resilience import fault_injector
        fault_injector.on_call("decode.generate", batch=B)
        if S + max_new_tokens > self.max_len:
            raise ValueError(f"prompt {S} + {max_new_tokens} new tokens "
                             f"exceeds max_len {self.max_len}")
        if max_new_tokens <= 0:
            return np.asarray(ids)
        fallback = decode_fallback_active()
        ladder = []
        if draft_model is not None:
            # speculative decode runs on a mesh now: the per-row uneven
            # cache advance lowers through shard_map (_cache_update) and
            # is parity-tested bit-exact on the virtual CPU mesh — the
            # former SpeculativeMeshError refusal survives only on the
            # bundle-export surface
            from paddle_tpu.flags import flags
            K = int(num_speculative_tokens
                    if num_speculative_tokens is not None
                    else flags.decode_speculative_tokens)
            if K < 1:
                raise ValueError(
                    f"num_speculative_tokens must be >= 1, got {K}")
            if S + max_new_tokens + K > self.max_len:
                raise ValueError(
                    f"speculative decode can overshoot the cache by up to "
                    f"K={K} slots: prompt {S} + {max_new_tokens} new + {K} "
                    f"exceeds max_len {self.max_len}; build the decoder "
                    f"with more slack")
            eng = self._spec_engine(draft_model, draft_quant)
            if chunk_size is not None and not fallback:
                ladder.append(("speculative",
                               lambda: self._generate_chunked_spec(
                                   ids, max_new_tokens, eos_token_id,
                                   do_sample, temperature, top_k, top_p,
                                   seed, draft_model, draft_quant, K,
                                   chunk_size)))
            else:
                gen = (self._generate_speculative_fallback if fallback
                       else self._generate_speculative)
                ladder.append(("speculative", lambda: gen(
                    ids, max_new_tokens, eos_token_id, do_sample,
                    temperature, top_k, top_p, seed, eng, K)))
        elif num_speculative_tokens is not None:
            raise ValueError("num_speculative_tokens requires a "
                             "draft_model")
        elif draft_quant is not None:
            raise ValueError("draft_quant requires a draft_model")
        if chunk_size is not None:
            if not fallback:
                ladder.append(("chunked", lambda: self._generate_chunked(
                    ids, max_new_tokens, eos_token_id, do_sample,
                    temperature, top_k, top_p, seed, chunk_size)))
        if not fallback:
            ladder.append(("fused", lambda: self._generate_fused(
                ids, max_new_tokens, eos_token_id, do_sample, temperature,
                top_k, top_p, seed)))
        ladder.append(("per_token", lambda: self._generate_per_token(
            ids, max_new_tokens, eos_token_id, do_sample, temperature,
            top_k, top_p, seed)))

        self._events = []
        self.last_resilience = None
        # cleared BEFORE the ladder runs: a speculative rung that fails
        # and degrades mid-request must not leave a previous generate's
        # acceptance stats looking like this one's (and a non-speculative
        # generate must never report any) — every dispatch of this call,
        # however many chunks it takes, reports into this one record
        self.last_spec_stats = None
        degradations = []
        toks, level = None, None
        for li, (name, run) in enumerate(ladder):
            try:
                toks = run()
                level = name
                break
            except Exception as e:
                if classify_error(e) != "transient":
                    raise     # fatal (programming/capacity error): as-is
                if (li == len(ladder) - 1
                        or not _flags.resilience_auto_degrade):
                    # the ladder is exhausted and the caller may die on
                    # this: dump the crash flight recorder (last spans +
                    # resilience timeline + metrics) BEFORE raising
                    import paddle_tpu.obs as obs
                    obs.record_crash(
                        "decode.ladder_exhausted", error=e,
                        extra={"site": "decode.generate",
                               "failed_level": name,
                               "degradations": [d.as_dict()
                                                for d in degradations]})
                    raise DecodeFailedError(
                        f"decode failed at ladder level {name!r} with no "
                        f"further fallback: {str(e)[:300]}",
                        events=list(self._events), last_error=e) from e
                ev = DegradationEvent(
                    site="decode.generate", from_level=name,
                    to_level=ladder[li + 1][0],
                    error_class=type(e).__name__, error=str(e)[:300])
                record_event(ev)
                self._events.append(ev)
                degradations.append(ev)
        toks = np.asarray(toks)
        if eos_token_id is not None:
            toks = _trim_after_eos(toks, int(eos_token_id))
        out = np.concatenate(
            [np.asarray(ids), toks.astype(np.asarray(ids).dtype)], axis=1)
        self.last_resilience = {
            "level": level,
            "requested_level": ladder[0][0],
            "retries": sum(1 for e in self._events
                           if getattr(e, "kind", "") == "retry"),
            "degradations": [e.as_dict() for e in degradations],
            "events": [e.as_dict() for e in self._events],
        }
        return GenerateResult.wrap(out, self.last_resilience)

    def _generate_fused(self, ids, max_new_tokens, eos_token_id, do_sample,
                        temperature, top_k, top_p, seed):
        """Fused plain decode: prefill + ONE scan-loop dispatch. Returns
        the untrimmed (B, max_new) token buffer."""
        import jax.random as jrandom

        B, S = ids.shape
        kc, vc = self._empty_cache(B)
        logits, kc, vc = self._prefill(self.params, ids, kc, vc)
        # raw uint32 key: same threefry stream as the fallback's typed key
        # (and a plain array, so AOT bundles export the identical function)
        key = jrandom.PRNGKey(seed)
        done = jnp.zeros((B,), jnp.bool_)
        eos = jnp.asarray(-1 if eos_token_id is None else int(eos_token_id),
                          jnp.int32)
        return self._fused_decode(
            self.params, logits, kc, vc, jnp.asarray(S, jnp.int32), key,
            done, eos, jnp.asarray(float(temperature), jnp.float32),
            steps=max_new_tokens - 1, do_sample=bool(do_sample),
            use_eos=eos_token_id is not None,
            top_k=None if top_k is None else int(top_k),
            top_p=None if top_p is None else float(top_p))

    def _generate_speculative(self, ids, max_new, eos_norm, do_sample,
                              temperature, top_k, top_p, seed, eng, K):
        """Fused speculative decode: prefill(target) + prefill(draft) +
        ONE while-loop dispatch. Records acceptance stats into
        ``last_spec_stats``."""
        import jax.random as jrandom

        B, _ = ids.shape
        kc, vc = self._empty_cache(B)
        dkc, dvc = self._empty_cache(B, eng["cfg"])
        logits, kc, vc = self._prefill(self.params, ids, kc, vc)
        _, dkc, dvc = eng["prefill"](eng["params"], ids, dkc, dvc)
        key = jrandom.PRNGKey(seed)
        done0 = jnp.zeros((B,), jnp.bool_)
        eos = jnp.asarray(-1 if eos_norm is None else int(eos_norm),
                          jnp.int32)
        buf, sr, sa = eng["decode"](
            self.params, eng["params"], logits, kc, vc, dkc, dvc,
            jnp.asarray(ids.shape[1], jnp.int32), key, done0, eos,
            jnp.asarray(float(temperature), jnp.float32),
            max_new=int(max_new), K=int(K), do_sample=bool(do_sample),
            use_eos=eos_norm is not None,
            top_k=None if top_k is None else int(top_k),
            top_p=None if top_p is None else float(top_p))
        self._record_spec_stats(int(sr), int(sa), K)
        return np.asarray(buf)

    def _generate_speculative_fallback(self, ids, max_new, eos_norm,
                                       do_sample, temperature, top_k,
                                       top_p, seed, eng, K):
        """Per-round host loop (the debugging escape hatch): one jitted
        ``_spec_round`` dispatch per draft-and-verify round plus a host
        sync each round — the parity reference the fused while-loop is
        tested against (identical key discipline and round function)."""
        import jax.random as jrandom

        B, S = ids.shape
        kc, vc = self._empty_cache(B)
        dkc, dvc = self._empty_cache(B, eng["cfg"])
        logits, kc, vc = self._prefill(self.params, ids, kc, vc)
        _, dkc, dvc = eng["prefill"](eng["params"], ids, dkc, dvc)
        key = jrandom.PRNGKey(seed)
        temp = jnp.asarray(float(temperature), jnp.float32)
        use_eos = eos_norm is not None
        eos = jnp.asarray(-1 if eos_norm is None else int(eos_norm),
                          jnp.int32)
        if do_sample:
            key, sub = jrandom.split(key)
            tok = jnp.asarray(_sample_logits(logits, sub, temp, top_k,
                                             top_p), jnp.int32)
        else:
            tok = jnp.argmax(logits, -1).astype(jnp.int32)
        done = jnp.zeros((B,), jnp.bool_)
        if use_eos:
            tok = jnp.where(done, eos, tok)
            done = jnp.logical_or(done, tok == eos)
        buf = np.zeros((B, max_new), np.int32)
        buf[:, 0] = np.asarray(tok)
        count = np.ones((B,), np.int64)
        pos = jnp.full((B,), S, jnp.int32)
        sr = sa = 0
        tk = None if top_k is None else int(top_k)
        tp = None if top_p is None else float(top_p)
        while bool((count < max_new).any()):
            active = count < max_new
            live = active & ~np.asarray(done)
            emit, a, tok2, key, done2, kc, vc, dkc, dvc = eng["round"](
                self.params, eng["params"], tok, pos, key, done, kc, vc,
                dkc, dvc, eos, temp, K=int(K), do_sample=bool(do_sample),
                use_eos=use_eos, top_k=tk, top_p=tp)
            emit_h, a_h = np.asarray(emit), np.asarray(a)
            sr += int(live.sum())
            sa += int(a_h[live].sum())
            for b in range(B):
                if not active[b]:
                    continue
                n = min(int(a_h[b]) + 1, int(max_new - count[b]))
                buf[b, count[b]:count[b] + n] = emit_h[b, :n]
                count[b] += int(a_h[b]) + 1
            act_d = jnp.asarray(active)
            pos = jnp.where(act_d, pos + a + 1, pos)
            tok = jnp.where(act_d, tok2, tok)
            done = jnp.where(act_d, done2, done)
        self._record_spec_stats(sr, sa, K)
        return buf

    def _record_spec_stats(self, rounds: int, accepted: int, K: int):
        self.last_spec_stats = {
            "rounds": rounds,
            "accepted_drafts": accepted,
            # mean accepted draft tokens per verify step, over rows that
            # were live (not eos-done, budget not yet filled); emitted
            # tokens per verify step is this + 1 (the correction/bonus)
            "acceptance_len_mean": (accepted / rounds) if rounds
            else float(K),
            "num_speculative_tokens": K,
        }

    def _generate_per_token(self, ids, max_new_tokens, eos_token_id,
                            do_sample, temperature, top_k, top_p, seed):
        """Per-token host loop (the pre-fused path): one device dispatch
        per token plus a host sync each step. Kept as the
        ``decode_fallback`` debugging escape hatch, as the parity
        reference the fused path is tested against, and as the decode
        ladder's last rung. Returns the NEW tokens only (B, <=max_new) —
        the caller owns prompt concat and eos trimming."""
        import jax.random as jrandom

        B, S = ids.shape
        kc, vc = self._empty_cache(B)
        logits, kc, vc = self._prefill(self.params, ids, kc, vc)
        key = jrandom.key(seed)
        out = []
        pos = S
        done = np.zeros((B,), bool)
        for i in range(max_new_tokens):
            if do_sample:
                key, sub = jrandom.split(key)
                nxt = np.asarray(_sample_logits(logits, sub, temperature,
                                                top_k, top_p))
            else:
                nxt = np.asarray(jnp.argmax(logits, axis=-1))
            nxt = nxt.astype(np.asarray(ids).dtype)
            if eos_token_id is not None:
                # rows already finished stay pinned to eos (per-row
                # stopping; the reference pads post-eos positions likewise)
                nxt = np.where(done, eos_token_id, nxt)
                done |= nxt == eos_token_id
            out.append(jnp.asarray(nxt[:, None]))
            if (eos_token_id is not None and bool(done.all())) \
                    or i == max_new_tokens - 1:
                break  # no wasted forward for tokens nobody consumes
            # pos as a device scalar: a Python int would bake into the trace
            # and recompile every step
            logits, kc, vc = self._step(self.params, jnp.asarray(nxt[:, None]),
                                        kc, vc, jnp.asarray(pos, jnp.int32))
            pos += 1
        return np.asarray(jnp.concatenate(out, axis=1))


def decode_fallback_active() -> bool:
    """True when the per-token debugging path is requested, via the
    ``decode_fallback`` flag or the ``PADDLE_TPU_DECODE_FALLBACK`` env."""
    import os

    from paddle_tpu.flags import flags
    if flags.decode_fallback:
        return True
    return os.environ.get("PADDLE_TPU_DECODE_FALLBACK", "").strip().lower() \
        in ("1", "true", "yes", "on")


def _normalize_eos(eos_token_id) -> Optional[int]:
    """Uniform "no eos" convention across the decode surfaces: ``None``
    OR any negative id (the AOT bundles encode "none" as ``-1``, which no
    vocab token can match) both mean "decode to the full length"."""
    if eos_token_id is None:
        return None
    e = int(eos_token_id)
    return None if e < 0 else e


def _trim_after_eos(toks: np.ndarray, eos_token_id: int) -> np.ndarray:
    """Drop columns past the point where every row has emitted eos — the
    fused path pins finished rows to eos on device, so trimming here
    reproduces the per-token loop's early-stop output length exactly.
    A row whose FIRST emitted token is eos contributes length 1 (never
    0): the eos itself is part of the output, as in the host loop."""
    hit = toks == eos_token_id
    n = toks.shape[1]
    first = np.where(hit.any(axis=1), hit.argmax(axis=1), n - 1)
    return toks[:, :int(first.max()) + 1]


def _filter_logits(logits, temperature=1.0, top_k=None, top_p=None):
    """Temperature / top-k / top-p logit filtering over the LAST axis
    (any leading dims: (B, V) sampling, (B, K+1, V) speculative verify).
    ``temperature`` may be a traced runtime scalar; top-k/top-p change
    program structure and stay static. Returns filtered logits with
    excluded entries at -inf — the distribution BOTH sampling and the
    speculative accept/reject rule see (they must match exactly for the
    rejection rule to preserve the target distribution)."""
    lg = logits / jnp.maximum(jnp.asarray(temperature, logits.dtype), 1e-6)
    if top_k is not None:
        kth = jnp.sort(lg, axis=-1)[..., -int(top_k)][..., None]
        lg = jnp.where(lg < kth, -jnp.inf, lg)
    if top_p is not None:
        sorted_lg = jnp.flip(jnp.sort(lg, axis=-1), axis=-1)
        probs = jax.nn.softmax(sorted_lg, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        # smallest logit still inside the nucleus
        keep_n = jnp.sum(cum - probs < top_p, axis=-1)
        cutoff = jnp.take_along_axis(
            sorted_lg, jnp.maximum(keep_n - 1, 0)[..., None], axis=-1)
        lg = jnp.where(lg < cutoff, -jnp.inf, lg)
    return lg


def _sample_from(logits, key, temperature=1.0, top_k=None, top_p=None):
    """Temperature / top-k / top-p filtered categorical sample.
    (B, V) -> (B,). Pure trace-level function: runs inside the fused
    decode scan body and under the jitted `_sample_logits` wrapper."""
    return jax.random.categorical(
        key, _filter_logits(logits, temperature, top_k, top_p), axis=-1)


@functools.partial(jax.jit, static_argnames=("top_k", "top_p"))
def _sample_logits(logits, key, temperature=1.0, top_k=None, top_p=None):
    """Jitted `_sample_from` (the per-token host loops' sampling op).
    Temperature is a traced argument — no retrace across temperatures."""
    return _sample_from(logits, key, temperature, top_k, top_p)
