"""Plain float32 reference of the A.X-K1 decoder (SK Telecom, ``model_type:
axk1``): DeepSeek-V3's block at A.X-K1's widths. Straight ``jax.numpy``; no
kernel, no cache, no batching trick, and no import from ``paddle_tpu``: the
runner hands over the weights as plain arrays by their published names.
``rms_norm`` is the Llama reference's, ``swiglu`` the AFMoE one's.

The equations (``config.json`` keys in brackets), for the whole sequence at
once, in the EXPANDED form at every position::

    h = E[ids]
    for l in layers:
        x = RMSNorm(h; input_layernorm)
        q = RMSNorm(x W_qa; q_a_layernorm) W_qb           [q_lora_rank]
        q_h = [q_nope_h ; q_pe_h]                         [qk_nope_head_dim, qk_rope_head_dim]
        [c_kv ; k_pe] = x W_kva;  c_kv = RMSNorm(c_kv; kv_a_layernorm)   [kv_lora_rank]
        q_pe_h, k_pe = YaRN-RoPE(q_pe_h), YaRN-RoPE(k_pe)  # one k_pe for all heads
        [k_nope_h ; v_h] = c_kv W_kvb[h]                  [v_head_dim]
        s_h(i, j) = sigma (q_nope_h(i) . k_nope_h(j) + q_pe_h(i) . k_pe(j)),  j <= i
        sigma = (qk_nope + qk_rope)^-0.5 * mscale^2,  mscale = 0.1 mscale_all_dim ln(factor) + 1
        h = h + concat_h(softmax_j(s_h) v_h) W_o
        m = RMSNorm(h; post_attention_layernorm)
        if l < first_k_dense_replace:  f = SwiGLU_{intermediate_size}(m)
        else:
            s = sigmoid(m W_r)                     # float32, all n_routed_experts
            groups: n_group of equal size in index order; a group scores the
            sum of its two largest s (+ bias); the topk_group best are kept
            S = top_k over the kept groups' experts of s (+ bias)   [num_experts_per_tok]
            w_e = routed_scaling_factor * s_e / (sum_{S} s + 1e-20)   [norm_topk_prob]
            f = sum_{e in S} w_e expert_e(m) + shared(m)   # SwiGLU of moe_intermediate_size
        h = h + f
    logits = RMSNorm(h; norm) W_head

YaRN (``rope_scaling``): base frequencies ``theta^(-2i/d)``; between the
correction dimensions ``low = floor(d(beta_fast))`` and ``high =
ceil(d(beta_slow))``, ``d(b) = d_rope ln(orig / (2 pi b)) / (2 ln theta)``,
a linear ramp blends them into themselves over ``factor``; cos and sin
times ``mscale(factor, mscale) / mscale(factor, mscale_all_dim)``. The pairs
rotated together are INTERLEAVED (``modeling_deepseek.py`` reorders them
to halves, then ``rotate_half``).

Sources: every width and routing constant is a key of the published
``config.json`` (huggingface.co/skt/A.X-K1); the MLA equations, the YaRN
tables, the interleaved pairs, the softmax scale, the group score (the sum
of a group's two best, DeepSeek-V3's ``MoEGate``) and ``topk_method:
"none"`` read as no selection-correction bias (zero, which is DeepSeek-V3's
``noaux_tc`` selection) follow ``modeling_deepseek.py``'s convention,
which the configuration file lists under ``assumed``.

THE SHARE (``arch["experts_held"]``, ``arch["expert_offset"]``) is AFMoE's:
the router scores all, the sum runs over the chosen AND held experts;
``routed_part`` gives one share's part, and the shares' parts add up to the
uncut layer's (the tests hold that). ``route_override`` / ``record`` are
AFMoE's too: a selection in the place of the reference's own (a near-tie of
the eighth expert flips on bf16 rounding; a row of -1 is left to the
reference), and the reference's own handed out; ``scores`` hands out the scores it selected on (also under an
override), by which a selection that is not its own can be told a near-tie
or not (``adapters/axk1.py:route_margin``).

Attention runs over blocks of queries, one block's scores alive at a time,
so no (S, S) score matrix per head is alive at once at a long S. Weights
are cast to float32 one matrix at a time and the routed experts one expert
at a time; the host waits for each block and each product, so that what
it has queued does not pile up on the device. Everything runs under
``jax.default_matmul_precision("highest")``. ``round_to`` is for
``benchmark/precision_control.py`` alone (``reference/ouro_block.py`` says
what is rounded); the router's scores and weights are never rounded.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from benchmark.reference.afmoe_block import swiglu
from benchmark.reference.llama_block import rms_norm
from benchmark.reference.ouro_block import _stored_as

QUERY_BLOCK = 128


def _f32(a):
    return jnp.asarray(a, jnp.float32)


def _done(a):
    """``a`` once the device has computed it: the host waits, where
    running ahead of the device it would hold the buffers of every
    operation it has queued (gigabytes at a long S, the float32 copies of
    weights among them). A traced ``a`` passes through."""
    return a if isinstance(a, jax.core.Tracer) else jax.block_until_ready(a)


def yarn_mscale(scale: float, mscale: float) -> float:
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


def rope_tables(S: int, arch: dict):
    """cos, sin (S, qk_rope_head_dim / 2) of positions 0..S-1."""
    d, theta = int(arch["qk_rope_head_dim"]), float(arch["rope_theta"])
    base = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    rs = arch.get("rope_scaling")
    inv, ms = base, 1.0
    if rs is not None:
        def corr(rot):
            return d * math.log(rs["original_max_position_embeddings"]
                                / (rot * 2 * math.pi)) / (2 * math.log(theta))
        low = max(math.floor(corr(rs["beta_fast"])), 0)
        high = min(math.ceil(corr(rs["beta_slow"])), d - 1)
        if low == high:
            high += 0.001
        ramp = jnp.clip((jnp.arange(d // 2, dtype=jnp.float32) - low)
                        / (high - low), 0.0, 1.0)
        inv = (1.0 - ramp) * base + ramp * base / rs["factor"]
        ms = (yarn_mscale(rs["factor"], rs.get("mscale", 1))
              / yarn_mscale(rs["factor"], rs.get("mscale_all_dim", 0)))
    ang = jnp.outer(jnp.arange(S, dtype=jnp.float32), inv)
    return jnp.cos(ang) * ms, jnp.sin(ang) * ms


def rope(x, cos, sin):
    """x (B, S, H, d): interleaved pairs reordered to halves, then
    ``rotate_half``."""
    B, S, H, d = x.shape
    x = jnp.swapaxes(x.reshape(B, S, H, d // 2, 2), -1, -2).reshape(
        B, S, H, d)
    c, s = cos[None, :, None, :], sin[None, :, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def softmax_scale(arch: dict) -> float:
    s = (int(arch["qk_nope_head_dim"]) + int(arch["qk_rope_head_dim"])) \
        ** -0.5
    rs = arch.get("rope_scaling")
    if rs is not None and rs.get("mscale_all_dim"):
        m = yarn_mscale(rs["factor"], rs["mscale_all_dim"])
        s *= m * m
    return s


def attention(x, w, arch: dict, r=lambda t: t):
    """Multi-head latent attention over the normed input x (B, S,
    hidden), expanded, causal, over blocks of queries."""
    B, S, _ = x.shape
    H = int(arch["num_attention_heads"])
    dn, dr = int(arch["qk_nope_head_dim"]), int(arch["qk_rope_head_dim"])
    dv, R = int(arch["v_head_dim"]), int(arch["kv_lora_rank"])
    eps = float(arch["rms_norm_eps"])
    cos, sin = rope_tables(S, arch)
    cq = r(rms_norm(r(x @ _f32(w["self_attn.q_a_proj"])),
                    _f32(w["self_attn.q_a_layernorm"]), eps))
    q = r(cq @ _f32(w["self_attn.q_b_proj"])).reshape(B, S, H, dn + dr)
    a = r(x @ _f32(w["self_attn.kv_a_proj_with_mqa"]))
    ckv = r(rms_norm(a[..., :R], _f32(w["self_attn.kv_a_layernorm"]), eps))
    kpe = r(rope(a[..., None, R:], cos, sin))               # (B, S, 1, dr)
    q = jnp.concatenate([q[..., :dn], r(rope(q[..., dn:], cos, sin))], -1)
    kv = r(ckv @ _f32(w["self_attn.kv_b_proj"])).reshape(B, S, H, dn + dv)
    k = jnp.concatenate([kv[..., :dn],
                         jnp.broadcast_to(kpe, (B, S, H, dr))], -1)
    v = _done(kv[..., dn:])
    del kv
    sigma = softmax_scale(arch)
    kpos = jnp.arange(S)[None, :]
    out = []
    for q0 in range(0, S, QUERY_BLOCK):
        qb = q[:, q0:q0 + QUERY_BLOCK]
        seen = (q0 + jnp.arange(qb.shape[1]))[:, None] >= kpos
        s = jnp.einsum("bqhd,bkhd->bhqk", qb, k) * sigma
        p = jax.nn.softmax(jnp.where(seen[None, None], s, -jnp.inf), -1)
        # one block's (heads, block, S) float32 scores alive at a time
        out.append(_done(jnp.einsum("bhqk,bkhd->bqhd", p, v)))
    o = r(jnp.concatenate(out, axis=1)).reshape(B, S, H * dv)
    return r(o @ _f32(w["self_attn.o_proj"]))


def route(m, router, bias, arch: dict, select=None):
    """(weights (B, S, K) float32, chosen (B, S, K), the selection's scores
    ``sigmoid + bias`` (B, S, E)): the group-limited sigmoid top-k, float32
    throughout. ``select``: a choice in the place of it, its rows of -1
    left to it."""
    s = jax.nn.sigmoid(m @ router)
    c = s + bias
    given = select
    if select is None or bool(jnp.any(jnp.asarray(select) < 0)):
        G, kept_of = int(arch["n_group"]), c
        if G > 1:
            g = c.reshape(c.shape[:-1] + (G, -1))
            gs = jnp.sum(jax.lax.top_k(g, 2)[0], -1)
            _, best = jax.lax.top_k(gs, int(arch["topk_group"]))
            kept = jnp.any(best[..., None] == jnp.arange(G), -2)
            kept_of = jnp.where(kept[..., None], g, -jnp.inf).reshape(
                c.shape)
        _, select = jax.lax.top_k(kept_of, int(arch["num_experts_per_tok"]))
        if given is not None:
            select = jnp.where(jnp.asarray(given) < 0, select, given)
    top = jnp.take_along_axis(s, select, axis=-1)
    if arch["norm_topk_prob"]:
        top = top / (jnp.sum(top, -1, keepdims=True) + 1e-20)
    return top * float(arch["routed_scaling_factor"]), select, c


def routed_part(m, w, arch: dict, select=None, r=lambda t: t, record=None,
                li=None, scores=None):
    """sum over the chosen AND held experts of w_e expert_e(m), one expert
    at a time."""
    wts, sel, c = route(m, _f32(w["mlp.gate"]), _f32(w["mlp.gate.bias"]),
                        arch, select)
    if record is not None:
        record[li] = sel
    if scores is not None:
        scores[li] = c
    off = int(arch.get("expert_offset", 0))
    held = int(arch.get("experts_held", arch["n_routed_experts"]))
    out = jnp.zeros_like(m)
    for e in range(held):
        we = jnp.sum(jnp.where(sel == off + e, wts, 0.0), -1, keepdims=True)
        if not isinstance(we, jax.core.Tracer) and not bool(jnp.any(we)):
            continue
        gate, up, down = (_f32(a) for a in w["mlp.experts"](e))
        out = out + we * swiglu(m, gate, up, down, r)
        del gate, up, down
    return r(out)


def _dense(m, w, prefix, r):
    """SwiGLU with each float32 matrix alive only for its own product."""
    g = _done(r(m @ _f32(w[prefix + "gate_proj"])))
    u = _done(r(m @ _f32(w[prefix + "up_proj"])))
    return _done(r(r(jax.nn.silu(g) * u) @ _f32(w[prefix + "down_proj"])))


def block(h, w, li: int, arch: dict, round_to=None, select=None,
          record=None, scores=None):
    """One block on h (B, S, hidden); ``w`` by published names."""
    r = _stored_as(round_to)
    eps = float(arch["rms_norm_eps"])
    x = r(rms_norm(h, _f32(w["input_layernorm"]), eps))
    h = r(h + attention(x, w, arch, r))
    m = r(rms_norm(h, _f32(w["post_attention_layernorm"]), eps))
    if li < int(arch["first_k_dense_replace"]):
        f = _dense(m, w, "mlp.", r)
    else:
        f = routed_part(m, w, arch, select, r, record, li, scores)
        if "mlp.shared_experts.gate_proj" in w:
            f = r(f + _dense(m, w, "mlp.shared_experts.", r))
    return r(h + f)


def hidden_states(ids, arch: dict, layers: int, embed, layer_weights,
                  round_to=None, route_override=None, record=None,
                  scores=None):
    """The residual stream after ``layers`` blocks (before the final
    norm)."""
    with jax.default_matmul_precision("highest"):
        h = _stored_as(round_to)(jnp.asarray(embed)[ids].astype(jnp.float32))
        for li in range(layers):
            select = None if route_override is None \
                else route_override.get(li)
            h = block(h, layer_weights(li), li, arch, round_to, select,
                      record, scores)
        return h


def logits(ids, arch: dict, layers: int, embed, layer_weights, norm, head,
           positions=None, round_to=None, route_override=None, record=None,
           scores=None):
    """Float32 logits (B, S', V) of the full causal forward over ``ids``
    (B, S); ``positions`` keeps only those positions before the final norm
    and the head. ``scores`` (a dict) receives each routed layer's
    selection scores (B, S, E)."""
    h = hidden_states(ids, arch, layers, embed, layer_weights, round_to,
                      route_override, record, scores)
    with jax.default_matmul_precision("highest"):
        if positions is not None:
            h = h[:, jnp.asarray(positions)]
        h = _stored_as(round_to)(rms_norm(h, _f32(norm),
                                          float(arch["rms_norm_eps"])))
        return h @ _f32(head)


def layer_weights_by_name(params: dict, arch: dict):
    """``layer_weights(i)`` over a flat dict keyed as ``AxK1ForCausalLM``'s
    ``state_dict`` is (the router is ``mlp.router``, its bias
    ``mlp.expert_bias``, the experts two stacks)."""
    ffn = int(arch["moe_intermediate_size"])

    def get(i):
        pre = f"model.layers.{i}."
        w = {k: params[pre + k + ".weight"] for k in (
            "input_layernorm", "post_attention_layernorm",
            "self_attn.q_a_proj", "self_attn.q_a_layernorm",
            "self_attn.q_b_proj", "self_attn.kv_a_proj_with_mqa",
            "self_attn.kv_a_layernorm", "self_attn.kv_b_proj",
            "self_attn.o_proj")}
        if pre + "mlp.router.weight" not in params:
            for k in ("gate_proj", "up_proj", "down_proj"):
                w["mlp." + k] = params[f"{pre}mlp.{k}.weight"]
            return w
        w["mlp.gate"] = params[pre + "mlp.router.weight"]
        w["mlp.gate.bias"] = params[pre + "mlp.expert_bias"]
        gu, dn = (params[pre + "mlp.experts_gate_up"],
                  params[pre + "mlp.experts_down"])
        w["mlp.experts"] = lambda e: (gu[e][:, :ffn], gu[e][:, ffn:], dn[e])
        for k in ("gate_proj", "up_proj", "down_proj"):
            name = f"{pre}mlp.shared_experts.{k}.weight"
            if name in params:
                w["mlp.shared_experts." + k] = params[name]
        return w
    return get
