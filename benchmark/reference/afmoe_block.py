"""Plain float32 reference of the AFMoE decoder (Arcee Trinity,
``model_type: afmoe``). Straight ``jax.numpy``; no kernel, no cache, no
batching trick, and no import from ``paddle_tpu``: the runner hands over the
weights as plain arrays by their published names. ``rms_norm`` and ``rope``
are the Llama reference's.

The equations (``config.json`` keys in brackets)::

    h = E[ids] * sqrt(hidden_size)                        [mup_enabled]
    for l in layers:
        a = RMSNorm(h; input_layernorm)
        q, k, v, g = a Wq, a Wk, a Wv, a Wg               # g: (heads * head_dim)
        q, k = RMSNorm(q; q_norm), RMSNorm(k; k_norm)     # per head, over head_dim
        if layer_types[l] == sliding_attention:
            q, k = RoPE(q), RoPE(k)                       [rope_theta], split-half
            visible(i, j) = 0 <= i - j < sliding_window   # itself included
        else:                                             # full_attention: NoPE
            visible(i, j) = j <= i
        o = softmax(q k^T / sqrt(head_dim) | visible) v
        h = h + RMSNorm((o * sigmoid(g)) Wo; post_attention_layernorm)
        m = RMSNorm(h; pre_mlp_layernorm)
        if l < num_dense_layers:  f = SwiGLU_{intermediate_size}(m)
        else:
            s = sigmoid(m Wr)                 # float32, all num_experts
            S = top_k(s + expert_bias)        [num_experts_per_tok]; the bias selects only
            w_e = route_scale * s_e / (sum_{e' in S} s_e' + 1e-20)   [route_norm]
            f = shared(m) + sum_{e in S} w_e expert_e(m)  # SwiGLU of moe_intermediate_size
        h = h + RMSNorm(f; post_mlp_layernorm)
    logits = RMSNorm(h; norm) W_head

Sources: every width, ``layer_types``, ``sliding_window``, the routing
constants and ``mup_enabled`` are keys of the published ``config.json``
(huggingface.co/arcee-ai/Trinity-Large-Preview); q/k norm, the gate, NoPE
on full layers, the window's convention, the four norms' places and
``n_group = topk_group = 1`` meaning no group limit are from
``modeling_afmoe.py`` beside it.

THE SHARE (``arch["experts_held"]``, ``arch["expert_offset"]``): the
deployment's experts are spread over chips and this one holds experts
``[offset, offset + held)``. The router scores all ``num_experts``, the
weights are normalised over the chosen whether or not they are held, and the
sum runs over the chosen AND held. Absent experts and their exchange are left
out, here as in the program; the eight shares' routed parts add up to the
uncut layer's (``routed_part``; the tests hold that).

``route_override`` (layer index -> (B, S, K) chosen experts) puts a
selection in the place of the reference's own: with random weights a token
whose fourth and fifth scores lie within bf16's rounding of the router's
input chooses another expert in a bf16 program than here, and that layer's
output moves by a whole expert. Under the program's selection what is left
is rounding. ``record`` (a dict) receives each routed layer's own selection.

The window's mask is built over blocks of queries, so no (S, S) float
matrix per head is alive at once at a long S. Weights are cast to float32
one layer at a time and the routed experts ONE EXPERT at a time
(``w["mlp.experts"](e)``): a float32 copy of one layer's 32 experts is 3.6
GB at the published widths. Everything runs under
``jax.default_matmul_precision("highest")``.

``round_to`` is for ``benchmark/precision_control.py`` alone
(``reference/ouro_block.py`` says what is rounded). The router's scores and
weights are never rounded: the published model computes them in float32.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from benchmark.reference.llama_block import rms_norm, rope
from benchmark.reference.ouro_block import _stored_as

SLIDING = "sliding_attention"
QUERY_BLOCK = 512


def attention(q, k, v, window):
    """Causal softmax attention (B, S, H, D) under a band of ``window``
    positions (None: all of the past), over blocks of queries."""
    S, scale = q.shape[1], 1.0 / math.sqrt(q.shape[-1])
    kpos = jnp.arange(S)[None, :]
    out = []
    for q0 in range(0, S, QUERY_BLOCK):
        qb = q[:, q0:q0 + QUERY_BLOCK]
        dist = (q0 + jnp.arange(qb.shape[1]))[:, None] - kpos
        seen = dist >= 0
        if window is not None:
            seen = jnp.logical_and(seen, dist < window)
        s = jnp.einsum("bqhd,bkhd->bhqk", qb, k) * scale
        p = jax.nn.softmax(jnp.where(seen[None, None], s, -jnp.inf), -1)
        out.append(jnp.einsum("bhqk,bkhd->bqhd", p, v))
    return jnp.concatenate(out, axis=1)


def swiglu(x, gate, up, down, r=lambda x: x):
    return r(r(jax.nn.silu(r(x @ gate)) * r(x @ up)) @ down)


def route(m, router, bias, arch, select=None):
    """(weights (B, S, K) float32, chosen (B, S, K)): float32 throughout."""
    s = jax.nn.sigmoid(m @ router)
    if select is None:
        _, select = jax.lax.top_k(s + bias, int(arch["num_experts_per_tok"]))
    top = jnp.take_along_axis(s, select, axis=-1)
    if arch["route_norm"]:
        top = top / (jnp.sum(top, -1, keepdims=True) + 1e-20)
    return top * float(arch["route_scale"]), select


def routed_part(m, w, arch, select=None, r=lambda x: x, record=None, li=None):
    """sum over the chosen AND held experts of w_e expert_e(m): one expert
    at a time, over every token, weighted 0 where it was not chosen."""
    f32 = lambda a: jnp.asarray(a, jnp.float32)  # noqa: E731
    wts, sel = route(m, f32(w["mlp.router.gate"]), f32(w["mlp.expert_bias"]),
                     arch, select)
    if record is not None:
        record[li] = sel
    off = int(arch.get("expert_offset", 0))
    held = int(arch.get("experts_held", arch["num_experts"]))
    out = jnp.zeros_like(m)
    for e in range(held):
        we = jnp.sum(jnp.where(sel == off + e, wts, 0.0), -1, keepdims=True)
        if not isinstance(we, jax.core.Tracer) and not bool(jnp.any(we)):
            continue                  # (under jax.grad every expert runs)
        gate, up, down = (f32(a) for a in w["mlp.experts"](e))
        out = out + we * swiglu(m, gate, up, down, r)
        del gate, up, down
    return r(out)


def block(h, w, li: int, arch: dict, round_to=None, select=None, record=None):
    """One block on h (B, S, hidden); ``w`` by published names."""
    r = _stored_as(round_to)
    f32 = lambda a: jnp.asarray(a, jnp.float32)  # noqa: E731
    eps = float(arch["rms_norm_eps"])
    B, S, _ = h.shape
    H, KV = arch["num_attention_heads"], arch["num_key_value_heads"]
    sliding = arch["layer_types"][li] == SLIDING
    a = r(rms_norm(h, f32(w["input_layernorm"]), eps))
    q = r(a @ f32(w["self_attn.q_proj"])).reshape(B, S, H, -1)
    k = r(a @ f32(w["self_attn.k_proj"])).reshape(B, S, KV, -1)
    v = r(a @ f32(w["self_attn.v_proj"])).reshape(B, S, KV, -1)
    g = r(a @ f32(w["self_attn.gate_proj"]))
    q = r(rms_norm(q, f32(w["self_attn.q_norm"]), eps))
    k = r(rms_norm(k, f32(w["self_attn.k_norm"]), eps))
    if sliding:
        theta = float(arch["rope_theta"])
        q, k = r(rope(q, theta)), r(rope(k, theta))
    k, v = jnp.repeat(k, H // KV, axis=2), jnp.repeat(v, H // KV, axis=2)
    o = r(attention(q, k, v, int(arch["sliding_window"]) if sliding
                    else None)).reshape(B, S, -1)
    att = r(r(o * jax.nn.sigmoid(g)) @ f32(w["self_attn.o_proj"]))
    h = r(h + r(rms_norm(att, f32(w["post_attention_layernorm"]), eps)))
    m = r(rms_norm(h, f32(w["pre_mlp_layernorm"]), eps))
    if li < int(arch["num_dense_layers"]):
        f = swiglu(m, f32(w["mlp.gate_proj"]), f32(w["mlp.up_proj"]),
                   f32(w["mlp.down_proj"]), r)
    else:
        f = routed_part(m, w, arch, select, r, record, li)
        if "mlp.shared_experts.gate_proj" in w:
            f = r(f + swiglu(m, f32(w["mlp.shared_experts.gate_proj"]),
                             f32(w["mlp.shared_experts.up_proj"]),
                             f32(w["mlp.shared_experts.down_proj"]), r))
    return r(h + r(rms_norm(f, f32(w["post_mlp_layernorm"]), eps)))


def hidden_states(ids, arch: dict, layers: int, embed, layer_weights,
                  round_to=None, route_override=None, record=None):
    """The residual stream after ``layers`` blocks (before the final
    norm)."""
    with jax.default_matmul_precision("highest"):
        h = jnp.asarray(embed)[ids].astype(jnp.float32)
        if arch.get("mup_enabled"):
            h = h * math.sqrt(float(arch["hidden_size"]))
        h = _stored_as(round_to)(h)
        for li in range(layers):
            select = None if route_override is None \
                else route_override.get(li)
            h = block(h, layer_weights(li), li, arch, round_to, select,
                      record)
        return h


def logits(ids, arch: dict, layers: int, embed, layer_weights, norm, head,
           positions=None, round_to=None, route_override=None, record=None):
    """Float32 logits (B, S', V) of the full causal forward over ``ids``
    (B, S); ``positions`` keeps only those sequence positions before the
    final norm and the head."""
    h = hidden_states(ids, arch, layers, embed, layer_weights, round_to,
                      route_override, record)
    with jax.default_matmul_precision("highest"):
        if positions is not None:
            h = h[:, jnp.asarray(positions)]
        h = _stored_as(round_to)(rms_norm(
            h, jnp.asarray(norm, jnp.float32), float(arch["rms_norm_eps"])))
        return h @ jnp.asarray(head, jnp.float32)


# the program's parameter names (models/afmoe.py: Ouro's sandwich names,
# the experts stacked gate|up and down) by the published ones
def layer_weights_by_name(params: dict, arch: dict):
    """``layer_weights(i)`` over a flat dict keyed as ``AfmoeForCausalLM``'s
    ``state_dict`` is."""
    ffn = int(arch["moe_intermediate_size"])

    def get(i):
        pre = f"model.layers.{i}."
        w = {"input_layernorm": params[pre + "input_layernorm.weight"],
             "post_attention_layernorm":
                 params[pre + "input_layernorm_2.weight"],
             "pre_mlp_layernorm":
                 params[pre + "post_attention_layernorm.weight"],
             "post_mlp_layernorm":
                 params[pre + "post_attention_layernorm_2.weight"]}
        for k in ("q_proj", "k_proj", "v_proj", "o_proj", "gate_proj",
                  "q_norm", "k_norm"):
            w["self_attn." + k] = params[f"{pre}self_attn.{k}.weight"]
        if pre + "mlp.router.weight" not in params:
            for k in ("gate_proj", "up_proj", "down_proj"):
                w["mlp." + k] = params[f"{pre}mlp.{k}.weight"]
            return w
        w["mlp.router.gate"] = params[pre + "mlp.router.weight"]
        w["mlp.expert_bias"] = params[pre + "mlp.expert_bias"]
        gu, dn = (params[pre + "mlp.experts_gate_up"],
                  params[pre + "mlp.experts_down"])
        w["mlp.experts"] = lambda e: (gu[e][:, :ffn], gu[e][:, ffn:], dn[e])
        for k in ("gate_proj", "up_proj", "down_proj"):
            name = f"{pre}mlp.shared_experts.{k}.weight"
            if name in params:
                w["mlp.shared_experts." + k] = params[name]
        return w
    return get


def loss_fn(params: dict, ids, labels, arch: dict, layers: int):
    """Differentiable mean cross-entropy over such a dict: for the gradient
    comparison at tiny width in the tests."""
    lg = logits(ids, arch, layers, params["model.embed_tokens.weight"],
                layer_weights_by_name(params, arch),
                params["model.norm.weight"], params["lm_head.weight"])
    lp = jax.nn.log_softmax(lg, axis=-1)
    nll = -jnp.take_along_axis(lp, jnp.asarray(labels)[..., None], -1)
    return jnp.mean(nll)
