"""Plain float32 reference of the decoder the benchmark's configurations
run: RMSNorm, split-half RoPE, causal GQA/MHA attention, SwiGLU, untied
head. Straight ``jax.numpy``; no kernel, no cache, no batching trick, and no
import from ``paddle_tpu``: the runner hands over the weights as plain
arrays by their Hugging Face names.

Follows the published Llama/Mistral block (``modeling_mistral.py`` /
``modeling_llama.py`` of transformers): pre-norm residual blocks,
``rotate_half`` RoPE over the two halves of each head, K/V heads repeated
to the query heads, softmax in float32, ``down(silu(gate(x)) * up(x))``.
No departure.

Weights are cast to float32 one layer at a time (``layer_weights(i)`` is
called when layer ``i`` is needed), so a single layer's float32 copy is
alive at a time. On a TPU a float32 matmul runs in lower precision unless
asked otherwise, so everything runs under
``jax.default_matmul_precision("highest")``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

LAYER_KEYS = ("input_layernorm", "q_proj", "k_proj", "v_proj", "o_proj",
              "post_attention_layernorm", "gate_proj", "up_proj", "down_proj")


def rms_norm(x, w, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w


def rope(x, theta):
    """x (B, S, H, D), positions 0..S-1, the two halves rotated."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.outer(jnp.arange(x.shape[1], dtype=jnp.float32), inv)
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@functools.partial(jax.jit, static_argnames=("heads", "kv_heads", "theta",
                                             "eps"))
def block(h, w, *, heads, kv_heads, theta, eps):
    """One decoder block on h (B, S, hidden), weights (in, out) float32."""
    B, S, _ = h.shape
    x = rms_norm(h, w["input_layernorm"], eps)
    q = (x @ w["q_proj"]).reshape(B, S, heads, -1)
    k = (x @ w["k_proj"]).reshape(B, S, kv_heads, -1)
    v = (x @ w["v_proj"]).reshape(B, S, kv_heads, -1)
    q, k = rope(q, theta), rope(k, theta)
    rep = heads // kv_heads
    k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(
        jnp.float32(q.shape[-1]))
    causal = jnp.tril(jnp.ones((S, S), bool))
    s = jnp.where(causal[None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    a = jnp.einsum("bhqk,bkhd->bqhd", p, v).reshape(B, S, -1)
    h = h + a @ w["o_proj"]
    x = rms_norm(h, w["post_attention_layernorm"], eps)
    return h + (jax.nn.silu(x @ w["gate_proj"]) * (x @ w["up_proj"])) \
        @ w["down_proj"]


def hidden_states(ids, arch: dict, layers: int, embed, layer_weights):
    """Final-norm input: the residual stream after ``layers`` blocks."""
    with jax.default_matmul_precision("highest"):
        h = jnp.asarray(embed)[ids].astype(jnp.float32)
        for li in range(layers):
            w = {k: jnp.asarray(v, jnp.float32)
                 for k, v in layer_weights(li).items()}
            h = block(h, w, heads=arch["num_attention_heads"],
                      kv_heads=arch["num_key_value_heads"],
                      theta=float(arch["rope_theta"]),
                      eps=float(arch["rms_norm_eps"]))
            del w
        return h


def logits(ids, arch: dict, layers: int, embed, layer_weights, norm, head,
           positions=None):
    """Float32 logits (B, S', V) of the full causal forward over ``ids``
    (B, S); ``positions`` keeps only those sequence positions before the
    head (the head over a long sequence and a large vocabulary is the
    largest array here)."""
    h = hidden_states(ids, arch, layers, embed, layer_weights)
    with jax.default_matmul_precision("highest"):
        if positions is not None:
            h = h[:, jnp.asarray(positions)]
        h = rms_norm(h, jnp.asarray(norm, jnp.float32),
                     float(arch["rms_norm_eps"]))
        return h @ jnp.asarray(head, jnp.float32)


def cross_entropy(ids, labels, arch, layers, embed, layer_weights, norm,
                  head):
    """Mean token cross-entropy of ``labels`` under the full forward, one
    sequence at a time (the logits of one sequence are alive at a time)."""
    total, count = 0.0, 0
    for b in range(ids.shape[0]):
        lg = logits(ids[b:b + 1], arch, layers, embed, layer_weights, norm,
                    head)[0]
        lp = jax.nn.log_softmax(lg, axis=-1)
        nll = -jnp.take_along_axis(lp, jnp.asarray(labels[b])[:, None], 1)
        total += float(jnp.sum(nll))
        count += int(nll.shape[0])
    return total / count


def layer_weights_by_name(params: dict):
    """``layer_weights(i)`` over a flat dict keyed by the Hugging Face names
    (``model.layers.<i>.self_attn.q_proj.weight`` ...)."""
    def get(i):
        pre = f"model.layers.{i}."
        return {
            "input_layernorm": params[pre + "input_layernorm.weight"],
            "post_attention_layernorm":
                params[pre + "post_attention_layernorm.weight"],
            **{k: params[f"{pre}self_attn.{k}.weight"]
               for k in ("q_proj", "k_proj", "v_proj", "o_proj")},
            **{k: params[f"{pre}mlp.{k}.weight"]
               for k in ("gate_proj", "up_proj", "down_proj")}}
    return get


def loss_fn(params: dict, ids, labels, arch: dict, layers: int):
    """Differentiable mean cross-entropy over such a dict: for the gradient
    comparison at tiny width in the benchmark's tests."""
    lg = logits(ids, arch, layers, params["model.embed_tokens.weight"],
                layer_weights_by_name(params), params["model.norm.weight"],
                params["lm_head.weight"])
    lp = jax.nn.log_softmax(lg, axis=-1)
    nll = -jnp.take_along_axis(lp, jnp.asarray(labels)[..., None], -1)
    return jnp.mean(nll)
