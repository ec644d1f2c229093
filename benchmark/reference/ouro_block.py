"""Plain float32 reference of the Ouro looped decoder (ByteDance Ouro 1.4B /
2.6B, "LoopLM"). Straight ``jax.numpy``; no kernel, no cache, no batching
trick, and no import from ``paddle_tpu``: the runner hands over the weights
as plain arrays by their Hugging Face names. ``rms_norm`` and ``rope`` are
the Llama reference's.

The equations (T = ``total_ut_steps``, L = ``num_hidden_layers``)::

    h = E[ids]
    for t in 0..T-1:                          # the SAME L layers, T times
        for l in 0..L-1:
            a = RMSNorm(h; input_layernorm_l)
            q, k, v = a Wq_l, a Wk_l, a Wv_l ;  q, k = RoPE(q), RoPE(k)
            o = softmax(causal(q k^T / sqrt(head_dim))) v
            h = h + RMSNorm(o Wo_l; input_layernorm_2_l)
            m = RMSNorm(h; post_attention_layernorm_l)
            h = h + RMSNorm((silu(m Wg_l) * (m Wu_l)) Wd_l;
                            post_attention_layernorm_2_l)
        h = RMSNorm(h; model.norm)            # at the end of EVERY pass
    logits = h W_head                         # from the last pass

Keys and values are computed anew in every pass from that pass's own
residual stream: pass t never sees the keys of pass t' (a cached decoder
therefore holds T * L cache layers).

Sources: T, ``early_exit_threshold`` and every width are keys of the
published ``config.json`` (huggingface.co/ByteDance/Ouro-2.6B); the four
norms a block (a norm before each sub-layer and one on its output before
the residual add), the final norm inside the loop, no bias, no q/k norm and
split-half ``rotate_half`` RoPE are from the model's own
``modeling_ouro.py`` beside that file and section 3 of arXiv:2510.25741.

One departure: the early-exit gate (a ``hidden_size -> 1`` linear and a
sigmoid per pass, 2049 parameters) is not computed. At the published
``early_exit_threshold`` of 1.0 no accumulated exit probability passes the
threshold before the last pass, so every token runs all T passes and the
gate decides nothing.

Weights are cast to float32 one layer at a time, T times over
(``layer_weights(l)`` is called whenever layer ``l`` is needed), so a single
layer's float32 copy is alive at a time. On a TPU a float32 matmul runs in
lower precision unless asked otherwise, so everything runs under
``jax.default_matmul_precision("highest")``.

``round_to`` (a dtype's name; None everywhere the reference is the reference)
is for the control of the gates' limits (``benchmark/precision_control.py``):
every activation a program would store — the output of each norm, matmul,
RoPE, softmax and residual add — is cast to that dtype and back, so the same
float32 arithmetic keeps only what a program in that precision keeps. The
weights are left as they are handed over and the head's output is not
rounded: it is what the gates compare.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmark.reference.llama_block import rms_norm, rope

LAYER_KEYS = ("input_layernorm", "q_proj", "k_proj", "v_proj", "o_proj",
              "input_layernorm_2", "post_attention_layernorm", "gate_proj",
              "up_proj", "down_proj", "post_attention_layernorm_2")


def _stored_as(round_to):
    """What a program that stores its activations as ``round_to`` keeps of a
    float32 value; the value itself for None."""
    if round_to is None:
        return lambda x: x
    return lambda x: x.astype(round_to).astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("heads", "kv_heads", "theta",
                                             "eps", "round_to"))
def block(h, w, *, heads, kv_heads, theta, eps, round_to=None):
    """One sandwich-norm block on h (B, S, hidden), weights (in, out)
    float32."""
    r = _stored_as(round_to)
    B, S, _ = h.shape
    x = r(rms_norm(h, w["input_layernorm"], eps))
    q = r(x @ w["q_proj"]).reshape(B, S, heads, -1)
    k = r(x @ w["k_proj"]).reshape(B, S, kv_heads, -1)
    v = r(x @ w["v_proj"]).reshape(B, S, kv_heads, -1)
    q, k = r(rope(q, theta)), r(rope(k, theta))
    rep = heads // kv_heads
    k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(
        jnp.float32(q.shape[-1]))
    causal = jnp.tril(jnp.ones((S, S), bool))
    s = jnp.where(causal[None, None], s, -jnp.inf)
    p = r(jax.nn.softmax(s, axis=-1))
    a = r(jnp.einsum("bhqk,bkhd->bqhd", p, v)).reshape(B, S, -1)
    h = r(h + r(rms_norm(r(a @ w["o_proj"]), w["input_layernorm_2"], eps)))
    x = r(rms_norm(h, w["post_attention_layernorm"], eps))
    m = r(r(jax.nn.silu(r(x @ w["gate_proj"])) * r(x @ w["up_proj"]))
          @ w["down_proj"])
    return r(h + r(rms_norm(m, w["post_attention_layernorm_2"], eps)))


def hidden_states(ids, arch: dict, layers: int, embed, layer_weights, norm,
                  round_to=None):
    """The residual stream after ``arch["total_ut_steps"]`` passes over
    ``layers`` blocks, the final norm applied at the end of each pass (the
    last one included)."""
    eps = float(arch["rms_norm_eps"])
    r = _stored_as(round_to)
    with jax.default_matmul_precision("highest"):
        h = jnp.asarray(embed)[ids].astype(jnp.float32)
        nw = jnp.asarray(norm, jnp.float32)
        for _ in range(int(arch["total_ut_steps"])):
            for li in range(layers):
                w = {k: jnp.asarray(v, jnp.float32)
                     for k, v in layer_weights(li).items()}
                h = block(h, w, heads=arch["num_attention_heads"],
                          kv_heads=arch["num_key_value_heads"],
                          theta=float(arch["rope_theta"]), eps=eps,
                          round_to=round_to)
                del w
            h = r(rms_norm(h, nw, eps))
        return h


def logits(ids, arch: dict, layers: int, embed, layer_weights, norm, head,
           positions=None, round_to=None):
    """Float32 logits (B, S', V) of the full causal forward over ``ids``
    (B, S); ``positions`` keeps only those sequence positions before the
    head."""
    h = hidden_states(ids, arch, layers, embed, layer_weights, norm,
                      round_to)
    with jax.default_matmul_precision("highest"):
        if positions is not None:
            h = h[:, jnp.asarray(positions)]
        return h @ jnp.asarray(head, jnp.float32)


def layer_weights_by_name(params: dict):
    """``layer_weights(i)`` over a flat dict keyed by the Hugging Face names
    (``model.layers.<i>.self_attn.q_proj.weight`` ...)."""
    def get(i):
        pre = f"model.layers.{i}."
        return {
            **{k: params[f"{pre}{k}.weight"]
               for k in ("input_layernorm", "input_layernorm_2",
                         "post_attention_layernorm",
                         "post_attention_layernorm_2")},
            **{k: params[f"{pre}self_attn.{k}.weight"]
               for k in ("q_proj", "k_proj", "v_proj", "o_proj")},
            **{k: params[f"{pre}mlp.{k}.weight"]
               for k in ("gate_proj", "up_proj", "down_proj")}}
    return get


def loss_fn(params: dict, ids, labels, arch: dict, layers: int):
    """Differentiable mean cross-entropy over such a dict: for the gradient
    comparison at tiny width in the tests (the T uses of each weight add
    up under ``jax.grad``)."""
    lg = logits(ids, arch, layers, params["model.embed_tokens.weight"],
                layer_weights_by_name(params), params["model.norm.weight"],
                params["lm_head.weight"])
    lp = jax.nn.log_softmax(lg, axis=-1)
    nll = -jnp.take_along_axis(lp, jnp.asarray(labels)[..., None], -1)
    return jnp.mean(nll)
