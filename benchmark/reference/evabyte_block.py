"""Plain float32 reference of the EvaByte decoder (EvaByte 6.5B, EVA
attention). Straight ``jax.numpy``; no kernel, no cache, no windows as
batches, and no import from ``paddle_tpu``: the runner hands over the
weights as plain arrays by their Hugging Face names. ``rms_norm`` and
``rope`` are the Llama reference's.

The equations (H heads of D, ``s = D ** -0.5``, W = ``window_size``, C =
``chunk_size``; positions i, j, chunks c; window of a position ``i // W``)::

    h = float32(E[ids])
    for l in 0..L-1:
        a = RMSNorm(h) * (1 + w_in_l)                 # norm_add_unit_offset
        q, k, v = a Wq_l, a Wk_l, a Wv_l ;  q, k = RoPE(q), RoPE(k)
        for every COMPLETE chunk c (keys j in [c C, (c + 1) C)), per head:
            k~_c = sum_j softmax_j(s * mu_l . k_j) k_j
            v~_c = sum_j softmax_j(s * phi_l . k_j) v_j
        query i attends in ONE softmax of scores s * q_i . (k_j | k~_c) over
            (a) keys j with j // W == i // W and j <= i
            (b) summaries c with c // (W / C) < i // W
          o_i = sum_j p_ij v_j + sum_c p_ic v~_c
        h = h + o Wo_l                                # float32: fp32_skip_add
        m = RMSNorm(h) * (1 + w_post_l)
        h = h + (silu(m Wg_l) * (m Wu_l)) Wd_l
    logits = (RMSNorm(h) * (1 + w_norm)) W_head       # num_pred_heads * V

So a window is ALIGNED, not sliding: position ``W`` sees itself and the
``W / C`` summaries of window 0. Head ``p`` of the ``num_pred_heads``
vocabularies in ``W_head`` predicts byte ``t + 1 + p``; the next byte is
columns ``[0, V)``.

Sources: every width, W, C, ``num_pred_heads``, ``norm_add_unit_offset``,
``fp32_skip_add``, ``fp32_logits``, ``rope_theta`` are keys of the published
``config.json`` (huggingface.co/EvaByte/EvaByte). NOT keys of it, taken from
the model's ``eva.py`` / ``eva_prep_kv_kernel.py`` / ``eva_agg_kernel.py``
beside that file and from Zheng, Wang, Kong, "Efficient Attention via
Control Variates" (ICLR 2023: exact attention on a local set, one
control-variate summary a chunk elsewhere, one normaliser), and listed under
``assumed`` in the configuration file: the pooling weights (``adaptive_mu_k``
against the keys for k~, ``adaptive_phi`` against the keys for v~), RoPE
(split-half ``rotate_half``) before pooling, visibility by whole windows.

The attention is computed in blocks of ``q_block`` queries against all keys
and summaries (a block's scores are (H, q_block, S + S / C) float32), so
that some 4100 positions at width 4096 fit; nothing else is blocked. Weights
are cast to float32 one layer at a time. On a TPU a float32 matmul runs in
lower precision unless asked otherwise, so everything runs under
``jax.default_matmul_precision("highest")``.

``round_to`` (a dtype's name; None everywhere the reference is the
reference) is for the control of the gates' limits
(``benchmark/precision_control.py``): every activation a program would
store in its compute dtype — the output of each norm, matmul, RoPE,
pooling, softmax — is cast to that dtype and back. The residual stream is
not: the published model keeps it in float32 whatever the compute dtype.
The weights are left as they are handed over and the head's output is not
rounded: it is what the gates compare.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmark.reference.llama_block import rms_norm, rope

LAYER_KEYS = ("input_layernorm", "q_proj", "k_proj", "v_proj", "o_proj",
              "adaptive_mu_k", "adaptive_phi", "post_attention_layernorm",
              "gate_proj", "up_proj", "down_proj")


def _stored_as(round_to):
    """What a program that stores its activations as ``round_to`` keeps of
    a float32 value; the value itself for None."""
    if round_to is None:
        return lambda x: x
    return lambda x: x.astype(round_to).astype(jnp.float32)


def summaries(k, v, mu, phi, chunk: int):
    """k, v (B, S, H, D) rotated; mu, phi (H, D) -> (k~, v~), each (B, S //
    chunk, H, D): the complete chunks only."""
    B, S, H, D = k.shape
    n = S // chunk
    s = jnp.float32(D) ** -0.5
    kc = k[:, :n * chunk].reshape(B, n, chunk, H, D)
    vc = v[:, :n * chunk].reshape(B, n, chunk, H, D)
    wk = jax.nn.softmax(s * jnp.einsum("bnjhd,hd->bnjh", kc, mu), axis=2)
    wv = jax.nn.softmax(s * jnp.einsum("bnjhd,hd->bnjh", kc, phi), axis=2)
    return (jnp.einsum("bnjh,bnjhd->bnhd", wk, kc),
            jnp.einsum("bnjh,bnjhd->bnhd", wv, vc))


def attention(q, k, v, ks, vs, window: int, chunk: int, q_block: int, r):
    """(B, S, H, D): the one softmax over (a) and (b), a block of queries
    at a time; the masks are built from i, j and c as written above."""
    B, S, H, D = q.shape
    s = jnp.float32(D) ** -0.5
    j = jnp.arange(S)
    c = jnp.arange(ks.shape[1])
    out = []
    for a in range(0, S, q_block):
        i = jnp.arange(a, min(a + q_block, S))
        qa = q[:, a:a + q_block]
        own = jnp.logical_and(j[None, :] // window == i[:, None] // window,
                              j[None, :] <= i[:, None])
        earlier = c[None, :] // (window // chunk) < i[:, None] // window
        sc = jnp.concatenate([
            jnp.where(own, s * jnp.einsum("bihd,bjhd->bhij", qa, k),
                      -jnp.inf),
            jnp.where(earlier, s * jnp.einsum("bihd,bchd->bhic", qa, ks),
                      -jnp.inf)], axis=-1)
        p = r(jax.nn.softmax(sc, axis=-1))
        out.append(jnp.einsum("bhij,bjhd->bihd", p[..., :S], v)
                   + jnp.einsum("bhic,bchd->bihd", p[..., S:], vs))
    return jnp.concatenate(out, axis=1)


@functools.partial(jax.jit, static_argnames=(
    "heads", "theta", "eps", "window", "chunk", "q_block", "round_to"))
def block(h, w, *, heads, theta, eps, window, chunk, q_block=512,
          round_to=None):
    """One block on the float32 stream h (B, S, hidden), weights (in, out)
    float32, the norms' ``w`` as published (the multiplier is 1 + w)."""
    r = _stored_as(round_to)
    B, S, _ = h.shape
    x = r(rms_norm(h, 1.0 + w["input_layernorm"], eps))
    q = r(x @ w["q_proj"]).reshape(B, S, heads, -1)
    k = r(x @ w["k_proj"]).reshape(B, S, heads, -1)
    v = r(x @ w["v_proj"]).reshape(B, S, heads, -1)
    q, k = r(rope(q, theta)), r(rope(k, theta))
    ks, vs = summaries(k, v, w["adaptive_mu_k"], w["adaptive_phi"], chunk)
    a = r(attention(q, k, v, r(ks), r(vs), window, chunk, q_block, r))
    h = h + r(a.reshape(B, S, -1) @ w["o_proj"])
    x = r(rms_norm(h, 1.0 + w["post_attention_layernorm"], eps))
    return h + r(r(jax.nn.silu(r(x @ w["gate_proj"])) * r(x @ w["up_proj"]))
                 @ w["down_proj"])


def hidden_states(ids, arch: dict, layers: int, embed, layer_weights, norm,
                  round_to=None):
    """The final norm's output over the float32 stream after ``layers``
    blocks."""
    eps = float(arch["rms_norm_eps"])
    r = _stored_as(round_to)
    with jax.default_matmul_precision("highest"):
        h = jnp.asarray(embed)[ids].astype(jnp.float32)
        for li in range(layers):
            w = {k: jnp.asarray(v, jnp.float32)
                 for k, v in layer_weights(li).items()}
            h = block(h, w, heads=int(arch["num_attention_heads"]),
                      theta=float(arch["rope_theta"]), eps=eps,
                      window=int(arch["window_size"]),
                      chunk=int(arch["chunk_size"]), round_to=round_to)
            del w
        return r(rms_norm(h, 1.0 + jnp.asarray(norm, jnp.float32), eps))


def logits(ids, arch: dict, layers: int, embed, layer_weights, norm, head,
           positions=None, round_to=None):
    """Float32 logits (B, S', num_pred_heads * V) of the full forward over
    ``ids`` (B, S); ``positions`` keeps only those sequence positions
    before the head."""
    h = hidden_states(ids, arch, layers, embed, layer_weights, norm,
                      round_to)
    with jax.default_matmul_precision("highest"):
        if positions is not None:
            h = h[:, jnp.asarray(positions)]
        return h @ jnp.asarray(head, jnp.float32)


def layer_weights_by_name(params: dict):
    """``layer_weights(i)`` over a flat dict keyed by the Hugging Face
    names (``model.layers.<i>.self_attn.q_proj.weight`` ...)."""
    def get(i):
        pre = f"model.layers.{i}."
        return {
            **{k: params[f"{pre}{k}.weight"]
               for k in ("input_layernorm", "post_attention_layernorm")},
            **{k: params[f"{pre}self_attn.{k}.weight"]
               for k in ("q_proj", "k_proj", "v_proj", "o_proj")},
            **{k: params[f"{pre}self_attn.{k}"]
               for k in ("adaptive_mu_k", "adaptive_phi")},
            **{k: params[f"{pre}mlp.{k}.weight"]
               for k in ("gate_proj", "up_proj", "down_proj")}}
    return get
