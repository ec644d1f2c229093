"""Adapter of the ``afmoe`` family (Arcee Trinity) for
``runners/serve_model.py``: configuration file -> program config, model
class, reference check (``adapters/README.md``).

The configuration file's ``num_experts`` gives what THIS CHIP holds (its
share of an eight-chip expert-parallel deployment); ``num_experts_published``
is the router's width and ``expert_offset`` the first held expert.
``vocab_size`` stays the published one; ``vocab_rows_held`` is this chip's
vocabulary-parallel slice of embedding and head, and is what the program,
the traffic and the reference see as the vocabulary.
``harness/model.py:arch_of`` refuses this family (a sliding window, a
``head_dim`` that is not ``hidden_size / heads``), so the widths are read
here. ``harness/model.py:rehearsal`` shrinks the Llama keys
only: the family's own sizes are kept as RATIOS to them (expert width to
``intermediate_size``, window to ``max_position_embeddings``), so that a
rehearsal routes and its window (256 / 64 = 4 positions) bites.

The two tolerances of the runner's gates, and what each was set from. The
section states bf16 weights and activations; a token passes 5 blocks whose
outputs are re-normed before they join the residual stream (12 Llama blocks
read 0.042-0.046 under a gate of 0.1, 48 looped ones 0.175-0.225 under 0.6:
PERF.md section 6). What is new here is the ROUTING: with random weights a
token whose fourth and fifth scores lie within bf16's rounding of the
router's input picks another expert in the program than in the float32
reference. Measured on the chip at the published widths (my chip run, PR
33, 4 seeds x 512 positions, the program's prefill against the reference):
7-17 of 512 positions flip one of their four experts in a routed layer
(1.4-3.3 %), 46-57 of 512 (9-11 %) in some layer. A flip costs little: the
flipped positions read at most 0.061-0.076 of a standard deviation where
the others read at most 0.028-0.033 (median 0.022), and with the program's
own selection handed to the reference (``route_override``) every position
reads at most 0.028-0.033 — a dense stack of this depth. The near-tied
pair carries the two smallest of four normalised weights and both experts
are random maps of one input, so exchanging them moves the layer's output
by a fraction that the sandwich norm then scales with the rest.

LOGITS_TOL — max |sys - ref| over the vocabulary / std of the reference's
logits, per position, prefill + ``CHECK_STEPS`` cached decode steps. The
timed decoder's own check (prompts of 64 and 100, 18 positions a run) read
0.0275-0.0529 on the chip (my chip runs, PR 33, 28 seeds over both
sections); over 2048 positions with their flips the largest was 0.0757.
``benchmark/precision_control.py --config trinity-large-preview`` at the
section's depth (on the chip: the reference is float32 under ``highest``
wherever it runs; seeds 2147491101-2) reads bfloat16 0.0280-0.0286,
passing, and float8_e4m3fn, the nearest precision below, 0.285-0.327. 0.15
lies 2.0 times above the largest reading with a flip in it (2.8 times
above the checks' 0.0529) and 1.9 times under the smallest of fp8; a
float32 path reads 1e-6 at a tiny width and a decoder without the window
fails at once (benchmark/tests). The limit was
NOT widened for the flips: they fit under a limit set for rounding.
With these limits in place the control's own verdict, at ``--layers 2``
(one dense + one routed layer, published widths, CPU, seeds 2147495101-3):
bfloat16 0.0197-0.0207 ``ok: true``, float8_e4m3fn 0.230-0.243 ``ok:
false`` in every seed. Past the window (the bucketed admission prefill of
4090 and of 6000 tokens, then decode across the wrap of the rolling
buffers; my chip run, PR 33, 2 seeds) the decoder reads 0.027-0.033.

TIE_ULPS — the engine's greedy token's reference logit within so many bf16
ulps (2**-8 relative) of the reference's maximum. The engine's tokens read
at most 0.0-0.96 ulps on the chip over the 28 seeds, the bfloat16
reference's own 0.0-0.23; the float8 reference's 5.9-12.9 (2.9-7.3 at 2
layers; a token past the window read 1.51). 4 (the Llama gate) lies 2.6
times above 1.51 and 1.5 times under 5.9: the logits limit is the one with
wide room on both sides, and it alone fails the control in every seed —
the tie limit alone would have passed one float8 seed of three at 2 layers.
"""

from __future__ import annotations

import numpy as np

from benchmark.reference import afmoe_block as ref

LOGITS_TOL = 0.15
TIE_ULPS = 4

_PLAIN = ("hidden_size", "intermediate_size", "num_attention_heads",
          "num_key_value_heads", "head_dim",
          "max_position_embeddings", "rope_theta", "rms_norm_eps",
          "tie_word_embeddings", "num_experts_per_tok", "num_shared_experts",
          "route_scale", "route_norm", "mup_enabled")


def arch_of(config_file: dict) -> dict:
    """The widths the runner, the readers and the reference read. A key of
    the published config this path cannot express is refused."""
    c = config_file
    for key, want in (("model_type", "afmoe"), ("hidden_act", "silu"),
                      ("score_func", "sigmoid"), ("n_group", 1),
                      ("topk_group", 1), ("rope_scaling", None)):
        if c.get(key, want) != want:
            raise ValueError(f"the program's AFMoE path has {key} = "
                             f"{want!r} only, not {c[key]!r}")
    arch = {k: c[k] for k in _PLAIN}
    arch["vocab_size"] = int(c["vocab_rows_held"])          # the slice
    arch["num_hidden_layers"] = int(c["num_hidden_layers"])
    arch["num_experts"] = int(c["num_experts_published"])   # router width
    arch["experts_held"] = int(c["num_experts"])
    arch["expert_offset"] = int(c["expert_offset"])
    # the stage this chip runs: its layer kinds, the dense ones leading
    arch["stage_layer_types"] = tuple(c["layer_types"])
    arch["stage_dense_layers"] = int(c["num_dense_layers"])
    arch["moe_ratio"] = c["moe_intermediate_size"] / c["intermediate_size"]
    arch["window_ratio"] = c["sliding_window"] / c["max_position_embeddings"]
    return arch


def at_depth(arch: dict, layers: int) -> dict:
    """``arch`` with the family's sizes at the arch's own (perhaps
    rehearsed) Llama widths, and the stage's layer kinds at a (perhaps
    cut) depth of ``layers``: leading dense layers go first, one routed
    layer always stays."""
    L = int(layers)
    dense = min(arch["stage_dense_layers"], L - 1)
    kinds = arch["stage_layer_types"][arch["stage_dense_layers"] - dense:][:L]
    return {**arch, "num_hidden_layers": L, "num_dense_layers": dense,
            "layer_types": tuple(kinds),
            "moe_intermediate_size": max(8, round(
                arch["intermediate_size"] * arch["moe_ratio"])),
            "sliding_window": max(1, round(
                arch["max_position_embeddings"] * arch["window_ratio"]))}


def program_config(arch: dict, section: dict):
    """The program's ``AfmoeConfig`` at the section's depth and dtype."""
    from paddle_tpu.models.afmoe import AfmoeConfig
    a = at_depth(arch, section["num_hidden_layers"])
    return AfmoeConfig(
        dtype=section["dtype"],
        **{k: a[k] for k in _PLAIN + (
            "vocab_size", "num_hidden_layers", "num_dense_layers",
            "layer_types", "moe_intermediate_size", "sliding_window",
            "num_experts", "experts_held", "expert_offset")})


def build_model(cfg):
    """Born in the section's dtype (the runner's ``model.to`` finds nothing
    to cast): a float32 copy of this share would not fit the chip."""
    from paddle_tpu.models.afmoe import AfmoeForCausalLM
    return AfmoeForCausalLM(cfg)


def layer_weights_from_decoder(params: dict, a: dict):
    """The reference's ``layer_weights(i)`` by published names over a
    ``LlamaDecoder``'s parameters: q|k|v|gate and gate|up split back, the
    experts' stacks one expert at a time."""
    D = a["head_dim"]
    hq, hk = a["num_attention_heads"] * D, a["num_key_value_heads"] * D

    def halves(gu):
        f = gu.shape[-1] // 2
        return gu[..., :f], gu[..., f:]

    def get(i):
        pre = f"model.layers.{i}."
        qkv = params[pre + "self_attn.qkv.weight"]
        w = {"input_layernorm": params[pre + "input_layernorm.weight"],
             "post_attention_layernorm":
                 params[pre + "input_layernorm_2.weight"],
             "pre_mlp_layernorm":
                 params[pre + "post_attention_layernorm.weight"],
             "post_mlp_layernorm":
                 params[pre + "post_attention_layernorm_2.weight"],
             "self_attn.q_proj": qkv[:, :hq],
             "self_attn.k_proj": qkv[:, hq:hq + hk],
             "self_attn.v_proj": qkv[:, hq + hk:hq + 2 * hk],
             "self_attn.gate_proj": qkv[:, hq + 2 * hk:],
             "self_attn.o_proj": params[pre + "self_attn.o_proj.weight"],
             "self_attn.q_norm": params[pre + "self_attn.q_norm.weight"],
             "self_attn.k_norm": params[pre + "self_attn.k_norm.weight"]}
        if pre + "mlp.router.weight" not in params:
            g, u = halves(params[pre + "mlp.gate_up.weight"])
            w.update({"mlp.gate_proj": g, "mlp.up_proj": u,
                      "mlp.down_proj": params[pre + "mlp.down_proj.weight"]})
            return w
        g, u = halves(params[pre + "mlp.shared_experts.gate_up.weight"])
        gu, dn = (params[pre + "mlp.experts_gate_up"],
                  params[pre + "mlp.experts_down"])
        w.update({
            "mlp.router.gate": params[pre + "mlp.router.weight"],
            "mlp.expert_bias": params[pre + "mlp.expert_bias"],
            "mlp.shared_experts.gate_proj": g,
            "mlp.shared_experts.up_proj": u,
            "mlp.shared_experts.down_proj":
                params[pre + "mlp.shared_experts.down_proj.weight"],
            "mlp.experts": lambda e: halves(gu[e]) + (dn[e],)})
        return w
    return get


def reference_logits(params: dict, arch: dict, layers: int, ids, positions,
                     round_to=None, route_override=None,
                     record=None) -> np.ndarray:
    """The float32 reference's logits (S', V) of one sequence ``ids``
    (1, S) at ``positions``, over the decoder's own parameters and this
    chip's share. ``round_to``: for ``benchmark/precision_control.py``.
    ``route_override`` / ``record``: the reference's own (a selection in
    the place of its own; its own handed out)."""
    a = at_depth(arch, layers)
    return np.asarray(ref.logits(
        ids, a, layers, params["model.embed_tokens.weight"],
        layer_weights_from_decoder(params, a),
        params["model.norm.weight"], params["lm_head.weight"],
        positions=positions, round_to=round_to,
        route_override=route_override, record=record)[0], np.float32)


def program_routes(dec, ids) -> dict:
    """{layer: (1, S, K) experts the PROGRAM's prefill chose for ``ids``
    (1, S)}: what ``route_override`` takes to read the comparison without
    the flips of near-tied scores."""
    import jax.numpy as jnp

    from paddle_tpu.inference.generate import _forward_cached
    seen = []
    kc, vc = dec._empty_cache(1)
    _forward_cached(dec.params, dec.cfg, jnp.asarray(ids, jnp.int32), kc, vc,
                    0, dec.max_len, moe_stats=seen)
    routed = range(dec.cfg.num_dense_layers, dec.cfg.num_hidden_layers)
    return {li: np.asarray(sel)[None] for li, (_, sel) in zip(routed, seen)}
