"""Adapter of the ``evabyte`` family (EvaByte 6.5B, EVA attention) for
``runners/serve_model.py`` / ``runners/serve_model_ctx.py``: configuration
file -> program config, model class, reference check (``adapters/README.md``
says what an adapter is, ``adapters/EVABYTE.md`` what this one hands over).

``harness/model.py:arch_of`` would take this family's Llama keys and drop
its own (``window_size``, ``chunk_size``, ``num_pred_heads``), so the widths
are read here; a key of the published config the program's EVA path cannot
express is refused, never ignored. ``harness/model.py:rehearsal`` lays only
the Llama widths over the arch: a rehearsal keeps the window of 2048 under
a ``max_len`` of 128 and never crosses it (it proves the plumbing); the
crossing at a tiny width is ``benchmark/tests/test_evabyte_cell.py``'s,
which hands this adapter an arch of its own.

The decoder's parameters hold each norm's multiplier ``1 + w`` (folded at
build time), q|k|v and gate|up fused, and a head of ``num_pred_heads``
vocabularies of which the runner's gates see the first: the next byte's
logits, columns ``[0, vocab_size)``, which are also what the decoder
returns. The reference is handed ``w`` (the multiplier less one, exact at
the seeded ``w = 0``) and the whole head.

The two tolerances of the runner's gates, and what each was set from, are
written above them below.
"""

from __future__ import annotations

import numpy as np

from benchmark.reference import evabyte_block as ref

# LOGITS_TOL — max |sys - ref| over the 320 next-byte logits / std of the
# reference's logits there, per position, prefill + CHECK_STEPS cached
# decode steps; the check's prompts are 2044 and 4100 positions
# (``traffic/longdoc-backlog.json``: ``check_prompt_lens``), so the compared
# steps cross a window's end, and the second prefill reads summaries. The
# section states bf16 weights and activations over a float32 residual
# stream; a token passes 8 pre-norm blocks.
# Readings (PERF.md section 6, PR 36):
#   the timed decoder on the chip, 19 runs of the cell, each its own seed,
#   both prompts: 0.0151-0.0213 (my chip runs, PR 36, both sessions);
#   ``precision_control.py --config evabyte-6.5b`` on the chip at the
#   section's depth (its own prompts of 64 and 100; seed 2147493603):
#   bfloat16 0.0156 passing, float8_e4m3fn — the nearest precision below —
#   0.274 failing; the same control at the cell's lengths (``readings(...,
#   prompts=[2044, 4100])``, seed 2147493631): bfloat16 0.0112 passing,
#   float8 1.69 failing (a float8 summary of 16 float8 keys, read by every
#   later window, carries the rounding across the window's end).
# 0.1 (the Llama gate's value) lies 4.7 times above the largest reading of
# the program and 2.7 times under the smaller reading of float8: room on
# both sides. A float32 path reads 1e-6 at a tiny width, and against a
# reference whose window ends elsewhere or whose chunks are longer the same
# decoder reads O(1) (benchmark/tests/test_evabyte_cell.py).
LOGITS_TOL = 0.1
# TIE_ULPS — the engine's greedy byte's reference logit within so many bf16
# ulps (2**-8 relative) of the reference's maximum.
# Over 320 logits near-ties are rarer than over a subword vocabulary: the
# engine's bytes read at most 0.0-0.82 ulps on the chip over the 9 runs, the
# bfloat16 reference's own 0.0 in both controls, the float8 reference's 13.9
# (prompts of 64 and 100) and 92.8 (2044 and 4100). 4 (the Llama gate) lies
# 4.9 times above 0.82 and 3.5 times under 13.9; either limit alone fails
# the control, in both of its forms.
TIE_ULPS = 4

_PLAIN = ("hidden_size", "intermediate_size", "num_attention_heads",
          "num_key_value_heads", "vocab_size", "max_position_embeddings",
          "rope_theta", "rms_norm_eps", "tie_word_embeddings",
          "window_size", "chunk_size", "num_pred_heads")


def arch_of(config_file: dict) -> dict:
    """The widths the runner, the readers and the reference read."""
    c = config_file
    for key, want in (("model_type", "evabyte"), ("attention_class", "eva"),
                      ("hidden_act", "silu"), ("attention_bias", False),
                      ("num_chunks", None), ("rope_scaling", None),
                      ("norm_add_unit_offset", True), ("fp32_ln", False),
                      ("fp32_skip_add", True), ("fp32_logits", True),
                      ("tie_word_embeddings", False)):
        if c.get(key, want) != want:
            raise ValueError(f"the program's EvaByte path has {key} = "
                             f"{want!r} only, not {c[key]!r}")
    if c["num_key_value_heads"] != c["num_attention_heads"]:
        raise ValueError("EVA pools keys per query head: "
                         "num_key_value_heads must equal num_attention_heads")
    if c["window_size"] % c["chunk_size"]:
        raise ValueError("window_size is not whole chunks of chunk_size")
    arch = {k: c[k] for k in _PLAIN}
    arch["num_hidden_layers"] = int(c["num_hidden_layers"])
    arch["head_dim"] = c["hidden_size"] // c["num_attention_heads"]
    return arch


def program_config(arch: dict, section: dict):
    """The program's ``EvabyteConfig`` at the section's depth and dtype."""
    from paddle_tpu.models.evabyte import EvabyteConfig
    return EvabyteConfig(
        dtype=section["dtype"],
        num_hidden_layers=int(section["num_hidden_layers"]),
        **{k: arch[k] for k in _PLAIN})


def build_model(cfg):
    from paddle_tpu.models.evabyte import EvabyteForCausalLM
    return EvabyteForCausalLM(cfg)


def layer_weights_from_decoder(params: dict, arch: dict):
    """The reference's ``layer_weights(i)`` by published names over a
    ``LlamaDecoder``'s parameters: q|k|v and gate|up split back, each
    norm's ``w`` from the folded ``1 + w``."""
    hq = arch["num_attention_heads"] * arch["head_dim"]
    ffn = arch["intermediate_size"]

    def w_of(folded):
        return np.asarray(folded, np.float32) - 1.0

    def get(i):
        pre = f"model.layers.{i}."
        qkv = params[pre + "self_attn.qkv.weight"]
        gu = params[pre + "mlp.gate_up.weight"]
        return {
            "input_layernorm": w_of(params[pre + "input_layernorm.weight"]),
            "post_attention_layernorm":
                w_of(params[pre + "post_attention_layernorm.weight"]),
            "q_proj": qkv[:, :hq], "k_proj": qkv[:, hq:2 * hq],
            "v_proj": qkv[:, 2 * hq:],
            "o_proj": params[pre + "self_attn.o_proj.weight"],
            "adaptive_mu_k": params[pre + "self_attn.adaptive_mu_k"],
            "adaptive_phi": params[pre + "self_attn.adaptive_phi"],
            "gate_proj": gu[:, :ffn], "up_proj": gu[:, ffn:],
            "down_proj": params[pre + "mlp.down_proj.weight"]}
    return get


def reference_logits(params: dict, arch: dict, layers: int, ids, positions,
                     round_to=None) -> np.ndarray:
    """The float32 reference's next-byte logits (S', vocab_size) of one
    sequence ``ids`` (1, S) at ``positions``, over the decoder's own
    parameters. ``round_to``: for ``benchmark/precision_control.py``."""
    lg = ref.logits(
        ids, arch, layers, params["model.embed_tokens.weight"],
        layer_weights_from_decoder(params, arch),
        np.asarray(params["model.norm.weight"], np.float32) - 1.0,
        params["lm_head.weight"], positions=positions, round_to=round_to)
    return np.asarray(lg[0, :, :int(arch["vocab_size"])], np.float32)
