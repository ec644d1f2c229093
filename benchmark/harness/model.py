"""From a configuration file to the program's own model objects.

The one place where Hugging Face config keys meet ``LlamaConfig``; a key
the program's Llama path cannot express is an error, never ignored.
"""

from __future__ import annotations


def arch_of(config_file: dict) -> dict:
    keys = ("hidden_size", "intermediate_size", "num_hidden_layers",
            "num_attention_heads", "num_key_value_heads", "vocab_size",
            "max_position_embeddings", "rope_theta", "rms_norm_eps",
            "tie_word_embeddings")
    arch = {k: config_file[k] for k in keys}
    arch["head_dim"] = config_file.get(
        "head_dim", arch["hidden_size"] // arch["num_attention_heads"])
    if arch["head_dim"] * arch["num_attention_heads"] != arch["hidden_size"]:
        raise ValueError("LlamaConfig derives head_dim from hidden_size / "
                         "heads; this configuration's differs")
    if config_file.get("sliding_window") is not None:
        raise ValueError("the program's Llama path has no sliding window")
    if config_file.get("hidden_act", "silu") != "silu":
        raise ValueError("the program's Llama path is SwiGLU (silu) only")
    return arch


def llama_config(arch: dict, section: dict):
    """The program's ``LlamaConfig`` at the section's depth and dtype."""
    from paddle_tpu.models.llama import LlamaConfig
    return LlamaConfig(
        vocab_size=arch["vocab_size"], hidden_size=arch["hidden_size"],
        intermediate_size=arch["intermediate_size"],
        num_hidden_layers=int(section["num_hidden_layers"]),
        num_attention_heads=arch["num_attention_heads"],
        num_key_value_heads=arch["num_key_value_heads"],
        max_position_embeddings=arch["max_position_embeddings"],
        rms_norm_eps=arch["rms_norm_eps"], rope_theta=arch["rope_theta"],
        tie_word_embeddings=arch["tie_word_embeddings"],
        dtype=section["dtype"])


# The tiny width of --rehearse: the benchmark's own tests only. The GQA
# ratio of the configuration is kept, so the same routes are taken.
def rehearsal(arch: dict, section: dict, mix: dict) -> tuple:
    heads = 4
    kv = max(1, heads * arch["num_key_value_heads"]
             // arch["num_attention_heads"])
    arch = {**arch, "hidden_size": 64, "intermediate_size": 128,
            "num_attention_heads": heads, "num_key_value_heads": kv,
            "head_dim": 16, "vocab_size": 256,
            "max_position_embeddings": 256}
    section = {**section, "num_hidden_layers": 1}
    mix = dict(mix)
    if "max_len" in section:
        section.update(max_len=128, num_slots=4, chunk_size=4)
        mix["prompt_len"] = {"dist": "uniform", "min": 6, "max": 60}
        mix["output_len"] = {"dist": "uniform", "min": 8, "max": 16}
        mix["warm_seconds"], mix["drain_seconds"] = 0.5, 20.0
        if "rate_per_s" in mix:
            mix["rate_per_s"] = 20.0
    else:
        mix.update(batch=2, seq=64, pool=2, warm_steps=1, trace_steps=2)
    return arch, section, mix


def state_arrays(model) -> dict:
    """The model's weights as plain arrays by their names."""
    return {name: t.value for name, t in model.state_dict().items()}


def layer_weights_from_decoder(params: dict, arch: dict):
    """``layer_weights(i)`` for the reference over a ``LlamaDecoder``'s
    parameters, which hold q|k|v and gate|up concatenated along the output
    axis (``_build_params``): split back, one layer at a time, so that what
    the reference sees is what the decoder serves."""
    hq = arch["num_attention_heads"] * arch["head_dim"]
    hk = arch["num_key_value_heads"] * arch["head_dim"]
    ffn = arch["intermediate_size"]

    def get(i):
        pre = f"model.layers.{i}."
        qkv = params[pre + "self_attn.qkv.weight"]
        gu = params[pre + "mlp.gate_up.weight"]
        return {
            "input_layernorm": params[pre + "input_layernorm.weight"],
            "post_attention_layernorm":
                params[pre + "post_attention_layernorm.weight"],
            "q_proj": qkv[:, :hq], "k_proj": qkv[:, hq:hq + hk],
            "v_proj": qkv[:, hq + hk:],
            "o_proj": params[pre + "self_attn.o_proj.weight"],
            "gate_proj": gu[:, :ffn], "up_proj": gu[:, ffn:],
            "down_proj": params[pre + "mlp.down_proj.weight"]}
    return get
