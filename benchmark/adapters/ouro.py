"""Adapter of the ``ouro`` family for ``runners/serve_model.py``: the three
steps of a serving run that know the model — configuration file ->
program config, model class, reference check (``adapters/README.md``).

The two tolerances of the runner's gates, and what each was set from. The
section states bf16 weights and activations, and a token passes 48 block
applications (12 layers, four passes; Mistral's section, gate 0.1, has 12)
whose outputs are re-normed before they join the residual stream, which is
itself re-normed after every pass: bf16's rounding adds up over the blocks,
slower than linearly (the control below reads 0.046 at 4 blocks, 0.054 at 8
and 0.196 at 48; 12 Llama blocks read 0.042-0.046, PERF.md section 6).

Each limit lies between two readings. The upper one, and a witness for the
lower, are ``benchmark/precision_control.py --config ouro-2.6b``'s: the
float32 reference, its activations stored in a dtype, in the decoder's place
in ``serve_model._check_against_reference`` with the tokens it decodes
itself (CPU, published widths, 12 layers x 4 passes, seeds 2147484001-3;
PERF.md section 6). ``benchmark/tests`` run it at 2 layers.

LOGITS_TOL — max |sys - ref| over the vocabulary / std of the reference's
logits, per position, prefill + ``CHECK_STEPS`` cached decode steps. The
decoder on the chip at the published widths read 0.175-0.225 (my chip runs,
PR 28, 18 seeds), the reference with bfloat16 activations 0.196-0.238:
that is bf16, not a fault. With float8_e4m3fn activations, the nearest
precision below, it reads 2.90-3.15. 0.6 lies 2.5 times above the
largest reading of program and witness and 4.8 times under the smallest
of fp8. A float32 path reads 1e-6 at a tiny width (benchmark/tests), a
decoder whose pass t attends over pass t-1's keys 1.9-3.4.

TIE_ULPS — the engine's greedy token's reference logit within so many bf16
ulps (2**-8 relative) of the reference's maximum. With logits 0.2 of a
standard deviation off, a near-tie flips further from the maximum than on
a Llama-deep stack (gate 4 there): the engine's tokens on the chip read at
most 1.66-9.05 ulps over the 18 seeds, the bfloat16 reference's own
2.39-6.94; the float8 reference's 89-139, the wrong-pass
decoder's 85-170. 24 lies 2.7 times above 9.05 and 3.7
times under the smallest of fp8.
"""

from __future__ import annotations

import numpy as np

from benchmark.harness import model as mdl
from benchmark.reference import ouro_block as ref

LOGITS_TOL = 0.6
TIE_ULPS = 24


def arch_of(config_file: dict) -> dict:
    """``harness/model.py``'s widths, plus the loop. The catalog's keys
    this path cannot express are refused, not ignored."""
    arch = mdl.arch_of(config_file)
    arch["total_ut_steps"] = int(config_file["total_ut_steps"])
    arch["early_exit_threshold"] = float(config_file["early_exit_threshold"])
    if config_file.get("rope_scaling") is not None:
        raise ValueError("the program's decoder has no rope scaling")
    if config_file.get("use_sliding_window") or set(
            config_file.get("layer_types", ())) - {"full_attention"}:
        raise ValueError("the program's decoder is full attention only")
    return arch


def program_config(arch: dict, section: dict):
    """The program's ``OuroConfig`` at the section's depth and dtype."""
    from paddle_tpu.models.ouro import OuroConfig
    base = mdl.llama_config(arch, section)
    return OuroConfig(**vars(base),
                      total_ut_steps=arch["total_ut_steps"],
                      early_exit_threshold=arch["early_exit_threshold"])


def build_model(cfg):
    from paddle_tpu.models.ouro import OuroForCausalLM
    return OuroForCausalLM(cfg)


def layer_weights_from_decoder(params: dict, arch: dict):
    """The Llama split of the decoder's fused q|k|v and gate|up
    (``harness/model.py``), with the two norms a sandwich block adds."""
    plain = mdl.layer_weights_from_decoder(params, arch)

    def get(i):
        pre = f"model.layers.{i}."
        return {**plain(i),
                "input_layernorm_2": params[pre + "input_layernorm_2.weight"],
                "post_attention_layernorm_2":
                    params[pre + "post_attention_layernorm_2.weight"]}
    return get


def reference_logits(params: dict, arch: dict, layers: int, ids,
                     positions, round_to=None) -> np.ndarray:
    """The float32 reference's logits (S', V) of one sequence ``ids``
    (1, S) at ``positions``, over the decoder's own parameters.
    ``round_to`` is for ``benchmark/precision_control.py`` alone: the
    reference with its activations stored in that dtype."""
    return np.asarray(ref.logits(
        ids, arch, layers, params["model.embed_tokens.weight"],
        layer_weights_from_decoder(params, arch),
        params["model.norm.weight"], params["lm_head.weight"],
        positions=positions, round_to=round_to)[0], np.float32)
