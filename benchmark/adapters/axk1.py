"""Adapter of the ``axk1`` family (SK Telecom A.X-K1: DeepSeek-V3's block,
multi-head latent attention and group-limited sigmoid routing) for
``runners/serve_model.py``: configuration file -> program config, model
class, reference check (``adapters/README.md``).

The configuration file's ``n_routed_experts`` gives what THIS CHIP holds
(its share of a sixteen-chip expert-parallel deployment);
``n_routed_experts_published`` is the router's width and ``expert_offset``
the first held expert. ``vocab_size`` stays the published one;
``vocab_rows_held`` is this chip's vocabulary-parallel slice of embedding
and head, and is what the program, the traffic and the reference see as the
vocabulary. ``harness/model.py:arch_of`` refuses this family (its
``head_dim`` is not ``hidden_size / heads``), so the widths are read here.
``harness/model.py:rehearsal`` shrinks the Llama keys only: the family's
own sizes are kept as RATIOS to them (the latent attention's widths to the
head size, the ranks to the hidden size, the expert width to
``intermediate_size``; the group counts as published), so that a rehearsal
takes the same routes: 8 groups of which 4 are kept, a rope part that is
rotated.

The cell's runner is ``runners/serve_model_routed.py``: the runner's two
gates through ``check_against_reference`` below, which reads them without
the routing's near-ties. With random weights a token whose eighth and ninth
scores (or fourth and fifth groups) lie within bf16's rounding of the
router's input picks another expert in the program than in the float32
reference; at the published widths 3.9 % of positions of a routed layer
do, 0.6 % with a held expert, and a flipped held expert moves the logits
by 0.4-0.63 of a standard deviation (CPU, 1 dense + 1 routed layer: PERF.md
section 6), where storing the activations in float8 moves them by
0.77. No limit set from rounding separates the two, so the reference
follows the program's choices and each choice is judged against the scores
that reference selected on (``route_margin``, within ``NEAR_TIE``); the
logits limits were NOT widened for the flips.

The three limits, and what each was set from (PERF.md section 6).
The section states bf16 weights and activations; a token passes 5 pre-norm
blocks, one dense and four routed. "The control" is the plain reference
with its activations stored in float8_e4m3fn, the nearest precision below:
``benchmark/precision_control.py --config ax-k1`` (the runner's plain
check), and the same rounded reference put through THIS check (its own
routes handed to the float32 reference and judged there).

LOGITS_TOL — max |sys - ref| over the vocabulary / std of the reference's
logits, per position, prefill + ``CHECK_STEPS`` cached decode steps, at the
check's prompts (the traffic's ``check_prompt_lens``, 2100 and 4100). The
program read 0.0426-0.0437 on the chip at 5 layers (the cell's check, three
seeds), and 0.040 at a 4100-token prompt, where the bfloat16 reference read
0.042 and the control 0.549 (this check, on the chip); at 2112 / 4160 tokens
0.039-0.045 (eight seeds); at 1 dense + 1 routed layer (published widths,
CPU) 0.0226, the bfloat16 reference 0.0235 (both checks). The control read
0.758 through precision_control (2 layers, CPU) and 0.371 through this
check. 0.15 lies 3.4 times above the largest reading and 2.5 times under
the smallest of the control.

TIE_ULPS — the engine's greedy token's reference logit within so many bf16
ulps (2**-8 relative) of the reference's maximum. The engine's tokens read
0.0-0.79 ulps on the chip (eight seeds); the control 20.1 (precision_control,
2 layers, CPU). 4, the Llama gate, lies 5 times above and 5 times under.

NEAR_TIE — below.
"""

from __future__ import annotations

import numpy as np

from benchmark.reference import axk1_block as ref

LOGITS_TOL = 0.15
TIE_ULPS = 4

_PLAIN = ("hidden_size", "intermediate_size", "num_attention_heads",
          "num_key_value_heads", "max_position_embeddings", "rope_theta",
          "rms_norm_eps", "tie_word_embeddings", "num_experts_per_tok",
          "n_shared_experts", "n_group", "topk_group", "norm_topk_prob",
          "routed_scaling_factor", "rope_scaling")
# the latent attention's widths, kept in proportion to the head size
_HEAD = ("qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim")


def arch_of(config_file: dict) -> dict:
    """The widths the runner, the readers and the reference read. A key of
    the published config this path cannot express is refused."""
    c = config_file
    for key, want in (("model_type", "axk1"), ("hidden_act", "silu"),
                      ("scoring_func", "sigmoid"), ("moe_layer_freq", 1),
                      ("attention_bias", False)):
        if c.get(key, want) != want:
            raise ValueError(f"the program's A.X-K1 path has {key} = "
                             f"{want!r} only, not {c[key]!r}")
    if c.get("topk_method") not in ("none", "noaux_tc") \
            or (c.get("rope_scaling") or {}).get("type", "yarn") != "yarn":
        raise ValueError("the program's A.X-K1 path has no selection-"
                         "correction bias and YaRN rope scaling only")
    arch = {k: c[k] for k in _PLAIN}
    arch["vocab_size"] = int(c["vocab_rows_held"])          # the slice
    arch["num_hidden_layers"] = int(c["num_hidden_layers"])
    arch["head_dim"] = c["qk_nope_head_dim"] + c["qk_rope_head_dim"]
    arch["head_published"] = arch["head_dim"]
    arch["n_routed_experts"] = int(c["n_routed_experts_published"])
    arch["experts_held"] = int(c["n_routed_experts"])
    arch["expert_offset"] = int(c["expert_offset"])
    arch["stage_dense_layers"] = int(c["first_k_dense_replace"])
    # what adapters/afmoe.py:at_depth reads, which the accepted reader of
    # moe_experts_touched calls to count the routed layers: every layer
    # attends over all of the past (no window), the dense one leading
    arch["stage_layer_types"] = ("full_attention",) * int(
        c["num_hidden_layers"])
    arch["moe_ratio"] = c["moe_intermediate_size"] / c["intermediate_size"]
    arch["window_ratio"] = 1.0
    arch["published"] = {k: int(c[k]) for k in _HEAD + (
        "q_lora_rank", "kv_lora_rank", "moe_intermediate_size",
        "hidden_size", "intermediate_size")}
    return arch


def at_depth(arch: dict, layers: int) -> dict:
    """``arch`` with the family's sizes at the arch's own (perhaps
    rehearsed) widths, and the stage at a (perhaps cut) depth of
    ``layers``: the leading dense layer first, one routed layer always
    stays. At the published widths every size is the published one."""
    pub, L = arch["published"], int(layers)
    f = arch["head_dim"] / arch["head_published"]
    fh = arch["hidden_size"] / pub["hidden_size"]
    rope = max(2, 2 * round(pub["qk_rope_head_dim"] * f / 2))
    return {**arch, "num_hidden_layers": L,
            "first_k_dense_replace": min(arch["stage_dense_layers"], L - 1),
            "qk_rope_head_dim": rope,
            "qk_nope_head_dim": arch["head_dim"] - rope,
            "v_head_dim": max(2, round(pub["v_head_dim"] * f)),
            "q_lora_rank": max(8, round(pub["q_lora_rank"] * fh)),
            "kv_lora_rank": max(8, round(pub["kv_lora_rank"] * fh)),
            "moe_intermediate_size": max(8, round(
                pub["moe_intermediate_size"] * arch["intermediate_size"]
                / pub["intermediate_size"]))}


_PROGRAM = _PLAIN + ("vocab_size", "num_hidden_layers",
                     "first_k_dense_replace", "qk_rope_head_dim",
                     "qk_nope_head_dim", "v_head_dim", "q_lora_rank",
                     "kv_lora_rank", "moe_intermediate_size",
                     "n_routed_experts", "experts_held", "expert_offset")


def program_config(arch: dict, section: dict):
    """The program's ``AxK1Config`` at the section's depth and dtype."""
    from paddle_tpu.models.axk1 import AxK1Config
    a = at_depth(arch, section["num_hidden_layers"])
    return AxK1Config(dtype=section["dtype"], **{k: a[k] for k in _PROGRAM})


def build_model(cfg):
    """Born in the section's dtype: a float32 copy of this share would not
    fit the chip."""
    from paddle_tpu.models.axk1 import AxK1ForCausalLM
    return AxK1ForCausalLM(cfg)


def layer_weights_from_decoder(params: dict, a: dict):
    """The reference's ``layer_weights(i)`` by published names over a
    ``LlamaDecoder``'s parameters: the fused q_a|kv_a and gate|up split
    back, the experts' stacks one expert at a time."""
    Q = a["q_lora_rank"]

    def halves(gu):
        f = gu.shape[-1] // 2
        return gu[..., :f], gu[..., f:]

    def get(i):
        pre = f"model.layers.{i}."
        at = pre + "self_attn."
        qa = params[at + "qa_kva.weight"]
        w = {"input_layernorm": params[pre + "input_layernorm.weight"],
             "post_attention_layernorm":
                 params[pre + "post_attention_layernorm.weight"],
             "self_attn.q_a_proj": qa[:, :Q],
             "self_attn.kv_a_proj_with_mqa": qa[:, Q:]}
        for k in ("q_a_layernorm", "q_b_proj", "kv_a_layernorm",
                  "kv_b_proj", "o_proj"):
            w["self_attn." + k] = params[f"{at}{k}.weight"]
        if pre + "mlp.router.weight" not in params:
            g, u = halves(params[pre + "mlp.gate_up.weight"])
            w.update({"mlp.gate_proj": g, "mlp.up_proj": u,
                      "mlp.down_proj": params[pre + "mlp.down_proj.weight"]})
            return w
        g, u = halves(params[pre + "mlp.shared_experts.gate_up.weight"])
        gu, dn = (params[pre + "mlp.experts_gate_up"],
                  params[pre + "mlp.experts_down"])
        w.update({
            "mlp.gate": params[pre + "mlp.router.weight"],
            "mlp.gate.bias": params[pre + "mlp.expert_bias"],
            "mlp.shared_experts.gate_proj": g,
            "mlp.shared_experts.up_proj": u,
            "mlp.shared_experts.down_proj":
                params[pre + "mlp.shared_experts.down_proj.weight"],
            "mlp.experts": lambda e: halves(gu[e]) + (dn[e],)})
        return w
    return get


def reference_logits(params: dict, arch: dict, layers: int, ids, positions,
                     round_to=None, route_override=None, record=None,
                     scores=None) -> np.ndarray:
    """The float32 reference's logits (S', V) of one sequence ``ids``
    (1, S) at ``positions``, over the decoder's own parameters and this
    chip's share. ``round_to``: for ``benchmark/precision_control.py``.
    ``route_override`` / ``record`` / ``scores``: the reference's own."""
    a = at_depth(arch, layers)
    return np.asarray(ref.logits(
        ids, a, layers, params["model.embed_tokens.weight"],
        layer_weights_from_decoder(params, a),
        params["model.norm.weight"], params["lm_head.weight"],
        positions=positions, round_to=round_to,
        route_override=route_override, record=record, scores=scores)[0],
        np.float32)


# The score margin by which the program's routing may differ from a
# group-limited top-k of the reference's scores (``route_margin``): two
# bf16 ulps of a score in [0.5, 1). The program routes on bf16
# activations, the reference on float32 ones; where the eighth and ninth
# scores (or the fourth and fifth groups) lie closer than the two differ,
# either choice is the model's. Readings, the largest margin a choice of
# the program needed, over every position: on the chip at 5 layers,
# 0.0044-0.0067 in the cell's check at 2100 / 4100 tokens (three seeds), and
# at a 4100-token sequence 0.0062, the bfloat16 reference 0.0056, the
# control 0.080 (479-1792 positions a routed layer past the limit); at a
# 2136-token sequence 0.0048 / 0.0049 / 0.104; at 1 dense + 1 routed layer
# (published widths, CPU, 536 tokens) 0.0010 / 0.0009 / 0.037; at 5 layers
# and a width of 1024 (CPU) 0.0034 / 0.0040 / 0.071. (These were read
# while a choice was measured against the K-th best score of its groups,
# which is the best one left out wherever a choice differs from the own by
# one swap.) Judged against the scores of a reference that follows the
# program's choices; against the reference's own, a flip in one layer
# moves the next layer's scores at that position by a held expert's output
# and the same program read 0.04-0.09.
NEAR_TIE = 2.0 ** -7


def own_choice(scores, arch: dict, K: int = None) -> np.ndarray:
    """(S, K): the group-limited top-k of the selection scores (S, E), as
    the reference takes it (a group scores its two best)."""
    S, E = scores.shape
    G, kept_n = int(arch["n_group"]), int(arch["topk_group"])
    K = int(arch["num_experts_per_tok"]) if K is None else K
    gs = np.sort(scores.reshape(S, G, E // G), -1)[..., -2:].sum(-1)
    kept = np.argsort(-gs, -1, kind="stable")[:, :kept_n]
    own = np.where((np.arange(E)[None, :, None] // (E // G)
                    == kept[:, None, :]).any(-1), scores, -np.inf)
    return np.argsort(-own, -1, kind="stable")[:, :K]


def route_margin(prog, scores, arch: dict) -> np.ndarray:
    """Per position, the least ``eps`` within which the choice ``prog``
    (S, K) is a group-limited top-k of the selection scores ``scores`` (S,
    E): K distinct experts that lie in ``topk_group`` groups, no group left
    out scoring more than ``eps`` above the worst group kept (a group
    scores its two best), and no expert of the kept groups left out
    scoring more than ``eps`` above the worst one chosen. 0 where ``prog``
    is the scores' own choice; inf where it names an expert twice or spans
    too many groups."""
    import itertools
    S, E = scores.shape
    G, kept_n = int(arch["n_group"]), int(arch["topk_group"])
    K, per = prog.shape[1], E // G
    c = scores.reshape(S, G, per)
    gs = np.sort(c, -1)[..., -2:].sum(-1)                    # (S, G)
    own = own_choice(scores, arch, K)
    need = np.zeros(S)
    for i in np.nonzero((np.sort(prog, -1) != np.sort(own, -1)).any(-1))[0]:
        mine = set((prog[i] // per).tolist())
        if len(set(prog[i].tolist())) < K or len(mine) > kept_n:
            need[i] = np.inf
            continue
        low = scores[i][prog[i]].min()
        left = scores[i].copy()
        left[prog[i]] = -np.inf                     # the experts left out
        left = left.reshape(G, per).max(-1)
        rest = sorted(set(range(G)) - mine)

        def need_of(ks):
            out = [g for g in range(G) if g not in ks]
            return max(gs[i][out].max() - gs[i][ks].min() if out else 0.0,
                       left[ks].max() - low, 0.0)
        need[i] = min(
            need_of(sorted(mine | set(extra)))
            for extra in itertools.combinations(rest, kept_n - len(mine)))
    return need


def near_tie_choices(choice, scores, arch: dict) -> list:
    """The choices other than ``choice`` (K,) that a rounding of the
    selection scores ``scores`` (E,) could make: the scores' own
    group-limited top-k, and ``choice`` with one expert swapped for one of
    the best scored others, each within ``NEAR_TIE`` of a group-limited
    top-k of ``scores`` (``route_margin``)."""
    K = len(choice)
    order = np.argsort(-scores, kind="stable")
    mine = set(choice.tolist())
    rest = [e for e in order[:K + 24] if e not in mine]
    alts = [np.sort(np.where(choice == o, e, choice))
            for o in choice for e in rest]
    own = np.sort(own_choice(scores[None], arch, K)[0])
    if not np.array_equal(own, np.sort(choice)):
        alts.append(own)
    if not alts:
        return []
    need = route_margin(np.stack(alts), np.repeat(scores[None], len(alts),
                                                   0), arch)
    seen, out = set(), []
    for a, n in zip(alts, need):
        if n <= NEAR_TIE and a.tobytes() not in seen:
            seen.add(a.tobytes())
            out.append(a)
    return out


# the most reference forwards the tie gate makes to read one check's served
# tokens under near-tie routes (check_against_reference): a program whose
# tokens are wrong at many positions fails without all of them
_TIE_TRIES = 32


def _program(dec):
    """The decoder's own forward, jitted as a prefill from position 0 and
    as one decode step, each also returning what every routed layer chose
    (routed layers, rows x positions, K)."""
    import functools

    import jax
    import jax.numpy as jnp

    from paddle_tpu.inference.generate import _forward_cached

    def fwd(p, ids, kc, vc, pos, from_zero):
        seen = []
        lg, kc, vc = _forward_cached(p, dec.cfg, ids, kc, vc, pos,
                                     dec.max_len, moe_stats=seen,
                                     from_zero=from_zero)
        return lg, kc, vc, jnp.stack([c for _, c in seen])
    return (jax.jit(functools.partial(fwd, pos=0, from_zero=True)),
            jax.jit(functools.partial(fwd, from_zero=False)))


def check_against_reference(dec, adapter, arch, layers, seqs,
                            prompt_lens) -> dict:
    """``runners/serve_model.py``'s two gates, read without the routing's
    near-ties. The program's logits (prefill + ``CHECK_STEPS`` cached
    decode steps) and what each of its routed layers chose at every
    position of the sequence come from the decoder's own forward. The
    reference follows those choices at every position and layer
    (``route_override``), so its residual stream tracks the program's and
    a near-tie flipped in one layer cannot move the next layer's scores by
    a held expert's output; each choice, at every position of the prompt
    and of the served tokens, is then judged against the scores that
    reference selected on (``route_margin``): one that lies further than
    ``NEAR_TIE`` from a group-limited top-k of them fails the check,
    whatever the logits read.
    The logits gate compares with that reference; the tie gate takes the
    engine's token within ``TIE_ULPS`` of the maximum of it or of the
    reference on its own routes. The engine's programs route at near-ties
    of their own (another batch and bucket round otherwise): a served
    token past ``TIE_ULPS`` of both is read again at its position under
    each near-tie choice of each routed layer there (``near_tie_choices``,
    the layers after it the reference's own), and passes if one of those
    references takes it within ``TIE_ULPS`` (at most ``_TIE_TRIES``
    forwards a check). Same keys as the runner's, plus ``route_flips``
    (positions x routed layers where the program's choice is not the
    reference scores' own), ``route_margin_max`` (the largest margin any
    of them needed), ``route_flips_refused`` (of them, those past
    ``NEAR_TIE``) and ``tie_routes_tried`` (the forwards the tie gate
    made under near-tie choices)."""
    import jax.numpy as jnp

    from benchmark.runners.serve import CHECK_STEPS
    pre, step = _program(dec)
    dense = at_depth(arch, layers)["first_k_dense_replace"]
    worst_logit, worst_gap, strict = 0.0, 0.0, []
    flips, refused, margin, tried = 0, 0, 0.0, 0
    tol, ties = float(adapter.LOGITS_TOL), float(adapter.TIE_ULPS)
    for seq, P in zip(seqs, prompt_lens):
        ids = np.asarray(seq[:-1], np.int32)[None]
        kc, vc = dec._empty_cache(1)
        lg, kc, vc, r = pre(dec.params, jnp.asarray(ids[:, :P]), kc, vc)
        have, chose = [np.asarray(lg[0], np.float32)], [np.asarray(r)]
        for t in range(P, ids.shape[1]):
            lg, kc, vc, r = step(dec.params, jnp.asarray(ids[:, t:t + 1]),
                                 kc, vc, jnp.int32(t))
            if len(have) <= CHECK_STEPS:
                have.append(np.asarray(lg[0], np.float32))
            chose.append(np.asarray(r))
        del kc, vc, lg
        prog = np.concatenate(chose, axis=1)            # (routed, S, K)
        positions = np.arange(P - 1, ids.shape[1])
        want = adapter.reference_logits(dec.params, arch, layers, ids,
                                        positions)
        scores = {}
        routed = adapter.reference_logits(
            dec.params, arch, layers, ids, positions, scores=scores,
            route_override={dense + i: c[None] for i, c in enumerate(prog)})
        # every position is judged: a choice the reference followed
        # anywhere shaped the latent the compared positions attend over
        for i, c in enumerate(prog):
            need = route_margin(c, np.asarray(scores[dense + i])[0], arch)
            flips += int((need > 0).sum())
            refused += int((need > NEAR_TIE).sum())
            margin = max(margin, float(need.max()))
        served = np.asarray(seq[P:])
        gaps = []
        for ref in (want, routed):
            top = ref.max(-1)
            got = ref[np.arange(len(served)), served]
            gaps.append((top - got) / (2.0 ** -8 * np.abs(top)))
        gap = np.minimum(*gaps)
        strict.append(float((gap == 0).mean()))
        for j in np.nonzero(gap > ties)[0]:
            t = P - 1 + j
            for i in range(len(prog)):
                for alt in near_tie_choices(
                        prog[i][t], np.asarray(scores[dense + i])[0, t],
                        arch):
                    if tried >= _TIE_TRIES or gap[j] <= ties:
                        break
                    over = {}
                    for k, c in enumerate(prog):
                        c = c.copy()
                        if k >= i:
                            c[t] = alt if k == i else -1
                        over[dense + k] = c[None]
                    lg = adapter.reference_logits(
                        dec.params, arch, layers, ids, [t],
                        route_override=over)[0]
                    tried += 1
                    gap[j] = min(gap[j], (lg.max() - lg[served[j]])
                                 / (2.0 ** -8 * abs(lg.max())))
        worst_gap = max(worst_gap, float(gap.max()))
        have = np.stack(have)
        w = routed[:len(have)]
        worst_logit = max(worst_logit, float(
            (np.abs(have - w).max(-1) / w.std(-1)).max()))
    return {"logits_err_max": worst_logit, "logits_tol": tol,
            "token_gap_ulps_max": worst_gap, "tie_ulps": ties,
            "strict_top1_min": min(strict), "route_flips": flips,
            "route_margin_max": margin, "route_flips_refused": refused,
            "tie_routes_tried": tried, "ok": worst_logit <= tol and worst_gap <= ties and not refused}
