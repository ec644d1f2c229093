"""What the A.X-K1 configuration brings to the benchmark: the
configuration file against the catalog, the adapter's cut to a depth and
its ratios at the rehearsal's width, its split of the decoder's fused
parameters, the runner's gate on the decoder at a tiny width, the cell's
rehearsal with and without the trace, the counts of ``mla_flops.py`` against
a count by hand, and the three new readers on hand-made counters and
traces."""

import json
import os

import numpy as np
import pytest

import jax.numpy as jnp

from benchmark import mla_flops as mf, run as bench_run
from benchmark.harness import model as mdl, resolve
from benchmark.harness.trace import WINDOW_SPAN
from benchmark.peaks import peaks
from benchmark.reference import axk1_block as ref

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "axk1.serve.longqa-backlog"
adapter = resolve.load_module("adapters", "axk1")
runner = resolve.load_module("runners", "serve_model")
with open(os.path.join(ROOT, "benchmark/configs/ax-k1.json")) as fh:
    CONFIG = json.load(fh)


# the published config.json of skt/A.X-K1, every key
PUBLISHED = {
    "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 1,
    "hidden_act": "silu", "hidden_size": 7168, "intermediate_size": 18432,
    "kv_lora_rank": 512, "max_position_embeddings": 131072,
    "model_type": "axk1", "moe_intermediate_size": 2048,
    "moe_layer_freq": 1, "n_group": 8, "n_routed_experts": 192,
    "n_shared_experts": 1, "norm_topk_prob": True,
    "num_attention_heads": 64, "num_experts_per_tok": 8,
    "num_hidden_layers": 61, "num_key_value_heads": 64, "q_lora_rank": 1536,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
    "rope_theta": 10000,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 32,
                     "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 4096,
                     "type": "yarn"},
    "routed_scaling_factor": 2.5, "scoring_func": "sigmoid",
    "seq_aux": True, "tie_word_embeddings": False, "topk_group": 4,
    "topk_method": "none", "v_head_dim": 128, "vocab_size": 163840}


def test_the_configuration_keeps_every_published_width():
    assert CONFIG["source"] == \
        "https://huggingface.co/skt/A.X-K1/blob/main/config.json"
    reduced = ["num_hidden_layers", "n_routed_experts"]
    assert CONFIG["reduced"] == reduced
    assert all(CONFIG[k] == v for k, v in PUBLISHED.items()
               if k not in reduced)
    assert (CONFIG["n_routed_experts"], CONFIG["n_routed_experts_published"],
            CONFIG["expert_offset"], CONFIG["num_hidden_layers"],
            CONFIG["num_hidden_layers_published"]) == (12, 192, 0, 5, 61)
    assert (CONFIG["vocab_size"], CONFIG["vocab_rows_held"]) \
        == (163840, 20480)
    sec = CONFIG["sections"]["serve"]
    assert (sec["num_slots"], sec["max_len"], sec["chunk_size"]) \
        == (24, 16384, 16)
    cfg = adapter.program_config(adapter.arch_of(CONFIG), sec)
    assert (cfg.num_hidden_layers, cfg.num_dense_layers, cfg.hidden_size,
            cfg.intermediate_size, cfg.moe_intermediate_size,
            cfg.q_lora_rank, cfg.kv_lora_rank, cfg.qk_nope_head_dim,
            cfg.qk_rope_head_dim, cfg.v_head_dim, cfg.num_attention_heads,
            cfg.vocab_size) == (5, 1, 7168, 18432, 2048, 1536, 512, 128, 64,
                                128, 64, 20480)
    assert (cfg.n_routed_experts, cfg.experts_held, cfg.num_experts_per_tok,
            cfg.n_group, cfg.topk_group, cfg.routed_scaling_factor) \
        == (192, 12, 8, 8, 4, 2.5)
    assert cfg.rope_scaling == CONFIG["rope_scaling"]
    for key, bad in (("scoring_func", "softmax"), ("model_type", "deepseek"),
                     ("hidden_act", "gelu"), ("moe_layer_freq", 2)):
        with pytest.raises(ValueError, match=key):
            adapter.arch_of({**CONFIG, key: bad})
    with pytest.raises(ValueError, match="YaRN"):
        adapter.arch_of({**CONFIG, "rope_scaling": {"type": "linear"}})


def test_a_cut_keeps_a_routed_layer_and_the_rehearsal_the_routes():
    arch = adapter.arch_of(CONFIG)
    assert adapter.at_depth(arch, 1)["first_k_dense_replace"] == 0
    assert adapter.at_depth(arch, 2)["first_k_dense_replace"] == 1
    tiny, sec, _ = mdl.rehearsal(arch, CONFIG["sections"]["serve"],
                                 {"prompt_len": {}, "output_len": {}})
    cfg = adapter.program_config(tiny, sec)
    # 8 groups of 24 of which 4 are kept, top 8; a rope part rotated
    assert (cfg.hidden_size, cfg.head_dim, cfg.num_hidden_layers) \
        == (64, 16, 1)
    assert (cfg.n_group, cfg.topk_group, cfg.num_experts_per_tok,
            cfg.n_routed_experts, cfg.experts_held) == (8, 4, 8, 192, 12)
    assert cfg.qk_rope_head_dim >= 2 and cfg.routed and cfg.latent
    # the accepted moe_experts_touched reader counts the routed layers
    # through the AFMoE adapter's cut
    afmoe = resolve.load_module("adapters", "afmoe")
    a5 = afmoe.at_depth(arch, 5)
    assert (a5["num_hidden_layers"], a5["num_dense_layers"]) == (5, 1)


def _tiny(seed=7):
    import paddle_tpu as paddle
    from paddle_tpu.inference.generate import LlamaDecoder
    arch, sec, _ = mdl.rehearsal(
        adapter.arch_of(CONFIG),
        {**CONFIG["sections"]["serve"], "dtype": "float32"},
        {"prompt_len": {}, "output_len": {}})
    sec["num_hidden_layers"] = 2          # the dense layer and a routed one
    cfg = adapter.program_config(arch, sec)
    paddle.seed(seed)
    model = adapter.build_model(cfg)
    rng = np.random.default_rng(seed)
    for name, p in model.named_parameters():
        if "norm" in name:
            p._value = jnp.asarray(1.0 + 0.2 * rng.standard_normal(p.shape),
                                   p._value.dtype)
    return model, cfg, arch, LlamaDecoder(model, max_len=64)


def test_adapter_split_gives_back_the_models_own_weights():
    model, cfg, arch, dec = _tiny()
    sd = {n: np.asarray(t.value) for n, t in model.state_dict().items()}
    a2 = adapter.at_depth(arch, 2)
    for li in (0, 1):
        a = ref.layer_weights_by_name(sd, a2)(li)
        b = adapter.layer_weights_from_decoder(dec.params, a2)(li)
        assert set(a) == set(b)
        for k in a:
            if k == "mlp.experts":
                for e in (0, 11):
                    assert all(np.array_equal(np.asarray(x), np.asarray(y))
                               for x, y in zip(a[k](e), b[k](e)))
            else:
                assert np.array_equal(np.asarray(a[k]), np.asarray(b[k])), k


def test_the_gate_passes_the_decoder_at_a_tiny_width():
    """The runner's own comparison in float32: the decoder reads about 1e-6
    of a standard deviation against ``reference_logits``; the program's
    own routes, handed to the reference, change nothing where nothing
    flipped."""
    from paddle_tpu.serving import ServingEngine
    model, cfg, arch, dec = _tiny()
    eng = ServingEngine(dec, num_slots=2, chunk_size=4)
    rng = np.random.default_rng(3)
    lens = [16, 25]
    rids = [eng.submit(rng.integers(0, 256, (n,), dtype=np.int32), 10)
            for n in lens]
    done = eng.drain()
    seqs = [np.asarray(done[r])[0] for r in rids]
    ok = runner._check_against_reference(dec, adapter, arch, 2, seqs, lens)
    assert ok["ok"] and ok["logits_err_max"] < 1e-4
    assert ok["logits_tol"] == adapter.LOGITS_TOL
    ids = np.asarray(seqs[0][:20], np.int32)[None]
    kc, vc = dec._empty_cache(1)
    routes = {1: np.asarray(adapter._program(dec)[0](
        dec.params, jnp.asarray(ids), kc, vc)[3])}
    own = {}
    want = adapter.reference_logits(dec.params, arch, 2, ids, np.arange(20),
                                    record=own)
    assert sorted(own) == [1] and routes[1].shape == (1, 20, 8)
    assert np.array_equal(np.sort(routes[1], -1),
                          np.sort(np.asarray(own[1]), -1))
    assert np.array_equal(want, adapter.reference_logits(
        dec.params, arch, 2, ids, np.arange(20), route_override=routes))


def _scores():
    """8 groups of 24 experts: groups 0-3 clearly kept (two experts at 0.9
    and 0.8), group 4 just below them (0.8 and 0.79) or far (0.5)."""
    c = np.full((1, 192), 0.1, np.float32)
    for g in range(4):
        c[0, g * 24:g * 24 + 2] = (0.9, 0.8)
    c[0, 96:98] = (0.8, 0.7995)
    return c


def test_route_margin_takes_a_near_tie_and_refuses_the_rest():
    arch = adapter.arch_of(CONFIG)
    c = _scores()
    own = [0, 1, 24, 25, 48, 49, 72, 73]

    def need(prog, scores=c):
        return float(adapter.route_margin(np.array([prog]), scores,
                                          arch)[0])
    assert need(own) == 0.0
    # the eighth swapped for an expert of its own group within the margin
    near = c.copy()
    near[0, 74] = 0.8 - adapter.NEAR_TIE / 2
    assert need([0, 1, 24, 25, 48, 49, 72, 74], near) == pytest.approx(
        adapter.NEAR_TIE / 2)
    # ... and for one a whole score step below: a wrong choice
    assert need([0, 1, 24, 25, 48, 49, 72, 74]) == pytest.approx(0.7)
    # a fifth group in place of the fourth: its two best in place of
    # group 3's (group score 1.5995 against 1.7: not near) ...
    assert need([0, 1, 24, 25, 48, 49, 96, 97]) > adapter.NEAR_TIE
    # ... and a near-tie of it
    tie = c.copy()
    tie[0, 96:98] = (0.9, 0.8 - adapter.NEAR_TIE / 4)
    assert need([0, 1, 24, 25, 48, 49, 96, 97], tie) == pytest.approx(
        adapter.NEAR_TIE / 4)
    # an expert twice, or five groups, is no choice
    assert need([0, 0, 24, 25, 48, 49, 72, 73]) == np.inf
    assert need([0, 1, 24, 25, 48, 49, 72, 96]) == np.inf
    # positions are judged each on its own scores
    two = np.concatenate([c, near])
    got = adapter.route_margin(np.array([own, [0, 1, 24, 25, 48, 49, 72,
                                               74]]), two, arch)
    assert got[0] == 0.0 and got[1] == pytest.approx(adapter.NEAR_TIE / 2)


def test_a_row_of_minus_one_in_a_route_override_is_the_references_own():
    arch = adapter.arch_of(CONFIG)
    rng = np.random.default_rng(0)
    m = jnp.asarray(rng.standard_normal((1, 3, 16)), jnp.float32)
    router = jnp.asarray(rng.standard_normal((16, 192)), jnp.float32)
    bias = jnp.zeros((192,), jnp.float32)
    w_own, own, _ = ref.route(m, router, bias, arch)
    given = np.tile(np.arange(8, dtype=np.int32), (1, 3, 1))
    given[0, 1] = -1
    w, sel, _ = ref.route(m, router, bias, arch, select=given)
    assert np.array_equal(np.asarray(sel)[0, [0, 2]], given[0, [0, 2]])
    assert np.array_equal(np.asarray(sel)[0, 1], np.asarray(own)[0, 1])
    assert np.allclose(np.asarray(w)[0, 1], np.asarray(w_own)[0, 1])


def test_near_tie_choices_are_the_swaps_a_rounding_could_make():
    arch = adapter.arch_of(CONFIG)
    c = _scores()
    own = np.array([0, 1, 24, 25, 48, 49, 72, 73])
    # every other choice lies a score step or a group below
    assert adapter.near_tie_choices(own, c[0], arch) == []
    # an expert of a kept group within the margin of the four at 0.8: any
    # of them may give way to it
    near = c.copy()
    near[0, 74] = 0.8 - adapter.NEAR_TIE / 2
    got = adapter.near_tie_choices(own, near[0], arch)
    assert sorted(tuple(sorted(set(own) - set(a))) for a in got) == [
        (1,), (25,), (49,), (73,)]
    assert all(74 in a for a in got)
    # a choice that is not the scores' own lists the own one
    swapped = np.array([0, 1, 24, 25, 48, 49, 72, 74])
    assert any(np.array_equal(a, own) for a in adapter.near_tie_choices(
        swapped, near[0], arch))


def test_the_tie_gate_reads_a_token_under_the_near_ties_of_its_position(
        monkeypatch):
    """A served token that only another routing of its own position makes
    the greedy one: the tie gate reads it again at that position under
    each near-tie choice of the routed layer there, the other positions on
    the program's routes. With no margin (no choice but the program's) it
    fails; with the margin at 0.5 it passes. The reference is the
    real one, but for the doctored token's logit, which a choice other
    than the program's at that position raises to the maximum (at a tiny
    width a held expert moves no top logit past the next)."""
    from paddle_tpu.serving import ServingEngine
    model, cfg, arch, dec = _tiny()
    eng = ServingEngine(dec, num_slots=2, chunk_size=4)
    rng = np.random.default_rng(3)
    lens = [16, 25]
    rids = [eng.submit(rng.integers(0, 256, (n,), dtype=np.int32), 10)
            for n in lens]
    done = eng.drain()
    seqs = [np.asarray(done[r])[0] for r in rids]
    t = len(seqs[0]) - 2                  # the position of the last token
    ids = np.asarray(seqs[0][:-1], np.int32)[None]
    kc, vc = dec._empty_cache(1)
    prog = np.asarray(adapter._program(dec)[0](
        dec.params, jnp.asarray(ids), kc, vc)[3])[0]        # (S, K)
    sc = {}
    base = adapter.reference_logits(dec.params, arch, 2, ids, [t], scores=sc,
                                    route_override={1: prog[None]})[0]
    gaps = (base.max() - base) / (2.0 ** -8 * abs(base.max()))
    token = int(np.argmin(np.where(gaps > 3 * adapter.TIE_ULPS, gaps,
                                   np.inf)))
    seqs[0] = np.concatenate([seqs[0][:-1], [token]])
    real, seen = adapter.reference_logits, []

    def reference(params, arch_, layers, ids_, positions, **kw):
        lg = real(params, arch_, layers, ids_, positions, **kw)
        over = kw.get("route_override")
        if list(positions) == [t] and over is not None and not np.array_equal(
                np.sort(over[1][0, t]), np.sort(prog[t])):
            seen.append(over[1][0, t])
            lg = lg.copy()
            lg[0, token] = lg[0].max() * (1 + 2.0 ** -9)
        return lg
    monkeypatch.setattr(adapter, "reference_logits", reference)
    monkeypatch.setattr(adapter, "NEAR_TIE", 0.0)
    narrow = adapter.check_against_reference(dec, adapter, arch, 2, seqs,
                                             lens)
    assert not narrow["ok"] and narrow["tie_routes_tried"] == len(seen) == 0
    assert narrow["token_gap_ulps_max"] > adapter.TIE_ULPS
    assert narrow["logits_err_max"] < 1e-4
    monkeypatch.setattr(adapter, "NEAR_TIE", 0.5)
    wide = adapter.check_against_reference(dec, adapter, arch, 2, seqs, lens)
    assert wide["ok"] and wide["tie_routes_tried"] == len(seen) == 1
    assert wide["token_gap_ulps_max"] <= adapter.TIE_ULPS
    assert 0 < adapter.route_margin(seen[0][None], np.asarray(sc[1])[0, t:],
                                    arch)[0] <= 0.5


def test_the_check_reads_past_near_ties_and_fails_a_router_without_groups(
        monkeypatch):
    """The near-tie aware check on the decoder at a tiny width: it passes;
    a decoder whose router ignores the group limit (every group kept)
    chooses experts the reference's scores rank far below, and fails."""
    from paddle_tpu.inference.generate import LlamaDecoder
    from paddle_tpu.serving import ServingEngine
    model, cfg, arch, dec = _tiny()
    def seqs_of(d):
        eng = ServingEngine(d, num_slots=2, chunk_size=4)
        rng = np.random.default_rng(3)
        rids = [eng.submit(rng.integers(0, 256, (n,), dtype=np.int32), 10)
                for n in (16, 25)]
        done = eng.drain()
        return [np.asarray(done[r])[0] for r in rids]
    ok = adapter.check_against_reference(dec, adapter, arch, 2,
                                         seqs_of(dec), [16, 25])
    assert ok["ok"] and ok["logits_err_max"] < 1e-4
    assert ok["route_flips_refused"] == 0 and ok["route_margin_max"] == 0
    monkeypatch.setattr(cfg, "topk_group", cfg.n_group)
    bad = LlamaDecoder(model, max_len=64)
    res = adapter.check_against_reference(bad, adapter, arch, 2,
                                          seqs_of(bad), [16, 25])
    assert not res["ok"] and res["route_flips_refused"] > 0
    assert res["route_margin_max"] > adapter.NEAR_TIE


def test_the_cells_runner_swaps_the_check_for_its_run_alone(monkeypatch):
    """``serve_model_routed`` runs ``serve_model_ctx`` (the check's lengths
    from the mix) with the adapter's near-tie aware check in the place of
    ``serve_model``'s, the device's peak bytes before and after it added
    to its line, and puts the plain one back after, also when the run
    raises."""
    from types import SimpleNamespace
    routed = resolve.load_module("runners", "serve_model_routed")
    sm = routed.serve_model
    plain = sm._check_against_reference
    seen = {}
    def fake(*a):
        seen["check"] = sm._check_against_reference("dec", "adapter")
        seen["lens"] = sm.CHECK_PROMPTS
        return "ran"
    monkeypatch.setattr(sm, "run", fake)
    monkeypatch.setattr(sm, "CHECK_PROMPTS", (64, 100))
    cell = resolve.load_cell(CELL)
    monkeypatch.setattr(routed.resolve, "load_module", lambda *a: (
        SimpleNamespace(check_against_reference=lambda *b: {"ok": b})))
    assert cell["runner"] == "serve_model_routed"
    args = SimpleNamespace(root=os.path.join(ROOT, "benchmark"))
    assert routed.run(cell, args, [], 0.0, None) == "ran"
    assert seen == {"check": {"ok": ("dec", "adapter"),
                              "memory_peak_bytes_before": 0,
                              "memory_peak_bytes_after": 0},
                    "lens": (2100, 4100)}
    assert sm._check_against_reference is plain
    monkeypatch.setattr(sm, "run", lambda *a: 1 / 0)
    with pytest.raises(ZeroDivisionError):
        routed.run(cell, args, [], 0.0, None)
    assert sm._check_against_reference is plain


@pytest.mark.parametrize("trace", [0, 1])
def test_the_cell_rehearses(capsys, trace):
    rc = bench_run.main(["--workload", CELL, "--seed", str(2**31 + 44),
                         "--seconds", "1.5", "--trace", str(trace),
                         "--rehearse"])
    last = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0 and last["correct"] is True and last["failed"] == 0
    if trace:
        assert {"sched_occupancy", "moe_experts_touched"} \
            <= set(last["metrics"])
    else:
        assert set(last["metrics"]) == {"out_tok_s", "setup_s"}


def test_mla_flops_against_a_count_by_hand():
    a = adapter.at_depth(adapter.arch_of(CONFIG), 5)
    p = mf.mla_params(a)
    # q_a 11.01 M, q_b 18.87, kv_a 4.13, kv_b 8.39, o 58.72 (+ two norms)
    assert p["attention"] == (7168 * 1536 + 1536 + 1536 * 64 * 192
                              + 7168 * 576 + 512 + 512 * 64 * 256
                              + 8192 * 7168) == 101_124_096
    assert p["dense_ffn"] == 3 * 7168 * 18432
    assert p["expert"] == p["shared"] == 3 * 7168 * 2048 == 44_040_192
    assert p["router"] == 7168 * 192 + 192
    held = (5 * (p["attention"] + p["norms"]) + p["dense_ffn"]
            + 4 * (p["router"] + p["shared"] + 12 * p["expert"])
            + 7168 + 2 * 7168 * 20480)
    assert round(held / 1e9, 2) == 3.49             # 6.98 GB in bf16
    # the decode attention: 2 x 64 x (512 + 64 + 512) a position
    d = mf.mla_decode_attention(live_positions=1000.0, heads=64,
                                kv_lora_rank=512, rope_dim=64,
                                bytes_per_position=1280)
    assert d == {"flops": 2.0 * 64 * 1088 * 1000, "bytes": 1280 * 1000.0}
    # the prefill: a row of 3 positions, 6 causal pairs
    f = mf.mla_prefill_attention(causal_pairs=6, positions=3, heads=64,
                                 qk_head_dim=192, v_head_dim=128)
    assert f == {"flops": 2.0 * 64 * 320 * 6,
                 "bytes": 3.0 * 64 * 2 * (2 * 192 + 2 * 128)}
    s = mf.mla_decode_step(arch=a, rows=24.0, experts_touched=30.0,
                           pairs_held=48.0, live_latent=1000.0,
                           bytes_per_position=1280)
    fixed = (5 * (p["attention"] + p["norms"]) + p["dense_ffn"]
             + 4 * (p["router"] + p["shared"]) + 7168 + 7168 * 20480)
    assert s["weight_bytes"] == 2 * (fixed + 24 * 7168 + 30 * p["expert"])
    assert s["bytes"] == s["weight_bytes"] + 1280 * 1000
    assert s["flops"] == (2.0 * 24 * (fixed - 5 * p["norms"] - 7168)
                          + 2.0 * 48 * p["expert"] + d["flops"])


def _ctx(trace=None):
    """Counters of a window of 100 chunks of 16 steps on 24 full slots:
    5000 live positions a row a step over 5 latent layers; 6 held experts
    touched and 12 pairs a routed layer a step; admission prefills of 5000
    and 7000 positions in the bucket of 8192 (one of 6000 before the
    window)."""
    def pre(*lens):
        return {"rows": len(lens), "positions": sum(lens),
                "causal_pairs": sum(n * (n + 1) // 2 for n in lens)}
    before = {"chunk_dispatches": 10, "live_latent_positions_total": 9,
              "occupancy_mean": 1.0, "occupancy_samples": 10,
              "moe_experts_touched_total": 3, "moe_pairs_held_total": 4,
              "cache_bytes_per_position": 5 * 1280, "cache_layers": 5,
              "prefill_by_bucket": {8192: pre(6000)}}
    steps = 100 * 16
    after = {**before, "chunk_dispatches": 110,
             "live_latent_positions_total": 9 + steps * 24 * 5000 * 5,
             "occupancy_samples": 110,
             "moe_experts_touched_total": 3 + steps * 4 * 6,
             "moe_pairs_held_total": 4 + steps * 4 * 12,
             "prefill_by_bucket": {8192: pre(6000, 5000, 7000)}}
    return {"trace": trace, "engine": {"before": before, "after": after},
            "arch": adapter.arch_of(CONFIG),
            "section": CONFIG["sections"]["serve"],
            "peaks": peaks("TPU v5 lite")}


def _trace(chunk_ms=160.0, kernel_ms=40.0, prefill=True):
    """Two runs of the chunk program holding the latent kernel (5 layers x
    16 steps of ``kernel_ms / 80`` each) and one admission prefill of the
    bucket of 8192: 5 layers x 2 head groups of flash calls of 3 ms."""
    ms = 1_000_000
    mods, ops, t = [], [], 10 * ms
    for _ in range(2):
        mods.append(["jit_ring_chunk_decode(1)", t, int(chunk_ms * ms), ""])
        for i in range(80):
            ops.append([f"mla_decode_attention.{i}", t + i * ms,
                        int(kernel_ms * ms / 80), ""])
        t += int(chunk_ms * ms) + ms
    if prefill:
        mods.append(["jit_ring_admit_prefill(2)", t, 60 * ms, ""])
        for i in range(10):
            ops.append([f"flash_fwd.{i}", t + 5 * i * ms, 3 * ms,
                        "(bf16[32,8192,192]{2,1,0}, f32[32,8192,1]{2,1,0}) "
                        "custom-call(...)"])
        t += 61 * ms
    return {"devices": {0: {"modules": mods, "ops": ops}},
            "host": [[WINDOW_SPAN, 0, t + ms, ""]]}


def _read(name, ctx):
    m = resolve.load_json("layer_metrics", name)
    return resolve.load_module("readers", m["reader"]).read(
        ctx, **m.get("args", {}))


def test_the_readers_on_hand_made_counters_and_traces():
    ctx = _ctx(_trace())
    live = 24 * 5000 * 5.0                      # latent positions a step
    need = mf.mla_decode_attention(live_positions=live, heads=64,
                                   kv_lora_rank=512, rope_dim=64,
                                   bytes_per_position=1280)
    least = max(need["bytes"] / 819e9, need["flops"] / 197e12)
    assert _read("mla_decode_roofline", ctx) == pytest.approx(
        100 * least / (40e-3 / 16))
    step = mf.mla_decode_step(
        arch=adapter.at_depth(ctx["arch"], 5), rows=24.0,
        experts_touched=24.0, pairs_held=48.0, live_latent=live,
        bytes_per_position=1280)
    least = max(step["bytes"] / 819e9, step["flops"] / 197e12)
    assert _read("mla_step_roofline", ctx) == pytest.approx(
        100 * least / (160e-3 / 16))
    # the prefill: the window's two prefills of the bucket of 8192 (the
    # one before is taken off); a call of 32 of the 64 heads is half of
    # one layer of the mean row's need
    pairs = (5000 * 5001 + 7000 * 7001) // 2 / 2
    per = mf.mla_prefill_attention(causal_pairs=pairs / 2,
                                   positions=6000 / 2, heads=64,
                                   qk_head_dim=192, v_head_dim=128)
    least = max(10 * per["bytes"] / 819e9, 10 * per["flops"] / 197e12)
    assert _read("mla_prefill_roofline", ctx) == pytest.approx(
        100 * least / 30e-3)
    assert len(ctx["notes"]) == 3


def test_the_readers_find_nothing_rather_than_invent():
    from benchmark.harness.trace import TraceError
    none = _ctx(None)
    for name in ("mla_decode_roofline", "mla_prefill_roofline",
                 "mla_step_roofline"):
        assert _read(name, none) is None
    # a program before this PR: no latent counter, no prefill counts
    old = _ctx(_trace())
    for m in old["engine"].values():
        del m["live_latent_positions_total"], m["prefill_by_bucket"]
    for name in ("mla_decode_roofline", "mla_prefill_roofline",
                 "mla_step_roofline"):
        assert _read(name, old) is None
    assert _read("mla_prefill_roofline", _ctx(_trace(prefill=False))) is None
    uncounted = _ctx(_trace())
    del uncounted["engine"]["after"]["prefill_by_bucket"][8192]
    assert _read("mla_prefill_roofline", uncounted) is None
    gone = _trace()
    gone["devices"][0]["ops"] = [
        e for e in gone["devices"][0]["ops"] if "mla" not in e[0]]
    with pytest.raises(TraceError, match="mla_decode_attention"):
        _read("mla_decode_roofline", _ctx(gone))
