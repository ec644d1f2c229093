"""What the Trinity (AFMoE) configuration brings to the benchmark: the
configuration file against the catalog, the adapter's cut to a depth and
its split of the decoder's fused parameters, the runner's gate on the
decoder, on a decoder without the window and on the reference one
precision below the section's, the counts of ``moe_flops.py`` at the
published widths, and the four new readers on hand-made counters and
traces. Both new cells are rehearsed by ``test_cli.py``, as every cell of
``BENCHMARK.json`` is."""

import json
import os

import numpy as np
import pytest

import jax.numpy as jnp

from benchmark import moe_flops as mf
from benchmark.harness import model as mdl, resolve
from benchmark.harness.trace import WINDOW_SPAN
from benchmark.peaks import peaks
from benchmark.reference import afmoe_block as ref

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
adapter = resolve.load_module("adapters", "afmoe")
runner = resolve.load_module("runners", "serve_model")
with open(os.path.join(ROOT,
                       "benchmark/configs/trinity-large-preview.json")) as fh:
    CONFIG = json.load(fh)
S, F = "sliding_attention", "full_attention"


def test_the_configuration_keeps_every_published_width():
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as fh:
        rows = [json.loads(line) for line in fh]
    want = next(r for r in rows if r["name"] == "Trinity-Large-Preview")
    assert CONFIG["source"] == want["source_url"]
    reduced = ["num_hidden_layers", "num_dense_layers", "layer_types",
               "num_experts"]
    assert CONFIG["reduced"] == reduced
    assert all(CONFIG[k] == v for k, v in want["config"].items()
               if k not in reduced)
    assert (CONFIG["num_experts"], CONFIG["num_experts_published"],
            CONFIG["expert_offset"]) == (32, 256, 0)
    # the published vocabulary stays; the rows this chip holds stand beside
    assert (CONFIG["vocab_size"], CONFIG["vocab_rows_held"]) \
        == (200192, 25024)
    assert CONFIG["layer_types"] == [S, S, S, S, F]
    arch = adapter.arch_of(CONFIG)
    for name, slots, max_len in (("serve", 16, 8192),
                                 ("serve_batch", 96, 1024)):
        sec = CONFIG["sections"][name]
        assert (sec["num_slots"], sec["max_len"]) == (slots, max_len)
        cfg = adapter.program_config(arch, sec)
        assert (cfg.num_hidden_layers, cfg.num_dense_layers) == (5, 1)
        assert cfg.layer_types == (S, S, S, S, F)
        assert (cfg.num_experts, cfg.experts_held, cfg.expert_offset,
                cfg.num_experts_per_tok) == (256, 32, 0, 4)
        assert (cfg.head_dim, cfg.hidden_size, cfg.intermediate_size,
                cfg.moe_intermediate_size, cfg.sliding_window,
                cfg.vocab_size) == (128, 3072, 12288, 3072, 4096, 25024)
        assert [cfg.cache_len(ci, max_len) for ci in range(5)] \
            == [min(4096, max_len)] * 4 + [max_len]
    for key, bad in (("score_func", "softmax"), ("n_group", 2),
                     ("rope_scaling", {"type": "yarn"}),
                     ("hidden_act", "gelu")):
        with pytest.raises(ValueError, match=key):
            adapter.arch_of({**CONFIG, key: bad})


def test_a_cut_in_depth_keeps_a_routed_windowed_layer_and_the_ratios():
    arch = adapter.arch_of(CONFIG)
    one = adapter.at_depth(arch, 1)
    assert (one["layer_types"], one["num_dense_layers"]) == ((S,), 0)
    two = adapter.at_depth(arch, 2)
    assert (two["layer_types"], two["num_dense_layers"]) == ((S, S), 1)
    # --rehearse shrinks the Llama keys; the family's follow by ratio
    tiny, sec, _ = mdl.rehearsal(arch, CONFIG["sections"]["serve"],
                                 {"prompt_len": {}, "output_len": {}})
    cfg = adapter.program_config(tiny, sec)
    assert (cfg.hidden_size, cfg.head_dim, cfg.moe_intermediate_size,
            cfg.sliding_window, cfg.num_hidden_layers) == (64, 16, 32, 4, 1)
    assert cfg.routed and cfg.has_windows and cfg.experts_held == 32
    assert cfg.cache_len(0, sec["max_len"]) == 4      # its window bites


def _tiny(seed=7):
    import paddle_tpu as paddle
    from paddle_tpu.inference.generate import LlamaDecoder
    from paddle_tpu.models.afmoe import AfmoeForCausalLM
    arch, sec, _ = mdl.rehearsal(
        adapter.arch_of(CONFIG),
        {**CONFIG["sections"]["serve"], "dtype": "float32"},
        {"prompt_len": {}, "output_len": {}})
    sec["num_hidden_layers"] = 3          # dense, routed, routed: s, s, s
    cfg = adapter.program_config(arch, sec)
    paddle.seed(seed)
    model = AfmoeForCausalLM(cfg)
    rng = np.random.default_rng(seed)
    for name, p in model.named_parameters():
        if "norm" in name:
            p._value = jnp.asarray(1.0 + 0.2 * rng.standard_normal(p.shape),
                                   p._value.dtype)
    return model, cfg, arch, LlamaDecoder(model, max_len=64)


def test_adapter_split_gives_back_the_models_own_weights():
    model, cfg, arch, dec = _tiny()
    sd = {n: np.asarray(t.value) for n, t in model.state_dict().items()}
    a3 = adapter.at_depth(arch, 3)
    for li in (0, 1):
        a = ref.layer_weights_by_name(sd, a3)(li)
        b = adapter.layer_weights_from_decoder(dec.params, a3)(li)
        assert set(a) == set(b)
        for k in a:
            if k == "mlp.experts":
                for e in (0, 31):
                    assert all(np.array_equal(np.asarray(x), np.asarray(y))
                               for x, y in zip(a[k](e), b[k](e)))
            else:
                assert np.array_equal(np.asarray(a[k]), np.asarray(b[k])), k


def _engine_seqs(dec, lens, budget=10):
    from paddle_tpu.serving import ServingEngine
    eng = ServingEngine(dec, num_slots=2, chunk_size=4)
    rng = np.random.default_rng(3)
    rids = [eng.submit(rng.integers(0, 256, (n,), dtype=np.int32), budget)
            for n in lens]
    done = eng.drain()
    return [np.asarray(done[r])[0] for r in rids]


def test_the_gate_passes_the_decoder_and_fails_one_without_the_window(
        monkeypatch):
    """The runner's own comparison, at a tiny width in float32 with
    prompts longer than the window of 4: the decoder reads about 1e-6 of a
    standard deviation; one that lets a windowed layer see all of the
    past fails by the logits gate. The program's own routes, handed to the
    reference, change nothing where nothing flipped."""
    from paddle_tpu.inference.generate import LlamaDecoder
    from paddle_tpu.models.afmoe import AfmoeConfig
    model, cfg, arch, dec = _tiny()
    lens = [16, 25]
    seqs = _engine_seqs(dec, lens)
    ok = runner._check_against_reference(dec, adapter, arch, 3, seqs, lens)
    assert ok["ok"] and ok["logits_err_max"] < 1e-4
    assert ok["logits_tol"] == adapter.LOGITS_TOL
    ids = np.asarray(seqs[0][:20], np.int32)[None]
    routes = adapter.program_routes(dec, ids)
    own = {}
    want = adapter.reference_logits(dec.params, arch, 3, ids, np.arange(20),
                                    record=own)
    assert sorted(routes) == sorted(own) == [1, 2]
    assert all(np.array_equal(np.sort(routes[li], -1),
                              np.sort(np.asarray(own[li]), -1))
               for li in routes)
    assert np.array_equal(want, adapter.reference_logits(
        dec.params, arch, 3, ids, np.arange(20), route_override=routes))

    monkeypatch.setattr(AfmoeConfig, "cache_len",
                        lambda self, ci, max_len: max_len)
    monkeypatch.setattr(AfmoeConfig, "layer_window", lambda self, li: None)
    bad = LlamaDecoder(model, max_len=64)
    res = runner._check_against_reference(
        bad, adapter, arch, 3, _engine_seqs(bad, lens), lens)
    assert not res["ok"] and res["logits_err_max"] > adapter.LOGITS_TOL


def test_the_gate_fails_the_reference_one_precision_below():
    """``precision_control.py`` at the published widths, the depth cut to 2
    of the section's 5 layers (the dense one and one routed, windowed
    layer of 32 held experts) and the check's prompts shortened, for the
    time a CPU takes: the reference with its activations stored in the
    section's bfloat16 stands in for the program and passes
    ``serve_model``'s comparison; stored in float8_e4m3fn it fails."""
    from benchmark import precision_control as pc
    got = pc.readings("trinity-large-preview", "serve", 2**31 + 353,
                      ["bfloat16", "float8_e4m3fn"], layers=2,
                      prompts=[16, 25], budget=9)
    own, below = got["bfloat16"], got["float8_e4m3fn"]
    assert own["logits_tol"] == adapter.LOGITS_TOL
    assert own["ok"] and 0.005 < own["logits_err_max"] < adapter.LOGITS_TOL
    assert not below["ok"]
    assert below["logits_err_max"] > 5 * own["logits_err_max"]


def test_counts_at_the_published_widths():
    a = adapter.at_depth(adapter.arch_of(CONFIG), 5)
    p = mf.afmoe_params(a)
    # q 18.9 M, k 3.1, v 3.1, gate 18.9, o 18.9 (+ the two head norms)
    assert p["attention"] == 3072 * (3 * 6144 + 2 * 1024) + 256 == 62_914_816
    assert p["dense_ffn"] == 3 * 3072 * 12288 == 113_246_208
    assert p["expert"] == p["shared"] == 3 * 3072 * 3072 == 28_311_552
    assert p["router"] == 3072 * 256 + 256
    held = (5 * (p["attention"] + p["norms"]) + p["dense_ffn"]
            + 4 * (p["router"] + p["shared"] + 32 * p["expert"])
            + 3072 + 2 * 3072 * 25024)
    assert round(held / 1e9, 2) == 4.32            # ISSUE 33's 8.64 GB in bf16
    kv = 2 * 8 * 128 * 2                           # one layer's K and V
    got = mf.moe_decode_step(
        arch=a, rows=16, experts_touched=28.0, pairs_held=32.0,
        live_full=20_000, live_window=15_000, bytes_full=kv,
        bytes_window=4 * kv)
    fixed = held - 4 * 32 * p["expert"] - 3072 * 25024   # less the embedding
    assert got["expert_bytes"] == 2 * 28 * p["expert"]
    assert got["weight_bytes"] == 2 * (fixed + 16 * 3072 + 28 * p["expert"])
    assert got["kv_bytes"] == 20_000 * kv + 15_000 * 4 * kv
    assert got["bytes"] == got["weight_bytes"] + got["kv_bytes"]
    assert got["flops"] == (
        2 * 16 * (fixed - 5 * p["norms"] - 3072) + 2 * 32 * p["expert"]
        + 4 * 48 * 128 * (20_000 + 4 * 15_000))
    band = mf.banded_flash_fwd(seq=8192, window=4096, heads=48, head_dim=128)
    pairs = 4096 * 4097 // 2 + 4096 * 4096
    assert band["flops"] == 4 * 48 * 128 * pairs
    assert band["bytes"] == 4 * 48 * 8192 * 128 * 2 + 48 * 8192 * 4
    wide = mf.banded_flash_fwd(seq=1024, window=4096, heads=48, head_dim=128)
    assert wide["flops"] == 4 * 48 * 128 * (1024 * 1025 // 2)


def _metrics(chunks, occ_sum, **extra):
    return {"chunk_dispatches": chunks,
            "occupancy_mean": occ_sum / chunks if chunks else 0.0,
            "occupancy_samples": chunks, **extra}


def _trace(runs_ms, name="jit_ring_chunk_decode(123)", ops=()):
    t, mods, evs = 1_000_000, [], []
    for ms in runs_ms:
        mods.append((name, t, int(ms * 1e6), ""))
        at = t + 10
        for op, us in ops:
            evs.append((op, at, int(us * 1e3), ""))
            at += int(us * 1e3) + 10
        t += int(ms * 1e6) + 1000
    return {"devices": {0: {"modules": mods, "ops": evs}},
            "host": [(WINDOW_SPAN, 0, t + 10, 0)]}


def _counters(chunks, **scaled):
    """The engine's metrics after ``chunks`` dispatches of 16 steps on a
    full batch, every counter at its rate per chunk."""
    kv = 2 * 8 * 128 * 2
    return _metrics(
        chunks, float(chunks), cache_bytes_per_position_full=kv,
        cache_bytes_per_position_window=4 * kv,
        **{k: v * chunks for k, v in scaled.items()})


RATES = dict(moe_experts_touched_total=16 * 28, moe_pairs_held_total=16 * 32,
             live_kv_positions_total=20_000,
             live_window_positions_total=15_000)


def _ctx(trace):
    return {"trace": trace, "arch": adapter.arch_of(CONFIG),
            "section": CONFIG["sections"]["serve"],
            "peaks": peaks("TPU v5 lite"),
            "engine": {"before": _counters(10, **RATES),
                       "after": _counters(110, **RATES)}}


def _args(metric):
    with open(os.path.join(ROOT, "benchmark/layer_metrics",
                           metric + ".json")) as fh:
        return json.load(fh)["args"]


def test_moe_step_roofline_and_experts_touched_on_hand_made_counters():
    roof = resolve.load_module("readers", "moe_step_roofline")
    touched = resolve.load_module("readers", "moe_experts_touched")
    ctx = _ctx(_trace([96.0, 97.0, 95.0]))
    a = adapter.at_depth(ctx["arch"], 5)
    need = mf.moe_decode_step(
        arch=a, rows=16, experts_touched=28.0, pairs_held=32.0,
        live_full=20_000, live_window=15_000, bytes_full=4096,
        bytes_window=16384)
    got = roof.read(ctx, **_args("moe_step_roofline"))
    assert got == pytest.approx(100 * (need["bytes"] / 819e9) / (0.096 / 16))
    assert 0 < got < 100
    assert "memory-bound" in ctx["notes"][0]
    assert touched.read(ctx, **_args("moe_experts_touched")) \
        == pytest.approx(7.0)            # 28 over the 4 routed layers
    # a program without the counters (the parent), no trace, or a trace
    # without the chunk program: nothing to read, and no error
    bare = {**ctx, "engine": {"before": _metrics(10, 10.0),
                              "after": _metrics(110, 110.0)}}
    assert roof.read(bare, module="^jit_ring_chunk_decode") is None
    assert touched.read(bare) is None
    assert roof.read({**ctx, "trace": None},
                     module="^jit_ring_chunk_decode") is None
    assert roof.read({**ctx, "trace": _trace([5.0], "jit_other")},
                     module="^jit_ring_chunk_decode") is None


def test_window_attn_and_banded_flash_rooflines_on_hand_made_traces():
    win = resolve.load_module("readers", "window_attn_roofline")
    band = resolve.load_module("readers", "flash_fwd_band_roofline")
    # five decode_attention calls of 100 us a step, 16 steps a chunk
    ctx = _ctx(_trace([96.0, 96.0], ops=[("decode_attention", 100.0)] * 80))
    nbytes = 20_000 * 4096 + 15_000 * 16384
    got = win.read(ctx, **_args("window_attn_roofline"))
    assert got == pytest.approx(100 * (nbytes / 819e9) / (5 * 100e-6))
    assert 0 < got < 100
    bare = {**ctx, "engine": {"before": _metrics(10, 10.0),
                              "after": _metrics(110, 110.0)}}
    assert win.read(bare, **_args("window_attn_roofline")) is None
    # the decode_attn_roofline this cell does not list would read one live
    # count times five layers: more bytes than the rolling buffers hold
    assert 20_000 * 5 * 4096 > nbytes
    pre = _trace([400.0], "jit_ring_admit_prefill(7)",
                 ops=[("flash_fwd_band", 9000.0)] * 4)
    ctx = _ctx(pre)
    need = mf.banded_flash_fwd(seq=8192, window=4096, heads=48, head_dim=128)
    got = band.read(ctx, **_args("flash_fwd_band_roofline"))
    assert got == pytest.approx(100 * (need["flops"] / 197e12) / 9e-3)
    assert "compute-bound" in ctx["notes"][0] and "4 calls" in ctx["notes"][0]
    assert band.read(_ctx(_trace([400.0], "jit_ring_admit_prefill(7)")),
                     kernel="flash_fwd_band") is None
