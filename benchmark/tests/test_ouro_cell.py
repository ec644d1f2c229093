"""What the Ouro configuration brings to the benchmark: the reference
against itself written out by hand, the adapter's split of the decoder's
fused parameters, the gate on a decoder that reads the wrong pass's keys
and on the reference one precision below the section's, the counts of a
looped decode step at the published widths, the ``loop_step_roofline``
reader on hand-made counters, and the copied serving loop against its
original. The new cell is rehearsed by ``test_cli.py``, as every cell of
``BENCHMARK.json`` is."""

import dataclasses
import difflib
import inspect
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark import loop_flops as lf
from benchmark.harness import resolve
from benchmark.harness.trace import WINDOW_SPAN
from benchmark.peaks import peaks
from benchmark.reference import llama_block, ouro_block as ref

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
adapter = resolve.load_module("adapters", "ouro")
runner = resolve.load_module("runners", "serve_model")
reader = resolve.load_module("readers", "loop_step_roofline")
with open(os.path.join(ROOT, "benchmark/configs/ouro-2.6b.json")) as fh:
    CONFIG = json.load(fh)


def _tiny(T=4, seed=7):
    """A tiny float32 Ouro model and its decoder, norm weights off one."""
    import paddle_tpu as paddle
    from paddle_tpu.inference.generate import LlamaDecoder
    from paddle_tpu.models.ouro import OURO_TINY, OuroForCausalLM
    cfg = dataclasses.replace(OURO_TINY, total_ut_steps=T)
    paddle.seed(seed)
    model = OuroForCausalLM(cfg)
    rng = np.random.default_rng(seed)
    for name, p in model.named_parameters():
        if "norm" in name:
            p._value = jnp.asarray(1.0 + 0.2 * rng.standard_normal(p.shape),
                                   p._value.dtype)
    arch = {"num_attention_heads": cfg.num_attention_heads,
            "num_key_value_heads": cfg.num_key_value_heads,
            "head_dim": cfg.head_dim, "rope_theta": cfg.rope_theta,
            "rms_norm_eps": cfg.rms_norm_eps,
            "intermediate_size": cfg.intermediate_size,
            "total_ut_steps": T}
    return model, cfg, arch, LlamaDecoder(model, max_len=64)


def test_the_configuration_keeps_every_published_width():
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as fh:
        rows = [json.loads(line) for line in fh]
    want = next(r for r in rows if r["name"] == "Ouro-2.6B")
    assert CONFIG["source"] == want["source_url"]
    assert all(CONFIG[k] == v for k, v in want["config"].items())
    assert CONFIG["reduced"] == ["num_hidden_layers"]
    assert CONFIG["total_ut_steps"] == 4
    assert CONFIG["early_exit_threshold"] == 1.0
    arch = adapter.arch_of(CONFIG)
    cfg = adapter.program_config(arch, CONFIG["sections"]["serve"])
    assert (cfg.num_hidden_layers, cfg.total_ut_steps,
            cfg.num_cache_layers) == (12, 4, 48)
    with pytest.raises(ValueError, match="full attention only"):
        adapter.arch_of({**CONFIG, "use_sliding_window": True})


def test_reference_equals_itself_unrolled_by_hand_for_two_passes():
    model, cfg, arch, _ = _tiny(T=2)
    sd = {n: np.asarray(t.value) for n, t in model.state_dict().items()}
    lw = ref.layer_weights_by_name(sd)
    ids = np.random.default_rng(0).integers(0, 256, (1, 10), dtype=np.int32)
    got = np.asarray(ref.logits(
        ids, arch, 2, sd["model.embed_tokens.weight"], lw,
        sd["model.norm.weight"], sd["lm_head.weight"]))
    kw = dict(heads=4, kv_heads=4, theta=float(cfg.rope_theta),
              eps=float(cfg.rms_norm_eps))
    w0, w1 = ({k: jnp.asarray(v, jnp.float32) for k, v in lw(i).items()}
              for i in (0, 1))
    norm = jnp.asarray(sd["model.norm.weight"])
    with jax.default_matmul_precision("highest"):
        h = jnp.asarray(sd["model.embed_tokens.weight"])[ids]
        h = ref.block(ref.block(h, w0, **kw), w1, **kw)         # pass 0
        h = llama_block.rms_norm(h, norm, kw["eps"])
        h = ref.block(ref.block(h, w0, **kw), w1, **kw)         # pass 1
        h = llama_block.rms_norm(h, norm, kw["eps"])
        want = np.asarray(h @ jnp.asarray(sd["lm_head.weight"]))
    assert np.array_equal(got, want)
    # the sandwich: with the two extra norms' weights at one and the block
    # outputs already unit-RMS it would equal nothing simpler; what must
    # hold is that leaving one out changes the result
    w0b = {**w0, "input_layernorm_2": jnp.ones_like(w0["input_layernorm_2"])}
    assert not np.array_equal(np.asarray(ref.block(h, w0, **kw)),
                              np.asarray(ref.block(h, w0b, **kw)))


def test_adapter_split_gives_back_the_models_own_weights():
    model, _, arch, dec = _tiny()
    sd = {n: np.asarray(t.value) for n, t in model.state_dict().items()}
    a = ref.layer_weights_by_name(sd)(1)
    b = adapter.layer_weights_from_decoder(dec.params, arch)(1)
    assert set(a) == set(b) == set(ref.LAYER_KEYS)
    assert all(np.array_equal(np.asarray(a[k]), np.asarray(b[k])) for k in a)


def _engine_seqs(dec, lens, budget=10):
    from paddle_tpu.serving import ServingEngine
    eng = ServingEngine(dec, num_slots=2, chunk_size=4)
    rng = np.random.default_rng(3)
    rids = [eng.submit(rng.integers(0, 256, (n,), dtype=np.int32), budget)
            for n in lens]
    done = eng.drain()
    return [np.asarray(done[r])[0] for r in rids]


def test_the_gate_passes_the_decoder_and_fails_the_previous_pass_keys(
        monkeypatch):
    """The runner's own comparison, at a tiny width in float32: the
    decoder reads about 1e-6 of a standard deviation; one whose pass t
    attends over pass t-1's buffers fails by the logits gate."""
    from paddle_tpu.inference import generate as gen
    from paddle_tpu.inference.generate import LlamaDecoder
    model, cfg, arch, dec = _tiny()
    lens = [16, 25]
    ok = runner._check_against_reference(
        dec, adapter, arch, cfg.num_hidden_layers, _engine_seqs(dec, lens),
        lens)
    assert ok["ok"] and ok["logits_err_max"] < 1e-4
    assert ok["logits_tol"] == adapter.LOGITS_TOL

    L, real = cfg.num_hidden_layers, gen._block_forward

    def previous_pass(p, cfg_, li, ci, *a, **k):
        return real(p, cfg_, li, max(ci - L, li), *a, **k)
    monkeypatch.setattr(gen, "_block_forward", previous_pass)
    bad = LlamaDecoder(model, max_len=64)
    res = runner._check_against_reference(
        bad, adapter, arch, cfg.num_hidden_layers, _engine_seqs(bad, lens),
        lens)
    assert not res["ok"] and res["logits_err_max"] > adapter.LOGITS_TOL


def test_the_gate_fails_the_reference_one_precision_below():
    """``precision_control.py`` at the published widths, the depth cut to 2
    of the section's 12 layers (8 block applications of its 48) and the
    check's prompts shortened, for the time a CPU takes: the reference with
    its activations stored in the section's bfloat16 stands in for the
    program and passes ``serve_model``'s comparison; stored in
    float8_e4m3fn it fails. The readings at the section's depth, from the
    same script, are the ones the adapter's limits are argued from."""
    from benchmark import precision_control as pc
    got = pc.readings("ouro-2.6b", "serve", 2**31 + 353,
                      ["bfloat16", "float8_e4m3fn"], layers=2,
                      prompts=[16, 25], budget=10)
    own, below = got["bfloat16"], got["float8_e4m3fn"]
    assert own["logits_tol"] == adapter.LOGITS_TOL
    assert own["ok"] and 0.01 < own["logits_err_max"] < adapter.LOGITS_TOL
    assert not below["ok"]
    assert below["logits_err_max"] > 5 * own["logits_err_max"]


def _loop_body(mod):
    return inspect.getsource(mod.run).splitlines()


def test_the_copied_loop_differs_from_its_original_only_where_it_says():
    """``serve_model.run`` is ``serve.run`` copied (``serve.py`` is an
    accepted file and exposes none of its loop): until a ``benchmark`` PR
    folds them (PERF.md section 7), a repair to one has to reach the other.
    What may differ is written down here, line for line: the adapter's three
    calls, the cache in the printed line, and the profiler stopped at the
    window's end instead of after the drain."""
    serve = resolve.load_module("runners", "serve")
    diff = [ln for ln in difflib.unified_diff(
        _loop_body(serve), _loop_body(runner), lineterm="", n=0)
        if not ln.startswith(("---", "+++", "@@"))]
    with open(os.path.join(ROOT, "benchmark/tests/data",
                           "serve_model_vs_serve.txt")) as fh:
        assert diff == fh.read().splitlines()


def test_looped_step_counts_at_the_published_widths():
    w = dict(hidden=2048, ffn=5632, heads=16, kv_heads=16, head_dim=128)
    # 4 * 2048^2 + 3 * 2048 * 5632 + 4 * 2048 (ISSUE 28)
    assert lf.looped_layer_params(**w) == 51_388_416
    assert lf.looped_layer_params(**w, norms=2) == 51_388_416 - 4096
    kvpp = 2 * 16 * 128 * 2 * 48             # K and V, bf16, 48 cache layers
    assert kvpp == 393_216
    got = lf.looped_decode_step(layers=12, loop_steps=4, vocab=49152,
                                rows=8, live_positions=2800,
                                kv_bytes_per_position=kvpp, **w)
    weights = 2 * (4 * (12 * 51_388_416 + 2048) + 2048 * 49152 + 8 * 2048)
    assert got["weight_bytes"] == weights == 5_134_663_680
    assert got["kv_bytes"] == 2800 * kvpp
    assert got["bytes"] == weights + 2800 * kvpp
    mats = 51_388_416 - 8192
    assert got["flops"] == (2 * 8 * (48 * mats + 2048 * 49152)
                            + 4 * 16 * 128 * 2800 * 48)
    # one pass over a pre-norm block is the plain decoder's step
    one = lf.looped_decode_step(layers=12, loop_steps=1, vocab=49152,
                                rows=8, live_positions=0,
                                kv_bytes_per_position=0, norms=2, **w)
    assert one["weight_bytes"] == 2 * (12 * (mats + 4096) + 2048
                                       + 2048 * 49152 + 8 * 2048)


def _metrics(chunks, live, occ_sum, **extra):
    return {"chunk_dispatches": chunks, "live_kv_positions_total": live,
            "occupancy_mean": occ_sum / chunks if chunks else 0.0,
            "occupancy_samples": chunks, **extra}


def _trace(runs_ms, name="jit_ring_chunk_decode(123)"):
    t, mods = 1_000_000, []
    for ms in runs_ms:
        mods.append((name, t, int(ms * 1e6), ""))
        t += int(ms * 1e6) + 1000
    return {"devices": {0: {"modules": mods, "ops": []}},
            "host": [(WINDOW_SPAN, 0, t + 10, 0)]}


def test_loop_step_roofline_on_hand_made_counters():
    arch = adapter.arch_of(CONFIG)
    section = CONFIG["sections"]["serve"]
    loop = {"cache_bytes_per_position": 393_216}
    ctx = {"trace": _trace([224.0, 226.0, 225.0]), "arch": arch,
           "section": section, "peaks": peaks("TPU v5 lite"),
           "engine": {"before": _metrics(10, 28_000, 10.0, **loop),
                      "after": _metrics(110, 308_000, 110.0, **loop)}}
    args = {"module": "^jit_ring_chunk_decode", "norms": 4}
    got = reader.read(ctx, **args)
    # 2800 live positions a chunk, all 8 slots occupied, 14.0625 ms a step
    need = 5_134_663_680 + 2800 * 393_216
    assert got == pytest.approx(100 * (need / 819e9) / (0.225 / 16))
    assert 0 < got < 100
    note = ctx["notes"][0]
    assert "memory-bound" in note and "2800.0 live positions" in note
    # as the metric's own file asks for it
    with open(os.path.join(ROOT, "benchmark/layer_metrics",
                           "loop_step_roofline.json")) as fh:
        assert json.load(fh)["args"] == args
    # a program without the counters (the parent), no trace, or a trace
    # without the chunk program: nothing to read, and no error
    bare = {**ctx, "engine": {"before": _metrics(10, 28_000, 10.0),
                              "after": _metrics(110, 308_000, 110.0)}}
    assert reader.read(bare, **args) is None
    assert reader.read({**ctx, "trace": None}, **args) is None
    assert reader.read({**ctx, "trace": _trace([5.0], "jit_other")},
                       **args) is None
