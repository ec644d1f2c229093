"""AOT predictor bundles (round-4 VERDICT item 3): save in one process,
load in a FRESH subprocess with no model Python, get batched predict and
greedy generate parity with the in-process paths.

Reference: paddle/fluid/inference/api/analysis_predictor.h +
paddle_analysis_config.h (configurable predictor over an exported
artifact, named IO, multiple entries, shape buckets).
"""

import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import nn


def _run_fresh(code: str) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


def test_predict_bundle_subprocess_parity(tmp_path):
    paddle.seed(0)
    net = nn.Sequential(nn.Linear(8, 16), nn.ReLU(), nn.Linear(16, 4))
    net.eval()
    x = np.random.default_rng(0).standard_normal((4, 8)).astype(np.float32)
    expect = net(paddle.to_tensor(x)).numpy()

    from paddle_tpu.inference import export_predict_bundle
    bdir = str(tmp_path / "bundle")
    export_predict_bundle(net, [x], bdir, input_names=["features"],
                          output_names=["logits"], extra_batch_sizes=[2])
    meta = json.load(open(os.path.join(bdir, "bundle.json")))
    assert meta["inputs"] == ["features"]
    assert len(meta["buckets"]) == 2

    np.save(tmp_path / "x.npy", x)
    np.save(tmp_path / "expect.npy", expect)
    # fresh process: ONLY the inference surface is imported — loading
    # must not need the model class or state dict
    code = textwrap.dedent(f"""
        import numpy as np
        import jax; jax.config.update("jax_platforms", "cpu")
        from paddle_tpu.inference import Config, create_predictor
        cfg = Config()
        cfg.set_aot_bundle({bdir!r})
        pred = create_predictor(cfg)
        assert pred.get_input_names() == ["features"]
        assert pred.get_output_names() == ["logits"]
        x = np.load({str(tmp_path / 'x.npy')!r})
        h = pred.get_input_handle("features")
        h.copy_from_cpu(x)
        pred.run()
        out = pred.get_output_handle("logits").copy_to_cpu()
        np.testing.assert_allclose(
            out, np.load({str(tmp_path / 'expect.npy')!r}),
            rtol=1e-5, atol=1e-5)
        # second bucket (B=2) serves too
        out2 = pred.run([x[:2]])[0]
        np.testing.assert_allclose(
            out2, np.load({str(tmp_path / 'expect.npy')!r})[:2],
            rtol=1e-5, atol=1e-5)
        # B=3 has no exact bucket: round 5 pads to the nearest (B=4)
        # bucket and trims, instead of erroring
        out3 = pred.run([x[:3]])[0]
        np.testing.assert_allclose(
            out3, np.load({str(tmp_path / 'expect.npy')!r})[:3],
            rtol=1e-5, atol=1e-5)
        # a genuinely unservable shape still errors clearly
        try:
            pred.run([np.zeros((3, 9), np.float32)])
            raise SystemExit("bucket miss should raise")
        except ValueError as e:
            assert "bucket" in str(e)
        print("PREDICT_OK")
    """)
    assert "PREDICT_OK" in _run_fresh(code)


@pytest.mark.slow
def test_decoder_bundle_subprocess_generate_parity(tmp_path):
    from paddle_tpu.inference import export_decoder_bundle
    from paddle_tpu.inference.generate import LlamaDecoder
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM

    cfg = LlamaConfig(vocab_size=128, hidden_size=32, intermediate_size=64,
                      num_hidden_layers=2, num_attention_heads=4,
                      num_key_value_heads=4, max_position_embeddings=64)
    paddle.seed(3)
    model = LlamaForCausalLM(cfg)
    dec = LlamaDecoder(model, max_len=64)
    rng = np.random.default_rng(1)
    ids = rng.integers(0, cfg.vocab_size, (2, 8)).astype(np.int64)
    expect = dec.generate(ids, max_new_tokens=6)

    bdir = str(tmp_path / "dec_bundle")
    export_decoder_bundle(dec, bdir, prompt_lens=[8], decode_steps=[5, 16],
                          batch_sizes=[2])
    np.save(tmp_path / "ids.npy", ids)
    np.save(tmp_path / "expect.npy", expect)

    code = textwrap.dedent(f"""
        import numpy as np
        import jax; jax.config.update("jax_platforms", "cpu")
        from paddle_tpu.inference import Config, create_predictor
        cfg = Config()
        cfg.set_aot_bundle({bdir!r})
        pred = create_predictor(cfg)
        ids = np.load({str(tmp_path / 'ids.npy')!r})
        out = pred.generate(ids, max_new_tokens=6)
        np.testing.assert_array_equal(
            out, np.load({str(tmp_path / 'expect.npy')!r}))
        # larger decode bucket (16 >= 9) serves a longer request, trimmed
        out10 = pred.generate(ids, max_new_tokens=10)
        assert out10.shape == (2, 18)
        assert (out10[:, :14] == out[:, :14]).all()
        print("GENERATE_OK")
    """)
    assert "GENERATE_OK" in _run_fresh(code)


def test_int8_decoder_bundle_subprocess_parity(tmp_path):
    """Round-5 VERDICT item 6: the int8 weight-only decode path exports
    into an AOT bundle (quantized params baked into the modules) and a
    fresh process with zero model Python serves it bit-exactly."""
    from paddle_tpu.inference import export_decoder_bundle
    from paddle_tpu.inference.generate import LlamaDecoder
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM

    cfg = LlamaConfig(vocab_size=128, hidden_size=32, intermediate_size=64,
                      num_hidden_layers=2, num_attention_heads=4,
                      num_key_value_heads=4, max_position_embeddings=64)
    paddle.seed(7)
    model = LlamaForCausalLM(cfg)
    dec = LlamaDecoder(model, max_len=64, weight_dtype="int8")
    assert any(k.endswith(":int8") for k in dec.params)
    rng = np.random.default_rng(2)
    ids = rng.integers(0, cfg.vocab_size, (2, 8)).astype(np.int64)
    expect = dec.generate(ids, max_new_tokens=6)

    bdir = str(tmp_path / "int8_bundle")
    export_decoder_bundle(dec, bdir, prompt_lens=[8], decode_steps=[5],
                          batch_sizes=[2])
    import json
    with open(bdir + "/bundle.json") as f:
        assert json.load(f)["weight_dtype"] == "int8"
    np.save(tmp_path / "ids.npy", ids)
    np.save(tmp_path / "expect.npy", expect)

    code = textwrap.dedent(f"""
        import numpy as np
        import jax; jax.config.update("jax_platforms", "cpu")
        from paddle_tpu.inference import Config, create_predictor
        cfg = Config()
        cfg.set_aot_bundle({bdir!r})
        pred = create_predictor(cfg)
        ids = np.load({str(tmp_path / 'ids.npy')!r})
        out = pred.generate(ids, max_new_tokens=6)
        np.testing.assert_array_equal(
            out, np.load({str(tmp_path / 'expect.npy')!r}))
        print("INT8_GENERATE_OK")
    """)
    assert "INT8_GENERATE_OK" in _run_fresh(code)


def test_predictor_ergonomics_padding_warmup_memory(tmp_path):
    """Round-5 VERDICT item 8: nearest-bucket batch padding (a batch of 3
    served against a B=8 bucket, outputs trimmed), warmup-on-load, input
    dtype coercion, and memory reporting."""
    import paddle_tpu.nn as nn
    from paddle_tpu.inference import (AotPredictor, Config,
                                      create_predictor,
                                      export_predict_bundle)

    paddle.seed(11)
    net = nn.Sequential(nn.Linear(4, 8), nn.ReLU(), nn.Linear(8, 2))
    x8 = np.random.default_rng(0).normal(size=(8, 4)).astype(np.float32)
    bdir = str(tmp_path / "ergo_bundle")
    export_predict_bundle(net, [x8], bdir, input_names=["x"],
                          output_names=["y"])

    cfg = Config()
    cfg.set_aot_bundle(bdir)
    cfg.enable_warmup()
    pred = create_predictor(cfg)

    # batch 3 against the B=8 bucket: padded up, trimmed back, correct
    x3 = x8[:3]
    out = pred._aot.run({"x": x3})
    ref = net(paddle.to_tensor(x3)).numpy()
    np.testing.assert_allclose(out["y"], ref, rtol=1e-5, atol=1e-6)
    assert out["y"].shape == (3, 2)
    assert pred._aot.padded_calls == 1

    # dtype coercion: float64 feed serves against the float32 bucket
    out64 = pred._aot.run({"x": x8.astype(np.float64)})
    np.testing.assert_allclose(out64["y"],
                               net(paddle.to_tensor(x8)).numpy(),
                               rtol=1e-5, atol=1e-6)

    # memory report sizes the artifact
    rep = pred.memory_report()
    assert rep["artifact_bytes"] > 0
    assert all(v > 0 for v in rep["entries_bytes"].values())

    # a shape that can't pad (different feature dim) still errors clearly
    with pytest.raises(ValueError, match="no shape bucket"):
        pred._aot.run({"x": np.zeros((3, 5), np.float32)})


def test_decoder_generate_batch_padding(tmp_path):
    """generate() with a smaller batch than any bucket pads the prompt
    rows and trims the result — per-row outputs must equal the full-batch
    serve of the same rows (greedy decode rows are independent)."""
    from paddle_tpu.inference import AotPredictor, export_decoder_bundle
    from paddle_tpu.inference.generate import LlamaDecoder
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM

    cfg = LlamaConfig(vocab_size=64, hidden_size=16, intermediate_size=32,
                      num_hidden_layers=2, num_attention_heads=2,
                      num_key_value_heads=2, max_position_embeddings=32)
    paddle.seed(5)
    model = LlamaForCausalLM(cfg)
    dec = LlamaDecoder(model, max_len=32)
    bdir = str(tmp_path / "pad_bundle")
    export_decoder_bundle(dec, bdir, prompt_lens=[4], decode_steps=[4],
                          batch_sizes=[8])
    pred = AotPredictor(bdir)
    rng = np.random.default_rng(3)
    ids8 = rng.integers(0, cfg.vocab_size, (8, 4)).astype(np.int64)
    full = pred.generate(ids8, max_new_tokens=4)
    out3 = pred.generate(ids8[:3], max_new_tokens=4)
    assert out3.shape == (3, 8)
    np.testing.assert_array_equal(out3, full[:3])
    assert pred.padded_calls == 1


def test_decoder_bundle_multi_batch_and_limits(tmp_path):
    """Review fixes: every exported batch size is servable (per-B cache
    metadata), max_len overflow raises, and eos via the predictor serves
    the fused device-side stop (it used to raise NotImplementedError)."""
    from paddle_tpu.inference import AotPredictor, Config, \
        create_predictor, export_decoder_bundle
    from paddle_tpu.inference.generate import LlamaDecoder
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM

    cfg = LlamaConfig(vocab_size=64, hidden_size=16, intermediate_size=32,
                      num_hidden_layers=1, num_attention_heads=2,
                      num_key_value_heads=2, max_position_embeddings=32)
    paddle.seed(5)
    model = LlamaForCausalLM(cfg)
    dec = LlamaDecoder(model, max_len=32)
    bdir = str(tmp_path / "b")
    export_decoder_bundle(dec, bdir, prompt_lens=[4], decode_steps=[4],
                          batch_sizes=[1, 3])
    pred = AotPredictor(bdir)
    rng = np.random.default_rng(2)
    for B in (1, 3):
        ids = rng.integers(0, 64, (B, 4)).astype(np.int64)
        out = pred.generate(ids, max_new_tokens=5)
        np.testing.assert_array_equal(
            out, dec.generate(ids, max_new_tokens=5))
    with pytest.raises(ValueError, match="max_len"):
        pred.generate(np.zeros((1, 4), np.int64), max_new_tokens=40)
    # B=2 between the exported 1 and 3: round 5 pads to the B=3 bucket
    ids2 = rng.integers(0, 64, (2, 4)).astype(np.int64)
    np.testing.assert_array_equal(
        pred.generate(ids2, max_new_tokens=5),
        dec.generate(ids2, max_new_tokens=5))
    # a prompt length with no bucket still errors clearly
    with pytest.raises(ValueError, match="prefill bucket"):
        pred.generate(np.zeros((1, 6), np.int64), max_new_tokens=5)
    c = Config()
    c.set_aot_bundle(bdir)
    p = create_predictor(c)
    # eos through the Config/Predictor surface rides the fused device-side
    # stop: exact parity with the in-process decoder
    ids3 = rng.integers(0, 64, (1, 4)).astype(np.int64)
    eos = int(dec.generate(ids3, max_new_tokens=5)[0, -2])
    np.testing.assert_array_equal(
        p.generate(ids3, max_new_tokens=5, eos_token_id=eos),
        dec.generate(ids3, max_new_tokens=5, eos_token_id=eos))


def test_padded_run_preserves_non_batch_output(tmp_path):
    """ADVICE r6 (low): in the nearest-bucket padded run() path, a
    NON-batch output whose leading dim coincidentally equals the padded
    batch must not be trimmed — the exporter records which outputs are
    batch-major (abstract re-trace at a second batch) and run() trims
    only those."""
    from paddle_tpu.inference import AotPredictor, export_predict_bundle

    NB = 8  # the only bucket: non-batch output's leading dim == NB

    class WithTable(nn.Layer):
        def __init__(self):
            super().__init__()
            self.fc = nn.Linear(4, 4)
            # a (NB, 3) parameter returned AS-IS: not batch-major, but its
            # leading dim equals the padded bucket batch
            self.table = self.create_parameter(
                [NB, 3], default_initializer=nn.initializer.Constant(2.0))

        def forward(self, x):
            return self.fc(x), self.table * 1.0

    paddle.seed(0)
    net = WithTable()
    net.eval()
    x8 = np.random.default_rng(0).standard_normal((NB, 4)).astype(np.float32)
    bdir = str(tmp_path / "bundle")
    export_predict_bundle(net, [x8], bdir, input_names=["x"],
                          output_names=["y", "table"])
    meta = json.load(open(os.path.join(bdir, "bundle.json")))
    assert meta["output_batch_major"] == [True, False]

    pred = AotPredictor(bdir)
    x3 = x8[:3]
    out = pred.run({"x": x3})                    # pads 3 -> 8
    assert pred.padded_calls == 1
    assert out["y"].shape == (3, 4)              # batch output trimmed
    assert out["table"].shape == (NB, 3)         # non-batch PRESERVED
    np.testing.assert_allclose(out["table"], np.full((NB, 3), 2.0))
    ref = net(paddle.to_tensor(x3))[0].numpy()
    np.testing.assert_allclose(out["y"], ref, rtol=1e-5, atol=1e-6)

    # a legacy bundle (no batch-axis metadata) must refuse padded serving
    # instead of guessing
    meta.pop("output_batch_major")
    json.dump(meta, open(os.path.join(bdir, "bundle.json"), "w"))
    legacy = AotPredictor(bdir)
    with pytest.raises(ValueError, match="batch-axis metadata"):
        legacy.run({"x": x3})
    # exact-shape serving still fine
    assert legacy.run({"x": x8})["y"].shape == (NB, 4)


def test_decoder_bundle_sampled_and_eos_fused(tmp_path):
    """Fused-decode bundle entries: eos id + RNG key are runtime inputs
    (one entry serves any eos/seed), sampling statics are baked at export
    and enforced; outputs match the in-process fused decoder exactly."""
    from paddle_tpu.inference import AotPredictor, export_decoder_bundle
    from paddle_tpu.inference.generate import LlamaDecoder
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM

    paddle.seed(0)
    model = LlamaForCausalLM(LlamaConfig(
        vocab_size=64, hidden_size=32, intermediate_size=64,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=64))
    dec = LlamaDecoder(model, max_len=32)
    prompt = np.random.default_rng(0).integers(0, 64, (2, 5))

    sdir = str(tmp_path / "sampled")
    export_decoder_bundle(dec, sdir, prompt_lens=[5], decode_steps=[9],
                          batch_sizes=[2], do_sample=True,
                          temperature=0.8, top_k=8)
    pred = AotPredictor(sdir)
    meta = json.load(open(os.path.join(sdir, "bundle.json")))
    assert meta["decode_mode"]["do_sample"] is True

    out = pred.generate(prompt, max_new_tokens=10, do_sample=True, seed=3)
    ref = dec.generate(prompt, max_new_tokens=10, do_sample=True,
                       temperature=0.8, top_k=8, seed=3)
    np.testing.assert_array_equal(out, ref)
    # a different seed diverges through the SAME exported module
    out2 = pred.generate(prompt, max_new_tokens=10, do_sample=True, seed=4)
    assert not np.array_equal(out, out2)
    # greedy request against a sampled bundle is a contract violation
    with pytest.raises(ValueError, match="do_sample"):
        pred.generate(prompt, max_new_tokens=4)

    # eos as a runtime input on a GREEDY fused bundle: early rows freeze,
    # output trimmed exactly like the in-process path
    gdir = str(tmp_path / "greedy")
    export_decoder_bundle(dec, gdir, prompt_lens=[5], decode_steps=[9],
                          batch_sizes=[2])
    pg = AotPredictor(gdir)
    free = dec.generate(prompt, max_new_tokens=10)
    eos = int(free[0, 6])                 # forces an early stop in row 0
    out_e = pg.generate(prompt, max_new_tokens=10, eos_token_id=eos)
    ref_e = dec.generate(prompt, max_new_tokens=10, eos_token_id=eos)
    np.testing.assert_array_equal(out_e, ref_e)


def _tiny_decoder(seed=0, max_len=32):
    from paddle_tpu.inference.generate import LlamaDecoder
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM

    paddle.seed(seed)
    model = LlamaForCausalLM(LlamaConfig(
        vocab_size=64, hidden_size=32, intermediate_size=64,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=64))
    return LlamaDecoder(model, max_len=max_len)


def test_speculative_decoder_bundle_parity_and_stats(tmp_path):
    """Speculative AOT bundle: the export carries draft prefill entries +
    draft cache metadata, ``decode_mode`` records the speculation
    statics, and serving is draft-prefill + prefill + ONE decode module
    execution with exact token parity against the in-process speculative
    decoder (greedy speculation == plain greedy, so the bundle's output
    must also equal a non-speculative greedy serve)."""
    from paddle_tpu.inference import AotPredictor, export_decoder_bundle

    dec = _tiny_decoder(21)
    prompt = np.random.default_rng(0).integers(0, 64, (2, 5))
    bdir = str(tmp_path / "spec")
    export_decoder_bundle(dec, bdir, prompt_lens=[5], decode_steps=[8],
                          batch_sizes=[2], draft_model="skip:1",
                          num_speculative_tokens=2)
    meta = json.load(open(os.path.join(bdir, "bundle.json")))
    assert meta["decode_mode"]["speculative"] == {
        "num_speculative_tokens": 2, "draft": "skip:1", "draft_layers": 1}
    assert meta["decode_mode"]["temperature"] == "runtime"
    assert meta["draft_prefill_buckets"] == [
        {"file": "draft_prefill_b2_s5.aot", "batch": 2, "seq": 5}]
    assert "2" in meta["draft_caches"]
    assert meta["decode_buckets"][0]["speculative"] is True

    pred = AotPredictor(bdir, warmup=False)
    out = pred.generate(prompt, max_new_tokens=8)
    ref = dec.generate(prompt, max_new_tokens=8, draft_model="skip:1",
                       num_speculative_tokens=2)
    np.testing.assert_array_equal(out, ref)
    np.testing.assert_array_equal(out, dec.generate(prompt,
                                                    max_new_tokens=8))
    stats = pred.last_spec_stats
    assert stats["num_speculative_tokens"] == 2
    assert stats["rounds"] > 0
    assert 0.0 <= stats["acceptance_len_mean"] <= 2.0

    # eos as a runtime input through the speculative entry (and the
    # negative-id "none" convention)
    free = dec.generate(prompt, max_new_tokens=8)
    eos = int(free[0, 7])
    out_e = pred.generate(prompt, max_new_tokens=8, eos_token_id=eos)
    ref_e = dec.generate(prompt, max_new_tokens=8, eos_token_id=eos,
                         draft_model="skip:1", num_speculative_tokens=2)
    np.testing.assert_array_equal(out_e, ref_e)
    np.testing.assert_array_equal(
        pred.generate(prompt, max_new_tokens=8, eos_token_id=-1), out)

    # speculative buckets serve max_new_tokens <= steps (the buffer
    # size), not steps + 1
    with pytest.raises(ValueError, match="capacity"):
        pred.generate(prompt, max_new_tokens=9)
    # exporting with K but no draft is rejected
    with pytest.raises(ValueError, match="requires a draft_model"):
        export_decoder_bundle(dec, str(tmp_path / "bad"), prompt_lens=[5],
                              decode_steps=[8], batch_sizes=[2],
                              num_speculative_tokens=2)
    # and a bucket that could overshoot the cache is rejected up front
    with pytest.raises(ValueError, match="overshoot"):
        export_decoder_bundle(dec, str(tmp_path / "bad2"), prompt_lens=[5],
                              decode_steps=[30], batch_sizes=[2],
                              draft_model="skip:1",
                              num_speculative_tokens=2)


def test_decoder_bundle_runtime_temperature(tmp_path):
    """Satellite: temperature is a runtime input to exported decode
    entries — ONE sampled bundle serves any temperature (bit-exact with
    the in-process decoder at that temperature); a legacy bundle whose
    metadata still records a baked temperature refuses a mismatching
    request instead of silently serving the wrong distribution."""
    from paddle_tpu.inference import AotPredictor, export_decoder_bundle

    dec = _tiny_decoder(22)
    prompt = np.random.default_rng(1).integers(0, 64, (2, 5))
    bdir = str(tmp_path / "sampled")
    export_decoder_bundle(dec, bdir, prompt_lens=[5], decode_steps=[8],
                          batch_sizes=[2], do_sample=True,
                          temperature=0.8, top_k=8)
    meta = json.load(open(os.path.join(bdir, "bundle.json")))
    assert meta["decode_mode"]["temperature"] == "runtime"
    assert meta["decode_mode"]["default_temperature"] == 0.8

    pred = AotPredictor(bdir, warmup=False)
    for temp in (0.5, 1.3):
        out = pred.generate(prompt, max_new_tokens=8, do_sample=True,
                            temperature=temp, seed=3)
        ref = dec.generate(prompt, max_new_tokens=8, do_sample=True,
                           temperature=temp, top_k=8, seed=3)
        np.testing.assert_array_equal(out, ref, err_msg=str(temp))
    # no temperature passed: the export-time value is the default
    np.testing.assert_array_equal(
        pred.generate(prompt, max_new_tokens=8, do_sample=True, seed=4),
        dec.generate(prompt, max_new_tokens=8, do_sample=True,
                     temperature=0.8, top_k=8, seed=4))

    # legacy static-temperature metadata: asking for a different value
    # is a contract violation (re-export, don't mis-serve)
    meta["decode_mode"]["temperature"] = 0.8
    del meta["decode_mode"]["default_temperature"]
    json.dump(meta, open(os.path.join(bdir, "bundle.json"), "w"))
    legacy = AotPredictor(bdir, warmup=False)
    with pytest.raises(ValueError, match="re-export"):
        legacy.generate(prompt, max_new_tokens=8, do_sample=True,
                        temperature=1.3, seed=3)


@pytest.mark.parametrize("layout,n_buffers,shape", [
    ("per_layer", 3, (2, 2, 16, 8)),
    ("per_layer", 1, (2, 2, 16, 8)),       # a one-layer model or draft
    ("stacked", 1, (3, 2, 2, 16, 8)),      # a bundle from before PR 27
])
def test_bundle_runtime_rebuilds_the_recorded_carry(layout, n_buffers,
                                                    shape):
    """The serving process builds the KV carry with the pytree structure
    the bundle's programs were exported with: a tuple of ``n_buffers``
    per-layer buffers — also when that is one — and, for a bundle written
    when the carry was one array stacked over layers, that one array."""
    from paddle_tpu.inference.bundle import AotPredictor

    pred = AotPredictor.__new__(AotPredictor)
    pred._sharding = None
    pred.meta = {"max_len": 16, "caches": {"2": {
        "n_buffers": n_buffers, "layout": layout, "shape": list(shape),
        "dtype": "float32"}}}
    for cache in pred._make_cache(2):
        if layout == "stacked":
            assert cache.shape == shape
        else:
            assert isinstance(cache, tuple)
            assert [b.shape for b in cache] == [shape] * n_buffers
