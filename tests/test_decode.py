"""KV-cache decode + AOT export (VERDICT round-2 item 8).

Reference capability: block_multi_head_attention_kernel.cu (cached decode
attention) + analysis_predictor.h (load-and-run without rebuilding)."""

import os
import subprocess
import sys

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.inference.generate import LlamaDecoder
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM

CFG = dict(vocab_size=64, hidden_size=32, intermediate_size=64,
           num_hidden_layers=2, num_attention_heads=4,
           num_key_value_heads=2, max_position_embeddings=64)


def _model(seed=0):
    paddle.seed(seed)
    return LlamaForCausalLM(LlamaConfig(**CFG))


def test_cached_decode_matches_naive_and_never_retraces():
    model = _model()
    dec = LlamaDecoder(model, max_len=32)
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, 64, (2, 5))
    out = dec.generate(prompt, max_new_tokens=6)
    assert out.shape == (2, 11)
    assert dec.trace_count == 2, "exactly one prefill + one step trace"

    ids = prompt.copy()
    for _ in range(6):
        logits = model(paddle.to_tensor(ids)).numpy()
        ids = np.concatenate([ids, logits[:, -1].argmax(-1)[:, None]], axis=1)
    np.testing.assert_array_equal(out, ids)

    # second generate with the same shapes: zero new traces
    dec.generate(prompt, max_new_tokens=6)
    assert dec.trace_count == 2


def test_decode_gqa_and_eos():
    model = _model(1)
    dec = LlamaDecoder(model, max_len=32)
    prompt = np.array([[1, 2, 3]])
    out = dec.generate(prompt, max_new_tokens=20, eos_token_id=None)
    assert out.shape == (1, 23)
    # eos early stop
    first = dec.generate(prompt, max_new_tokens=20)[0, 3]
    out2 = dec.generate(prompt, max_new_tokens=20, eos_token_id=int(first))
    assert out2.shape[1] < 23


def test_predictor_generate():
    from paddle_tpu.inference import Config, create_predictor
    model = _model(2)
    cfg = Config()
    cfg.set_layer(model)
    pred = create_predictor(cfg)
    out = pred.generate(np.array([[1, 2, 3]]), max_new_tokens=4, max_len=16)
    assert out.shape == (1, 7)


def test_aot_export_fresh_process_no_retrace(tmp_path):
    """save -> load in a FRESH process (model code never re-imported or
    re-traced) -> identical logits."""
    import jax.numpy as jnp
    from paddle_tpu.inference import save_compiled

    model = _model(3)
    x = np.arange(6, dtype=np.int64).reshape(1, 6) % 64
    ref = model(paddle.to_tensor(x)).numpy()

    from paddle_tpu.autograd import tape

    def fwd(ids):
        with tape.no_grad():
            return model(paddle.to_tensor(ids)).value

    path = str(tmp_path / "llama.ptpu-aot")
    save_compiled(fwd, [jnp.asarray(x)], path)

    runner = tmp_path / "runner.py"
    runner.write_text(f"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys
sys.path.insert(0, {os.path.dirname(os.path.dirname(os.path.abspath(__file__)))!r})
import jax; jax.config.update("jax_platforms", "cpu")
import numpy as np
# NOTE: only the AOT loader is imported -- no model classes, no tracing
from paddle_tpu.inference.aot import load_compiled
fn = load_compiled({path!r})
x = np.arange(6, dtype=np.int64).reshape(1, 6) % 64
out = fn(x)
np.save({str(tmp_path / "out.npy")!r}, np.asarray(out))
print("AOT_RUN_OK")
""")
    r = subprocess.run([sys.executable, str(runner)], capture_output=True,
                       text=True, timeout=300)
    assert "AOT_RUN_OK" in r.stdout, r.stderr[-2000:]
    got = np.load(tmp_path / "out.npy")
    np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-6)


def test_eos_per_row_pinning():
    """Rows that hit eos early are pinned to eos while other rows continue
    (batched stopping semantics)."""
    model = _model(4)
    dec = LlamaDecoder(model, max_len=32)
    prompt = np.array([[1, 2, 3], [4, 5, 6]])
    free = dec.generate(prompt, max_new_tokens=8)
    # pick row 0's first generated token as the "eos" so it stops at step 1
    eos = int(free[0, 3])
    out = dec.generate(prompt, max_new_tokens=8, eos_token_id=eos)
    row0 = out[0, 3:]
    # after row 0's first eos, everything is pinned to eos
    first_eos = np.argmax(row0 == eos)
    assert row0[first_eos] == eos
    assert np.all(row0[first_eos:] == eos)
    # row 1 keeps decoding its own argmax sequence until it hits eos or ends
    row1 = out[1, 3:]
    upto = np.argmax(row1 == eos) if (row1 == eos).any() else len(row1)
    np.testing.assert_array_equal(row1[:upto], free[1, 3:3 + upto])


def test_sampled_decode_topk_topp():
    """Sampling surface: temperature/top-k/top-p filtered categorical
    (reference fused generation-op sampling analog)."""
    model = _model(5)
    dec = LlamaDecoder(model, max_len=32)
    prompt = np.array([[1, 2, 3], [4, 5, 6]])
    out = dec.generate(prompt, max_new_tokens=6, do_sample=True,
                       temperature=0.8, top_k=8, seed=1)
    assert out.shape == (2, 9)
    assert np.all((out >= 0) & (out < 64))
    # determinism under the same seed
    out2 = dec.generate(prompt, max_new_tokens=6, do_sample=True,
                        temperature=0.8, top_k=8, seed=1)
    np.testing.assert_array_equal(out, out2)
    # different seeds diverge (overwhelmingly likely over 12 draws)
    out3 = dec.generate(prompt, max_new_tokens=6, do_sample=True,
                        temperature=0.8, top_k=8, seed=2)
    assert not np.array_equal(out, out3)
    # top-p path runs
    out4 = dec.generate(prompt, max_new_tokens=4, do_sample=True,
                        top_p=0.9, seed=3)
    assert out4.shape == (2, 7)
    # temperature -> 0 approaches greedy
    greedy = dec.generate(prompt, max_new_tokens=6)
    cold = dec.generate(prompt, max_new_tokens=6, do_sample=True,
                        temperature=1e-4, seed=4)
    np.testing.assert_array_equal(greedy, cold)


@pytest.mark.slow
def test_model_generate_api_llama_and_gpt():
    """GenerationMixin surface: model.generate on both families; Llama
    rides the KV-cache decoder, GPT the no-cache fallback — same tokens."""
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM

    model = _model(6)
    prompt = np.array([[1, 2, 3]])
    out = model.generate(prompt, max_new_tokens=5)
    assert out.shape == (1, 8)
    # KV decoder and the generic no-cache fallback agree token-for-token
    from paddle_tpu.nn.generation import generate_tokens
    ref = generate_tokens(model, prompt, max_new_tokens=5)
    np.testing.assert_array_equal(out, ref)

    paddle.seed(7)
    gpt = GPTForCausalLM(GPTConfig(
        vocab_size=64, hidden_size=32, num_hidden_layers=2,
        num_attention_heads=4, intermediate_size=64,
        max_position_embeddings=32, dropout=0.0))
    gpt.eval()
    gout = gpt.generate(prompt, max_new_tokens=4)
    assert gout.shape == (1, 7)
    assert np.all((gout >= 0) & (gout < 64))


def test_int8_weight_only_decoder_runs_and_tracks_full_precision():
    """weight_dtype='int8' decoder: logits stay close to the bf16 path
    (per-channel int8 round-trip error), shapes/compile behavior intact."""
    import jax.numpy as jnp
    from paddle_tpu.inference.generate import LlamaDecoder

    cfg = LlamaConfig(**CFG)
    model = _model()
    B, S = 2, 8
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, cfg.vocab_size, (B, S))

    full = LlamaDecoder(model, max_len=32)
    q = LlamaDecoder(model, max_len=32, weight_dtype="int8")
    kc, vc = full._empty_cache(B)
    lf, _, _ = full._prefill(full.params, jnp.asarray(prompt), kc, vc)
    kc, vc = q._empty_cache(B)
    lq, _, _ = q._prefill(q.params, jnp.asarray(prompt), kc, vc)
    lf, lq = np.asarray(lf), np.asarray(lq)
    # int8 weight round-trip: logits correlate strongly with full precision
    corr = np.corrcoef(lf.ravel(), lq.ravel())[0, 1]
    assert corr > 0.99, corr

    out = q.generate(prompt, max_new_tokens=4)
    assert out.shape == (B, S + 4)

    with pytest.raises(ValueError):
        LlamaDecoder(model, max_len=32, weight_dtype="int4")


@pytest.mark.slow
def test_beam_search_k1_equals_greedy_and_backtrace_consistent():
    from paddle_tpu.nn.generation import beam_search, generate_tokens

    model = _model()
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, CFG["vocab_size"], (2, 6))

    greedy = generate_tokens(model, prompt, max_new_tokens=5)
    beam1 = beam_search(model, prompt, beam_size=1, max_new_tokens=5)
    np.testing.assert_array_equal(greedy, beam1)

    # k=4: the returned best hypothesis is a valid decode (finite path
    # log-prob, right shape). NOTE: "beam >= greedy score" is NOT a
    # theorem — the greedy prefix can be pruned mid-search — so it is
    # deliberately not asserted.
    import jax
    import jax.numpy as jnp
    from paddle_tpu.autograd import tape

    def path_logprob(seq):
        with tape.no_grad():
            logits = model(paddle.to_tensor(seq[None, :-1])).value
        lp = jax.nn.log_softmax(logits.astype(jnp.float32), -1)
        tgt = jnp.asarray(seq[1:])
        take = jnp.take_along_axis(lp[0, -5:], tgt[-5:, None], axis=1)
        return float(take.sum())

    beam4 = beam_search(model, prompt, beam_size=4, max_new_tokens=5)
    assert beam4.shape == (2, 11)
    for b in range(2):
        assert np.isfinite(path_logprob(beam4[b]))

    # max_new_tokens=0 returns the prompt unchanged (generate_tokens parity)
    np.testing.assert_array_equal(
        beam_search(model, prompt, beam_size=2, max_new_tokens=0), prompt)


def test_beam_search_eos_freezes_beams():
    from paddle_tpu.nn.generation import beam_search

    model = _model()
    rng = np.random.default_rng(1)
    prompt = rng.integers(0, CFG["vocab_size"], (1, 4))
    out = beam_search(model, prompt, beam_size=3, max_new_tokens=6,
                      eos_token_id=0)
    assert out.shape[1] <= 4 + 6
    # once eos appears in the chosen beam, everything after is eos
    seq = out[0, 4:]
    if (seq == 0).any():
        first = int(np.argmax(seq == 0))
        assert np.all(seq[first:] == 0)


@pytest.mark.parametrize("kv_heads", [2, 4], ids=["gqa", "mha"])
def test_serving_carry_and_ring_are_one_buffer_per_layer(kv_heads):
    """The KV carry and the admission ring hold one 4-D buffer per layer
    (head-major, GQA and MHA alike since PR 39) through a whole serve —
    six requests through two slots, so admissions land mid-stream and the
    rows of a chunk sit at different cache positions — and every request
    gets the tokens of the per-token reference."""
    from paddle_tpu.serving import ServingEngine

    paddle.seed(7)
    cfg = LlamaConfig(**{**CFG, "num_key_value_heads": kv_heads})
    dec = LlamaDecoder(LlamaForCausalLM(cfg), max_len=48)
    rng = np.random.default_rng(kv_heads)
    reqs = [(rng.integers(0, cfg.vocab_size, (n,)), new)
            for n, new in [(3, 9), (7, 5), (5, 12), (4, 6), (9, 8), (6, 10)]]
    want = _with_fallback(lambda: [np.asarray(dec.generate(p[None], new))
                                   for p, new in reqs])

    slots, D = 2, cfg.head_dim
    per_layer = (slots, kv_heads, 48, D)                      # head-major
    eng = ServingEngine(dec, num_slots=slots, chunk_size=4)
    rids = [eng.submit(p, new) for p, new in reqs]
    got, uneven = {}, False
    while len(got) < len(reqs):
        got.update(eng.step())
        for cache in (eng.state.kc, eng.state.vc,
                      eng._b._ring_kc, eng._b._ring_vc):
            assert isinstance(cache, tuple)
            assert [b.shape for b in cache] == \
                [per_layer] * cfg.num_hidden_layers
        pos = np.asarray(eng.state.pos)[~np.asarray(eng.state.done)]
        uneven = uneven or len(set(pos.tolist())) > 1
    assert uneven, "no chunk ran with its rows at different positions"
    m = eng.metrics()
    assert m["admission_ring"]["staged"] == len(reqs)
    assert m["admission_ring"]["host_scattered"] == 0
    for rid, ref in zip(rids, want):
        np.testing.assert_array_equal(np.asarray(got[rid]), ref)


def _with_fallback(fn):
    """Run fn under the per-token fallback flag (the debugging path the
    fused decode is verified against)."""
    from paddle_tpu.flags import flags
    flags.decode_fallback = True
    try:
        return fn()
    finally:
        flags.decode_fallback = False


def test_every_decode_mode_is_one_fused_dispatch():
    """Tentpole acceptance: greedy, greedy+eos, sampled and sampled+eos
    each execute the whole token loop in ONE device dispatch after the
    prefill (dispatch_count counts jit executions via a wrapper), and for
    a fixed seed every mode matches the per-token fallback path exactly —
    including the eos early-stop output length."""
    model = _model(5)
    dec = LlamaDecoder(model, max_len=32)
    prompt = np.array([[1, 2, 3], [4, 5, 6]])
    # an eos that actually fires early in row 0 (from the free-run tokens)
    eos = int(dec.generate(prompt, max_new_tokens=12)[0, 5])

    cases = [
        dict(),
        dict(eos_token_id=eos),
        dict(do_sample=True, temperature=0.8, top_k=8, seed=1),
        dict(do_sample=True, top_p=0.9, seed=3, eos_token_id=eos),
    ]
    for kw in cases:
        d0 = dec.dispatch_count
        fused = dec.generate(prompt, max_new_tokens=12, **kw)
        assert dec.dispatch_count - d0 == 2, \
            f"{kw}: expected prefill + one fused decode dispatch"
        ref = _with_fallback(
            lambda: dec.generate(prompt, max_new_tokens=12, **kw))
        assert fused.shape == ref.shape, kw
        np.testing.assert_array_equal(fused, ref, err_msg=str(kw))
    # the trim is actually exercised: a single row that hits eos early
    # yields a SHORTER output than max_new_tokens allows
    out_eos = dec.generate(prompt[:1], max_new_tokens=12, eos_token_id=eos)
    assert out_eos.shape[1] < 15
    ref_eos = _with_fallback(
        lambda: dec.generate(prompt[:1], max_new_tokens=12,
                             eos_token_id=eos))
    np.testing.assert_array_equal(out_eos, ref_eos)

    # fallback really is per-token: many dispatches, not 2
    d0 = dec.dispatch_count
    _with_fallback(lambda: dec.generate(prompt, max_new_tokens=6,
                                        do_sample=True, seed=0))
    assert dec.dispatch_count - d0 > 2


def test_fused_decode_zero_retrace_across_calls_and_seeds():
    """Seeds/eos ids are runtime inputs: repeat generates with different
    seeds and eos values reuse the SAME compiled fused program (zero new
    traces), per decode mode."""
    model = _model(6)
    dec = LlamaDecoder(model, max_len=32)
    prompt = np.array([[1, 2, 3]])
    dec.generate(prompt, max_new_tokens=8, do_sample=True, seed=0)
    dec.generate(prompt, max_new_tokens=8, eos_token_id=5)
    t0 = dec.trace_count
    dec.generate(prompt, max_new_tokens=8, do_sample=True, seed=7)
    dec.generate(prompt, max_new_tokens=8, do_sample=True, seed=8)
    dec.generate(prompt, max_new_tokens=8, eos_token_id=9)
    assert dec.trace_count == t0


def test_generate_tokens_fused_one_dispatch_and_parity():
    """nn.generation.generate_tokens on a Layer model: the whole no-cache
    token loop compiles into one dispatch (model.forward is never invoked
    after the first trace) and matches the per-token loop exactly."""
    from paddle_tpu.nn.generation import generate_tokens

    model = _model(7)
    prompt = np.array([[1, 2, 3], [7, 8, 9]])

    def both(kw):
        fused = generate_tokens(model, prompt, max_new_tokens=6, **kw)
        from paddle_tpu.flags import flags
        flags.decode_fallback = True
        try:
            ref = generate_tokens(model, prompt, max_new_tokens=6, **kw)
        finally:
            flags.decode_fallback = False
        assert fused.shape == ref.shape, kw
        np.testing.assert_array_equal(fused, ref, err_msg=str(kw))
        return fused

    both(dict())
    free = both(dict(do_sample=True, temperature=0.8, top_k=8, seed=2))
    eos = int(free[0, 4])
    both(dict(eos_token_id=eos))

    # compiled: a repeat call at the same shapes never invokes forward
    calls = {"n": 0}
    orig = model.forward

    def counting(*a, **k):
        calls["n"] += 1
        return orig(*a, **k)

    model.forward = counting
    try:
        generate_tokens(model, prompt, max_new_tokens=6)
    finally:
        model.forward = orig
    assert calls["n"] == 0, "fused generate_tokens re-ran the eager forward"


def test_speculative_decode_one_dispatch_and_parity_sweep():
    """Speculative tentpole acceptance: {greedy, temperature, top-k,
    top-p} x {eos, no-eos} x batch sizes, asserting (a) the fused
    speculative generate is prefill(target) + prefill(draft) + exactly
    ONE decode dispatch, (b) bit-exact token parity against the
    per-round speculative fallback, and (c) greedy speculative == the
    non-speculative fused greedy decode (speculation must be invisible
    in the output)."""
    model = _model(8)
    dec = LlamaDecoder(model, max_len=40)
    rng = np.random.default_rng(0)
    modes = [
        dict(),                                            # greedy
        dict(do_sample=True, temperature=0.7, seed=1),     # temperature
        dict(do_sample=True, temperature=0.9, top_k=8, seed=2),
        dict(do_sample=True, top_p=0.9, seed=3),
    ]
    for B in (1, 3):
        prompt = rng.integers(0, 64, (B, 5))
        plain = dec.generate(prompt, max_new_tokens=8)
        # an eos that actually fires early in row 0 of the greedy run
        eos_live = int(plain[0, 7])
        for kw in modes:
            for eos in (None, eos_live):
                kw = dict(kw, draft_model="skip:1",
                          num_speculative_tokens=2)
                if eos is not None:
                    kw["eos_token_id"] = eos
                d0 = dec.dispatch_count
                fused = dec.generate(prompt, max_new_tokens=8, **kw)
                assert dec.dispatch_count - d0 == 3, \
                    f"{kw}: expected 2 prefills + ONE decode dispatch"
                stats = dec.last_spec_stats
                assert stats["num_speculative_tokens"] == 2
                assert 0.0 <= stats["acceptance_len_mean"] <= 2.0
                ref = _with_fallback(
                    lambda: dec.generate(prompt, max_new_tokens=8, **kw))
                assert fused.shape == ref.shape, kw
                np.testing.assert_array_equal(fused, ref, err_msg=str(kw))
                if not kw.get("do_sample") and eos is None:
                    # greedy speculation preserves the target's argmax
                    # sequence exactly
                    np.testing.assert_array_equal(fused, plain)
        # the fallback really is per-round: more than 3 dispatches
        d0 = dec.dispatch_count
        _with_fallback(lambda: dec.generate(
            prompt, max_new_tokens=8, draft_model="skip:1",
            num_speculative_tokens=2))
        assert dec.dispatch_count - d0 > 3


def test_speculative_separate_draft_model():
    """A standalone smaller LlamaForCausalLM as the draft: same
    one-dispatch + fallback-parity contract as the layer-skip view."""
    model = _model(9)
    paddle.seed(10)
    draft = LlamaForCausalLM(LlamaConfig(**{**CFG, "num_hidden_layers": 1}))
    dec = LlamaDecoder(model, max_len=40)
    prompt = np.random.default_rng(1).integers(0, 64, (2, 4))
    d0 = dec.dispatch_count
    fused = dec.generate(prompt, max_new_tokens=8, draft_model=draft,
                         num_speculative_tokens=3)
    assert dec.dispatch_count - d0 == 3
    ref = _with_fallback(lambda: dec.generate(
        prompt, max_new_tokens=8, draft_model=draft,
        num_speculative_tokens=3))
    np.testing.assert_array_equal(fused, ref)
    # speculation never changes greedy output
    np.testing.assert_array_equal(fused, dec.generate(prompt,
                                                      max_new_tokens=8))


def test_speculative_validation_errors():
    model = _model(10)
    dec = LlamaDecoder(model, max_len=20)
    prompt = np.array([[1, 2, 3]])
    with pytest.raises(ValueError, match="skip"):
        dec.generate(prompt, max_new_tokens=4, draft_model="skip:0")
    with pytest.raises(ValueError, match="skip"):
        dec.generate(prompt, max_new_tokens=4, draft_model="skip:2")
    with pytest.raises(ValueError, match="draft_model must be"):
        dec.generate(prompt, max_new_tokens=4, draft_model="tiny")
    with pytest.raises(ValueError, match=">= 1"):
        dec.generate(prompt, max_new_tokens=4, draft_model="skip:1",
                     num_speculative_tokens=0)
    with pytest.raises(ValueError, match="requires a draft_model"):
        dec.generate(prompt, max_new_tokens=4, num_speculative_tokens=2)
    # speculative rounds can overshoot by K: the cache must have slack
    with pytest.raises(ValueError, match="slack"):
        dec.generate(prompt, max_new_tokens=17, draft_model="skip:1",
                     num_speculative_tokens=2)
    paddle.seed(11)
    bad_vocab = LlamaForCausalLM(LlamaConfig(**{**CFG, "vocab_size": 32}))
    with pytest.raises(ValueError, match="vocab"):
        dec.generate(prompt, max_new_tokens=4, draft_model=bad_vocab)


def test_chunked_speculative_slicing_invariance_greedy():
    """Tentpole: decode_chunk composes with speculation. Every
    chunk_size slicing of a speculative generate emits the fused
    one-dispatch speculative path's exact greedy stream (chunk
    boundaries never re-run or drop a verify round), each chunk
    dispatch commits at least chunk_size tokens (so the dispatch count
    never exceeds the plain chunked path's), and ``last_spec_stats``
    reports CUMULATIVE per-request totals across chunk re-entries."""
    model = _model(12)
    dec = LlamaDecoder(model, max_len=64)
    rng = np.random.default_rng(3)
    prompt = rng.integers(0, 64, (3, 5))
    kw = dict(draft_model="skip:1", num_speculative_tokens=2)
    fused = np.asarray(dec.generate(prompt, max_new_tokens=12, **kw))
    fstats = dec.last_spec_stats
    assert fstats["rounds"] > 0
    for T in (1, 2, 3, 5, 8, 12):
        d0 = dec.dispatch_count
        got = np.asarray(dec.generate(prompt, max_new_tokens=12,
                                      chunk_size=T, **kw))
        np.testing.assert_array_equal(got, fused, err_msg=f"T={T}")
        # 2 prefills + at most ceil(max_new/T) chunks — acceptance can
        # only SHRINK the chunk count, never grow it
        assert dec.dispatch_count - d0 <= 2 + -(-12 // T), f"T={T}"
        stats = dec.last_spec_stats
        assert stats["num_speculative_tokens"] == 2
        # cumulative across re-entries: never last-chunk-only (a single
        # chunk can hold at most T rounds of the total)
        assert stats["rounds"] >= fstats["rounds"], f"T={T}"
        assert stats["accepted_drafts"] >= fstats["accepted_drafts"]


def test_chunked_speculative_eos_mixed_rows():
    """Chunk-slicing invariance under speculation with an eos that
    fires EARLY in some rows and never in others: done rows hold the
    fill while live neighbours keep verifying, for every slicing."""
    model = _model(12)
    dec = LlamaDecoder(model, max_len=64)
    rng = np.random.default_rng(4)
    prompt = rng.integers(0, 64, (3, 4))
    plain = np.asarray(dec.generate(prompt, max_new_tokens=10))
    eos = int(plain[0, 6])
    kw = dict(draft_model="skip:1", num_speculative_tokens=2,
              eos_token_id=eos)
    fused = np.asarray(dec.generate(prompt, max_new_tokens=10, **kw))
    for T in (1, 3, 7, 10):
        got = np.asarray(dec.generate(prompt, max_new_tokens=10,
                                      chunk_size=T, **kw))
        np.testing.assert_array_equal(got, fused, err_msg=f"T={T}")


def test_chunked_speculative_sampled_slicing_invariance():
    """Sampled speculative chunking draws from PER-ROW key streams (the
    admission contract): every chunk_size slicing draws the SAME
    tokens — the per-row round sequence, and therefore the key stream,
    is continuous across chunk boundaries."""
    model = _model(12)
    dec = LlamaDecoder(model, max_len=64)
    rng = np.random.default_rng(5)
    prompt = rng.integers(0, 64, (2, 5))
    kw = dict(draft_model="skip:1", num_speculative_tokens=2,
              do_sample=True, top_k=8, temperature=0.8, seed=6)
    ref = np.asarray(dec.generate(prompt, max_new_tokens=10,
                                  chunk_size=1, **kw))
    for T in (2, 4, 7, 10):
        got = np.asarray(dec.generate(prompt, max_new_tokens=10,
                                      chunk_size=T, **kw))
        np.testing.assert_array_equal(got, ref, err_msg=f"T={T}")


def test_trim_after_eos_edge_cases():
    """Satellite: first-emitted-token-is-eos and negative-eos ("none")
    conventions are uniform across LlamaDecoder.generate,
    generate_tokens, and the trim helper itself."""
    from paddle_tpu.inference.generate import (_normalize_eos,
                                               _trim_after_eos)
    from paddle_tpu.nn.generation import generate_tokens

    # unit: a row whose FIRST token is eos contributes length 1, never 0
    toks = np.array([[7, 1, 2, 3]])
    np.testing.assert_array_equal(_trim_after_eos(toks, 7), [[7]])
    # no row hits eos: full length retained
    np.testing.assert_array_equal(_trim_after_eos(toks, 9), toks)
    # trim length is the LATEST first-eos across rows
    toks2 = np.array([[7, 7, 7, 7], [1, 2, 7, 7]])
    np.testing.assert_array_equal(_trim_after_eos(toks2, 7),
                                  toks2[:, :3])
    assert _normalize_eos(None) is None
    assert _normalize_eos(-1) is None
    assert _normalize_eos(-5) is None
    assert _normalize_eos(3) == 3

    model = _model(12)
    dec = LlamaDecoder(model, max_len=32)
    prompt = np.array([[1, 2, 3], [4, 5, 6]])
    free = dec.generate(prompt, max_new_tokens=8)
    # negative eos == None: the bundles' "-1 means no eos" convention
    np.testing.assert_array_equal(
        dec.generate(prompt, max_new_tokens=8, eos_token_id=-1), free)
    # eos == the very first emitted token of BOTH rows: output is
    # prompt + exactly one (eos) column, fused and fallback alike
    eos01 = int(free[0, 3])
    forced = np.array([[1, 2, 3], [1, 2, 3]])
    out = dec.generate(forced, max_new_tokens=8, eos_token_id=eos01)
    assert out.shape == (2, 4)
    assert np.all(out[:, 3] == eos01)
    ref = _with_fallback(lambda: dec.generate(forced, max_new_tokens=8,
                                              eos_token_id=eos01))
    np.testing.assert_array_equal(out, ref)
    # same conventions through the speculative path
    sout = dec.generate(forced, max_new_tokens=8, eos_token_id=eos01,
                        draft_model="skip:1", num_speculative_tokens=2)
    np.testing.assert_array_equal(sout, out)
    np.testing.assert_array_equal(
        dec.generate(prompt, max_new_tokens=8, eos_token_id=-1,
                     draft_model="skip:1", num_speculative_tokens=2),
        free)

    # generate_tokens: same negative-eos and first-token-eos handling
    gfree = generate_tokens(model, prompt, max_new_tokens=6)
    np.testing.assert_array_equal(
        generate_tokens(model, prompt, max_new_tokens=6, eos_token_id=-1),
        gfree)
    g0 = int(gfree[0, 3])
    gout = generate_tokens(model, forced, max_new_tokens=6,
                           eos_token_id=g0)
    assert gout.shape == (2, 4)
    assert np.all(gout[:, 3] == g0)


def test_runtime_temperature_is_not_a_static():
    """Satellite: temperature is a runtime scalar input to the fused
    decode programs — changing it never retraces (the same compiled
    program serves any temperature) and still matches the per-token
    fallback bit-exactly."""
    model = _model(13)
    dec = LlamaDecoder(model, max_len=32)
    prompt = np.array([[1, 2, 3], [4, 5, 6]])
    dec.generate(prompt, max_new_tokens=6, do_sample=True,
                 temperature=0.8, seed=0)
    # warm the fallback's step program too: only temperature-driven
    # retraces should show up in the window below
    _with_fallback(lambda: dec.generate(prompt, max_new_tokens=6,
                                        do_sample=True, temperature=0.8,
                                        seed=0))
    t0 = dec.trace_count
    for temp in (0.5, 1.0, 1.7):
        fused = dec.generate(prompt, max_new_tokens=6, do_sample=True,
                             temperature=temp, seed=1)
        ref = _with_fallback(lambda: dec.generate(
            prompt, max_new_tokens=6, do_sample=True, temperature=temp,
            seed=1))
        np.testing.assert_array_equal(fused, ref, err_msg=str(temp))
    assert dec.trace_count == t0, "temperature change retraced the program"
    # speculative program too
    dec2 = LlamaDecoder(model, max_len=40)
    kw = dict(do_sample=True, top_k=8, seed=2, draft_model="skip:1",
              num_speculative_tokens=2)
    dec2.generate(prompt, max_new_tokens=6, temperature=0.8, **kw)
    t0 = dec2.trace_count
    dec2.generate(prompt, max_new_tokens=6, temperature=1.4, **kw)
    assert dec2.trace_count == t0

    # generate_tokens' fused program: one compiled entry across temps
    from paddle_tpu.nn.generation import generate_tokens
    generate_tokens(model, prompt, max_new_tokens=4, do_sample=True,
                    temperature=0.6, seed=3)
    jitted = model._ptpu_fused_generate
    generate_tokens(model, prompt, max_new_tokens=4, do_sample=True,
                    temperature=1.9, seed=3)
    assert model._ptpu_fused_generate is jitted
    assert jitted._cache_size() == 1


def test_model_generate_speculative_surface_and_flag_default():
    """The GenerationMixin surface threads draft_model/K through and
    sizes the decoder cache with K slots of slack; with no explicit K
    the ``decode_speculative_tokens`` flag supplies the default."""
    model = _model(14)
    prompt = np.array([[1, 2, 3]])
    plain = model.generate(prompt, max_new_tokens=6)
    out = model.generate(prompt, max_new_tokens=6, draft_model="skip:1",
                         num_speculative_tokens=2)
    np.testing.assert_array_equal(out, plain)  # greedy: invisible

    paddle.set_flags({"decode_speculative_tokens": 2})
    try:
        out2 = model.generate(prompt, max_new_tokens=6,
                              draft_model="skip:1")
        np.testing.assert_array_equal(out2, plain)
    finally:
        paddle.set_flags({"decode_speculative_tokens": 4})


# -- mesh-sharded decode (GSPMD tensor parallelism) -------------------------
#
# The conftest forces an 8-virtual-device CPU platform, so a 2x4 {dp,tp}
# mesh is always available. Parity is asserted at TOKEN level: sharded
# matmuls reassociate float reductions (logits differ in ulps), but the
# argmax/categorical picks — the decode OUTPUT — must be bit-exact.

def _mesh(shape=(2, 4)):
    from paddle_tpu.parallel import ProcessMesh
    return ProcessMesh(shape=shape, dim_names=("dp", "tp"))


def _spec_axes(x):
    """Mesh axis names a live array is actually sharded over."""
    axes = set()
    for e in tuple(getattr(x.sharding, "spec", ()) or ()):
        if e is None:
            continue
        axes.update(e if isinstance(e, (tuple, list)) else (e,))
    return axes


@pytest.fixture(scope="module")
def mesh_pair():
    """One model, two decoders: the single-device reference and the
    2x4 {dp,tp}-sharded one (params sharded by the decode partition
    rules, carry sharded on device)."""
    model = _model(30)
    ref = LlamaDecoder(model, max_len=32)
    sh = LlamaDecoder(model, max_len=32, mesh=_mesh((2, 4)))
    return ref, sh


def test_sharded_decode_chunk_reentry_bitexact_greedy(mesh_pair):
    """decode_chunk re-entry on the 2x4 mesh == the unsharded
    run-to-completion path, bit-exact, and the carry STAYS sharded
    across chunks (inspected via .sharding — never gathered to host)."""
    ref, sh = mesh_pair
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, 64, (2, 5))
    want = np.asarray(ref.generate(prompt, max_new_tokens=12))

    st = sh.init_decode_state(prompt)
    assert all("dp" in _spec_axes(b) for b in st.kc), st.kc[0].sharding
    assert _spec_axes(st.pos) == {"dp"}
    assert _spec_axes(st.logits) == {"dp", "tp"}
    kc_spec0 = [b.sharding for b in st.kc]
    t1, st = sh.decode_chunk(st, 5)
    # re-entry contract: same placements out as in, layer by layer
    assert all(b.sharding.is_equivalent_to(s0, b.ndim)
               for b, s0 in zip(st.kc, kc_spec0))
    assert all("dp" in _spec_axes(b) for b in st.kc)
    t2, st = sh.decode_chunk(st, 7)
    assert all("dp" in _spec_axes(b) for b in st.kc)
    got = np.concatenate([prompt, np.asarray(t1), np.asarray(t2)], axis=1)
    np.testing.assert_array_equal(got, want)


def test_sharded_decode_chunk_bitexact_sampled(mesh_pair):
    """Per-row-keyed sampling on the mesh draws the SAME tokens as the
    unsharded chunked path (the admission contract survives sharding)."""
    ref, sh = mesh_pair
    rng = np.random.default_rng(1)
    prompt = rng.integers(0, 64, (2, 5))
    kw = dict(do_sample=True, top_k=8, temperature=0.8, seed=3,
              chunk_size=4)
    a = np.asarray(ref.generate(prompt, 10, **kw))
    b = np.asarray(sh.generate(prompt, 10, **kw))
    np.testing.assert_array_equal(a, b)
    # and a different chunk slicing on the mesh changes nothing
    c = np.asarray(sh.generate(prompt, 10, **{**kw, "chunk_size": 7}))
    np.testing.assert_array_equal(a, c)


def test_sharded_full_generate_modes_parity(mesh_pair):
    """The fused one-dispatch path under the mesh: greedy, greedy+eos
    and sampled each match the single-device decoder token-for-token
    (dispatch accounting unchanged: prefill + ONE fused dispatch)."""
    ref, sh = mesh_pair
    prompt = np.array([[1, 2, 3], [4, 5, 6]])
    free = np.asarray(ref.generate(prompt, max_new_tokens=12))
    eos = int(free[0, 5])
    for kw in (dict(), dict(eos_token_id=eos),
               dict(do_sample=True, temperature=0.8, top_k=8, seed=1)):
        d0 = sh.dispatch_count
        got = np.asarray(sh.generate(prompt, max_new_tokens=12, **kw))
        assert sh.dispatch_count - d0 == 2, kw
        want = np.asarray(ref.generate(prompt, max_new_tokens=12, **kw))
        np.testing.assert_array_equal(got, want, err_msg=str(kw))


def test_sharded_head_axis_cache_on_2x2():
    """On a mesh whose tp divides the KV head count the cache IS sharded
    on the head axis (the Pope et al. tensor-parallel attention layout),
    and re-entry keeps it there."""
    model = _model(31)
    ref = LlamaDecoder(model, max_len=32)
    sh = LlamaDecoder(model, max_len=32, mesh=_mesh((2, 2)))
    prompt = np.array([[5, 6, 7], [8, 9, 10]])
    st = sh.init_decode_state(prompt)
    # each layer's head-major buffer (B, KV, max_len, D): dp on B, tp on KV
    for b in st.kc:
        assert _spec_axes(b) == {"dp", "tp"}
        assert tuple(b.sharding.spec)[:2] == ("dp", "tp")
    toks, st = sh.decode_chunk(st, 8)
    assert all(_spec_axes(b) == {"dp", "tp"} for b in st.kc)
    want = np.asarray(ref.generate(prompt, max_new_tokens=8))
    np.testing.assert_array_equal(
        np.concatenate([prompt, np.asarray(toks)], axis=1), want)


def test_sharded_speculative_parity(mesh_pair):
    """Speculative decode on a mesh — the path that used to refuse with
    SpeculativeMeshError — is a working path: the shard_map'd per-row
    uneven cache advance makes fused AND chunked speculative decode
    bit-exact vs the single-device decoder on the virtual CPU mesh,
    greedy and per-row-keyed sampled alike."""
    ref, sh = mesh_pair
    rng = np.random.default_rng(7)
    prompt = rng.integers(0, 64, (2, 5))
    kw = dict(draft_model="skip:1", num_speculative_tokens=2)
    want = np.asarray(ref.generate(prompt, max_new_tokens=10, **kw))
    got = np.asarray(sh.generate(prompt, max_new_tokens=10, **kw))
    np.testing.assert_array_equal(got, want)
    # chunk re-entry on the mesh slices the same stream
    gotc = np.asarray(sh.generate(prompt, max_new_tokens=10,
                                  chunk_size=3, **kw))
    np.testing.assert_array_equal(gotc, want)
    # per-row-keyed sampling: mesh == host, chunked == fused
    skw = dict(do_sample=True, top_k=8, temperature=0.8, seed=3, **kw)
    a = np.asarray(ref.generate(prompt, 10, chunk_size=4, **skw))
    b = np.asarray(sh.generate(prompt, 10, chunk_size=4, **skw))
    np.testing.assert_array_equal(a, b)


def test_model_generate_mesh_surface(mesh_pair):
    """The GenerationMixin surface threads mesh= through to the decoder
    (topology is part of the decoder cache key) and stays bit-exact."""
    model = _model(32)
    prompt = np.array([[1, 2, 3]])
    plain = np.asarray(model.generate(prompt, max_new_tokens=6))
    out = np.asarray(model.generate(prompt, max_new_tokens=6,
                                    mesh=_mesh((2, 2))))
    np.testing.assert_array_equal(out, plain)
    assert model._decoder.sharding is not None
    assert model._decoder.sharding.axes == {"dp": 2, "tp": 2}
