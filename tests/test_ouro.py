"""Ouro looped LM (models/ouro.py) on the normal path: the eager model, the
trainer's tape, ``LlamaDecoder``'s cached programs and ``ServingEngine``
against the benchmark's plain float32 reference
(benchmark/reference/ouro_block.py, which imports nothing from paddle_tpu),
at a tiny width on the CPU.

What the loop forces and these tests hold: one stack of L weight layers run
T times over T * L cache layers, four norms a block, the final norm at the
end of every pass; T = 1 without the extra norms is the Llama path to the
bit; every engine feature either carries T * L buffers or refuses typed.
"""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from benchmark.reference import ouro_block as ref
from paddle_tpu.inference import generate as gen
from paddle_tpu.inference.generate import LlamaDecoder, LoopedDraftError
from paddle_tpu.models.llama import TINY_CONFIG, LlamaForCausalLM
from paddle_tpu.models.ouro import OURO_TINY, OuroConfig, OuroForCausalLM
from paddle_tpu.serving import ServingEngine


def _arch(cfg):
    return {"num_attention_heads": cfg.num_attention_heads,
            "num_key_value_heads": cfg.num_key_value_heads,
            "head_dim": cfg.head_dim, "rope_theta": cfg.rope_theta,
            "rms_norm_eps": cfg.rms_norm_eps,
            "intermediate_size": cfg.intermediate_size,
            "total_ut_steps": cfg.total_ut_steps}


def _state(model):
    return {n: jnp.asarray(t.value) for n, t in model.state_dict().items()}


def _model(cfg=OURO_TINY, seed=11, jitter=True):
    """A seeded model whose norm weights are not all ones, so that a norm
    left out or applied in the wrong place shows."""
    paddle.seed(seed)
    model = OuroForCausalLM(cfg)
    if jitter:
        rng = np.random.default_rng(seed)
        for name, p in model.named_parameters():
            if "norm" in name:
                p._value = jnp.asarray(
                    1.0 + 0.2 * rng.standard_normal(p.shape), p._value.dtype)
    return model


def _ref_logits(model, cfg, ids, positions=None):
    sd = _state(model)
    return np.asarray(ref.logits(
        ids, _arch(cfg), cfg.num_hidden_layers,
        sd["model.embed_tokens.weight"], ref.layer_weights_by_name(sd),
        sd["model.norm.weight"], sd["lm_head.weight"], positions=positions))


@pytest.fixture(scope="module")
def ids():
    return np.random.default_rng(5).integers(0, OURO_TINY.vocab_size,
                                             (2, 20), dtype=np.int32)


def test_eager_logits_match_the_reference(ids):
    model = _model()
    have = np.asarray(model(paddle.to_tensor(ids)).value)
    want = _ref_logits(model, OURO_TINY, ids)
    assert np.abs(have - want).max() <= 2e-5 * np.abs(want).max()


@pytest.mark.parametrize("T", [1, 2, 4])
def test_decoder_cached_path_matches_the_reference(ids, T):
    """Prefill, then decode steps through the cache, against the
    reference's full forward: pass t must read pass t's own keys."""
    cfg = dataclasses.replace(OURO_TINY, total_ut_steps=T)
    model = _model(cfg)
    dec = LlamaDecoder(model, max_len=64)
    L = cfg.num_hidden_layers
    kc, vc = dec._empty_cache(1)
    # T * L cache layers over L weight layers
    assert len(kc) == len(vc) == T * L == cfg.num_cache_layers
    assert sum(k.endswith("self_attn.qkv.weight") for k in dec.params) == L
    P, K = 12, 6
    seq = ids[:1]
    want = _ref_logits(model, cfg, seq[:, :P + K],
                       positions=np.arange(P - 1, P + K))[0]
    lg, kc, vc = dec._prefill(dec.params, jnp.asarray(seq[:, :P]), kc, vc)
    have = [np.asarray(lg[0])]
    for t in range(K):
        lg, kc, vc = dec._step(dec.params,
                               jnp.asarray(seq[:, P + t:P + t + 1]), kc, vc,
                               jnp.int32(P + t))
        have.append(np.asarray(lg[0]))
    err = np.abs(np.stack(have) - want).max(-1) / want.std(-1)
    assert err.max() <= 1e-4
    # every cache layer was written: none is a spare
    assert all(float(jnp.abs(b[:, :P + K]).max()) > 0 for b in kc)


def test_one_pass_without_the_extra_norms_is_the_llama_path_to_the_bit(ids):
    """The shared code's guard. What selects the looped behaviour is the
    config's pass count and the parameters' extra norms: a Llama model
    (no such norms) under a one-pass ``OuroConfig`` goes through the
    decoder as it does under its ``LlamaConfig`` — the same program text,
    logits and tokens."""
    cfg = dataclasses.replace(TINY_CONFIG, num_key_value_heads=4)
    one_pass = OuroConfig(**vars(cfg), total_ut_steps=1)
    assert one_pass.num_cache_layers == cfg.num_cache_layers \
        == cfg.num_hidden_layers

    def through_the_decoder(config):
        paddle.seed(3)
        dec = LlamaDecoder(LlamaForCausalLM(config), max_len=64)
        kc, vc = dec._empty_cache(2)
        args = (dec.params, jnp.asarray(ids[:, :9]), kc, vc)
        text = dec._prefill._jitted.lower(*args).as_text()
        lg, _, _ = dec._prefill(*args)
        return text, np.asarray(lg), np.asarray(dec.generate(ids[:, :9], 8))
    (ta, la, ka), (tb, lb, kb) = (through_the_decoder(c)
                                  for c in (cfg, one_pass))
    assert ta == tb
    assert np.array_equal(la, lb) and np.array_equal(ka, kb)


def test_engine_tokens_equal_generate_with_staggered_admissions(ids):
    model = _model()
    dec = LlamaDecoder(model, max_len=64)
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, 256, (n,), dtype=np.int32)
               for n in (5, 9, 17, 8, 12)]
    budgets = [9, 6, 11, 7, 10]
    solo = [np.asarray(dec.generate(p[None], b))[0]
            for p, b in zip(prompts, budgets)]
    eng = ServingEngine(dec, num_slots=2, chunk_size=3)
    m0 = eng.metrics()
    assert m0["cache_layers"] == 8
    # K and V, 4 heads x 16, float32, over 8 cache layers
    assert m0["cache_bytes_per_position"] == 2 * 4 * 16 * 4 * 8
    rids = [eng.submit(p, b) for p, b in zip(prompts[:3], budgets[:3])]
    out = dict(eng.step())              # two admitted, one queued
    rids += [eng.submit(p, b) for p, b in zip(prompts[3:], budgets[3:])]
    out.update(eng.drain())
    for rid, want in zip(rids, solo):
        assert np.array_equal(np.asarray(out[rid])[0], want)
    m = eng.metrics()
    assert m["admission_ring"]["host_scattered"] == 0
    assert len(eng.state.kc) == 8


def test_llama_engine_reads_the_plain_cache_gauges():
    paddle.seed(0)
    dec = LlamaDecoder(LlamaForCausalLM(TINY_CONFIG), max_len=32)
    eng = ServingEngine(dec, num_slots=2, chunk_size=4)
    eng.submit(np.arange(6, dtype=np.int32), 5)
    eng.drain()
    m = eng.metrics()
    L = TINY_CONFIG.num_hidden_layers
    assert m["cache_layers"] == L
    assert m["cache_bytes_per_position"] == 2 * 2 * 16 * 4 * L
    assert eng.registry.get("serving.cache.layers").value == L


def test_trainer_gradients_match_the_reference(ids):
    """One ``ShardedTrainer`` step: the tape adds up the T uses of every
    weight, as ``jax.grad`` of the reference's loss does."""
    from paddle_tpu.parallel import ProcessMesh
    from paddle_tpu.parallel.train import ShardedTrainer
    cfg = dataclasses.replace(OURO_TINY, total_ut_steps=2)
    model = _model(cfg, seed=21)
    labels = np.roll(ids, -1, axis=1)
    before = {k: np.asarray(v) for k, v in _state(model).items()}
    want_loss, want = jax.value_and_grad(ref.loss_fn)(
        {k: jnp.asarray(v) for k, v in before.items()}, ids, labels,
        _arch(cfg), cfg.num_hidden_layers)
    opt = paddle.optimizer.SGD(learning_rate=1.0,
                               parameters=model.parameters())
    mesh = ProcessMesh(shape=(1, 1, 1), dim_names=("dp", "sep", "mp"))
    trainer = ShardedTrainer(model, opt, lambda m, i, l: m.loss(i, l), mesh,
                             {})
    with mesh:
        loss = float(np.asarray(trainer.train_step(ids, labels).value))
    assert loss == pytest.approx(float(want_loss), rel=2e-5)
    after = _state(model)
    for name, g in want.items():
        got = before[name] - np.asarray(after[name])
        scale = float(np.abs(np.asarray(g)).max()) + 1e-12
        assert np.abs(got - np.asarray(g)).max() <= 2e-4 * scale + 3e-7, name
    # a looped layer's gradient is not that of a single use
    assert model.flops_per_token(20) > LlamaForCausalLM.flops_per_token(
        model, 20)


def _run(eng, out=None):
    out = {} if out is None else out
    while len(eng.scheduler) or list(eng.scheduler.slots.occupied()):
        out.update(eng.step())
    return out


def test_snapshot_restore_and_extract_rows_carry_every_cache_layer(tmp_path):
    model = _model()
    rng = np.random.default_rng(2)
    prompts = [rng.integers(0, 256, (n,), dtype=np.int32) for n in (6, 11)]
    solo = [np.asarray(LlamaDecoder(model, max_len=64).generate(p[None], 12))
            for p in prompts]

    def engine():
        return ServingEngine(LlamaDecoder(model, max_len=64), num_slots=2,
                             chunk_size=3)
    # snapshot mid-flight, restore into a fresh engine
    src = engine()
    rids = [src.submit(p, 12) for p in prompts]
    src.step()
    src.snapshot(str(tmp_path / "snap"))
    dst = engine()
    assert dst.restore(str(tmp_path / "snap"))["in_flight"] == 2
    done = _run(dst)
    for rid, want in zip(rids, solo):
        assert np.array_equal(np.asarray(done[rid]), want)
    # migrate one row between live engines
    a, b = engine(), engine()
    rids = [a.submit(p, 12) for p in prompts]
    done = dict(a.step())
    payload = a.extract_rows([rids[1]])
    mapping = b.absorb_rows(payload)
    _run(a, done)
    done2 = _run(b)
    assert np.array_equal(np.asarray(done[rids[0]]), solo[0])
    assert np.array_equal(np.asarray(done2[mapping[rids[1]]]), solo[1])


def test_prefix_slabs_carry_every_cache_layer():
    model = _model()
    dec = LlamaDecoder(model, max_len=64)
    rng = np.random.default_rng(4)
    shared = rng.integers(0, 256, (16,), dtype=np.int32)
    prompts = [np.concatenate([shared, rng.integers(0, 256, (n,),
                                                    dtype=np.int32)])
               for n in (3, 5)]
    solo = [np.asarray(dec.generate(p[None], 6)) for p in prompts]
    eng = ServingEngine(dec, num_slots=2, chunk_size=3, prefix_cache=True,
                        prefix_cache_bytes=1 << 24, prefix_block_tokens=4)
    r0 = eng.submit(prompts[0], 6)
    out = eng.drain()
    r1 = eng.submit(prompts[1], 6)
    out.update(eng.drain())
    assert np.array_equal(np.asarray(out[r0]), solo[0])
    assert np.array_equal(np.asarray(out[r1]), solo[1])
    assert out[r1].resilience["serving"]["prefix_hit"] in ("partial", "full")
    assert len(eng.prefix_cache._slabs[0].kc) == 8


def test_aot_bundle_holds_every_pass_and_serves_the_same_tokens(tmp_path):
    """An exported bundle holds every cache layer: its entries take and
    return the whole tuple."""
    from paddle_tpu.inference import AotPredictor, export_decoder_bundle
    dec = LlamaDecoder(_model(), max_len=64)
    export_decoder_bundle(dec, str(tmp_path), prompt_lens=[8],
                          decode_steps=[8], batch_sizes=[2], chunk_sizes=[4])
    pred = AotPredictor(str(tmp_path))
    assert pred.meta["caches"]["2"]["n_buffers"] == 8
    rng = np.random.default_rng(6)
    reqs = [(rng.integers(0, 256, (n,), dtype=np.int32), b)
            for n, b in ((3, 7), (8, 4), (5, 6))]
    solo = [np.asarray(dec.generate(p[None], b)) for p, b in reqs]
    eng = ServingEngine(pred, num_slots=2, chunk_size=4)
    rids = [eng.submit(p, b) for p, b in reqs]
    out = eng.drain()
    for rid, want in zip(rids, solo):
        assert np.array_equal(np.asarray(out[rid]), want)
    m = eng.metrics()
    assert m["cache_layers"] == 8


def test_layer_skip_draft_is_refused_typed_and_a_real_draft_serves(ids):
    model = _model()
    dec = LlamaDecoder(model, max_len=64)
    with pytest.raises(LoopedDraftError, match="total_ut_steps=4"):
        ServingEngine(dec, num_slots=2, chunk_size=3, draft_model="skip:1",
                      num_speculative_tokens=2)
    with pytest.raises(LoopedDraftError):
        dec.generate(ids[:, :6], 4, draft_model="skip:1")
    with pytest.raises(ValueError, match="early exit is not built"):
        OuroConfig(early_exit_threshold=0.5)
    # a separate draft model (its own one-pass cache beside the looped
    # target's) speculates and verifies to the plain tokens
    paddle.seed(8)
    draft = LlamaForCausalLM(dataclasses.replace(
        TINY_CONFIG, num_key_value_heads=4, num_hidden_layers=1))
    want = np.asarray(dec.generate(ids[:, :6], 8))
    got = np.asarray(LlamaDecoder(model, max_len=64).generate(
        ids[:, :6], 8, draft_model=draft, num_speculative_tokens=2))
    assert np.array_equal(got, want)


def test_reading_the_previous_pass_keys_fails_the_logits_comparison(
        ids, monkeypatch):
    """The mistake the cache layout invites: pass t attending over pass
    t-1's buffers. The comparison of cached decode steps with the
    reference's full forward sees it at once."""
    cfg = OURO_TINY
    model = _model()
    L = cfg.num_hidden_layers
    real = gen._block_forward

    def previous_pass(p, cfg_, li, ci, *a, **k):
        return real(p, cfg_, li, max(ci - L, li), *a, **k)
    monkeypatch.setattr(gen, "_block_forward", previous_pass)
    dec = LlamaDecoder(model, max_len=64)
    P, K = 12, 4
    seq = ids[:1]
    want = _ref_logits(model, cfg, seq[:, :P + K],
                       positions=np.arange(P - 1, P + K))[0]
    kc, vc = dec._empty_cache(1)
    lg, kc, vc = dec._prefill(dec.params, jnp.asarray(seq[:, :P]), kc, vc)
    have = [np.asarray(lg[0])]
    for t in range(K):
        lg, kc, vc = dec._step(dec.params,
                               jnp.asarray(seq[:, P + t:P + t + 1]), kc, vc,
                               jnp.int32(P + t))
        have.append(np.asarray(lg[0]))
    err = np.abs(np.stack(have) - want).max(-1) / want.std(-1)
    assert err[1:].max() > 0.1      # every gate the benchmark writes
