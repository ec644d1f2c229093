"""AFMoE (models/afmoe.py) on the normal path: the eager model, the
trainer's tape, ``LlamaDecoder``'s cached programs and ``ServingEngine``
against the benchmark's plain float32 reference
(benchmark/reference/afmoe_block.py, which imports nothing from
paddle_tpu), at a tiny width on the CPU.

What the block forces and these tests hold: a routed feed-forward over one
chip's share of the experts (the shares add up to the whole layer), q/k
norm, an output gate, RoPE on windowed layers only, and cache buffers of
two lengths — a windowed layer's is rolling, a position kept at
``position % window``, so every prompt here is longer than the tiny window
and the buffers wrap; every engine feature either carries the buffers at
their lengths or refuses typed.
"""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from benchmark.reference import afmoe_block as ref
from paddle_tpu.inference import generate as gen
from paddle_tpu.inference.generate import LlamaDecoder, WindowedModelError
from paddle_tpu.models.afmoe import (AFMOE_TINY, AfmoeConfig,
                                     AfmoeConfigError, AfmoeForCausalLM)
from paddle_tpu.ops.moe import routed_ffn, sigmoid_topk_route
from paddle_tpu.serving import ServingEngine

W = AFMOE_TINY.sliding_window      # 8: four sliding layers, then a full one

_ARCH_KEYS = ("num_attention_heads", "num_key_value_heads", "head_dim",
              "rope_theta", "rms_norm_eps", "hidden_size", "sliding_window",
              "layer_types", "num_dense_layers", "num_experts",
              "num_experts_per_tok", "route_norm", "route_scale",
              "experts_held", "expert_offset", "mup_enabled",
              "moe_intermediate_size")


def _arch(cfg):
    return {k: getattr(cfg, k) for k in _ARCH_KEYS}


def _state(model):
    return {n: jnp.asarray(t.value) for n, t in model.state_dict().items()}


def _model(cfg=AFMOE_TINY, seed=11):
    """A seeded model whose norm weights are not all ones, so that a norm
    left out or applied in the wrong place shows."""
    paddle.seed(seed)
    model = AfmoeForCausalLM(cfg)
    rng = np.random.default_rng(seed)
    for name, p in model.named_parameters():
        if "norm" in name:
            p._value = jnp.asarray(
                1.0 + 0.2 * rng.standard_normal(p.shape), p._value.dtype)
    return model


def _ref_logits(model, cfg, ids, positions=None, **kw):
    sd, arch = _state(model), _arch(cfg)
    return np.asarray(ref.logits(
        ids, arch, cfg.num_hidden_layers, sd["model.embed_tokens.weight"],
        ref.layer_weights_by_name(sd, arch), sd["model.norm.weight"],
        sd["lm_head.weight"], positions=positions, **kw))


@pytest.fixture(scope="module")
def ids():
    return np.random.default_rng(5).integers(0, AFMOE_TINY.vocab_size,
                                             (2, 40), dtype=np.int32)


@pytest.fixture(scope="module")
def model():
    return _model()


def test_eager_logits_match_the_reference(model, ids):
    have = np.asarray(model(paddle.to_tensor(ids[:, :30])).value)
    want = _ref_logits(model, AFMOE_TINY, ids[:, :30])
    assert np.abs(have - want).max() <= 2e-5 * np.abs(want).max()


@pytest.mark.parametrize("P", [5, W, 20, 33])
def test_cached_decode_through_rolling_buffers_matches_the_reference(
        model, ids, P):
    """Prefill, then decode steps through the cache, against the
    reference's full forward: a prompt shorter than the window, one that
    fills it, and two that wrap it (the prefill itself, then every step)."""
    cfg = AFMOE_TINY
    dec = LlamaDecoder(model, max_len=64)
    kc, vc = dec._empty_cache(1)
    # four rolling buffers of the window, one of max_len
    assert [b.shape[2] for b in kc] == [W, W, W, W, 64]
    K = 7
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


@pytest.mark.parametrize("head_major", [True, False])
def test_prefill_rows_keeps_the_last_window_by_true_len(head_major):
    """Slot s of a rolling buffer holds the last position p < true_len
    with p % L == s; the bucket's padded tail is never written."""
    L, S = 4, 11
    t = jnp.arange(2 * S, dtype=jnp.float32).reshape(2, 1, S, 1)
    if not head_major:
        t = jnp.swapaxes(t, 1, 2)
    got = np.asarray(gen._prefill_rows(t, jnp.asarray([10, 6]), L,
                                       head_major)).reshape(2, L)
    # row 0: positions 6..9 at slots 2, 3, 0, 1; row 1: positions 2..5
    assert got[0].tolist() == [8, 9, 6, 7]
    assert (got[1] - S).tolist() == [4, 5, 2, 3]
    whole = np.asarray(gen._prefill_rows(t, None, L, head_major))
    assert whole.reshape(2, L)[0].tolist() == [8, 9, 10, 7]


def test_writing_the_padded_tail_into_a_rolling_buffer_fails_the_comparison(
        model, ids, monkeypatch):
    """The mistake a rolling buffer invites: a bucket's padded tail
    written past ``true_len``, where in a buffer that wraps it lands on
    live positions. One decode step after a bucketed admission sees it."""
    P, S = 20, 32
    bucket = np.zeros((1, S), np.int32)
    bucket[0, :P] = ids[0, :P]
    want = _ref_logits(model, AFMOE_TINY, ids[:1, :P + 1],
                       positions=np.arange(P, P + 1))[0, 0]

    def step_after_admission():
        dec = LlamaDecoder(model, max_len=64)
        kc, vc = dec._empty_cache(1)
        _, kc, vc = dec._admit_prefill(
            dec.params, jnp.asarray(bucket), kc, vc,
            jnp.asarray([P], jnp.int32), jnp.zeros((1,), jnp.int32))
        lg, _, _ = dec._step(dec.params, jnp.asarray(ids[:1, P:P + 1]), kc,
                             vc, jnp.int32(P))
        return np.abs(np.asarray(lg[0]) - want).max() / want.std()
    assert step_after_admission() <= 1e-4
    real = gen._prefill_rows
    monkeypatch.setattr(gen, "_prefill_rows",
                        lambda t, n, L, hm: real(t, None, L, hm))
    assert step_after_admission() > 0.05


def _run(eng, out=None):
    out = {} if out is None else out
    while len(eng.scheduler) or list(eng.scheduler.slots.occupied()):
        out.update(eng.step())
    return out


def test_engine_tokens_equal_generate_and_the_counters_count(model):
    """Ring admissions through buckets longer than the window (the
    prefill's rows land by ``true_len``), staggered; the engine's
    counters of the routing and of live positions by layer kind."""
    dec = LlamaDecoder(model, max_len=64)
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, 256, (n,), dtype=np.int32)
               for n in (5, 12, 33, 8, 20)]
    budgets = [9, 6, 11, 7, 10]
    solo = [np.asarray(dec.generate(p[None], b))[0]
            for p, b in zip(prompts, budgets)]
    eng = ServingEngine(dec, num_slots=2, chunk_size=3)
    m0 = eng.metrics()
    kv = 2 * 2 * 16 * 4               # K and V, 2 heads x 16, float32
    assert (m0["cache_bytes_per_position_window"],
            m0["cache_bytes_per_position_full"]) == (4 * kv, kv)
    rids = [eng.submit(p, b) for p, b in zip(prompts[:3], budgets[:3])]
    out = dict(eng.step())              # two admitted, one queued
    rids += [eng.submit(p, b) for p, b in zip(prompts[3:], budgets[3:])]
    _run(eng, out)
    for rid, want in zip(rids, solo):
        assert np.array_equal(np.asarray(out[rid])[0], want)
    m = eng.metrics()
    assert m["admission_ring"]["host_scattered"] == 0
    assert [b.shape[2] for b in eng.state.kc] == [W, W, W, W, 64]
    steps = m["chunk_dispatches"] * 3
    # every expert is held: a live row's 4 pairs land in each of the 4
    # routed layers, a frozen row's nowhere
    assert 0 < m["moe_pairs_held_total"] <= steps * 2 * 4 * 4
    assert m["moe_pairs_held_total"] % 16 == 0
    assert 0 < m["moe_experts_touched_total"] <= m["moe_pairs_held_total"]
    assert 1 <= m["moe_load_max"] <= 2
    assert 0 < m["live_window_positions_total"] \
        < m["live_kv_positions_total"]
    assert eng.registry.get("serving.moe.pairs_held").value \
        == m["moe_pairs_held_total"]
    assert eng.registry.get(
        "serving.cache.bytes_per_position.window").value == 4 * kv


def test_snapshot_restore_and_extract_rows_carry_both_lengths(
        model, tmp_path):
    rng = np.random.default_rng(2)
    prompts = [rng.integers(0, 256, (n,), dtype=np.int32) for n in (6, 19)]
    solo = [np.asarray(LlamaDecoder(model, max_len=64).generate(p[None], 12))
            for p in prompts]

    def engine():
        return ServingEngine(LlamaDecoder(model, max_len=64), num_slots=2,
                             chunk_size=3)
    src = engine()
    rids = [src.submit(p, 12) for p in prompts]
    src.step()
    src.snapshot(str(tmp_path / "snap"))
    dst = engine()
    assert dst.restore(str(tmp_path / "snap"))["in_flight"] == 2
    done = _run(dst)
    for rid, want in zip(rids, solo):
        assert np.array_equal(np.asarray(done[rid]), want)
    a, b = engine(), engine()
    rids = [a.submit(p, 12) for p in prompts]
    done = dict(a.step())
    payload = a.extract_rows([rids[1]])
    mapping = b.absorb_rows(payload)
    _run(a, done)
    done2 = _run(b)
    assert np.array_equal(np.asarray(done[rids[0]]), solo[0])
    assert np.array_equal(np.asarray(done2[mapping[rids[1]]]), solo[1])


def test_what_addresses_rows_by_position_refuses_a_windowed_model(
        model, ids):
    dec = LlamaDecoder(model, max_len=64)
    with pytest.raises(WindowedModelError, match="prefix cache"):
        ServingEngine(dec, num_slots=2, chunk_size=3, prefix_cache=True,
                      prefix_cache_bytes=1 << 20)
    with pytest.raises(WindowedModelError, match="speculative"):
        ServingEngine(dec, num_slots=2, chunk_size=3, draft_model="skip:1",
                      num_speculative_tokens=2)
    with pytest.raises(WindowedModelError):
        dec.generate(ids[:, :6], 4, draft_model="skip:1")


@pytest.mark.parametrize("field,value", [
    ("score_func", "softmax"), ("n_group", 2), ("topk_group", 2),
    ("rope_scaling", {"type": "yarn"}), ("layer_types", ("full_attention",)),
    ("experts_held", 17), ("expert_offset", -1)])
def test_config_keys_the_program_does_not_build_are_refused_typed(
        field, value):
    with pytest.raises(AfmoeConfigError):
        dataclasses.replace(AFMOE_TINY, **{field: value})


def test_default_layer_kinds_and_derived_properties():
    cfg = AfmoeConfig(num_hidden_layers=8, num_dense_layers=2)
    assert cfg.layer_types == ("sliding_attention",) * 3 \
        + ("full_attention",) + ("sliding_attention",) * 3 \
        + ("full_attention",)
    assert cfg.head_dim == 128 and cfg.hidden_size // 32 == 128
    assert cfg.has_windows and cfg.routed and cfg.experts_held == 256
    assert cfg.cache_len(0, 1024) == 1024 and cfg.cache_len(0, 8192) == 4096
    assert cfg.cache_len(3, 8192) == 8192
    assert cfg.embedding_scale == 64.0 and not cfg.layer_rope(3)


def test_parameters_are_born_in_the_configs_dtype():
    cfg = dataclasses.replace(AFMOE_TINY, dtype="bfloat16",
                              num_hidden_layers=2,
                              layer_types=("sliding_attention",
                                           "full_attention"))
    paddle.seed(0)
    m = AfmoeForCausalLM(cfg)
    assert {str(p.dtype) for p in m.parameters()} == {"bfloat16"}
    held = [p._value for p in m.parameters()]
    m.to(dtype="bfloat16")
    assert all(a is b._value for a, b in zip(held, m.parameters()))
    # the decoder takes the experts' stacks by reference
    dec = LlamaDecoder(m, max_len=16)
    assert dec.params["model.layers.1.mlp.experts_gate_up"] \
        is m.model.layers[1].mlp.experts_gate_up._value
    # the context sets a default: a layer that names its dtype keeps it
    import paddle_tpu.nn as nn
    from paddle_tpu.nn.layer_base import param_dtype
    with param_dtype("bfloat16"):
        named, plain = nn.Layer(dtype="float32"), nn.Layer()
    assert (str(named.create_parameter([2]).dtype),
            str(plain.create_parameter([2]).dtype),
            str(nn.Layer().create_parameter([2]).dtype)) \
        == ("float32", "bfloat16", "float32")


def _layer_inputs(seed=4, T=24):
    cfg = AFMOE_TINY
    rng = np.random.default_rng(seed)
    H, F, E = cfg.hidden_size, cfg.moe_intermediate_size, cfg.num_experts
    x = jnp.asarray(rng.standard_normal((T, H)), jnp.float32)
    router = jnp.asarray(rng.standard_normal((H, E)) * 0.3, jnp.float32)
    gu = jnp.asarray(rng.standard_normal((E, H, 2 * F)) * 0.1, jnp.float32)
    dn = jnp.asarray(rng.standard_normal((E, F, H)) * 0.1, jnp.float32)
    return cfg, x, router, jnp.zeros((E,), jnp.float32), gu, dn


def test_the_eight_shares_add_up_to_the_uncut_layer():
    """THE SHARE TEST: the routed parts that the eight shares give — the
    program's ``routed_ffn`` told which two of the sixteen experts it
    holds, and the reference's ``routed_part`` given the same share — add
    up to what the uncut reference gives for the whole layer (the shared
    expert, which every chip computes alike, counted once: it is outside
    the routed part)."""
    cfg, x, router, bias, gu, dn = _layer_inputs()
    F = cfg.moe_intermediate_size
    kw = dict(top_k=cfg.num_experts_per_tok, route_norm=cfg.route_norm,
              route_scale=cfg.route_scale)
    arch = {**_arch(cfg), "experts_held": cfg.num_experts}

    def weights(lo, n):
        return {"mlp.router.gate": router, "mlp.expert_bias": bias,
                "mlp.experts": lambda e: (gu[lo + e][:, :F],
                                          gu[lo + e][:, F:], dn[lo + e])}
    with jax.default_matmul_precision("highest"):
        whole = np.asarray(ref.routed_part(x[None], weights(0, 16), arch))[0]
        prog = ref_sum = 0.0
        pairs = 0
        for s in range(8):
            y, stats, _ = routed_ffn(x, router, bias, gu[2 * s:2 * s + 2],
                                     dn[2 * s:2 * s + 2],
                                     expert_offset=2 * s, **kw)
            prog = prog + np.asarray(y)
            pairs += int(stats[0])
            ref_sum = ref_sum + np.asarray(ref.routed_part(
                x[None], weights(2 * s, 2),
                {**arch, "experts_held": 2, "expert_offset": 2 * s}))[0]
        uncut, _, _ = routed_ffn(x, router, bias, gu, dn, **kw)
    assert pairs == x.shape[0] * cfg.num_experts_per_tok   # no pair lost
    scale = np.abs(whole).max()
    assert np.abs(ref_sum - whole).max() <= 1e-5 * scale
    assert np.abs(prog - whole).max() <= 1e-5 * scale
    assert np.abs(np.asarray(uncut) - whole).max() <= 1e-5 * scale
    # a share alone is not the layer
    assert np.abs(np.asarray(y) - whole).max() > 0.1 * scale


def test_rows_that_are_not_live_reach_no_expert():
    cfg, x, router, bias, gu, dn = _layer_inputs()
    kw = dict(top_k=4, route_norm=True, route_scale=cfg.route_scale)
    live = jnp.arange(x.shape[0]) < 10
    y, stats, _ = routed_ffn(x, router, bias, gu, dn, live=live, **kw)
    full, full_stats, _ = routed_ffn(x, router, bias, gu, dn, **kw)
    assert int(stats[0]) == 40 and int(full_stats[0]) == 96
    assert np.array_equal(np.asarray(y[10:]), np.zeros_like(y[10:]))
    assert np.allclose(np.asarray(y[:10]), np.asarray(full[:10]), atol=1e-6)


def test_a_tie_for_the_fourth_place_goes_to_the_lowest_index():
    """Two experts with the same router column score every token alike.
    Where they tie for the fourth place the program picks the lower
    index, as ``lax.top_k`` does — and so does the reference: a flip
    between the two needs rounding, not the rule."""
    cfg, x, router, bias, _, _ = _layer_inputs()
    router = router.at[:, 11].set(router[:, 3])
    _, sel = sigmoid_topk_route(x, router, bias, 4, True, 1.0)
    s = np.asarray(jax.nn.sigmoid(x @ router))
    sel = np.asarray(sel)
    order = np.argsort(-s, axis=-1, kind="stable")
    tied_fourth = [t for t in range(len(s)) if 3 in order[t, 3:5]
                   and 11 in order[t, 3:5]]
    assert tied_fourth                       # the seed gives such tokens
    for t in tied_fourth:
        assert 3 in sel[t] and 11 not in sel[t]
    _, ref_sel = ref.route(x[None], router, bias, _arch(cfg))
    assert np.array_equal(np.sort(np.asarray(ref_sel)[0], -1),
                          np.sort(sel, -1))
    # a bias selects and does not weigh
    w0, _ = sigmoid_topk_route(x, router, bias, 4, False, 1.0)
    w1, sel1 = sigmoid_topk_route(x, router, bias.at[7].set(10.0), 4,
                                  False, 1.0)
    assert (np.asarray(sel1) == 7).any(-1).all()
    assert float(w1.max()) <= 1.0


def test_route_override_puts_a_selection_in_the_place_of_its_own(
        model, ids):
    cfg = AFMOE_TINY
    record = {}
    own = _ref_logits(model, cfg, ids[:1, :12], record=record)
    assert sorted(record) == [1, 2, 3, 4]
    again = _ref_logits(model, cfg, ids[:1, :12], route_override=record)
    assert np.array_equal(own, again)
    other = {li: (np.asarray(s) + 1) % cfg.num_experts
             for li, s in record.items()}
    moved = _ref_logits(model, cfg, ids[:1, :12], route_override=other)
    assert np.abs(moved - own).max() > 1e-3 * np.abs(own).max()


def test_trainer_loss_and_gradients_match_the_reference(ids):
    """One ``ShardedTrainer`` step of the eager model at the tiny width
    against ``jax.grad`` of the reference's loss: the routed experts'
    stacks, the router (through the normalised weights), the gate, the
    q/k norms."""
    from paddle_tpu.parallel import ProcessMesh
    from paddle_tpu.parallel.train import ShardedTrainer
    cfg = dataclasses.replace(
        AFMOE_TINY, num_hidden_layers=3, num_dense_layers=1,
        layer_types=("sliding_attention", "sliding_attention",
                     "full_attention"))
    model = _model(cfg, seed=21)
    x = ids[:, :20]
    labels = np.roll(x, -1, axis=1)
    before = {k: np.asarray(v) for k, v in _state(model).items()}
    want_loss, want = jax.value_and_grad(ref.loss_fn)(
        {k: jnp.asarray(v) for k, v in before.items()}, x, labels,
        _arch(cfg), cfg.num_hidden_layers)
    opt = paddle.optimizer.SGD(learning_rate=1.0,
                               parameters=model.parameters())
    mesh = ProcessMesh(shape=(1, 1, 1), dim_names=("dp", "sep", "mp"))
    trainer = ShardedTrainer(model, opt, lambda m, i, l: m.loss(i, l), mesh,
                             {})
    with mesh:
        loss = float(np.asarray(trainer.train_step(x, labels).value))
    assert loss == pytest.approx(float(want_loss), rel=2e-5)
    after = _state(model)
    for name, g in want.items():
        if name.endswith("expert_bias"):
            continue                        # a buffer: selects, not trained
        got = before[name] - np.asarray(after[name])
        scale = float(np.abs(np.asarray(g)).max()) + 1e-12
        assert np.abs(got - np.asarray(g)).max() <= 2e-4 * scale + 3e-7, name
    moved = before["model.layers.1.mlp.router.weight"] \
        - np.asarray(after["model.layers.1.mlp.router.weight"])
    assert np.abs(moved).max() > 0
