"""A.X-K1 (models/axk1.py): multi-head latent attention over a latent
cache, YaRN RoPE, group-limited sigmoid routing as one chip's share, served
by ``LlamaDecoder`` / ``ServingEngine``. Every comparison is against the
plain float32 reference ``benchmark/reference/axk1_block.py`` or a
closed formula, at a tiny width in float32 on the CPU, with the norm
weights jittered off 1.0 (a wrong cache index or norm shows then)."""

import dataclasses
import hashlib
import math
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from benchmark.reference import afmoe_block as afref
from benchmark.reference import axk1_block as ref
from paddle_tpu.inference.generate import LatentCacheError, LlamaDecoder
from paddle_tpu.models.axk1 import (AXK1_TINY, AxK1Config, AxK1ConfigError,
                                    AxK1ForCausalLM, yarn_inv_freq)
from paddle_tpu.ops.moe import routed_ffn, sigmoid_topk_route

# the reference's arch keys over a program config
_ARCH = ("hidden_size", "num_attention_heads", "q_lora_rank", "kv_lora_rank",
         "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim", "rms_norm_eps",
         "rope_theta", "rope_scaling", "first_k_dense_replace",
         "n_routed_experts", "num_experts_per_tok", "n_group", "topk_group",
         "norm_topk_prob", "routed_scaling_factor", "experts_held",
         "expert_offset", "moe_intermediate_size", "n_shared_experts")


def _arch(cfg):
    return {k: getattr(cfg, k) for k in _ARCH}


def _model(cfg=AXK1_TINY, seed=5):
    paddle.seed(seed)
    model = AxK1ForCausalLM(cfg)
    rng = np.random.default_rng(seed)
    for name, p in model.named_parameters():
        if "norm" in name:
            p._value = jnp.asarray(1.0 + 0.2 * rng.standard_normal(p.shape),
                                   p._value.dtype)
    return model


def _ref_logits(model, ids):
    cfg = model.config
    sd = {n: np.asarray(t.value) for n, t in model.state_dict().items()}
    return np.asarray(ref.logits(
        ids, _arch(cfg), cfg.num_hidden_layers,
        sd["model.embed_tokens.weight"],
        ref.layer_weights_by_name(sd, _arch(cfg)),
        sd["model.norm.weight"], sd["lm_head.weight"]))[0]


def _err(have, want):
    """max |have - want| over the vocabulary / std of want, per position."""
    return np.abs(have - want).max(-1) / want.std(-1)


@pytest.fixture(scope="module")
def tiny():
    model = _model()
    ids = np.random.default_rng(1).integers(0, 256, (1, 40)).astype(np.int32)
    return model, ids, _ref_logits(model, ids)


def test_decoder_prefill_and_cached_steps_match_the_reference(tiny):
    """Prefill of 30 positions (expanded) and 10 cached decode steps
    (absorbed, through the latent leaf) against the reference's full
    forward: float32 both sides, so what is left is summation order —
    1e-4 of a standard deviation, where a latent written at the wrong
    position, an unrotated key or a lost norm reads 0.1 and more. The
    eager model (expanded, XLA) agrees too."""
    model, ids, want = tiny
    dec = LlamaDecoder(model, max_len=64)
    kc, vc = dec._empty_cache(1)
    assert [x.shape for x in kc] == [(1, 1, 64, 32)] * 3     # the latent
    assert [x.shape for x in vc] == [(1, 1, 64, 128)] * 3    # the rope key
    lg, kc, vc = dec._prefill(dec.params, jnp.asarray(ids[:, :30]), kc, vc)
    have = [np.asarray(lg[0])]
    for t in range(10):
        lg, kc, vc = dec._step(dec.params, jnp.asarray(ids[:, 30 + t:31 + t]),
                               kc, vc, jnp.int32(30 + t))
        have.append(np.asarray(lg[0]))
    assert _err(np.stack(have), want[29:40]).max() < 1e-4
    eager = np.asarray(model(paddle.to_tensor(ids)).numpy())[0]
    assert _err(eager, want).max() < 1e-4


def test_absorbed_attention_is_the_expanded_one(tiny):
    """The same function in two orders: a position's logits from the
    expanded prefill over all of it, and from a prefill one shorter plus
    one absorbed step over the latent cache (at 1e-5 of a standard
    deviation in float32)."""
    model, ids, _ = tiny
    dec = LlamaDecoder(model, max_len=64)
    for P in (1, 7, 23):
        kc, vc = dec._empty_cache(1)
        full, _, _ = dec._prefill(dec.params, jnp.asarray(ids[:, :P + 1]),
                                  kc, vc)
        kc, vc = dec._empty_cache(1)
        _, kc, vc = dec._prefill(dec.params, jnp.asarray(ids[:, :P]), kc, vc)
        step, _, _ = dec._step(dec.params, jnp.asarray(ids[:, P:P + 1]), kc,
                               vc, jnp.int32(P))
        assert _err(np.asarray(step), np.asarray(full)).max() < 1e-5


def test_engine_serves_through_the_ring_and_counts_the_latent(tiny):
    """Mixed prompts through ``ServingEngine`` (ring admission, the one
    chunk program): each request's tokens are its solo greedy
    ``generate``'s, the latent positions the steps read are counted by
    hand (a row at position n reads n + 1, over the chunk's steps and the
    three latent layers), and the prefills' true lengths by bucket."""
    from paddle_tpu.serving import ServingEngine
    model, ids, _ = tiny
    dec = LlamaDecoder(model, max_len=64)
    prompts = [ids[0, :5], ids[0, :19], ids[0, :12]]
    solo = [np.asarray(dec.generate(p[None], max_new_tokens=9))[0]
            for p in prompts]
    eng = ServingEngine(dec, num_slots=2, chunk_size=4)
    m0 = eng.metrics()
    rids = [eng.submit(p, 9) for p in prompts]
    done = eng.drain()
    for r, want in zip(rids, solo):
        assert np.array_equal(np.asarray(done[r])[0], want)
    m = eng.metrics()
    assert m["cache_layers"] == 3
    assert m["cache_bytes_per_position"] == 3 * (32 + 128) * 2 * 4 // 2
    assert m["prefill_by_bucket"] == {
        8: {"rows": 1, "positions": 5, "causal_pairs": 15},
        32: {"rows": 1, "positions": 19, "causal_pairs": 190},
        16: {"rows": 1, "positions": 12, "causal_pairs": 78}}
    read = m["live_latent_positions_total"] \
        - m0["live_latent_positions_total"]
    assert read > 0 and read % 3 == 0
    # every chunk's occupied rows, at the positions the live counter saw
    live = m["live_kv_positions_total"] - m0["live_kv_positions_total"]
    rows = read // 3 - 4 * live
    assert rows > 0 and rows % (4 * 5 // 2) == 0    # sum of 1..4 a row


def test_the_kernel_path_serves_the_same_tokens(tiny, monkeypatch):
    """With the decoder's kernels routed (interpreted on the CPU), a
    latent of 128 so that ``mla_decode_attention`` and ``kv_row_write``
    take the leaves, the engine's tokens equal XLA's path's."""
    from paddle_tpu.ops.pallas import mla_decode_attention as mla
    from paddle_tpu.serving import ServingEngine
    cfg = dataclasses.replace(AXK1_TINY, kv_lora_rank=128,
                              num_hidden_layers=2)
    model = _model(cfg)
    ids = np.random.default_rng(2).integers(0, 256, (40,)).astype(np.int32)
    calls = []
    real = mla.mla_decode_attention
    monkeypatch.setattr(mla, "mla_decode_attention",
                        lambda *a, **k: calls.append(1) or real(*a, **k))
    out = {}
    for flag in (False, True):
        paddle.set_flags({"decode_attention_interpret": flag})
        try:
            dec = LlamaDecoder(model, max_len=256)
            eng = ServingEngine(dec, num_slots=2, chunk_size=4)
            rids = [eng.submit(ids[:n], 7) for n in (9, 33)]
            done = eng.drain()
            out[flag] = [np.asarray(done[r]) for r in rids]
        finally:
            paddle.set_flags({"decode_attention_interpret": False})
    assert calls                       # traced under the flag alone
    assert all(np.array_equal(a, b) for a, b in zip(out[False], out[True]))


def test_an_untiled_prefill_runs_flash_over_whole_blocks(monkeypatch):
    """A prefill from position 0 at a length the flash forward does not
    tile (1099: no divisor of it up to 512 is a whole number of
    sublanes) runs flash over zeros to 1536 positions under the kernels'
    routing, the appended keys hidden by the causal mask and the appended
    queries' rows dropped: its logits, and a decode step's over the latent
    it cached, are XLA's masked form's at the true length (1e-5 of a
    standard deviation, float32; every position's output feeds the next
    layer's keys, which the last position attends over)."""
    from paddle_tpu.inference import generate
    model = _model(dataclasses.replace(AXK1_TINY, num_hidden_layers=2))
    ids = np.random.default_rng(4).integers(0, 256, (1, 1100)).astype(
        np.int32)
    seen = []
    real = generate._flash_prefill
    monkeypatch.setattr(generate, "_flash_prefill", lambda q, *a, **k: (
        seen.append(q.shape[1]) or real(q, *a, **k)))
    out = {}
    for flag in (False, True):
        paddle.set_flags({"decode_attention_interpret": flag})
        try:
            dec = LlamaDecoder(model, max_len=2048)
            kc, vc = dec._empty_cache(1)
            lg, kc, vc = dec._prefill(dec.params, jnp.asarray(ids[:, :-1]),
                                      kc, vc)
            step, _, _ = dec._step(dec.params, jnp.asarray(ids[:, -1:]), kc,
                                   vc, jnp.int32(1099))
            out[flag] = np.stack([np.asarray(lg[0]), np.asarray(step[0])])
        finally:
            paddle.set_flags({"decode_attention_interpret": False})
    assert seen and set(seen) == {1536}
    assert _err(out[True], out[False]).max() < 1e-5


def _groups_numpy(s, n_group, topk_group, top_k):
    """The chosen set by counting: groups scored by their two best, the
    best groups kept, the top_k of the kept experts."""
    T, E = s.shape
    out = []
    for t in range(T):
        g = s[t].reshape(n_group, -1)
        score = np.sort(g, -1)[:, -2:].sum(-1)
        kept = np.argsort(-score, kind="stable")[:topk_group]
        cand = [e for e in range(E) if e // (E // n_group) in kept]
        out.append(set(sorted(cand, key=lambda e: -s[t, e])[:top_k]))
    return out


def test_group_limited_routing_against_a_count():
    """8 groups of 24 experts, 4 kept, top 8 (the published shape): the
    chosen set is the count's; where an ungrouped top 8 would reach into a
    dropped group (one strong expert alone in a weak group) the limit
    changes it."""
    rng = np.random.default_rng(0)
    T, H, E = 16, 32, 192
    x = rng.standard_normal((T, H)).astype(np.float32)
    w = rng.standard_normal((H, E)).astype(np.float32)
    bias = np.zeros(E, np.float32)
    wts, sel = sigmoid_topk_route(x, w, bias, 8, True, 2.5, 8, 4)
    s = np.asarray(jax.nn.sigmoid(jnp.dot(
        x, w, precision=jax.lax.Precision.HIGHEST)))
    assert [set(r) for r in np.asarray(sel)] == _groups_numpy(s, 8, 4, 8)
    np.testing.assert_allclose(np.asarray(wts).sum(-1), 2.5, rtol=1e-6)
    # a token whose best expert sits in a group with nothing else strong
    logits = np.full((1, E), -4.0, np.float32)
    logits[0, 5] = 6.0                                # group 0, alone
    for gi in (1, 2, 3, 4):                          # four groups of pairs
        logits[0, gi * 24:gi * 24 + 2] = 3.0
    eye = np.eye(E, dtype=np.float32)
    _, free = sigmoid_topk_route(logits, eye, bias, 8, True, 2.5)
    _, held = sigmoid_topk_route(logits, eye, bias, 8, True, 2.5, 8, 4)
    assert 5 in np.asarray(free)[0] and 5 not in np.asarray(held)[0]
    assert set(np.asarray(held)[0]) <= set(range(24, 120))


def test_one_group_traces_todays_routing():
    """``n_group = 1`` traces none of the group limit: the lowered text of
    the routed feed-forward at Trinity's routing (4 of 16, normalised,
    scaled) is the parent tree's, hash for hash, and the program text of
    the AFMoE cells therefore too (``test_evabyte.py`` holds theirs)."""
    def f(x, rw, b, gu, dn):
        return routed_ffn(x, rw, b, gu, dn, top_k=4, route_norm=True,
                          route_scale=2.448, expert_offset=0)
    args = (jnp.zeros((8, 32), jnp.float32), jnp.zeros((32, 16)),
            jnp.zeros((16,)), jnp.zeros((4, 32, 16)), jnp.zeros((4, 8, 32)))
    text = jax.jit(f).lower(*args).as_text()
    text = re.sub(r"loc\([^()]*(\([^()]*\)[^()]*)*\)", "", text)
    text = "\n".join(ln for ln in text.splitlines()
                     if not ln.startswith("#loc"))
    # taken on the parent of the change that added the group limit
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == PARENT_ROUTED
    a = sigmoid_topk_route(args[0], args[1], args[2], 4, True, 2.448)
    b = sigmoid_topk_route(args[0], args[1], args[2], 4, True, 2.448, 1, 1)
    assert all(np.array_equal(u, v) for u, v in zip(a, b))


PARENT_ROUTED = "e751eba127e216fd"


def test_yarn_tables_against_the_closed_formula():
    """At the published rope part (64, theta 1e4, factor 32 over 4096):
    the correction dimensions are 10 and 23, frequencies below 10 are the
    base ones, above 23 the base over 32, a linear ramp between; cos and
    sin unscaled (mscale / mscale_all_dim = 1); the softmax scale is
    192^-0.5 x (0.1 ln 32 + 1)^2."""
    cfg = AxK1Config(num_hidden_layers=2)
    d, th = 64, 10000.0
    corr = [d * math.log(4096 / (2 * math.pi * b)) / (2 * math.log(th))
            for b in (32, 1)]
    low, high = math.floor(corr[0]), math.ceil(corr[1])
    assert (low, high) == (10, 23)
    base = 1.0 / th ** (np.arange(0, d, 2) / d)
    r = np.clip((np.arange(32) - low) / (high - low), 0, 1)
    want = (1 - r) * base + r * base / 32
    np.testing.assert_allclose(np.asarray(yarn_inv_freq(d, th,
                                                        cfg.rope_scaling)),
                               want, rtol=1e-6)
    cos, sin = cfg.rope_tables(300, jnp.float32)
    ang = np.outer(np.arange(300), want)
    np.testing.assert_allclose(np.asarray(cos), np.cos(ang), atol=2e-5)
    np.testing.assert_allclose(np.asarray(sin), np.sin(ang), atol=2e-5)
    m = 0.1 * math.log(32) + 1
    assert math.isclose(cfg.softmax_scale, 192 ** -0.5 * m * m)
    assert math.isclose(cfg.softmax_scale, 0.13087, rel_tol=1e-4)
    # the reference's tables are the same
    rc, rs = ref.rope_tables(300, _arch(cfg))
    np.testing.assert_allclose(np.asarray(rc), np.cos(ang), atol=2e-5)


def test_sixteen_shares_add_up_to_the_uncut_layer():
    """THE SHARE: a routed layer of 32 experts cut into 16 shares of 2
    (experts [2 i, 2 i + 2)), each computed by the program's
    ``routed_ffn`` as one chip would; the shares' routed parts plus the
    shared expert once are the uncut reference layer's feed-forward."""
    rng = np.random.default_rng(4)
    T, H, F, E = 12, 32, 16, 32
    arch = {"n_routed_experts": E, "num_experts_per_tok": 8, "n_group": 8,
            "topk_group": 4, "norm_topk_prob": True,
            "routed_scaling_factor": 2.5}
    m = rng.standard_normal((T, H)).astype(np.float32)
    router = rng.standard_normal((H, E)).astype(np.float32)
    bias = np.zeros(E, np.float32)
    gu = (0.2 * rng.standard_normal((E, H, 2 * F))).astype(np.float32)
    dn = (0.2 * rng.standard_normal((E, F, H))).astype(np.float32)
    shared = [(0.2 * rng.standard_normal(s)).astype(np.float32)
              for s in ((H, F), (H, F), (F, H))]
    w = {"mlp.gate": router, "mlp.gate.bias": bias,
         "mlp.experts": lambda e: (gu[e][:, :F], gu[e][:, F:], dn[e])}
    with jax.default_matmul_precision("highest"):
        whole = ref.routed_part(m, w, arch) + afref.swiglu(m, *shared)
        parts = sum(np.asarray(routed_ffn(
            m, router, bias, gu[2 * i:2 * i + 2], dn[2 * i:2 * i + 2],
            top_k=8, route_norm=True, route_scale=2.5, expert_offset=2 * i,
            n_group=8, topk_group=4)[0]) for i in range(16))
        total = parts + np.asarray(afref.swiglu(m, *shared))
    np.testing.assert_allclose(total, np.asarray(whole), rtol=1e-4,
                               atol=1e-5)


def test_the_configuration_refuses_what_it_does_not_build():
    for key, bad in (("scoring_func", "softmax"), ("topk_method", "greedy"),
                     ("q_lora_rank", None), ("n_group", 5),
                     ("rope_scaling", {"type": "linear", "factor": 2})):
        with pytest.raises(AxK1ConfigError):
            AxK1Config(**{key: bad})


@pytest.fixture(scope="module")
def latent_dec():
    return LlamaDecoder(_model(dataclasses.replace(AXK1_TINY,
                                                   num_hidden_layers=2)),
                        max_len=64)


def test_a_quantized_cache_or_weights_refuse_a_latent_model():
    model = _model(dataclasses.replace(AXK1_TINY, num_hidden_layers=2))
    for quant in ("int8wk", "int8w"):
        with pytest.raises(LatentCacheError, match="latent"):
            LlamaDecoder(model, max_len=64, quant=quant)


def test_a_mesh_refuses_a_latent_model():
    model = _model(dataclasses.replace(AXK1_TINY, num_hidden_layers=2))
    with pytest.raises(LatentCacheError, match="mesh"):
        LlamaDecoder(model, max_len=64, mesh="tp:2")


def test_the_prefix_cache_and_its_slabs_refuse_a_latent_model(latent_dec):
    from paddle_tpu.serving import ServingEngine
    with pytest.raises(LatentCacheError, match="prefix"):
        ServingEngine(latent_dec, num_slots=2, chunk_size=4,
                      prefix_cache=True)
    eng = ServingEngine(latent_dec, num_slots=2, chunk_size=4)
    with pytest.raises(LatentCacheError, match="slab"):
        eng.prefill_extract(np.arange(6, dtype=np.int32))


def test_speculative_engines_refuse_a_latent_model(latent_dec):
    from paddle_tpu.serving import ServingEngine
    with pytest.raises(LatentCacheError, match="speculative"):
        ServingEngine(latent_dec, num_slots=2, chunk_size=4,
                      draft_model="skip:1", num_speculative_tokens=2)
    with pytest.raises(LatentCacheError, match="speculative"):
        latent_dec.generate(np.arange(6, dtype=np.int32)[None],
                            max_new_tokens=4, draft_model="skip:1")


def test_a_decoder_bundle_refuses_a_latent_model(latent_dec, tmp_path):
    from paddle_tpu.inference.bundle import export_decoder_bundle
    with pytest.raises(LatentCacheError, match="bundle"):
        export_decoder_bundle(latent_dec, str(tmp_path / "b"), [8], 4)
