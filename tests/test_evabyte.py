"""EvaByte (models/evabyte.py) on the normal path: the eager model,
``LlamaDecoder``'s cached programs and ``ServingEngine`` against the
benchmark's plain float32 reference (benchmark/reference/evabyte_block.py,
which imports nothing from paddle_tpu), at a tiny width on the CPU:
windows of 8 positions, chunks of 2, 3 layers.

What the block forces and these tests hold: a cache layer of TWO leaves —
a window of exact positions that is reset at a window's end and a summary
a chunk that becomes visible there —, one softmax over both, a prefill
that fills both, a float32 residual stream, norms by ``1 + w`` and a head
of several vocabularies; every engine feature either carries the two
leaves or refuses typed; the accepted configurations' programs keep their
text.
"""

import dataclasses
import hashlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from benchmark.reference import evabyte_block as ref
from paddle_tpu.inference.generate import LlamaDecoder, WindowedModelError
from paddle_tpu.models.evabyte import (EVABYTE_TINY, EvabyteConfigError,
                                       EvabyteForCausalLM)
from paddle_tpu.serving import ServingEngine

CFG = EVABYTE_TINY
W, C, V = CFG.window_size, CFG.chunk_size, CFG.vocab_size       # 8, 2, 320
ARCH = {"num_attention_heads": CFG.num_attention_heads,
        "rope_theta": CFG.rope_theta, "rms_norm_eps": CFG.rms_norm_eps,
        "window_size": W, "chunk_size": C}


def _model(seed=11):
    """A seeded model whose norm weights are not all zero, so that a norm
    left out, or taken as ``w`` where it is ``1 + w``, shows."""
    paddle.seed(seed)
    model = EvabyteForCausalLM(CFG)
    rng = np.random.default_rng(seed)
    for name, p in model.named_parameters():
        if "norm" in name:
            p._value = jnp.asarray(0.2 * rng.standard_normal(p.shape),
                                   p._value.dtype)
    return model


def _ref_logits(model, ids, positions=None):
    """(B, S', V): the reference's next-byte logits (head 0)."""
    sd = {n: jnp.asarray(t.value) for n, t in model.state_dict().items()}
    return np.asarray(ref.logits(
        ids, ARCH, CFG.num_hidden_layers, sd["model.embed_tokens.weight"],
        ref.layer_weights_by_name(sd), sd["model.norm.weight"],
        sd["lm_head.weight"], positions=positions))[..., :V]


@pytest.fixture(scope="module")
def ids():
    return np.random.default_rng(5).integers(0, V, (2, 30), dtype=np.int32)


@pytest.fixture(scope="module")
def model():
    return _model()


def test_eager_logits_match_the_reference(model, ids):
    got = np.asarray(model(paddle.to_tensor(ids)).value)
    assert got.shape == (2, 30, CFG.num_pred_heads * V)
    assert got.dtype == np.float32
    np.testing.assert_allclose(got[..., :V], _ref_logits(model, ids),
                               atol=2e-5)


# 1 below, at and 1 above a window's end (8, 16) and a chunk's end
@pytest.mark.parametrize("prompt", [5, 6, 7, 8, 9, 15, 16, 17])
def test_cached_decode_across_two_window_ends_matches_the_reference(
        model, ids, prompt):
    """Prefill ``prompt`` positions, then decode to position 29 through
    the two leaves: every step's LOGITS against the reference's full
    forward (positions 8, 16 and 24 reset the window leaf and make four
    more summaries visible)."""
    want = _ref_logits(model, ids)
    dec = LlamaDecoder(model, max_len=64)
    kc, vc = dec._empty_cache(2)
    assert [b.shape for b in kc] == [(2, 4, W, 16), (2, 4, 64 // C, 16)] * 3
    lg, kc, vc = dec._prefill(dec.params, jnp.asarray(ids[:, :prompt]),
                              kc, vc)
    np.testing.assert_allclose(np.asarray(lg), want[:, prompt - 1],
                               atol=2e-5)
    for t in range(prompt, ids.shape[1]):
        lg, kc, vc = dec._step(dec.params, jnp.asarray(ids[:, t:t + 1]),
                               kc, vc, jnp.int32(t))
        np.testing.assert_allclose(np.asarray(lg), want[:, t], atol=2e-5,
                                   err_msg=f"position {t}")


def test_bucketed_admission_prefill_fills_both_leaves_by_true_len(model,
                                                                   ids):
    """Two rows right-padded to one bucket of 32, at different phases of
    their windows: the logits at ``true_len - 1`` and, decoding on, the
    leaves the prefill left (the window that holds ``true_len - 1``, not
    the padded tail's)."""
    want = _ref_logits(model, ids)
    dec = LlamaDecoder(model, max_len=64)
    true_len = np.array([11, 22], np.int32)
    padded = np.zeros((2, 32), np.int32)
    for b, n in enumerate(true_len):
        padded[b, :n] = ids[b, :n]
    kc, vc = dec._empty_cache(2)
    lg, kc, vc = dec._admit_prefill(
        dec.params, jnp.asarray(padded), kc, vc, jnp.asarray(true_len),
        jnp.zeros((2,), jnp.int32))
    for b, n in enumerate(true_len):
        np.testing.assert_allclose(np.asarray(lg)[b], want[b, n - 1],
                                   atol=2e-5)
    st = dec.init_decode_state(ids[:, :4])          # a carry to lay over
    st = dataclasses.replace(st, logits=lg, kc=kc, vc=vc,
                             pos=jnp.asarray(true_len))
    toks = jnp.stack([jnp.asarray(ids[b, n:n + 6])
                      for b, n in enumerate(true_len)])
    for t in range(6):                  # per-row positions, teacher-forced
        lg, kc, vc = dec._step(dec.params, toks[:, t:t + 1], st.kc, st.vc,
                               st.pos + t)
        st = dataclasses.replace(st, kc=kc, vc=vc)
        for b, n in enumerate(true_len):
            np.testing.assert_allclose(np.asarray(lg)[b], want[b, n + t],
                                       atol=2e-5)


def _run(eng, out=None):
    out = {} if out is None else out
    while len(eng.scheduler) or list(eng.scheduler.slots.occupied()):
        out.update(eng.step())
    return out


def _pending_logits_match(eng, model, prompts):
    """Every occupied row's carry logits against the reference's at the
    row's last position (prompt + the tokens delivered so far)."""
    lg = np.asarray(eng.state.logits)
    for i, slot in eng.scheduler.slots.occupied():
        seq = np.concatenate([prompts[slot.request.id]]
                             + [np.asarray(t) for t in slot.tokens])
        assert slot.kv_pos == len(seq)
        want = _ref_logits(model, seq[None], positions=[len(seq) - 1])[0, 0]
        np.testing.assert_allclose(lg[i], want, atol=2e-5)


def test_engine_ring_rows_at_different_phases_match_generate_and_reference(
        model):
    """Ring admissions, staggered, rows at different phases of their
    windows in one chunk: tokens equal ``generate()`` alone, the carry's
    LOGITS equal the reference's after every chunk, and the counters
    count what a hand count gives."""
    dec = LlamaDecoder(model, max_len=64)
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, V, (n,), dtype=np.int32)
               for n in (5, 15, 33, 8, 22)]
    budgets = [14, 9, 11, 17, 10]
    solo = [np.asarray(dec.generate(p[None], b))[0]
            for p, b in zip(prompts, budgets)]
    eng = ServingEngine(dec, num_slots=3, chunk_size=3)
    m0 = eng.metrics()
    row = 2 * 4 * 16 * 4 * 3        # K and V, 4 heads x 16, float32, layers
    assert (m0["cache_leaf_kinds"], m0["cache_layers"]) == (2, 3)
    assert (m0["cache_bytes_per_position_window"],
            m0["cache_bytes_per_position_summary"],
            m0["cache_bytes_per_position_full"]) == (row, row, 0)
    rids = [eng.submit(p, b) for p, b in zip(prompts[:4], budgets[:4])]
    by_id = dict(zip(rids, prompts))
    out = {}
    for k in range(4):
        if k == 2:
            rids.append(eng.submit(prompts[4], budgets[4]))
            by_id[rids[-1]] = prompts[4]
        out.update(eng.step())
        assert eng.metrics()["chunk_dispatches"] == k + 1
        _pending_logits_match(eng, model, by_id)
    _run(eng, out)
    for rid, want in zip(rids, solo):
        assert np.array_equal(np.asarray(out[rid])[0], want)
    m = eng.metrics()
    assert m["admission_ring"]["host_scattered"] == 0
    assert [b.shape[2] for b in eng.state.kc] == [W, 64 // C] * 3
    assert m["eva_prefill_windows_total"] == sum(
        -(-len(p) // W) for p in prompts)
    assert 0 < m["live_window_positions_total"] <= W * m[
        "chunk_dispatches"] * 3
    assert 0 < m["live_summary_positions_total"] \
        < m["live_kv_positions_total"] // C + 1
    assert m["eva_window_ends_total"] >= 3 and \
        m["eva_chunks_summarised_total"] > m["eva_window_ends_total"]
    assert eng.registry.get("serving.cache.leaf_kinds").value == 2
    assert eng.registry.get(
        "serving.cache.bytes_per_position.summary").value == row
    assert eng.registry.get("serving.eva.window_ends").value \
        == m["eva_window_ends_total"]


def test_counters_of_one_row_equal_a_hand_count(model):
    """One row from position 13, chunks of 4 steps: window rows, visible
    summaries, chunks summarised and window ends, chunk by chunk."""
    dec = LlamaDecoder(model, max_len=64)
    eng = ServingEngine(dec, num_slots=1, chunk_size=4)
    prompt = np.arange(13, dtype=np.int32)
    eng.submit(prompt, 12)
    _run(eng)
    m = eng.metrics()
    # a chunk starts where the row's next write goes: 13 (the admission's
    # token is picked from the prefill's logits), then 17, 21
    starts = [13, 17, 21][:m["chunk_dispatches"]]
    assert m["chunk_dispatches"] == 3
    assert m["live_window_positions_total"] == sum(
        n % W + 1 for n in starts)
    assert m["live_summary_positions_total"] == sum(
        n // W * (W // C) for n in starts)
    assert m["eva_chunks_summarised_total"] == sum(
        (n + 4) // C - n // C for n in starts)
    assert m["eva_window_ends_total"] == sum(
        (n + 4) // W - n // W for n in starts)
    assert m["eva_prefill_windows_total"] == 2
    # what the prefill of 13 positions needed, by its bucket: the causal
    # half of window 0 (8 x 9 / 2) and of the 5 rows of window 1, whose
    # queries each see window 0's 4 summaries: a count over i, j, c
    local = sum(1 for i in range(13) for j in range(i + 1)
                if j // W == i // W)
    summ = sum(1 for i in range(13) for c in range(13 // C)
               if c // (W // C) < i // W)
    assert (local, summ) == (36 + 15, 20)
    assert m["eva_prefill_by_bucket"] == {eng.scheduler.bucket(13): {
        "rows": 1, "positions": 13, "local_pairs": local,
        "summary_pairs": summ}}


def test_snapshot_restore_and_migration_carry_a_row_with_summaries(
        model, tmp_path):
    rng = np.random.default_rng(2)
    prompts = [rng.integers(0, V, (n,), dtype=np.int32) for n in (6, 19)]
    solo = [np.asarray(LlamaDecoder(model, max_len=64).generate(p[None], 12))
            for p in prompts]

    def engine():
        return ServingEngine(LlamaDecoder(model, max_len=64), num_slots=2,
                             chunk_size=3)
    src = engine()
    rids = [src.submit(p, 12) for p in prompts]
    src.step()
    src.step()                  # the second row is past position 24 now
    src.snapshot(str(tmp_path / "snap"))
    dst = engine()
    assert dst.restore(str(tmp_path / "snap"))["in_flight"] == 2
    done = _run(dst)
    for rid, want in zip(rids, solo):
        assert np.array_equal(np.asarray(done[rid]), want)
    a, b = engine(), engine()
    rids = [a.submit(p, 12) for p in prompts]
    done = dict(a.step())
    payload = a.extract_rows([rids[1]])     # 19 positions: 8 summaries
    mapping = b.absorb_rows(payload)
    _run(a, done)
    done2 = _run(b)
    assert np.array_equal(np.asarray(done[rids[0]]), solo[0])
    assert np.array_equal(np.asarray(done2[mapping[rids[1]]]), solo[1])


def test_two_leaf_decode_attention_kernel_matches_xla():
    """``decode_attention_pair`` in interpret mode against the masked
    softmax over both leaves, per-row live lengths, a row with no summary
    yet and one whose window leaf is full."""
    from paddle_tpu.ops.pallas.decode_attention import (
        decode_attention_pair, supported_pair)
    rng = np.random.default_rng(3)
    B, H, D, L1, L2 = 4, 4, 128, 256, 128

    def arr(*shape):
        return jnp.asarray(rng.standard_normal(shape), jnp.float32)
    q, k1, v1 = arr(B, H, D), arr(B, H, L1, D), arr(B, H, L1, D)
    k2, v2 = arr(B, H, L2, D), arr(B, H, L2, D)
    n1 = jnp.asarray([1, 200, 256, 77], jnp.int32)
    n2 = jnp.asarray([0, 128, 33, 64], jnp.int32)
    assert supported_pair(q, k1, k2)
    got = decode_attention_pair(q, k1, v1, n1, k2, v2, n2, block_l=64)
    s = jnp.concatenate([jnp.einsum("bhd,bhld->bhl", q, k1),
                         jnp.einsum("bhd,bhld->bhl", q, k2)], -1) / D ** 0.5
    live = jnp.concatenate([jnp.arange(L1)[None] < n1[:, None],
                            jnp.arange(L2)[None] < n2[:, None]], -1)
    p = jax.nn.softmax(jnp.where(live[:, None], s, -jnp.inf), -1)
    want = jnp.einsum("bhl,bhld->bhd", p, jnp.concatenate([v1, v2], 2))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)
    # a scalar length for every row, as the per-token rung passes it
    got1 = decode_attention_pair(q, k1, v1, 9, k2, v2, 64)
    live1 = jnp.concatenate([jnp.arange(L1) < 9, jnp.arange(L2) < 64])
    p1 = jax.nn.softmax(jnp.where(live1[None, None], s, -jnp.inf), -1)
    np.testing.assert_allclose(
        np.asarray(got1), np.asarray(jnp.einsum(
            "bhl,bhld->bhd", p1, jnp.concatenate([v1, v2], 2))), atol=2e-5)


def test_prefill_attention_kernel_matches_the_masked_form():
    """``eva_prefill_attention`` in interpret mode (windows' own keys,
    causal, then the summaries of the windows before, one online softmax)
    against ``ops/eva.py``'s one masked softmax; one block a window and
    several."""
    from paddle_tpu.ops import eva
    from paddle_tpu.ops.pallas.eva_attention import (eva_prefill_attention,
                                                     supported)
    rng = np.random.default_rng(4)
    B, H, D = 1, 2, 16
    for Wn, Cn, nW in ((16, 2, 3), (1024, 2, 3)):
        S = Wn * nW
        q, k, v = (jnp.asarray(rng.standard_normal((B, S, H, D)),
                               jnp.float32) for _ in range(3))
        mu, phi = (jnp.asarray(rng.standard_normal((H, D)), jnp.float32)
                   for _ in range(2))
        ks, vs = eva.chunk_summaries(jnp.swapaxes(k, 1, 2),
                                     jnp.swapaxes(v, 1, 2), mu, phi, Cn)
        want = eva.eva_attention(q, k, v, ks, vs, Wn, Cn)
        assert supported(S, Wn, Wn // Cn) and not supported(S + 1, Wn, 1)
        got = eva_prefill_attention(
            *(jnp.swapaxes(x, 1, 2).reshape(B * H, S, D) for x in (q, k, v)),
            ks.reshape(B * H, -1, D), vs.reshape(B * H, -1, D),
            window=Wn, per=Wn // Cn)
        np.testing.assert_allclose(
            np.asarray(jnp.swapaxes(got.reshape(B, H, S, D), 1, 2)),
            np.asarray(want), atol=2e-5)


def test_chunk_pool_kernel_matches_the_pooling():
    """``eva_chunk_pool`` in interpret mode: each row's own chunk of the
    window leaf, by a per-row and by a scalar start."""
    from paddle_tpu.ops import eva
    from paddle_tpu.ops.pallas.eva_attention import (eva_chunk_pool,
                                                     pool_supported)
    rng = np.random.default_rng(8)
    B, H, Wn, D, Cn = 3, 4, 64, 128, 16
    kw, vw = (jnp.asarray(rng.standard_normal((B, H, Wn, D)), jnp.float32)
              for _ in range(2))
    mu, phi = (jnp.asarray(rng.standard_normal((H, D)), jnp.float32)
               for _ in range(2))
    assert pool_supported(kw, Cn) and not pool_supported(kw[:, :, :60], Cn)
    for start in (jnp.asarray([0, 48, 16], jnp.int32), jnp.int32(32)):
        got = eva_chunk_pool(kw, vw, mu, phi, start, chunk=Cn)
        st = np.broadcast_to(np.asarray(start), (B,))
        rows = [slice(int(s0), int(s0) + Cn) for s0 in st]
        want = eva.chunk_summaries(
            jnp.stack([kw[b, :, r] for b, r in enumerate(rows)]),
            jnp.stack([vw[b, :, r] for b, r in enumerate(rows)]),
            mu, phi, Cn)
        for g, w in zip(got, want):
            np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                       atol=2e-5)


def test_decoder_through_the_kernels_in_interpret_mode(model, ids):
    """``decode_attention_interpret`` routes a width the kernels take
    through ``decode_attention_pair``, ``eva_chunk_pool`` and
    ``kv_row_write``: per-row positions across a window's end against the
    XLA forms."""
    from paddle_tpu.flags import flags
    from paddle_tpu.ops.pallas import decode_attention as da
    from paddle_tpu.ops.pallas import eva_attention as ea
    cfg = dataclasses.replace(CFG, hidden_size=256, num_attention_heads=2,
                              num_key_value_heads=2, num_hidden_layers=1,
                              window_size=128, chunk_size=16)
    paddle.seed(4)
    m = EvabyteForCausalLM(cfg)
    prompt = np.random.default_rng(6).integers(0, V, (2, 120),
                                               dtype=np.int32)
    want = np.asarray(LlamaDecoder(m, max_len=2048).generate(
        prompt, 12, chunk_size=4))
    calls, pools = [], []
    real, real_pool = da.decode_attention_pair, ea.eva_chunk_pool

    def counted(*a, **kw):
        calls.append(a[1].shape)
        return real(*a, **kw)

    def counted_pool(*a, **kw):
        pools.append(kw["chunk"])
        return real_pool(*a, **kw)
    flags.decode_attention_interpret = True
    da.decode_attention_pair, ea.eva_chunk_pool = counted, counted_pool
    try:
        got = np.asarray(LlamaDecoder(m, max_len=2048).generate(
            prompt, 12, chunk_size=4))
    finally:
        flags.decode_attention_interpret = False
        da.decode_attention_pair, ea.eva_chunk_pool = real, real_pool
    assert calls and calls[0] == (2, 2, 128, 128) and pools[0] == 16
    assert np.array_equal(got, want)


def _refusals():
    def prefix_cache(dec, ids):
        ServingEngine(dec, num_slots=2, chunk_size=3, prefix_cache=True,
                      prefix_cache_bytes=1 << 20)

    def prefix_slab(dec, ids):
        ServingEngine(dec, num_slots=2, chunk_size=3).prefill_extract(
            ids[0, :9])

    def speculative_engine(dec, ids):
        ServingEngine(dec, num_slots=2, chunk_size=3, draft_model="skip:1",
                      num_speculative_tokens=2)

    def speculative_verify(dec, ids):
        dec.generate(ids[:, :6], 4, draft_model="skip:1")

    def bundle(dec, ids):
        from paddle_tpu.inference.bundle import export_decoder_bundle
        export_decoder_bundle(dec, "/nonexistent/never-written",
                              batch_sizes=[1], prompt_lens=[8],
                              decode_steps=[4])

    def banded_backward(dec, ids):
        from paddle_tpu.ops.pallas.flash_attention import (
            WindowBackwardError, flash_attention_fn)
        x = jnp.ones((1, 16, 2, 16), jnp.float32)
        try:
            jax.grad(lambda q: flash_attention_fn(
                q, x, x, causal=True, window=W).sum())(x)
        except WindowBackwardError as e:
            raise WindowedModelError(str(e)) from e

    def int8_cache(dec, ids):
        LlamaDecoder(_model(), max_len=64, quant="int8wk")
    return [prefix_cache, prefix_slab, speculative_engine,
            speculative_verify, bundle, banded_backward, int8_cache]


@pytest.mark.parametrize("ask", _refusals(), ids=lambda f: f.__name__)
def test_what_the_two_leaves_cannot_serve_is_refused_typed(model, ids, ask):
    with pytest.raises(WindowedModelError):
        ask(LlamaDecoder(model, max_len=64), ids)


@pytest.mark.parametrize("field,value", [
    ("attention_class", "softmax"), ("num_chunks", 128),
    ("rope_scaling", {"type": "yarn"}), ("window_size", 9),
    ("num_key_value_heads", 2)])
def test_config_keys_the_program_does_not_build_are_refused_typed(
        field, value):
    with pytest.raises(EvabyteConfigError):
        dataclasses.replace(CFG, **{field: value})


@pytest.mark.parametrize("name", ["norm_add_unit_offset", "fp32_skip_add",
                                  "eva"])
def test_what_the_program_builds_one_way_is_not_an_option(name):
    """The ``1 + w`` norm, the float32 stream and the two leaves are what
    the eager model, the decoder and the reference all do: constants of
    the class, which no instance can be built without."""
    assert name not in {f.name for f in dataclasses.fields(CFG)}
    assert getattr(CFG, name) is True
    with pytest.raises(TypeError):
        dataclasses.replace(CFG, **{name: False})


def test_parameters_and_pooling_vectors_as_the_configuration_says():
    paddle.seed(1)
    m = EvabyteForCausalLM(dataclasses.replace(CFG, dtype="bfloat16"))
    sd = m.state_dict()
    assert {str(t.value.dtype) for t in sd.values()} == {"bfloat16"}
    assert tuple(sd["lm_head.weight"].shape) == (
        64, CFG.num_pred_heads * V)
    mu = np.asarray(sd["model.layers.0.self_attn.adaptive_mu_k"].value,
                    np.float32)
    assert mu.shape == (4, 16) and np.abs(mu).max() <= 16 ** -0.5
    assert float(np.abs(np.asarray(
        sd["model.norm.weight"].value, np.float32)).max()) == 0.0
    dec = LlamaDecoder(m, max_len=64)          # the 1 + w is folded
    assert float(dec.params["model.norm.weight"][0]) == 1.0


# the lowered texts of the accepted configurations' chunk program (4 steps,
# 2 rows) and bucket-128 admission prefill at max_len 128. The chunk
# programs' are those of the parent of PR 36 (fde7c2a) still: the two-leaf
# path is chosen at trace time by the config, and no PR since has meant to
# move them. The prefills' were RETAKEN by PR 37 on its own tree, which
# meant to change them: ``admit_prefill`` gathers each row at ``true_len -
# 1`` before the last final norm and the head where it made all S rows of
# logits (its attention is still buffer-wide, or over the fresh keys for a
# windowed model, now in XLA's form off the TPU: one routing rule for the
# flash forward, ``_routing.kernel_backend``);
# tests/test_prefill_from_zero.py holds the new route's logits to the old.
# THIS IS EVIDENCE, NOT A CONTRACT ON THE COMPILER'S TEXT: a PR
# that means to change the shared block (or a JAX upgrade) retakes the
# hashes on its own tree and says so, or replaces this test with the
# logits-parity tests that already hold these configurations
# (tests/test_llama.py, test_afmoe.py, test_ouro.py). "ouro" (both
# programs) was RETAKEN by PR 39 on its own tree, which meant to move it:
# an MHA cache is head-major now (LlamaConfig.cache_head_major); "llama"
# (GQA) and "afmoe" are unchanged, so those cells run the parent's programs.
_TEXTS = {
    "llama": ("e3fe9ce2b00457d9", "a978c6f63fa34a6f"),
    "afmoe": ("f72d6133ef314834", "3a9bceaaa23385d0"),
    "ouro": ("3f1a2be5c433c459", "9acf84fd765c5e7e"),
}


@pytest.mark.parametrize("family", sorted(_TEXTS))
def test_accepted_configurations_programs_keep_their_text(family):
    from paddle_tpu.models import (AFMOE_TINY, OURO_TINY, TINY_CONFIG,
                                   AfmoeForCausalLM, LlamaForCausalLM,
                                   OuroForCausalLM)
    cfg, cls = {"llama": (TINY_CONFIG, LlamaForCausalLM),
                "afmoe": (AFMOE_TINY, AfmoeForCausalLM),
                "ouro": (OURO_TINY, OuroForCausalLM)}[family]
    dec = LlamaDecoder(cls(cfg), max_len=128)
    B = 2
    kc, vc = dec._empty_cache(B)

    def z(shape, dt):
        return jnp.zeros(shape, dt)
    chunk = dec._ring_chunk_decode._jitted.lower(
        dec.params, z((B, cfg.vocab_size), jnp.float32), kc, vc,
        z((B,), jnp.int32), z((B, 2), jnp.uint32), z((B,), jnp.bool_),
        z((B,), jnp.int32), z((B,), jnp.float32), None, *(None,) * 9,
        steps=4, do_sample=False, top_k=None, top_p=None).as_text()
    kc1, vc1 = dec._empty_cache(1)
    prefill = dec._admit_prefill._jitted.lower(
        dec.params, z((1, 128), jnp.int32), kc1, vc1, z((1,), jnp.int32),
        z((1,), jnp.int32)).as_text()
    got = tuple(hashlib.sha256(t.encode()).hexdigest()[:16]
                for t in (chunk, prefill))
    assert got == _TEXTS[family]
