"""Quantized decode: int8 weight (int8w) + int8 KV-cache (int8wk) recipes.

The load-bearing properties:
- recipe resolution: ``quant=`` wins, ``weight_dtype="int8"`` aliases
  int8w, ``PADDLE_TPU_DECODE_QUANT`` / ``FLAGS_decode_quant`` are the
  defaults, garbage is a typed refusal;
- PARITY WITHIN A RECIPE IS BIT-EXACT: the fused one-dispatch loop, the
  chunked re-enterable loop (any slicing) and the per-token fallback all
  run the same quantize/dequantize stream, so greedy tokens — and
  per-row-keyed sampled tokens across chunk slicings — are identical;
- dispatch accounting is unchanged: every quantized generate is still
  prefill + ONE dispatch;
- the quantized carry flows through serving admission, prefix-cache
  slab extract/load (full/partial/miss all bit-exact vs solo), and AOT
  bundle export/load; ``decode_mode.quant`` records the recipe and a
  mismatched ask is refused typed (``QuantMismatchError``) both ways;
- int8w on a mesh falls back to the XLA dequant form with token parity
  vs the single-device int8w path; int8wk on a mesh is refused typed
  (``QuantizedKVMeshError``);
- cache-aware admission ordering: same-priority queued requests reorder
  toward prefix-slab reuse (FIFO within a digest group), counted by
  ``serving.admission.cache_reordered``.

Quality vs fp32 is NOT bit-exact (int8 rounding moves logits); the
documented gate — teacher-forced top-1 agreement >= 99% with logit RMSE
reported — is hard-asserted in ``bench.py --decode --quant``.
"""

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.inference.generate import LlamaDecoder
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from paddle_tpu.quantization.kv_cache import (
    QuantMismatchError, canonical_quant, is_quantized_kv,
    resolve_decode_quant)

GQA = dict(vocab_size=64, hidden_size=32, intermediate_size=64,
           num_hidden_layers=2, num_attention_heads=4,
           num_key_value_heads=2, max_position_embeddings=64)
MHA = dict(GQA, num_key_value_heads=4)


def _model(seed=0, cfg=GQA):
    paddle.seed(seed)
    return LlamaForCausalLM(LlamaConfig(**cfg))


@pytest.fixture(scope="module")
def model():
    return _model(11)


@pytest.fixture(scope="module")
def prompt():
    return np.random.default_rng(0).integers(0, 64, (2, 5))


# -- recipe resolution -------------------------------------------------------

def test_recipe_resolution_and_refusals(monkeypatch):
    assert resolve_decode_quant() is None
    assert resolve_decode_quant("int8w") == "int8w"
    assert resolve_decode_quant(weight_dtype="int8") == "int8w"
    assert resolve_decode_quant("int8wk", weight_dtype="int8") == "int8wk"
    assert canonical_quant("none") is None
    assert canonical_quant("fp32") is None
    with pytest.raises(QuantMismatchError):
        canonical_quant("int4")
    with pytest.raises(ValueError):
        resolve_decode_quant(weight_dtype="fp8")
    with pytest.raises(QuantMismatchError):
        # an explicit fp32 ask contradicting weight_dtype='int8'
        resolve_decode_quant("fp32", weight_dtype="int8")
    monkeypatch.setenv("PADDLE_TPU_DECODE_QUANT", "int8wk")
    assert resolve_decode_quant() == "int8wk"
    monkeypatch.delenv("PADDLE_TPU_DECODE_QUANT")
    paddle.set_flags({"decode_quant": "int8w"})
    try:
        assert resolve_decode_quant() == "int8w"
    finally:
        paddle.set_flags({"decode_quant": ""})


def test_decoder_surface(model):
    dec = LlamaDecoder(model, max_len=32, quant="int8wk")
    assert dec.quant == "int8wk" and dec.quant_kv
    assert dec.weight_dtype == "int8"      # legacy alias surface
    kc, vc = dec._empty_cache(2)
    assert len(kc) == model.config.num_hidden_layers
    for b in kc + vc:
        assert is_quantized_kv(b) and b["q"].dtype == np.int8
        assert b["s"].shape == b["q"].shape[:-1] + (1,)
    # the legacy weight_dtype argument still builds int8w
    alias = LlamaDecoder(model, max_len=32, weight_dtype="int8")
    assert alias.quant == "int8w" and not alias.quant_kv


def test_model_generate_quant_kwarg(model, prompt):
    dec = LlamaDecoder(model, max_len=64, quant="int8w")
    want = np.asarray(dec.generate(prompt, max_new_tokens=6))
    got = np.asarray(model.generate(prompt, max_new_tokens=6,
                                    quant="int8w"))
    np.testing.assert_array_equal(got, want)
    # recipe is part of the cached-decoder key: fp32 ask rebuilds
    plain = np.asarray(model.generate(prompt, max_new_tokens=6))
    ref = np.asarray(LlamaDecoder(model, max_len=64)
                     .generate(prompt, max_new_tokens=6))
    np.testing.assert_array_equal(plain, ref)


# -- parity within a recipe: fused == chunked == per-token -------------------

@pytest.mark.parametrize("cfg", [GQA, MHA], ids=["gqa", "mha"])
@pytest.mark.parametrize("quant", ["int8w", "int8wk"])
def test_greedy_parity_across_paths(cfg, quant):
    model = _model(7, cfg)
    dec = LlamaDecoder(model, max_len=32, quant=quant)
    prompt = np.random.default_rng(1).integers(0, 64, (2, 5))
    fused = np.asarray(dec.generate(prompt, max_new_tokens=10))
    for T in (1, 3, 10):
        ch = np.asarray(dec.generate(prompt, max_new_tokens=10,
                                     chunk_size=T))
        np.testing.assert_array_equal(ch, fused)
    paddle.set_flags({"decode_fallback": True})
    try:
        pt = np.asarray(dec.generate(prompt, max_new_tokens=10))
    finally:
        paddle.set_flags({"decode_fallback": False})
    np.testing.assert_array_equal(pt, fused)


@pytest.mark.parametrize("quant", ["int8w", "int8wk"])
def test_sampled_chunk_slicing_invariance(model, prompt, quant):
    """Per-row-keyed sampling: a row's draw depends only on its seed —
    chunk slicing must not move it (the admission contract, now over a
    quantized carry)."""
    dec = LlamaDecoder(model, max_len=32, quant=quant)
    kw = dict(do_sample=True, top_k=8, temperature=0.9, seed=5)
    a = np.asarray(dec.generate(prompt, 8, chunk_size=2, **kw))
    b = np.asarray(dec.generate(prompt, 8, chunk_size=5, **kw))
    np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("quant", [None, "int8w", "int8wk"])
def test_dispatch_accounting_unchanged(model, prompt, quant):
    dec = LlamaDecoder(model, max_len=32, quant=quant)
    dec.generate(prompt, max_new_tokens=6)            # compile+warm
    d0 = dec.dispatch_count
    dec.generate(prompt, max_new_tokens=6)
    assert dec.dispatch_count - d0 == 2               # prefill + 1


def test_int8wk_state_reentry_is_quantized(model, prompt):
    """The DecodeState carry holds the int8 rows + scales across chunk
    re-entry — no fp copy of the cache ever materializes in the carry."""
    dec = LlamaDecoder(model, max_len=32, quant="int8wk")
    st = dec.init_decode_state(prompt)
    assert all(is_quantized_kv(b) for b in st.kc + st.vc)
    toks, st2 = dec.decode_chunk(st, 4)
    assert all(is_quantized_kv(b) and b["q"].dtype == np.int8
               for b in st2.kc + st2.vc)
    # chained chunks == run-to-completion
    toks2, _ = dec.decode_chunk(st2, 4)
    got = np.concatenate([prompt, np.asarray(toks), np.asarray(toks2)], 1)
    want = np.asarray(dec.generate(prompt, max_new_tokens=8))
    np.testing.assert_array_equal(got, want)


# -- serving + prefix cache over the quantized carry -------------------------

def test_engine_parity_and_quant_ask(model, prompt):
    from paddle_tpu.serving import ServingEngine
    dec = LlamaDecoder(model, max_len=48, quant="int8wk")
    eng = ServingEngine(dec, num_slots=2, chunk_size=3, quant="int8wk")
    rids = [eng.submit(prompt[i % 2], 7, seed=i) for i in range(4)]
    res = eng.drain()
    for i, rid in enumerate(rids):
        solo = np.asarray(dec.generate(prompt[i % 2][None], 7))
        np.testing.assert_array_equal(np.asarray(res[rid]), solo)
    assert eng.status()["quant"] == "int8wk"
    with pytest.raises(QuantMismatchError):
        ServingEngine(dec, num_slots=2, chunk_size=3, quant="int8w")
    with pytest.raises(QuantMismatchError):
        ServingEngine(LlamaDecoder(model, max_len=48), num_slots=2,
                      chunk_size=3, quant="int8wk")


def test_prefix_cache_hit_classes_quantized(model):
    """Full / partial / miss admissions over int8 KV slabs, all
    bit-exact vs solo; slab byte accounting charges the actual dtypes
    (int8 rows at 1 byte/elt) and snapshots report the slab dtype."""
    from paddle_tpu.serving import ServingEngine
    rng = np.random.default_rng(3)
    dec = LlamaDecoder(model, max_len=48, quant="int8wk")
    dec_fp = LlamaDecoder(model, max_len=48)
    eng = ServingEngine(dec, num_slots=2, chunk_size=3,
                        prefix_cache=True, prefix_cache_bytes=1 << 30,
                        prefix_block_tokens=4)
    pre = rng.integers(0, 64, (12,))
    lead = np.concatenate([pre, rng.integers(0, 64, (4,))])
    r0 = eng.submit(lead, 6, seed=0)
    eng.drain()
    r_full = eng.submit(lead, 6, seed=1)                       # full
    r_part = eng.submit(np.concatenate(
        [pre, rng.integers(0, 64, (4,))]), 6, seed=2)          # partial
    r_miss = eng.submit(rng.integers(0, 64, (16,)), 6, seed=3)  # miss
    out = eng.drain()
    m = eng.metrics()["prefix_cache"]
    assert m["engine_hits_full"] >= 1 and m["engine_hits_partial"] >= 1
    for rid in (r_full, r_part, r_miss):
        got = np.asarray(out[rid])
        solo = np.asarray(dec.generate(got[:, :-6], 6))
        np.testing.assert_array_equal(got, solo)
    rec = out[r_full].resilience["serving"]
    assert rec["prefix_hit"] == "full"
    assert rec["admission_dispatches"] == 0
    # byte accounting at actual dtypes: the int8 pool is well under the
    # fp32 pool for the same traffic (scales cost 1/D extra)
    eng_fp = ServingEngine(dec_fp, num_slots=2, chunk_size=3,
                           prefix_cache=True, prefix_cache_bytes=1 << 30,
                           prefix_block_tokens=4)
    eng_fp.submit(lead, 6, seed=0)
    eng_fp.drain()
    b_q = eng.prefix_cache.lookup(lead).slab.nbytes
    b_fp = eng_fp.prefix_cache.lookup(lead).slab.nbytes
    assert b_q < 0.6 * b_fp, (b_q, b_fp)
    snap = eng.prefix_cache.snapshot()
    assert snap["slab_dtypes"] == ["float32+int8"]
    assert all(row["dtype"] == "float32+int8"
               for row in snap["slab_table"])
    assert eng.status()["prefix_cache"]["slab_dtypes"] \
        == ["float32+int8"]


def test_cache_aware_admission_ordering(model):
    """Among same-priority queued requests, ones whose prefix digest is
    already cached are admitted first and same-digest requests admit
    together (FIFO within the group); the reorders are counted in
    metrics()['admission_cache_reordered']."""
    from paddle_tpu.serving import ServingEngine
    rng = np.random.default_rng(4)
    dec = LlamaDecoder(model, max_len=48)
    eng = ServingEngine(dec, num_slots=1, chunk_size=3,
                        prefix_cache=True, prefix_cache_bytes=1 << 30,
                        prefix_block_tokens=4)
    assert eng.scheduler.cache_aware
    pre = rng.integers(0, 64, (8,))
    shared = [np.concatenate([pre, rng.integers(0, 64, (4,))])
              for _ in range(2)]
    cold = [rng.integers(0, 64, (12,)) for _ in range(2)]
    # seed the cache with the shared prefix, drain fully
    eng.submit(shared[0], 4, seed=0)
    eng.drain()
    # queue: cold, cold, shared — with one slot, the shared-prefix
    # request (a guaranteed slab hit) jumps the two colds
    ids = [eng.submit(cold[0], 4, seed=1), eng.submit(cold[1], 4, seed=2),
           eng.submit(shared[1], 4, seed=3)]
    order = []
    while len(order) < 3:
        order.extend(rid for rid, _ in eng.step())
    assert order[0] == ids[2], order           # the cached one led
    assert order[1:] == ids[:2], order         # colds kept FIFO
    assert eng.metrics()["admission_cache_reordered"] >= 1
    # parity survives the reordering
    for p, rid in zip(cold + [shared[1]], ids):
        solo = np.asarray(dec.generate(p[None], 4))
        np.testing.assert_array_equal(np.asarray(eng.result(rid)), solo)


def test_cache_aware_off_by_default_without_prefix_cache(model):
    from paddle_tpu.serving import ServingEngine
    eng = ServingEngine(LlamaDecoder(model, max_len=48), num_slots=2,
                        chunk_size=3)
    assert not eng.scheduler.cache_aware
    assert eng.metrics()["admission_cache_reordered"] == 0


def test_scheduler_cache_aware_unit():
    """Scheduler-level ordering semantics without an engine: FIFO within
    a digest group, cached-group head first, priorities untouched."""
    from paddle_tpu.serving import Request, Scheduler
    s = Scheduler(2, cache_aware=True)
    s.cache_probe = lambda g: g == "hot"
    mk = lambda i, g, pr=0: Request(  # noqa: E731
        id=i, prompt=np.zeros(4, np.int64), max_new_tokens=1,
        priority=pr, prefix_group=g)
    for i, g in enumerate(["cold1", "cold2", "hot", "hot"]):
        s.push(mk(i, g))
    picked = [r.id for _, r in s.admissions()]
    assert picked == [2, 3]                 # the hot group led, FIFO in it
    assert s.cache_reordered >= 1
    s.slots.release(0), s.slots.release(1)
    picked = [r.id for _, r in s.admissions()]
    assert picked == [0, 1]                 # colds drained FIFO
    # a higher-priority tier is never jumped by a cached lower one
    s2 = Scheduler(1, policy="priority", cache_aware=True)
    s2.cache_probe = lambda g: g == "hot"
    s2.push(mk(0, "hot", pr=5))
    s2.push(mk(1, "coldtop", pr=0))
    assert [r.id for _, r in s2.admissions()] == [1]


# -- AOT bundles -------------------------------------------------------------

@pytest.mark.parametrize("quant", ["int8w", "int8wk"])
def test_bundle_roundtrip_and_refusals(model, prompt, quant, tmp_path):
    from paddle_tpu.inference import AotPredictor, export_decoder_bundle
    from paddle_tpu.serving import ServingEngine
    dec = LlamaDecoder(model, max_len=32, quant=quant)
    want = np.asarray(dec.generate(prompt[:1], max_new_tokens=6))
    d = str(tmp_path / quant)
    export_decoder_bundle(dec, d, prompt_lens=[5], decode_steps=[5],
                          batch_sizes=[1], chunk_sizes=[3])
    pred = AotPredictor(d)
    assert pred.quant_recipe == quant
    got = np.asarray(pred.generate(prompt[:1], 6))
    np.testing.assert_array_equal(got, want)
    # matching explicit ask serves; mismatched asks refuse typed
    pred.generate(prompt[:1], 6, quant=quant)
    with pytest.raises(QuantMismatchError):
        pred.generate(prompt[:1], 6, quant="fp32")
    other = "int8w" if quant == "int8wk" else "int8wk"
    with pytest.raises(QuantMismatchError):
        pred.generate(prompt[:1], 6, quant=other)
    # the recipe is recorded in decode_mode.quant
    assert pred.meta["decode_mode"]["quant"]["recipe"] == quant
    if quant == "int8wk":
        assert pred.meta["decode_mode"]["quant"]["kv_cache"] == "int8"
        assert pred.meta["caches"]["1"]["dtype"] == "int8"
        assert "quant" in pred.meta["caches"]["1"]
    # chunked serving over the bundle (quantized carry as runtime IO)
    eng = ServingEngine(pred, num_slots=1, chunk_size=3, quant=quant)
    rid = eng.submit(prompt[0], 6)
    np.testing.assert_array_equal(np.asarray(eng.drain()[rid]), want)


def test_unquantized_bundle_refuses_quant_ask(model, prompt, tmp_path):
    from paddle_tpu.inference import AotPredictor, export_decoder_bundle
    from paddle_tpu.serving import ServingEngine
    dec = LlamaDecoder(model, max_len=32)
    d = str(tmp_path / "fp")
    export_decoder_bundle(dec, d, prompt_lens=[5], decode_steps=[5],
                          batch_sizes=[1], chunk_sizes=[3])
    pred = AotPredictor(d)
    assert pred.quant_recipe is None
    pred.generate(prompt[:1], 6, quant="none")        # explicit fp32 OK
    with pytest.raises(QuantMismatchError):
        pred.generate(prompt[:1], 6, quant="int8wk")
    with pytest.raises(QuantMismatchError):
        ServingEngine(AotPredictor(d), num_slots=1, chunk_size=3,
                      quant="int8w")


# -- mesh --------------------------------------------------------------------

def _mesh(shape=(2, 2)):
    from paddle_tpu.parallel import ProcessMesh
    return ProcessMesh(shape=shape, dim_names=("dp", "tp"))


def test_int8w_mesh_token_parity(model, prompt):
    """int8w under a mesh: the Pallas tile gates off, the XLA dequant
    form shards — tokens must match the single-device int8w path."""
    ref = LlamaDecoder(model, max_len=32, quant="int8w")
    sh = LlamaDecoder(model, max_len=32, quant="int8w", mesh=_mesh())
    a = np.asarray(ref.generate(prompt, max_new_tokens=8))
    b = np.asarray(sh.generate(prompt, max_new_tokens=8))
    np.testing.assert_array_equal(a, b)
    c = np.asarray(sh.generate(prompt, max_new_tokens=8, chunk_size=3))
    np.testing.assert_array_equal(a, c)


def test_int8wk_mesh_refused_typed(model):
    from paddle_tpu.inference.sharding import QuantizedKVMeshError
    from paddle_tpu.runtime.resilience import classify_error
    with pytest.raises(QuantizedKVMeshError) as ei:
        LlamaDecoder(model, max_len=32, quant="int8wk", mesh=_mesh())
    # fatal for the resilience classifier: never a retry/degrade
    assert classify_error(ei.value) != "transient"


# -- speculative decode under quantization -----------------------------------

def test_spec_draft_quant_int8w_greedy_invisible(model, prompt):
    """``draft_quant='int8w'`` quantizes ONLY the draft: the verify pass
    runs the fp32 target exactly, so greedy speculative output == the
    plain fused greedy decode — a worse draft can only shorten the
    acceptance length, never change a token."""
    draft = _model(21, dict(GQA, num_hidden_layers=1))
    dec = LlamaDecoder(model, max_len=40)
    plain = dec.generate(prompt, max_new_tokens=8)
    d0 = dec.dispatch_count
    fused = dec.generate(prompt, max_new_tokens=8, draft_model=draft,
                         num_speculative_tokens=2, draft_quant="int8w")
    assert dec.dispatch_count - d0 == 3, \
        "expected 2 prefills + ONE decode dispatch"
    np.testing.assert_array_equal(np.asarray(fused), np.asarray(plain))
    stats = dec.last_spec_stats
    assert stats["num_speculative_tokens"] == 2
    assert 0.0 <= stats["acceptance_len_mean"] <= 2.0


def test_spec_draft_quant_sampled_matches_unquantized_target_stream(
        model, prompt):
    """Sampled speculative decode with a quantized draft still follows
    the TARGET's keyed sampling stream: rejection sampling corrects the
    draft's proposal distribution, and the quantized draft only shifts
    WHICH tokens get proposed. The output must stay a valid sample of
    the target — here pinned by seed against the same-seed plain run
    shape/vocab contract."""
    draft = _model(22, dict(GQA, num_hidden_layers=1))
    dec = LlamaDecoder(model, max_len=40)
    out = dec.generate(prompt, max_new_tokens=8, draft_model=draft,
                       num_speculative_tokens=2, draft_quant="int8w",
                       do_sample=True, temperature=0.8, top_k=8, seed=7)
    arr = np.asarray(out)
    assert arr.shape == (prompt.shape[0], prompt.shape[1] + 8)
    assert arr.max() < 64 and arr.min() >= 0
    # determinism under a fixed seed: the quantized-draft stream replays
    again = dec.generate(prompt, max_new_tokens=8, draft_model=draft,
                         num_speculative_tokens=2, draft_quant="int8w",
                         do_sample=True, temperature=0.8, top_k=8,
                         seed=7)
    np.testing.assert_array_equal(arr, np.asarray(again))


def test_spec_skip_draft_under_int8w_target(model, prompt):
    """The layer-skip draft under a QUANTIZED target: 'skip:N' reuses
    the target's int8 params, so the whole speculative stack runs
    quantized — greedy speculation stays invisible vs the plain int8w
    decode."""
    qdec = LlamaDecoder(model, max_len=40, quant="int8w")
    plain = qdec.generate(prompt, max_new_tokens=8)
    d0 = qdec.dispatch_count
    fused = qdec.generate(prompt, max_new_tokens=8,
                          draft_model="skip:1",
                          num_speculative_tokens=2)
    assert qdec.dispatch_count - d0 == 3
    np.testing.assert_array_equal(np.asarray(fused), np.asarray(plain))


def test_spec_draft_quant_refusals(model, prompt):
    """Typed refusals: draft_quant without a draft, draft_quant over a
    layer-skip view (quantize the target instead), unknown recipe."""
    dec = LlamaDecoder(model, max_len=40)
    draft = _model(23, dict(GQA, num_hidden_layers=1))
    with pytest.raises(ValueError, match="requires a draft_model"):
        dec.generate(prompt, max_new_tokens=4, draft_quant="int8w")
    with pytest.raises(ValueError, match="quantize the target"):
        dec.generate(prompt, max_new_tokens=4, draft_model="skip:1",
                     num_speculative_tokens=2, draft_quant="int8w")
    with pytest.raises(ValueError, match="draft_quant"):
        dec.generate(prompt, max_new_tokens=4, draft_model=draft,
                     num_speculative_tokens=2, draft_quant="int4")
