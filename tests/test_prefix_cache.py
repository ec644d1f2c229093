"""Content-hashed prefix cache + KV slab pool (serving/prefix_cache.py).

The load-bearing properties:
- admission through the cache is BIT-EXACT with cold admission for
  every hit class — full hit (zero prefill dispatches, asserted via
  dispatch accounting), partial hit (suffix-only prefill on top of the
  loaded slab) and miss — for greedy AND per-row-keyed sampling;
- block-boundary hashing: a shared prefix with a different suffix hits
  at the longest common block boundary; a one-token divergence inside
  the first block misses outright;
- refcount pinning: a slab with an in-flight request on it cannot be
  evicted, however tight the byte budget;
- LRU + byte-budget eviction recycles the pool oldest-first;
- mesh path: slabs live under the carry's NamedShardings (no
  gather-to-host), and a shared cache refuses a different topology
  typed (``MeshMismatchError``);
- batched same-bucket admission folds several waiting (suffix-)prefills
  into one dispatch, recorded as ``admission.dispatches_saved``.
"""

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.inference.generate import LlamaDecoder
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from paddle_tpu.serving import PrefixCache, ServingEngine, prefix_digests

pytestmark = pytest.mark.serving

CFG = dict(vocab_size=64, hidden_size=32, intermediate_size=64,
           num_hidden_layers=2, num_attention_heads=4,
           num_key_value_heads=2, max_position_embeddings=64)

BLOCK = 4          # hash granularity small enough for short test prompts
CACHE_KW = dict(prefix_cache=True, prefix_cache_bytes=1 << 30,
                prefix_block_tokens=BLOCK)


def _model(seed=0):
    paddle.seed(seed)
    return LlamaForCausalLM(LlamaConfig(**CFG))


@pytest.fixture(scope="module")
def dec():
    return LlamaDecoder(_model(), max_len=64)


def _mesh(shape=(2, 2)):
    from paddle_tpu.parallel import ProcessMesh
    return ProcessMesh(shape=shape, dim_names=("dp", "tp"))


@pytest.fixture(scope="module")
def shdec():
    """A 2x2 {dp,tp}-sharded decoder over the SAME weights as ``dec``."""
    return LlamaDecoder(_model(), max_len=64, mesh=_mesh((2, 2)))


def _spec_axes(x):
    """Mesh axes a live array is sharded over; for a KV cache (a tuple of
    per-layer buffers) the axes every layer's buffer is sharded over."""
    if isinstance(x, tuple):
        per_layer = [_spec_axes(b) for b in x]
        assert all(a == per_layer[0] for a in per_layer), per_layer
        return per_layer[0]
    axes = set()
    for e in tuple(getattr(x.sharding, "spec", ()) or ()):
        if e is None:
            continue
        axes.update(e if isinstance(e, (tuple, list)) else (e,))
    return axes


def _shared_prefix_mix(rng, prefix_len=8, suffix_len=3):
    """One shared prefix + three prompts over it: the leader, an exact
    duplicate, and a different-suffix sibling."""
    pre = rng.integers(0, 64, (prefix_len,))
    p1 = np.concatenate([pre, rng.integers(0, 64, (suffix_len,))])
    p2 = np.concatenate([pre, rng.integers(0, 64, (suffix_len + 2,))])
    return pre, p1, p2


# -- hashing ---------------------------------------------------------------

def test_prefix_digests_ladder():
    toks = np.arange(10)
    d = prefix_digests(toks, 4)
    assert [L for L, _ in d] == [10, 8, 4]      # full first, then blocks
    # exact multiples do not duplicate the full length
    assert [L for L, _ in prefix_digests(np.arange(8), 4)] == [8, 4]
    # same prefix -> same boundary digests, regardless of suffix
    d2 = prefix_digests(np.concatenate([toks[:8], [63, 62]]), 4)
    assert dict(d)[8] == dict(d2)[8]
    assert dict(d)[4] == dict(d2)[4]
    # a one-token divergence inside the FIRST block changes every digest
    toks3 = toks.copy()
    toks3[1] = (toks3[1] + 1) % 64
    d3 = prefix_digests(toks3, 4)
    assert not (set(h for _, h in d3) & set(h for _, h in d))
    with pytest.raises(ValueError, match="at least 1"):
        prefix_digests(np.zeros((0,)), 4)


# -- host-side pool semantics (no device work) ------------------------------

def _fake_slab_arrays(nbytes=1024):
    kc = np.zeros((nbytes // 4,), np.float32)
    return kc, kc.copy(), np.zeros((1, 4), np.float32)


def test_pool_lru_eviction_under_byte_budget():
    one = sum(a.nbytes for a in _fake_slab_arrays())
    cache = PrefixCache(bytes_budget=2 * one, block_tokens=4)
    rng = np.random.default_rng(0)
    toks = [rng.integers(0, 64, (8,)) for _ in range(3)]
    slabs = [cache.insert(t, *_fake_slab_arrays(), bucket=8)
             for t in toks]
    assert len(cache) == 2 and cache.evictions == 1
    # the OLDEST (first) slab went; the newer two still hit
    assert cache.lookup(toks[0]).kind == "miss"
    assert cache.lookup(toks[1]).kind == "full"
    assert cache.lookup(toks[2]).kind == "full"
    # touching slab 1 makes slab 2 the LRU victim of the next insert
    cache.lookup(toks[1])
    cache.insert(rng.integers(0, 64, (8,)), *_fake_slab_arrays(),
                 bucket=8)
    assert cache.lookup(toks[1]).kind == "full"
    assert cache.lookup(toks[2]).kind == "miss"
    st = cache.stats()
    assert st["evictions"] == 2 and st["bytes_cached"] <= 2 * one


def test_pool_refcount_pins_against_eviction():
    one = sum(a.nbytes for a in _fake_slab_arrays())
    cache = PrefixCache(bytes_budget=one, block_tokens=4)
    rng = np.random.default_rng(1)
    t0 = rng.integers(0, 64, (8,))
    s0 = cache.insert(t0, *_fake_slab_arrays(), bucket=8)
    cache.pin(s0)
    # over budget: the only evictable slab is the NEW one — the pinned
    # slab is untouchable
    s1 = cache.insert(rng.integers(0, 64, (8,)), *_fake_slab_arrays(),
                      bucket=8)
    assert s1 is None                      # evicted on the way in
    assert cache.lookup(t0).kind == "full"
    assert cache.stats()["evictions"] == 1
    # tighten BELOW the pinned slab: the pool overshoots rather than
    # evicting it
    cache.bytes_budget = 1
    cache._evict_to_budget()
    assert cache.lookup(t0).kind == "full"
    assert cache.stats()["bytes_cached"] > cache.bytes_budget
    # unpin -> eviction to budget runs immediately
    cache.unpin(s0)
    assert cache.lookup(t0).kind == "miss"
    assert cache.stats()["bytes_cached"] <= cache.bytes_budget
    with pytest.raises(RuntimeError, match="unpin"):
        cache.unpin(s0)


def test_pool_dedupes_identical_full_prefixes():
    cache = PrefixCache(bytes_budget=1 << 20, block_tokens=4)
    toks = np.arange(9)
    s1 = cache.insert(toks, *_fake_slab_arrays(), bucket=16)
    s2 = cache.insert(toks, *_fake_slab_arrays(), bucket=16)
    assert s1 is s2 and len(cache) == 1


# -- engine admission: hit classes, parity, accounting ----------------------

def test_full_hit_zero_prefill_dispatches_bitexact(dec):
    """The tentpole contract: an exact-duplicate prompt admits with
    ZERO prefill dispatches (one row-scatter), tokens bit-exact vs the
    cold admission and vs a solo generate."""
    rng = np.random.default_rng(2)
    _, p1, _ = _shared_prefix_mix(rng)
    solo = np.asarray(dec.generate(p1[None], 6))
    eng = ServingEngine(dec, num_slots=2, chunk_size=4, **CACHE_KW)
    a = eng.submit(p1, 6)
    eng.drain()
    d0 = dec.dispatch_count
    prefills0 = eng.prefill_dispatches
    b = eng.submit(p1, 6)
    eng.drain()
    assert eng.prefill_dispatches == prefills0     # ZERO new prefills
    # and no hidden dispatch either: only the chunk dispatches moved
    assert dec.dispatch_count - d0 == \
        eng.chunk_dispatches + eng.step_dispatches - 2  # 2 chunks pre-dup
    np.testing.assert_array_equal(np.asarray(eng.result(a)), solo)
    np.testing.assert_array_equal(np.asarray(eng.result(b)), solo)
    rec = eng.result(b).resilience["serving"]
    assert rec["prefix_hit"] == "full"
    assert rec["admission_dispatches"] == 0
    assert rec["prefill_tokens_saved"] == len(p1)
    assert eng.result(a).resilience["serving"]["prefix_hit"] == "miss"
    m = eng.metrics()
    assert m["prefix_cache"]["engine_hits_full"] == 1
    assert m["admission_dispatches_saved"] >= 1


def test_partial_hit_suffix_prefill_bitexact(dec):
    """A shared prefix with a different suffix hits at the block
    boundary: the admission prefills ONLY the uncached suffix, and the
    output is bit-exact vs a solo generate."""
    rng = np.random.default_rng(3)
    pre, p1, p2 = _shared_prefix_mix(rng)       # share 8 = 2 blocks
    solo1 = np.asarray(dec.generate(p1[None], 6))
    solo2 = np.asarray(dec.generate(p2[None], 6))
    eng = ServingEngine(dec, num_slots=2, chunk_size=4, **CACHE_KW)
    a = eng.submit(p1, 6)
    eng.drain()
    b = eng.submit(p2, 6)
    eng.drain()
    np.testing.assert_array_equal(np.asarray(eng.result(a)), solo1)
    np.testing.assert_array_equal(np.asarray(eng.result(b)), solo2)
    rec = eng.result(b).resilience["serving"]
    assert rec["prefix_hit"] == "partial"
    assert rec["prefill_tokens_saved"] == len(pre)   # the 2 shared blocks
    assert rec["admission_dispatches"] == 1          # the suffix prefill
    assert eng.metrics()["prefix_cache"]["engine_hits_partial"] == 1


def test_one_token_prefix_divergence_misses(dec):
    rng = np.random.default_rng(4)
    _, p1, _ = _shared_prefix_mix(rng)
    p_div = p1.copy()
    p_div[1] = (p_div[1] + 1) % 64        # diverge inside block 0
    solo = np.asarray(dec.generate(p_div[None], 6))
    eng = ServingEngine(dec, num_slots=2, chunk_size=4, **CACHE_KW)
    eng.submit(p1, 6)
    eng.drain()
    b = eng.submit(p_div, 6)
    eng.drain()
    rec = eng.result(b).resilience["serving"]
    assert rec["prefix_hit"] == "miss"
    assert rec["prefill_tokens_saved"] == 0
    np.testing.assert_array_equal(np.asarray(eng.result(b)), solo)
    assert eng.metrics()["prefix_cache"]["engine_hits_partial"] == 0


def test_cached_admission_parity_sampled_per_row_keys(dec):
    """Per-row-keyed sampling: cached admission (full AND partial hits)
    draws the identical stream as a cache-less engine of a different
    shape — the hit class cannot touch a request's RNG."""
    rng = np.random.default_rng(5)
    pre, p1, p2 = _shared_prefix_mix(rng)
    reqs = [(p1, 6, 3, 0.8), (p1, 6, 3, 0.8), (p2, 7, 4, 1.1),
            (p1, 5, 9, 0.7)]
    outs = []
    for kw, slots, T in ((CACHE_KW, 2, 3), ({}, 1, 7)):
        eng = ServingEngine(dec, num_slots=slots, chunk_size=T,
                            do_sample=True, top_k=8, **kw)
        ids = []
        for p, n, s, t in reqs:
            ids.append(eng.submit(p, n, seed=s, temperature=t))
            eng.drain()          # serialize so the duplicates can hit
        outs.append([np.asarray(eng.result(r)) for r in ids])
        if kw:
            m = eng.metrics()["prefix_cache"]
            assert m["engine_hits_full"] >= 1
            assert m["engine_hits_partial"] >= 1
    for a, b in zip(*outs):
        np.testing.assert_array_equal(a, b)


def test_engine_pins_inflight_slab_against_eviction(dec):
    """A slab with a request in flight on it survives a byte budget
    that would otherwise evict it; once the request finishes, it
    becomes evictable again."""
    rng = np.random.default_rng(6)
    _, p1, _ = _shared_prefix_mix(rng)
    # a 1-byte budget keeps nothing: every miss-inserted slab evicts on
    # the way in, every admission is a miss, outputs stay bit-exact
    eng = ServingEngine(dec, num_slots=2, chunk_size=2,
                        prefix_cache=True, prefix_cache_bytes=1,
                        prefix_block_tokens=BLOCK)
    a = eng.submit(p1, 8)
    b = eng.submit(p1, 8)
    res = eng.drain()
    solo = np.asarray(dec.generate(p1[None], 8))
    np.testing.assert_array_equal(np.asarray(res[a]), solo)
    np.testing.assert_array_equal(np.asarray(res[b]), solo)
    cache = eng.prefix_cache
    assert cache.stats()["pinned"] == 0
    assert len(cache) == 0
    assert cache.stats()["evictions"] >= 1

    # the deterministic pinning drill: generous budget, then tighten
    # while a full-hit request is in flight on the slab
    eng2 = ServingEngine(dec, num_slots=2, chunk_size=2, **CACHE_KW)
    a = eng2.submit(p1, 8)
    eng2.drain()
    b = eng2.submit(p1, 16)          # full hit: slab pinned in flight
    eng2.step()                      # admitted, not finished
    slot = eng2.scheduler.slots.entries[0]
    assert slot is not None and slot.pinned_slab is not None
    cache2 = eng2.prefix_cache
    cache2.bytes_budget = 1          # tighten under the pinned slab
    cache2._evict_to_budget()
    assert cache2.lookup(p1).kind == "full"    # pinned: NOT evicted
    eng2.drain()                     # finish -> unpin -> evictable
    assert cache2.stats()["pinned"] == 0
    cache2._evict_to_budget()
    assert cache2.lookup(p1).kind == "miss"
    np.testing.assert_array_equal(
        np.asarray(eng2.result(b)),
        np.asarray(dec.generate(p1[None], 16)))


def test_batched_same_bucket_admission(dec):
    """Several same-bucket waiting requests admit with ONE batched
    prefill dispatch; dispatches-saved is recorded; outputs bit-exact."""
    rng = np.random.default_rng(7)
    reqs = [rng.integers(0, 64, (5,)) for _ in range(4)]   # bucket 8
    solo = [np.asarray(dec.generate(p[None], 5)) for p in reqs]
    eng = ServingEngine(dec, num_slots=4, chunk_size=4,
                        batch_admission=True)
    ids = [eng.submit(p, 5, seed=i) for i, p in enumerate(reqs)]
    res = eng.drain()
    for i, rid in enumerate(ids):
        np.testing.assert_array_equal(np.asarray(res[rid]), solo[i])
    m = eng.metrics()
    assert m["prefill_dispatches"] == 1
    assert m["batched_admission_groups"] == 1
    assert m["admission_dispatches_saved"] == 3
    # exactly one group leader charged with the dispatch
    disp = [res[r].resilience["serving"]["admission_dispatches"]
            for r in ids]
    assert sorted(disp) == [0, 0, 0, 1]
    # mixed buckets still group correctly (8-bucket and 16-bucket)
    eng2 = ServingEngine(dec, num_slots=4, chunk_size=4,
                         batch_admission=True)
    mixed = [rng.integers(0, 64, (n,)) for n in (4, 6, 11, 12)]
    solo2 = [np.asarray(dec.generate(p[None], 4)) for p in mixed]
    ids2 = [eng2.submit(p, 4, seed=i) for i, p in enumerate(mixed)]
    res2 = eng2.drain()
    for i, rid in enumerate(ids2):
        np.testing.assert_array_equal(np.asarray(res2[rid]), solo2[i])
    assert eng2.metrics()["prefill_dispatches"] == 2   # one per bucket


def test_batched_admission_with_prefix_cache(dec):
    """Batching composes with the cache: a batched group may mix cold
    rows and suffix rows (per-row pos0), still one dispatch."""
    rng = np.random.default_rng(8)
    pre = rng.integers(0, 64, (8,))
    p1 = np.concatenate([pre, rng.integers(0, 64, (3,))])
    p2 = np.concatenate([pre, rng.integers(0, 64, (4,))])
    p3 = rng.integers(0, 64, (11,))
    solos = [np.asarray(dec.generate(p[None], 5)) for p in (p1, p2, p3)]
    eng = ServingEngine(dec, num_slots=4, chunk_size=4,
                        batch_admission=True, **CACHE_KW)
    a = eng.submit(p1, 5)
    eng.drain()                       # seed the prefix
    prefills0 = eng.prefill_dispatches
    b = eng.submit(p2, 5)             # partial (suffix bucket 8)
    c = eng.submit(p3, 5)             # miss (suffix = all 11 -> 16)
    res = eng.drain()
    for rid, solo in ((a, solos[0]), (b, solos[1]), (c, solos[2])):
        got = res[rid] if rid in res else eng.result(rid)
        np.testing.assert_array_equal(np.asarray(got), solo)
    assert res[b].resilience["serving"]["prefix_hit"] == "partial"
    assert res[c].resilience["serving"]["prefix_hit"] == "miss"
    # different suffix buckets -> two dispatches here (8 and 16)
    assert eng.prefill_dispatches - prefills0 == 2


def test_status_and_flight_carry_prefix_state(dec):
    """/statusz ('prefix_cache' in status()) and the crash flight
    recorder both show the live pool state."""
    rng = np.random.default_rng(9)
    _, p1, _ = _shared_prefix_mix(rng)
    eng = ServingEngine(dec, num_slots=2, chunk_size=4, **CACHE_KW)
    eng.submit(p1, 5)
    eng.drain()
    eng.submit(p1, 5)
    eng.drain()
    st = eng.status()["prefix_cache"]
    assert st["slabs"] == 1 and st["hits_full"] == 1
    assert st["slab_table"] and st["slab_table"][0]["length"] == len(p1)
    assert 0 <= st["occupancy"] <= 1
    # cache-disabled engines keep the schema stable
    eng0 = ServingEngine(dec, num_slots=2, chunk_size=4)
    assert eng0.status()["prefix_cache"] is None
    assert eng0.metrics()["prefix_cache"] is None
    # the flight recorder's postmortem includes the pool state
    import paddle_tpu.obs as obs
    import json as _json
    import tempfile, os
    with tempfile.TemporaryDirectory() as d:
        path = obs.flight_recorder.dump("test.prefix",
                                        path=os.path.join(d, "pm.json"))
        rec = _json.load(open(path))
        assert rec["state"]["serving.prefix_cache"]["slabs"] == 1


# -- AOT bundle serving -----------------------------------------------------

def test_bundle_prefix_cache_serving(dec, tmp_path):
    """The exported bucketed admit entries (with per-row pos0) serve
    full AND partial hits over a bundle — zero model Python."""
    from paddle_tpu.inference import AotPredictor, export_decoder_bundle
    export_decoder_bundle(dec, str(tmp_path), prompt_lens=[8, 16],
                          decode_steps=[8], batch_sizes=[2],
                          chunk_sizes=[4])
    pred = AotPredictor(str(tmp_path))
    assert pred.meta["decode_mode"]["chunked"]["admit_pos0"] is True
    rng = np.random.default_rng(10)
    pre = rng.integers(0, 64, (8,))
    p1 = np.concatenate([pre, rng.integers(0, 64, (3,))])
    p2 = np.concatenate([pre, rng.integers(0, 64, (5,))])
    solo1 = np.asarray(dec.generate(p1[None], 5))
    solo2 = np.asarray(dec.generate(p2[None], 5))
    eng = ServingEngine(pred, num_slots=2, chunk_size=4, **CACHE_KW)
    a = eng.submit(p1, 5)
    eng.drain()
    b = eng.submit(p1, 5)         # full hit
    c = eng.submit(p2, 5)         # partial: suffix via pos0 entry
    res = eng.drain()
    np.testing.assert_array_equal(np.asarray(eng.result(a)), solo1)
    np.testing.assert_array_equal(np.asarray(res[b]), solo1)
    np.testing.assert_array_equal(np.asarray(res[c]), solo2)
    assert res[b].resilience["serving"]["prefix_hit"] == "full"
    assert res[b].resilience["serving"]["admission_dispatches"] == 0
    assert res[c].resilience["serving"]["prefix_hit"] == "partial"


# -- mesh-sharded serving ---------------------------------------------------

def test_mesh_slab_residency_and_parity(dec, shdec):
    """Slabs live under the carry's NamedShardings — extraction, full-
    and partial-hit admission never gather the mesh state to host —
    and cached tokens stay bit-exact vs the unsharded solo path."""
    rng = np.random.default_rng(11)
    pre, p1, p2 = _shared_prefix_mix(rng)
    solo1 = np.asarray(dec.generate(p1[None], 6))
    solo2 = np.asarray(dec.generate(p2[None], 6))
    eng = ServingEngine(shdec, num_slots=4, chunk_size=4, **CACHE_KW)
    a = eng.submit(p1, 6)
    eng.drain()
    slab = eng.prefix_cache._slabs[0]
    assert "tp" in _spec_axes(slab.kc), "slab cache not head-sharded"
    assert _spec_axes(slab.logits) <= {"dp", "tp"}
    b = eng.submit(p1, 6)         # full hit from the sharded slab
    c = eng.submit(p2, 6)         # partial hit
    res = eng.drain()
    np.testing.assert_array_equal(np.asarray(eng.result(a)), solo1)
    np.testing.assert_array_equal(np.asarray(res[b]), solo1)
    np.testing.assert_array_equal(np.asarray(res[c]), solo2)
    m = eng.metrics()["prefix_cache"]
    assert m["engine_hits_full"] == 1 and m["engine_hits_partial"] == 1
    # the carry never left the mesh through cached admissions
    assert "dp" in _spec_axes(eng.state.kc)
    assert "tp" in _spec_axes(eng.state.kc)
    assert eng.prefix_cache.mesh_axes == {"dp": 2, "tp": 2}


def test_shared_cache_mesh_mismatch_refused(dec, shdec):
    from paddle_tpu.inference.sharding import MeshMismatchError
    cache = PrefixCache(bytes_budget=1 << 30, block_tokens=BLOCK)
    ServingEngine(shdec, num_slots=4, chunk_size=4, prefix_cache=cache)
    with pytest.raises(MeshMismatchError, match="mesh"):
        ServingEngine(dec, num_slots=2, chunk_size=4,
                      prefix_cache=cache)
    # same topology: sharing is fine
    eng2 = ServingEngine(shdec, num_slots=4, chunk_size=4,
                         prefix_cache=cache)
    assert eng2.prefix_cache is cache


def test_engine_prefix_cache_argument_validation(dec):
    with pytest.raises(TypeError, match="prefix_cache"):
        ServingEngine(dec, num_slots=2, chunk_size=4, prefix_cache=42)
    with pytest.raises(ValueError, match="block_tokens"):
        ServingEngine(dec, num_slots=2, chunk_size=4, prefix_cache=True,
                      prefix_block_tokens=0)
    # flags/env default: disabled
    eng = ServingEngine(dec, num_slots=2, chunk_size=4)
    assert eng.prefix_cache is None
