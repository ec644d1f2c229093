"""Continuous batching: chunked resumable fused decode + slot-admission
serving engine (Orca-style iteration-level batching).

The load-bearing properties:
- chunked decode chained over N steps is BIT-EXACT with run-to-completion
  for greedy (chunk slicing can't change tokens);
- a request served by the engine is bit-exact vs a solo ``generate`` of
  the same request (admission parity: batch neighbours, slot reuse and
  length-bucketed prefill are invisible);
- sampled rows draw from per-row key streams — output depends only on
  the request's seed, not on engine shape (distribution-preserving);
- dispatch accounting: one admission prefill per request + one dispatch
  per chunk, nothing hidden;
- a chunk dispatch that keeps failing degrades to the per-token rung
  without dropping any in-flight request (``faults`` drill).
"""

import dataclasses

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.inference.generate import LlamaDecoder
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from paddle_tpu.serving import Request, Scheduler, ServingEngine, \
    bucket_length

pytestmark = pytest.mark.serving

CFG = dict(vocab_size=64, hidden_size=32, intermediate_size=64,
           num_hidden_layers=2, num_attention_heads=4,
           num_key_value_heads=2, max_position_embeddings=64)


def _model(seed=0):
    paddle.seed(seed)
    return LlamaForCausalLM(LlamaConfig(**CFG))


@pytest.fixture(scope="module")
def dec():
    return LlamaDecoder(_model(), max_len=64)


def _mixed_requests(rng, n, eos_every=None, dec=None):
    """n requests with mixed prompt lengths and budgets; every
    ``eos_every``-th one gets a reachable eos id (its solo greedy
    mid-stream token)."""
    reqs = []
    for i in range(n):
        p = rng.integers(0, 64, (int(rng.integers(2, 12)),))
        nt = int(rng.integers(2, 12))
        eos = None
        if eos_every and i % eos_every == 0 and nt >= 4:
            ref = np.asarray(dec.generate(p[None], nt))
            eos = int(ref[0, len(p) + nt // 2])
        reqs.append((p, nt, eos))
    return reqs


# -- chunked resumable decode ----------------------------------------------

@pytest.mark.parametrize("T", [1, 3, 8, 16])
def test_chunked_generate_bitexact_greedy(dec, T):
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, 64, (2, 5))
    ref = np.asarray(dec.generate(prompt, max_new_tokens=12))
    out = np.asarray(dec.generate(prompt, max_new_tokens=12, chunk_size=T))
    np.testing.assert_array_equal(out, ref)


def test_chunked_generate_bitexact_greedy_eos(dec):
    rng = np.random.default_rng(1)
    prompt = rng.integers(0, 64, (2, 4))
    eos = int(np.asarray(dec.generate(prompt, 12))[0, 9])
    ref = np.asarray(dec.generate(prompt, 12, eos_token_id=eos))
    out = np.asarray(dec.generate(prompt, 12, eos_token_id=eos,
                                  chunk_size=5))
    np.testing.assert_array_equal(out, ref)


def test_decode_state_resume_matches_run_to_completion(dec):
    """The exported carry re-enters: two chunks (4 + 8) == one 12-token
    generate, bit-exact."""
    rng = np.random.default_rng(2)
    prompt = rng.integers(0, 64, (3, 6))
    ref = np.asarray(dec.generate(prompt, 12))
    st = dec.init_decode_state(prompt)
    t1, st = dec.decode_chunk(st, 4)
    assert st.steps_done == 4
    t2, st = dec.decode_chunk(st, 8)
    got = np.concatenate([prompt, np.asarray(t1), np.asarray(t2)], axis=1)
    np.testing.assert_array_equal(got, ref)


def test_chunked_dispatch_count_and_record(dec):
    rng = np.random.default_rng(3)
    prompt = rng.integers(0, 64, (1, 4))
    d0 = dec.dispatch_count
    res = dec.generate(prompt, max_new_tokens=12, chunk_size=5)
    # one prefill + ceil(12/5) chunk dispatches
    assert dec.dispatch_count - d0 == 1 + 3
    assert res.resilience["level"] == "chunked"
    assert dec.last_spec_stats is None


def test_chunk_size_validation(dec):
    prompt = np.array([[1, 2, 3]])
    with pytest.raises(ValueError, match="chunk_size"):
        dec.generate(prompt, 4, chunk_size=0)
    # chunked + draft_model is a WORKING path now (the chunked
    # speculative program), not a refusal — and stats are reported
    out = dec.generate(prompt, 4, chunk_size=4, draft_model="skip:1",
                       num_speculative_tokens=2)
    ref = dec.generate(prompt, 4, draft_model="skip:1",
                       num_speculative_tokens=2)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))
    assert dec.last_spec_stats["num_speculative_tokens"] == 2


# -- scheduler -------------------------------------------------------------

def test_bucket_length():
    assert bucket_length(1) == 8
    assert bucket_length(8) == 8
    assert bucket_length(9) == 16
    assert bucket_length(100) == 128
    assert bucket_length(5, buckets=[4, 16]) == 16
    with pytest.raises(ValueError, match="exceeds"):
        bucket_length(33, buckets=[16, 32])


def test_scheduler_fifo_and_priority():
    sch = Scheduler(num_slots=1, policy="priority")
    for rid, pr in ((0, 5), (1, 1), (2, 5)):
        sch.push(Request(id=rid, prompt=np.arange(3), max_new_tokens=2,
                         priority=pr))
    order = []
    while len(sch):
        [(slot, req)] = sch.admissions()
        order.append(req.id)
        sch.slots.release(slot)
    assert order == [1, 0, 2]      # lowest priority first, FIFO in class

    sch = Scheduler(num_slots=1, policy="fifo")
    for rid, pr in ((0, 5), (1, 1)):
        sch.push(Request(id=rid, prompt=np.arange(3), max_new_tokens=2,
                         priority=pr))
    [(slot, req)] = sch.admissions()
    assert req.id == 0             # fifo ignores priority


# -- engine ----------------------------------------------------------------

def test_engine_admission_parity_greedy(dec):
    """Each request's tokens bit-exact vs a solo generate — across mixed
    prompt lengths (bucketed prefill), mixed budgets, eos early-stops and
    slot reuse — with the exact dispatch accounting."""
    rng = np.random.default_rng(4)
    reqs = _mixed_requests(rng, 8, eos_every=3, dec=dec)
    solo = [np.asarray(dec.generate(p[None], n, eos_token_id=e))
            for p, n, e in reqs]
    eng = ServingEngine(dec, num_slots=3, chunk_size=4)
    d0 = dec.dispatch_count
    ids = [eng.submit(p, n, eos_token_id=e) for p, n, e in reqs]
    res = eng.drain()
    for i, rid in enumerate(ids):
        np.testing.assert_array_equal(np.asarray(res[rid]), solo[i])
    m = eng.metrics()
    assert m["prefill_dispatches"] == len(reqs)
    assert m["step_dispatches"] == 0
    assert dec.dispatch_count - d0 == \
        m["prefill_dispatches"] + m["chunk_dispatches"]
    rec = res[ids[0]].resilience
    assert rec["level"] == "chunked"
    assert rec["serving"]["queue_delay_s"] >= 0.0
    assert rec["serving"]["chunks"] >= 1


def test_engine_priority_order(dec):
    eng = ServingEngine(dec, num_slots=1, chunk_size=4, policy="priority")
    p = np.arange(4) % 64
    low = eng.submit(p, 3, priority=9)
    high = eng.submit(p + 1, 3, priority=0)
    finished = []
    while len(finished) < 2:
        finished.extend(rid for rid, _ in eng.step())
    assert finished == [high, low]


def test_engine_sampled_fixed_keys_row_independent(dec):
    """Sampled outputs are keyed by the request's seed alone: a 3-slot
    T=3 engine and a 1-slot T=7 engine produce IDENTICAL tokens for the
    same submissions — batch neighbours, slot assignment and chunk
    slicing cannot shift any row's stream."""
    rng = np.random.default_rng(5)
    reqs = [(rng.integers(0, 64, (int(rng.integers(2, 8)),)),
             int(rng.integers(3, 9)), i, 0.7 + 0.2 * (i % 3))
            for i in range(6)]
    outs = []
    for slots, T in ((3, 3), (1, 7)):
        eng = ServingEngine(dec, num_slots=slots, chunk_size=T,
                            do_sample=True, top_k=8)
        ids = [eng.submit(p, n, seed=s, temperature=t)
               for p, n, s, t in reqs]
        res = eng.drain()
        outs.append([np.asarray(res[r]) for r in ids])
    for a, b in zip(*outs):
        np.testing.assert_array_equal(a, b)
    # and generate(chunk_size=) at B=1 is the same stream
    p, n, s, t = reqs[0]
    g = np.asarray(dec.generate(p[None], n, do_sample=True, top_k=8,
                                seed=s, temperature=t, chunk_size=4))
    np.testing.assert_array_equal(g, outs[0][0])


def _tiny_decoder(kind):
    """Tiny decoders of the three kinds the benchmark's configurations
    are: GQA (head-major cache), MHA, and a looped model whose cache has
    layers x passes buffers."""
    paddle.seed(3)
    if kind == "ouro":
        from paddle_tpu.models.ouro import OURO_TINY, OuroForCausalLM
        return LlamaDecoder(OuroForCausalLM(OURO_TINY), max_len=64)
    kv = 2 if kind == "gqa" else 4
    return LlamaDecoder(LlamaForCausalLM(LlamaConfig(
        **{**CFG, "num_key_value_heads": kv})), max_len=64)


@pytest.mark.parametrize("kind", ["gqa", "mha", "ouro"])
def test_one_chunk_program_serves_plain_carry_and_engine(kind):
    """The decoder has ONE chunk program: ``decode_chunk`` on a plain
    carry (no ring operand) and the engine's dispatch with an empty ring
    run it to the same tokens and the same carry, leaf for leaf; the
    per-token rung is the same jitted object under its own fault site."""
    import jax
    d = _tiny_decoder(kind)
    assert not hasattr(d, "_chunk_decode")
    assert not hasattr(d, "_chunk_step")
    assert d._ring_chunk_decode._jitted is d._ring_chunk_step._jitted
    ids = np.random.default_rng(21).integers(0, d.cfg.vocab_size, (3, 6))
    # each dispatch consumes the state it is given: one state a branch
    st0 = lambda: d.init_decode_state(  # noqa: E731
        ids, eos_token_id=5, temperature=0.7, seed=4)
    toks_d, st_d = d.decode_chunk(st0(), 4, do_sample=True, top_k=8)
    eng = ServingEngine(d, num_slots=3, chunk_size=4, do_sample=True,
                        top_k=8)
    ring, staged = eng._ring_args()
    assert staged == 0 and (ring[0] == -1).all()
    toks_e, st_e = eng._b.decode(st0(), 4, ring)
    np.testing.assert_array_equal(np.asarray(toks_e), np.asarray(toks_d))
    def flat(st):
        return jax.tree_util.tree_flatten(
            {f.name: getattr(st, f.name) for f in dataclasses.fields(st)})
    leaves_d, tree_d = flat(st_d)
    leaves_e, tree_e = flat(st_e)
    assert tree_d == tree_e
    assert len(leaves_d) >= 6 + 2 * d.cfg.num_cache_layers
    for a, b in zip(leaves_d, leaves_e):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # and the rung's site dispatches the same program one step at a time
    toks_1, st_1 = eng._b.decode(st0(), 1, ring, rung="step")
    np.testing.assert_array_equal(np.asarray(toks_1)[:, 0],
                                  np.asarray(toks_d)[:, 0])
    assert int(st_1.steps_done) == 1 and int(st_e.steps_done) == 4


@pytest.mark.parametrize("request_keyed", [False, True])
def test_row_key_rule_has_one_site(dec, request_keyed):
    """``ServingEngine._row_key`` is the admitted row's key on the ring
    path and on the host-scatter path alike: the seed alone (the rule of
    ``generate(chunk_size=)`` at B=1), or the request-keyed stream."""
    import jax.random as jrandom

    from paddle_tpu.serving.engine import derive_row_key
    eng = ServingEngine(dec, num_slots=2, chunk_size=4,
                        request_keyed_rng=request_keyed)
    req = Request(id=7, prompt=np.arange(4), max_new_tokens=4, seed=123,
                  rng_request_id=41, rng_tokens_emitted=3)
    want = (derive_row_key(123, 41, 3) if request_keyed
            else jrandom.split(jrandom.PRNGKey(123), 1)[0])
    np.testing.assert_array_equal(np.asarray(eng._row_key(req)),
                                  np.asarray(want))
    req.rng_request_id = None      # falls back to the engine's own id
    if request_keyed:
        np.testing.assert_array_equal(
            np.asarray(eng._row_key(req)),
            np.asarray(derive_row_key(123, 7, 3)))


def test_host_scatter_and_ring_engines_draw_the_same_samples(dec):
    """One seed, one stream: an engine that admits by host scatter (the
    prefix cache is on) and a ring engine sample the same tokens."""
    rng = np.random.default_rng(17)
    reqs = [(rng.integers(0, 64, (int(rng.integers(3, 10)),)),
             int(rng.integers(4, 10)), 100 + i) for i in range(5)]
    outs = []
    for kw in (dict(), dict(prefix_cache=True,
                            prefix_cache_bytes=1 << 30)):
        eng = ServingEngine(dec, num_slots=2, chunk_size=3,
                            do_sample=True, top_k=12, **kw)
        ids = [eng.submit(p, n, temperature=0.9, seed=sd)
               for p, n, sd in reqs]
        res = eng.drain()
        outs.append([np.asarray(res[r]) for r in ids])
        scattered = int(eng._c_host_scattered.value)
        assert (scattered == len(reqs)) if kw else (scattered == 0)
    for a, b in zip(*outs):
        np.testing.assert_array_equal(a, b)


def test_engine_speculative_parity_stats_and_accounting(dec):
    """Tentpole: the engine over the chunked speculative program is
    bit-exact vs the PLAIN engine on the same submissions, with the
    speculative dispatch accounting (prefill + draft prefill per
    request + chunk dispatches, zero per-token steps, zero host
    scatters) and CUMULATIVE per-request acceptance stats on the
    result record — never stale, never last-chunk-only."""
    rng = np.random.default_rng(11)
    reqs = _mixed_requests(rng, 6, eos_every=3, dec=dec)
    outs, engines = [], []
    for kw in (dict(), dict(draft_model="skip:1",
                            num_speculative_tokens=2)):
        eng = ServingEngine(dec, num_slots=3, chunk_size=4, **kw)
        d0 = dec.dispatch_count
        ids = [eng.submit(p, n, eos_token_id=e) for p, n, e in reqs]
        res = eng.drain()
        m = eng.metrics()
        assert m["step_dispatches"] == 0
        assert m["admission_ring"]["host_scattered"] == 0
        assert m["admission_ring"]["staged"] == len(reqs)
        assert m["admission_ring"]["scattered"] == len(reqs)
        assert dec.dispatch_count - d0 == \
            m["prefill_dispatches"] + m["draft_prefill_dispatches"] \
            + m["chunk_dispatches"]
        outs.append([np.asarray(res[r]) for r in ids])
        engines.append((eng, res, ids))
    for a, b in zip(*outs):
        np.testing.assert_array_equal(a, b)
    plain_m = engines[0][0].metrics()
    eng, res, ids = engines[1]
    m = eng.metrics()
    assert plain_m["speculative"] is None
    assert m["draft_prefill_dispatches"] == len(reqs)
    sp = m["speculative"]
    assert sp["active"] and sp["num_speculative_tokens"] == 2
    assert sp["rounds"] > 0
    assert sp["acceptance_len_mean"] == pytest.approx(
        sp["accepted_drafts"] / sp["rounds"])
    st = eng.status()["speculative"]
    assert st["rounds"] == sp["rounds"]
    # per-request record: cumulative totals, consistent mean
    tot_rounds = 0
    for rid in ids:
        rec = res[rid].resilience["serving"]["speculative"]
        assert rec["num_speculative_tokens"] == 2
        assert rec["rounds"] > 0
        assert rec["acceptance_len_mean"] == pytest.approx(
            rec["accepted_drafts"] / rec["rounds"])
        assert rec["overflow_tokens"] >= 0
        tot_rounds += rec["rounds"]
    assert tot_rounds == sp["rounds"]
    plain_rec = engines[0][1][engines[0][2][0]].resilience["serving"]
    assert plain_rec["speculative"] is None


def test_engine_speculative_sampled_shape_invariance(dec):
    """Sampled speculative serving draws from per-row key streams: a
    3-slot T=3 engine and a 1-slot T=7 engine emit IDENTICAL tokens
    for the same seeded submissions."""
    rng = np.random.default_rng(12)
    reqs = [(rng.integers(0, 64, (int(rng.integers(2, 8)),)),
             int(rng.integers(3, 9)), i, 0.7 + 0.2 * (i % 3))
            for i in range(5)]
    outs = []
    for slots, T in ((3, 3), (1, 7)):
        eng = ServingEngine(dec, num_slots=slots, chunk_size=T,
                            do_sample=True, top_k=8,
                            draft_model="skip:1",
                            num_speculative_tokens=2)
        ids = [eng.submit(p, n, seed=s, temperature=t)
               for p, n, s, t in reqs]
        res = eng.drain()
        outs.append([np.asarray(res[r]) for r in ids])
    for a, b in zip(*outs):
        np.testing.assert_array_equal(a, b)


def test_engine_admission_ring_full_backpressure(dec):
    """A ring smaller than the slot count: when a step frees more slots
    than the ring holds, the spill is re-queued (FIFO order kept, not
    dropped, not host-scattered) and the ``ring_full`` counter says so.
    Parity is unaffected."""
    rng = np.random.default_rng(13)
    reqs = [(rng.integers(0, 64, (4,)), 4 + i % 3) for i in range(8)]
    solo = [np.asarray(dec.generate(p[None], n)) for p, n in reqs]
    eng = ServingEngine(dec, num_slots=4, chunk_size=4, ring_slots=2)
    ids = [eng.submit(p, n) for p, n in reqs]
    res = eng.drain()
    for i, rid in enumerate(ids):
        np.testing.assert_array_equal(np.asarray(res[rid]), solo[i])
    ring = eng.metrics()["admission_ring"]
    assert ring["slots"] == 2
    assert ring["full"] > 0                  # backpressure actually hit
    assert ring["host_scattered"] == 0
    assert ring["staged"] == ring["scattered"] == len(reqs)


def test_engine_occupancy_accounting(dec):
    eng = ServingEngine(dec, num_slots=4, chunk_size=4)
    p = np.arange(5) % 64
    eng.submit(p, 8)
    eng.drain()
    m = eng.metrics()
    assert m["occupancy_samples"] == 2          # ceil(8/4) chunks
    assert m["occupancy_mean"] == pytest.approx(0.25)   # 1 of 4 slots
    assert m["slot_steps_total"] == 2 * 4 * 4   # ALL rows ride each chunk
    assert m["requests_completed"] == 1
    assert m["queue_delay_mean_s"] >= 0.0

    eng2 = ServingEngine(dec, num_slots=2, chunk_size=4)
    for i in range(2):
        eng2.submit(p, 4, seed=i)
    eng2.drain()
    assert eng2.metrics()["occupancy_mean"] == pytest.approx(1.0)


def test_engine_submit_validation(dec):
    eng = ServingEngine(dec, num_slots=2, chunk_size=4)
    with pytest.raises(ValueError, match="max_len"):
        eng.submit(np.arange(8), 100)           # 8 + 100 > 64
    with pytest.raises(ValueError, match="ONE request"):
        eng.submit(np.zeros((2, 4), np.int32), 4)
    with pytest.raises(ValueError, match="max_new_tokens"):
        eng.submit(np.arange(4), 0)


# -- AOT bundle serving ----------------------------------------------------

def test_bundle_chunked_serving_parity(dec, tmp_path):
    """The same scheduler over exported StableHLO entries
    (decode_mode.chunked): greedy parity vs the in-process decoder."""
    from paddle_tpu.inference import AotPredictor, export_decoder_bundle
    export_decoder_bundle(dec, str(tmp_path), prompt_lens=[8],
                          decode_steps=[8], batch_sizes=[2],
                          chunk_sizes=[4])
    pred = AotPredictor(str(tmp_path))
    mode = pred.meta["decode_mode"]["chunked"]
    assert mode["chunk_sizes"] == [1, 4]        # T=1 rung always exported
    assert {b["chunk"] for b in pred.meta["chunk_buckets"]} == {1, 4}
    assert pred.meta["admit_prefill_buckets"] == [
        {"file": "admit_prefill_s8.aot", "batch": 1, "seq": 8}]

    rng = np.random.default_rng(6)
    reqs = [(rng.integers(0, 64, (int(rng.integers(2, 9)),)),
             int(rng.integers(3, 9))) for _ in range(5)]
    solo = [np.asarray(dec.generate(p[None], n)) for p, n in reqs]
    eng = ServingEngine(pred, num_slots=2, chunk_size=4)
    # prompt buckets come from the bundle's exported admit entries
    assert eng.scheduler.prompt_buckets == [8]
    ids = [eng.submit(p, n) for p, n in reqs]
    res = eng.drain()
    for i, rid in enumerate(ids):
        np.testing.assert_array_equal(np.asarray(res[rid]), solo[i])
    assert eng.metrics()["prefill_dispatches"] == len(reqs)
    # a bundle has no ring entries: every admission was a host scatter
    assert eng.metrics()["admission_ring"] is None
    assert int(eng._c_host_scattered.value) == len(reqs)
    with pytest.raises(ValueError, match="admission ring"):
        eng._b.decode(eng.state, 4, ring=())
    # the entries' signatures are the exported contract (a bundle written
    # before the decoder had one chunk program still loads): carry in,
    # tokens + carry out, each cache one argument
    import jax

    from paddle_tpu.inference.aot import _split
    for fname, n_in, n_out in (("decode_chunk_b2_t4.aot", 8, 7),
                               ("decode_chunk_b2_t1.aot", 8, 7),
                               ("admit_prefill_s8.aot", 5, 3)):
        path = str(tmp_path / fname)
        with open(path, "rb") as f:
            exp = jax.export.deserialize(
                bytearray(_split(f.read(), path)[1]))
        args = jax.tree_util.treedef_children(exp.in_tree)[0]
        assert len(args.children()) == n_in, fname
        assert len(exp.out_tree.children()) == n_out, fname


def test_bundle_without_chunked_entries_refuses(dec, tmp_path):
    from paddle_tpu.inference import AotPredictor, export_decoder_bundle
    export_decoder_bundle(dec, str(tmp_path), prompt_lens=[8],
                          decode_steps=[8], batch_sizes=[2])
    with pytest.raises(ValueError, match="chunk_sizes"):
        ServingEngine(AotPredictor(str(tmp_path)), num_slots=2,
                      chunk_size=4)


# -- resilience ------------------------------------------------------------

@pytest.mark.faults
def test_chunk_failure_degrades_without_dropping_requests(dec):
    """The drill of the ISSUE: a plan kills every 'decode.chunk' dispatch
    mid-serve; the engine steps down to the per-token rung on the SAME
    carry — every in-flight request completes, greedy outputs stay
    bit-exact, and the degradation is on each affected record."""
    from paddle_tpu.flags import set_flags
    from paddle_tpu.runtime.resilience import fault_injector

    rng = np.random.default_rng(7)
    reqs = [(rng.integers(0, 64, (int(rng.integers(2, 8)),)),
             int(rng.integers(3, 9))) for _ in range(5)]
    solo = [np.asarray(dec.generate(p[None], n)) for p, n in reqs]
    set_flags({"resilience_backoff_s": 0.0})
    fault_injector.configure([{"kind": "dispatch_error",
                               "site": "decode.chunk",
                               "call": 2, "times": 1000}])
    try:
        eng = ServingEngine(dec, num_slots=2, chunk_size=4)
        ids = [eng.submit(p, n) for p, n in reqs]
        res = eng.drain()
        for i, rid in enumerate(ids):
            np.testing.assert_array_equal(np.asarray(res[rid]), solo[i])
        m = eng.metrics()
        assert m["degradations"] >= 1
        assert m["step_dispatches"] >= eng.chunk_size
        rec = res[ids[-1]].resilience
        assert rec["level"] == "per_token"
        assert rec["degradations"]
    finally:
        fault_injector.clear()
        set_flags({"resilience_backoff_s": 0.5})


@pytest.mark.faults
def test_chunked_generate_resilience_across_dispatches(dec):
    """GenerateResult.resilience spans EVERY chunk dispatch of one
    generate: a transient absorbed on chunk 2 of 3 lands in the one
    record; a permanently failing chunk rung degrades to fused with no
    stale state (bit-exact output) and no stale spec stats."""
    from paddle_tpu.flags import set_flags
    from paddle_tpu.runtime.resilience import fault_injector

    rng = np.random.default_rng(8)
    prompt = rng.integers(0, 64, (1, 4))
    ref = np.asarray(dec.generate(prompt, 9))
    # seed stale speculative stats from a previous generate
    dec.generate(prompt, 6, draft_model="skip:1")
    assert dec.last_spec_stats is not None
    set_flags({"resilience_backoff_s": 0.0})
    try:
        fault_injector.configure([{"kind": "dispatch_error",
                                   "site": "decode.chunk", "call": 2}])
        res = dec.generate(prompt, 9, chunk_size=3)
        np.testing.assert_array_equal(np.asarray(res), ref)
        assert res.resilience["level"] == "chunked"
        assert res.resilience["retries"] == 1       # absorbed mid-request
        assert dec.last_spec_stats is None          # stale stats cleared

        fault_injector.configure([{"kind": "dispatch_error",
                                   "site": "decode.chunk",
                                   "call": 2, "times": 1000}])
        res = dec.generate(prompt, 9, chunk_size=3)
        np.testing.assert_array_equal(np.asarray(res), ref)
        assert res.resilience["level"] == "fused"   # rung changed...
        assert res.resilience["degradations"]       # ...mid-request
        assert dec.last_spec_stats is None
    finally:
        fault_injector.clear()
        set_flags({"resilience_backoff_s": 0.5})


# -- the slow sweep --------------------------------------------------------

@pytest.mark.slow
def test_chunk_size_sweep(dec):
    """Chunk-size sweep: greedy and greedy+eos parity for every T, and
    engine parity at several (slots, T) shapes."""
    rng = np.random.default_rng(9)
    prompt = rng.integers(0, 64, (3, 7))
    ref = np.asarray(dec.generate(prompt, 20))
    eos = int(ref[1, 12])
    ref_eos = np.asarray(dec.generate(prompt, 20, eos_token_id=eos))
    for T in (1, 2, 3, 5, 7, 16, 20, 32):
        np.testing.assert_array_equal(
            np.asarray(dec.generate(prompt, 20, chunk_size=T)), ref)
        np.testing.assert_array_equal(
            np.asarray(dec.generate(prompt, 20, eos_token_id=eos,
                                    chunk_size=T)), ref_eos)
    reqs = _mixed_requests(rng, 10, eos_every=4, dec=dec)
    solo = [np.asarray(dec.generate(p[None], n, eos_token_id=e))
            for p, n, e in reqs]
    for slots, T in ((1, 5), (2, 3), (4, 8), (5, 2)):
        eng = ServingEngine(dec, num_slots=slots, chunk_size=T)
        ids = [eng.submit(p, n, eos_token_id=e) for p, n, e in reqs]
        res = eng.drain()
        for i, rid in enumerate(ids):
            np.testing.assert_array_equal(np.asarray(res[rid]), solo[i],
                                          err_msg=f"slots={slots} T={T}")


# -- mesh-sharded serving (GSPMD tensor parallelism) ------------------------
#
# The conftest's 8-virtual-device CPU platform hosts a 2x2 {dp,tp} mesh:
# tp divides the test config's 2 KV heads (head-axis-sharded caches) and
# dp divides the 4-slot batch (the slot table maps onto dp replicas).
# Parity is token-level bit-exactness vs the single-device path.

def _mesh(shape=(2, 2)):
    from paddle_tpu.parallel import ProcessMesh
    return ProcessMesh(shape=shape, dim_names=("dp", "tp"))


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


@pytest.fixture(scope="module")
def shdec():
    """A 2x2 {dp,tp}-sharded decoder over the SAME weights as the
    module's unsharded ``dec`` fixture (same paddle.seed)."""
    return LlamaDecoder(_model(), max_len=64, mesh=_mesh((2, 2)))


def test_sharded_engine_parity_and_carry_stays_sharded(dec, shdec):
    """The serving tentpole: requests served over the sharded carry are
    bit-exact vs solo unsharded generates, the DecodeState stays sharded
    through admission row-scatters, chunk re-entries and retirement
    (asserted via .sharding), and the dispatch accounting is unchanged."""
    rng = np.random.default_rng(40)
    reqs = _mixed_requests(rng, 8, eos_every=3, dec=dec)
    solo = [np.asarray(dec.generate(p[None], n, eos_token_id=e))
            for p, n, e in reqs]
    eng = ServingEngine(shdec, num_slots=4, chunk_size=4)
    assert _spec_axes(eng.state.kc) == {"dp", "tp"}
    ids = [eng.submit(p, n, eos_token_id=e) for p, n, e in reqs]
    seen_specs = set()
    finished = {}
    while len(finished) < len(reqs):
        for rid, res in eng.step():
            finished[rid] = res
        # between EVERY step the carry is still on the mesh: admission
        # scatters and harvests never gathered it
        seen_specs.add(str([b.sharding.spec for b in eng.state.kc]))
        assert "dp" in _spec_axes(eng.state.kc)
        assert _spec_axes(eng.state.pos) == {"dp"}
    assert len(seen_specs) == 1, f"carry placement drifted: {seen_specs}"
    for i, rid in enumerate(ids):
        np.testing.assert_array_equal(np.asarray(finished[rid]), solo[i])
    m = eng.metrics()
    assert m["prefill_dispatches"] == len(reqs)
    assert m["step_dispatches"] == 0


def test_sharded_engine_status_reports_mesh(shdec):
    eng = ServingEngine(shdec, num_slots=4, chunk_size=4)
    st = eng.status()
    mesh = st["mesh"]
    assert mesh["axes"] == {"dp": 2, "tp": 2}
    assert mesh["size"] == 4
    assert mesh["device_kind"]
    cs = mesh["carry_sharding"]
    assert "dp" in cs["kv_cache"] and "tp" in cs["kv_cache"]
    assert "dp" in cs["pos"]
    # the slot table maps onto the dp axis: 2 replicas x 2 slots
    assert [g["slots"] for g in mesh["dp_slot_groups"]] == [[0, 1], [2, 3]]
    # unsharded engines report mesh: null (statusz schema stays stable)
    from paddle_tpu.inference.generate import LlamaDecoder as _LD
    eng2 = ServingEngine(_LD(_model(), max_len=32), num_slots=2,
                         chunk_size=4)
    assert eng2.status()["mesh"] is None


def test_sharded_engine_sampled_matches_unsharded_engine(dec, shdec):
    """Sampled serving: per-row key streams make the tokens a function
    of the request seed alone — the sharded engine and an unsharded
    engine of a DIFFERENT shape draw identical tokens."""
    rng = np.random.default_rng(41)
    reqs = [(rng.integers(0, 64, (int(rng.integers(2, 8)),)),
             int(rng.integers(3, 9)), i, 0.7 + 0.2 * (i % 3))
            for i in range(6)]
    outs = []
    for backend, slots, T in ((dec, 3, 3), (shdec, 4, 5)):
        eng = ServingEngine(backend, num_slots=slots, chunk_size=T,
                            do_sample=True, top_k=8)
        ids = [eng.submit(p, n, seed=s, temperature=t)
               for p, n, s, t in reqs]
        res = eng.drain()
        outs.append([np.asarray(res[r]) for r in ids])
    for a, b in zip(*outs):
        np.testing.assert_array_equal(a, b)


def test_engine_mesh_argument_mismatch_refusals(dec, shdec):
    from paddle_tpu.inference.sharding import MeshMismatchError
    # engine mesh vs unsharded decoder: typed refusal
    with pytest.raises(MeshMismatchError, match="without"):
        ServingEngine(dec, num_slots=2, chunk_size=4, mesh=_mesh((2, 2)))
    # engine mesh vs a different decoder topology: typed refusal
    with pytest.raises(MeshMismatchError, match="match"):
        ServingEngine(shdec, num_slots=4, chunk_size=4,
                      mesh=_mesh((1, 2)))
    # matching mesh: accepted
    eng = ServingEngine(shdec, num_slots=4, chunk_size=4,
                        mesh=_mesh((2, 2)))
    assert eng.status()["mesh"]["axes"] == {"dp": 2, "tp": 2}


def test_sharded_bundle_records_mesh_and_refuses_mismatch(dec, shdec,
                                                          tmp_path):
    """export_decoder_bundle from a mesh-built decoder records the
    topology + partition rules in decode_mode.mesh; the engine serves it
    bit-exactly over the sharded StableHLO entries; mismatched meshes
    and impossible device counts refuse TYPED at load."""
    import json as _json

    from paddle_tpu.inference import AotPredictor, export_decoder_bundle
    from paddle_tpu.inference.sharding import MeshMismatchError
    export_decoder_bundle(shdec, str(tmp_path), prompt_lens=[8],
                          decode_steps=[8], batch_sizes=[2],
                          chunk_sizes=[4])
    pred = AotPredictor(str(tmp_path))
    rec = pred.meta["decode_mode"]["mesh"]
    assert rec["axes"] == {"dp": 2, "tp": 2}
    assert rec["partition_rules"]

    rng = np.random.default_rng(42)
    reqs = [(rng.integers(0, 64, (int(rng.integers(2, 9)),)),
             int(rng.integers(3, 9))) for _ in range(4)]
    solo = [np.asarray(dec.generate(p[None], n)) for p, n in reqs]
    eng = ServingEngine(pred, num_slots=2, chunk_size=4,
                        mesh=_mesh((2, 2)))
    ids = [eng.submit(p, n) for p, n in reqs]
    res = eng.drain()
    for i, rid in enumerate(ids):
        np.testing.assert_array_equal(np.asarray(res[rid]), solo[i])
    assert "tp" in _spec_axes(eng.state.kc)

    # a different mesh against the recorded topology: typed refusal
    with pytest.raises(MeshMismatchError, match="match"):
        ServingEngine(pred, num_slots=2, chunk_size=4, mesh=_mesh((1, 2)))
    # an engine mesh against an UNsharded bundle: typed refusal
    udir = tmp_path / "unsharded"
    export_decoder_bundle(dec, str(udir), prompt_lens=[8],
                          decode_steps=[8], batch_sizes=[2],
                          chunk_sizes=[4])
    with pytest.raises(MeshMismatchError, match="without"):
        ServingEngine(AotPredictor(str(udir)), num_slots=2, chunk_size=4,
                      mesh=_mesh((2, 2)))
    # a recorded topology this process cannot host: refused AT LOAD
    meta_path = tmp_path / "bundle.json"
    meta = _json.loads(meta_path.read_text())
    meta["decode_mode"]["mesh"]["axes"] = {"dp": 4, "tp": 4}
    meta_path.write_text(_json.dumps(meta))
    with pytest.raises(MeshMismatchError, match="devices"):
        AotPredictor(str(tmp_path))


@pytest.mark.faults
def test_sharded_chunk_failure_degrades_on_sharded_carry(dec, shdec):
    """The sharded rung drill: a plan kills every 'decode.chunk'
    dispatch mid-serve; the engine steps down to the per-token rung on
    the SAME SHARDED carry — no gather-to-host, no dropped in-flight
    request, greedy outputs bit-exact vs unsharded solo generates, and
    the carry is still on the mesh afterwards."""
    from paddle_tpu.flags import set_flags
    from paddle_tpu.runtime.resilience import fault_injector

    rng = np.random.default_rng(43)
    reqs = [(rng.integers(0, 64, (int(rng.integers(2, 8)),)),
             int(rng.integers(3, 9))) for _ in range(5)]
    solo = [np.asarray(dec.generate(p[None], n)) for p, n in reqs]
    set_flags({"resilience_backoff_s": 0.0})
    fault_injector.configure([{"kind": "dispatch_error",
                               "site": "decode.chunk",
                               "call": 2, "times": 1000}])
    try:
        eng = ServingEngine(shdec, num_slots=2, chunk_size=4)
        ids = [eng.submit(p, n) for p, n in reqs]
        res = eng.drain()
        for i, rid in enumerate(ids):
            np.testing.assert_array_equal(np.asarray(res[rid]), solo[i])
        m = eng.metrics()
        assert m["degradations"] >= 1
        assert m["step_dispatches"] >= eng.chunk_size
        assert res[ids[-1]].resilience["level"] == "per_token"
        # the rung ran on the mesh: the carry never left it
        assert "dp" in _spec_axes(eng.state.kc)
        assert "tp" in _spec_axes(eng.state.kc)
    finally:
        fault_injector.clear()
        set_flags({"resilience_backoff_s": 0.5})
