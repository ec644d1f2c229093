"""The chunk program is given its KV carry and the ring prefill is given
the admission ring (buffer donation): the serving step neither allocates
nor copies a whole cache it is about to overwrite.

What is held here:
- the compiled programs alias every carry cache buffer / every ring
  buffer to an output, for the three kinds of configuration the
  benchmark runs (GQA, MHA, a looped model) and on a mesh (``dp:2,tp:2``
  and the ``tp:4`` of the four-chip section): the outputs are pinned
  where the inputs were, so aliasing needs no resharding copy;
- the chunk only reads the ring: admissions staged before and after
  chunks decode the tokens a host-scatter engine decodes;
- tokens stay bit-exact with run-to-completion through the engine and
  through chained ``decode_chunk`` calls, and across snapshot/restore
  and row migration;
- the ladder: an injected fault leaves the carry alive and degrades to
  the per-token rung; a dispatch that failed AFTER it had taken the
  carry surfaces as ``DecodeFailedError``; a failed ring prefill leaves
  the engine a live ring;
- a caller that reuses a consumed state is told so.
"""

import jax
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.inference.generate import ConsumedStateError, LlamaDecoder
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from paddle_tpu.serving import ServingEngine

pytestmark = pytest.mark.serving

CFG = dict(vocab_size=64, hidden_size=32, intermediate_size=64,
           num_hidden_layers=2, num_attention_heads=4,
           num_key_value_heads=2, max_position_embeddings=64)
KINDS = ["gqa", "mha", "ouro", "gqa-mesh"]
COMPILED = KINDS + ["mha-tp4"]      # the four-chip section's mesh


def _decoder(kind):
    paddle.seed(3)
    if kind == "ouro":
        from paddle_tpu.models.ouro import OURO_TINY, OuroForCausalLM
        return LlamaDecoder(OuroForCausalLM(OURO_TINY), max_len=64)
    kv = 4 if kind.startswith("mha") else 2
    mesh = {"gqa-mesh": "dp:2,tp:2", "mha-tp4": "tp:4"}.get(kind)
    return LlamaDecoder(
        LlamaForCausalLM(LlamaConfig(**{**CFG, "num_key_value_heads": kv})),
        max_len=64, mesh=mesh)


@pytest.fixture(scope="module")
def decs():
    """One decoder a kind for the module (a test that swaps an entry of
    its decoder builds its own)."""
    made = {}

    def get(kind):
        if kind not in made:
            made[kind] = _decoder(kind)
        return made[kind]
    return get


def _device_bytes(tree):
    """Bytes one device holds of ``tree`` (a shard's, on a mesh)."""
    return sum(x.addressable_shards[0].data.nbytes
               for x in jax.tree_util.tree_leaves(tree))


def _aliased(jitted, args, kwargs=None):
    """(parameters the compiled program aliases to an output — the
    entries of its ``input_output_alias`` — and the bytes of them on
    one device)."""
    compiled = jitted.lower(*args, **(kwargs or {})).compile()
    head = compiled.as_text().split("\n", 1)[0]
    assert "input_output_alias=" in head, head[:200]
    return (head.count("-alias)"),
            compiled.memory_analysis().alias_size_in_bytes)


def _requests(n, seed=0, budget=(5, 12)):
    rng = np.random.default_rng(seed)
    return [(rng.integers(0, 64, (int(rng.integers(2, 10)),)),
             int(rng.integers(*budget))) for _ in range(n)]


# -- (a) the compiled forms

@pytest.mark.parametrize("kind", COMPILED)
def test_chunk_program_aliases_every_carry_cache_buffer(decs, kind):
    """Every ``kc``/``vc`` buffer of the carry comes back as the output
    it went in as — no entry copy, no second cache — and nothing else
    does: the ring is read, the small per-row fields are not donated."""
    d = decs(kind)
    eng = ServingEngine(d, num_slots=4, chunk_size=4)
    st, b = eng.state, eng._b
    assert len(st.kc) == d.cfg.num_cache_layers
    ring, _ = eng._ring_args()
    slot, pos, keys, eos, temp, aidx, _ = b._ring_dev(ring)
    args = (d.params, st.logits, st.kc, st.vc, st.pos, st.keys, st.done,
            st.eos, st.temp, st.adapter_idx, b._ring_logits, b._ring_kc,
            b._ring_vc, slot, pos, keys, eos, temp, aidx)
    n, nbytes = _aliased(d._ring_chunk_decode._jitted, args,
                         dict(steps=4, **b._kw))
    assert n == 2 * d.cfg.num_cache_layers
    assert nbytes == _device_bytes((st.kc, st.vc))
    # the per-token rung is the same object, so the same aliasing
    assert d._ring_chunk_step._jitted is d._ring_chunk_decode._jitted
    # and lowering took nothing: the engine's carry is still alive
    assert not st.consumed and not b.ring_consumed()


@pytest.mark.parametrize("kind", COMPILED)
def test_ring_prefill_aliases_every_ring_buffer(decs, kind):
    """The ring's logits and every ring cache buffer are written in
    place; the empty pair the rows prefill from is not donated."""
    import jax.numpy as jnp
    d = decs(kind)
    eng = ServingEngine(d, num_slots=4, chunk_size=4)
    b = eng._b
    kc1, vc1 = b._prefill_cache(1)
    one = lambda v: jnp.asarray([v], jnp.int32)  # noqa: E731
    args = (d.params, jnp.zeros((1, 8), jnp.int32), kc1, vc1, one(5),
            one(0), b._ring_logits, b._ring_kc, b._ring_vc, one(0), None)
    n, nbytes = _aliased(d._ring_admit_prefill._jitted, args)
    assert n == 1 + 2 * d.cfg.num_cache_layers
    assert nbytes == _device_bytes((b._ring_logits, b._ring_kc,
                                    b._ring_vc))
    where = lambda x: x.addressable_shards[0].data \
        .unsafe_buffer_pointer()  # noqa: E731
    before = [where(x) for x in b._ring_kc + b._ring_vc]
    b.ring_admit(np.zeros((1, 8), np.int32), [5], [0], [0])
    assert not b.ring_consumed()            # rebound from the result,
    assert [where(x) for x in b._ring_kc + b._ring_vc] == before  # in place
    assert not jax.tree_util.tree_leaves(kc1)[0].is_deleted()
    assert b._prefill_cache(1)[0] is kc1    # still the shared pair


def test_chunk_span_reports_the_carry_as_aliased(decs):
    """How an operator sees that donation engages: under obs the
    ``decode.chunk`` dispatch's cost record — taken after the dispatch,
    from arguments that are consumed by then — holds the carry's bytes
    as ``alias_bytes``, and ``peak_bytes`` does not count them again."""
    import paddle_tpu.obs as obs
    from paddle_tpu.flags import set_flags
    d = decs("mha")
    set_flags({"obs_enabled": True})
    try:
        eng = ServingEngine(d, num_slots=4, chunk_size=4)
        carry = _device_bytes((eng.state.kc, eng.state.vc))
        eng.submit(np.arange(5), 3)
        eng.drain()
        cost = obs.site_costs().get("decode.chunk")
    finally:
        set_flags({"obs_enabled": False})
    if not cost or "alias_bytes" not in cost:
        pytest.skip("memory_analysis unavailable on this backend")
    assert cost["alias_bytes"] == carry
    assert cost["peak_bytes"] == (cost["temp_bytes"] + cost["output_bytes"]
                                  - carry)
    assert cost["output_bytes"] >= carry


# -- (b) the chunk reads the ring, it does not take it

@pytest.mark.parametrize("kind", ["gqa", "ouro"])
def test_chunks_leave_the_ring_to_the_next_admission(decs, kind):
    """Admit, run two chunks, admit again: the second admission stages
    into the ring the first one used, and every request decodes what a
    host-scatter engine (no ring) decodes."""
    d = decs(kind)
    reqs = _requests(4, seed=5, budget=(9, 14))
    outs = []
    for kw in (dict(), dict(prefix_cache=True, prefix_cache_bytes=1 << 26)):
        eng = ServingEngine(d, num_slots=2, chunk_size=4, **kw)
        ids = [eng.submit(p, n) for p, n in reqs[:2]]
        got = {}
        for i in range(2):
            got.update(eng.step())
            assert not (eng._ring_slots and eng._b.ring_consumed())
        ids += [eng.submit(p, n) for p, n in reqs[2:]]
        got.update(eng.drain())
        outs.append([np.asarray(got[r]) for r in ids])
        if eng._ring_slots:
            m = eng.metrics()["admission_ring"]
            assert m["staged"] == m["scattered"] == len(reqs)
            assert m["host_scattered"] == 0
        else:
            assert eng.metrics()["admission_ring"] is None
    for a, b in zip(*outs):
        np.testing.assert_array_equal(a, b)


# -- (c) same tokens as run-to-completion

@pytest.mark.parametrize("kind", KINDS)
def test_donated_chunks_are_bit_exact_with_run_to_completion(decs, kind):
    """Greedy tokens through ``ServingEngine`` and through chained
    ``decode_chunk`` calls equal ``generate``'s, and each chunk consumes
    the state it was given and no other."""
    d = decs(kind)
    reqs = _requests(5, seed=2)
    solo = [np.asarray(d.generate(p[None], n)) for p, n in reqs]
    eng = ServingEngine(d, num_slots=2, chunk_size=3)
    ids = [eng.submit(p, n) for p, n in reqs]
    res = eng.drain()
    for rid, want in zip(ids, solo):
        np.testing.assert_array_equal(np.asarray(res[rid]), want)
    assert eng.metrics()["step_dispatches"] == 0
    p, n = reqs[0][0], 9
    want = np.asarray(d.generate(p[None], n))[:, len(p):]
    st = d.init_decode_state(p[None])
    parts = []
    for T in (2, 3, 4):
        old = st
        t, st = d.decode_chunk(st, T)
        assert old.consumed and not st.consumed
        parts.append(np.asarray(t))
    np.testing.assert_array_equal(np.concatenate(parts, axis=1), want)


# -- (d) the ladder

def _chunk_fails_after_dispatch(dec):
    """Swap the decoder's chunk entry for one that runs the real program
    — which takes the carry it is given — and then fails like a backend
    that lost the result."""
    real = dec._ring_chunk_decode._jitted

    def lost(*args, **kwargs):
        real(*args, **kwargs)
        raise RuntimeError("UNAVAILABLE: lost after the dispatch")

    dec._ring_chunk_decode = dec._counted(lost, "decode.chunk",
                                          consumes=(2, 3))


@pytest.fixture
def no_backoff():
    from paddle_tpu.flags import set_flags
    from paddle_tpu.runtime.resilience import fault_injector
    set_flags({"resilience_backoff_s": 0.0})
    yield fault_injector
    fault_injector.clear()
    set_flags({"resilience_backoff_s": 0.5})


@pytest.mark.faults
@pytest.mark.parametrize("kind", ["gqa", "ouro"])
def test_injected_chunk_fault_leaves_the_carry_to_the_step_rung(
        decs, kind, no_backoff):
    """A fault plan fires before the dispatch: the carry is alive, the
    per-token rung re-enters it one donated step at a time and every
    request finishes bit-exactly."""
    d = decs(kind)
    reqs = _requests(4, seed=7, budget=(3, 9))
    solo = [np.asarray(d.generate(p[None], n)) for p, n in reqs]
    no_backoff.configure([{"kind": "dispatch_error", "site": "decode.chunk",
                           "call": 2, "times": 1000}])
    eng = ServingEngine(d, num_slots=2, chunk_size=4)
    ids = [eng.submit(p, n) for p, n in reqs]
    res = eng.drain()
    for rid, want in zip(ids, solo):
        np.testing.assert_array_equal(np.asarray(res[rid]), want)
    m = eng.metrics()
    assert m["degradations"] >= 1 and m["step_dispatches"] >= 4
    assert res[ids[-1]].resilience["level"] == "per_token"
    assert not eng.state.consumed


@pytest.mark.faults
@pytest.mark.parametrize("retries", [0, 2])
def test_consumed_carry_surfaces_as_decode_failed(no_backoff, retries):
    """A chunk that failed after it had taken the carry leaves nothing
    for a retry or for the per-token rung: the engine raises the typed
    ``DecodeFailedError`` on that step and on every later one (the
    router's breaker counts them), never a raw deleted-buffer error."""
    from paddle_tpu.flags import set_flags
    from paddle_tpu.runtime.resilience import DecodeFailedError
    d = _decoder("gqa")
    eng = ServingEngine(d, num_slots=2, chunk_size=4)
    for n in (12, 9):
        eng.submit(np.arange(n % 7 + 2), n)
    assert eng.step() == []                       # one good chunk
    _chunk_fails_after_dispatch(d)
    set_flags({"resilience_retries": retries})
    try:
        for step in range(2):
            with pytest.raises(DecodeFailedError,
                               match="consumed the carry") as ei:
                eng.step()
            assert "deleted" not in str(ei.value).lower()
            # with no retry the first failure is the backend's own
            # error; a retry, and every later step, finds the carry gone
            assert isinstance(ei.value.last_error, ConsumedStateError) \
                == bool(retries or step)
    finally:
        set_flags({"resilience_retries": 3})
    assert eng.state.consumed
    m = eng.metrics()
    assert m["step_dispatches"] == 0 and m["degradations"] == 0
    assert len(eng.export_inflight()) == 2        # the router's requeue


@pytest.mark.faults
@pytest.mark.parametrize("took_ring", [False, True])
def test_failed_ring_prefill_leaves_a_live_ring_and_no_rowless_slot(
        no_backoff, took_ring):
    """The second of three admission prefills fails — before its
    dispatch (an injected fault: the ring is untouched and keeps the
    first request's row) or after it had taken the donated ring (the
    engine builds a new one and the first request's row is lost with
    the old). Either way no request stays in a slot without a row, and
    once the fault is gone all three decode bit-exactly."""
    d = _decoder("gqa")
    reqs = _requests(3, seed=9)
    solo = [np.asarray(d.generate(p[None], n)) for p, n in reqs]
    eng = ServingEngine(d, num_slots=3, chunk_size=4)
    real = d._ring_admit_prefill
    ids = [eng.submit(p, n) for p, n in reqs]
    if took_ring:
        calls = []

        def second_fails(*args, **kwargs):
            calls.append(1)
            out = real._jitted(*args, **kwargs)
            if len(calls) == 2:
                raise RuntimeError("UNAVAILABLE: lost after the dispatch")
            return out

        d._ring_admit_prefill = d._counted(
            second_fails, "decode.admit_prefill", consumes=(6, 7, 8))
        raised = ConsumedStateError      # what the retry found
    else:
        from paddle_tpu.runtime.resilience import InjectedFault
        no_backoff.configure([{"kind": "dispatch_error",
                               "site": "decode.admit_prefill",
                               "call": 2, "times": 1000}])
        raised = InjectedFault
    with pytest.raises(raised, match="decode.admit_prefill"):
        eng.step()
    assert not eng._b.ring_consumed()
    staged = [m for m in eng._ring_meta if m is not None]
    assert len(staged) == (0 if took_ring else 1)
    assert len(eng.scheduler) == 3 - len(staged)
    assert len(eng.scheduler.slots.occupied()) == len(staged)
    no_backoff.clear()
    d._ring_admit_prefill = real
    res = eng.drain()
    for rid, want in zip(ids, solo):
        np.testing.assert_array_equal(np.asarray(res[rid]), want)


# -- (e) readers of the carry after donated chunks

@pytest.mark.parametrize("kind", ["gqa", "ouro"])
def test_snapshot_and_migration_read_the_live_carry(decs, kind, tmp_path):
    """``snapshot`` -> ``restore`` and ``extract_rows`` -> ``absorb_rows``
    after donated chunks read the state the engine holds now, and the
    requests go on bit-exactly."""
    d = decs(kind)
    reqs = _requests(4, seed=13, budget=(10, 15))
    solo = [np.asarray(d.generate(p[None], n)) for p, n in reqs]
    eng = ServingEngine(d, num_slots=2, chunk_size=3)
    ids = [eng.submit(p, n) for p, n in reqs]
    got = {}
    for _ in range(2):
        got.update(eng.step())
    eng.snapshot(str(tmp_path / "snap"))
    fresh = ServingEngine(d, num_slots=2, chunk_size=3)
    assert fresh.restore(str(tmp_path / "snap"))["in_flight"] == 2
    got.update(fresh.step())
    # and one of the restored rows moves on to a third engine mid-flight
    victim = next(s.request.id
                  for _, s in fresh.scheduler.slots.occupied())
    third = ServingEngine(d, num_slots=2, chunk_size=3)
    mapping = third.absorb_rows(fresh.extract_rows([victim]))
    moved = third.drain()
    got.update(fresh.drain())
    got[victim] = moved[mapping[victim]]
    for rid, want in zip(ids, solo):
        np.testing.assert_array_equal(np.asarray(got[rid]), want)


# -- (f) the contract, for library users

@pytest.mark.parametrize("kind", ["gqa", "mha", "ouro"])
def test_reusing_a_consumed_state_raises_a_clear_error(decs, kind):
    d = decs(kind)
    prompt = np.arange(6)[None]
    st = d.init_decode_state(prompt)
    toks, st2 = d.decode_chunk(st, 3)
    assert st.consumed and not st2.consumed
    d0 = d.dispatch_count
    with pytest.raises(ConsumedStateError, match="go on from the state"):
        d.decode_chunk(st, 3)
    assert d.dispatch_count == d0            # nothing was dispatched
    # the small fields of the old state stay readable; its caches do not
    assert np.asarray(st.pos).tolist() == [6]
    with pytest.raises(RuntimeError, match="deleted"):
        np.asarray(st.kc[0])
    # branching twice from one prompt: build the state twice
    again, _ = d.decode_chunk(d.init_decode_state(prompt), 3)
    np.testing.assert_array_equal(np.asarray(again), np.asarray(toks))
