"""A prefill that starts at position 0 by construction does only the work
its rows need (PR 37): attention over its own S fresh keys instead of the
whole ``max_len`` buffer, the final norm and the head at each row's last
true position instead of at all S.

Tiny widths on the CPU. The OLD route — ``_forward_cached(return_all=True)``
with no word about where it starts, so buffer-wide attention and all-position
logits, gathered at ``true_len - 1`` — is kept here as the plain reference.
The vocabulary (200) is no other width of these configs, so a ``(B, S, V)``
array in a program's text can only be the logits.
"""

import dataclasses
import hashlib
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.inference.generate import LlamaDecoder, _forward_cached
from paddle_tpu.models import (AFMOE_TINY, OURO_TINY, TINY_CONFIG,
                               AfmoeForCausalLM, LlamaForCausalLM,
                               OuroForCausalLM)
from paddle_tpu.models.evabyte import EVABYTE_TINY, EvabyteForCausalLM
from paddle_tpu.models.ouro import OuroConfig
from paddle_tpu.serving import ServingEngine

V, H, MAX_LEN, S = 200, 4, 128, 32
GQA = dataclasses.replace(TINY_CONFIG, vocab_size=V, intermediate_size=96)
MHA = dataclasses.replace(GQA, num_key_value_heads=H)
LOOPED = OuroConfig(**{**vars(OURO_TINY), "vocab_size": V,
                       "intermediate_size": 96, "total_ut_steps": 3})
FAMILIES = {"gqa": (GQA, LlamaForCausalLM), "mha": (MHA, LlamaForCausalLM),
            "looped": (LOOPED, OuroForCausalLM)}
WINDOWED = {"afmoe": (AFMOE_TINY, AfmoeForCausalLM)}    # vocabulary 256


def _decoder(family, **kw):
    cfg, cls = {**FAMILIES, **WINDOWED}[family]
    paddle.seed(7)
    return LlamaDecoder(cls(cfg), max_len=MAX_LEN, **kw)


@pytest.fixture(scope="module", params=sorted(FAMILIES))
def dec(request):
    return _decoder(request.param)


def _rows(lens, seed=0):
    """(ids (B, S) right-padded with zeros, true_len (B,), the prompts)."""
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(1, V, (n,), dtype=np.int32) for n in lens]
    ids = np.zeros((len(lens), S), np.int32)
    for j, p in enumerate(prompts):
        ids[j, :len(p)] = p
    return jnp.asarray(ids), jnp.asarray(lens, jnp.int32), prompts


def _ring_args(dec, ids, true_len):
    """Positional arguments of ``ring_admit_prefill`` (a fresh ring of 4
    rows and empty pair: the ring is donated)."""
    B = ids.shape[0]
    kc, vc = dec._empty_cache(B)
    rkc, rvc = dec._empty_cache(4)
    return (dec.params, ids, kc, vc, true_len, jnp.zeros((B,), jnp.int32),
            jnp.zeros((4, dec.cfg.vocab_size), jnp.float32), rkc, rvc,
            jnp.arange(B, dtype=jnp.int32))


def _old_route(dec, ids, true_len):
    """The parent's admission: all-position logits from attention over the
    whole cache buffer, one row of them kept."""
    kc, vc = dec._empty_cache(ids.shape[0])
    pos0 = jnp.zeros((ids.shape[0],), jnp.int32)
    logits_all, kc, vc = jax.jit(
        lambda p, i, k, v, at: _forward_cached(
            p, dec.cfg, i, k, v, at, MAX_LEN, return_all=True))(
        dec.params, ids, kc, vc, pos0)
    return jnp.take_along_axis(
        logits_all, (true_len - 1)[:, None, None], axis=1)[:, 0], kc, vc


def _shape(*dims):
    return re.compile(r"tensor<" + "x".join(map(str, dims)) + r"x(f32|bf16)>")


def _scores(B, s):
    return _shape(B, H, s, MAX_LEN)          # (B, H, S, max_len)


LOGITS_ALL = _shape(1, S, V)                 # (B, S, V)


# -- (a) the same logits, caches and tokens -----------------------------------

def test_ring_admission_equals_solo_prefill_and_the_old_route(dec):
    """Two rows of different true lengths in one bucket: the ring's logits
    are the solo prefill's of each unpadded prompt and the old route's
    (float tolerance: the softmax sums the same S terms over another
    extent), the same greedy pick, the same cache rows."""
    lens = [5, 23]
    ids, true_len, prompts = _rows(lens)
    ring_logits, rkc, rvc = dec._ring_admit_prefill(
        *_ring_args(dec, ids, true_len))
    got = np.asarray(ring_logits[:2])
    old, okc, ovc = _old_route(dec, ids, true_len)
    np.testing.assert_allclose(got, np.asarray(old), atol=2e-5)
    assert np.array_equal(got.argmax(-1), np.asarray(old).argmax(-1))
    for j, p in enumerate(prompts):
        kc, vc = dec._empty_cache(1)
        solo, _, _ = dec._prefill(dec.params, jnp.asarray(p[None]), kc, vc)
        np.testing.assert_allclose(got[j], np.asarray(solo[0]), atol=2e-5)
        assert got[j].argmax() == int(np.asarray(solo[0]).argmax())
    # the buffers: a row's first true_len positions, every cache layer
    assert dec.cfg.cache_head_major          # every family, since PR 39
    tokens = 1                               # positions' axis of (KV, L, D)
    for new, ref in zip(rkc + rvc, okc + ovc):
        for j, n in enumerate(lens):
            a, b = (np.take(np.asarray(x[j]), range(n), axis=tokens)
                    for x in (new, ref))
            np.testing.assert_allclose(a, b, atol=2e-5)


def test_served_tokens_equal_solo_generate(dec):
    """Requests through the engine's ring (two share a bucket and an
    admission round) decode token for token like solo ``generate``."""
    rng = np.random.default_rng(2)
    prompts = [rng.integers(1, V, (n,), dtype=np.int32)
               for n in (5, 23, 17, 9)]
    solo = [np.asarray(dec.generate(p[None], 6))[0] for p in prompts]
    eng = ServingEngine(dec, num_slots=2, chunk_size=3)
    rids = [eng.submit(p, 6) for p in prompts]
    out = eng.drain()
    for rid, want in zip(rids, solo):
        assert np.array_equal(np.asarray(out[rid])[0], want)
    m = eng.metrics()
    assert m["admission_ring"]["host_scattered"] == 0
    assert m["prefill_dispatches"] == len(prompts)


# -- (b) what the ring prefill no longer holds --------------------------------

def test_ring_prefill_holds_no_buffer_wide_scores_and_no_all_position_logits(
        dec):
    ids, true_len, _ = _rows([23])
    text = dec._ring_admit_prefill._jitted.lower(
        *_ring_args(dec, ids, true_len)).as_text()
    assert not _scores(1, S).search(text)
    assert not LOGITS_ALL.search(text)
    assert _shape(1, V).search(text)         # one row of logits
    # the solo prefill too: the route the engine is compared with
    kc, vc = dec._empty_cache(1)
    solo = dec._prefill._jitted.lower(dec.params, ids, kc, vc).as_text()
    assert not _scores(1, S).search(solo) and not LOGITS_ALL.search(solo)


@pytest.fixture
def flash_calls(monkeypatch):
    """Route the decoder's kernels off the TPU (interpret mode) and count
    the prefill's flash forward calls at trace time, by their window."""
    from paddle_tpu.inference import generate as G
    calls, real = [], G._flash_prefill

    def counted(q, k, v, **kw):
        calls.append(kw["window"])
        return real(q, k, v, **kw)
    monkeypatch.setattr(G, "_flash_prefill", counted)
    paddle.set_flags({"decode_attention_interpret": True})
    yield calls
    paddle.set_flags({"decode_attention_interpret": False})


def test_assigning_a_flag_sets_it_and_shadows_nothing():
    """``flags.x = v`` (tests/test_decode.py, test_evabyte.py, and any
    ``monkeypatch.setattr(flags, ...)`` with its teardown) goes through
    the registry: stored as an attribute it hid the flag from every later
    ``set_flags`` in the process, and the kernel route of the test below
    was never taken when such a test had run before it."""
    from paddle_tpu.flags import flags
    flags.decode_attention_interpret = True
    assert flags.decode_attention_interpret is True
    flags.decode_attention_interpret = False
    assert "decode_attention_interpret" not in vars(flags)
    paddle.set_flags({"decode_attention_interpret": True})
    assert flags.decode_attention_interpret is True
    paddle.set_flags({"decode_attention_interpret": False})
    with pytest.raises(AttributeError):
        flags.no_such_flag = 1


@pytest.mark.parametrize("family", ["gqa", "looped", "afmoe"])
def test_on_the_kernels_backend_a_cold_prefill_is_one_flash_call_a_layer(
        family, flash_calls):
    """One rule for plain and windowed layers: where the decoder's kernels
    run (a TPU; here the interpret flag) the fresh keys go through the
    flash forward once a cache layer, under a band where the layer's
    window is shorter than the bucket — one inner ``jit``, so the lowered
    program holds the kernel's body once a window, not once a layer; the
    logits are those of the XLA form the CPU suite otherwise runs."""
    ids, true_len, _ = _rows([23], seed=6)
    d = _decoder(family)            # flags are read at trace time
    got, _, _ = d._ring_admit_prefill(*_ring_args(d, ids, true_len))
    cfg = d.cfg
    windows = [cfg.layer_window(li) for li in range(cfg.num_hidden_layers)]
    assert flash_calls == [w if w and w < S else None
                           for w in windows] * cfg.total_ut_steps
    n = len(flash_calls)
    text = d._ring_admit_prefill._jitted.lower(
        *_ring_args(d, ids, true_len)).as_text()
    assert len(re.findall(r"func\.func private @_flash_prefill", text)) \
        == len(set(flash_calls))
    paddle.set_flags({"decode_attention_interpret": False})
    plain = _decoder(family)
    want, _, _ = plain._ring_admit_prefill(
        *_ring_args(plain, ids, true_len))
    assert len(flash_calls) == n                # none in the CPU's form
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want[0]),
                               atol=2e-5)


# -- (c) the entries that may start anywhere keep the buffer ------------------

def test_suffix_prefill_attends_over_the_buffer_and_matches_a_cold_prefill(
        dec):
    """``admit_prefill`` takes per-row offsets: its attention stays
    buffer-wide (the head is the gathered one), and a prompt prefilled as
    prefix + suffix at ``pos0 > 0`` ends in the cold prefill's logits."""
    P, cut = 20, 12
    ids, true_len, (prompt,) = _rows([P], seed=4)
    kc, vc = dec._empty_cache(1)
    one = jnp.ones((1,), jnp.int32)
    text = dec._admit_prefill._jitted.lower(
        dec.params, ids, kc, vc, true_len, 0 * one).as_text()
    assert _scores(1, S).search(text)
    assert not LOGITS_ALL.search(text)
    cold, _, _ = dec._ring_admit_prefill(*_ring_args(dec, ids, true_len))
    _, kc, vc = dec._admit_prefill(
        dec.params, jnp.asarray(prompt[None, :cut]), kc, vc, cut * one,
        0 * one)
    suffix = np.zeros((1, 16), np.int32)
    suffix[0, :P - cut] = prompt[cut:]
    warm, _, _ = dec._admit_prefill(
        dec.params, jnp.asarray(suffix), kc, vc, (P - cut) * one, cut * one)
    np.testing.assert_allclose(np.asarray(warm[0]), np.asarray(cold[0]),
                               atol=2e-5)
    assert int(np.asarray(warm[0]).argmax()) == int(
        np.asarray(cold[0]).argmax())


# the speculative round's lowered text (K = 3, one row, 'skip:1' draft) on
# the parent commit (cb49b35): draft steps and the verify's S = K + 1
# forward at pos > 0 are none of PR 37's entries. "mha" RETAKEN by PR 39
# on its own tree: the MHA caches it reads and writes are head-major now
_SPEC_ROUND_TEXT = {"gqa": "38d62f7e126e1d8b", "mha": "1da5c487e8b15d69"}


def _spec_round_text(dec):
    eng = dec._spec_engine("skip:1")
    kc, vc = dec._empty_cache(1)
    dkc, dvc = dec._empty_cache(1, eng["cfg"])
    one = jnp.ones((1,), jnp.int32)
    return eng["round"]._jitted.lower(
        dec.params, eng["params"], one, 5 * one, jax.random.PRNGKey(0),
        jnp.zeros((1,), jnp.bool_), kc, vc, dkc, dvc, jnp.int32(-1),
        jnp.float32(1.0), K=3, do_sample=False, use_eos=False, top_k=None,
        top_p=None).as_text()


@pytest.mark.parametrize("family", sorted(_SPEC_ROUND_TEXT))
def test_speculative_verify_keeps_its_text_and_its_tokens(family):
    d = _decoder(family)
    text = _spec_round_text(d)
    assert _scores(1, 4).search(text)            # S = K + 1 over the buffer
    assert _sha(text) == _SPEC_ROUND_TEXT[family]
    ids = np.asarray(_rows([9], seed=5)[2][0])[None]
    plain = np.asarray(d.generate(ids, 8))
    spec = np.asarray(d.generate(ids, 8, draft_model="skip:1",
                                 num_speculative_tokens=3))
    assert np.array_equal(plain, spec)


# -- (d) a quantised cache's prefill ------------------------------------------

# the int8wk solo prefill's lowered text on the parent commit (cb49b35)
_INT8WK_PREFILL_TEXT = "b1cacd7ff8d1f1b3"


def test_a_quantized_cache_keeps_its_prefill():
    """``int8wk``: the prefill attends over the dequantised rows a decode
    step will read, so the solo prefill's text is the parent's and the
    ring prefill keeps the buffer-wide scores."""
    d = _decoder("gqa", quant="int8wk")
    ids, true_len, _ = _rows([23])
    kc, vc = d._empty_cache(1)
    solo = d._prefill._jitted.lower(d.params, ids, kc, vc).as_text()
    assert _sha(solo) == _INT8WK_PREFILL_TEXT
    ring = d._ring_admit_prefill._jitted.lower(
        *_ring_args(d, ids, true_len)).as_text()
    assert _scores(1, S).search(ring)
    assert not LOGITS_ALL.search(ring)


# -- (e) the chunk programs do not move ---------------------------------------

# the lowered text of each configuration's chunk program (4 steps, 2 rows,
# max_len 128) on the parent commit (cb49b35); the first, third and fourth
# are the ones tests/test_evabyte.py has held since PR 36. "mha" and
# "looped" were RETAKEN by PR 39 on its own tree, which meant to move them:
# an MHA cache is head-major now (LlamaConfig.cache_head_major), so its
# buffers, its row write and XLA's attention over it are laid out as GQA's
# (off the TPU the decode kernel is not routed: this is the XLA form).
# "gqa", "afmoe" and "evabyte" are the parent's still: the programs of the
# GQA, AFMoE and EvaByte cells did not move by a byte
_CHUNK_TEXT = {
    "gqa": "e3fe9ce2b00457d9",
    "mha": "0f68522e3360948c",
    "looped": "3f1a2be5c433c459",
    "afmoe": "f72d6133ef314834",
    "evabyte": "356c1db56d7bbd60",
}
_CHUNK_CONFIGS = {
    "gqa": (TINY_CONFIG, LlamaForCausalLM),
    "mha": (dataclasses.replace(TINY_CONFIG, num_key_value_heads=4),
            LlamaForCausalLM),
    "looped": (OURO_TINY, OuroForCausalLM),
    "afmoe": (AFMOE_TINY, AfmoeForCausalLM),
    "evabyte": (EVABYTE_TINY, EvabyteForCausalLM),
}


def _sha(text):
    return hashlib.sha256(text.encode()).hexdigest()[:16]


def _chunk_text(family):
    cfg, cls = _CHUNK_CONFIGS[family]
    d = LlamaDecoder(cls(cfg), max_len=MAX_LEN)
    B = 2
    kc, vc = d._empty_cache(B)

    def z(shape, dt):
        return jnp.zeros(shape, dt)
    return d._ring_chunk_decode._jitted.lower(
        d.params, z((B, cfg.vocab_size), jnp.float32), kc, vc,
        z((B,), jnp.int32), z((B, 2), jnp.uint32), z((B,), jnp.bool_),
        z((B,), jnp.int32), z((B,), jnp.float32), None, *(None,) * 9,
        steps=4, do_sample=False, top_k=None, top_p=None).as_text()


@pytest.mark.parametrize("family", sorted(_CHUNK_TEXT))
def test_chunk_programs_keep_their_text(family):
    assert _sha(_chunk_text(family)) == _CHUNK_TEXT[family]


def _parent_hashes():
    """What to run on a checkout of the parent to retake the hashes above
    (``python -c 'import test_prefill_from_zero as t; t._parent_hashes()'``
    with tests/ on the path)."""
    for f in sorted(_CHUNK_TEXT):
        print("chunk", f, _sha(_chunk_text(f)))
    for f in sorted(_SPEC_ROUND_TEXT):
        print("spec_round", f, _sha(_spec_round_text(_decoder(f))))
    d = _decoder("gqa", quant="int8wk")
    kc, vc = d._empty_cache(1)
    print("int8wk_prefill", _sha(d._prefill._jitted.lower(
        d.params, _rows([23])[0], kc, vc).as_text()))
