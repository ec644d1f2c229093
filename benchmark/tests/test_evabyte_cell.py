"""What the EvaByte configuration brings to the benchmark: the
configuration file against the catalog, the adapter's refusals and its
split of the decoder's folded and fused parameters, the runner's gates on
the decoder ACROSS A WINDOW'S END at a tiny width (an arch of this test's
own, window 16 and chunk 4: ``--rehearse`` keeps the published window of
2048 under a ``max_len`` of 128 and never crosses it) and on a reference
with another window, ``serve_model_ctx``'s hand-over of the check's
lengths, the cell's rehearsal, the counts of ``eva_flops.py`` against a
count by hand, and the four new readers on hand-made counters and
traces."""

import json
import os

import numpy as np
import pytest

from benchmark import eva_flops as ef, run as bench_run
from benchmark.harness import resolve
from benchmark.harness.trace import WINDOW_SPAN
from benchmark.peaks import peaks

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "evabyte.serve.longdoc-backlog"
adapter = resolve.load_module("adapters", "evabyte")
runner = resolve.load_module("runners", "serve_model")
with open(os.path.join(ROOT, "benchmark/configs/evabyte-6.5b.json")) as fh:
    CONFIG = json.load(fh)

TINY = {"hidden_size": 64, "intermediate_size": 128,
        "num_attention_heads": 4, "num_key_value_heads": 4, "head_dim": 16,
        "vocab_size": 96, "max_position_embeddings": 128,
        "rope_theta": 100000, "rms_norm_eps": 1e-5,
        "tie_word_embeddings": False, "window_size": 16, "chunk_size": 4,
        "num_pred_heads": 2, "num_hidden_layers": 2}
SECTION = {"num_hidden_layers": 2, "dtype": "float32", "num_slots": 2,
           "max_len": 128, "chunk_size": 4, "mesh": None}


def test_the_configuration_keeps_every_published_key():
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as fh:
        rows = [json.loads(line) for line in fh]
    want = next(r for r in rows if r["name"] == "EvaByte")
    assert CONFIG["source"] == want["source_url"]
    assert CONFIG["reduced"] == ["num_hidden_layers"]
    assert all(CONFIG[k] == v for k, v in want["config"].items()
               if k != "num_hidden_layers")
    assert (CONFIG["num_hidden_layers"],
            CONFIG["num_hidden_layers_published"]) == (8, 32)
    sec = CONFIG["sections"]["serve"]
    assert (sec["num_slots"], sec["max_len"], sec["chunk_size"],
            sec["dtype"]) == (8, 32768, 16, "bfloat16")
    cfg = adapter.program_config(adapter.arch_of(CONFIG), sec)
    assert (cfg.hidden_size, cfg.intermediate_size, cfg.num_attention_heads,
            cfg.num_key_value_heads, cfg.head_dim, cfg.vocab_size,
            cfg.num_pred_heads, cfg.window_size, cfg.chunk_size,
            cfg.num_hidden_layers, cfg.rope_theta) == (
        4096, 11008, 32, 32, 128, 320, 8, 2048, 16, 8, 100000)
    # a cache layer: a window leaf of 2048 rows and 32768 / 16 summaries
    assert [cfg.cache_len(b, 32768) for b in range(4)] == [2048] * 4
    assert cfg.eva and cfg.cache_leaves == 2
    for key, bad in (("attention_class", "softmax"), ("num_chunks", 64),
                     ("rope_scaling", {"type": "yarn"}),
                     ("hidden_act", "gelu"), ("fp32_skip_add", False),
                     ("norm_add_unit_offset", False)):
        with pytest.raises(ValueError, match=key):
            adapter.arch_of({**CONFIG, key: bad})


def _decoder(seed=5):
    """A float32 decoder at the tiny width through the adapter, its norm
    weights jittered off zero AFTER the build (the decoder holds 1 + w)."""
    import jax.numpy as jnp

    import paddle_tpu as paddle
    from paddle_tpu.inference.generate import LlamaDecoder
    paddle.seed(seed)
    model = adapter.build_model(adapter.program_config(TINY, SECTION))
    rng = np.random.default_rng(seed)
    for name, p in model.named_parameters():
        if "norm" in name:
            p._value = jnp.asarray(0.2 * rng.standard_normal(p.shape),
                                   p._value.dtype)
    return LlamaDecoder(model, max_len=SECTION["max_len"])


@pytest.fixture(scope="module")
def served():
    """(decoder, sequences, prompt lengths): the engine's greedy tokens
    from prompts of 12 and 36 — the compared decode steps of the first
    cross position 16, the second prefills two windows and 4 positions."""
    from paddle_tpu.serving import ServingEngine
    dec = _decoder()
    eng = ServingEngine(dec, num_slots=2, chunk_size=4)
    rng = np.random.default_rng(2)
    lens = [12, 36]
    rids = [eng.submit(rng.integers(0, TINY["vocab_size"], (n,),
                                    dtype=np.int32), runner.CHECK_BUDGET)
            for n in lens]
    done = eng.drain()
    return dec, [np.asarray(done[r])[0] for r in rids], lens


def test_the_runners_gates_hold_across_a_windows_end(served):
    dec, seqs, lens = served
    out = runner._check_against_reference(dec, adapter, TINY, 2, seqs, lens)
    assert out["ok"] and out["logits_err_max"] < 1e-4
    assert out["token_gap_ulps_max"] == 0.0
    assert (out["logits_tol"], out["tie_ulps"]) == (
        adapter.LOGITS_TOL, adapter.TIE_ULPS)


@pytest.mark.parametrize("key,value", [("window_size", 32),
                                       ("chunk_size", 8)])
def test_a_reference_of_another_window_or_chunk_fails_the_gates(
        served, key, value):
    """The gates see the mechanism: against a reference whose window ends
    elsewhere, or whose chunks are longer, the same decoder fails."""
    dec, seqs, lens = served
    out = runner._check_against_reference(
        dec, adapter, {**TINY, key: value}, 2, seqs, lens)
    assert not out["ok"] and out["logits_err_max"] > adapter.LOGITS_TOL


def test_the_adapter_hands_the_reference_w_and_the_next_bytes_logits(served):
    dec = served[0]
    w = adapter.layer_weights_from_decoder(dec.params, TINY)(1)
    assert set(w) == set(adapter.ref.LAYER_KEYS)
    folded = np.asarray(dec.params["model.layers.1.input_layernorm.weight"])
    np.testing.assert_allclose(w["input_layernorm"], folded - 1.0)
    assert 0.05 < np.abs(w["input_layernorm"]).max() < 1.0
    assert w["q_proj"].shape == w["k_proj"].shape == (64, 64)
    assert w["gate_proj"].shape == (64, 128)
    assert dec.params["lm_head.weight"].shape == (64, 2 * 96)
    ids = np.arange(20, dtype=np.int32)[None] % 96
    got = adapter.reference_logits(dec.params, TINY, 2, ids, [3, 19])
    assert got.shape == (2, 96) and got.dtype == np.float32


def test_serve_model_ctx_sets_the_checks_lengths_from_the_mix(monkeypatch):
    ctx = resolve.load_module("runners", "serve_model_ctx")
    seen = {}
    monkeypatch.setattr(ctx.serve_model, "run",
                        lambda *a: seen.update(
                            lens=ctx.serve_model.CHECK_PROMPTS) or "ran")
    monkeypatch.setattr(ctx.serve_model, "CHECK_PROMPTS", (64, 100))
    cell = resolve.load_cell(CELL)
    assert cell["runner"] == "serve_model_ctx"
    assert ctx.run(cell, None, None, 0.0, None) == "ran"
    assert seen["lens"] == (2044, 4100)
    # 8 compared steps from 2044 cross 2048; 4100 is two windows and 4
    W, steps = CONFIG["window_size"], runner.CHECK_STEPS
    assert 2044 < W <= 2044 + steps and 4100 // W == 2 and 4100 % W == 4
    assert ctx.run({"mix": {}}, None, None, 0.0, None) == "ran"
    assert seen["lens"] == (2044, 4100)     # no lengths named: left alone


@pytest.mark.parametrize("trace", [0, 1])
def test_the_cell_rehearses(capsys, trace):
    rc = bench_run.main(["--workload", CELL, "--seed", str(2**31 + 36),
                         "--seconds", "1.5", "--trace", str(trace),
                         "--rehearse"])
    last = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0 and last["correct"] is True and last["failed"] == 0
    if trace:
        assert "eva_summary_share" in last["metrics"]
        assert "sched_occupancy" in last["metrics"]
    else:
        assert set(last["metrics"]) == {"out_tok_s", "setup_s"}


def test_eva_flops_against_a_count_by_hand():
    # a prompt of 2 windows of 8 and 3 positions, chunks of 2
    p = ef.eva_prefill_pairs(19, 8, 2)
    local = sum(1 for i in range(19) for j in range(19)
                if j // 8 == i // 8 and j <= i)
    summary = sum(1 for i in range(19) for c in range(19 // 2)
                  if c // 4 < i // 8)
    assert (p["local"], p["summary"]) == (local, summary) == (78, 56)
    a = ef.eva_prefill_attention(n=19, window=8, chunk=2, heads=4,
                                 head_dim=16, layers=3, bytes_per_el=2)
    assert a["flops"] == 4 * 16 * 4 * (78 + 56) * 3
    assert a["bytes"] == (4 * 19 + 2 * 9) * 4 * 16 * 2 * 3
    # one decode step at the published widths, 8 rows
    mats = 4 * 4096 * 4096 + 3 * 4096 * 11008
    assert ef.eva_layer_params(hidden=4096, ffn=11008, heads=32,
                               head_dim=128) == mats + 2 * 4096 + 2 * 4096
    row = 2 * 32 * 128 * 2 * 8          # K and V of one entry over 8 layers
    d = ef.eva_decode_step(
        layers=8, hidden=4096, ffn=11008, heads=32, head_dim=128,
        head_rows=2560, chunk=16, rows=8.0, live_window=8 * 1000.0,
        live_summary=8 * 700.0, bytes_window_row=row, bytes_summary_row=row)
    assert d["weight_bytes"] == 2 * (8 * (mats + 4 * 4096) + 4096
                                     + 4096 * 2560 + 8 * 4096)
    assert d["leaf_bytes"] == 8 * 1700 * row
    assert d["written_bytes"] == 8 * 2 * row
    assert d["bytes"] == d["weight_bytes"] + d["leaf_bytes"] + 16 * row
    assert d["flops"] == (2.0 * 8 * (8 * mats + 4096 * 2560)
                          + 4.0 * 32 * 128 * 8 * 1700 * 8
                          + 8.0 * 32 * 128 * 16 * 8 * 8)
    assert 3.2e9 < d["weight_bytes"] < 3.3e9 and 1.7e9 < d["leaf_bytes"]


def _prefills(*lens):
    """The engine's count of what prefills of these true lengths needed."""
    pairs = [ef.eva_prefill_pairs(n, 2048, 16) for n in lens]
    return {"rows": len(lens), "positions": sum(lens),
            "local_pairs": sum(p["local"] for p in pairs),
            "summary_pairs": sum(p["summary"] for p in pairs)}


def _ctx(trace=None):
    """Counters of a window of 100 chunks of 16 steps on 8 full slots:
    1000 live window rows and 700 visible summaries a row; admission
    prefills of 5000 and 7000 positions in the bucket of 8192 and one of
    3000 in the bucket of 4096 (one of 6000 before the window)."""
    row = 2 * 32 * 128 * 2 * 8
    before = {"chunk_dispatches": 10, "live_window_positions_total": 5,
              "live_summary_positions_total": 7, "occupancy_mean": 1.0,
              "occupancy_samples": 10,
              "cache_bytes_per_position_window": row,
              "cache_bytes_per_position_summary": row,
              "eva_prefill_by_bucket": {8192: _prefills(6000)}}
    after = {**before, "chunk_dispatches": 110,
             "live_window_positions_total": 5 + 100 * 8 * 1000,
             "live_summary_positions_total": 7 + 100 * 8 * 700,
             "occupancy_samples": 110,
             "eva_prefill_by_bucket": {8192: _prefills(6000, 5000, 7000),
                                       4096: _prefills(3000)}}
    arch = adapter.arch_of(CONFIG)
    return {"trace": trace, "engine": {"before": before, "after": after},
            "arch": arch, "section": CONFIG["sections"]["serve"],
            "peaks": peaks("TPU v5 lite")}


def _trace(chunk_ms=128.0, kernel_ms=4.0, prefill=True):
    """One device: two runs of the chunk program holding the two-leaf
    kernel (8 layers x 16 steps of ``kernel_ms / 128`` each), and one
    admission prefill of 4 windows: 8 ``eva_prefill_attention`` calls of
    2 ms."""
    ms = 1_000_000
    mods, ops, t = [], [], 10 * ms
    for _ in range(2):
        mods.append(["jit_ring_chunk_decode(1)", t, int(chunk_ms * ms), ""])
        for i in range(128):
            ops.append([f"decode_attention_pair.{i}", t + i * ms // 2,
                        int(kernel_ms * ms / 128), ""])
        t += int(chunk_ms * ms) + ms
    if prefill:
        mods.append(["jit_ring_admit_prefill(2)", t, 40 * ms, ""])
        for i in range(8):
            ops.append([f"eva_prefill_attention.{i}", t + 4 * i * ms, 2 * ms,
                        "bf16[32,8192,128]{2,1,0} custom-call(...)"])
        t += 41 * ms
    return {"devices": {0: {"modules": mods, "ops": ops}},
            "host": [[WINDOW_SPAN, 0, t + ms, ""]]}


def _read(name, ctx):
    m = resolve.load_json("layer_metrics", name)
    return resolve.load_module("readers", m["reader"]).read(
        ctx, **m.get("args", {}))


def test_the_readers_on_hand_made_counters_and_traces():
    ctx = _ctx(_trace())
    row = 2 * 32 * 128 * 2 * 8
    assert _read("eva_summary_share", ctx) == pytest.approx(
        100 * 700 / 1700)
    # the kernel: 8 x 1700 entries x 131072 B a step, 4 ms / 16 a step
    least = 8 * 1700 * row / 819e9
    assert _read("eva_attn_roofline", ctx) == pytest.approx(
        100 * least / (4e-3 / 16))
    need = ef.eva_decode_step(
        layers=8, hidden=4096, ffn=11008, heads=32, head_dim=128,
        head_rows=2560, chunk=16, rows=8.0, live_window=8000.0,
        live_summary=5600.0, bytes_window_row=row, bytes_summary_row=row)
    assert _read("eva_step_roofline", ctx) == pytest.approx(
        100 * (need["bytes"] / 819e9) / (128e-3 / 16))
    # the prefill: one call a layer over the bucket of 8192 positions; the
    # engine counted the window's prefills of that bucket (5000 and 7000
    # true positions: the one before the window is taken off), the need a
    # row of them over the 16 ms of the kernel's calls, 8 layers
    per = [ef.eva_prefill_attention(n=n, window=2048, chunk=16, heads=32,
                                    head_dim=128, layers=1)["flops"]
           for n in (5000, 7000)]
    assert _read("eva_prefill_roofline", ctx) == pytest.approx(
        100 * (8 * sum(per) / 2 / 197e12) / 16e-3)
    assert any("eva_prefill_roofline" in n and "[8192] positions" in n
               for n in ctx["notes"])
    assert len(ctx["notes"]) == 3


def test_the_readers_find_nothing_rather_than_invent():
    from benchmark.harness.trace import TraceError
    none = _ctx(None)
    for name in ("eva_attn_roofline", "eva_step_roofline",
                 "eva_prefill_roofline"):
        assert _read(name, none) is None
    # a program before this PR: no counters, no EVA keys in its arch
    old = _ctx(_trace())
    for m in old["engine"].values():
        del m["live_summary_positions_total"], m["eva_prefill_by_bucket"]
    old["arch"] = {k: v for k, v in old["arch"].items()
                   if k not in ("window_size", "chunk_size")}
    for name in ("eva_attn_roofline", "eva_step_roofline",
                 "eva_prefill_roofline", "eva_summary_share"):
        assert _read(name, old) is None
    # a traced window that held no admission prefill
    assert _read("eva_prefill_roofline", _ctx(_trace(prefill=False))) is None
    # a traced bucket the engine counted no prefill of: no guess
    uncounted = _ctx(_trace())
    del uncounted["engine"]["after"]["eva_prefill_by_bucket"][8192]
    assert _read("eva_prefill_roofline", uncounted) is None
    # the decode kernel missing from the chunk program fails the run
    gone = _trace()
    gone["devices"][0]["ops"] = [
        e for e in gone["devices"][0]["ops"] if "pair" not in e[0]]
    with pytest.raises(TraceError, match="decode_attention_pair"):
        _read("eva_attn_roofline", _ctx(gone))
