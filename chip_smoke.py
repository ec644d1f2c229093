#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the two main paths once, in this one process, at Llama-2-7B width
(hidden 4096, 32 heads of 128, FFN 11008, vocab 32000; only the depth is
cut, to what one 16 GB v5e chip holds) with seeded random weights:

  probe   platform, device kind and count, versions, compile-cache directory,
          the native runtime library built from csrc/
  train   ShardedTrainer over LlamaForCausalLM (bf16 compute, f32 master
          weights, AdamW) on a (1,1,1) mesh at sequence 2048: a few
          train_step calls on one repeated batch and one train_steps dispatch
  serve   ServingEngine over LlamaDecoder in bf16: six requests admitted
          mid-flight, drained, one compared with the per-token reference rung

Each phase prints one JSON line; any failed phase makes the exit code
non-zero. The last stdout line of a passing run is
    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}
Without a TPU the default run fails at once and prints no result.

  --chips 4    only the sharded paths and what they are compared with: trainer
               steps on a dp=2 x mp=2 mesh against the same model, batch and
               seed on one device, and LlamaDecoder(mesh="tp:4") serving
               against the unsharded decoder over the same weights
  --rehearse   the same phases at TINY_CONFIG on whatever platform is present
               (reported truthfully): for CPU rehearsals and tier-1 only

Times printed here are smoke output, not measurements.
"""

from __future__ import annotations

import argparse
import gc
import json
import sys
import time
import traceback

# Depth of each phase at 7B width: the deepest whose AOT compile for v5e
# (memory_analysis of the step / admission-prefill / chunk programs) leaves
# about 3 GiB of the chip's 16 GiB clear. Bytes per depth: PERF.md "Cells".
TRAIN_LAYERS = 3
SERVE_LAYERS = 8
SHARDED_SERVE_LAYERS = 4

# |loss on 4 chips - loss on 1 chip| <= LOSS_RTOL * loss, per step: bf16
# matmuls reassociate across the mp split
LOSS_RTOL = 1e-3
TOP1_GATE = 0.99
TIE_ULPS = 4


def _emit(obj) -> None:
    print(json.dumps(obj), flush=True)


def _sizes(rehearse: bool, chips: int) -> dict:
    from paddle_tpu.models.llama import LLAMA_7B_CONFIG, TINY_CONFIG
    if rehearse:
        # one layer, two admission buckets: tier-1 pays for every compile
        return dict(cfg=TINY_CONFIG, train_layers=1, serve_layers=1,
                    batch=2, seq=64, max_len=128, chunk=4,
                    prompts=(20, 24, 33, 64, 28, 40),
                    budgets=(4, 8, 12, 16, 6, 10))
    return dict(cfg=LLAMA_7B_CONFIG, train_layers=TRAIN_LAYERS,
                serve_layers=(SERVE_LAYERS if chips == 1
                              else SHARDED_SERVE_LAYERS),
                batch=2, seq=2048, max_len=2048, chunk=16,
                prompts=(64, 120, 500, 512, 1000, 128),
                budgets=(16, 32, 48, 64, 24, 40))


def _memory(devices) -> list:
    """bytes_in_use / peak_bytes_in_use per device (None where the backend
    keeps no statistics, as the CPU does). The peak is the process's so far,
    not one phase's."""
    out = []
    for d in devices:
        st = d.memory_stats() or {}
        out.append({"device": d.id, "bytes_in_use": st.get("bytes_in_use"),
                    "peak_bytes_in_use": st.get("peak_bytes_in_use")})
    return out


def _placement(arr) -> dict:
    """Where one array lives: devices holding a piece, distinct pieces."""
    shards = arr.addressable_shards
    return {"devices": len(arr.sharding.device_set),
            "distinct_shards": len({str(s.index) for s in shards}),
            "shard_shape": list(shards[0].data.shape)}


def _program(kernels: dict, collectives: dict, **nbytes) -> dict:
    """One compiled program in a phase's line: its Pallas kernels and
    collectives (obs.cost.program_census) and its memory_analysis bytes."""
    return {"kernels": kernels, "collectives": collectives,
            "kernel_calls": sum(kernels.values()),
            "collective_ops": sum(collectives.values()), **nbytes}


def _all_in_use(memory) -> bool:
    """No device left empty (None = the backend keeps no statistics)."""
    return all(m["bytes_in_use"] is None or m["bytes_in_use"] > 0
               for m in memory)


# ---------------------------------------------------------------------------
# probe
# ---------------------------------------------------------------------------

def phase_probe(cache_dir: str) -> dict:
    import importlib.metadata as md
    import os

    import jax
    import jaxlib

    from paddle_tpu import native

    try:
        libtpu = md.version("libtpu")
    except md.PackageNotFoundError:
        libtpu = None
    had_build = os.path.isdir(native._BUILD_DIR) and bool(
        os.listdir(native._BUILD_DIR))
    so_path = native._build_lib()
    native.load_library()
    d0 = jax.devices()[0]
    return {"ok": True, "platform": d0.platform, "kind": d0.device_kind,
            "count": len(jax.devices()), "jax": jax.__version__,
            "jaxlib": jaxlib.__version__, "libtpu": libtpu,
            "compile_cache_dir": cache_dir,
            "compile_cache_entries": (len(os.listdir(cache_dir))
                                      if os.path.isdir(cache_dir) else 0),
            "native_lib": os.path.basename(so_path),
            "native_lib_built_now": not had_build}


# ---------------------------------------------------------------------------
# train
# ---------------------------------------------------------------------------

def _train_run(sz: dict, seed: int, mesh_shape, sharded: bool,
               steps: int, multi: int) -> dict:
    """Build model + trainer from ``seed`` on a mesh of ``mesh_shape`` over
    ("dp", "sep", "mp"), run ``steps`` train_step calls and one
    ``multi``-step train_steps dispatch on one repeated seeded batch."""
    import dataclasses

    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu.models.llama import LlamaForCausalLM, llama_tp_plan
    from paddle_tpu.obs.cost import program_census
    from paddle_tpu.parallel import ProcessMesh
    from paddle_tpu.parallel.train import ShardedTrainer

    cfg = dataclasses.replace(sz["cfg"], num_hidden_layers=sz["train_layers"])
    B, S = sz["batch"], sz["seq"]
    paddle.seed(seed)
    model = LlamaForCausalLM(cfg)
    opt = paddle.optimizer.AdamW(learning_rate=1e-4,
                                 parameters=model.parameters(),
                                 multi_precision=True)
    # the framework's active mesh only inside the `with` below, so that a
    # caller's process (tier-1) gets its own back
    mesh = ProcessMesh(shape=mesh_shape, dim_names=("dp", "sep", "mp"))
    plan = llama_tp_plan(model, mesh) if sharded else {}
    trainer = ShardedTrainer(model, opt, lambda m, i, l: m.loss(i, l),
                             mesh, plan, amp_dtype="bfloat16")
    rng = np.random.default_rng(seed)
    ids = rng.integers(0, cfg.vocab_size, (B, S), dtype=np.int32)
    labels = rng.integers(0, cfg.vocab_size, (B, S), dtype=np.int32)

    with mesh:
        t0 = time.perf_counter()
        compiled = trainer.compile_lowered(((B, S), np.int32),
                                           ((B, S), np.int32)).compile()
        compile_s = time.perf_counter() - t0
        mem = compiled.memory_analysis()
        program = _program(**program_census(compiled),
                           argument_bytes=mem.argument_size_in_bytes,
                           output_bytes=mem.output_size_in_bytes,
                           temp_bytes=mem.temp_size_in_bytes,
                           alias_bytes=mem.alias_size_in_bytes)
        del compiled
        losses, step_s = [], []
        for _ in range(steps):
            t0 = time.perf_counter()
            losses.append(float(np.asarray(
                trainer.train_step(ids, labels).value)))
            step_s.append(round(time.perf_counter() - t0, 3))
        t0 = time.perf_counter()
        multi_losses = np.asarray(trainer.train_steps(
            np.stack([ids] * multi), np.stack([labels] * multi)).value)
        multi_s = time.perf_counter() - t0
    losses += [float(v) for v in multi_losses]

    names = sorted(trainer.trainable,
                   key=lambda n: -trainer._tensors[n]._value.size)[:3]
    out = {
        "layers": cfg.num_hidden_layers, "hidden": cfg.hidden_size,
        "heads": cfg.num_attention_heads, "ffn": cfg.intermediate_size,
        "vocab": cfg.vocab_size, "batch": B, "seq": S,
        "mesh": dict(zip(mesh.dim_names, mesh.shape)),
        "params": model.num_params(), "losses": losses,
        "compile_s": round(compile_s, 1), "step_wall_s": step_s,
        "multi_step_wall_s": round(multi_s, 1), "program": program,
        "largest_params": {n: _placement(trainer._tensors[n]._value)
                           for n in names},
        "memory": _memory(mesh.jax_mesh.devices.reshape(-1)),
    }
    del trainer, model, opt
    gc.collect()
    return out


def _losses_ok(losses) -> bool:
    import math
    return all(math.isfinite(v) for v in losses) and losses[-1] < losses[0]


def phase_train(sz: dict, seed: int, on_tpu: bool) -> dict:
    out = _train_run(sz, seed, (1, 1, 1), sharded=False, steps=3, multi=2)
    ok = _losses_ok(out["losses"])
    if on_tpu:
        # the compiled step must hold the kernels: flash fwd/bwd (S >=
        # flags.flash_attention_min_seq) and RMSNorm fwd/bwd
        k = out["program"]["kernels"]
        ok = ok and all(k.get(n, 0) > 0 for n in (
            "flash_fwd", "flash_dq", "flash_dkv", "rms_norm_fwd",
            "rms_norm_bwd"))
    return {"ok": ok, **out}


def phase_train_sharded(sz: dict, seed: int) -> dict:
    one = _train_run(sz, seed, (1, 1, 1), sharded=False, steps=3, multi=2)
    four = _train_run(sz, seed, (2, 1, 2), sharded=True, steps=3, multi=2)
    diffs = [abs(a - b) / abs(a)
             for a, b in zip(one["losses"], four["losses"])]
    spread = all(p["devices"] == 4 and p["distinct_shards"] > 1
                 for p in four["largest_params"].values())
    ok = (_losses_ok(one["losses"]) and _losses_ok(four["losses"])
          and max(diffs) <= LOSS_RTOL and spread
          and _all_in_use(four["memory"])
          and four["program"]["collective_ops"] > 0)
    return {"ok": ok, "loss_rel_diff_max": max(diffs),
            "loss_rtol": LOSS_RTOL, "one_device": one, "four_devices": four}


# ---------------------------------------------------------------------------
# serve
# ---------------------------------------------------------------------------

def _build_serving_model(sz: dict, seed: int):
    import dataclasses

    import paddle_tpu as paddle
    from paddle_tpu.models.llama import LlamaForCausalLM

    cfg = dataclasses.replace(sz["cfg"], num_hidden_layers=sz["serve_layers"],
                              dtype="bfloat16")
    paddle.seed(seed)
    model = LlamaForCausalLM(cfg)
    model.to(dtype="bfloat16")
    return cfg, model


def _requests(sz: dict, cfg, seed: int):
    import numpy as np
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, cfg.vocab_size, (n,), dtype=np.int32)
               for n in sz["prompts"]]
    return prompts, list(sz["budgets"])


def _serve_run(dec, sz: dict, prompts, budgets) -> dict:
    """Serve the requests through a default-option ServingEngine over
    ``dec``, submitted across steps so that admission happens mid-flight.
    Returns the tokens, the dispatch accounting, the program census per
    dispatch site (obs cost telemetry) and where the live carry sits."""
    import numpy as np

    import paddle_tpu as paddle
    import paddle_tpu.obs as obs
    from paddle_tpu.serving import ServingEngine

    old = paddle.get_flags(["obs_enabled", "obs_cost_analysis"])
    paddle.set_flags({"obs_enabled": True, "obs_cost_analysis": True})
    obs.clear_cost_cache()
    try:
        t0 = time.perf_counter()
        eng = ServingEngine(dec, num_slots=8, chunk_size=sz["chunk"])
        d0 = dec.dispatch_count
        done = {}
        rids = [eng.submit(p, b) for p, b in zip(prompts[:3], budgets[:3])]
        for _ in range(2):
            done.update(eng.step())
        rids += [eng.submit(p, b) for p, b in zip(prompts[3:5], budgets[3:5])]
        done.update(eng.step())
        rids += [eng.submit(p, b) for p, b in zip(prompts[5:], budgets[5:])]
        done.update(eng.drain())
        wall = time.perf_counter() - t0
        m = eng.metrics()
        carry = _placement(eng.state.kc[0])
        costs = obs.site_costs()
    finally:
        paddle.set_flags(old)
    tokens = [np.asarray(done[r])[0] for r in rids]    # (1, P+G) results
    counted = (m["prefill_dispatches"] + m["chunk_dispatches"]
               + m["step_dispatches"])
    programs = {}
    for site in ("decode.admit_prefill", "decode.chunk"):
        c = costs[site]      # absent = the census did not run: fail loudly
        programs[site] = _program(
            c["kernels"], c["collectives"],
            **{k: c.get(k) for k in ("argument_bytes", "output_bytes",
                                     "temp_bytes", "alias_bytes")})
    return {
        "tokens": tokens,
        "budgets_met": all(len(t) == len(p) + b
                           for t, p, b in zip(tokens, prompts, budgets)),
        "dispatches": dec.dispatch_count - d0,
        "prefill_dispatches": m["prefill_dispatches"],
        "chunk_dispatches": m["chunk_dispatches"],
        "step_dispatches": m["step_dispatches"],
        "accounting_ok": dec.dispatch_count - d0 == counted,
        "serve_wall_s": round(wall, 1), "programs": programs,
        "kv_carry": carry,
    }


def _top1_agreement(dec, seq, prompt_len: int) -> dict:
    """Teacher-forced agreement of ``seq``'s generated tokens with a
    single-sequence forward over ``dec``'s weights: the share of positions
    where the served token is the reference's top-1, strictly, and counting
    as agreement a served token whose reference logit lies within
    TIE_ULPS bf16 ulps of the reference maximum (random weights give
    near-flat logits, and a reassociated bf16 matmul flips such ties)."""
    import numpy as np

    import jax
    import jax.numpy as jnp
    from paddle_tpu.inference.generate import _forward_cached

    ids = jnp.asarray(seq[None, :-1], jnp.int32)
    kc, vc = dec._empty_cache(1)
    logits, _, _ = jax.jit(
        lambda p, i, k, v: _forward_cached(p, dec.cfg, i, k, v, 0,
                                           dec.max_len, return_all=True)
    )(dec.params, ids, kc, vc)
    logits = np.asarray(logits[0, prompt_len - 1:], np.float32)
    served = np.asarray(seq[prompt_len:])
    top = logits.max(-1)
    got = logits[np.arange(len(served)), served]
    gap_ulps = (top - got) / (2.0 ** -8 * np.abs(top))
    return {"strict": float((gap_ulps == 0).mean()),
            "tie_aware": float((gap_ulps <= TIE_ULPS).mean()),
            "worst_gap_ulps": float(gap_ulps.max())}


def _agreement_verdict(agreements) -> dict:
    """The weakest rung that every compared request reached."""
    strict = min(a["strict"] for a in agreements)
    tie = min(a["tie_aware"] for a in agreements)
    return {"ok": tie >= TOP1_GATE,
            "parity": ("teacher_forced_top1" if strict >= TOP1_GATE
                       else "teacher_forced_top1_within_bf16_ties"),
            "top1_agreement_min": strict,
            "top1_agreement_within_ties_min": tie,
            "worst_gap_ulps": max(a["worst_gap_ulps"] for a in agreements),
            "top1_gate": TOP1_GATE, "tie_ulps": TIE_ULPS}


def _parity(dec, got, prompt, budget: int) -> dict:
    """One served request against the per-token reference rung
    (decode_fallback) of the same decoder on the same device: token-exact
    where that holds, else teacher-forced top-1 agreement."""
    import numpy as np

    import paddle_tpu as paddle

    old = paddle.get_flags(["decode_fallback"])
    paddle.set_flags({"decode_fallback": True})
    try:
        ref = np.asarray(dec.generate(prompt[None], max_new_tokens=budget))[0]
    finally:
        paddle.set_flags(old)
    if np.array_equal(ref, got):
        return {"ok": True, "parity": "token_exact"}
    return {**_agreement_verdict([_top1_agreement(dec, got, len(prompt))]),
            "first_divergence_at_token":
                int(np.argmax(ref != got)) - len(prompt)}


def phase_serve(sz: dict, seed: int) -> dict:
    import jax
    from paddle_tpu.inference.generate import LlamaDecoder

    cfg, model = _build_serving_model(sz, seed)
    t0 = time.perf_counter()
    dec = LlamaDecoder(model, max_len=sz["max_len"])
    del model        # the decoder snapshots (fused) weights of its own
    gc.collect()
    build_s = time.perf_counter() - t0
    prompts, budgets = _requests(sz, cfg, seed)
    run = _serve_run(dec, sz, prompts, budgets)
    tokens = run.pop("tokens")
    # the request whose prompt is exactly one admission bucket long, so the
    # engine's prefill and the reference's see the same shapes
    j = sz["prompts"].index(64)
    parity = _parity(dec, tokens[j], prompts[j], budgets[j])
    ok = run["budgets_met"] and run["accounting_ok"] and parity.pop("ok")
    return {"ok": ok, "layers": cfg.num_hidden_layers,
            "hidden": cfg.hidden_size, "heads": cfg.num_attention_heads,
            "kv_heads": cfg.num_key_value_heads,
            "ffn": cfg.intermediate_size, "vocab": cfg.vocab_size,
            "dtype": cfg.dtype, "max_len": sz["max_len"], "num_slots": 8,
            "chunk_size": sz["chunk"], "prompt_lens": list(sz["prompts"]),
            "budgets": budgets, "decoder_build_s": round(build_s, 1),
            **run, **parity, "memory": _memory(jax.devices()[:1])}


def phase_serve_sharded(sz: dict, seed: int) -> dict:
    import numpy as np

    import jax
    from paddle_tpu.inference.generate import LlamaDecoder

    cfg, model = _build_serving_model(sz, seed)
    prompts, budgets = _requests(sz, cfg, seed)
    dec1 = LlamaDecoder(model, max_len=sz["max_len"])
    one = _serve_run(dec1, sz, prompts, budgets)
    dec4 = LlamaDecoder(model, max_len=sz["max_len"], mesh="tp:4")
    four = _serve_run(dec4, sz, prompts, budgets)
    t1, t4 = one.pop("tokens"), four.pop("tokens")
    exact = [bool(np.array_equal(a, b)) for a, b in zip(t1, t4)]
    if all(exact):
        verdict = {"ok": True, "parity": "token_exact"}
    else:
        # the sharded engine's tokens, teacher-forced through the unsharded
        # decoder: sharded matmuls reassociate, near-ties may flip
        verdict = _agreement_verdict([_top1_agreement(dec1, t, len(p))
                                      for t, p in zip(t4, prompts)])
    agree_ok = verdict.pop("ok")
    big = sorted((k for k, v in dec4.params.items() if hasattr(v, "sharding")),
                 key=lambda k: -dec4.params[k].size)[:3]
    params = {k: _placement(dec4.params[k]) for k in big}
    memory = _memory(jax.devices()[:4])
    spread = (four["kv_carry"]["devices"] == 4
              and all(p["devices"] == 4 for p in params.values())
              and any(p["distinct_shards"] > 1 for p in params.values()))
    collectives = all(p["collective_ops"] > 0
                      for p in four["programs"].values())
    ok = (agree_ok and spread and _all_in_use(memory) and collectives
          and all(r["budgets_met"] and r["accounting_ok"]
                  for r in (one, four)))
    return {"ok": ok, "layers": cfg.num_hidden_layers,
            "hidden": cfg.hidden_size, "heads": cfg.num_attention_heads,
            "ffn": cfg.intermediate_size, "vocab": cfg.vocab_size,
            "mesh": "tp:4", **verdict, "token_exact_requests": exact,
            "largest_params": params,
            "one_device": one, "four_devices": four, "memory": memory}


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------

def _run_phase(name: str, fn, *args) -> bool:
    t0 = time.perf_counter()
    try:
        out = fn(*args)
    except Exception as e:    # the phase boundary: report it, fail the run
        traceback.print_exc(file=sys.stderr)
        out = {"ok": False, "error": f"{type(e).__name__}: {str(e)[:400]}"}
    _emit({"phase": name, **out,
           "phase_wall_s": round(time.perf_counter() - t0, 1)})
    return bool(out["ok"])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    from paddle_tpu.runtime.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    import jax

    devices = jax.devices()
    on_tpu = devices[0].platform == "tpu"
    if not on_tpu and not args.rehearse:
        print(f"chip_smoke: no TPU (JAX found {devices[0].platform}); "
              f"--rehearse runs the tiny configuration elsewhere",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, JAX found {len(devices)}", file=sys.stderr)
        return 2

    sz = _sizes(args.rehearse, args.chips)
    ok = _run_phase("probe", phase_probe, cache_dir)
    if args.chips == 4:
        ok &= _run_phase("train_sharded", phase_train_sharded, sz, args.seed)
        ok &= _run_phase("serve_sharded", phase_serve_sharded, sz, args.seed)
    else:
        ok &= _run_phase("train", phase_train, sz, args.seed, on_tpu)
        ok &= _run_phase("serve", phase_serve, sz, args.seed)
    _emit({"ok": bool(ok),
           "device": {"platform": devices[0].platform,
                      "kind": devices[0].device_kind,
                      "count": len(devices)}})
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
