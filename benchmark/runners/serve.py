"""Serving runner: seeded model -> LlamaDecoder -> ServingEngine, driven by
the cell's traffic source from one thread, timed by the benchmark's own
clock through ``on_tokens``.

The recipe is the one ``chip_smoke.py`` proved on the chip (copied, not
imported). The loop is the benchmark's own server loop: submit what is due,
``engine.step()`` while anything is in flight, sleep to the next arrival
otherwise. A request due while a chunk runs is submitted when the chunk
returns; its latency is still timed from the instant it was due.
"""

from __future__ import annotations

import gc
import time

import numpy as np

from benchmark.harness import common, model as mdl, resolve
from benchmark.harness.stats import median, percentile
from benchmark.reference import llama_block as ref

# --- correctness gates, outside the window --------------------------------
# Logits, decoder's cached path (prefill, then decode steps through the
# cache) against the float32 reference's full forward, per position:
# max|sys - ref| over the vocabulary / std of the reference's logits there.
# The section states bf16 weights and activations: every matmul output is
# rounded to 8 bits of mantissa (relative 2**-9 on average) and the error
# grows with depth. Measured on the chip (my chip runs, PR 24): 0.042-0.046
# of a logit's standard deviation at 12 layers of Mistral widths. The gate
# is 0.1: a path in float32 reads ~1e-5 (benchmark/tests, on the CPU), an
# fp8 cache or activations (4 bits of mantissa fewer, 16 times the error)
# reads several tenths, and a wrong mask, RoPE or cache offset reads O(1).
LOGITS_TOL = 0.1
# Tokens: the engine's greedy token must be the reference's top-1, or its
# reference logit must lie within TIE_ULPS bf16 ulps (2**-8 relative) of the
# reference maximum: random weights give near-flat logits and a
# reassociated bf16 matmul flips near-ties (PR 21 found strict equality
# does not hold). Every position must pass.
TIE_ULPS = 4
CHECK_PROMPTS = (64, 100)      # the first is exactly a bucket long
CHECK_BUDGET = 24              # two chunks at chunk_size 16
CHECK_STEPS = 8                # decode steps of the logits comparison


def _check_against_reference(dec, arch, layers, seqs, prompt_lens) -> dict:
    """Both gates over ``seqs`` (prompt + the engine's tokens)."""
    import jax.numpy as jnp
    lw = mdl.layer_weights_from_decoder(dec.params, arch)
    worst_logit, worst_gap, strict = 0.0, 0.0, []
    for seq, P in zip(seqs, prompt_lens):
        ids = np.asarray(seq[:-1], np.int32)[None]
        positions = np.arange(P - 1, ids.shape[1])
        want = np.asarray(ref.logits(
            ids, arch, layers, dec.params["model.embed_tokens.weight"], lw,
            dec.params["model.norm.weight"], dec.params["lm_head.weight"],
            positions=positions)[0], np.float32)
        # tokens: the engine's against the reference's top-1
        served = np.asarray(seq[P:])
        top = want.max(-1)
        got = want[np.arange(len(served)), served]
        gap = (top - got) / (2.0 ** -8 * np.abs(top))
        worst_gap = max(worst_gap, float(gap.max()))
        strict.append(float((gap == 0).mean()))
        # logits: prefill then CHECK_STEPS decode steps through the cache
        kc, vc = dec._empty_cache(1)
        lg, kc, vc = dec._prefill(dec.params, jnp.asarray(ids[:, :P]), kc,
                                  vc)
        have = [np.asarray(lg[0], np.float32)]
        for t in range(CHECK_STEPS):
            lg, kc, vc = dec._step(dec.params,
                                   jnp.asarray(ids[:, P + t:P + t + 1]),
                                   kc, vc, jnp.int32(P + t))
            have.append(np.asarray(lg[0], np.float32))
        have = np.stack(have)
        w = want[:len(have)]
        err = np.abs(have - w).max(-1) / w.std(-1)
        worst_logit = max(worst_logit, float(err.max()))
        del kc, vc, lg
    return {"logits_err_max": worst_logit, "logits_tol": LOGITS_TOL,
            "token_gap_ulps_max": worst_gap, "tie_ulps": TIE_ULPS,
            "strict_top1_min": min(strict),
            "ok": worst_logit <= LOGITS_TOL and worst_gap <= TIE_ULPS}


def reduce_requests(recs: dict, tok_times: list, win0: float,
                    win1: float) -> dict:
    """From the per-request records to the samples the metrics are taken
    over. The sample is the requests DUE inside the window. A request that
    errored, was shed, outlived the drain or returned another number of
    tokens than its budget is failed and misses every latency. TTFT runs
    from the instant the request was due (not from its submit) to its first
    ``on_tokens`` callback; TPOT is (last callback - first callback) over
    the tokens delivered after the first callback, and a request whose
    tokens all came in one callback is left out of that sample."""
    def whole(r):        # exactly its budget, in callbacks and in the result
        return (r["n"] == r["budget"]
                and r.get("total_len") == r["prompt_len"] + r["budget"])
    window = [r for r in recs.values() if win0 <= r["due_t"] < win1]
    for r in window:
        r["in_window"] = True
    ok = [r for r in window
          if not r["error"] and r["final_t"] is not None and whole(r)]
    return {
        "window": window, "ok": ok, "failed": len(window) - len(ok),
        # (b) every finished request has exactly its budget of tokens
        "budgets_ok": all(whole(r) for r in recs.values()
                          if r["final_t"] is not None and not r["error"]),
        "tokens_in_window": sum(n for t, n in tok_times if win0 <= t < win1),
        "ttft_ms": [(r["first_t"] - r["due_t"]) * 1e3 for r in ok],
        "tpot_ms": [(r["last_t"] - r["first_t"]) / (r["n"] - r["first_n"])
                    * 1e3 for r in ok if r["n"] > r["first_n"]],
        "late_ms": [(r["submit_t"] - r["due_t"]) * 1e3 for r in window]}


def run(cell: dict, args, devices, t_start: float, watch) -> dict:
    import jax

    import paddle_tpu as paddle
    from paddle_tpu.inference.generate import LlamaDecoder
    from paddle_tpu.models.llama import LlamaForCausalLM
    from paddle_tpu.serving import ServingEngine

    arch = mdl.arch_of(cell["config_file"])
    section = dict(cell["config_file"]["sections"][cell["section"]])
    mix = cell["mix"]
    if args.rehearse:
        arch, section, mix = mdl.rehearsal(arch, section, mix)
    cfg = mdl.llama_config(arch, section)
    layers, chunk = cfg.num_hidden_layers, int(section["chunk_size"])
    slots, max_len = int(section["num_slots"]), int(section["max_len"])

    # -- build: the recipe chip_smoke.py proved ----------------------------
    t0 = time.perf_counter()
    paddle.seed(args.seed)
    model = LlamaForCausalLM(cfg)
    model.to(dtype=section["dtype"])
    dec = LlamaDecoder(model, max_len=max_len, mesh=section.get("mesh"))
    del model            # the decoder snapshots (fused) weights of its own
    gc.collect()
    eng = ServingEngine(dec, num_slots=slots, chunk_size=chunk)
    build_s = time.perf_counter() - t0

    # -- warm every shape the window will use ------------------------------
    t0 = time.perf_counter()
    rng = np.random.default_rng([int(args.seed), 9])
    lo, hi = int(mix["prompt_len"]["min"]), int(mix["prompt_len"]["max"])
    done = {}
    # one request per admission bucket the mix's prompts fall into, by the
    # engine's own bucketing
    for b in sorted({eng.scheduler.bucket(n) for n in range(lo, hi + 1)}):
        eng.submit(rng.integers(0, cfg.vocab_size, (min(b, hi),),
                                dtype=np.int32), chunk + 1)
    done.update(eng.drain())
    lens = [min(p, hi) for p in CHECK_PROMPTS] if not args.rehearse \
        else [16, 25]
    prompts = [rng.integers(0, cfg.vocab_size, (n,), dtype=np.int32)
               for n in lens]
    rids = [eng.submit(p, CHECK_BUDGET) for p in prompts]
    done.update(eng.drain())
    warm_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    seqs = [np.asarray(done[r])[0] for r in rids]
    check = _check_against_reference(dec, arch, layers, seqs, lens)
    check_s = time.perf_counter() - t0
    common.say("reference_check", check)
    gc.collect()

    # -- the cell's traffic: warm stretch, window, drain -------------------
    warm_seconds = float(mix.get("warm_seconds", 5.0))
    drain_seconds = float(mix.get("drain_seconds", 20.0))
    gen = resolve.load_module("traffic", mix["generator"], args.root)
    source = gen.Source(mix, args.seed, cfg.vocab_size, slots,
                        warm_seconds + args.seconds)
    trace_len = min(5.0, args.seconds / 2.0)
    prof = common.Profiler(cell["name"], args.rehearse) if args.trace else None
    recs, tok_times = {}, []
    inflight = 0

    def on_tokens(rid, new, final):
        now = time.perf_counter()
        r = recs[rid]
        n = int(len(new))
        if n:
            if r["first_t"] is None:
                r["first_t"], r["first_n"] = now, n
            r["last_t"] = now
            r["n"] += n
            tok_times.append((now, n))
        if final:
            r["final_t"] = now

    t_origin = time.perf_counter()
    win0 = t_origin + warm_seconds
    win1 = win0 + args.seconds
    m_before = m_after = None
    trace = None
    setup_s = None
    while True:
        now = time.perf_counter()
        if m_before is None and now >= win0:
            m_before = eng.metrics()
            setup_s = now - t_start
            win0 = now            # the window opens here, on this clock
            win1 = win0 + args.seconds
        if m_before is not None and m_after is None and now >= win1:
            m_after = eng.metrics()
        # the traced stretch is the end of the window; the profiler itself
        # is stopped after the drain (see common.Profiler)
        if prof is not None and not prof.done:
            if prof.on and now >= win1:
                prof.close_window()
            elif not prof.on and win1 - trace_len <= now < win1:
                prof.start()
                now = time.perf_counter()
        submitting = now < win1
        if submitting:
            due = source.due(now - t_origin)
            if due:
                with jax.profiler.TraceAnnotation("bench.submit"):
                    for q in due:
                        t_sub = time.perf_counter()
                        rid = eng.submit(q["prompt"], q["max_new_tokens"],
                                         on_tokens=on_tokens)
                        recs[rid] = {
                            "due_t": t_origin + q["due_s"], "submit_t": t_sub,
                            "budget": q["max_new_tokens"],
                            "prompt_len": int(len(q["prompt"])),
                            "first_t": None, "first_n": 0, "last_t": None,
                            "final_t": None, "n": 0, "serving": None,
                            "error": None}
                        inflight += 1
        if inflight:
            with jax.profiler.TraceAnnotation("bench.engine_step"):
                finished = eng.step()
            t_fin = time.perf_counter()
            for rid, res in finished:
                inflight -= 1
                r = recs[rid]
                if isinstance(res, BaseException):
                    r["error"] = repr(res)[:200]
                else:
                    r["serving"] = res.resilience["serving"]
                    r["total_len"] = int(np.asarray(res).shape[-1])
                source.finished(t_fin - t_origin)
        elif not submitting:
            break
        else:
            nd = source.next_due_s()
            until = win1 if nd is None else min(win1, t_origin + nd)
            with jax.profiler.TraceAnnotation("bench.sleep"):
                time.sleep(max(0.0, until - time.perf_counter()))
        if not submitting and time.perf_counter() > win1 + drain_seconds:
            break
    if prof is not None:
        prof.stop()
        trace = prof.load(keep=args.keep_trace)
    if m_after is None:
        m_after = eng.metrics()
    t_end = time.perf_counter()

    # -- reduce ---------------------------------------------------------------
    red = reduce_requests(recs, tok_times, win0, win1)
    window, ok, failed = red["window"], red["ok"], red["failed"]
    ttft, tpot, late = red["ttft_ms"], red["tpot_ms"], red["late_ms"]
    budgets_ok, tokens_in_window = red["budgets_ok"], red["tokens_in_window"]
    compiles = watch.inside(win0, win1)
    e2e = {"out_tok_s": tokens_in_window / args.seconds, "setup_s": setup_s}
    if ttft:
        e2e["ttft_p95_ms"] = percentile(ttft, 95)
    if tpot:
        e2e["tpot_p95_ms"] = percentile(tpot, 95)
    mid = (win0 + win1) / 2

    def backlog_at(t):      # requests due and not yet holding a first token
        return sum(1 for r in recs.values() if r["due_t"] <= t and
                   (r["first_t"] is None or r["first_t"] > t))
    common.say("serve", {
        "layers": layers, "slots": slots, "chunk_size": chunk,
        "max_len": max_len, "build_s": build_s, "warm_shapes_s": warm_s,
        "reference_check_s": check_s, "warm_traffic_s": warm_seconds,
        "drain_s": t_end - win1, "window_s": args.seconds,
        "requests_due_in_window": len(window),
        "requests_submitted_in_all": len(recs),
        "ttft_ms": {"median": median(ttft) if ttft else None,
                    "p95": e2e.get("ttft_p95_ms"), "samples": len(ttft)},
        "tpot_ms": {"median": median(tpot) if tpot else None,
                    "p95": e2e.get("tpot_p95_ms"), "samples": len(tpot),
                    "left_out_single_callback": len(ok) - len(tpot)},
        "generator_lateness_ms": {
            "median": median(late) if late else None,
            "p95": percentile(late, 95) if late else None,
            "max": max(late) if late else None},
        "waiting_for_first_token": {"at_middle": backlog_at(mid),
                                    "at_end": backlog_at(win1)},
        "tokens_in_window": tokens_in_window,
        "compilations_in_window": compiles,
        "dispatches_in_window": {
            k: m_after[k] - m_before[k]
            for k in ("prefill_dispatches", "chunk_dispatches",
                      "step_dispatches")},
        "admission_ring": m_after["admission_ring"],
        "queue_depth_peak": m_after["queue_depth_peak"],
    })
    ctx = {"trace": trace, "requests": window, "section": section,
           "arch": arch, "mix": mix,
           "engine": {"before": m_before, "after": m_after}}
    return {"correct": bool(check["ok"] and budgets_ok and compiles == 0),
            "attempted": len(window), "failed": failed, "e2e": e2e,
            "ctx": ctx}
