"""Serving runner for any model family: the loop of ``runners/serve.py``
with its three model-bound steps — configuration file -> program config,
model class, reference check — taken from the adapter the configuration
file names (``"adapter": "<name>"`` -> ``adapters/<name>.py``, see
``adapters/README.md``). The next architecture adds an adapter, not a
runner.

The gates are the serving runner's, on the decoder that is then timed:
logits of prefill + ``CHECK_STEPS`` cached decode steps against the
reference's full forward, per position, over the reference's standard
deviation there; the engine's greedy tokens within so many bf16 ulps of the
reference's maximum. Both tolerances are the adapter's (``LOGITS_TOL``,
``TIE_ULPS``): how far bf16 rounding carries depends on how many blocks a
token passes, so each family states its own, measured. Everything
``runners/serve.py`` exposes (``reduce_requests``, the prompts, budget and
steps of the check) is imported from it; the loop body, which it does not
expose, is a copy (PERF.md section 7: a
``benchmark`` PR moves the Llama cells here and deletes the original).
"""

from __future__ import annotations

import gc
import time

import numpy as np

from benchmark.harness import common, model as mdl, resolve
from benchmark.harness.stats import median, percentile
from benchmark.runners.serve import (CHECK_BUDGET, CHECK_PROMPTS,
                                     CHECK_STEPS, reduce_requests)


def _check_against_reference(dec, adapter, arch, layers, seqs,
                             prompt_lens) -> dict:
    """Both gates over ``seqs`` (prompt + the engine's tokens)."""
    import jax.numpy as jnp
    worst_logit, worst_gap, strict = 0.0, 0.0, []
    for seq, P in zip(seqs, prompt_lens):
        ids = np.asarray(seq[:-1], np.int32)[None]
        want = adapter.reference_logits(
            dec.params, arch, layers, ids, np.arange(P - 1, ids.shape[1]))
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
    tol, ties = float(adapter.LOGITS_TOL), float(adapter.TIE_ULPS)
    return {"logits_err_max": worst_logit, "logits_tol": tol,
            "token_gap_ulps_max": worst_gap, "tie_ulps": ties,
            "strict_top1_min": min(strict),
            "ok": worst_logit <= tol and worst_gap <= ties}


def run(cell: dict, args, devices, t_start: float, watch) -> dict:
    import jax

    import paddle_tpu as paddle
    from paddle_tpu.inference.generate import LlamaDecoder
    from paddle_tpu.serving import ServingEngine

    adapter = resolve.load_module("adapters", cell["config_file"]["adapter"],
                                  args.root)
    arch = adapter.arch_of(cell["config_file"])
    section = dict(cell["config_file"]["sections"][cell["section"]])
    mix = cell["mix"]
    if args.rehearse:
        arch, section, mix = mdl.rehearsal(arch, section, mix)
    cfg = adapter.program_config(arch, section)
    layers, chunk = cfg.num_hidden_layers, int(section["chunk_size"])
    slots, max_len = int(section["num_slots"]), int(section["max_len"])

    # -- build: the recipe chip_smoke.py proved ----------------------------
    t0 = time.perf_counter()
    paddle.seed(args.seed)
    model = adapter.build_model(cfg)
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
    check = _check_against_reference(dec, adapter, arch, layers, seqs, lens)
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
    prof_stop_s = 0.0
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
        # the traced stretch is the end of the window, and the profiler is
        # stopped with it, before the drain: collecting the trace costs time
        # by the events it holds, and a chunk program of 48 blocks writes so
        # many that with the drain's inside it the stop took 170 s (my chip
        # runs, PR 28). The stop stalls this thread with requests still in
        # flight, so the drain's deadline moves by the stall, and a traced
        # run's tails are longer for the requests it caught (a traced run is
        # read for its per-layer metrics, which end at win1).
        if prof is not None and not prof.done:
            if prof.on and now >= win1:
                prof.stop()
                prof_stop_s = time.perf_counter() - now
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
        if not submitting and (time.perf_counter()
                               > win1 + prof_stop_s + drain_seconds):
            break
    if prof is not None:
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
        "drain_s": t_end - win1, "profiler_stop_s": prof_stop_s,
        "window_s": args.seconds,
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
        # the cache as the engine built it (None from a program without
        # the gauges), and the block applications that makes of the
        # window's chunks
        "cache": {k: m_after.get(k)
                  for k in ("cache_layers", "cache_bytes_per_position")},
        "block_runs_in_window": (
            (m_after["chunk_dispatches"] - m_before["chunk_dispatches"])
            * chunk * m_after["cache_layers"]
            if "cache_layers" in m_after else None),
    })
    ctx = {"trace": trace, "requests": window, "section": section,
           "arch": arch, "mix": mix,
           "engine": {"before": m_before, "after": m_after}}
    return {"correct": bool(check["ok"] and budgets_ok and compiles == 0),
            "attempted": len(window), "failed": failed, "e2e": e2e,
            "ctx": ctx}
