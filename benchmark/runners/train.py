"""Training runner: seeded model -> ShardedTrainer (f32 master weights,
AdamW ``multi_precision``, bf16 AMP) on the section's mesh, one
``train_step`` per batch of the cell's pool, the loss read back every step:
the path a user's loop takes. The recipe is ``chip_smoke.py``'s, copied.
"""

from __future__ import annotations

import math
import time

import numpy as np

from benchmark import flops as fl
from benchmark.harness import common, model as mdl, resolve
from benchmark.reference import llama_block as ref

# Step-0 loss against the float32 reference's cross-entropy on the same
# batch and weights, relative. The section states f32 master weights with
# bf16 compute (AMP): the forward's matmuls round to 8 bits of mantissa, and
# the loss is a mean over 4096 tokens, so the errors average out. Measured
# on the chip (my chip runs, PR 24, 15 runs): 0 to 3e-6. The gate is 1e-4:
# a wrong mask, RoPE or label shift moves the loss by percents; with random
# weights the loss sits near ln(vocab) + half the logits' variance whatever
# the precision, so this gate cannot tell bf16 from f32 - the gradient
# comparison at tiny width in benchmark/tests does that part.
LOSS_RTOL = 1e-4


def run(cell: dict, args, devices, t_start: float, watch) -> dict:
    import jax

    import paddle_tpu as paddle
    from paddle_tpu.models.llama import LlamaForCausalLM, llama_tp_plan
    from paddle_tpu.parallel import ProcessMesh
    from paddle_tpu.parallel.train import ShardedTrainer

    arch = mdl.arch_of(cell["config_file"])
    section = dict(cell["config_file"]["sections"][cell["section"]])
    mix = cell["mix"]
    if args.rehearse:
        arch, section, mix = mdl.rehearsal(arch, section, mix)
    cfg = mdl.llama_config(arch, section)
    B, S = int(mix["batch"]), int(mix["seq"])
    gen = resolve.load_module("traffic", mix["generator"], args.root)
    batches = gen.pool(mix, args.seed, cfg.vocab_size)

    t0 = time.perf_counter()
    paddle.seed(args.seed)
    model = LlamaForCausalLM(cfg)
    # the reference's loss on batch 0 and the initial weights, before the
    # optimizer's state takes its memory
    sd = mdl.state_arrays(model)
    want = ref.cross_entropy(
        batches[0][0], batches[0][1], arch, cfg.num_hidden_layers,
        sd["model.embed_tokens.weight"], ref.layer_weights_by_name(sd),
        sd["model.norm.weight"], sd["lm_head.weight"])
    del sd
    check_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    opt = paddle.optimizer.AdamW(
        learning_rate=float(section["learning_rate"]),
        parameters=model.parameters(), multi_precision=True)
    shape = tuple(section["mesh"])
    mesh = ProcessMesh(shape=shape, dim_names=("dp", "sep", "mp"))
    plan = llama_tp_plan(model, mesh) if math.prod(shape) > 1 else {}
    trainer = ShardedTrainer(model, opt, lambda m, i, l: m.loss(i, l), mesh,
                             plan, amp_dtype=section.get("amp_dtype"))
    n_params = model.num_params()
    build_s = time.perf_counter() - t0

    warm_steps = int(mix.get("warm_steps", 5))
    trace_steps = int(mix.get("trace_steps", 10))
    prof = common.Profiler(cell["name"], args.rehearse) if args.trace else None
    losses, step_ends = [], []
    trace = None
    with mesh:
        def step(i):
            ids, labels = batches[i % len(batches)]
            with jax.profiler.TraceAnnotation("bench.train_step"):
                out = trainer.train_step(ids, labels)
            with jax.profiler.TraceAnnotation("bench.loss_readback"):
                return float(np.asarray(out.value))

        t0 = time.perf_counter()
        first = step(0)              # compiles; the step-0 loss
        for i in range(1, warm_steps):
            step(i)
        warm_s = time.perf_counter() - t0
        win0 = time.perf_counter()
        setup_s = win0 - t_start
        win1 = win0 + args.seconds
        i = warm_steps
        tr_at = None
        while True:
            now = time.perf_counter()
            if now >= win1:
                break
            if prof is not None and not prof.done:
                if not prof.on and now >= win0 + args.seconds / 3.0:
                    prof.start()
                    tr_at = len(losses)
                elif prof.on and len(losses) - tr_at >= trace_steps:
                    prof.stop()      # stalls the loop; a traced run
                    #                  reports no rate
            losses.append(step(i))
            step_ends.append(time.perf_counter())
            i += 1
        if prof is not None:
            if prof.on:
                prof.stop()
            trace = prof.load(keep=args.keep_trace)

    done = sum(1 for t in step_ends if t < win1)
    tok_s = done * B * S / args.seconds
    compiles = watch.inside(win0, win1)
    rel = abs(first - want) / abs(want)
    finite = all(math.isfinite(v) for v in losses)
    per_tok = fl.model_train_flops_per_token(
        layers=cfg.num_hidden_layers, hidden=arch["hidden_size"],
        ffn=arch["intermediate_size"], heads=arch["num_attention_heads"],
        kv_heads=arch["num_key_value_heads"], head_dim=arch["head_dim"],
        vocab=arch["vocab_size"], seq=S)
    common.say("train", {
        "layers": cfg.num_hidden_layers, "params": n_params, "batch": B,
        "seq": S, "mesh": list(shape), "build_s": build_s,
        "reference_check_s": check_s, "warm_steps": warm_steps,
        "warm_s": warm_s, "steps_in_window": done,
        "step_ms_mean": args.seconds / done * 1e3 if done else None,
        "loss_step0": first, "loss_reference": want, "loss_rel_diff": rel,
        "loss_rtol": LOSS_RTOL, "loss_last": losses[-1] if losses else None,
        "compilations_in_window": compiles,
        "model_flops_per_token": per_tok,
    })
    ctx = {"trace": trace, "section": {**section,
                                       "num_hidden_layers":
                                           cfg.num_hidden_layers},
           "arch": arch, "mix": mix,
           "mfu_inputs": {"flops_per_token": per_tok,
                          "chips": len(devices)}}
    return {"correct": bool(rel <= LOSS_RTOL and finite and compiles == 0),
            "attempted": len(losses), "failed": 0,
            "e2e": {"train_tok_s": tok_s, "setup_s": setup_s}, "ctx": ctx}
