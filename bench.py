"""Benchmark driver: five training configs plus the decode and serving
benches.

Default (driver contract): flagship Llama train-step throughput on one chip,
printing ONE JSON line {"metric", "value", "unit", "vs_baseline"}.

  python bench.py                     # llama (driver default)
  python bench.py --config resnet50   # ResNet-50 images/sec
  python bench.py --config bert       # BERT-base MLM tokens/sec
  python bench.py --config unet       # SD2.1-style UNet step time
  python bench.py --config ernie      # ERNIE-style semi-auto DistTensor LM
  python bench.py --all               # all five (llama line printed last)
  python bench.py --profile           # + per-component time breakdown to
                                      #   bench_profile.json

Protocol: best mean-over-steps across 3 trials of N steady-state steps
after compilation warmup, K steps per dispatch;
MFU = model FLOPs / (step time * bf16 peak of the device kind, from
paddle_tpu.obs.cost.DEVICE_PEAK_FLOPS), reported on stderr and only on a
TPU. vs_baseline is the ratio against BASELINE.json's recorded value for
the metric when present, else null.

The backend is whatever JAX initializes: nothing here switches platform.
A backend that fails to initialize, a failed device trace and a failed
config all end in a structured failure line and a non-zero exit code.

Reference capability analog: python/paddle/profiler/timer.py (Benchmark ips
reporting) + tools/ci_op_benchmark.sh regression gating.
"""

from __future__ import annotations

import argparse
import json
import sys
import time


def _mfu_pct(flops_per_sec: float, n_dev: int = 1) -> str:
    """MFU as text for the stderr summaries: a share of the device kind's
    bf16 peak (the one table in obs.cost), "not measured" off the TPU."""
    from paddle_tpu.obs.cost import device_peak_flops
    peak = device_peak_flops()
    if peak is None:
        return "not measured"
    return f"{flops_per_sec / (peak * n_dev) * 100:.1f}%"


def _stacked_batch(trainer, arrays, steps: int):
    """Tile the batch K times and pre-place it with the trainer's stacked
    data sharding (protocol: input H2D excluded from timing)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    sh = NamedSharding(trainer.mesh.jax_mesh, P(None, *trainer.data_spec))
    return [jax.device_put(jnp.stack([jnp.asarray(a)] * steps), sh)
            for a in arrays]


def _measure_steps(trainer, arrays, steps: int, trials: int = 3) -> float:
    """Per-step time with K steps per dispatch (ShardedTrainer.train_steps):
    one executable runs `steps` scan iterations and the host fetches the
    last loss, so one dispatch and one fetch are spread over K steps."""
    import numpy as np

    stacked = _stacked_batch(trainer, arrays, steps)
    losses = trainer.train_steps(*stacked)  # compile + warm
    float(np.asarray(losses.value)[-1])
    best = float("inf")
    for _ in range(trials):
        t0 = time.perf_counter()
        losses = trainer.train_steps(*stacked)
        float(np.asarray(losses.value)[-1])
        best = min(best, (time.perf_counter() - t0) / steps)
    return best


def _trace_profile(trainer, arrays, steps: int, config_name: str) -> dict:
    """Device-trace a K-step dispatch and write the per-kernel-family time
    breakdown to bench_profile_{config}.json. A trace that cannot be
    taken or read raises: the caller's guard turns that into a failure
    record and a non-zero exit."""
    import collections
    import glob
    import gzip
    import re
    import shutil
    import tempfile

    import numpy as np

    import jax

    stacked = _stacked_batch(trainer, arrays, steps)
    losses = trainer.train_steps(*stacked)
    float(np.asarray(losses.value)[-1])
    tdir = tempfile.mkdtemp(prefix="bench_trace_")
    fams = collections.Counter()
    counts = collections.Counter()
    total = 0.0
    try:
        with jax.profiler.trace(tdir):
            losses = trainer.train_steps(*stacked)
            float(np.asarray(losses.value)[-1])
        found = glob.glob(f"{tdir}/plugins/profile/*/*.trace.json.gz")
        if not found:
            raise RuntimeError("the profiler wrote no *.trace.json.gz")
        with gzip.open(found[0]) as fh:
            data = json.load(fh)
        events = data["traceEvents"]
        pids = {e["pid"]: e["args"].get("name", "") for e in events
                if e.get("ph") == "M" and e.get("name") == "process_name"}
        dev = {p for p, n in pids.items() if "TPU" in n}
        if not dev:
            raise RuntimeError("no TPU device lane in trace (CPU run?)")
        for e in events:
            if e.get("ph") == "X" and e.get("pid") in dev and \
                    not e["name"].startswith(("jit_", "while", "0", "body")):
                fam = re.sub(r"[.\d]+$", "", e["name"]) or e["name"]
                ms = e.get("dur", 0) / 1e3 / steps
                fams[fam] += ms
                counts[fam] += 1
                total += ms
    finally:
        shutil.rmtree(tdir, ignore_errors=True)
    rows = {"config": config_name, "steps": steps,
            "device_ms_per_step": round(total, 3),
            "families_ms_per_step": {
                k: round(v, 4) for k, v in fams.most_common(20)},
            "families_count_per_step": {
                k: round(counts[k] / steps, 1)
                for k, _ in fams.most_common(20)}}
    path = f"bench_profile_{config_name}.json"
    with open(path, "w") as f:
        json.dump(rows, f, indent=2)
    print(f"trace profile -> {path}: " + json.dumps(
        rows["families_ms_per_step"]), file=sys.stderr)
    return rows


def _obs_mark():
    """Start an obs evidence window (None when obs is off): spans
    admitted after the returned mark belong to the timed section."""
    import paddle_tpu.obs as obs
    return obs.tracer.mark() if obs.enabled() else None


def _obs_window(mark, wall_s=None):
    """Summarize one obs window: per-site dispatch-span counts (error
    spans excluded — a failed dispatch never ran), the per-dispatch
    FLOPs each site's compiled program costs (XLA cost_analysis via
    obs.cost), and the window's model-FLOPs-utilisation when a wall
    time is given."""
    import paddle_tpu.obs as obs
    counts = obs.tracer.counts(mark)
    costs = obs.site_costs()
    flops = {s: costs[s]["flops"] for s in counts
             if s in costs and "flops" in costs[s]}
    total = sum(counts[s] * f for s, f in flops.items())
    out = {"dispatch_spans": counts, "flops_per_dispatch": flops,
           "total_flops": total}
    if wall_s and total:
        mfu = obs.mfu(total, wall_s)    # None off the TPU
        out["mfu"] = None if mfu is None else round(mfu, 6)
    return out


def _obs_finish(mark, trace_name, **extra):
    """Close an obs evidence block: export the window's spans as a
    chrome-trace-loadable file and bundle the metrics snapshot +
    per-site cost records. Returns the bench record's ``obs`` block."""
    import paddle_tpu.obs as obs
    if mark is None:
        return {"enabled": False}
    path = obs.tracer.export_chrome_trace(trace_name, since=mark)
    block = {"enabled": True, "trace_path": path,
             "spans_dropped": obs.tracer.dropped,
             "metrics": obs.metrics.snapshot(),
             "site_costs": obs.site_costs(),
             "peak_flops_per_sec": obs.device_peak_flops()}
    block.update(extra)
    return block


def _emit(metric: str, value: float, unit: str) -> dict:
    vs = None
    try:
        with open("BASELINE.json") as f:
            base = json.load(f).get("published", {})
        target = base.get(metric)
        if target:
            vs = round(value / float(target), 3)
    except Exception:
        pass
    line = {"metric": metric, "value": round(value, 1), "unit": unit,
            "vs_baseline": vs}
    print(json.dumps(line))
    return line


def _trainer_for(model, loss_fn, lr=1e-4, opt_name="adamw", amp=True,
                 multi_precision=True):
    """f32 master weights + bf16 MXU ops via the AMP dispatch hook (the
    trainer's amp_dtype path), which keeps conv/BN dtype handling correct."""
    import jax

    import paddle_tpu as paddle
    from paddle_tpu.parallel import init_mesh
    from paddle_tpu.parallel.train import ShardedTrainer

    on_tpu = jax.devices()[0].platform == "tpu"
    if opt_name == "adamw":
        opt = paddle.optimizer.AdamW(learning_rate=lr,
                                     parameters=model.parameters(),
                                     multi_precision=multi_precision)
    else:
        opt = paddle.optimizer.Momentum(learning_rate=lr, momentum=0.9,
                                        parameters=model.parameters())
    mesh = init_mesh((1, 1, 1), ("dp", "sep", "mp"))
    trainer = ShardedTrainer(model, opt, loss_fn, mesh, {},
                             amp_dtype="bfloat16" if (on_tpu and amp) else None)
    return trainer, mesh, on_tpu


def bench_llama(profile=False):
    import numpy as np

    import jax
    import paddle_tpu as paddle
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM, llama_tp_plan
    from paddle_tpu.parallel import init_mesh
    from paddle_tpu.parallel.train import ShardedTrainer

    n_dev = len(jax.devices())
    on_tpu = jax.devices()[0].platform == "tpu"

    # ~134M-param Llama (GPT2-small scale)
    cfg = LlamaConfig(vocab_size=32000, hidden_size=768, intermediate_size=2048,
                      num_hidden_layers=12, num_attention_heads=12,
                      num_key_value_heads=12, max_position_embeddings=1024,
                      dtype="bfloat16" if on_tpu else "float32")
    B, S = (8, 1024) if on_tpu else (2, 128)
    steps = 20 if on_tpu else 3

    mesh = init_mesh((1, 1, n_dev) if n_dev > 1 else (1, 1, 1),
                     ("dp", "sep", "mp"))
    model = LlamaForCausalLM(cfg)
    if on_tpu:
        import jax.numpy as jnp
        for p in model.parameters():
            p._set_value(p.value.astype(jnp.bfloat16))
    opt = paddle.optimizer.AdamW(learning_rate=1e-4,
                                 parameters=model.parameters(),
                                 multi_precision=False)
    trainer = ShardedTrainer(model, opt, lambda m, i, l: m.loss(i, l),
                             mesh, llama_tp_plan(model, mesh))
    rng = np.random.default_rng(0)
    ids = rng.integers(0, cfg.vocab_size, (B, S))
    labels = rng.integers(0, cfg.vocab_size, (B, S))

    # _measure_steps fetches the last loss to the host: the device
    # executes FIFO, so that fetch fences the whole timed window.
    with mesh:
        step_time = _measure_steps(trainer, (ids, labels), steps)

    tokens_per_sec = B * S / step_time
    flops = model.flops_per_token(S) * B * S
    print(f"llama: step={step_time*1e3:.1f}ms params={model.num_params()/1e6:.1f}M "
          f"MFU~{_mfu_pct(flops / step_time, n_dev)}", file=sys.stderr)
    if profile:
        _profile_llama(trainer, model, mesh, ids, labels, step_time)
    return _emit("llama_110m_train_tokens_per_sec", tokens_per_sec,
                 "tokens/sec")


def _profile_llama(trainer, model, mesh, ids, labels, full_step):
    """Per-component breakdown artifact:
    ablation-timed fwd / fwd+bwd / optimizer segments + compiled-module
    cost analysis, written to bench_profile.json."""
    import numpy as np

    import jax
    import jax.numpy as jnp
    import paddle_tpu as paddle
    from paddle_tpu.autograd import tape
    from paddle_tpu.framework.tensor import Tensor

    state = dict(model.state_dict())
    names = tuple(state.keys())
    params = {n: state[n].value for n in names}
    ids_d = jnp.asarray(ids)
    labels_d = jnp.asarray(labels)

    def run_model(params, mode):
        originals = []
        try:
            for n in names:
                t = state[n]
                originals.append((t, t._value))
                t._value = params[n]
            with tape.no_grad():
                if mode == "loss":
                    return model.loss(Tensor(ids_d), Tensor(labels_d))._value
                if mode == "logits":
                    return model(Tensor(ids_d)).astype("float32").sum()._value
                return model.model(Tensor(ids_d)).astype("float32").sum()._value
        finally:
            for t, v in originals:
                t._value = v

    def fence(out):
        # fetch ONE element, not the first leaf: copying a full
        # embedding-grad leaf (~100MB) to the host would swamp the
        # measurement
        leaf = jax.tree_util.tree_leaves(out)[0]
        np.asarray(leaf.ravel()[:1])

    def timed(fn, *args):
        f = jax.jit(fn)
        fence(f(*args))
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            for _ in range(5):
                out = f(*args)
            fence(out)
            best = min(best, (time.perf_counter() - t0) / 5)
        return best

    rows = {
        "full_step_ms": full_step * 1e3,
        "fwd_loss_ms": timed(lambda p: run_model(p, "loss"), params) * 1e3,
        "fwd_hidden_ms": timed(lambda p: run_model(p, "hidden"), params) * 1e3,
        "fwd_bwd_ms": timed(
            jax.grad(lambda p: run_model(p, "loss")), params) * 1e3,
        "fwd_bwd_no_head_ms": timed(
            jax.grad(lambda p: run_model(p, "hidden")), params) * 1e3,
    }
    # subtraction-based estimates: the ablation jits lack the trainer's
    # buffer donation, so they run slightly slower than the full step and
    # differences can underflow — clamp at 0 and treat as approximate
    rows["optimizer_ms_approx"] = max(
        0.0, rows["full_step_ms"] - rows["fwd_bwd_ms"])
    rows["lm_head_ce_ms_approx"] = max(
        0.0, rows["fwd_bwd_ms"] - rows["fwd_bwd_no_head_ms"])
    try:
        lowered = jax.jit(jax.grad(lambda p: run_model(p, "loss"))).lower(params)
        cost = lowered.compile().cost_analysis()
        if isinstance(cost, (list, tuple)):
            cost = cost[0]
        rows["cost_analysis_flops"] = float(cost.get("flops", -1))
        rows["cost_analysis_bytes"] = float(cost.get("bytes accessed", -1))
    except Exception as e:  # cost analysis unsupported on some backends
        rows["cost_analysis_error"] = str(e)[:200]
    with open("bench_profile.json", "w") as f:
        json.dump(rows, f, indent=2)
    print("profile: " + json.dumps(rows), file=sys.stderr)


def bench_resnet50():
    import numpy as np

    import jax
    from paddle_tpu.vision.models.resnet import resnet50
    import paddle_tpu.nn.functional as F

    model = resnet50()
    model.train()

    def loss_fn(m, x, y):
        return F.cross_entropy(m(x), y)

    # pure-bf16 params/activations like the other bf16 configs (BN stats
    # stay f32 inside _batch_norm_train); the AMP-with-f32-weights path
    # left ~16ms/step of f32 BN/elementwise passes at B=64
    on_tpu0 = __import__("jax").devices()[0].platform == "tpu"
    if on_tpu0:
        import jax.numpy as jnp
        import ml_dtypes
        for p in model.parameters():
            p._set_value(p.value.astype(jnp.bfloat16))
        for _n, b in model.named_buffers():
            b._set_value(b.value.astype(jnp.bfloat16))
    trainer, mesh, on_tpu = _trainer_for(model, loss_fn, lr=0.1,
                                         opt_name="momentum", amp=False)
    B = 64 if on_tpu else 4
    side = 224 if on_tpu else 64
    steps = 10 if on_tpu else 2
    rng = np.random.default_rng(0)
    import ml_dtypes as _md
    x = rng.normal(size=(B, 3, side, side)).astype(
        _md.bfloat16 if on_tpu else np.float32)
    y = rng.integers(0, 1000, (B,))
    with mesh:
        step_time = _measure_steps(trainer, (x, y), steps)
    ips = B / step_time
    # ~4.1 GF inference FLOPs per 224x224 image; x3 for fwd+bwd
    print(f"resnet50: step={step_time*1e3:.1f}ms B={B} "
          f"MFU~{_mfu_pct(12.3e9 * B / step_time)}", file=sys.stderr)
    return _emit("resnet50_train_images_per_sec", ips, "images/sec")


def bench_bert(profile=False):
    import numpy as np

    import jax
    from paddle_tpu.models.bert import BertConfig, BertForMaskedLM

    cfg = BertConfig(dropout=0.0)  # BERT-base
    model = BertForMaskedLM(cfg)
    # pure-bf16 params (the flagship llama/ernie protocol) rather than
    # f32-master AMP: the per-op f32->bf16 weight casts cost ~15% step time
    import jax as _jax
    if _jax.devices()[0].platform == "tpu":
        import jax.numpy as jnp
        for p in model.parameters():
            p._set_value(p.value.astype(jnp.bfloat16))
    trainer, mesh, on_tpu = _trainer_for(
        model, lambda m, i, l: m.loss(i, l), lr=1e-4, amp=False,
        multi_precision=False)
    B, S = (16, 512) if on_tpu else (2, 64)
    steps = 20 if on_tpu else 2
    rng = np.random.default_rng(0)
    ids = rng.integers(0, cfg.vocab_size, (B, S))
    labels = rng.integers(0, cfg.vocab_size, (B, S))
    with mesh:
        step_time = _measure_steps(trainer, (ids, labels), steps)
        if profile:
            _trace_profile(trainer, (ids, labels), steps, "bert")
    tps = B * S / step_time
    n = sum(p.size for p in model.parameters())
    print(f"bert: step={step_time*1e3:.1f}ms params={n/1e6:.0f}M "
          f"MFU~{_mfu_pct(6 * n * B * S / step_time)}", file=sys.stderr)
    return _emit("bert_base_mlm_tokens_per_sec", tps, "tokens/sec")


def bench_unet(profile=False):
    import numpy as np

    import jax
    import jax.numpy as jnp
    from paddle_tpu.framework.tensor import Tensor
    from paddle_tpu.models.unet import UNetConfig, UNet2DConditionModel

    on_tpu = jax.devices()[0].platform == "tpu"
    cfg = UNetConfig() if on_tpu else UNetConfig(
        model_channels=32, channel_mult=(1, 2), num_res_blocks=1,
        attention_levels=(1,), context_dim=32, groups=8)
    model = UNet2DConditionModel(cfg)

    def loss_fn(m, x, t, ctx, target):
        eps = m(x, t, ctx)
        return ((eps - target).astype("float32") ** 2).mean()

    # bf16 params + optimizer state (the llama-bench treatment) rather
    # than AMP-with-f32-master: at 748M params the AdamW update alone
    # moves ~21GB/step in f32 (~26ms of the round-3 207ms device step),
    # and every activation copy/transpose halves too
    if on_tpu:
        for p in model.parameters():
            p._set_value(p.value.astype(jnp.bfloat16))
    trainer, mesh, on_tpu = _trainer_for(model, loss_fn, lr=1e-4, amp=False,
                                         multi_precision=False)
    B = 8 if on_tpu else 1
    side = 64 if on_tpu else 16
    ctx_len, ctx_dim = (77, cfg.context_dim or 1024) if on_tpu else (8, 32)
    steps = 10 if on_tpu else 2
    rng = np.random.default_rng(0)
    import ml_dtypes
    npdt = ml_dtypes.bfloat16 if on_tpu else np.float32
    x = rng.normal(size=(B, cfg.in_channels, side, side)).astype(npdt)
    t = rng.integers(0, 1000, (B,)).astype(np.int64)
    ctx = rng.normal(size=(B, ctx_len, ctx_dim)).astype(npdt)
    tgt = rng.normal(size=x.shape).astype(npdt)
    with mesh:
        step_time = _measure_steps(trainer, (x, t, ctx, tgt), steps)
        if profile and on_tpu:
            _trace_profile(trainer, (x, t, ctx, tgt), steps, "unet")
    n = sum(p.size for p in model.parameters())
    # step FLOPs from the compiled single-step module (convs dominate; an
    # analytic count would re-derive what XLA already knows)
    mfu_s = ""
    if profile:
        # costs a second XLA compile of the single-step program — opt-in
        try:
            lowered = trainer.compile_lowered(
                *[(a.shape, a.dtype)
                  for a in map(np.asarray, (x, t, ctx, tgt))])
            cost = lowered.compile().cost_analysis()
            if isinstance(cost, (list, tuple)):
                cost = cost[0]
            flops = float(cost.get("flops", 0) if cost else 0)
            if flops > 0:
                mfu_s = f" MFU~{_mfu_pct(flops / step_time)}"
        except Exception:
            pass
    print(f"unet: step={step_time*1e3:.1f}ms params={n/1e6:.0f}M B={B}"
          f"{mfu_s}", file=sys.stderr)
    return _emit("sd_unet_train_images_per_sec", B / step_time, "images/sec")


def bench_ernie(profile=False):
    """ERNIE-style semi-auto config: DistTensor placements (semi-auto API)
    on a GPT-arch LM, compiled via the same GSPMD path the multi-chip run
    uses (auto_parallel/api.py shard_tensor analog on a 1-chip mesh)."""
    import numpy as np

    import jax
    import paddle_tpu as paddle
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM
    from paddle_tpu.parallel import init_mesh, Replicate, Shard
    from paddle_tpu.parallel.train import ShardedTrainer

    on_tpu = jax.devices()[0].platform == "tpu"
    cfg = GPTConfig(vocab_size=30000, hidden_size=1024, num_hidden_layers=12,
                    num_attention_heads=16, intermediate_size=4096,
                    max_position_embeddings=1024) if on_tpu else GPTConfig(
        vocab_size=256, hidden_size=64, num_hidden_layers=2,
        num_attention_heads=4, intermediate_size=128,
        max_position_embeddings=128)
    model = GPTForCausalLM(cfg)
    mesh = init_mesh((1, 1, 1), ("dp", "sep", "mp"))
    # semi-auto: mp placements on attention/mlp weights (sharding degree 1
    # on a single chip; the placement machinery is what's being measured)
    plan = {}
    for name, p in model.named_parameters():
        pls = [Replicate()] * mesh.ndim
        if name.endswith("weight") and p.ndim == 2 and "embed" not in name:
            pls[2] = Shard(1)
        plan[name] = pls
    if on_tpu:
        import jax.numpy as jnp
        for p in model.parameters():
            p._set_value(p.value.astype(jnp.bfloat16))
    opt = paddle.optimizer.AdamW(learning_rate=1e-4,
                                 parameters=model.parameters(),
                                 multi_precision=False)
    trainer = ShardedTrainer(model, opt, lambda m, i, l: m.loss(i, l),
                             mesh, plan)
    B, S = (8, 1024) if on_tpu else (2, 64)
    steps = 20 if on_tpu else 2
    rng = np.random.default_rng(0)
    ids = rng.integers(0, cfg.vocab_size, (B, S))
    labels = rng.integers(0, cfg.vocab_size, (B, S))
    with mesh:
        step_time = _measure_steps(trainer, (ids, labels), steps)
        if profile:
            _trace_profile(trainer, (ids, labels), steps, "ernie")
    tps = B * S / step_time
    n = sum(p.size for p in model.parameters())
    print(f"ernie: step={step_time*1e3:.1f}ms params={n/1e6:.0f}M "
          f"MFU~{_mfu_pct(6 * n * B * S / step_time)}", file=sys.stderr)
    return _emit("ernie_semiauto_tokens_per_sec", tps, "tokens/sec")


def _decode_round(dec, prompt, n_hi, n_lo):
    """One marginal-seconds/token sample: difference of two generate
    lengths — prefill and per-call dispatch cancel out."""
    t0 = time.perf_counter()
    dec.generate(prompt, max_new_tokens=n_hi)
    t_hi = time.perf_counter() - t0
    t0 = time.perf_counter()
    dec.generate(prompt, max_new_tokens=n_lo)
    t_lo = time.perf_counter() - t0
    return (t_hi - t_lo) / (n_hi - n_lo)


def _decode_interleaved(decoders, prompt, n_hi=96, n_lo=32, reps=7,
                        warmup=2):
    """All decoder variants measured A/B/A/B within ONE session so
    chip-state drift (clock/thermal state) hits every variant equally —
    variants measured back-to-back moved 0.31-0.49 ms/tok in absolute
    numbers across sessions. Fixed warmup round count; per-variant stats
    are median and IQR over the interleaved rounds."""
    import numpy as np

    for _ in range(warmup):
        for dec in decoders:
            _decode_round(dec, prompt, n_hi, n_lo)
    samples = [[] for _ in decoders]
    for _ in range(reps):
        for i, dec in enumerate(decoders):
            samples[i].append(_decode_round(dec, prompt, n_hi, n_lo))
    out = []
    for s in samples:
        a = np.asarray(s)
        q1, med, q3 = np.percentile(a, [25, 50, 75])
        out.append({"median": float(med), "iqr": float(q3 - q1)})
    return out


def _bench_decode_config(cfg_kwargs, metric, label):
    """Greedy KV-cache decode: bf16 vs int8-weight-only marginal tok/s
    (weight_only_linear + block_multi_head_attention capability analog)."""
    import numpy as np

    import jax
    import jax.numpy as jnp
    from paddle_tpu.inference.generate import LlamaDecoder
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM

    on_tpu = jax.devices()[0].platform == "tpu"
    cfg = LlamaConfig(**cfg_kwargs, dtype="bfloat16") if on_tpu else \
        LlamaConfig(vocab_size=256, hidden_size=64, intermediate_size=128,
                    num_hidden_layers=2, num_attention_heads=4,
                    num_key_value_heads=4, max_position_embeddings=128)
    model = LlamaForCausalLM(cfg)
    if on_tpu:
        for p in model.parameters():
            p._set_value(p.value.astype(jnp.bfloat16))
    B, prompt_len = (8, 128) if on_tpu else (1, 8)
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, cfg.vocab_size, (B, prompt_len))
    hi, lo = (96, 32) if on_tpu else (8, 4)
    dec = LlamaDecoder(model, max_len=prompt_len + hi + 1)
    dec_i8 = LlamaDecoder(model, max_len=prompt_len + hi + 1,
                          weight_dtype="int8")
    stats_bf, stats_i8 = _decode_interleaved([dec, dec_i8], prompt, hi, lo)
    s_bf, s_i8 = stats_bf["median"], stats_i8["median"]
    n = sum(p.size for p in model.parameters())
    # HBM utilization: the per-token weight stream (every parameter is
    # read once per decoded token at B<<weights) over ~819 GB/s v5e peak
    peak_bw = 819e9
    util_bf = (n * 2 / s_bf) / peak_bw * 100
    util_i8 = (n * 1 / s_i8) / peak_bw * 100
    print(f"{label}: bf16 {s_bf*1e3:.2f}±{stats_bf['iqr']*1e3:.2f}ms/tok "
          f"({B/s_bf:.0f} tok/s, weight-stream {n*2/s_bf/1e9:.0f} GB/s = "
          f"{util_bf:.0f}% HBM), "
          f"int8 {s_i8*1e3:.2f}±{stats_i8['iqr']*1e3:.2f}ms/tok "
          f"({B/s_i8:.0f} tok/s, {n/s_i8/1e9:.0f} GB/s = {util_i8:.0f}% "
          f"HBM), int8/bf16 {s_bf/s_i8:.2f}x (interleaved A/B, median±IQR "
          f"over 7 rounds)", file=sys.stderr)
    return _emit(metric, B / s_bf, "tokens/sec")


def bench_decode():
    return _bench_decode_config(
        dict(vocab_size=32000, hidden_size=768, intermediate_size=2048,
             num_hidden_layers=12, num_attention_heads=12,
             num_key_value_heads=12, max_position_embeddings=1024),
        "llama_110m_greedy_decode_tokens_per_sec", "decode-134M")


def bench_decode_1b():
    """The weight-bandwidth-bound regime: ~941M params, where int8
    weight-only shows its step-time win (the 134M model is
    kernel-overhead-bound at B=8 and int8 is ~parity there)."""
    return _bench_decode_config(
        dict(vocab_size=32000, hidden_size=2048, intermediate_size=5504,
             num_hidden_layers=16, num_attention_heads=16,
             num_key_value_heads=16, max_position_embeddings=1024),
        "llama_1b_greedy_decode_tokens_per_sec", "decode-1B")


def bench_decode_1b_served():
    """Bundle-SERVED decode at the 1B config:
    export bf16 and int8 weight-only decoders as AOT bundles, load them
    through AotPredictor (zero model Python), and measure marginal
    seconds/token interleaved — the number a serving deployment actually
    gets. Heavy (bakes ~2 GB
    of weights into StableHLO modules per variant), so it is opt-in:
    ``python bench.py --config decode1b_served``."""
    import os
    import tempfile

    import numpy as np

    import jax
    import jax.numpy as jnp
    from paddle_tpu.inference import AotPredictor, export_decoder_bundle
    from paddle_tpu.inference.generate import LlamaDecoder
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM

    on_tpu = jax.devices()[0].platform == "tpu"
    if on_tpu:
        cfg = LlamaConfig(vocab_size=32000, hidden_size=2048,
                          intermediate_size=5504, num_hidden_layers=16,
                          num_attention_heads=16, num_key_value_heads=16,
                          max_position_embeddings=1024, dtype="bfloat16")
        B, prompt_len, hi, lo = 8, 128, 96, 32
    else:
        cfg = LlamaConfig(vocab_size=256, hidden_size=64,
                          intermediate_size=128, num_hidden_layers=2,
                          num_attention_heads=4, num_key_value_heads=4,
                          max_position_embeddings=128)
        B, prompt_len, hi, lo = 1, 8, 8, 4
    model = LlamaForCausalLM(cfg)
    if on_tpu:
        for p in model.parameters():
            p._set_value(p.value.astype(jnp.bfloat16))
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, cfg.vocab_size, (B, prompt_len))
    max_len = prompt_len + hi + 1

    import shutil
    tmp = tempfile.mkdtemp(prefix="bench_served_")
    try:   # exports bake ~2 GB of weights per variant: never leak them
        preds = []
        for tag, wd in (("bf16", None), ("int8", "int8")):
            dec = LlamaDecoder(model, max_len=max_len, weight_dtype=wd)
            bdir = os.path.join(tmp, tag)
            # BOTH step counts as decode buckets: the marginal-time
            # protocol subtracts a lo-step serve from a hi-step serve, so
            # each must run its own fixed-step module (one shared hi
            # bucket would make the subtraction measure pure noise)
            export_decoder_bundle(dec, bdir, prompt_lens=[prompt_len],
                                  decode_steps=[hi - 1, lo - 1],
                                  batch_sizes=[B])
            del dec
            preds.append(AotPredictor(bdir))
        stats_bf, stats_i8 = _decode_interleaved(preds, prompt, hi, lo)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    s_bf, s_i8 = stats_bf["median"], stats_i8["median"]
    n = sum(p.size for p in model.parameters())
    print(f"decode-1B-served: bf16 {s_bf*1e3:.2f}±"
          f"{stats_bf['iqr']*1e3:.2f}ms/tok ({B/s_bf:.0f} tok/s), "
          f"int8 {s_i8*1e3:.2f}±{stats_i8['iqr']*1e3:.2f}ms/tok "
          f"({B/s_i8:.0f} tok/s), int8/bf16 {s_bf/s_i8:.2f}x "
          f"(AOT-bundle served, interleaved A/B, {n/1e6:.0f}M params)",
          file=sys.stderr)
    return _emit("llama_1b_served_int8_decode_tokens_per_sec", B / s_i8,
                 "tokens/sec")


def bench_moe():
    """MoE LM train step (dropless ragged dispatch, stacked-expert grouped
    GEMM — incubate/nn/moe.py): tokens/sec on one chip. The reference's
    MoE tier lives in incubate/distributed/models/moe."""
    import numpy as np

    import jax
    import jax.numpy as jnp
    import paddle_tpu as paddle
    import paddle_tpu.nn as nn
    from paddle_tpu.incubate.nn import MoEMLP
    from paddle_tpu.parallel import init_mesh
    from paddle_tpu.parallel.train import ShardedTrainer

    on_tpu = jax.devices()[0].platform == "tpu"
    d, f, E, V = (1024, 4096, 8, 32000) if on_tpu else (32, 64, 4, 256)
    n_layers = 4 if on_tpu else 2

    class MoEBlock(nn.Layer):
        def __init__(self):
            super().__init__()
            self.norm = nn.LayerNorm(d)
            self.moe = MoEMLP(d, f, n_experts=E, top_k=2, dispatch="ragged")

        def forward(self, x):
            return x + self.moe(self.norm(x))

    class MoELM(nn.Layer):
        def __init__(self):
            super().__init__()
            self.embed = nn.Embedding(V, d)
            self.blocks = nn.LayerList([MoEBlock() for _ in range(n_layers)])

        def loss(self, ids, labels):
            h = self.embed(ids)
            for b in self.blocks:
                h = b(h)
            from paddle_tpu.ops.fused_ce import fused_lm_loss
            return fused_lm_loss(h, self.embed.weight.t(), labels)

    model = MoELM()
    if on_tpu:
        for p in model.parameters():
            p._set_value(p.value.astype(jnp.bfloat16))
    mesh = init_mesh((1, 1, 1), ("dp", "sep", "mp"))
    opt = paddle.optimizer.AdamW(learning_rate=1e-4,
                                 parameters=model.parameters(),
                                 multi_precision=False)
    trainer = ShardedTrainer(model, opt, lambda m, i, l: m.loss(i, l),
                             mesh, {})
    B, S = (8, 1024) if on_tpu else (2, 32)
    steps = 10 if on_tpu else 2
    rng = np.random.default_rng(0)
    ids = rng.integers(0, V, (B, S))
    labels = rng.integers(0, V, (B, S))
    with mesh:
        step_time = _measure_steps(trainer, (ids, labels), steps)
    tps = B * S / step_time
    n = sum(p.size for p in model.parameters())
    print(f"moe: step={step_time*1e3:.1f}ms params={n/1e6:.0f}M "
          f"(E={E} top2 dropless)", file=sys.stderr)
    return _emit("moe_lm_train_tokens_per_sec", tps, "tokens/sec")


def _parse_mesh(spec):
    """``--mesh dp:D,tp:T`` -> ordered axes dict (None passes through)."""
    if spec is None or isinstance(spec, dict):
        return spec
    axes = {}
    for part in str(spec).split(","):
        part = part.strip()
        if not part:
            continue
        name, sep, size = part.partition(":")
        if not sep:
            raise ValueError(f"--mesh wants 'name:size,...' (e.g. "
                             f"'dp:2,tp:2'), got segment {part!r}")
        axes[name.strip()] = int(size)
    return axes or None


def _bench_mesh(mesh):
    """Build the decode mesh for a bench run (after the backend probe) —
    or fail with a clear record when the devices aren't there."""
    if mesh is None:
        return None
    import jax

    from paddle_tpu.parallel import decode_mesh
    axes = _parse_mesh(mesh)
    need = 1
    for v in axes.values():
        need *= int(v)
    if jax.device_count() < need:
        raise ValueError(
            f"--mesh {axes} needs {need} devices; this process has "
            f"{jax.device_count()} (on CPU set JAX_PLATFORMS=cpu so the "
            f"bench can force a virtual device mesh)")
    return decode_mesh(axes)


def bench_decode_modes(steps=None, mesh=None):
    """``--decode``: the fused one-dispatch decode microbenchmark.

    Measures tokens/s AND device-dispatch count per generate call for
    greedy / greedy+eos / sampled / speculative at several batch sizes
    (the dispatch count is the fused path's headline property: 2 =
    prefill + one fused token loop — 3 for speculative, which adds the
    draft prefill — vs ~N+1 for the per-token fallback). Speculative
    rows additionally report the mean accepted-draft count per verify
    step (``acceptance_len_mean``); every row carries
    ``tokens_per_dispatch``. The full breakdown rides in the emitted
    BENCH json line under "decode". ``steps`` overrides the per-mode
    repetition count (``--steps``).

    With obs enabled (PADDLE_TPU_OBS=1) each mode's timed window is also
    an obs evidence window: per-site dispatch-SPAN counts are asserted
    to equal the decoder's dispatch accounting exactly (fused generate =
    prefill + 1), per-dispatch FLOPs and window MFU ride in each row's
    ``obs`` entry, and the whole run exports a chrome-trace-loadable
    ``obs_trace_decode.json`` recorded in the top-level ``obs`` block."""
    import numpy as np

    import jax
    from paddle_tpu.inference.generate import LlamaDecoder
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM

    on_tpu = jax.devices()[0].platform == "tpu"
    if on_tpu:
        import jax.numpy as jnp
        cfg = LlamaConfig(vocab_size=32000, hidden_size=768,
                          intermediate_size=2048, num_hidden_layers=12,
                          num_attention_heads=12, num_key_value_heads=12,
                          max_position_embeddings=1024, dtype="bfloat16")
        batches, prompt_len, n_new, reps = (1, 8, 32), 128, 96, 3
        spec_draft, spec_k = "skip:3", 4
        if steps:
            reps = int(steps)
    else:
        cfg = LlamaConfig(vocab_size=256, hidden_size=64,
                          intermediate_size=128, num_hidden_layers=2,
                          num_attention_heads=4, num_key_value_heads=4,
                          max_position_embeddings=256)
        batches, prompt_len, n_new, reps = (1, 2), 8, 8, 2
        spec_draft, spec_k = "skip:1", 2
        if steps:
            reps = int(steps)
    model = LlamaForCausalLM(cfg)
    if on_tpu:
        for p in model.parameters():
            p._set_value(p.value.astype(jnp.bfloat16))
    mesh_obj = _bench_mesh(mesh)
    # + spec_k + 1 slack: speculative rounds overshoot by up to K slots
    dec = LlamaDecoder(model, max_len=prompt_len + n_new + spec_k + 1,
                       mesh=mesh_obj)
    rng = np.random.default_rng(0)
    # an eos id no token can match: full-length decode, measuring the
    # eos-enabled program's overhead rather than a data-dependent stop
    never_eos = -2
    spec_kw = {"draft_model": spec_draft,
               "num_speculative_tokens": spec_k}
    modes = [("greedy", {}),
             ("greedy_eos", {"eos_token_id": never_eos}),
             ("sampled", {"do_sample": True, "temperature": 0.8,
                          "top_k": 40, "seed": 0}),
             ("spec_greedy", dict(spec_kw)),
             ("spec_sampled", {"do_sample": True, "temperature": 0.8,
                               "top_k": 40, "seed": 0, **spec_kw})]
    # speculative modes run on a mesh too: the shard_map'd per-row
    # uneven cache advance made SpeculativeMeshError a working path
    run_mark = _obs_mark()        # the whole-run trace export window
    rows = {}
    for B in batches:
        prompt = rng.integers(0, cfg.vocab_size, (B, prompt_len))
        for name, kw in modes:
            dec.generate(prompt, max_new_tokens=n_new, **kw)  # compile+warm
            d0 = dec.dispatch_count
            wm = _obs_mark()      # per-mode span/dispatch evidence window
            t0 = time.perf_counter()
            for _ in range(reps):
                dec.generate(prompt, max_new_tokens=n_new, **kw)
            dt = time.perf_counter() - t0
            disp = (dec.dispatch_count - d0) // reps
            row = {
                "tokens_per_sec": round(B * n_new * reps / dt, 1),
                "ms_per_token": round(dt / reps / n_new * 1e3, 3),
                "dispatches_per_generate": disp,
                "tokens_per_dispatch": round(n_new / disp, 2),
            }
            if wm is not None:
                w = _obs_window(wm, wall_s=dt)
                spans = sum(w["dispatch_spans"].values())
                # the acceptance contract: trace spans ARE the dispatch
                # accounting (fused generate = prefill + 1, speculative
                # adds the draft prefill) — nothing hidden either way
                assert spans == disp * reps, \
                    f"span/dispatch mismatch [{name} B={B}]: " \
                    f"{w['dispatch_spans']} vs {disp}x{reps}"
                row["obs"] = {
                    "spans_per_generate": {
                        s: c // reps
                        for s, c in sorted(w["dispatch_spans"].items())},
                    "flops_per_dispatch": w["flops_per_dispatch"],
                    "mfu": w.get("mfu"),
                }
            if name.startswith("spec_"):
                row["acceptance_len_mean"] = round(
                    dec.last_spec_stats["acceptance_len_mean"], 3)
                row["num_speculative_tokens"] = spec_k
            rows[f"{name}_b{B}"] = row
            extra = (f", accept {row['acceptance_len_mean']:.2f}/{spec_k}"
                     if name.startswith("spec_") else "")
            print(f"decode[{name} B={B}]: "
                  f"{row['tokens_per_sec']:.0f} tok/s, "
                  f"{row['dispatches_per_generate']} "
                  f"dispatches/generate{extra}", file=sys.stderr)
    head = rows[f"sampled_b{batches[-1]}"]
    line = _emit("llama_sampled_fused_decode_tokens_per_sec",
                 head["tokens_per_sec"], "tokens/sec")
    line["decode"] = {"config": "134M" if on_tpu else "tiny-cpu",
                      "new_tokens": n_new, "reps": reps,
                      "speculative": (None if mesh_obj is not None
                                      else {"draft": spec_draft,
                                            "k": spec_k}),
                      "modes": rows}
    if mesh_obj is not None:
        md = dec.sharding.describe()
        md.pop("partition_rules", None)
        line["decode"]["mesh"] = md
    line["obs"] = _obs_finish(run_mark, "obs_trace_decode.json")
    # re-print the enriched record as the LAST stdout line (the driver
    # parses the final json line; _emit already printed the bare metric)
    print(json.dumps(line))
    return line


def bench_decode_quant(quant="int8w", steps=None):
    """``--decode --quant int8w|int8wk``: the quantized-decode benchmark.

    The SAME model served by the fp32/bf16 decoder and the quantized one
    (per-channel absmax int8 weights; ``int8wk`` adds the int8 KV cache
    with per-row scales and dequant fused into the scan body /
    decode-attention tile), measured interleaved. The record carries
    tokens/s for both, the obs cost telemetry's bytes-moved-per-dispatch
    for the fused decode program of each, and the param-dict weight
    bytes — the Pope et al. weight-bandwidth evidence.

    Hard asserts (the acceptance contract):
    - dispatch counts identical and == prefill + 1 for both variants;
    - the quantized decoder's fused, chunked and per-token paths emit
      BIT-EXACT greedy tokens (the achievable-exactness gate: same
      quantized computation, different program slicing);
    - teacher-forced top-1 agreement vs the fp32 decoder >= 99% with
      the per-position logit RMSE reported (the documented tolerance
      policy — free-running streams diverge after one flip, so the
      quality gate conditions each position on the same prefix);
    - per-dispatch bytes (obs cost telemetry) >= 1.8x lower than fp32;
    - the chunked decode path emits identical tokens with
      ``FLAGS_use_decode_attention`` on and off (the Pallas
      decode-attention routing, interpret-mode off-TPU)."""
    import numpy as np

    import jax
    import jax.numpy as jnp
    import paddle_tpu.obs as obs
    from paddle_tpu.flags import flags as _flags
    from paddle_tpu.inference.generate import LlamaDecoder, _forward_cached
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM

    on_tpu = jax.devices()[0].platform == "tpu"
    if on_tpu:
        cfg = LlamaConfig(vocab_size=32000, hidden_size=768,
                          intermediate_size=2048, num_hidden_layers=12,
                          num_attention_heads=12, num_key_value_heads=4,
                          max_position_embeddings=1024, dtype="bfloat16")
        B, prompt_len, n_new, reps = 8, 128, 96, 3
        max_len, chunk = 256, 16
    else:
        # GQA (kv < heads) so the decode-attention kernel path is live;
        # hidden 64 keeps int8 weight noise well under the top-1 margin,
        # and the wide MLP keeps the dispatch weight-dominated (the
        # regime the recipe exists for — a cache-dominated toy would
        # dilute the int8w byte ratio below what any real model shows)
        cfg = LlamaConfig(vocab_size=256, hidden_size=128,
                          intermediate_size=512, num_hidden_layers=2,
                          num_attention_heads=4, num_key_value_heads=2,
                          max_position_embeddings=256)
        B, prompt_len, n_new, reps = 2, 8, 16, 2
        max_len, chunk = 48, 5
    if steps:
        reps = int(steps)
    import paddle_tpu as paddle
    paddle.seed(0)
    model = LlamaForCausalLM(cfg)
    if on_tpu:
        for p in model.parameters():
            p._set_value(p.value.astype(jnp.bfloat16))
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, cfg.vocab_size, (B, prompt_len))
    dec_fp = LlamaDecoder(model, max_len=max_len)
    dec_q = LlamaDecoder(model, max_len=max_len, quant=quant)

    def pbytes(dec):
        return int(sum(np.dtype(v.dtype).itemsize * int(np.prod(v.shape))
                       for v in dec.params.values()))

    # -- dispatch accounting: both variants are prefill + ONE dispatch --
    outs, disps = {}, {}
    for name, dec in (("fp32", dec_fp), (quant, dec_q)):
        dec.generate(prompt, max_new_tokens=n_new)       # compile+warm
        d0 = dec.dispatch_count
        outs[name] = np.asarray(dec.generate(prompt, max_new_tokens=n_new))
        disps[name] = dec.dispatch_count - d0
    assert disps["fp32"] == disps[quant] == 2, \
        f"dispatch counts diverged (want prefill + 1 == 2): {disps}"

    # -- bit-exact parity: fused == chunked == per-token, quantized ----
    chq = np.asarray(dec_q.generate(prompt, max_new_tokens=n_new,
                                    chunk_size=chunk))
    assert np.array_equal(chq, outs[quant]), \
        "quantized chunked decode diverged from the fused path"
    old_fb = _flags.decode_fallback
    _flags.set("decode_fallback", True)
    try:
        ptq = np.asarray(dec_q.generate(prompt, max_new_tokens=n_new))
    finally:
        _flags.set("decode_fallback", old_fb)
    assert np.array_equal(ptq, outs[quant]), \
        "quantized per-token fallback diverged from the fused path"

    # -- quality vs fp32: teacher-forced top-1 agreement + logit RMSE --
    full = jnp.asarray(outs["fp32"][:, :-1])
    def logits_all(dec):
        kc, vc = dec._empty_cache(B)
        lg, _, _ = _forward_cached(dec.params, dec.cfg, full, kc, vc, 0,
                                   dec.max_len, return_all=True)
        return np.asarray(lg)
    lf, lq = logits_all(dec_fp), logits_all(dec_q)
    k = prompt_len - 1          # positions whose next token is generated
    agreement = float((lf.argmax(-1) == lq.argmax(-1))[:, k:].mean())
    rmse = float(np.sqrt(((lf - lq)[:, k:].astype(np.float64) ** 2)
                         .mean()))
    assert agreement >= 0.99, \
        f"teacher-forced top-1 agreement {agreement:.4f} below the " \
        f"0.99 gate (logit RMSE {rmse:.5f})"

    # -- bytes moved per dispatch (obs cost telemetry) ------------------
    old_obs, old_cost = _flags.obs_enabled, _flags.obs_cost_analysis
    _flags.set("obs_enabled", True)
    _flags.set("obs_cost_analysis", True)
    try:
        obs.clear_cost_cache()
        dec_fp.generate(prompt, max_new_tokens=n_new)
        cost_fp = dict(obs.site_costs().get("decode.fused") or {})
        dec_q.generate(prompt, max_new_tokens=n_new)
        cost_q = dict(obs.site_costs().get("decode.fused") or {})
    finally:
        _flags.set("obs_enabled", old_obs)
        _flags.set("obs_cost_analysis", old_cost)
    # the weight-stream evidence: the fused program's ARGUMENT bytes
    # (params + carry at their actual dtypes — what a dispatch streams
    # from HBM). XLA-CPU's "bytes accessed" also counts the transient
    # f32 dequant copy the XLA fallback materializes, so it measures the
    # CPU lowering, not the int8-to-VMEM path the Pallas tile runs on
    # TPU; argument bytes are the backend-independent operand truth.
    bfp = cost_fp.get("argument_bytes")
    bq = cost_q.get("argument_bytes")
    assert bfp and bq, \
        f"obs cost telemetry produced no bytes record: {cost_fp} {cost_q}"
    bytes_ratio = bfp / bq
    assert bytes_ratio >= 1.8, \
        f"per-dispatch weight bytes dropped only {bytes_ratio:.2f}x " \
        f"({bfp:.0f} -> {bq:.0f}); the weight-bandwidth win is gone"

    # -- chunked decode-attention routing: flag on/off bit-exact -------
    old_da = _flags.use_decode_attention
    old_int = _flags.decode_attention_interpret
    # kernel eligibility needs a 128-aligned cache length
    klen = max_len if max_len % 128 == 0 else 128
    try:
        _flags.set("use_decode_attention", True)
        if not on_tpu:      # off-TPU the kernel needs the interpret gate
            _flags.set("decode_attention_interpret", True)
        dec_on = LlamaDecoder(model, max_len=klen, quant=quant)
        toks_on = np.asarray(dec_on.generate(prompt, n_new,
                                             chunk_size=chunk))
        _flags.set("use_decode_attention", False)
        dec_off = LlamaDecoder(model, max_len=klen, quant=quant)
        toks_off = np.asarray(dec_off.generate(prompt, n_new,
                                               chunk_size=chunk))
    finally:
        _flags.set("use_decode_attention", old_da)
        _flags.set("decode_attention_interpret", old_int)
    assert np.array_equal(toks_on, toks_off), \
        "chunked decode-attention path diverged between " \
        "FLAGS_use_decode_attention on and off"

    # -- throughput, interleaved A/B -----------------------------------
    times = {"fp32": [], quant: []}
    for _ in range(reps):
        for name, dec in (("fp32", dec_fp), (quant, dec_q)):
            t0 = time.perf_counter()
            dec.generate(prompt, max_new_tokens=n_new)
            times[name].append(time.perf_counter() - t0)
    tps = {name: B * n_new / float(np.median(ts))
           for name, ts in times.items()}

    print(f"decode-quant[{quant}]: {tps[quant]:.0f} tok/s vs fp32 "
          f"{tps['fp32']:.0f} tok/s ({tps[quant]/tps['fp32']:.2f}x), "
          f"bytes/dispatch {bfp:.2e} -> {bq:.2e} ({bytes_ratio:.2f}x "
          f"lower), weight bytes {pbytes(dec_fp):.2e} -> "
          f"{pbytes(dec_q):.2e}, teacher-forced top-1 agreement "
          f"{agreement:.4f} (RMSE {rmse:.5f}), fused/chunked/per-token "
          f"bit-exact, decode-attention on/off bit-exact",
          file=sys.stderr)
    line = _emit(f"llama_decode_quant_{quant}_tokens_per_sec",
                 tps[quant], "tokens/sec")
    line["decode_quant"] = {
        "config": "134M-gqa4" if on_tpu else "tiny-cpu-gqa2",
        "recipe": quant,
        "new_tokens": n_new, "reps": reps, "batch": B,
        "tokens_per_sec": {k: round(v, 1) for k, v in tps.items()},
        "speedup_vs_fp32": round(tps[quant] / tps["fp32"], 3),
        "dispatches_per_generate": disps,
        # the fused program's argument stream (params + carry at their
        # actual dtypes) per dispatch — the weight-bandwidth evidence
        "weight_stream_bytes_per_dispatch": {"fp32": bfp, quant: bq},
        "bytes_ratio_fp32_over_quant": round(bytes_ratio, 3),
        "weight_bytes": {"fp32": pbytes(dec_fp), quant: pbytes(dec_q)},
        "parity": {
            "fused_chunked_per_token_bit_exact": True,
            "decode_attention_on_off_bit_exact": True,
            "teacher_forced_top1_agreement": round(agreement, 5),
            "logit_rmse": round(rmse, 6),
            "policy": "bit-exact across program slicings of the same "
                      "recipe; >=0.99 teacher-forced top-1 vs fp32",
        },
        "site_costs": {"fp32": cost_fp, quant: cost_q},
    }
    # re-print the enriched record as the LAST stdout line (the driver
    # parses the final json line; _emit already printed the bare metric)
    print(json.dumps(line))
    return line


def bench_serve(n_requests=None, slots=None, chunk=None, mesh=None,
                quant=None):
    """``--serve``: continuous batching vs static batching.

    A Poisson-arrival, mixed-output-length workload served two ways over
    the SAME decoder and wall clock: (a) the continuous-batching engine
    (``paddle_tpu.serving.ServingEngine`` — slot admission between
    chunked fused-decode dispatches), (b) static batching (assemble a
    full batch in arrival order, run ONE fused generate to the longest
    member's budget — rows that asked for less ride dead until it
    finishes). Reports tokens/s (requested tokens only), mean slot
    occupancy (useful-token fraction of slot-steps actually run),
    p50/p99 per-request latency and dispatch counts; the
    static-vs-continuous tokens/s ratio is the headline.

    Contract checks (hard asserts): every continuous result is bit-exact
    vs a solo greedy ``generate`` of the same request, and the dispatch
    accounting is one admission prefill per request + one dispatch per
    chunk — nothing hidden. With PADDLE_TPU_OBS=1 the continuous section
    is an obs evidence window: the exported ``obs_trace_serve.json``
    must show exactly one ``decode.admit_prefill`` span per admitted
    request, one ``decode.chunk`` span per chunk dispatch and one
    ``serving.request`` timeline span per request (asserted), plus the
    engine's Prometheus snapshot and per-dispatch FLOPs in the record's
    ``obs`` block."""
    import numpy as np

    import jax
    import paddle_tpu.obs as obs
    from paddle_tpu.inference.generate import LlamaDecoder
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.serving import ServingEngine

    # live telemetry plane (FLAGS_obs_export_port / PADDLE_TPU_OBS_PORT):
    # started BEFORE the model build so a prober can scrape /metrics and
    # /statusz through the whole run, warmup included; the continuous
    # engine attaches once it exists
    exporter = None
    if obs.resolve_export_port():
        exporter = obs.ObsExporter()
        exporter.start()
        print(f"serve: obs exporter on 127.0.0.1:{exporter.port} "
              f"(/metrics /statusz /tracez)", file=sys.stderr)

    on_tpu = jax.devices()[0].platform == "tpu"
    if on_tpu:
        import jax.numpy as jnp
        cfg = LlamaConfig(vocab_size=32000, hidden_size=768,
                          intermediate_size=2048, num_hidden_layers=12,
                          num_attention_heads=12, num_key_value_heads=12,
                          max_position_embeddings=1024, dtype="bfloat16")
        n_req = n_requests or 32
        slots = slots or 8
        chunk = chunk or 16   # 16 decode steps per chunk dispatch
        prompt_len, len_pool, mean_gap = 32, (8, 16, 32, 96), 0.02
    else:
        cfg = LlamaConfig(vocab_size=256, hidden_size=64,
                          intermediate_size=128, num_hidden_layers=2,
                          num_attention_heads=4, num_key_value_heads=4,
                          max_position_embeddings=256)
        n_req = n_requests or 24
        slots = slots or 4
        chunk = chunk or 8
        prompt_len, len_pool, mean_gap = 8, (4, 8, 16, 96), 0.002
    model = LlamaForCausalLM(cfg)
    if on_tpu:
        for p in model.parameters():
            p._set_value(p.value.astype(jnp.bfloat16))
    mesh_obj = _bench_mesh(mesh)
    max_len = prompt_len + max(len_pool)
    dec = LlamaDecoder(model, max_len=max_len, mesh=mesh_obj, quant=quant)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, (prompt_len,))
               for _ in range(n_req)]
    lens = rng.choice(len_pool, n_req)
    arrivals = np.cumsum(rng.exponential(mean_gap, n_req))
    useful = int(lens.sum())

    # warm every compiled program both serving modes will hit, so the
    # timed windows measure steady-state serving
    warm = ServingEngine(dec, num_slots=slots, chunk_size=chunk,
                         quant=quant)
    for k in range(slots + 1):
        warm.submit(prompts[k % n_req], int(len_pool[k % len(len_pool)]))
    warm.drain()
    for L in sorted(set(int(v) for v in len_pool)):
        dec.generate(np.stack([prompts[0]] * slots), max_new_tokens=L)

    # -- continuous ---------------------------------------------------------
    # quant= doubles as the typed recipe cross-check on the engine
    eng = ServingEngine(dec, num_slots=slots, chunk_size=chunk,
                        quant=quant)
    if exporter is not None:
        exporter.add_engine(eng)
    d0 = dec.dispatch_count
    wm = _obs_mark()    # obs window covers EXACTLY the continuous section
    finish = {}
    submitted = 0
    t0 = time.perf_counter()
    while len(finish) < n_req:
        now = time.perf_counter() - t0
        while submitted < n_req and arrivals[submitted] <= now:
            eng.submit(prompts[submitted], int(lens[submitted]),
                       seed=submitted)
            submitted += 1
        if (submitted < n_req and not len(eng.scheduler)
                and not eng.scheduler.slots.occupied()):
            time.sleep(max(0.0, arrivals[submitted]
                           - (time.perf_counter() - t0)))
            continue
        for rid, res in eng.step():
            finish[rid] = (time.perf_counter() - t0, res)
    cont_wall = time.perf_counter() - t0
    m = eng.metrics()
    disp_cont = dec.dispatch_count - d0
    lat = np.asarray([finish[i][0] - arrivals[i] for i in range(n_req)])
    cont = {
        "tokens_per_sec": round(useful / cont_wall, 1),
        "wall_s": round(cont_wall, 3),
        "occupancy_useful": round(useful / m["slot_steps_total"], 3),
        "occupancy_slots_mean": round(m["occupancy_mean"], 3),
        "latency_p50_s": round(float(np.percentile(lat, 50)), 4),
        "latency_p99_s": round(float(np.percentile(lat, 99)), 4),
        "queue_delay_p50_s": round(m["queue_delay_p50_s"], 4),
        "dispatches": disp_cont,
        "prefill_dispatches": m["prefill_dispatches"],
        "chunk_dispatches": m["chunk_dispatches"],
    }
    # contract: per-request greedy outputs bit-exact vs solo generate,
    # and the dispatch count is exactly prefills + chunks
    assert m["prefill_dispatches"] == n_req, \
        f"expected one admission prefill per request, got {m}"
    assert disp_cont == (m["prefill_dispatches"] + m["chunk_dispatches"]
                         + m["step_dispatches"]), \
        f"hidden dispatches: {disp_cont} vs {m}"
    # steady-state dispatches-per-chunk == 1: the device admission ring
    # splices every admitted row inside the NEXT chunk program — zero
    # host-side scatters, zero extra dispatch boundaries, no per-token
    # rung exercised. Every request stages exactly one ring row.
    ring = m["admission_ring"]
    assert ring is not None, "decoder serving should run the ring"
    assert ring["host_scattered"] == 0, \
        f"ring admission must not host-scatter: {ring}"
    assert ring["staged"] == n_req and ring["scattered"] == n_req, \
        f"one ring splice per admitted request: {ring} vs {n_req}"
    assert m["step_dispatches"] == 0, \
        f"clean serve must stay on the chunk rung: {m}"
    cont["admission_ring"] = ring
    # obs evidence (PADDLE_TPU_OBS=1): the exported trace's dispatch-span
    # counts must equal the engine's asserted accounting — one prefill
    # span per admitted request, one chunk span per chunk dispatch.
    # Captured BEFORE the parity solo generates below add their own
    # spans; the trace export closes the window here too.
    obs_block = {"enabled": False}
    if wm is not None:
        w = _obs_window(wm, wall_s=cont_wall)
        sp = w["dispatch_spans"]
        assert sp.get("decode.admit_prefill", 0) == \
            m["prefill_dispatches"], f"prefill spans vs accounting: {sp}"
        assert sp.get("decode.chunk", 0) == m["chunk_dispatches"], \
            f"chunk spans vs accounting: {sp}"
        assert sp.get("serving.request", 0) == n_req, \
            f"request timeline spans vs requests: {sp}"
        obs_block = _obs_finish(wm, "obs_trace_serve.json",
                                window=w,
                                engine_metrics_prometheus=eng.registry
                                .to_prometheus())
    # cost-model MFU, PER DEVICE: decode work is ~2*N_params FLOPs per
    # token; under a mesh each device does 1/mesh_size of it, so the
    # honest utilisation denominator is (devices x wall x peak). Off-mesh
    # this is the usual single-chip number (mesh_size=1).
    mesh_size = dec.sharding.size if dec.sharding is not None else 1
    mfu = obs.mfu(useful * 2 * model.num_params() / mesh_size, cont_wall)
    cont["mfu_model_per_device"] = None if mfu is None else round(mfu, 6)
    cont["request_latency_p50_s"] = round(m["request_latency_p50_s"], 4)
    cont["request_latency_p99_s"] = round(m["request_latency_p99_s"], 4)
    cont["queue_depth_peak"] = m["queue_depth_peak"]
    cont["ttft_p50_s"] = round(m["ttft_p50_s"], 4)
    cont["ttft_p99_s"] = round(m["ttft_p99_s"], 4)
    cont["tpot_mean_s"] = round(m["tpot_mean_s"], 5)
    for i in range(n_req):
        solo = np.asarray(dec.generate(prompts[i][None], int(lens[i])))
        got = np.asarray(finish[i][1])
        assert np.array_equal(got, solo), \
            f"request {i}: continuous output diverged from solo generate"

    # -- static -------------------------------------------------------------
    lat_s, batches = [], 0
    slot_steps_static = 0
    d0 = dec.dispatch_count
    t0 = time.perf_counter()
    i = 0
    while i < n_req:
        j = min(i + slots, n_req)
        wait = arrivals[i:j].max() - (time.perf_counter() - t0)
        if wait > 0:           # a static batch launches only when full
            time.sleep(wait)
        bp = [prompts[k] for k in range(i, j)]
        while len(bp) < slots:
            bp.append(prompts[i])          # pad rows; not counted
        L = int(lens[i:j].max())           # everyone rides to the longest
        dec.generate(np.stack(bp), max_new_tokens=L)
        tend = time.perf_counter() - t0
        lat_s.extend(tend - arrivals[k] for k in range(i, j))
        slot_steps_static += slots * L
        batches += 1
        i = j
    static_wall = time.perf_counter() - t0
    lat_s = np.asarray(lat_s)
    static = {
        "tokens_per_sec": round(useful / static_wall, 1),
        "wall_s": round(static_wall, 3),
        "occupancy_useful": round(useful / slot_steps_static, 3),
        "latency_p50_s": round(float(np.percentile(lat_s, 50)), 4),
        "latency_p99_s": round(float(np.percentile(lat_s, 99)), 4),
        "dispatches": dec.dispatch_count - d0,
        "batches": batches,
    }

    speedup = cont["tokens_per_sec"] / static["tokens_per_sec"]
    print(f"serve: continuous {cont['tokens_per_sec']:.0f} tok/s "
          f"(occupancy {cont['occupancy_useful']:.2f}, "
          f"p50 {cont['latency_p50_s']*1e3:.0f}ms, "
          f"p99 {cont['latency_p99_s']*1e3:.0f}ms, "
          f"{cont['dispatches']} dispatches) vs static "
          f"{static['tokens_per_sec']:.0f} tok/s "
          f"(occupancy {static['occupancy_useful']:.2f}, "
          f"p50 {static['latency_p50_s']*1e3:.0f}ms, "
          f"p99 {static['latency_p99_s']*1e3:.0f}ms, "
          f"{static['dispatches']} dispatches): {speedup:.2f}x tokens/s, "
          f"parity+dispatch contract checked on {n_req} requests",
          file=sys.stderr)
    line = _emit("serving_continuous_tokens_per_sec",
                 cont["tokens_per_sec"], "tokens/sec")
    mesh_rec = None
    if dec.sharding is not None:
        mesh_rec = dec.sharding.describe()
        mesh_rec.pop("partition_rules", None)
        mesh_rec["carry_sharding"] = eng.status()["mesh"]["carry_sharding"]
    line["serve"] = {
        "config": "134M" if on_tpu else "tiny-cpu",
        "requests": n_req, "slots": slots, "chunk_size": chunk,
        "prompt_len": prompt_len, "output_len_pool": list(len_pool),
        "poisson_mean_gap_s": mean_gap,
        "quant": dec.quant,
        "mesh": mesh_rec,
        "continuous": cont, "static": static,
        "speedup_tokens_per_sec": round(speedup, 3),
        "continuous_beats_static": bool(
            speedup > 1.0 and cont["occupancy_useful"]
            > static["occupancy_useful"]),
    }
    line["obs"] = obs_block
    if exporter is not None:
        line["obs_export_port"] = exporter.port
    # re-print the enriched record as the LAST stdout line (the driver
    # parses the final json line; _emit already printed the bare metric)
    print(json.dumps(line))
    if exporter is not None:
        exporter.stop()          # release the port before returning
    return line


def bench_serve_spec(n_requests=None, slots=None, chunk=None, mesh=None):
    """``--serve --speculative [--mesh dp:D,tp:T]``: speculative
    continuous batching vs the plain engine, SAME workload.

    Two engines over one decoder: (a) the plain ring engine, (b) the
    speculative engine (``draft_model='skip:1'``, greedy). Hard asserts:

    - dispatch accounting is exact on BOTH engines — prefills + chunks
      (+ draft prefills for b), admission adds zero host round-trips
      (``admission.host_scattered == 0``, one ring splice per request);
    - greedy tokens are BIT-EXACT between the two engines (speculative
      verify-accept is teacher-forced-equivalent by construction);
    - the dispatch win is real: speculative ``tokens_per_dispatch``
      (useful tokens over ALL dispatches) > 1.8 and its chunk-dispatch
      count is strictly below the plain engine's.

    Under ``--mesh`` the same contract runs sharded (the shard_map'd
    speculative path) — bit-exact on the virtual CPU mesh."""
    import numpy as np

    import jax
    from paddle_tpu.inference.generate import LlamaDecoder
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.serving import ServingEngine

    on_tpu = jax.devices()[0].platform == "tpu"
    if on_tpu:
        import jax.numpy as jnp
        cfg = LlamaConfig(vocab_size=32000, hidden_size=768,
                          intermediate_size=2048, num_hidden_layers=12,
                          num_attention_heads=12, num_key_value_heads=12,
                          max_position_embeddings=1024, dtype="bfloat16")
        n_req = n_requests or 16
        slots = slots or 8
        chunk = chunk or 16
        prompt_len, len_pool = 32, (8, 16, 32, 96)
        draft, K = "skip:3", 4
    else:
        cfg = LlamaConfig(vocab_size=256, hidden_size=64,
                          intermediate_size=128, num_hidden_layers=2,
                          num_attention_heads=4, num_key_value_heads=4,
                          max_position_embeddings=256)
        n_req = n_requests or 16
        slots = slots or 4
        chunk = chunk or 8
        prompt_len, len_pool = 8, (4, 8, 16, 96)
        draft, K = "skip:1", 2
    model = LlamaForCausalLM(cfg)
    if on_tpu:
        for p in model.parameters():
            p._set_value(p.value.astype(jnp.bfloat16))
    mesh_obj = _bench_mesh(mesh)
    max_len = prompt_len + max(len_pool) + K
    dec = LlamaDecoder(model, max_len=max_len, mesh=mesh_obj)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, (prompt_len,))
               for _ in range(n_req)]
    lens = rng.choice(len_pool, n_req)
    useful = int(lens.sum())

    def run(label, **kw):
        eng = ServingEngine(dec, num_slots=slots, chunk_size=chunk, **kw)
        for i in range(n_req):        # queue everything; drain steadily
            eng.submit(prompts[i], int(lens[i]), seed=i)
        d0 = dec.dispatch_count
        t0 = time.perf_counter()
        res = eng.drain()
        wall = time.perf_counter() - t0
        m = eng.metrics()
        disp = dec.dispatch_count - d0
        draft_pf = m["draft_prefill_dispatches"]
        assert disp == (m["prefill_dispatches"] + draft_pf
                        + m["chunk_dispatches"]
                        + m["step_dispatches"]), \
            f"{label}: hidden dispatches: {disp} vs {m}"
        assert m["step_dispatches"] == 0, \
            f"{label}: clean serve must stay on the chunk rung: {m}"
        ring = m["admission_ring"]
        assert ring["host_scattered"] == 0, \
            f"{label}: ring admission must not host-scatter: {ring}"
        assert ring["staged"] == n_req and ring["scattered"] == n_req, \
            f"{label}: one ring splice per request: {ring} vs {n_req}"
        rec = {"wall_s": round(wall, 3),
               "tokens_per_sec": round(useful / wall, 1),
               "dispatches": disp,
               "prefill_dispatches": m["prefill_dispatches"],
               "draft_prefill_dispatches": draft_pf,
               "chunk_dispatches": m["chunk_dispatches"],
               "tokens_per_dispatch": round(useful / disp, 3),
               "admission_ring": ring,
               "speculative": m["speculative"]}
        return rec, res

    # warm both compiled paths outside the timed windows
    for kw in ({}, {"draft_model": draft, "num_speculative_tokens": K}):
        w = ServingEngine(dec, num_slots=slots, chunk_size=chunk, **kw)
        for k in range(slots + 1):
            w.submit(prompts[k % n_req], int(len_pool[k % len(len_pool)]))
        w.drain()

    plain, res_p = run("plain")
    spec, res_s = run("spec", draft_model=draft,
                      num_speculative_tokens=K)
    # greedy parity: request id i is i-th submitted on both engines
    for i in range(n_req):
        a, b = np.asarray(res_p[i]), np.asarray(res_s[i])
        assert np.array_equal(a, b), \
            f"request {i}: speculative tokens diverged from plain engine"
    # the K-fold lever, measured: fewer chunk dispatches for the same
    # tokens, and ~2 tokens per dispatch overall
    assert spec["chunk_dispatches"] < plain["chunk_dispatches"], \
        f"speculation must cut chunk dispatches: {spec} vs {plain}"
    assert spec["tokens_per_dispatch"] > 1.8, \
        f"speculative tokens_per_dispatch too low: {spec}"
    reduction = plain["dispatches"] / spec["dispatches"]
    acc = spec["speculative"]["acceptance_len_mean"]
    print(f"serve-spec: plain {plain['dispatches']} dispatches "
          f"({plain['tokens_per_dispatch']:.2f} tok/dispatch) vs "
          f"speculative {spec['dispatches']} "
          f"({spec['tokens_per_dispatch']:.2f} tok/dispatch, "
          f"acceptance_len_mean {acc:.2f}): {reduction:.2f}x dispatch "
          f"reduction, bit-exact on {n_req} requests"
          + (f" on mesh {_parse_mesh(mesh)}" if mesh else ""),
          file=sys.stderr)
    line = _emit("serving_speculative_tokens_per_dispatch",
                 spec["tokens_per_dispatch"], "tokens/dispatch")
    mesh_rec = None
    if dec.sharding is not None:
        mesh_rec = dec.sharding.describe()
        mesh_rec.pop("partition_rules", None)
    line["serve_spec"] = {
        "config": "134M" if on_tpu else "tiny-cpu",
        "requests": n_req, "slots": slots, "chunk_size": chunk,
        "prompt_len": prompt_len, "output_len_pool": list(len_pool),
        "draft": draft, "num_speculative_tokens": K,
        "mesh": mesh_rec,
        "plain": plain, "speculative": spec,
        "dispatch_reduction": round(reduction, 3),
        "parity_bit_exact": True,
    }
    print(json.dumps(line))
    return line


def bench_serve_replicated(n_requests=None, replicas=3, slots=None,
                           chunk=None, faults=False):
    """``--serve --replicas N [--faults]``: fault-isolated replicated
    serving — the zero-request-loss gate.

    N independent ``ServingEngine`` replicas over the SAME weights,
    fronted by the health-checked ``serving.Router``. With ``--faults``
    the run injects the ISSUE's drill: one replica's chunk dispatches
    die FATALLY mid-serve (its circuit breaker must open and its
    accepted work requeue to survivors with generated tokens replayed)
    while another replica's heartbeat is delayed (it must go suspect,
    keep serving, and recover). Hard asserts, in-bench:

    - ZERO lost accepted requests: every submitted request resolves to
      tokens BIT-EXACT (greedy) with an undisturbed solo generate, or
      to a typed error (``DeadlineExceededError``/``ReplicaDeadError``)
      — accounting submitted == bit_exact + typed, nothing silent;
    - with --faults, exactly one replica died, >=1 request requeued,
      and the hung replica recovered;
    - ``snapshot()`` -> ``restore()`` round-trips continue generation
      bit-exactly on fp32 AND int8wk carries.

    Reports tokens/s and p99 latency under injected failure — the
    "fast AND survives" evidence row."""
    import tempfile

    import numpy as np

    from paddle_tpu.flags import set_flags
    from paddle_tpu.inference.generate import LlamaDecoder
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.runtime.resilience import (DeadlineExceededError,
                                               ReplicaDeadError,
                                               fault_injector)
    from paddle_tpu.serving import ReplicaSet, Router, ServingEngine

    replicas = int(replicas)
    if replicas < 2:
        raise ValueError(f"--replicas needs >= 2, got {replicas}")
    cfg = LlamaConfig(vocab_size=256, hidden_size=64,
                      intermediate_size=128, num_hidden_layers=2,
                      num_attention_heads=4, num_key_value_heads=4,
                      max_position_embeddings=256)
    n_req = n_requests or 18
    slots = slots or 2
    chunk = chunk or 4
    prompt_len, len_pool = 8, (4, 8, 12, 16)
    model = LlamaForCausalLM(cfg)
    max_len = prompt_len + max(len_pool) + 8
    decs = [LlamaDecoder(model, max_len=max_len)
            for _ in range(replicas)]
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, (prompt_len,))
               for _ in range(n_req)]
    lens = rng.choice(len_pool, n_req)
    solo = [np.asarray(decs[0].generate(prompts[i][None], int(lens[i])))
            for i in range(n_req)]

    router = Router(ReplicaSet.from_backends(
        decs, num_slots=slots, chunk_size=chunk), breaker_threshold=2)
    plan = []
    if faults:
        # the ISSUE drill: kill replica1 mid-chunk (fatal — the ladder
        # cannot save it), delay replica2's heartbeat for a window
        plan = [
            {"kind": "dispatch_error", "site": "serving.replica1.chunk",
             "call": 2, "times": 10**9, "code": "INTERNAL"},
            {"kind": "dispatch_error", "site": "serving.replica1.step",
             "call": 1, "times": 10**9, "code": "INTERNAL"},
            {"kind": "delay_heartbeat", "node": "replica2",
             "after_beats": 2, "skip_beats": 4},
        ]
        set_flags({"resilience_backoff_s": 0.0})
        fault_injector.configure(plan)
    saw_suspect = False
    t0 = time.perf_counter()
    try:
        rids = [router.submit(prompts[i], int(lens[i]))
                for i in range(n_req)]
        outcomes = {}
        finish_at = {}
        while any(r.has_work() for r in router.replicas.live()):
            for rid, res in router.step():
                outcomes[rid] = res
                finish_at[rid] = time.perf_counter() - t0
            if faults:
                states = {r.name: r.state for r in router.replicas}
                saw_suspect = saw_suspect or \
                    states.get("replica2") == "suspect"
        for _ in range(8):        # idle beats let the skip window lapse
            router.step()
    finally:
        if faults:
            fault_injector.clear()
            set_flags({"resilience_backoff_s": 0.5})
    wall = time.perf_counter() - t0

    # -- the zero-loss ledger (hard-asserted) -------------------------------
    bit_exact, typed, requeued_ok = 0, 0, 0
    for i, rid in enumerate(rids):
        out = outcomes.get(rid)
        assert out is not None, \
            f"request {i} vanished: submitted but never resolved"
        if isinstance(out, (DeadlineExceededError, ReplicaDeadError)):
            typed += 1
            continue
        assert not isinstance(out, BaseException), \
            f"request {i} resolved to an UNtyped error: {out!r}"
        assert np.array_equal(np.asarray(out), solo[i]), \
            f"request {i} diverged from the undisturbed run"
        bit_exact += 1
        if out.resilience.get("router", {}).get("requeues"):
            requeued_ok += 1
    assert bit_exact + typed == n_req, \
        f"loss: {n_req} submitted, {bit_exact} exact + {typed} typed"
    m = router.metrics()
    states = m["states"]
    if faults:
        assert states["replica1"] == "dead", \
            f"killed replica's breaker never opened: {states}"
        assert m["replica_deaths"] == 1 and m["requeued"] >= 1, m
        assert requeued_ok >= 1, \
            "no request survived a requeue bit-exactly"
        assert saw_suspect and states["replica2"] == "healthy", \
            f"hung replica drill: suspect={saw_suspect}, {states}"

    # -- snapshot -> restore round-trip, fp32 + int8wk carries --------------
    snap_parity = {}
    budget = max(len_pool)        # long enough to still be mid-flight
    for quant in (None, "int8wk"):
        qdec = (decs[0] if quant is None
                else LlamaDecoder(model, max_len=max_len, quant=quant))
        ref = [np.asarray(qdec.generate(prompts[i][None], budget))
               for i in range(4)]
        eng = ServingEngine(qdec, num_slots=slots, chunk_size=chunk)
        ids = [eng.submit(prompts[i], budget) for i in range(4)]
        got = {}
        for _ in range(2):
            for rid, res in eng.step():
                got[rid] = res
        with tempfile.TemporaryDirectory(prefix="bench_snap_") as tmp:
            eng.snapshot(tmp)
            fresh = ServingEngine(qdec, num_slots=slots,
                                  chunk_size=chunk)
            info = fresh.restore(tmp)
        assert info["in_flight"] >= 1, \
            f"snapshot drill never caught a row mid-flight: {info}"
        got.update(fresh.drain())
        for i, rid in enumerate(ids):
            assert np.array_equal(np.asarray(got[rid]), ref[i]), \
                f"snapshot->restore diverged (quant={quant}, req {i})"
        snap_parity[quant or "fp32"] = {
            "resumed_in_flight": info["in_flight"],
            "resumed_queued": info["queued"], "bit_exact": True}

    useful = int(lens.sum())
    lat = np.asarray([finish_at[r] for r in rids if r in finish_at
                      and not isinstance(outcomes[r], BaseException)])
    p99 = float(np.percentile(lat, 99)) if lat.size else float("nan")
    print(f"serve-replicated: {replicas} replicas, {n_req} requests, "
          f"faults={'on' if faults else 'off'} — {bit_exact} bit-exact "
          f"+ {typed} typed = ZERO lost; "
          f"{m['requeued']} requeued, deaths {m['replica_deaths']}, "
          f"suspects {m['heartbeat_suspects']}, "
          f"{useful / wall:.0f} tok/s, p99 {p99 * 1e3:.0f}ms; "
          f"snapshot round-trip bit-exact (fp32 + int8wk)",
          file=sys.stderr)
    line = _emit("serving_replicated_tokens_per_sec",
                 round(useful / wall, 1), "tokens/sec")
    line["serve_replicated"] = {
        "replicas": replicas, "slots_per_replica": slots,
        "chunk_size": chunk, "requests": n_req,
        "faults_injected": plan,
        "bit_exact": bit_exact, "typed_errors": typed,
        "lost": n_req - bit_exact - typed,
        "requeued": m["requeued"],
        "requeued_bit_exact": requeued_ok,
        "replica_deaths": m["replica_deaths"],
        "heartbeat_suspects": m["heartbeat_suspects"],
        "replica_states": states,
        "latency_p99_s": round(p99, 4),
        "wall_s": round(wall, 3),
        "snapshot_round_trip": snap_parity,
    }
    print(json.dumps(line))
    return line


def bench_serve_cluster(spec="prefill:1,decode:2", n_requests=None,
                        slots=None, chunk=None, faults=False):
    """``--serve --cluster prefill:1,decode:2 [--faults]``: the
    multi-process disaggregated serving benchmark — REAL OS processes,
    REAL SIGKILL.

    ``launch_cluster`` spawns one worker process per spec entry (>=3
    processes counting the frontend's pool), ships the model weights
    once as an npz, and fronts them with the ``ClusterRouter``:
    admission prefills run on the PREFILL pool and ship to a DECODE
    worker as a KV slab (the DistServe/Splitwise split), so decode-pool
    admission is one row-scatter. With ``--faults`` the drill is a real
    ``SIGKILL`` of a decode worker mid-run: its accepted work must
    requeue to survivors as ``prompt + tokens_so_far`` replay. Hard
    asserts, in-bench:

    - every worker is a DISTINCT live OS process (not the bench pid);
    - ZERO lost accepted requests: submitted == bit-exact (vs an
      undisturbed in-process solo generate over the same weights) +
      typed errors, even under the SIGKILL;
    - per-worker accounting split: prefill dispatches ONLY on the
      prefill pool, chunk dispatches ONLY on the decode pool, every
      delivered request a FULL prefix hit with zero admission
      dispatches decode-side;
    - the fleet /metrics (one frontend exposition, live-scraped from
      every worker's own exporter) carries per-worker-labelled samples.

    Reports tokens/s and p99 under (injected) process failure."""
    import os as _os
    import tempfile
    import urllib.request

    import numpy as np

    from paddle_tpu.inference.generate import LlamaDecoder
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.runtime.resilience import (DeadlineExceededError,
                                               ReplicaDeadError)
    from paddle_tpu.serving import launch_cluster, parse_cluster_spec

    roles = parse_cluster_spec(spec)
    cfg = LlamaConfig(vocab_size=256, hidden_size=64,
                      intermediate_size=128, num_hidden_layers=2,
                      num_attention_heads=4, num_key_value_heads=4,
                      max_position_embeddings=256)
    n_req = n_requests or 12
    slots = slots or 2
    chunk = chunk or 4
    prompt_len, len_pool = 8, (4, 8, 12)
    model = LlamaForCausalLM(cfg)
    max_len = prompt_len + max(len_pool) + 8
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, (prompt_len,))
               for _ in range(n_req)]
    lens = rng.choice(len_pool, n_req)
    # the undisturbed reference: the SAME weights decoded in-process
    solo_dec = LlamaDecoder(model, max_len=max_len)
    solo = [np.asarray(solo_dec.generate(prompts[i][None], int(lens[i])))
            for i in range(n_req)]

    workdir = tempfile.mkdtemp(prefix="bench_cluster_")
    t0 = time.perf_counter()
    with launch_cluster(
            model, workdir, prefill=roles["prefill"],
            decode=roles["decode"], unified=roles["unified"],
            max_len=max_len,
            engine_kw={"num_slots": slots, "chunk_size": chunk},
            heartbeat_s=0.4, ttl_s=2.0,
            rpc_timeout_s=30.0) as cl:
        router = cl.router
        obs_port = router.start_exporter(port=0)

        # >=3 REAL processes, none of them this one
        pids = {h.name: h.pid for h in router.workers}
        assert len(pids) >= 3, \
            f"the cluster drill needs >=3 worker processes, got {pids}"
        assert _os.getpid() not in pids.values(), \
            "worker 'process' is the bench process itself"
        for name, pid in pids.items():
            _os.kill(pid, 0)      # raises if the process does not exist

        rids = [router.submit(prompts[i], int(lens[i]))
                for i in range(n_req)]
        outcomes, finish_at = {}, {}
        steps, killed_pid, fleet_text = 0, None, None
        victim = next((h.name for h in router.workers
                       if h.role == "decode"),
                      next(h.name for h in router.workers
                           if h.serves_decode))
        while router.in_flight():
            for rid, res in router.step():
                outcomes[rid] = res
                finish_at[rid] = time.perf_counter() - t0
            steps += 1
            if fleet_text is None and steps >= 2:
                with urllib.request.urlopen(
                        f"http://127.0.0.1:{obs_port}/metrics",
                        timeout=10.0) as r:
                    fleet_text = r.read().decode()
            if (faults and killed_pid is None and steps >= 3
                    and router.in_flight() > 1):
                killed_pid = cl.kill(victim)
        wall = time.perf_counter() - t0
        m = router.metrics()
        wm = router.worker_metrics()
        with urllib.request.urlopen(
                f"http://127.0.0.1:{obs_port}/statusz",
                timeout=10.0) as r:
            statusz = json.loads(r.read().decode())

    # -- the zero-loss ledger (hard-asserted) -------------------------------
    disaggregated = roles["prefill"] > 0 \
        and m["disaggregation_fallbacks"] == 0
    bit_exact, typed, requeued_ok = 0, 0, 0
    for i, rid in enumerate(rids):
        out = outcomes.get(rid)
        assert out is not None, \
            f"request {i} vanished: submitted but never resolved"
        if isinstance(out, (DeadlineExceededError, ReplicaDeadError)):
            typed += 1
            continue
        assert not isinstance(out, BaseException), \
            f"request {i} resolved to an UNtyped error: {out!r}"
        assert np.array_equal(np.asarray(out), solo[i]), \
            f"request {i} diverged from the undisturbed in-process run"
        bit_exact += 1
        resil = getattr(out, "resilience", None) or {}
        srv = resil.get("serving", {})
        if disaggregated:
            assert srv.get("prefix_hit") == "full", \
                f"request {i} admitted decode-side despite the prefill " \
                f"pool: prefix_hit={srv.get('prefix_hit')!r}"
            assert int(srv.get("admission_dispatches") or 0) == 0, \
                f"request {i} issued {srv['admission_dispatches']} " \
                f"admission dispatches on a decode worker"
        if resil.get("cluster", {}).get("requeues"):
            requeued_ok += 1
    assert bit_exact + typed == n_req, \
        f"loss: {n_req} submitted, {bit_exact} exact + {typed} typed"

    # -- per-worker accounting: the disaggregation split --------------------
    for name, w in wm.items():
        assert "error" not in w, f"worker {name} metrics RPC: {w}"
        if w["role"] == "prefill":
            assert w["chunk_dispatches"] == 0, \
                f"prefill worker {name} ran decode chunks: {w}"
            assert w["prefill_dispatches"] > 0, \
                f"prefill worker {name} never prefilled: {w}"
        elif w["role"] == "decode" and disaggregated:
            assert w["prefill_dispatches"] == 0, \
                f"decode worker {name} ran its own prefills: {w}"
    assert any(w.get("chunk_dispatches", 0) > 0 for w in wm.values()
               if "error" not in w), "no live worker ran decode chunks"
    if disaggregated:
        assert m["disaggregated_admissions"] >= n_req, m

    # -- fleet observability: per-worker-labelled live scrape ---------------
    assert fleet_text is not None, "fleet /metrics was never scraped"
    for name in pids:
        assert f'worker="{name}"' in fleet_text, \
            f"fleet /metrics missing worker-labelled samples for {name}"
    assert "serving_cluster_submitted" in fleet_text, \
        "fleet /metrics missing the frontend's own registry"
    assert "cluster" in statusz and any(
        k.startswith("worker:") for k in statusz), \
        f"fleet /statusz missing per-worker blocks: {list(statusz)}"

    if faults:
        assert killed_pid is not None, \
            "fault drill never fired: the run finished too quickly"
        assert m["worker_deaths"] >= 1 and m["requeued"] >= 1, m
        assert requeued_ok >= 1, \
            "no request survived the SIGKILL requeue bit-exactly"
        states = m["states"]
        assert states[victim] == "dead", states

    useful = int(lens.sum())
    lat = np.asarray([finish_at[r] for r in rids if r in finish_at
                      and not isinstance(outcomes[r], BaseException)])
    p99 = float(np.percentile(lat, 99)) if lat.size else float("nan")
    print(f"serve-cluster: spec {spec} ({len(pids)} worker processes), "
          f"{n_req} requests, faults={'on' if faults else 'off'} — "
          f"{bit_exact} bit-exact + {typed} typed = ZERO lost; "
          f"{m['requeued']} requeued, deaths {m['worker_deaths']}, "
          f"{m['disaggregated_admissions']} disaggregated admissions, "
          f"{useful / wall:.0f} tok/s, p99 {p99 * 1e3:.0f}ms",
          file=sys.stderr)
    line = _emit("serving_cluster_tokens_per_sec",
                 round(useful / wall, 1), "tokens/sec")
    line["serve_cluster"] = {
        "spec": spec, "workers": {n: {"pid": p} for n, p in pids.items()},
        "slots_per_decode": slots, "chunk_size": chunk,
        "requests": n_req, "sigkill": killed_pid,
        "bit_exact": bit_exact, "typed_errors": typed,
        "lost": n_req - bit_exact - typed,
        "requeued": m["requeued"],
        "requeued_bit_exact": requeued_ok,
        "worker_deaths": m["worker_deaths"],
        "disaggregated_admissions": m["disaggregated_admissions"],
        "disaggregation_fallbacks": m["disaggregation_fallbacks"],
        "worker_states": m["states"],
        "worker_dispatches": {
            n: {"prefill": w.get("prefill_dispatches"),
                "chunk": w.get("chunk_dispatches")}
            for n, w in wm.items() if "error" not in w},
        "latency_p99_s": round(p99, 4),
        "wall_s": round(wall, 3),
    }
    print(json.dumps(line))
    return line


def bench_serve_rolling(spec="prefill:1,decode:2", n_requests=None,
                        slots=None, chunk=None):
    """``--serve --cluster prefill:1,decode:2 --rolling-restart``: the
    zero-downtime fleet-operations gate — REAL OS worker processes,
    live DecodeState migration, a rolling restart of EVERY worker while
    the fleet keeps serving, and a proactive SUSPECT evacuation.

    Three drills, all hard-asserted in-bench:

    - greedy pass: a delayed-heartbeat fault plan (inherited by the
      decode1 worker process through the environment) makes its
      heartbeat go stale mid-run WITHOUT dying — the router must mark
      it SUSPECT and migrate its in-flight rows to peers BEFORE any
      TTL fires (``proactive_evacuations >= 1``, ``worker_deaths ==
      0``); then ``rolling_restart()`` cycles every worker under load.
      Every accepted request must resolve bit-exact vs an undisturbed
      in-process solo decode: ZERO lost, zero typed errors.
    - sampled pass: the same rolling restart over a
      ``request_keyed_rng`` + ``do_sample`` decode pool — migration
      ships the live per-row RNG key, so sampled continuations are
      bit-exact vs an undisturbed solo ServingEngine too.
    - hot-reload epilogue: new weights are staged versioned, ONE
      worker is respawned onto them (content-derived version changes),
      migration between the mixed-version workers is refused typed
      (``WeightVersionError``), and the reloaded worker serves the NEW
      parameters bit-exactly."""
    import os as _os
    import tempfile

    import numpy as np

    from paddle_tpu.inference.generate import LlamaDecoder
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.runtime.resilience import WeightVersionError
    from paddle_tpu.serving import launch_cluster, parse_cluster_spec
    from paddle_tpu.serving.engine import ServingEngine

    roles = parse_cluster_spec(spec)
    cfg = LlamaConfig(vocab_size=256, hidden_size=64,
                      intermediate_size=128, num_hidden_layers=2,
                      num_attention_heads=4, num_key_value_heads=4,
                      max_position_embeddings=256)
    n_req = n_requests or 8
    slots = slots or 8
    chunk = chunk or 4
    prompt_len, len_pool = 8, (6, 10, 14)
    model = LlamaForCausalLM(cfg)
    max_len = prompt_len + max(len_pool) + 8
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, (prompt_len,))
               for _ in range(n_req)]
    lens = rng.choice(len_pool, n_req)
    solo_dec = LlamaDecoder(model, max_len=max_len)
    solo = [np.asarray(solo_dec.generate(prompts[i][None], int(lens[i])))
            for i in range(n_req)]

    # -- pass A: greedy + proactive SUSPECT + rolling restart ---------------
    # the stale-heartbeat drill rides the environment into the decode1
    # worker process: beat normally ~1.2s, then go silent for ~3.6s —
    # stale past suspect_after_s but far inside the 12s TTL, then resume
    plan = json.dumps([{"kind": "delay_heartbeat", "node": "decode1",
                        "after_beats": 4, "skip_beats": 12}])
    old_plan = _os.environ.get("PADDLE_TPU_FAULT_PLAN")
    _os.environ["PADDLE_TPU_FAULT_PLAN"] = plan
    workdir = tempfile.mkdtemp(prefix="bench_rolling_")
    t0 = time.perf_counter()
    try:
        cl = launch_cluster(
            model, workdir, prefill=roles["prefill"],
            decode=roles["decode"], unified=roles["unified"],
            max_len=max_len,
            engine_kw={"num_slots": slots, "chunk_size": chunk},
            heartbeat_s=0.3, ttl_s=12.0, suspect_after_s=1.8,
            rpc_timeout_s=30.0)
    finally:
        if old_plan is None:
            _os.environ.pop("PADDLE_TPU_FAULT_PLAN", None)
        else:
            _os.environ["PADDLE_TPU_FAULT_PLAN"] = old_plan
    with cl:
        router = cl.router
        live = [h.name for h in router.workers]
        assert all(h.weights_version for h in router.workers), \
            f"workers registered without a weights version: " \
            f"{[(h.name, h.weights_version) for h in router.workers]}"
        rids = [router.submit(prompts[i], int(lens[i]))
                for i in range(n_req)]
        restart_report, waves = None, 0
        while router.in_flight():
            router.step()
            m = router.metrics()
            # the rolling restart fires ONCE, mid-run, only after the
            # proactive drill has been observed — both must land while
            # requests are genuinely in flight
            if (restart_report is None
                    and m["proactive_evacuations"] >= 1
                    and router.in_flight() >= 2):
                restart_report = router.rolling_restart()
            if not router.in_flight() and restart_report is None:
                # the drill outran the queue: keep the fleet busy with
                # another wave of the SAME requests (same rng ids are
                # irrelevant under greedy)
                waves += 1
                assert waves <= 30, \
                    "proactive SUSPECT drill never fired in 30 waves"
                extra = [router.submit(prompts[i], int(lens[i]))
                         for i in range(n_req)]
                rids.extend(extra)
                solo.extend(solo[:n_req])
        wall_a = time.perf_counter() - t0
        m = router.metrics()
        assert restart_report is not None, \
            "rolling restart never fired: proactive evacuation was " \
            f"not observed while requests were in flight ({m})"
        restarted = [r["name"] for r in restart_report["restarted"]]
        assert sorted(restarted) == sorted(live), \
            f"rolling restart skipped workers: {restarted} vs {live}"
        assert m["proactive_evacuations"] >= 1, m
        assert m["migrations"] >= 1, m
        assert m["worker_deaths"] == 0, \
            f"the proactive drill leaked into a real death: {m}"
        for i, rid in enumerate(rids):
            out = router.outcome(rid)
            assert out is not None and not isinstance(out, BaseException), \
                f"greedy request {i} lost or errored: {out!r}"
            assert np.array_equal(np.asarray(out), solo[i]), \
                f"greedy request {i} diverged across migration/restart"

        # -- hot weight reload epilogue ---------------------------------
        model2 = LlamaForCausalLM(cfg)  # fresh init = different params
        staged = cl.stage_weights(model2)
        d0 = next(h for h in router.workers if h.name == "decode0")
        d1 = next(h for h in router.workers if h.name == "decode1")
        v_old = d0.weights_version
        d0.state = "restarting"
        router._sync_healthy()
        try:
            router._call(d0, "shutdown", timeout=5.0)
        except Exception:
            pass
        info = cl.respawn(d0)
        d0.pid = int(info["pid"])
        d0.obs_port = int(info.get("obs_port", d0.obs_port))
        d0.weights_version = info.get("weights_version")
        d0.state = "healthy"
        router._sync_healthy()
        assert d0.weights_version and d0.weights_version != v_old, \
            f"hot reload did not change the content version " \
            f"({v_old} -> {d0.weights_version})"
        # settle the fleet first: a worker still marked suspect from a
        # late stale-heartbeat window (first-chunk compile stalls the
        # worker GIL) recovers on the next idle sweep — migrate's
        # health validation must not mask the version refusal
        settle_by = time.monotonic() + 60.0
        while any(h.state == "suspect" for h in router.workers):
            assert time.monotonic() < settle_by, \
                f"fleet never settled: " \
                f"states={[(h.name, h.state) for h in router.workers]} " \
                f"ages={[(h.name, router.elastic.beat_age(h.name)) for h in router.workers]} " \
                f"members={router.elastic.members} " \
                f"procs={[(r, p.poll()) for r, p in cl.procs.items()]}"
            router.step()
            time.sleep(0.2)
        # mixed-version fleet: migration must refuse typed. Routing
        # happens at submit and queued requests are migratable, so no
        # step() runs between submit and the refusal (a step could
        # flip fleet states mid-check)
        solo2_dec = LlamaDecoder(model2, max_len=max_len)
        solo2 = np.asarray(solo2_dec.generate(prompts[0][None],
                                              int(lens[0])))
        rid2 = router.submit(prompts[0], int(lens[0]))
        src = router._handle(router._tracked[rid2].worker)
        dst = d1 if src.rank == d0.rank else d0
        try:
            router.migrate([rid2], src, dst)
            raise AssertionError(
                "mixed-version migrate was not refused")
        except WeightVersionError:
            pass
        router.drain(max_steps=500)
        out2 = router.outcome(rid2)
        assert out2 is not None and not isinstance(out2, BaseException), \
            f"hot-reload request lost: {out2!r}"
        if src.rank == d0.rank:
            # served by the reloaded worker: the NEW parameters decode.
            # The prefill pool still runs v1 here, so the router's
            # cross-version slab guard must have refused disaggregation
            # (local prefill fallback) — otherwise v1 prefill KV would
            # silently corrupt a v2 decode
            assert np.array_equal(np.asarray(out2), solo2), \
                "hot-reloaded worker did not serve the staged weights"
            if any(h.role == "prefill" for h in router.workers):
                assert (router.metrics()["disaggregation_fallbacks"]
                        >= 1), \
                    "cross-version slab was shipped without fallback"
        reload_info = {"staged": _os.path.basename(staged),
                       "version_old": v_old,
                       "version_new": d0.weights_version,
                       "served_by_reloaded": src.rank == d0.rank}
        m_a = router.metrics()

    # -- pass B: request-keyed sampled bit-exactness ------------------------
    n_s = max(4, n_req // 2)
    temps = [0.7 + 0.1 * (i % 3) for i in range(n_s)]
    ref_dec = LlamaDecoder(model, max_len=max_len)
    ref_eng = ServingEngine(ref_dec, num_slots=slots, chunk_size=chunk,
                            do_sample=True, request_keyed_rng=True)
    ref_ids = [ref_eng.submit(prompts[i], int(lens[i]),
                              temperature=temps[i], seed=7,
                              rng_request_id=i)
               for i in range(n_s)]
    ref_out = {}
    while len(ref_out) < n_s:
        for rid, res in ref_eng.step():
            ref_out[rid] = np.asarray(res)
    sampled_ref = [ref_out[r] for r in ref_ids]

    t1 = time.perf_counter()
    workdir_b = tempfile.mkdtemp(prefix="bench_rolling_s_")
    with launch_cluster(
            model, workdir_b, prefill=0, decode=2, max_len=max_len,
            engine_kw={"num_slots": slots, "chunk_size": chunk,
                       "do_sample": True},
            request_keyed_rng=True, heartbeat_s=0.3, ttl_s=12.0,
            rpc_timeout_s=30.0) as cl2:
        router2 = cl2.router
        rids_s = [router2.submit(prompts[i], int(lens[i]),
                                 temperature=temps[i], seed=7)
                  for i in range(n_s)]
        restarted_s = None
        steps = 0
        while router2.in_flight():
            router2.step()
            steps += 1
            if restarted_s is None and steps >= 2 \
                    and router2.in_flight() >= 2:
                restarted_s = router2.rolling_restart()
        wall_b = time.perf_counter() - t1
        m_b = router2.metrics()
        assert restarted_s is not None and \
            len(restarted_s["restarted"]) == 2, restarted_s
        assert m_b["migrations"] >= 1, \
            f"sampled rolling restart moved nothing live: {m_b}"
        assert m_b["worker_deaths"] == 0, m_b
        for i, rid in enumerate(rids_s):
            out = router2.outcome(rid)
            assert out is not None and not isinstance(out, BaseException), \
                f"sampled request {i} lost or errored: {out!r}"
            assert np.array_equal(np.asarray(out), sampled_ref[i]), \
                f"sampled request {i} diverged across migration/restart " \
                f"(the live RNG key did not ride the payload)"

    useful = int(lens.sum())
    print(f"serve-rolling: spec {spec} — greedy: {len(rids)} requests "
          f"bit-exact through {m_a['rolling_restarts']} rolling "
          f"restarts + {m_a['proactive_evacuations']} proactive "
          f"evacuations ({m_a['migrations']} rows migrated, 0 deaths, "
          f"{wall_a:.1f}s); sampled: {n_s} requests bit-exact through "
          f"{m_b['rolling_restarts']} restarts ({m_b['migrations']} "
          f"migrated, {wall_b:.1f}s); hot reload {reload_info['version_old']}"
          f" -> {reload_info['version_new']}, mixed-version migrate "
          f"refused typed", file=sys.stderr)
    line = _emit("serving_rolling_restart_workers",
                 float(m_a["rolling_restarts"]), "workers")
    line["serve_rolling"] = {
        "spec": spec,
        "greedy": {
            "requests": len(rids), "bit_exact": len(rids), "lost": 0,
            "rolling_restarts": m_a["rolling_restarts"],
            "proactive_evacuations": m_a["proactive_evacuations"],
            "evacuations": m_a["evacuations"],
            "migrations": m_a["migrations"],
            "worker_deaths": m_a["worker_deaths"],
            "slab_retries": m_a["slab_retries"],
            "wall_s": round(wall_a, 3),
        },
        "sampled": {
            "requests": n_s, "bit_exact": n_s, "lost": 0,
            "rolling_restarts": m_b["rolling_restarts"],
            "migrations": m_b["migrations"],
            "wall_s": round(wall_b, 3),
        },
        "hot_reload": reload_info,
    }
    print(json.dumps(line))
    return line


def bench_serve_frontend_failover(spec="prefill:1,decode:2",
                                  n_requests=None, slots=None,
                                  chunk=None):
    """``--serve --cluster prefill:1,decode:2 --kill-frontend``: the
    control-plane-SPOF gate — REAL OS processes end to end. The store
    daemon hosts the rendezvous, the frontend runs as its own process
    with a durable WAL, and mid-run — with at least 2 requests in
    flight AND 2 queued — it is SIGKILLed. A respawned frontend
    (``resume_wal=...``) must recover EVERY accepted request (resumed
    in place or ledger-replayed, counted separately) bit-exact vs an
    undisturbed run, and a zombie op stamped with the dead
    incarnation's epoch must be refused typed (``StaleEpochError``).
    Two passes: greedy, and request-keyed sampled (the RNG resume
    point rides the WAL)."""
    import os
    import tempfile

    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.serving import parse_cluster_spec
    from paddle_tpu.serving.cluster.frontend_proc import \
        run_frontend_failover_drill

    roles = parse_cluster_spec(spec)
    prefill = roles["prefill"]
    decode = roles["decode"] + roles["unified"]
    cfg = LlamaConfig(vocab_size=64, hidden_size=32,
                      intermediate_size=64, num_hidden_layers=2,
                      num_attention_heads=4, num_key_value_heads=4,
                      max_position_embeddings=64)
    model = LlamaForCausalLM(cfg)
    n_req = n_requests or 8
    slots = slots or 2
    chunk = chunk or 4
    workdir = tempfile.mkdtemp(prefix="bench_ffo_")
    passes = {}
    for label, sampled in (("greedy", False), ("sampled", True)):
        t0 = time.perf_counter()
        base = run_frontend_failover_drill(
            model, os.path.join(workdir, f"{label}_base"),
            prefill=prefill, decode=decode, n_requests=n_req,
            kill=False, sampled=sampled, num_slots=slots,
            chunk_size=chunk)
        killed = run_frontend_failover_drill(
            model, os.path.join(workdir, f"{label}_kill"),
            prefill=prefill, decode=decode, n_requests=n_req,
            kill=True, sampled=sampled, num_slots=slots,
            chunk_size=chunk)
        wall = time.perf_counter() - t0
        ready = killed["ready"]
        assert ready["occupied"] >= 2 and ready["queued"] >= 2, \
            f"{label}: the SIGKILL window had too little live work " \
            f"(occupied={ready['occupied']}, queued={ready['queued']})"
        assert killed["zombie_error"] == "StaleEpochError", \
            f"{label}: zombie frontend not fenced typed " \
            f"({killed['zombie_error']!r})"
        rep = killed["recovery"]
        accounted = (rep["finished_in_wal"] + rep["finished_in_gap"]
                     + rep["resumed"] + rep["replayed"])
        assert accounted == len(base["outcomes"]), \
            f"{label}: recovery lost requests: {rep} vs " \
            f"{len(base['outcomes'])} accepted"
        lost = sum(1 for o in killed["outcomes"].values()
                   if "unresolved" in o or "error" in o)
        assert lost == 0, \
            f"{label}: {lost} accepted requests lost to the frontend " \
            f"kill: {killed['outcomes']}"
        mismatched = [tag for tag, out in base["outcomes"].items()
                      if killed["outcomes"].get(tag) != out]
        assert not mismatched, \
            f"{label}: {len(mismatched)} requests diverged across the " \
            f"frontend failover: {mismatched}"
        passes[label] = {
            "requests": len(base["outcomes"]),
            "bit_exact": len(base["outcomes"]), "lost": 0,
            "killed_with_inflight": ready["occupied"],
            "killed_with_queued": ready["queued"],
            "epoch_before": ready["epoch"],
            "epoch_after": killed["epoch"],
            "resumed_in_place": rep["resumed"],
            "replayed": rep["replayed"],
            "finished_in_wal": rep["finished_in_wal"],
            "finished_in_gap": rep["finished_in_gap"],
            "wal_records": rep["wal_records"],
            "zombie_fenced": killed["zombie_error"],
            "wall_s": round(wall, 3),
        }
        print(f"serve-frontend-failover[{label}]: SIGKILL at "
              f"occupied={ready['occupied']}/queued={ready['queued']}, "
              f"epoch {ready['epoch']} -> {killed['epoch']}, "
              f"{rep['resumed']} resumed + {rep['replayed']} replayed "
              f"+ {rep['finished_in_gap']} finished-in-gap, "
              f"{len(base['outcomes'])} bit-exact, zombie fenced "
              f"typed ({wall:.1f}s)", file=sys.stderr)
    line = _emit("serving_frontend_failover_recovered",
                 float(passes["greedy"]["resumed_in_place"]
                       + passes["greedy"]["replayed"]
                       + passes["greedy"]["finished_in_gap"]),
                 "requests")
    line["serve_frontend_failover"] = {"spec": spec, **passes}
    print(json.dumps(line))
    return line


def bench_serve_prefix(n_groups=None, slots=None, chunk=None, mesh=None):
    """``--serve --prefix-mix``: the prefix-cache serving benchmark.

    A shared-prompt arrival mix — G "system prompts", each reused by
    several requests with distinct suffixes, plus exact-duplicate and
    unique cold prompts — served twice over the SAME decoder: (a) COLD,
    prefix cache disabled (every admission recomputes its full
    prefill), (b) CACHED, with the content-hashed slab pool + batched
    same-bucket admission on. Reports hit rate, prefill-dispatches-
    avoided, bytes cached and admission p50/p99 split by hit class.

    Contract checks (hard asserts): every cached-run result is
    BIT-EXACT vs a solo greedy generate (and therefore vs the cold
    run); full-prefix-hit admissions performed ZERO prefill dispatches
    (per-request ``admission_dispatches`` == 0 and the engine-level
    dispatch ledger balances exactly); the cached run's prefill
    dispatch count is STRICTLY below the cold run's; and full-hit
    admission p50 is STRICTLY below cold(miss) admission p50. With
    PADDLE_TPU_OBS=1 the record's ``obs`` block carries the hit-rate +
    bytes-cached accounting (engine registry + cache stats)."""
    import numpy as np

    import jax
    from paddle_tpu.inference.generate import LlamaDecoder
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.serving import ServingEngine

    on_tpu = jax.devices()[0].platform == "tpu"
    if on_tpu:
        import jax.numpy as jnp
        cfg = LlamaConfig(vocab_size=32000, hidden_size=768,
                          intermediate_size=2048, num_hidden_layers=12,
                          num_attention_heads=12, num_key_value_heads=12,
                          max_position_embeddings=1024, dtype="bfloat16")
        G = n_groups or 3
        slots = slots or 8
        chunk = chunk or 16
        block, prefix_len, suffix_len, n_new = 32, 64, 16, 32
        per_group, n_dups, n_unique = 5, 6, 4
    else:
        cfg = LlamaConfig(vocab_size=256, hidden_size=64,
                          intermediate_size=128, num_hidden_layers=2,
                          num_attention_heads=4, num_key_value_heads=4,
                          max_position_embeddings=256)
        G = n_groups or 3
        slots = slots or 4
        chunk = chunk or 4
        block, prefix_len, suffix_len, n_new = 4, 12, 4, 6
        per_group, n_dups, n_unique = 4, 8, 4
    model = LlamaForCausalLM(cfg)
    if on_tpu:
        for p in model.parameters():
            p._set_value(p.value.astype(jnp.bfloat16))
    mesh_obj = _bench_mesh(mesh)
    max_len = prefix_len + suffix_len + n_new + 8
    dec = LlamaDecoder(model, max_len=max_len, mesh=mesh_obj)
    rng = np.random.default_rng(0)

    # the arrival mix, in two phases so reuse can actually hit (a
    # prefix only serves admissions AFTER the admission that cached it):
    # phase A seeds the pool (one leader per shared prefix + uniques),
    # phase B is the steady-state tenant traffic (exact duplicates ->
    # full hits; shared-prefix suffix variants -> partial hits; fresh
    # uniques -> misses, exercising batched same-bucket admission)
    prefixes = [rng.integers(0, cfg.vocab_size, (prefix_len,))
                for _ in range(G)]
    leader = [np.concatenate([pre,
                              rng.integers(0, cfg.vocab_size,
                                           (suffix_len,))])
              for pre in prefixes]
    phase_a = list(leader) + [
        rng.integers(0, cfg.vocab_size, (prefix_len + suffix_len,))
        for _ in range(n_unique)]
    phase_b = []
    for _ in range(n_dups):                       # full hits
        phase_b.append(leader[0])
    for g in range(G):                            # partial hits
        for _ in range(per_group - 1):
            phase_b.append(np.concatenate(
                [prefixes[g], rng.integers(0, cfg.vocab_size,
                                           (suffix_len,))]))
    for _ in range(n_unique):                     # cold misses
        phase_b.append(rng.integers(0, cfg.vocab_size,
                                    (prefix_len + suffix_len,)))
    rng.shuffle(phase_b)
    requests = phase_a + phase_b
    n_req = len(requests)
    solo = [np.asarray(dec.generate(p[None], n_new)) for p in requests]
    useful = n_req * n_new

    def run(use_cache):
        eng = ServingEngine(
            dec, num_slots=slots, chunk_size=chunk,
            prefix_cache=bool(use_cache),
            prefix_cache_bytes=(1 << 30) if use_cache else None,
            prefix_block_tokens=block if use_cache else None,
            batch_admission=bool(use_cache))
        t0 = time.perf_counter()
        ids_a = [eng.submit(p, n_new, seed=i)
                 for i, p in enumerate(phase_a)]
        eng.drain()
        ids_b = [eng.submit(p, n_new, seed=1000 + i)
                 for i, p in enumerate(phase_b)]
        eng.drain()
        wall = time.perf_counter() - t0
        results = [eng.result(r) for r in ids_a + ids_b]
        return eng, results, wall

    # warm every compiled program both runs hit (prefill buckets, chunk
    # program, scatter/extract/load, suffix prefill) so the timed
    # admission histograms measure steady state, not compiles
    warm_eng, _, _ = run(True)
    del warm_eng
    run(False)

    run_mark = _obs_mark()
    eng_cold, res_cold, wall_cold = run(False)
    eng_hot, res_hot, wall_hot = run(True)
    m_cold, m_hot = eng_cold.metrics(), eng_hot.metrics()
    pc = m_hot["prefix_cache"]

    # -- the contract, hard-asserted ---------------------------------------
    for i in range(n_req):
        got_c, got_h = np.asarray(res_cold[i]), np.asarray(res_hot[i])
        assert np.array_equal(got_c, solo[i]), \
            f"request {i}: COLD output diverged from solo generate"
        assert np.array_equal(got_h, solo[i]), \
            f"request {i}: CACHED output diverged from solo generate"
    full_recs = [r.resilience["serving"] for r in res_hot
                 if r.resilience["serving"]["prefix_hit"] == "full"]
    assert full_recs, "prefix mix produced no full-prefix hits"
    assert all(r["admission_dispatches"] == 0 for r in full_recs), \
        "a full-prefix hit issued a prefill dispatch"
    assert pc["engine_hits_full"] >= n_dups - 1, \
        f"expected >= {n_dups - 1} full hits, got {pc}"
    assert pc["engine_hits_partial"] >= 1, f"no partial hits: {pc}"
    hit_rate = (pc["engine_hits_full"] + pc["engine_hits_partial"]) \
        / n_req
    assert hit_rate > 0, f"hit rate 0: {pc}"
    assert m_hot["prefill_dispatches"] < m_cold["prefill_dispatches"], \
        f"cached prefills {m_hot['prefill_dispatches']} not below " \
        f"cold {m_cold['prefill_dispatches']}"
    # the admission ledger balances exactly: every non-full admission
    # needed a prefill, minus the dispatches batching + full hits saved
    assert m_hot["prefill_dispatches"] == (
        pc["engine_misses"] + pc["engine_hits_partial"]
        + pc["engine_hits_full"] - m_hot["admission_dispatches_saved"]), \
        f"admission ledger does not balance: {m_hot}"
    p50_full = m_hot["admission_p50_s"]["full"]
    p50_cold = m_cold["admission_p50_s"]["miss"]
    assert p50_full < p50_cold, \
        f"full-hit admission p50 {p50_full} not below cold " \
        f"admission p50 {p50_cold}"

    obs_block = _obs_finish(run_mark, "obs_trace_serve_prefix.json",
                            prefix_cache=dict(pc),
                            hit_rate=round(hit_rate, 4),
                            bytes_cached=pc["bytes_cached"],
                            engine_metrics_prometheus=eng_hot.registry
                            .to_prometheus())
    avoided = m_cold["prefill_dispatches"] - m_hot["prefill_dispatches"]
    print(f"serve-prefix: hit rate {hit_rate:.2f} "
          f"({pc['engine_hits_full']} full / "
          f"{pc['engine_hits_partial']} partial / "
          f"{pc['engine_misses']} miss over {n_req} requests), "
          f"prefills {m_hot['prefill_dispatches']} vs cold "
          f"{m_cold['prefill_dispatches']} ({avoided} avoided), "
          f"{pc['prefill_tokens_saved']} prefill tokens saved, "
          f"{pc['bytes_cached']} bytes cached, admission p50 "
          f"full {p50_full*1e3:.2f}ms vs cold {p50_cold*1e3:.2f}ms, "
          f"parity checked on {n_req} requests x2", file=sys.stderr)
    line = _emit("serving_prefix_hit_rate_pct", hit_rate * 100, "%")
    mesh_rec = None
    if dec.sharding is not None:
        mesh_rec = dec.sharding.describe()
        mesh_rec.pop("partition_rules", None)
    line["serve_prefix"] = {
        "config": "134M" if on_tpu else "tiny-cpu",
        "requests": n_req, "slots": slots, "chunk_size": chunk,
        "block_tokens": block, "prefix_len": prefix_len,
        "groups": G, "duplicates": n_dups, "mesh": mesh_rec,
        "cold": {
            "prefill_dispatches": m_cold["prefill_dispatches"],
            "wall_s": round(wall_cold, 3),
            "tokens_per_sec": round(useful / wall_cold, 1),
            "admission_p50_s": m_cold["admission_p50_s"]["miss"],
            "admission_p99_s": m_cold["admission_p99_s"]["miss"],
        },
        "cached": {
            "prefill_dispatches": m_hot["prefill_dispatches"],
            "wall_s": round(wall_hot, 3),
            "tokens_per_sec": round(useful / wall_hot, 1),
            "hit_rate": round(hit_rate, 4),
            "hits_full": pc["engine_hits_full"],
            "hits_partial": pc["engine_hits_partial"],
            "misses": pc["engine_misses"],
            "prefill_tokens_saved": pc["prefill_tokens_saved"],
            "admission_dispatches_saved":
                m_hot["admission_dispatches_saved"],
            "batched_admission_groups":
                m_hot["batched_admission_groups"],
            "bytes_cached": pc["bytes_cached"],
            "slabs": pc["slabs"],
            "evictions": pc["evictions"],
            "admission_p50_s": m_hot["admission_p50_s"],
            "admission_p99_s": m_hot["admission_p99_s"],
        },
        "prefill_dispatches_avoided": avoided,
        "zero_dispatch_full_hits": len(full_recs),
        "parity_checked": n_req,
    }
    line["obs"] = obs_block
    # re-print the enriched record as the LAST stdout line (the driver
    # parses the final json line; _emit already printed the bare metric)
    print(json.dumps(line))
    return line


def bench_serve_http(n_requests=None, adapters=3, slots=None, chunk=None):
    """``--serve --http [--adapters N]``: the multi-tenant HTTP gate.

    One ``HttpFrontend`` over a LoRA-multiplexed engine, driven by REAL
    concurrent HTTP round-trips (half unary, half chunk-streamed) with
    requests spread over the base model + N registered adapters. Hard
    asserts:
    - every HTTP token sequence (unary body AND streamed-chunk
      concatenation) is BIT-EXACT vs the direct in-process engine on
      the same submissions — transport never changes tokens;
    - dispatch accounting via the decoder's own counter: every device
      dispatch is one admission prefill or ONE fused chunk shared by
      all in-flight tenants (zero per-token steps, zero host scatters,
      nothing hidden behind the socket);
    - the live ``/metrics`` scrape carries a per-adapter row counter
      for every tenant that sent traffic, summing to the request
      count, and ``/statusz`` exposes the adapter registry;
    - graceful drain: ``/healthz`` flips 503 and new generates shed
      typed while accepted work still answers."""
    import json as _json
    import threading
    import urllib.error
    import urllib.request

    import numpy as np

    from paddle_tpu.inference.generate import LlamaDecoder
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.serving import ServingEngine
    from paddle_tpu.serving.http import HttpFrontend
    from paddle_tpu.serving.lora import AdapterStore

    cfg = LlamaConfig(vocab_size=256, hidden_size=64,
                      intermediate_size=128, num_hidden_layers=2,
                      num_attention_heads=4, num_key_value_heads=4,
                      max_position_embeddings=256)
    n_req = n_requests or 12
    n_ad = max(1, int(adapters))
    slots = slots or 4
    chunk = chunk or 8
    prompt_len, len_pool = 8, (4, 8, 16)
    model = LlamaForCausalLM(cfg)
    dec = LlamaDecoder(model, max_len=prompt_len + max(len_pool))

    H, F = cfg.hidden_size, cfg.intermediate_size
    proj = []
    for li in range(cfg.num_hidden_layers):
        pre = f"model.layers.{li}."
        proj += [(pre + "self_attn.qkv.weight", H,
                  int(dec.params[pre + "self_attn.qkv.weight"].shape[-1])),
                 (pre + "self_attn.o_proj.weight", H, H),
                 (pre + "mlp.gate_up.weight", H, 2 * F),
                 (pre + "mlp.down_proj.weight", F, H)]
    rng = np.random.default_rng(0)
    store = AdapterStore()
    for j in range(n_ad):
        r = 2 + (j % 3)
        store.register(f"ad{j}", {
            pn: (0.05 * rng.standard_normal((din, r)),
                 0.05 * rng.standard_normal((r, dout)))
            for pn, din, dout in proj})

    prompts = [rng.integers(0, cfg.vocab_size, (prompt_len,))
               for _ in range(n_req)]
    lens = [int(len_pool[i % len(len_pool)]) for i in range(n_req)]
    # round-robin over base + every adapter: >= 3 adapters + base rows
    # genuinely share chunks once slots fill
    ads = [None if i % (n_ad + 1) == 0 else f"ad{i % (n_ad + 1) - 1}"
           for i in range(n_req)]

    # direct-engine reference, same submissions
    ref_eng = ServingEngine(dec, num_slots=slots, chunk_size=chunk,
                            adapter_store=store)
    rids = [ref_eng.submit(p, n, adapter=a, seed=i)
            for i, (p, n, a) in enumerate(zip(prompts, lens, ads))]
    refs = ref_eng.drain()
    want = {i: np.asarray(refs[r]).reshape(-1) for i, r in enumerate(rids)}

    eng = ServingEngine(dec, num_slots=slots, chunk_size=chunk,
                        adapter_store=store)
    fe = HttpFrontend(eng, port=0)
    port = fe.start()
    base = f"http://127.0.0.1:{port}"
    print(f"serve_http: frontend on {base} ({n_ad} adapters, "
          f"{n_req} requests)", file=sys.stderr)

    d0 = dec.dispatch_count
    results = {}

    def _roundtrip(i):
        body = {"prompt": [int(t) for t in prompts[i]],
                "max_new_tokens": lens[i], "adapter": ads[i],
                "seed": i, "stream": bool(i % 2)}
        req = urllib.request.Request(
            base + "/v1/generate", data=_json.dumps(body).encode(),
            headers={"Content-Type": "application/json"}, method="POST")
        with urllib.request.urlopen(req, timeout=300) as r:
            raw = r.read()
        if body["stream"]:
            lines = [_json.loads(ln) for ln in raw.splitlines() if ln]
            assert lines[-1].get("final") is True, lines[-1]
            gen = [t for ln in lines for t in ln["tokens"]]
            results[i] = ("stream", gen, len(lines))
        else:
            doc = _json.loads(raw)
            results[i] = ("unary", doc["tokens"], doc["generated"])

    t0 = time.perf_counter()
    threads = [threading.Thread(target=_roundtrip, args=(i,))
               for i in range(n_req)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.perf_counter() - t0

    # -- parity: HTTP tokens == direct engine, streamed and unary -----------
    for i in range(n_req):
        kind, toks, extra = results[i]
        if kind == "unary":
            assert toks == [int(t) for t in want[i]], \
                f"unary request {i} diverged over HTTP"
            assert extra == [int(t) for t in want[i][prompt_len:]]
        else:
            assert toks == [int(t) for t in want[i][prompt_len:]], \
                f"streamed request {i} diverged over HTTP"

    # -- dispatch accounting: nothing hidden behind the socket --------------
    m = eng.metrics()
    assert m["step_dispatches"] == 0, "per-token steps leaked in"
    assert m["admission_ring"]["host_scattered"] == 0
    assert dec.dispatch_count - d0 == \
        m["prefill_dispatches"] + m["chunk_dispatches"], \
        "device dispatches != admission prefills + fused chunks"
    rows = m["adapters"]["rows_by_adapter"]
    assert sum(rows.values()) == n_req

    # -- live scrape: per-adapter counters visible over HTTP ----------------
    with urllib.request.urlopen(base + "/metrics", timeout=30) as r:
        scrape = r.read().decode()
    for j in range(n_ad):
        if any(a == f"ad{j}" for a in ads):
            assert f"ad{j}" in scrape, \
                f"/metrics misses the ad{j} row counter"
    with urllib.request.urlopen(base + "/statusz", timeout=30) as r:
        statusz = _json.loads(r.read())
    assert statusz["default"]["adapters"]["adapters"], "no adapter block"

    # -- graceful drain ------------------------------------------------------
    assert fe.drain(timeout_s=60), "frontend failed to drain"
    try:
        urllib.request.urlopen(base + "/healthz", timeout=10)
        raise AssertionError("healthz must be 503 while draining")
    except urllib.error.HTTPError as e:
        assert e.code == 503
    fe.stop()

    tok = sum(lens)
    line = _emit("serve_http.tokens_per_s", tok / wall, "tok/s")
    streams = sum(1 for v in results.values() if v[0] == "stream")
    line.update({
        "requests": n_req, "streamed": streams, "unary": n_req - streams,
        "adapters": n_ad, "rows_by_adapter": rows,
        "chunk_dispatches": m["chunk_dispatches"],
        "prefill_dispatches": m["prefill_dispatches"],
        "stream_ttft_p50_s": m.get("stream_ttft_p50_s", {}),
        "parity_checked": n_req,
        "gates": {"http_parity": "bit-exact unary + streamed vs direct "
                                 "engine",
                  "dispatches": "prefills + fused chunks only",
                  "metrics": "per-adapter row counters in live scrape",
                  "drain": "healthz 503 + typed shed"},
    })
    print(json.dumps(line))
    return line


CONFIGS = {
    "moe": bench_moe,
    "llama": bench_llama,
    "resnet50": bench_resnet50,
    "bert": bench_bert,
    "unet": bench_unet,
    "ernie": bench_ernie,
    "decode": bench_decode,
    "decode_modes": bench_decode_modes,
    "decode1b": bench_decode_1b,
    "decode1b_served": bench_decode_1b_served,
    "serve": bench_serve,
    "serve_http": bench_serve_http,
    "serve_prefix": bench_serve_prefix,
    "serve_replicated": bench_serve_replicated,
}

def _run_guarded(name, fn, attempts=3, base_delay=5.0, sleep=time.sleep):
    """Run one bench config under the SHARED retry layer
    (runtime/resilience.resilient_call — the round-5 private
    ``TRANSIENT_MARKERS`` copy is gone): transient backend errors
    (UNAVAILABLE / DEADLINE_EXCEEDED / ABORTED / connection drops, plus
    RESOURCE_EXHAUSTED — bench runs are all setup phase) retry with
    exponential backoff. On final failure, emit a PARSEABLE BENCH json
    line carrying the failure class as the last stdout line — never a
    raw-traceback rc=1 tail — then exit nonzero (traceback goes to
    stderr)."""
    from paddle_tpu.runtime.resilience import classify_error, resilient_call

    retry_count = [0]

    def _log_retry(ev):
        retry_count[0] += 1
        print(f"{name}: transient backend failure "
              f"(attempt {ev.attempt}/{ev.max_attempts}, retrying in "
              f"{ev.delay_s:.0f}s): {ev.error}", file=sys.stderr)

    try:
        return resilient_call(fn, retries=attempts - 1, backoff=base_delay,
                              phase="setup", site=f"bench.{name}",
                              on_event=_log_retry, sleep=sleep)
    except SystemExit:
        raise
    except Exception as e:
        _emit_failure(name, e, attempts=retry_count[0] + 1)
        sys.exit(1)


def _emit_failure(name, e, attempts=1):
    """The parseable last-stdout-line BENCH failure record (never a raw
    rc=1 traceback tail — the round-5 evidence-loss class): the metric
    name, the resilient_call classifier's verdict and the error, with
    the traceback on stderr. Carries the probed-backend record (which
    platform and device kind the run was on) and, when obs is on, the
    metrics snapshot accumulated up to the failure."""
    from paddle_tpu.runtime.resilience import classify_error
    transient = classify_error(e, phase="setup") == "transient"
    import traceback
    traceback.print_exc(file=sys.stderr)
    record = {
        "metric": name, "value": None, "unit": None,
        "vs_baseline": None, "failed": True,
        "failure_class": ("backend_unavailable" if transient
                          else type(e).__name__),
        "error": str(e)[:400], "attempts": attempts,
        "backend": dict(_BACKEND),
    }
    try:
        import paddle_tpu.obs as obs
        record["obs"] = (obs.metrics.snapshot() if obs.enabled()
                         else {"enabled": False})
    except Exception:
        record["obs"] = None
    print(json.dumps(record))


# the probed-backend record every BENCH failure line carries: which
# platform and device kind the run was on
_BACKEND = {"status": "unprobed", "platform": None}


def _ensure_backend(devices_fn=None):
    """Probe the backend BEFORE any config runs, so that a backend that
    cannot initialize ends in main's structured failure record and a
    non-zero exit instead of a raw traceback out of the first config.
    Nothing here switches platform: whatever the init raises propagates,
    and no number is ever taken on a device the caller did not ask for."""
    import jax

    devs = (devices_fn or jax.devices)()
    _BACKEND.update(status="ok", platform=devs[0].platform,
                    device_kind=devs[0].device_kind, count=len(devs))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default="llama", choices=sorted(CONFIGS))
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--profile", action="store_true")
    ap.add_argument("--decode", action="store_true",
                    help="fused-decode microbenchmark: tokens/s + dispatch "
                         "counts for greedy/greedy+eos/sampled at several "
                         "batch sizes")
    ap.add_argument("--serve", action="store_true",
                    help="continuous-vs-static batching serving benchmark "
                         "(Poisson arrivals, mixed output lengths): "
                         "tokens/s, slot occupancy, p50/p99 latency, "
                         "dispatch counts")
    ap.add_argument("--serve-requests", type=int, default=None)
    ap.add_argument("--serve-slots", type=int, default=None)
    ap.add_argument("--serve-chunk", type=int, default=None)
    ap.add_argument("--replicas", type=int, default=0,
                    help="with --serve: replicated serving over N "
                         "independent engines behind the health-checked "
                         "Router — hard-asserts zero lost accepted "
                         "requests (bit-exact or typed error) and the "
                         "snapshot->restore round-trip")
    ap.add_argument("--cluster", default=None,
                    help="with --serve: multi-process disaggregated "
                         "serving over REAL OS worker processes, e.g. "
                         "'prefill:1,decode:2' — admission prefills on "
                         "the prefill pool ship to decode workers as KV "
                         "slabs; hard-asserts bit-exact parity vs an "
                         "in-process solo decode, the per-worker "
                         "dispatch split, and (with --faults) zero lost "
                         "requests under a mid-run SIGKILL of a decode "
                         "worker")
    ap.add_argument("--rolling-restart", action="store_true",
                    help="with --serve --cluster: the zero-downtime "
                         "fleet-operations gate — live DecodeState "
                         "migration, a proactive SUSPECT evacuation "
                         "(stale-heartbeat fault plan), a rolling "
                         "restart of EVERY worker under load, and a "
                         "hot weight reload with typed mixed-version "
                         "migration refusal; greedy AND request-keyed "
                         "sampled bit-exactness vs undisturbed runs "
                         "are hard-asserted in-bench")
    ap.add_argument("--kill-frontend", action="store_true",
                    help="with --serve --cluster: the control-plane-"
                         "SPOF gate — SIGKILL the FRONTEND process "
                         "mid-run with work in flight AND queued; a "
                         "respawned frontend replays the durable WAL, "
                         "re-adopts the workers and must recover every "
                         "accepted request bit-exact (greedy AND "
                         "request-keyed sampled), with the dead "
                         "incarnation's epoch fenced typed "
                         "(StaleEpochError) — all hard-asserted "
                         "in-bench")
    ap.add_argument("--faults", action="store_true",
                    help="with --serve --replicas: inject the replica-"
                         "kill + delayed-heartbeat fault plan; with "
                         "--serve --cluster: SIGKILL a decode worker "
                         "process mid-run; report p99 under failure")
    ap.add_argument("--speculative", action="store_true",
                    help="with --serve: speculative continuous batching "
                         "vs the plain engine on the same workload — "
                         "hard-asserts bit-exact greedy parity, exact "
                         "dispatch accounting (prefills + draft "
                         "prefills + chunks, admission adds zero), "
                         "tokens_per_dispatch > 1.8 and a strict "
                         "chunk-dispatch reduction; composes with "
                         "--mesh (sharded speculative decode)")
    ap.add_argument("--http", action="store_true",
                    help="with --serve: the multi-tenant HTTP gate — an "
                         "HttpFrontend over a LoRA-multiplexed engine "
                         "driven by real concurrent HTTP round-trips "
                         "(unary + chunk-streamed); bit-exact token "
                         "parity vs the direct engine, fused-dispatch "
                         "accounting, per-adapter /metrics counters and "
                         "the graceful-drain contract are hard-asserted")
    ap.add_argument("--adapters", type=int, default=3,
                    help="with --serve --http: number of LoRA adapters "
                         "to register and spread requests over (plus "
                         "base-model rows)")
    ap.add_argument("--prefix-mix", action="store_true",
                    help="with --serve: the prefix-cache benchmark — a "
                         "shared-prompt arrival mix served cold vs "
                         "cached (content-hashed KV slab pool), "
                         "reporting hit rate, prefill-dispatches-"
                         "avoided and admission p50/p99 by hit class; "
                         "parity and zero-dispatch full hits are "
                         "hard-asserted in-bench")
    ap.add_argument("--mesh", default=None,
                    help="serve/decode on a device mesh, e.g. "
                         "'dp:2,tp:2': tensor-parallel decode over tp, "
                         "batch/slot-table over dp, the DecodeState "
                         "carry sharded on device (recorded in the "
                         "bench record). On CPU (JAX_PLATFORMS=cpu) a "
                         "virtual device mesh is forced automatically.")
    ap.add_argument("--steps", type=int, default=None,
                    help="override the --decode per-mode repetition "
                         "count (e.g. --decode --steps 2 with "
                         "PADDLE_TPU_OBS=1 as a quick obs pass)")
    ap.add_argument("--quant", default=None, choices=("int8w", "int8wk"),
                    help="decode dtype recipe: with --decode, run the "
                         "quantized-decode benchmark (tokens/s, "
                         "bytes-moved/dispatch vs fp32, parity gates "
                         "hard-asserted); with --serve, serve the "
                         "continuous-batching benchmark over the "
                         "quantized decoder (int8wk = int8 KV carry)")
    args = ap.parse_args()

    from paddle_tpu.runtime.compile_cache import enable_compile_cache
    enable_compile_cache()
    if args.mesh:
        import os
        axes = _parse_mesh(args.mesh)
        need = 1
        for v in axes.values():
            need *= int(v)
        # on the CPU harness the virtual device mesh must be forced
        # BEFORE jax initializes (XLA_FLAGS lands at backend init)
        if os.environ.get("JAX_PLATFORMS",
                          "").strip().lower().startswith("cpu"):
            from __graft_entry__ import _force_cpu_platform
            _force_cpu_platform(max(need, 8))
    try:
        _ensure_backend()
        if args.serve and args.cluster:
            # the multi-process modes are CPU drills (one process per chip)
            from paddle_tpu.serving.cluster.launch import refuse_tpu_parent
            refuse_tpu_parent()
    except Exception as e:
        _emit_failure("backend_init", e)
        sys.exit(1)
    if args.serve and args.cluster and args.kill_frontend:
        _run_guarded("serve_frontend_failover",
                     lambda: bench_serve_frontend_failover(
                         spec=args.cluster,
                         n_requests=args.serve_requests,
                         slots=args.serve_slots,
                         chunk=args.serve_chunk))
        return
    if args.serve and args.cluster and args.rolling_restart:
        _run_guarded("serve_rolling", lambda: bench_serve_rolling(
            spec=args.cluster, n_requests=args.serve_requests,
            slots=args.serve_slots, chunk=args.serve_chunk))
        return
    if args.serve and args.cluster:
        _run_guarded("serve_cluster", lambda: bench_serve_cluster(
            spec=args.cluster, n_requests=args.serve_requests,
            slots=args.serve_slots, chunk=args.serve_chunk,
            faults=args.faults))
        return
    if args.serve and args.replicas:
        _run_guarded("serve_replicated", lambda: bench_serve_replicated(
            n_requests=args.serve_requests, replicas=args.replicas,
            slots=args.serve_slots, chunk=args.serve_chunk,
            faults=args.faults))
        return
    if args.serve and args.speculative:
        _run_guarded("serve_spec", lambda: bench_serve_spec(
            n_requests=args.serve_requests, slots=args.serve_slots,
            chunk=args.serve_chunk, mesh=args.mesh))
        return
    if args.serve and args.http:
        _run_guarded("serve_http", lambda: bench_serve_http(
            n_requests=args.serve_requests, adapters=args.adapters,
            slots=args.serve_slots, chunk=args.serve_chunk))
        return
    if args.serve and args.prefix_mix:
        _run_guarded("serve_prefix", lambda: bench_serve_prefix(
            slots=args.serve_slots, chunk=args.serve_chunk,
            mesh=args.mesh))
        return
    if args.serve:
        _run_guarded("serve", lambda: bench_serve(
            n_requests=args.serve_requests, slots=args.serve_slots,
            chunk=args.serve_chunk, mesh=args.mesh, quant=args.quant))
        return
    if args.decode and args.quant:
        _run_guarded("decode_quant",
                     lambda: bench_decode_quant(quant=args.quant,
                                                steps=args.steps))
        return
    if args.decode:
        _run_guarded("decode_modes",
                     lambda: bench_decode_modes(steps=args.steps,
                                                mesh=args.mesh))
        return
    if args.all:
        # a config that fails ends the run there: failure line, exit 1
        for name in ("resnet50", "bert", "unet", "ernie"):
            _run_guarded(name, CONFIGS[name])
        _run_guarded("llama", lambda: bench_llama(profile=args.profile))
        return
    if args.config == "llama":
        _run_guarded("llama", lambda: bench_llama(profile=args.profile))
    elif args.config in ("bert", "ernie", "unet"):
        _run_guarded(args.config,
                     lambda: CONFIGS[args.config](profile=args.profile))
    else:
        _run_guarded(args.config, CONFIGS[args.config])


if __name__ == "__main__":
    main()
