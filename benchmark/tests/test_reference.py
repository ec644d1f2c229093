"""The plain reference against the program at TINY_CONFIG width, float32,
on the CPU: the trainer's loss and gradients, and the decoder's logits
through its cache."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark.harness import model as mdl
from benchmark.reference import llama_block as ref


@pytest.fixture(scope="module")
def tiny():
    import paddle_tpu as paddle
    from paddle_tpu.models.llama import TINY_CONFIG, LlamaForCausalLM
    paddle.seed(123)
    model = LlamaForCausalLM(TINY_CONFIG)
    cfg = TINY_CONFIG
    arch = {"num_attention_heads": cfg.num_attention_heads,
            "num_key_value_heads": cfg.num_key_value_heads,
            "head_dim": cfg.head_dim, "rope_theta": cfg.rope_theta,
            "rms_norm_eps": cfg.rms_norm_eps,
            "intermediate_size": cfg.intermediate_size}
    rng = np.random.default_rng(0)
    ids = rng.integers(0, cfg.vocab_size, (2, 24), dtype=np.int32)
    labels = rng.integers(0, cfg.vocab_size, (2, 24), dtype=np.int32)
    return model, cfg, arch, ids, labels


def test_trainer_loss_and_gradients_match_the_reference(tiny):
    import paddle_tpu as paddle
    from paddle_tpu.parallel import ProcessMesh
    from paddle_tpu.parallel.train import ShardedTrainer
    model, cfg, arch, ids, labels = tiny
    before = {k: np.asarray(v) for k, v in mdl.state_arrays(model).items()}
    params = {k: jnp.asarray(v) for k, v in before.items()}
    want_loss, want_grads = jax.value_and_grad(ref.loss_fn)(
        params, ids, labels, arch, cfg.num_hidden_layers)
    ce = ref.cross_entropy(
        ids, labels, arch, cfg.num_hidden_layers,
        before["model.embed_tokens.weight"], ref.layer_weights_by_name(before),
        before["model.norm.weight"], before["lm_head.weight"])
    assert ce == pytest.approx(float(want_loss), rel=1e-6)
    # the trainer's own gradients: one SGD step at lr 1 moves every
    # parameter by exactly its gradient
    opt = paddle.optimizer.SGD(learning_rate=1.0,
                               parameters=model.parameters())
    mesh = ProcessMesh(shape=(1, 1, 1), dim_names=("dp", "sep", "mp"))
    trainer = ShardedTrainer(model, opt, lambda m, i, l: m.loss(i, l), mesh,
                             {})
    with mesh:
        loss = float(np.asarray(trainer.train_step(ids, labels).value))
    assert loss == pytest.approx(float(want_loss), rel=2e-5)
    after = mdl.state_arrays(model)
    for name, g in want_grads.items():
        got = before[name] - np.asarray(after[name])
        scale = float(np.abs(np.asarray(g)).max()) + 1e-12
        # float32 both sides; the subtraction of parameters of size ~1
        # leaves ~1e-7 absolute, so compare against the gradient's scale
        assert np.abs(got - np.asarray(g)).max() <= 2e-4 * scale + 3e-7, name


def test_decoder_cached_path_matches_the_reference_logits(tiny):
    import paddle_tpu as paddle
    from paddle_tpu.inference.generate import LlamaDecoder
    from paddle_tpu.models.llama import TINY_CONFIG, LlamaForCausalLM
    _, cfg, arch, ids, _ = tiny
    paddle.seed(321)
    model = LlamaForCausalLM(TINY_CONFIG)
    dec = LlamaDecoder(model, max_len=64)
    seq = ids[:1]
    P, K = 16, 6
    lw = mdl.layer_weights_from_decoder(dec.params, arch)
    want = np.asarray(ref.logits(
        seq[:, :P + K], arch, cfg.num_hidden_layers,
        dec.params["model.embed_tokens.weight"], lw,
        dec.params["model.norm.weight"], dec.params["lm_head.weight"],
        positions=np.arange(P - 1, P + K))[0])
    # the split of the fused weights gives back the model's own
    sd = mdl.state_arrays(model)
    a, b = ref.layer_weights_by_name(sd)(1), lw(1)
    assert all(np.array_equal(np.asarray(a[k]), np.asarray(b[k])) for k in a)
    kc, vc = dec._empty_cache(1)
    lg, kc, vc = dec._prefill(dec.params, jnp.asarray(seq[:, :P]), kc, vc)
    have = [np.asarray(lg[0])]
    for t in range(K):
        lg, kc, vc = dec._step(dec.params,
                               jnp.asarray(seq[:, P + t:P + t + 1]), kc, vc,
                               jnp.int32(P + t))
        have.append(np.asarray(lg[0]))
    # float32 on both sides: 1e-4 of a logit's spread, far under the 0.08
    # the bf16 sections are allowed on the chip
    err = np.abs(np.stack(have) - want).max(-1) / want.std(-1)
    assert err.max() < 1e-4
