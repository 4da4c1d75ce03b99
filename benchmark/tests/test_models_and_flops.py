"""Each plain reference against the program's model at a small size, and each
closed-form FLOPs function against the program's jaxpr walk."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import harness
from conftest import BENCH

FILES = harness.Files()


@pytest.fixture(scope="module")
def resnet():
    return (FILES.json("configs", "resnet18_cifar10.json"),
            FILES.module("reference", "resnet18_cifar10.py"))


@pytest.fixture(scope="module")
def gpt2():
    return (FILES.json("configs", "gpt2_medium.json"),
            FILES.module("reference", "gpt2_medium.py"))


def test_resnet18_reference_agrees_with_the_program(resnet):
    """float32 on both sides, BatchNorm statistics and affine randomised so a
    dropped or misplaced normalisation would show."""
    from ps_pytorch_tpu.models import build_model
    config, ref = resnet
    model = build_model("ResNet18", 10, "float32")
    x = jax.random.normal(jax.random.key(0), (2, 32, 32, 3))
    variables = model.init(jax.random.key(1), x, train=False)
    leaves, tree = jax.tree.flatten(variables)
    keys = jax.random.split(jax.random.key(2), len(leaves))
    leaves = [a + 0.1 * jax.random.uniform(k, a.shape) if a.ndim == 1 else a
              for a, k in zip(leaves, keys)]
    variables = jax.tree.unflatten(tree, leaves)
    with jax.default_matmul_precision("highest"):
        got = model.apply(variables, x, train=False)
    want = ref.forward(variables, x, config)
    assert got.shape == want.shape == (2, 10)
    np.testing.assert_allclose(got, want, atol=2e-4 * float(jnp.abs(want).max()))
    n = sum(a.size for a in jax.tree.leaves(variables["params"]))
    assert n == ref.param_count(config) == config["parameters"] == 11_173_962


def test_resnet18_closed_form_flops_within_2_percent(resnet):
    from ps_pytorch_tpu.models import build_model
    from ps_pytorch_tpu.utils.flops import training_flops
    config, ref = resnet
    walked = training_flops(build_model("ResNet18", 10, "bfloat16"),
                            (2, 32, 32, 3), 10) / 2
    assert ref.train_flops_per_sample(config) == pytest.approx(walked, rel=0.02)


def _small_lm(gpt2, **kw):
    from ps_pytorch_tpu.models.transformer import TransformerLM
    config = dict(gpt2[0], n_embd=64, n_layer=2, n_head=4, vocab_size=96)
    model = TransformerLM(vocab_size=96, d_model=64, n_layers=2, n_heads=4,
                          max_seq_len=32, **kw)
    tokens = jax.random.randint(jax.random.key(0), (2, 32), 0, 96)
    params = model.init(jax.random.key(1), tokens)["params"]
    return config, model, params, tokens


@pytest.mark.parametrize("attention", ["full", "flash"])
def test_gpt2_reference_agrees_with_the_program(gpt2, attention):
    config, model, params, tokens = _small_lm(gpt2, attention_impl=attention)
    # biases and LayerNorm affine start at 0 and 1: move them, so that a
    # dropped bias or scale would show
    leaves, tree = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.key(2), len(leaves))
    params = jax.tree.unflatten(tree, [
        a + 0.1 * jax.random.normal(k, a.shape) if a.ndim == 1 else a
        for a, k in zip(leaves, keys)])
    with jax.default_matmul_precision("highest"):
        got = model.apply({"params": params}, tokens)
    want = gpt2[1].forward({"params": params}, tokens, config)
    np.testing.assert_allclose(got, want, atol=2e-4 * float(jnp.abs(want).max()))
    n = sum(a.size for a in jax.tree.leaves(params))
    assert n == gpt2[1].param_count(config, 32)


def test_gpt2_medium_parameter_counts(gpt2):
    config, ref = gpt2
    assert ref.param_count(config, 1024) == config["parameters_as_run_s1024"]
    tied_biased = config["parameters_as_run_s1024"] \
        - config["n_embd"] * config["vocab_size"] \
        + config["n_layer"] * 4 * config["n_embd"]
    assert tied_biased == config["parameters_published"]


def test_gpt2_closed_form_flops_within_2_percent(gpt2):
    import optax
    from ps_pytorch_tpu.utils.flops import forward_flops
    config, model, params, tokens = _small_lm(gpt2, attention_impl="full")

    def loss(params):
        logits = model.apply({"params": params}, tokens)
        return optax.softmax_cross_entropy_with_integer_labels(
            logits[:, :-1], tokens[:, 1:]).mean()

    walked = forward_flops(jax.value_and_grad(loss), params) / tokens.size
    assert gpt2[1].train_flops_per_sample(config, seq_len=32) == \
        pytest.approx(walked, rel=0.02)
    real = gpt2[1].train_flops_per_sample(gpt2[0], seq_len=1024)
    assert real == pytest.approx(2.4227e9, rel=1e-3)


def test_flash_cost_function():
    cost = harness.load_module(os.path.join(
        BENCH, "kernel_costs", "flash_attention_causal.py"))
    shape = {"batch": 4, "seq_len": 1024, "heads": 16, "head_dim": 64,
             "layers": 24, "activation_dtypes": ["float32"]}
    flops, nbytes = cost.required_per_step(shape)
    # half of the dense 3 x 4 S d FLOPs per token per layer that the MFU
    # formula charges
    assert flops == pytest.approx(0.5 * 3 * 4 * 1024 * 1024 * 24 * 4096)
    bf16 = cost.required_per_step(dict(shape, activation_dtypes=["bfloat16"]))
    assert bf16[0] == flops and bf16[1] < nbytes
