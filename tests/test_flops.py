"""FLOPs model tests (ps_pytorch_tpu/utils/flops.py).

The reference has nothing to cite here — MFU is this framework's own bar
(VERDICT r1 missing-item 2). Exactness is checked on closed-form cases;
model-level counts are checked against independently derivable figures.
"""

import jax
import jax.numpy as jnp
import pytest

from ps_pytorch_tpu.models import build_model
from ps_pytorch_tpu.utils.flops import (
    count_jaxpr_flops, forward_flops, peak_flops_bf16, training_flops,
)


def test_dense_matmul_exact():
    f = lambda a, b: a @ b
    n = forward_flops(f, jnp.zeros((64, 128)), jnp.zeros((128, 256)))
    assert n == 2 * 64 * 128 * 256


def test_batched_dot_general_exact():
    f = lambda a, b: jnp.einsum("bij,bjk->bik", a, b)
    n = forward_flops(f, jnp.zeros((4, 8, 16)), jnp.zeros((4, 16, 32)))
    assert n == 2 * 4 * 8 * 16 * 32


def test_conv_exact():
    # SAME conv: out 1x32x32x64, kernel 3x3x3x64 ->
    # 2 * (1*32*32*64) * 3*3*3 flops.
    def f(x, k):
        return jax.lax.conv_general_dilated(
            x, k, (1, 1), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"))
    n = forward_flops(f, jnp.zeros((1, 32, 32, 3)), jnp.zeros((3, 3, 3, 64)))
    assert n == 2 * (32 * 32 * 64) * (3 * 3 * 3)


def test_grouped_conv_divides_flops():
    def make(groups):
        def f(x, k):
            return jax.lax.conv_general_dilated(
                x, k, (1, 1), "SAME",
                dimension_numbers=("NHWC", "HWIO", "NHWC"),
                feature_group_count=groups)
        return f
    dense = forward_flops(make(1), jnp.zeros((1, 16, 16, 8)),
                          jnp.zeros((3, 3, 8, 8)))
    grouped = forward_flops(make(4), jnp.zeros((1, 16, 16, 8)),
                            jnp.zeros((3, 3, 2, 8)))
    assert grouped == dense // 4


def test_recurses_through_jit_and_remat():
    f = lambda a, b: a @ b
    n_plain = forward_flops(f, jnp.zeros((32, 32)), jnp.zeros((32, 32)))
    n_jit = forward_flops(jax.jit(f), jnp.zeros((32, 32)), jnp.zeros((32, 32)))
    n_remat = forward_flops(jax.checkpoint(f), jnp.zeros((32, 32)),
                            jnp.zeros((32, 32)))
    assert n_plain == n_jit == n_remat == 2 * 32**3


def test_scan_multiplies_body():
    def f(x):
        def body(c, _):
            return c @ x, None
        out, _ = jax.lax.scan(body, x, None, length=5)
        return out
    n = forward_flops(f, jnp.zeros((16, 16)))
    assert n == 5 * 2 * 16**3


def test_shard_map_body_counts_on_every_device(mesh8):
    # The body's shapes are per-shard; all 8 devices run it, so the sharded
    # matmul costs what the global one does (on 4 real chips the step's MFU
    # read 4x low before this was counted).
    from jax.sharding import PartitionSpec as P
    f = jax.shard_map(lambda a, b: a @ b, mesh=mesh8,
                      in_specs=(P("data"), P()), out_specs=P("data"))
    n = forward_flops(f, jnp.zeros((64, 16)), jnp.zeros((16, 32)))
    assert n == 2 * 64 * 16 * 32


def test_pallas_kernel_counts_once_per_grid_point():
    # A pallas_call's jaxpr is ONE block's work: the flash kernel must be
    # charged the same dense S x S matmuls as the materializing path.
    from ps_pytorch_tpu.ops.flash_attention import flash_attention
    from ps_pytorch_tpu.parallel.ring import full_attention
    q = jnp.zeros((2, 2, 256, 64))
    n_flash = forward_flops(
        lambda q: flash_attention(q, q, q, causal=True, block_q=128,
                                  block_kv=128), q)
    n_full = forward_flops(lambda q: full_attention(q, q, q, causal=True), q)
    assert n_flash == n_full == 2 * (2 * 2 * 2 * 256 * 256 * 64)
    # whatever the schedule (the kernels' loops over live tiles have trip
    # counts the walker cannot see); the one backward pass is five products
    assert forward_flops(
        lambda q: flash_attention(q, q, q, causal=True), q) == n_full
    n_grad = forward_flops(jax.grad(
        lambda q: flash_attention(q, q, q, causal=True).sum()), q)
    assert n_grad == n_full // 2 * 7


@pytest.mark.parametrize("dv", [64, 128, 32])
def test_flash_charges_the_keys_and_the_values_widths_apart(dv):
    """A value of its own width: the scores, dQ and dK are products over the
    keys' width, the weighted sum, dP and dV over the value's: ``d + dv``
    forward and ``3 d + 2 dv`` backward, what ``full_attention`` is charged
    on the same ``(q, k, v)``."""
    from ps_pytorch_tpu.ops.flash_attention import flash_attention
    from ps_pytorch_tpu.parallel.ring import full_attention
    q, v = jnp.zeros((1, 4, 256, 64)), jnp.zeros((1, 2, 256, dv))
    k = jnp.zeros((1, 2, 256, 64))
    per_width = 2 * 4 * 256 * 256
    n_flash = forward_flops(
        lambda q, k, v: flash_attention(q, k, v, causal=True), q, k, v)
    assert n_flash == per_width * (64 + dv) == forward_flops(
        lambda q, k, v: full_attention(q, k, v, causal=True), q, k, v)
    n_grad = forward_flops(jax.grad(
        lambda q, k, v: flash_attention(q, k, v, causal=True).sum(),
        argnums=(0, 1, 2)), q, k, v)
    assert n_grad == per_width * (64 + dv + 3 * 64 + 2 * dv)


def test_strided_conv_backward_multiple_is_sane():
    """grad-input and grad-weight of a conv each cost ~1x forward, so
    value_and_grad should be ~3x forward — for STRIDED convs too (the
    grad-input conv carries lhs_dilation=stride; naive counting overcounts
    it by stride^2)."""
    def f(x, k):
        return jax.lax.conv_general_dilated(
            x, k, (2, 2), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC")).sum()
    x = jnp.zeros((1, 32, 32, 8))
    k = jnp.zeros((3, 3, 8, 16))
    fwd = forward_flops(f, x, k)
    both = forward_flops(jax.value_and_grad(f, argnums=(0, 1)), x, k)
    assert 2.7 <= both / fwd <= 3.3


def test_resnet18_training_flops_plausible():
    """CIFAR ResNet-18 forward is ~1.1 GF/image (2*MAC convention, 0.556 GMACs
    published for the 3x3-stem CIFAR variant); fwd+bwd lands in 2.5-3.2x fwd
    (first/last layers' grad-input is skipped or cheap)."""
    model = build_model("ResNet18", 10, jnp.bfloat16)
    train = training_flops(model, (8, 32, 32, 3), 10) / 8
    assert 2.7e9 < train < 3.7e9


def test_training_flops_scales_linearly_with_batch():
    model = build_model("LeNet", 10, jnp.float32)
    f8 = training_flops(model, (8, 28, 28, 1), 10)
    f16 = training_flops(model, (16, 28, 28, 1), 10)
    assert abs(f16 / f8 - 2.0) < 0.05


def test_peak_flops_table():
    assert peak_flops_bf16("TPU v5 lite") == pytest.approx(197e12)
    assert peak_flops_bf16("TPU v5e") == pytest.approx(197e12)
    assert peak_flops_bf16("TPU v4") == pytest.approx(275e12)
    assert peak_flops_bf16("TPU v5p") == pytest.approx(459e12)
    assert peak_flops_bf16("cpu") is None
    assert peak_flops_bf16("") is None
    # An unknown TPU generation is an error, not "MFU n/a".
    with pytest.raises(ValueError, match="TPU v9x"):
        peak_flops_bf16("TPU v9x")
