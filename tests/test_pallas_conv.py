"""Correctness pins for the Pallas 3x3 conv prototype (ops/pallas_conv.py)
against lax.conv_general_dilated — interpret mode on the CPU mesh, same
semantics the chip compiles (ops/_backend.py)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ps_pytorch_tpu.ops.pallas_conv import conv3x3, conv3x3_input_grad


def _xla_conv(x, w):
    return jax.lax.conv_general_dilated(
        x, w, window_strides=(1, 1), padding="SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        preferred_element_type=jnp.float32).astype(x.dtype)


@pytest.mark.parametrize("variant", ["taps9", "im2col"])
@pytest.mark.parametrize("shape,cout", [
    ((4, 8, 8, 16), 16),       # tiny, fast
    ((2, 32, 32, 64), 64),     # the trace's hot geometry (small batch)
    ((3, 8, 8, 16), 8),        # N not divisible by block_n; Cin != Cout
])
def test_matches_xla_f32(shape, cout, variant):
    kx, kw = jax.random.split(jax.random.key(0))
    x = jax.random.normal(kx, shape, jnp.float32)
    w = jax.random.normal(kw, (3, 3, shape[-1], cout), jnp.float32) * 0.1
    np.testing.assert_allclose(np.asarray(conv3x3(x, w, variant=variant)),
                               np.asarray(_xla_conv(x, w)),
                               rtol=1e-5, atol=1e-5)


def test_matches_xla_bf16():
    kx, kw = jax.random.split(jax.random.key(1))
    x = jax.random.normal(kx, (2, 16, 16, 32), jnp.bfloat16)
    w = jax.random.normal(kw, (3, 3, 32, 32), jnp.bfloat16) * 0.1
    # Both sides accumulate f32 and cast once; identical tap order is not
    # guaranteed, so compare at bf16 resolution.
    np.testing.assert_allclose(
        np.asarray(conv3x3(x, w), np.float32),
        np.asarray(_xla_conv(x, w), np.float32), rtol=2e-2, atol=2e-2)


@pytest.mark.parametrize("variant", ["taps9", "im2col"])
def test_input_grad_matches_autodiff(variant):
    kx, kw, kg = jax.random.split(jax.random.key(2), 3)
    x = jax.random.normal(kx, (2, 8, 8, 16), jnp.float32)
    w = jax.random.normal(kw, (3, 3, 16, 16), jnp.float32) * 0.1
    g = jax.random.normal(kg, (2, 8, 8, 16), jnp.float32)
    _, vjp = jax.vjp(lambda xx: _xla_conv(xx, w), x)
    np.testing.assert_allclose(
        np.asarray(conv3x3_input_grad(g, w, variant=variant)),
        np.asarray(vjp(g)[0]), rtol=1e-5, atol=1e-5)


def test_conv3x3_op_vjp_matches_autodiff():
    """The differentiable op (custom VJP: Pallas fwd + input-grad, XLA dW)
    must agree with autodiff through the XLA conv in BOTH cotangents."""
    from ps_pytorch_tpu.ops.pallas_conv import conv3x3_op
    kx, kw = jax.random.split(jax.random.key(4))
    x = jax.random.normal(kx, (2, 8, 8, 16), jnp.float32)
    w = jax.random.normal(kw, (3, 3, 16, 16), jnp.float32) * 0.1

    def scalar(f):
        return lambda xx, ww: (f(xx, ww) ** 2).mean()

    gx_p, gw_p = jax.grad(scalar(conv3x3_op), argnums=(0, 1))(x, w)
    gx_r, gw_r = jax.grad(scalar(_xla_conv), argnums=(0, 1))(x, w)
    np.testing.assert_allclose(np.asarray(gx_p), np.asarray(gx_r),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(gw_p), np.asarray(gw_r),
                               rtol=1e-4, atol=1e-4)


def test_resnet_conv_impl_pallas_matches_xla():
    """ResNet18 with conv_impl='pallas': identical param tree (explicit
    legacy conv names -> checkpoints interchangeable) and matching
    forward + parameter gradients against the XLA build."""
    from ps_pytorch_tpu.models import build_model
    mx = build_model("ResNet18", 10, "float32")
    mp = build_model("ResNet18", 10, "float32", conv_impl="pallas")
    x = jax.random.normal(jax.random.key(0), (2, 32, 32, 3), jnp.float32)
    vx = mx.init(jax.random.key(1), x, train=False)
    vp = mp.init(jax.random.key(1), x, train=False)
    assert jax.tree.structure(vx) == jax.tree.structure(vp)
    ox = mx.apply(vx, x, train=False)
    op = mp.apply(vx, x, train=False)       # xla params into the pallas net
    np.testing.assert_allclose(np.asarray(ox), np.asarray(op),
                               rtol=1e-5, atol=1e-5)

    def loss_grads(m):
        def f(p):
            out, _ = m.apply({"params": p,
                              "batch_stats": vx["batch_stats"]}, x,
                             train=True, mutable=["batch_stats"])
            return (out ** 2).mean()
        return jax.grad(f)(vx["params"])

    gx, gp = loss_grads(mx), loss_grads(mp)
    deltas = jax.tree.map(
        lambda a, b: float(jnp.max(jnp.abs(a - b))), gx, gp)
    assert max(jax.tree.leaves(deltas)) < 1e-5, deltas


def test_vgg_conv_impl_pallas_matches_xla():
    """VGG11 pallas build: same param tree (biased convs, He fan-out init)
    and matching forward on shared params."""
    from ps_pytorch_tpu.models import build_model
    mx = build_model("VGG11", 10, "float32")
    mp = build_model("VGG11", 10, "float32", conv_impl="pallas")
    x = jax.random.normal(jax.random.key(0), (2, 32, 32, 3), jnp.float32)
    vx = mx.init(jax.random.key(1), x, train=False)
    vp = mp.init(jax.random.key(1), x, train=False)
    assert jax.tree.structure(vx) == jax.tree.structure(vp)
    ox = mx.apply(vx, x, train=False)
    op = mp.apply(vx, x, train=False)
    np.testing.assert_allclose(np.asarray(ox), np.asarray(op),
                               rtol=1e-4, atol=1e-4)


def test_bottleneck_pallas_param_tree_matches_xla():
    """ResNet50 (Bottleneck) structure pin via eval_shape: the explicit
    Conv_0..Conv_3 names must produce the same tree either impl — a naming
    slip would silently break legacy-checkpoint loads for pallas builds."""
    from ps_pytorch_tpu.models import build_model
    mx = build_model("ResNet50", 10, "float32")
    mp = build_model("ResNet50", 10, "float32", conv_impl="pallas")
    x = jnp.zeros((1, 32, 32, 3), jnp.float32)
    sx = jax.eval_shape(lambda: mx.init(jax.random.key(0), x, train=False))
    sp = jax.eval_shape(lambda: mp.init(jax.random.key(0), x, train=False))
    assert jax.tree.structure(sx) == jax.tree.structure(sp)


@pytest.mark.slow
def test_pallas_conv_under_8dev_spmd_step():
    """conv3x3_op's custom VJP inside the jitted masked-psum SPMD train
    step over the 8-device mesh (shard_map + donate + optimizer): the path
    ``--conv-impl pallas`` takes through train.py."""
    from ps_pytorch_tpu.config import TrainConfig
    from ps_pytorch_tpu.models import build_model
    from ps_pytorch_tpu.optim import build_optimizer
    from ps_pytorch_tpu.parallel import (
        create_train_state, make_mesh, make_train_step,
    )
    cfg = TrainConfig(dataset="synthetic", network="ResNet18", batch_size=16,
                      lr=0.1, momentum=0.9, compute_dtype="float32",
                      conv_impl="pallas")
    mesh = make_mesh(data=len(jax.devices()))
    model = build_model(cfg.network, cfg.num_classes, cfg.compute_dtype,
                        conv_impl=cfg.conv_impl)
    tx = build_optimizer(cfg)
    state = create_train_state(model, tx, mesh, (1, 32, 32, 3),
                               jax.random.key(0))
    step_fn = make_train_step(model, tx, mesh, state, donate=True)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(16, 32, 32, 3)).astype(np.float32))
    y = jnp.asarray(rng.integers(0, 10, 16).astype(np.int32))
    mask = jnp.ones(mesh.shape["data"], jnp.float32)
    for i in range(2):
        state, m = step_fn(state, x, y, mask, jax.random.key(i))
    jax.block_until_ready(state.params)
    assert np.isfinite(float(m["loss"]))
    assert float(m["participating"]) == len(jax.devices())


def test_rejects_bad_shapes():
    x = jnp.zeros((2, 8, 8, 16))
    with pytest.raises(ValueError, match="3,3"):
        conv3x3(x, jnp.zeros((5, 5, 16, 16)))
    with pytest.raises(ValueError, match="3,3"):
        conv3x3(x, jnp.zeros((3, 3, 8, 16)))
    with pytest.raises(ValueError, match="variant"):
        conv3x3(x, jnp.zeros((3, 3, 16, 16)), variant="winograd")
