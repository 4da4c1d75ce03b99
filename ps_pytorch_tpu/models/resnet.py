"""CIFAR ResNet family — TPU-native re-design of the reference
``model_ops/resnet.py`` (BasicBlock/Bottleneck ``:14-64``, stem+stages ``:67-97``,
constructors ``:100-113``).

Architecture parity: 3x3 stride-1 stem (no maxpool, CIFAR variant), stages
[64,128,256,512] with strides [1,2,2,2], projection shortcut when shape
changes, 4x4 average pool, linear head. BatchNorm semantics follow the
reference: running stats are *replica-local* in distributed training (the
reference excludes BN running stats from weight sync,
``distributed_worker.py:245-252``); see parallel/dp.py for how that is
reproduced on the mesh.

NHWC layout, configurable compute dtype (bfloat16 for the MXU).
"""

from functools import partial
from typing import Any, Sequence

import flax.linen as nn
import jax.numpy as jnp

from ps_pytorch_tpu.telemetry.trace import device_scope

Conv = partial(nn.Conv, use_bias=False)


class PallasConv3x3(nn.Module):
    """3x3 stride-1 SAME conv backed by the Pallas prototype
    (ops/pallas_conv.py, custom VJP: Pallas fwd + input-grad, XLA dW).
    Param names/shapes/inits match ``nn.Conv``, so ``xla`` and ``pallas``
    conv_impl checkpoints are interchangeable (ResNets: bias-free; VGG:
    biased with He fan-out init — pass the same kernel_init/use_bias the
    nn.Conv call sites use)."""
    features: int
    dtype: Any = jnp.float32
    variant: str = "taps9"
    use_bias: bool = False
    kernel_init: Any = nn.initializers.lecun_normal()

    @nn.compact
    def __call__(self, x):
        from ps_pytorch_tpu.ops.pallas_conv import conv3x3_op
        kernel = self.param(
            "kernel", self.kernel_init,
            (3, 3, x.shape[-1], self.features), jnp.float32)
        out = conv3x3_op(x.astype(self.dtype), kernel.astype(self.dtype),
                         self.variant)
        if self.use_bias:
            bias = self.param("bias", nn.initializers.zeros,
                              (self.features,), jnp.float32)
            out = out + bias.astype(self.dtype)   # XLA fuses the add
        return out


def pallas_variant(conv_impl: str) -> str:
    """MXU schedule for a ``pallas*`` conv_impl: ``pallas`` -> taps9,
    ``pallas_im2col`` -> im2col. One mapping for ResNet and VGG, so an
    im2col schedule accepted by an A/B on the chip is adoptable from config
    alone."""
    return "im2col" if conv_impl == "pallas_im2col" else "taps9"


def _conv3(planes, dtype, conv_impl, name=None):
    """The 3x3 stride-1 conv used everywhere in the CIFAR ResNets: XLA by
    default; the Pallas path (either MXU schedule) when the A/B accepted
    it for this geometry."""
    if conv_impl.startswith("pallas"):
        return PallasConv3x3(planes, dtype=dtype, name=name,
                             variant=pallas_variant(conv_impl))
    return Conv(planes, (3, 3), padding=1, dtype=dtype, name=name)


class BasicBlock(nn.Module):
    planes: int
    stride: int = 1
    dtype: Any = jnp.float32
    conv_impl: str = "xla"
    expansion = 1

    @nn.compact
    def __call__(self, x, train: bool = True):
        # Conv names are EXPLICIT and equal to the legacy flax auto-names
        # ("Conv_<k>" in creation order): the pallas path substitutes a
        # different module TYPE for the stride-1 3x3s, and auto-naming
        # would both shift the numbering and collide across types —
        # explicit names keep xla/pallas checkpoints interchangeable.
        norm = partial(nn.BatchNorm, use_running_average=not train,
                       momentum=0.9, epsilon=1e-5, dtype=self.dtype)
        with device_scope("conv"):
            if self.stride == 1:
                out = _conv3(self.planes, self.dtype, self.conv_impl,
                             name="Conv_0")(x)
            else:
                out = Conv(self.planes, (3, 3),
                           strides=(self.stride, self.stride),
                           padding=1, dtype=self.dtype, name="Conv_0")(x)
        with device_scope("batchnorm"):
            out = nn.relu(norm()(out))
        with device_scope("conv"):
            out = _conv3(self.planes, self.dtype, self.conv_impl,
                         name="Conv_1")(out)
        with device_scope("batchnorm"):
            out = norm()(out)
        with device_scope("shortcut"):
            if self.stride != 1 or x.shape[-1] != self.planes * self.expansion:
                x = Conv(self.planes * self.expansion, (1, 1),
                         strides=(self.stride, self.stride), dtype=self.dtype,
                         name="Conv_2")(x)
                x = norm()(x)
            return nn.relu(out + x)


class Bottleneck(nn.Module):
    planes: int
    stride: int = 1
    dtype: Any = jnp.float32
    conv_impl: str = "xla"
    expansion = 4

    @nn.compact
    def __call__(self, x, train: bool = True):
        # Explicit legacy names — see BasicBlock.
        norm = partial(nn.BatchNorm, use_running_average=not train,
                       momentum=0.9, epsilon=1e-5, dtype=self.dtype)
        with device_scope("conv"):
            out = Conv(self.planes, (1, 1), dtype=self.dtype,
                       name="Conv_0")(x)
        with device_scope("batchnorm"):
            out = nn.relu(norm()(out))
        with device_scope("conv"):
            if self.stride == 1:
                out = _conv3(self.planes, self.dtype, self.conv_impl,
                             name="Conv_1")(out)
            else:
                out = Conv(self.planes, (3, 3),
                           strides=(self.stride, self.stride),
                           padding=1, dtype=self.dtype, name="Conv_1")(out)
        with device_scope("batchnorm"):
            out = nn.relu(norm()(out))
        with device_scope("conv"):
            out = Conv(self.planes * self.expansion, (1, 1), dtype=self.dtype,
                       name="Conv_2")(out)
        with device_scope("batchnorm"):
            out = norm()(out)
        with device_scope("shortcut"):
            if self.stride != 1 or x.shape[-1] != self.planes * self.expansion:
                x = Conv(self.planes * self.expansion, (1, 1),
                         strides=(self.stride, self.stride), dtype=self.dtype,
                         name="Conv_3")(x)
                x = norm()(x)
            return nn.relu(out + x)


class ResNet(nn.Module):
    block: Any
    num_blocks: Sequence[int]
    num_classes: int = 10
    dtype: Any = jnp.float32
    conv_impl: str = "xla"   # "pallas": stride-1 3x3s via ops/pallas_conv
    # (stem conv1 stays XLA — C_in=3 starves the lane dimension)
    imagenet_stem: bool = False  # 7x7/s2 conv + 3x3/s2 maxpool (torchvision
    # semantics) for 224px inputs — the ResNet-50/ImageNet config is NEW vs
    # the reference (BASELINE.json config 5); the CIFAR stem is the
    # reference's (``model_ops/resnet.py:69-71``).

    @nn.compact
    def __call__(self, x, train: bool = True):
        # x: [B, H, W, 3] NHWC (32px CIFAR or 224px ImageNet)
        with device_scope("conv"):
            x = x.astype(self.dtype)
            if self.imagenet_stem:
                x = Conv(64, (7, 7), strides=(2, 2), padding=3,
                         dtype=self.dtype, name="conv1")(x)
            else:
                x = Conv(64, (3, 3), padding=1, dtype=self.dtype,
                         name="conv1")(x)
        with device_scope("batchnorm"):
            x = nn.relu(nn.BatchNorm(use_running_average=not train,
                                     momentum=0.9, epsilon=1e-5,
                                     dtype=self.dtype, name="bn1")(x))
            if self.imagenet_stem:
                x = nn.max_pool(x, (3, 3), strides=(2, 2),
                                padding=((1, 1), (1, 1)))
        for stage, (planes, n, stride) in enumerate(
                zip((64, 128, 256, 512), self.num_blocks, (1, 2, 2, 2))):
            for i in range(n):
                x = self.block(planes, stride if i == 0 else 1,
                               dtype=self.dtype,
                               conv_impl=self.conv_impl)(x, train=train)
        with device_scope("head"):
            if self.imagenet_stem:
                x = x.mean(axis=(1, 2))      # global average pool (7x7 -> 1)
            else:
                x = nn.avg_pool(x, (4, 4), strides=(4, 4))  # reference :95
                x = x.reshape((x.shape[0], -1))
            x = nn.Dense(self.num_classes, dtype=self.dtype, name="linear")(x)
            return x.astype(jnp.float32)


def ResNet18(num_classes=10, dtype=jnp.float32, conv_impl="xla"):
    return ResNet(BasicBlock, (2, 2, 2, 2), num_classes, dtype, conv_impl)

def ResNet34(num_classes=10, dtype=jnp.float32, conv_impl="xla"):
    return ResNet(BasicBlock, (3, 4, 6, 3), num_classes, dtype, conv_impl)

def ResNet50(num_classes=10, dtype=jnp.float32, conv_impl="xla"):
    return ResNet(Bottleneck, (3, 4, 6, 3), num_classes, dtype, conv_impl)

def ResNet101(num_classes=10, dtype=jnp.float32, conv_impl="xla"):
    return ResNet(Bottleneck, (3, 4, 23, 3), num_classes, dtype, conv_impl)

def ResNet152(num_classes=10, dtype=jnp.float32, conv_impl="xla"):
    return ResNet(Bottleneck, (3, 8, 36, 3), num_classes, dtype, conv_impl)

def ResNet18_ImageNet(num_classes=1000, dtype=jnp.float32, conv_impl="xla"):
    return ResNet(BasicBlock, (2, 2, 2, 2), num_classes, dtype, conv_impl,
                  imagenet_stem=True)

def ResNet50_ImageNet(num_classes=1000, dtype=jnp.float32, conv_impl="xla"):
    return ResNet(Bottleneck, (3, 4, 6, 3), num_classes, dtype, conv_impl,
                  imagenet_stem=True)
