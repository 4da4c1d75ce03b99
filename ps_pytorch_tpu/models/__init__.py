"""Flax model zoo — TPU-native re-designs of the reference's model_ops/.

Layout is NHWC (TPU-native) rather than the reference's NCHW; compute dtype is
configurable (bfloat16 by default for the MXU) with float32 parameters.
"""

from typing import Any

import jax.numpy as jnp

from ps_pytorch_tpu.models.lenet import LeNet
from ps_pytorch_tpu.models.resnet import (
    ResNet18, ResNet34, ResNet50, ResNet101, ResNet152,
    ResNet18_ImageNet, ResNet50_ImageNet,
)
from ps_pytorch_tpu.models.vgg import (
    VGG11, VGG13, VGG16, VGG19, VGG11_BN, VGG13_BN, VGG16_BN, VGG19_BN,
)

# --compute-dtype name -> dtype: the one table, for the CNN zoo below and for
# the LM classes (runtime/lm_eval.build_lm_model).
DTYPES = {"float32": jnp.float32, "bfloat16": jnp.bfloat16, "float16": jnp.float16}

# Name -> constructor, mirroring the reference registry (util.py:8-19) but
# covering the full family the reference defines (resnet.py:100-113,
# vgg.py:71-108), not just the four names its registry exposes.
_REGISTRY = {
    "LeNet": LeNet,
    "ResNet18": ResNet18,
    "ResNet34": ResNet34,
    "ResNet50": ResNet50,
    "ResNet101": ResNet101,
    "ResNet152": ResNet152,
    "VGG11": VGG11_BN,   # reference maps "VGG11" -> vgg11_bn (util.py:18-19)
    "VGG13": VGG13_BN,
    "VGG16": VGG16_BN,
    "VGG19": VGG19_BN,
    "ResNet18_ImageNet": ResNet18_ImageNet,
    "ResNet50_ImageNet": ResNet50_ImageNet,
    "VGG11_plain": VGG11,
    "VGG13_plain": VGG13,
    "VGG16_plain": VGG16,
    "VGG19_plain": VGG19,
}


def build_model(model_name: str, num_classes: int = 10,
                compute_dtype: Any = jnp.float32,
                conv_impl: str = "xla") -> Any:
    """Name -> Flax module (reference: ``util.py:8-19`` build_model).

    ``conv_impl="pallas"`` / ``"pallas_im2col"`` swap the stride-1 3x3
    convs of the ResNet and VGG families for the Pallas prototype
    (ops/pallas_conv.py; the suffix picks the MXU schedule, see
    resnet.pallas_variant); other families (LeNet's 5x5s) ignore it.
    """
    if isinstance(compute_dtype, str):
        compute_dtype = DTYPES[compute_dtype]
    try:
        ctor = _REGISTRY[model_name]
    except KeyError:
        raise ValueError(
            f"unknown model {model_name!r}; choose from {sorted(_REGISTRY)}") from None
    if conv_impl != "xla" and model_name.startswith(("ResNet", "VGG")):
        return ctor(num_classes=num_classes, dtype=compute_dtype,
                    conv_impl=conv_impl)
    return ctor(num_classes=num_classes, dtype=compute_dtype)


def model_names():
    return sorted(_REGISTRY)
