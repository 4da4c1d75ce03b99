"""Plain float32 reference of ResNet-18 in its CIFAR form, and its FLOPs.

Written from the published architecture (He et al. 2015, basic blocks; the
CIFAR stem of kuangliu/pytorch-cifar that ``bapi/ps_pytorch``
``src/model_ops/resnet.py`` uses): 3x3 stride-1 stem with 64 channels and no
max-pool, four stages of two basic blocks at 64/128/256/512 channels with
strides 1/2/2/2, a 1x1 strided projection with BatchNorm on the shortcut
where the shape changes, 4x4 average pool, linear head. BatchNorm in
inference mode (running statistics, eps 1e-5).

Independent of ``ps_pytorch_tpu/models``: it takes the system's parameter
tree only as named arrays (``conv1``, ``bn1``, ``BasicBlock_<i>`` with
``Conv_0/1/2`` and ``BatchNorm_0/1/2``, ``linear``; NHWC activations, HWIO
kernels) and computes with ``jax.lax`` convolutions in float32 under
``highest`` matmul precision. No departures from the published network.
"""

import jax
import jax.numpy as jnp

EPS = 1e-5


def _conv(x, w, stride, pad):
    return jax.lax.conv_general_dilated(
        x, w.astype(jnp.float32), (stride, stride), ((pad, pad), (pad, pad)),
        dimension_numbers=("NHWC", "HWIO", "NHWC"))


def _bn(x, p, s):
    inv = jax.lax.rsqrt(s["var"].astype(jnp.float32) + EPS)
    return (x - s["mean"]) * inv * p["scale"] + p["bias"]


def forward(variables, x, config):
    """variables: {"params", "batch_stats"} of the system's model; x: [N, 32,
    32, 3]; -> float32 logits [N, num_classes]."""
    p, s = variables["params"], variables["batch_stats"]
    with jax.default_matmul_precision("highest"):
        x = x.astype(jnp.float32)
        x = jax.nn.relu(_bn(_conv(x, p["conv1"]["kernel"], 1, 1),
                            p["bn1"], s["bn1"]))
        i = 0
        for planes, blocks, stride in zip(config["stage_channels"],
                                          config["stage_blocks"],
                                          config["stage_strides"]):
            for b in range(blocks):
                bp, bs = p[f"BasicBlock_{i}"], s[f"BasicBlock_{i}"]
                st = stride if b == 0 else 1
                out = jax.nn.relu(_bn(_conv(x, bp["Conv_0"]["kernel"], st, 1),
                                      bp["BatchNorm_0"], bs["BatchNorm_0"]))
                out = _bn(_conv(out, bp["Conv_1"]["kernel"], 1, 1),
                          bp["BatchNorm_1"], bs["BatchNorm_1"])
                if "Conv_2" in bp:
                    x = _bn(_conv(x, bp["Conv_2"]["kernel"], st, 0),
                            bp["BatchNorm_2"], bs["BatchNorm_2"])
                x = jax.nn.relu(out + x)
                i += 1
        x = x.mean(axis=(1, 2))     # 4x4 average pool over a 4x4 map
        return x @ p["linear"]["kernel"] + p["linear"]["bias"]


def _convs(config):
    """(out_hw, c_in, c_out, k) of every convolution, in order."""
    hw, c = config["image_size"], config["image_channels"]
    out = [(hw, c, config["stem_channels"], 3)]
    c = config["stem_channels"]
    for planes, blocks, stride in zip(config["stage_channels"],
                                      config["stage_blocks"],
                                      config["stage_strides"]):
        for b in range(blocks):
            st = stride if b == 0 else 1
            hw //= st
            out.append((hw, c, planes, 3))
            out.append((hw, planes, planes, 3))
            if st != 1 or c != planes:
                out.append((hw, c, planes, 1))
            c = planes
    return out


def param_count(config):
    convs = _convs(config)
    n = sum(k * k * ci * co for _, ci, co, k in convs)
    n += sum(2 * co for _, _, co, _ in convs)            # BatchNorm scale, bias
    n += config["stage_channels"][-1] * config["num_classes"] + config["num_classes"]
    return n


def train_flops_per_sample(config, **_):
    """Required forward+backward FLOPs for one image: every convolution and
    the head cost their forward once more for the weight gradient and once
    more for the input gradient, except the stem, whose input is the image
    and needs no gradient. Elementwise work (BatchNorm, ReLU, the optimizer)
    is not counted, as in ``utils/flops.py``."""
    convs = _convs(config)
    fwd = [2 * hw * hw * co * ci * k * k for hw, ci, co, k in convs]
    head = 2 * config["stage_channels"][-1] * config["num_classes"]
    return 3 * (sum(fwd) + head) - fwd[0]
