"""Plain float32 LeNet (the program's: two 5x5 VALID convolutions with 2x2
max-pools, two dense layers with
no activation between them) for the harness tests."""

import jax
import jax.numpy as jnp


def forward(variables, x, config):
    p = variables["params"]
    with jax.default_matmul_precision("highest"):
        x = x.astype(jnp.float32)
        for name in ("conv1", "conv2"):
            x = jax.lax.conv_general_dilated(
                x, p[name]["kernel"], (1, 1), "VALID",
                dimension_numbers=("NHWC", "HWIO", "NHWC")) + p[name]["bias"]
            x = jax.lax.reduce_window(x, -jnp.inf, jax.lax.max,
                                      (1, 2, 2, 1), (1, 2, 2, 1), "VALID")
            x = jax.nn.relu(x)
        x = x.reshape(x.shape[0], -1)
        x = x @ p["fc1"]["kernel"] + p["fc1"]["bias"]     # no ReLU, as the program
        return x @ p["fc2"]["kernel"] + p["fc2"]["bias"]


def train_flops_per_sample(config, **_):
    return 3 * 2 * (24 * 24 * 20 * 25 + 8 * 8 * 50 * 20 * 25
                    + 800 * 500 + 500 * 10)
