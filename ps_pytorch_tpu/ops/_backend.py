"""Shared backend probe for the Pallas kernels: compile under Mosaic on
TPU, run in interpreter mode everywhere else (one definition, so the
kernels can never disagree about when they compile vs interpret)."""

from typing import List

import jax


def interpret_default() -> bool:
    return jax.default_backend() != "tpu"


def cnn_kernels(cfg) -> List[str]:
    """The Pallas kernels a CNN trainer's config switches on."""
    names = []
    if cfg.conv_impl != "xla":
        names.append(f"conv3x3:{cfg.conv_impl}")
    if cfg.compress_grad and cfg.grad_codec == "int8":
        names.append("quantize_int8")
    return names


def announce_kernels(names: List[str], dtype=None) -> None:
    """One line per trainer naming the enabled kernels and the mode they run
    in, so an interpreted kernel on a machine meant to have a chip is seen;
    ``dtype`` (the LM trainer's) is the dtype the built model feeds them."""
    if names:
        mode = "interpret" if interpret_default() else "mosaic"
        fed = f" dtype={jax.numpy.dtype(dtype).name}" if dtype is not None else ""
        print(f"KERNELS {' '.join(names)} mode={mode}{fed}")
