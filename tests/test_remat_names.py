"""What ``models/remat.py`` holds for every model: the one tuple of names a
rematerialised block keeps, given where the tensors are made, and what a name
does to a bfloat16 step. That a kept tensor is not made a second time is the
architecture suite's, a case an arch (``tests/arch_suite.py``)."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ps_pytorch_tpu.models import moe, remat, ssm, transformer
from ps_pytorch_tpu.models.moe import MoETransformerLM
from ps_pytorch_tpu.ops.eva_attention import SAVED_NAMES as EVA_SAVED_NAMES
from ps_pytorch_tpu.ops.flash_attention import SAVED_NAMES

MODELS = (transformer, moe, ssm)


def test_a_name_outside_the_tuple_raises():
    x = jnp.ones((2,))
    assert remat.kept(x, "attn_q") is not None
    with pytest.raises(ValueError, match="is not kept"):
        remat.kept(x, "attn_queries")


def test_every_name_of_the_tuple_is_given_once_in_the_models():
    """The tuple is the policy and the calls of ``kept`` are the tensors: a
    name in the tuple that no model gives keeps nothing, and a name given in
    two places holds two tensors."""
    given = []
    for mod in MODELS:
        with open(mod.__file__) as f:
            given += [name for name in re.findall(r'"(\w+)"', f.read())
                      if name in remat.KEPT_NAMES]
    # the forward kernels' own names are given inside their ``custom_vjp``s
    kernels = SAVED_NAMES + EVA_SAVED_NAMES
    assert sorted(given) == sorted(set(remat.KEPT_NAMES) - set(kernels))
    assert remat.KEPT_NAMES[:len(kernels)] == kernels
    assert len(set(remat.KEPT_NAMES)) == len(remat.KEPT_NAMES)


def _loss_and_gradients(options):
    """The tiny Trinity model (q/k norms, output gate, norms behind both
    sublayers, a dense layer and one of held experts under a bias: every
    attention and route name and ``mlp_out``) in bfloat16 under remat:
    (loss, gradient leaves) of one batch, compiled with XLA's ``options``."""
    model = MoETransformerLM(
        vocab_size=97, n_layers=2, n_heads=4, kv_heads=2, head_dim=8,
        d_model=24, max_seq_len=32, arch="trinity", ffn_dim=16, n_experts=8,
        top_k=3, experts_held=4, experts_share=1, dense_layers=1,
        dense_ffn_dim=40, dtype=jnp.bfloat16, remat=True,
        attention_impl="full")
    tokens = jax.random.randint(jax.random.key(1), (2, 32), 0, 97)
    variables = jax.jit(model.init)(jax.random.key(3), tokens)
    rest = {k: v for k, v in variables.items() if k != "params"}

    def loss(params):
        logits = model.apply({"params": params, **rest}, tokens)[0]
        logp = jax.nn.log_softmax(logits.astype(jnp.float32))
        return -jnp.mean(jnp.take_along_axis(
            logp[:, :-1], tokens[:, 1:, None], -1))

    step = jax.jit(jax.value_and_grad(loss)).lower(
        variables["params"]).compile(compiler_options=options)
    value, grads = step(variables["params"])
    return float(value), [np.asarray(g, np.float32)
                          for g in jax.tree.leaves(grads)]


def _named_and_unnamed(options):
    named = _loss_and_gradients(options)
    with pytest.MonkeyPatch.context() as patch:
        for mod in MODELS:      # each binds the function by name
            patch.setattr(mod, "kept", lambda x, name: x)
        return named, _loss_and_gradients(options)


# a float32 sum's last bit: a norm's scale gradient is a sum over the tokens,
# which XLA adds up in another order in another fusion
SUM_ORDER = 4 * float(np.finfo(np.float32).eps)


def _apart(named, unnamed):
    """The largest difference between two gradients, in units of the leaf's
    largest entry, over the leaves."""
    return max(float(np.abs(a - b).max() / np.abs(a).max())
               for a, b in zip(named[1], unnamed[1]))


def test_in_bfloat16_a_name_moves_only_where_xla_rounds():
    """``jax.checkpoint`` puts a ``reduce_precision`` to the tensor's own
    dtype on the producer of every floating residual a policy keeps
    (``jax/_src/ad_checkpoint.py:_insert_reduce_precision``), which pins a
    bfloat16 rounding that XLA, allowed excess precision, skips inside a
    fusion. So under XLA's default a bfloat16 step with the names and one
    without agree as two compilations of one program do, not bit for bit
    (PERF.md, Findings PR 46: the chip's losses); told to round where the
    program says (``xla_allow_excess_precision`` off), they are one step: the
    same loss to the last bit and every gradient equal but for the order of
    a float32 sum. A kept tensor is the tensor the recomputation makes."""
    named, unnamed = _named_and_unnamed({"xla_allow_excess_precision": False})
    assert len(named[1]) == 35
    assert named[0] == unnamed[0]
    assert _apart(named, unnamed) <= SUM_ORDER
    # the check is not vacuous: XLA's default is a bfloat16 rounding apart
    named, unnamed = _named_and_unnamed({})
    assert _apart(named, unnamed) > 100 * SUM_ORDER
