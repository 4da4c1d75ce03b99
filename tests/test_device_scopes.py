"""The device scopes (``telemetry/trace.py:DEVICE_SCOPES``) on the two step
builders with no architecture file (the ResNet-18 dp step and a bfloat16 GPT-2
sp step), compiled at toy sizes: every scope the step uses is in the compiled
program, forward and backward; no matmul, convolution, kernel, scatter, gather
or sort is left without one; and with ``device_scope`` patched to nothing the
compiled program is the same program: a scope is metadata. An ``--lm-arch``'s
step is held to the same three by its ``tests/test_arch_<name>.py``
(``tests/arch_suite.py``, whose reading of a compiled text this file shares:
names by the rule the benchmark reads a profile's ``tf_op`` by,
``benchmark/readers/device_scopes.py:scope_of``)."""

import contextlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

import arch_suite as suite
from ps_pytorch_tpu.models import build_model
from ps_pytorch_tpu.models import resnet as resnet_mod
from ps_pytorch_tpu.models import transformer as tr_mod
from ps_pytorch_tpu.models.transformer import TransformerLM
from ps_pytorch_tpu.optim.sgd import sgd
from ps_pytorch_tpu.parallel import dp, sp
from ps_pytorch_tpu.telemetry.trace import DEVICE_SCOPES, device_scope

S, V = 32, 97
# Every module these two steps open a scope in binds the function by name.
SCOPED_MODULES = (tr_mod, resnet_mod, dp, sp)


def _tx():
    return sgd(0.1, momentum=0.9)


def _dp_resnet18():
    model = build_model("ResNet18", 10, "bfloat16")
    mesh = Mesh(np.array(jax.devices()[:2]).reshape(2, 1), ("data", "model"))
    state = dp.create_train_state(model, _tx(), mesh, (1, 32, 32, 3),
                                  jax.random.key(0))
    step = dp.make_train_step(model, _tx(), mesh, state, donate=False)
    return step, (state, jnp.zeros((4, 32, 32, 3), jnp.float32),
                  jnp.zeros((4,), jnp.int32), jnp.ones((2,), jnp.float32),
                  jax.random.key(1))


def _sp_gpt2():
    model = TransformerLM(vocab_size=V, n_layers=2, n_heads=4, d_model=32,
                          max_seq_len=S, dtype=jnp.bfloat16,
                          attention_impl="flash")
    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
    state = sp.create_lm_train_state(model, _tx(), mesh, (2, S))
    step = sp.make_sp_train_step(model, _tx(), mesh, donate=False)
    return step, (state, jnp.zeros((2, S), jnp.int32))


# name -> (builder, the scopes its step uses)
CASES = {
    "dp_resnet18": (_dp_resnet18, {"conv", "batchnorm", "shortcut", "head",
                                   "loss", "grad_reduce", "optimizer"}),
    "sp_gpt2": (_sp_gpt2, suite.LM_SCOPES | {"ffn"}),
}


@pytest.fixture(scope="module")
def compiled():
    """case -> (lowered HLO text with every op's name, compiled text): built
    once a case."""
    cache = {}

    def get(case):
        if case not in cache:
            step, args = CASES[case][0]()
            lowered = step.lower(*args)
            cache[case] = (lowered.as_text(dialect="hlo", debug_info=True),
                           lowered.compile().as_text())
        return cache[case]
    return get


@pytest.mark.parametrize("case", sorted(CASES))
def test_every_scope_the_arch_uses_is_in_the_compiled_step(case, compiled):
    suite.check_scopes(*compiled(case), CASES[case][1], remat=False)


@pytest.mark.parametrize("case", sorted(CASES))
def test_no_heavy_op_is_without_a_scope(case, compiled):
    suite.check_heavy_ops(*compiled(case))


@pytest.mark.parametrize("case", sorted(CASES))
def test_a_scope_changes_no_program(case, compiled, monkeypatch):
    """The compiled texts, where an arch's file compares the lowered ones:
    these two steps are small enough to compile twice."""
    for mod in SCOPED_MODULES:
        monkeypatch.setattr(mod, "device_scope",
                            lambda name: contextlib.nullcontext())
    step, args = CASES[case][0]()
    bare = step.lower(*args).compile().as_text()
    assert not any(suite.scope_of(name)[0]
                   for _, name in suite.named_ops(bare))
    assert suite.program(bare) == suite.program(compiled(case)[1])


def test_a_name_outside_the_vocabulary_is_refused():
    with pytest.raises(ValueError, match="typo"):
        device_scope("typo")
    assert len(set(DEVICE_SCOPES)) == len(DEVICE_SCOPES)
    with device_scope(DEVICE_SCOPES[0]):
        pass


def test_the_compile_caches_key_sees_the_scopes(monkeypatch):
    """The scopes are metadata, which JAX's persistent cache leaves out of
    its key unless told: an executable cached before a layer had a name would
    be loaded for the program that names it, and profile without it."""
    from ps_pytorch_tpu.utils.compile_cache import enable_compile_cache
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/placed/elsewhere")
    flag = "jax_compilation_cache_include_metadata_in_key"
    before = getattr(jax.config, flag)
    try:
        jax.config.update(flag, False)
        enable_compile_cache()
        assert getattr(jax.config, flag) is True
    finally:
        jax.config.update(flag, before)
