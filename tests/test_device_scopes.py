"""The device scopes (``telemetry/trace.py:DEVICE_SCOPES``) on the four step
builders the benchmark's cells run, compiled at toy sizes: every scope an
arch uses is in the compiled program, forward and backward (and a
rematerialised forward under ``--remat``); no matmul, convolution, kernel,
scatter, gather or sort is left without one; and with ``device_scope``
patched to nothing the compiled program is the same program: a scope is
metadata. Names are read by the rule the benchmark reads a profile's ``tf_op``
by: ``benchmark/readers/device_scopes.py:scope_of``."""

import contextlib
import importlib.util
import pathlib
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from ps_pytorch_tpu.models import build_model
from ps_pytorch_tpu.models import gdn as gdn_mod
from ps_pytorch_tpu.models import moe as moe_mod
from ps_pytorch_tpu.models import resnet as resnet_mod
from ps_pytorch_tpu.models import ssm as ssm_mod
from ps_pytorch_tpu.models import transformer as tr_mod
from ps_pytorch_tpu.models.moe import MOE_STATE, MoETransformerLM
from ps_pytorch_tpu.models.transformer import TransformerLM
from ps_pytorch_tpu.optim.sgd import sgd
from ps_pytorch_tpu.parallel import dp, ep, sp
from ps_pytorch_tpu.parallel.dp import TrainState
from ps_pytorch_tpu.telemetry.trace import DEVICE_SCOPES, device_scope

REPO = pathlib.Path(__file__).resolve().parent.parent


def _reader():
    """The benchmark's reader module (it imports its neighbour
    ``trace_reduce`` by name)."""
    bench = str(REPO / "benchmark")
    sys.path.insert(0, bench)
    try:
        spec = importlib.util.spec_from_file_location(
            "device_scopes_reader", REPO / "benchmark" / "readers"
            / "device_scopes.py")
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod
    finally:
        sys.path.remove(bench)


READER = _reader()
S, V = 32, 97
# Every module that opens a scope binds the function by name.
SCOPED_MODULES = (tr_mod, moe_mod, ssm_mod, gdn_mod, resnet_mod, dp, sp, ep)
LM = {"embed", "attn_proj", "attn_pos", "attn_core", "head", "loss",
      "grad_reduce", "optimizer"}
EXPERTS = {"moe_route", "moe_dispatch", "moe_experts"}
STATE_SPACE = {"ssm_proj", "ssm_conv", "ssm_scan", "gmu"}
LINEAR_ATTENTION = {"gdn_proj", "gdn_mix", "gdn_core"}
# What no gradient passes through has no backward twin.
NO_BACKWARD = {"grad_reduce", "optimizer", "router_bias"}
HEAVY = {"dot", "convolution", "custom-call", "scatter", "gather", "sort"}


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


def _sp_phi4flash():
    """Depth 8: every kind of layer, under per-block remat."""
    model = TransformerLM(vocab_size=V, n_layers=8, n_heads=4, kv_heads=2,
                          head_dim=8, d_model=32, ffn_dim=48, max_seq_len=S,
                          dtype=jnp.bfloat16, attention_impl="flash",
                          arch="phi4flash")
    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
    state = sp.create_lm_train_state(model, _tx(), mesh, (2, S))
    step = sp.make_sp_train_step(model, _tx(), mesh, remat=True, donate=False)
    return step, (state, jnp.zeros((2, S), jnp.int32))


def _ep(arch, **kw):
    model = MoETransformerLM(
        vocab_size=V, max_seq_len=S, arch=arch, dtype=jnp.bfloat16,
        n_heads=4, kv_heads=2, head_dim=8, d_model=24, ffn_dim=16,
        n_experts=8, top_k=3, experts_held=4, **kw)
    tokens = jnp.zeros((2, S), jnp.int32)
    variables = dict(model.init(jax.random.key(0), tokens))
    tx = _tx()
    state = TrainState(step=jnp.zeros((), jnp.int32),
                       params=variables["params"],
                       opt_state=tx.init(variables["params"]),
                       batch_stats=variables.get(MOE_STATE, {}))
    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("data", "model"))
    step = ep.make_ep_train_step(model.clone(ep_axis="data"), tx, mesh, state,
                                 remat=True, donate=False)
    return step, (state, tokens)


# name -> (builder, the scopes its arch uses, built under --remat)
CASES = {
    "dp_resnet18": (_dp_resnet18, {"conv", "batchnorm", "shortcut", "head",
                                   "loss", "grad_reduce", "optimizer"}, False),
    "sp_gpt2": (_sp_gpt2, LM | {"ffn"}, False),
    "sp_phi4flash_remat": (_sp_phi4flash, LM | {"ffn"} | STATE_SPACE, True),
    "ep_dropless_held_remat": (
        lambda: _ep("smallthinker", n_layers=2, attention_impl="flash"),
        LM | EXPERTS, True),
    "ep_trinity": (
        lambda: _ep("trinity", n_layers=2, experts_share=1, dense_layers=1,
                    dense_ffn_dim=40),
        LM | EXPERTS | {"ffn", "moe_shared", "router_bias"}, True),
    # one period: three linear-attention layers (the row's 16 key and 32 value
    # heads of 128) and one attention layer
    "ep_qwen3next_remat": (
        lambda: _ep("qwen3next", n_layers=4, attention_impl="flash"),
        LM | EXPERTS | LINEAR_ATTENTION | {"moe_shared"}, True),
}

INSTRUCTION = re.compile(r"^\s*(?:ROOT )?%?[\w.\-]+ = .*?\s([a-z][\w\-]*)\(")
OP_NAME = re.compile(r'op_name="([^"]*)"')
# What differs between two compilations of one program: each op's metadata
# and the tables of source locations it points into.
METADATA = re.compile(
    r",? ?metadata=\{[^}]*\}|"
    r"^(?:FileNames|FunctionNames|FileLocations|StackFrames)\n.*?\n\n",
    re.M | re.S)
# The partitioner's own annotations at a shard_map's edge: no work.
ANNOTATION = re.compile(r'custom_call_target="xla\.sdy\.\w+Shape"')


def program(text):
    """A compiled text without its metadata, every name (``%fusion.12``: JAX
    derives some from the name stack) replaced by the order it first appears
    in: equal for two compilations of one program."""
    ids = {}
    return re.sub(r"%[\w.\-]+",
                  lambda m: ids.setdefault(m.group(0), f"%{len(ids)}"),
                  METADATA.sub("", text))


def scope_of(op_name):
    """(scope or None, part) of a JAX name stack, by the reader's rule."""
    scope, part = READER.scope_of(op_name, DEVICE_SCOPES)
    return (None if scope == READER.UNSCOPED else scope), part


def named_ops(text):
    """[(opcode, op_name)] of the instructions of an HLO text that carry an
    ``op_name``."""
    out = []
    for line in text.splitlines():
        m, name = INSTRUCTION.match(line), OP_NAME.search(line)
        if m and name and not ANNOTATION.search(line):
            out.append((m.group(1), name.group(1)))
    return out


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
    _, uses, remat = CASES[case]
    lowered, text = ({scope_of(name) for _, name in named_ops(t)}
                     for t in compiled(case))
    # The CPU compiler drops the metadata of ops it rewrites (GPT-2's
    # ``to_heads`` transposes, all there is of its ``attn_pos``): the set is
    # held on both texts together, the compiled one to the scopes every step
    # has work under.
    found = lowered | text
    assert {s for s, _ in found if s} == uses
    assert {"head", "loss", "optimizer"} <= {s for s, _ in text} <= uses | {None}
    for scope in uses - NO_BACKWARD:
        assert (scope, "forward") in found and (scope, "backward") in found, \
            scope
    assert remat == any(part == "recompute" for _, part in found)
    if remat:       # a block's interior, of which each arch has these
        inner = {"moe_experts", "ssm_scan", "gdn_core"} & uses
        assert {("attn_proj", "recompute")} \
            | {(scope, "recompute") for scope in inner} <= found


@pytest.mark.parametrize("case", sorted(CASES))
def test_no_heavy_op_is_without_a_scope(case, compiled):
    """In the compiled text an op may have lost its metadata to the CPU
    compiler (a dot it rewrote), and in the lowered text an op inside a called
    function (an interpreted kernel's body) has a name relative to the call:
    what either text names in full has to be scoped."""
    lowered, text = compiled(case)
    heavy = [(op, name) for op, name in named_ops(text) if op in HEAVY] + \
        [(op, name) for op, name in named_ops(lowered)
         if op in HEAVY and name.startswith("jit(")]
    assert len(heavy) > 10
    assert [(op, name) for op, name in heavy if scope_of(name)[0] is None] \
        == []


@pytest.mark.parametrize("case", sorted(CASES))
def test_a_scope_changes_no_program(case, compiled, monkeypatch):
    for mod in SCOPED_MODULES:
        monkeypatch.setattr(mod, "device_scope",
                            lambda name: contextlib.nullcontext())
    step, args = CASES[case][0]()
    bare = step.lower(*args).compile().as_text()
    assert not any(scope_of(name)[0] for _, name in named_ops(bare))
    assert program(bare) == program(compiled(case)[1])


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
