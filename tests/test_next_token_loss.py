"""``ops/next_token_loss.py``: the value against optax, the hand-written
gradient against autodiff of the spelling it replaced (slice, astype, optax:
``parent_loss`` below, the oracle), and on the lowered ep step of each
dropless arch what the spelling cost on the chip (ledger PR 31): no
``[B, S-1, V]`` array, so no slice, pad or scatter of one, and no float32
``[rows, V]`` kept for the backward pass."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from ps_pytorch_tpu.models.moe import MOE_STATE, MoETransformerLM, lm_variables
from ps_pytorch_tpu.ops.next_token_loss import next_token_loss
from ps_pytorch_tpu.optim.sgd import sgd
from ps_pytorch_tpu.parallel import ep
from ps_pytorch_tpu.parallel.dp import TrainState

S, V = 32, 97       # V odd; S - 1 = 31 appears in no other dimension below


def parent_loss(logits, tokens):
    """The spelling ``parallel/ep.py`` had until PR 32."""
    per = optax.softmax_cross_entropy_with_integer_labels(
        logits[:, :-1].astype(jnp.float32), tokens[:, 1:])
    return per.sum(), jnp.float32(per.size)


def shifted(tokens):
    """The targets and weights both step builders hand the function."""
    seq = tokens.shape[1]
    weights = jnp.broadcast_to(jnp.arange(seq) < seq - 1, tokens.shape)
    return jnp.roll(tokens, -1, axis=1), weights.astype(jnp.float32)


def new_loss(logits, tokens):
    return next_token_loss(logits, *shifted(tokens))


def _inputs(batch, vocab, dtype, seq=S, scale=3.0):
    logits = scale * jax.random.normal(jax.random.key(0), (batch, seq, vocab))
    tokens = jax.random.randint(jax.random.key(1), (batch, seq), 0, vocab)
    return logits.astype(dtype), tokens


SHAPES = [(1, V), (2, V), (2, 128)]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("batch,vocab", SHAPES)
def test_value_is_optaxs(batch, vocab, dtype):
    logits, tokens = _inputs(batch, vocab, dtype)
    got_sum, got_count = new_loss(logits, tokens)
    want_sum, want_count = parent_loss(logits, tokens)
    assert got_sum.dtype == got_count.dtype == jnp.float32
    assert float(got_count) == float(want_count) == batch * (S - 1)
    np.testing.assert_allclose(float(got_sum), float(want_sum), rtol=1e-6)
    # every position weighted, against optax with nothing sliced off
    targets = jnp.roll(tokens, -1, axis=1)
    all_sum, all_count = next_token_loss(
        logits, targets, jnp.ones(tokens.shape, jnp.float32))
    want = optax.softmax_cross_entropy_with_integer_labels(
        logits.astype(jnp.float32), targets).sum()
    assert float(all_count) == batch * S
    np.testing.assert_allclose(float(all_sum), float(want), rtol=1e-6)


@pytest.mark.parametrize("batch,vocab", SHAPES)
def test_float32_gradient_is_autodiffs_of_the_parents_spelling(batch, vocab):
    logits, tokens = _inputs(batch, vocab, jnp.float32)
    got = jax.grad(lambda x: new_loss(x, tokens)[0])(logits)
    want = jax.grad(lambda x: parent_loss(x, tokens)[0])(logits)
    assert got.dtype == logits.dtype and got.shape == logits.shape
    np.testing.assert_allclose(got, want, atol=1e-6, rtol=0)


@pytest.mark.parametrize("batch,vocab", SHAPES)
def test_bfloat16_gradient_is_within_an_ulp_of_the_float32_one(batch, vocab):
    logits, tokens = _inputs(batch, vocab, jnp.bfloat16)
    got = jax.grad(lambda x: new_loss(x, tokens)[0])(logits)
    assert got.dtype == jnp.bfloat16
    exact = jax.grad(lambda x: parent_loss(x, tokens)[0])(
        logits.astype(jnp.float32))
    # one bfloat16 ulp of a value v is at most 2^-7 |v|
    err = np.abs(np.asarray(got, np.float32) - np.asarray(exact))
    assert np.all(err <= 2.0 ** -7 * np.abs(np.asarray(exact)) + 1e-30)
    # and the parent's own bfloat16 gradient is the same rounding of it
    parent = jax.grad(lambda x: parent_loss(x, tokens)[0])(logits)
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(parent, np.float32))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("filler", [0, V - 1, -1, V + 5])
def test_a_weightless_row_gets_exact_zeros_whatever_its_target(filler, dtype):
    logits, tokens = _inputs(2, V, dtype)
    targets, weights = shifted(tokens)
    targets = targets.at[:, -1].set(filler)
    value, grad = jax.value_and_grad(
        lambda x: next_token_loss(x, targets, weights)[0])(logits)
    assert np.all(np.asarray(grad[:, -1], np.float32) == 0.0)
    assert np.any(np.asarray(grad[:, :-1], np.float32) != 0.0)
    np.testing.assert_allclose(float(value),
                               float(parent_loss(logits, tokens)[0]),
                               rtol=1e-6)


def test_the_weights_are_constants_to_the_gradient():
    logits, tokens = _inputs(2, V, jnp.float32)
    targets, weights = shifted(tokens)
    got = jax.grad(lambda w: next_token_loss(logits, targets, w)[0])(weights)
    assert got.dtype == weights.dtype and got.shape == weights.shape
    assert np.all(np.asarray(got) == 0.0)


# ------------------------------------------- the lowered step, each arch --

ARCH_MODELS = {
    "olmoe": dict(n_layers=2, n_heads=4, d_model=64, ffn_dim=32,
                  n_experts=8, top_k=4),
    "smallthinker": dict(n_layers=4, n_heads=4, kv_heads=2, head_dim=8,
                         d_model=24, ffn_dim=16, n_experts=8, top_k=3,
                         experts_held=4, experts_share=1),
    "trinity": dict(n_layers=5, n_heads=4, kv_heads=2, head_dim=8, d_model=24,
                    ffn_dim=16, n_experts=8, top_k=3, experts_held=4,
                    experts_share=1, dense_layers=1, dense_ffn_dim=40),
}
# a tensor type with S - 1 rows of V columns, in StableHLO's spelling
SLICED_LOGITS = re.compile(rf"x{S - 1}x{V}x")
# a scatter spans lines: its types follow the region that combines updates
SCATTER_RESULT = re.compile(
    r'"stablehlo\.scatter"\(.*?\}\) : \([^)]*\) -> (tensor<[^>]*>)', re.S)


def _ops_on_sliced_logits(text):
    """Which of slice, pad and scatter touch a ``[.., S-1, V]`` array."""
    found = {op for line in text.splitlines() if SLICED_LOGITS.search(line)
             for op in ("slice", "pad") if f"stablehlo.{op} " in line}
    if any(SLICED_LOGITS.search(t) for t in SCATTER_RESULT.findall(text)):
        found.add("scatter")
    return found


def arch_case(arch, batch, dtype=jnp.bfloat16):
    model = MoETransformerLM(vocab_size=V, max_seq_len=S, arch=arch,
                             dtype=dtype, **ARCH_MODELS[arch])
    tokens = jnp.asarray(
        np.random.default_rng(1).integers(0, V, (batch, S)), jnp.int32)
    variables = dict(model.init(jax.random.key(0), tokens))
    return model, variables, tokens


def one_device_ep_step(model, variables, tx):
    """(``make_ep_train_step`` on one of the fake devices, its state)."""
    from jax.sharding import Mesh
    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("data", "model"))
    state = TrainState(step=jnp.zeros((), jnp.int32),
                       params=variables["params"],
                       opt_state=tx.init(variables["params"]),
                       batch_stats=variables.get(MOE_STATE, {}))
    return ep.make_ep_train_step(model.clone(ep_axis="data"), tx, mesh,
                                 state, donate=False), state


@pytest.mark.parametrize("batch", [1, 2])
@pytest.mark.parametrize("arch", sorted(ARCH_MODELS))
def test_the_lowered_ep_step_holds_no_sliced_logits(arch, batch):
    """What the ledger showed at batch 1: the ``[:, :-1]`` slice of the
    logits (a relayout copy), the pad of its gradient back to S rows, and the
    scatter of the labels' gradient into ``[B, S-1, V]``."""
    model, variables, tokens = arch_case(arch, batch)
    step, state = one_device_ep_step(model, variables,
                                     sgd(lr=0.1, momentum=0.9))
    text = step.lower(state, tokens).as_text()
    assert f"x{S}x{V}x" in text          # the logits are there, whole
    assert not SLICED_LOGITS.search(text)
    assert not _ops_on_sliced_logits(text)
    # the scatters left are the model's: the embedding's gradient ([V, d]: V
    # leads) and the experts' dispatch; the labels' gradient had V last
    results = SCATTER_RESULT.findall(text)
    assert results and not [t for t in results
                            if re.search(rf"x{V}x\w+>", t)], results


def test_the_check_above_finds_the_parents_spelling():
    """The same pattern over the parent's loss lowered alone: the slice, the
    pad and the scatter are all there, each on ``[2, S-1, V]``."""
    logits, tokens = _inputs(2, V, jnp.bfloat16)
    text = jax.jit(jax.grad(lambda x: parent_loss(x, tokens)[0])).lower(
        logits).as_text()
    assert _ops_on_sliced_logits(text) == {"slice", "pad", "scatter"}
    mine = jax.jit(jax.grad(lambda x: new_loss(x, tokens)[0])).lower(
        logits).as_text()
    assert not SLICED_LOGITS.search(mine) and "scatter" not in mine


def _residuals(loss, params):
    """Shapes and dtypes of what ``jax.vjp`` keeps for the backward pass."""
    return [(tuple(a.shape), jnp.dtype(a.dtype)) for a in jax.tree.leaves(
        jax.eval_shape(lambda p: jax.vjp(loss, p)[1], params))]


def _wide_float32(residuals, batch):
    return [r for r in residuals if r[1] == jnp.float32 and r[0][-1:] == (V,)
            and int(np.prod(r[0])) >= batch * (S - 1) * V]


@pytest.mark.parametrize("arch", sorted(ARCH_MODELS))
def test_no_float32_logits_are_kept_for_the_backward_pass(arch):
    """bfloat16 model, as in the cells: of the head and the loss the backward
    pass keeps the bfloat16 logits and one float32 a row."""
    model, variables, tokens = arch_case(arch, batch=2)
    state = variables.get(MOE_STATE, {})

    def logits_of(params):
        return model.apply(lm_variables(params, state), tokens)[0]

    kept = _residuals(lambda p: new_loss(logits_of(p), tokens)[0],
                      variables["params"])
    assert ((2, S, V), jnp.dtype(jnp.bfloat16)) in kept
    assert not _wide_float32(kept, batch=2), kept
    # the parent's spelling kept them (the check is not vacuous)
    parent = _residuals(lambda p: parent_loss(logits_of(p), tokens)[0],
                        variables["params"])
    assert _wide_float32(parent, batch=2)
