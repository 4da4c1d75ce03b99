"""The ``olmoe`` arch (RMSNorm, RoPE, q/k norm, dropless top-k SwiGLU experts)
against its plain reference ``benchmark/reference/olmoe_1b_7b.py`` at a tiny
size: the common suite (``tests/arch_suite.py``) and what is OLMoE's alone:
the mistakes its tolerance has to catch, the loss's three terms, the counts of
parameters and FLOPs, RoPE, and what the config refuses."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import arch_suite as suite
from ps_pytorch_tpu.config import LM_ARCHS, TrainConfig
from ps_pytorch_tpu.models import moe as moe_mod
from ps_pytorch_tpu.models import transformer as tr_mod
from ps_pytorch_tpu.models.moe import MoETransformerLM
from ps_pytorch_tpu.models.transformer import ARCHS, TransformerLM, rope
from ps_pytorch_tpu.ops.grouped_matmul import gmm
from ps_pytorch_tpu.utils.flops import count_jaxpr_flops

S = 32
# The tiny preset: d=64, 4 heads of 16, 8 experts top-4 of width 32, 2 layers,
# S=32, vocab 97 — in the reference's (the published config's) keys.
CASE = suite.ArchCase(
    arch="olmoe", parallelism="ep", config="olmoe_1b_7b",
    tiny=dict(hidden_size=64, intermediate_size=32, num_attention_heads=4,
              num_key_value_heads=4, num_experts=8, num_experts_per_tok=4,
              num_hidden_layers=2, vocab_size=97, max_position_embeddings=S),
    flags=dict(lm_d_model=64, lm_ffn_dim=32, lm_heads=4, lm_experts=8,
               lm_moe_top_k=4, lm_layers=2, lm_vocab=97, lm_seq_len=S),
    logit_tol=1e-4,
    tol_reason="float32 both sides, only the order of reductions differs "
               "(the sorted grouped matmul against a dense loop over experts, "
               "flax's norm against a hand-written one): measured 2e-6 on "
               "logits up to 4; 1e-4 is fifty times that and far under what "
               "any of the MUTANTS below changes (0.02 to 1)",
    scopes=suite.LM_SCOPES | suite.EXPERT_SCOPES,
    remat_scopes=frozenset({"moe_experts"}), another_depth=1,
    refusals=(
        ("generate.py", suite.by_generate, ("lm_arch=olmoe", "not built")),
        ("serve.py", suite.by_serve, ("lm_arch=olmoe", "not built")),
        ("two chips", suite.by_two_chips,
         ("dropless routing across chips: not built",))),
    published_row=dict(aux_coef="load_balance_coef_as_run",
                       z_loss_coef="z_loss_coef_as_run"))
REF, PUBLISHED, TINY = CASE.reference, CASE.published, CASE.tiny_config
LOGIT_TOL = CASE.logit_tol

suite.install(globals(), CASE)


def _model(cls=MoETransformerLM, **kw):
    base = dict(vocab_size=97, n_layers=2, n_heads=4, d_model=64,
                max_seq_len=S, arch="olmoe", ffn_dim=32)
    if cls is MoETransformerLM:
        base.update(n_experts=8, top_k=4)
    base.update(kw)
    return cls(**base)


def _interleaved_rope(x, positions, theta):
    """RoPE with the GPT-J pairing (feature 2i with 2i+1): the wrong one."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[:, None] * inv[None]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     axis=-1).reshape(x.shape)


def _gmm_dropping_one_row(lhs, rhs, group_sizes):
    return gmm(lhs, rhs, group_sizes).at[-1].set(0.0)


MUTANTS = {
    # name -> (what to patch on the program's side, reference config)
    "renormalised_gate": (None, dict(TINY, norm_topk_prob=True)),
    "missing_qk_norm": ((tr_mod.ARCHS, "olmoe",
                         ARCHS["olmoe"]._replace(qk_norm=False)), TINY),
    "interleaved_rope": ((tr_mod, "rope", _interleaved_rope), TINY),
    "dropped_assignment": ((moe_mod, "gmm", _gmm_dropping_one_row), TINY),
}


@pytest.mark.parametrize("name", sorted(MUTANTS))
def test_the_logit_tolerance_catches(tiny, monkeypatch, name):
    """Each mistake the tolerance has to catch moves the logits by far more
    than LOGIT_TOL."""
    model, variables, tokens = tiny
    patch, config = MUTANTS[name]
    if patch is not None:
        target, attr, value = patch
        if isinstance(target, dict):
            monkeypatch.setitem(target, attr, value)
        else:
            monkeypatch.setattr(target, attr, value)
    got = suite.apply_logits(model, variables, tokens)
    want = REF.forward(variables, tokens, config)
    assert float(jnp.abs(got - want).max()) > 50 * LOGIT_TOL


def test_the_step_reports_the_reference_loss_terms():
    """The common step case holds every parameter's move to the reference's
    gradient; here its three terms: cross-entropy, the all-choices
    load-balance term and the z-loss, each as the step reports it."""
    _, variables, tokens = suite.tiny(CASE)
    _, _, m = suite.first_step(CASE, False)
    ce, lb, z = REF.loss_terms(variables, tokens, TINY)
    np.testing.assert_allclose(float(m["loss"]), float(ce), rtol=1e-5)
    np.testing.assert_allclose(float(m["aux"]), float(lb), rtol=1e-5)
    np.testing.assert_allclose(float(m["z_loss"]), float(z), rtol=1e-5)


def test_param_count_published_and_tiny(tiny):
    _, variables, _ = tiny
    assert REF.param_count(dict(PUBLISHED, num_hidden_layers=16)) \
        == PUBLISHED["parameters_published"] == 6_919_161_856
    assert REF.param_count(TINY) == sum(
        a.size for a in jax.tree.leaves(variables["params"]))


@pytest.mark.parametrize("what", ["forward", "forward_and_backward"])
def test_closed_form_flops_against_the_jaxpr_walk(tiny, what):
    """The closed form counts every matmul of the forward (attention dense
    S x S) and nothing else, so the walk of the forward agrees exactly. For
    training it charges 3x the forward; the walk finds less by what the
    closed form does not leave out but autodiff never computes: the gradient
    to the token ids (the first layer's q, k, v and router have no input
    gradient to pass on)."""
    model, variables, tokens = tiny
    per_token = REF.train_flops_per_sample(TINY, seq_len=S)
    if what == "forward":
        walked = count_jaxpr_flops(jax.make_jaxpr(
            lambda v: suite.apply_logits(model, v, tokens))(
                variables).jaxpr)
        assert walked == per_token // 3 * tokens.size
    else:
        walked = count_jaxpr_flops(jax.make_jaxpr(jax.grad(
            lambda v: suite.apply_logits(model, v, tokens).sum()))(
                variables).jaxpr)
        assert 0.9 * per_token * tokens.size < walked \
            <= per_token * tokens.size


def test_rope_depends_on_relative_position_only():
    q = jax.random.normal(jax.random.key(0), (1, 2, 1, 16))
    k = jax.random.normal(jax.random.key(1), (1, 2, 1, 16))
    dots = [float(jnp.sum(rope(q, jnp.array([a]), 10000.0)
                          * rope(k, jnp.array([b]), 10000.0)))
            for a, b in ((5, 2), (13, 10), (3, 0))]
    np.testing.assert_allclose(dots, dots[0], rtol=1e-5)
    assert abs(dots[0] - float(jnp.sum(q * k))) > 1e-3


def test_both_lm_classes_reach_the_shared_block_pieces():
    """RMSNorm, RoPE and the q/k norm are written once, in the attention
    half both classes call: the dense class under the olmoe arch has the
    same attention leaves as the MoE class, no position table, and its
    logits move when the positions do."""
    tokens = jnp.arange(8, dtype=jnp.int32)[None] * 7 % 97
    dense = _model(TransformerLM)
    p_dense = dense.init(jax.random.key(0), tokens)["params"]
    p_moe = _model().init(jax.random.key(0), tokens)["params"]
    attn = {"RMSNorm_0", "RMSNorm_1", "Dense_0", "Dense_1", "Dense_2",
            "Dense_3", "q_norm", "k_norm"}
    assert attn <= set(p_dense["block_0"]) and attn <= set(p_moe["block_0"])
    assert "pos_embed" not in p_dense and "pos_embed" not in p_moe
    assert set(p_dense["ln_f"]) == {"scale"}
    assert p_dense["block_0"]["Dense_4"]["kernel"].shape == (64, 32)
    a = dense.apply({"params": p_dense}, tokens)
    b = dense.apply({"params": p_dense}, tokens, positions=jnp.arange(8) * 3)
    assert float(jnp.abs(a - b).max()) > 1e-4


CONFIG_CASES = {
    "olmoe_takes_any_k_up_to_the_experts": (
        dict(lm_arch="olmoe", lm_parallelism="ep", lm_experts=64,
             lm_moe_top_k=8, lm_ffn_dim=1024), None),
    "olmoe_k_above_the_experts": (
        dict(lm_arch="olmoe", lm_parallelism="ep", lm_experts=4,
             lm_moe_top_k=5), "lm_moe_top_k"),
    "olmoe_needs_ep": (dict(lm_arch="olmoe"), "lm_parallelism=ep"),
    "capacity_path_keeps_k_1_or_2": (
        dict(lm_parallelism="ep", lm_moe_top_k=3), "capacity"),
    "unknown_arch": (dict(lm_arch="llama"), "unknown lm_arch"),
    "negative_ffn_dim": (dict(lm_ffn_dim=-1), "lm_ffn_dim"),
}


@pytest.mark.parametrize("name", sorted(CONFIG_CASES))
def test_config_validation(name):
    kw, error = CONFIG_CASES[name]
    if error is None:
        cfg = TrainConfig(**kw)
        assert TrainConfig.from_json(cfg.to_json()).lm_arch == "olmoe"
    else:
        with pytest.raises(ValueError, match=error):
            TrainConfig(**kw)


def test_config_names_the_archs_the_models_have():
    assert LM_ARCHS == tuple(ARCHS)
