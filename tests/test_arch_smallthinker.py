"""The ``smallthinker`` arch (grouped-query heads of their own size, window
layers with RoPE and global layers without position encoding, a dropless
ReLU-gated expert layer that holds a share of its experts, the router before
attention) against its plain reference
``benchmark/reference/smallthinker_21b_a3b.py`` at a tiny size: the common
suite (``tests/arch_suite.py``) and what is SmallThinker's alone: the mistakes
its tolerance has to catch, the loss's terms, the counts of parameters and
FLOPs, and what refuses grouped-query heads and windows."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import arch_suite as suite
from ps_pytorch_tpu.config import TrainConfig
from ps_pytorch_tpu.models import moe as moe_mod
from ps_pytorch_tpu.models import transformer as tr_mod
from ps_pytorch_tpu.models.moe import MoEBlock, MoETransformerLM
from ps_pytorch_tpu.models.transformer import ARCHS, TransformerLM
from ps_pytorch_tpu.ops.flash_attention import flash_attention
from ps_pytorch_tpu.ops.grouped_matmul import gmm
from ps_pytorch_tpu.parallel.ring import full_attention
from ps_pytorch_tpu.utils.flops import count_jaxpr_flops

S, WINDOW = 32, 8


def _model(**kw):
    base = dict(vocab_size=97, n_layers=4, n_heads=4, kv_heads=2, head_dim=8,
                d_model=24, max_seq_len=S, arch="smallthinker", ffn_dim=16,
                n_experts=8, top_k=3, experts_held=4, experts_share=1)
    base.update(kw)
    return MoETransformerLM(**base)


def _block_one(variables, held, share):
    """block_1's parameters (a window layer) with the experts of one share."""
    bp = dict(variables["params"]["block_1"])
    return bp, {k: v[share * held:(share + 1) * held] if k.startswith(
        "experts_") else v for k, v in bp["moe"].items()}


def _shares(side):
    """One layer at the tiny size, all 8 experts' weights seeded: the four
    shares' parts of the result (2 of 8 experts held, share 0..3) and what the
    uncut reference layer adds to the residual stream: the reference layer's
    output less the same layer's with its down projections zeroed (``x1``,
    the stream after attention)."""
    model = _model(experts_held=0, experts_share=0)
    tokens = jnp.zeros((1, S), jnp.int32)
    variables = jax.jit(model.init)(jax.random.key(3), tokens)
    x = jax.random.normal(jax.random.key(4), (S, 24))
    bp, _ = _block_one(variables, 8, 0)
    zeroed = {**bp, "moe": {**bp["moe"], "experts_down":
                            jnp.zeros_like(bp["moe"]["experts_down"])}}
    x1 = REF._layer(zeroed, x, UNCUT, 1)[0]
    y_uncut = REF._layer(bp, x, UNCUT, 1)[0] - x1
    parts = []
    for share in range(4):
        bp_s = {**bp, "moe": _block_one(variables, 2, share)[1]}
        if side == "program":
            block = MoEBlock(4, 24, 8, top_k=3, arch="smallthinker",
                             ffn_dim=16, layer=1, kv_heads=2, head_dim=8,
                             experts_held=2, experts_share=share)
            out, stats = block.apply({"params": bp_s}, x[None])
            out = out[0]
            assert float(stats["moe_dropped"]) == 0.0
        else:
            out = REF._layer(bp_s, x, dict(
                UNCUT, moe_num_primary_experts=2, experts_held=2,
                experts_share=share), 1)[0]
        parts.append(out - x1)
    # a thousandth of what one share adds is over the sums' rounding
    assert 5e-6 < 1e-3 * float(jnp.abs(y_uncut).max())
    return parts, y_uncut


# The tiny preset keeps every inequality of the real one: d=24 against 4
# query heads of 8 (heads x head_dim = 32 != d), 2 key/value heads, one period
# of 4 layers (global, window, window, window), a window of 8 keys at S=32,
# 8 experts top-3 of width 16 of which experts 4..7 are held (share 1 of 2),
# vocab 97 — in the reference's (the published config's) keys.
CASE = suite.ArchCase(
    arch="smallthinker", parallelism="ep", config="smallthinker_21b_a3b",
    tiny=dict(hidden_size=24, head_dim=8, num_attention_heads=4,
              num_key_value_heads=2, num_hidden_layers=4,
              sliding_window_size=WINDOW, moe_ffn_hidden_size=16,
              moe_num_active_primary_experts=3, moe_num_primary_experts=4,
              moe_num_primary_experts_published=8, experts_held=4,
              experts_share=1, vocab_size=97, max_position_embeddings=S),
    flags=dict(lm_d_model=24, lm_head_dim=8, lm_heads=4, lm_kv_heads=2,
               lm_layers=4, lm_ffn_dim=16, lm_moe_top_k=3, lm_experts=8,
               lm_experts_held=4, lm_vocab=97, lm_seq_len=S),
    row=dict(window=WINDOW), share=1, logit_tol=1e-4,
    tol_reason="float32 both sides, only the order of reductions differs "
               "(the sorted grouped matmul against a dense loop over experts, "
               "flax's norm against a hand-written one): measured 3e-6 on "
               "logits up to 6; 1e-4 is thirty times that and far under what "
               "any of the MUTANTS below changes",
    scopes=suite.LM_SCOPES | suite.EXPERT_SCOPES,
    remat_scopes=frozenset({"moe_experts"}), another_depth=1,
    refusals=(
        ("generate.py", suite.by_generate,
         ("lm_arch=smallthinker", "not built")),
        ("serve.py", suite.by_serve, ("lm_arch=smallthinker", "not built")),
        ("two chips", suite.by_two_chips,
         ("dropless routing across chips: not built",))),
    published_row=dict(aux_coef="load_balance_coef_as_run",
                       z_loss_coef="z_loss_coef_as_run"),
    shares=_shares)
REF, PUBLISHED, TINY = CASE.reference, CASE.published, CASE.tiny_config
UNCUT = dict(TINY, moe_num_primary_experts=8, experts_held=8, experts_share=0)
LOGIT_TOL = CASE.logit_tol

suite.install(globals(), CASE)


def test_the_tiny_model_keeps_the_real_ones_inequalities(tiny):
    """Heads times head_dim is not d, fewer key/value heads, a router over all
    8 outputs beside 4 held experts, no position table; the block of experts
    4..7 draws about half the assignments."""
    _, variables, _ = tiny
    stats = suite.logits(CASE)[1]
    assert 0.3 < float(stats["moe_held_share"]) < 0.7
    p = variables["params"]["block_1"]
    assert p["Dense_0"]["kernel"].shape == (24, 32)      # q: heads x head_dim
    assert p["Dense_1"]["kernel"].shape == (24, 16)      # k: kv heads x head_dim
    assert p["Dense_3"]["kernel"].shape == (32, 24)
    assert p["moe"]["router"]["kernel"].shape == (24, 8)  # all 8 outputs
    assert p["moe"]["experts_gate"].shape == (4, 24, 16)  # 4 held
    assert "pos_embed" not in variables["params"]


def _tiled_kv_attention(q, k, v, **kw):
    """Query head h reading key/value head h % kv_heads: the wrong one."""
    group = q.shape[1] // k.shape[1]
    return full_attention(q, jnp.tile(k, (1, group, 1, 1)),
                          jnp.tile(v, (1, group, 1, 1)), **kw)


def _gmm_skipping_the_first_expert(lhs, rhs, group_sizes):
    out = gmm(lhs, rhs, group_sizes)
    rows = jnp.arange(out.shape[0])[:, None]
    return jnp.where(rows < group_sizes[0], 0.0, out).astype(out.dtype)


def _row(**kw):
    return (tr_mod.ARCHS, "smallthinker",
            ARCHS["smallthinker"]._replace(window=WINDOW, **kw))


def _fp8(variables):
    return jax.tree.map(
        lambda a: a.astype(jnp.float8_e4m3fn).astype(jnp.float32), variables)


MUTANTS = {
    # name -> what to patch on the program's side: (target, name, value)
    "window_ignored": _row(window_layers=()),
    "rope_on_the_global_layer": _row(rope_layers=()),
    "rope_missing_on_a_window_layer": _row(rope_layers=(0, 0, 1, 1)),
    "kv_head_h_mod_4": (tr_mod, "full_attention", _tiled_kv_attention),
    "silu_for_relu": _row(expert_act="silu"),
    "gates_not_renormalised": _row(gate_norm=False),
    "router_after_attention": _row(early_router=False),
    "one_held_expert_skipped": (moe_mod, "gmm",
                                _gmm_skipping_the_first_expert),
    "parameters_in_float8": None,       # the reference's side, see below
}


@pytest.mark.parametrize("name", sorted(MUTANTS))
def test_the_logit_tolerance_catches(tiny, monkeypatch, name):
    """Each mistake the tolerance has to catch moves the logits by far more
    than LOGIT_TOL; so does the nearest precision below the one the
    configuration states (every parameter rounded to float8_e4m3fn)."""
    model, variables, tokens = tiny
    patch = MUTANTS[name]
    if patch is not None:
        target, attr, value = patch
        if isinstance(target, dict):
            monkeypatch.setitem(target, attr, value)
        else:
            monkeypatch.setattr(target, attr, value)
    got = suite.apply_logits(model, variables, tokens)
    want = REF.forward(_fp8(variables) if patch is None else variables,
                       tokens, TINY)
    assert float(jnp.abs(got - want).max()) > 50 * LOGIT_TOL


def test_the_step_reports_the_reference_loss_terms():
    """The common step case holds every parameter's move to the reference's
    gradient (through the early router, the renormalised gates, the sort, the
    grouped matmuls over the held experts and both kinds of attention layer);
    here the loss's terms: cross-entropy and the load-balance term over all 8
    router outputs, no z-loss, each as the step reports it, at the share of
    experts a trainer holds (the first)."""
    _, variables, tokens = suite.tiny(CASE)
    _, _, m = suite.first_step(CASE, False)
    assert ARCHS["smallthinker"].z_loss_coef == 0.0
    ce, lb, z = REF.loss_terms(variables, tokens, CASE.step_config)
    np.testing.assert_allclose(float(m["loss"]), float(ce), rtol=1e-5)
    np.testing.assert_allclose(float(m["aux"]), float(lb), rtol=1e-5)
    np.testing.assert_allclose(float(m["z_loss"]), float(z), rtol=1e-5)


def test_param_count_published_as_run_and_tiny(tiny):
    _, variables, _ = tiny
    published = dict(PUBLISHED, **PUBLISHED["published"], experts_held=64)
    assert REF.param_count(published) == PUBLISHED["parameters_published"] \
        == 21_506_562_560
    # as run (an eighth of the vocabulary, the memory rule's finding), and
    # the cut the rule tried first (a quarter)
    assert REF.param_count(PUBLISHED) == PUBLISHED["parameters_as_run"] \
        == 559_290_880
    assert REF.param_count(dict(PUBLISHED, vocab_size=37984)) == 656_529_920
    assert REF.param_count(TINY) == sum(
        a.size for a in jax.tree.leaves(variables["params"]))


@pytest.mark.parametrize("what", ["forward", "forward_and_backward"])
def test_closed_form_flops_against_the_jaxpr_walk(tiny, what):
    """The closed form charges attention by the pairs the masks admit and the
    experts at balance over the share held; the walk of the program finds the
    same projections, router and head, attention dense S x S (``full_attention``
    multiplies what it then masks) and the experts on every sorted row. With
    those two parts exchanged the forward agrees exactly; for training the
    closed form charges 3x the forward and the walk finds less by the gradient
    to the token ids."""
    model, variables, tokens = tiny
    parts = REF.macs_per_token(TINY, S)
    assert REF.train_flops_per_sample(TINY, seq_len=S) \
        == 6 * sum(parts.values())
    assert REF.keys_per_query(S) == (S + 1) / 2
    assert REF.keys_per_query(S, WINDOW) == sum(
        min(i + 1, WINDOW) for i in range(S)) / S
    assert parts["attention"] == 2 * 32 * (
        REF.keys_per_query(S) + 3 * REF.keys_per_query(S, WINDOW))
    assert parts["experts"] == 4 * 3 * (4 / 8) * 3 * 24 * 16
    walked_parts = dict(parts, attention=4 * 2 * 32 * S,
                        experts=4 * 3 * 3 * 24 * 16)
    per_token = 2 * sum(walked_parts.values())
    if what == "forward":
        walked = count_jaxpr_flops(jax.make_jaxpr(
            lambda v: suite.apply_logits(model, v, tokens))(
                variables).jaxpr)
        assert walked == per_token * tokens.size
    else:
        walked = count_jaxpr_flops(jax.make_jaxpr(jax.grad(
            lambda v: suite.apply_logits(model, v, tokens).sum()))(
                variables).jaxpr)
        assert 0.9 * 3 * per_token * tokens.size < walked \
            <= 3 * per_token * tokens.size


def test_the_real_shapes_flops_are_the_honest_count():
    """About 353M multiply-adds a token at S=16384 with a quarter of the
    vocabulary, 304M with the eighth the cell runs; the dense S x S charge
    the OLMoE reference makes would nearly double it."""
    parts = REF.macs_per_token(dict(PUBLISHED, vocab_size=37984), 16384)
    assert abs(REF.keys_per_query(16384, 4096) - 3584.125) < 1e-9
    assert abs(parts["experts"] - 4 * 1.5 * 3 * 2560 * 768) < 1e-6
    assert 352e6 < sum(parts.values()) < 354e6
    dense = dict(parts, attention=4 * 2 * 3584 * 16384)
    assert sum(dense.values()) > 1.9 * sum(parts.values())
    as_run = REF.macs_per_token(PUBLISHED, 16384)
    assert as_run["head"] == 2560 * 18992
    assert {k: v for k, v in as_run.items() if k != "head"} \
        == {k: v for k, v in parts.items() if k != "head"}
    assert 304e6 < sum(as_run.values()) < 305e6
    assert REF.train_flops_per_sample(PUBLISHED, seq_len=16384) \
        == 6 * sum(as_run.values())


_EP = dict(lm_parallelism="ep", lm_experts=64, lm_moe_top_k=6)
CONFIG_CASES = {
    "the_cells_flags": (
        dict(lm_arch="smallthinker", lm_heads=28, lm_kv_heads=4,
             lm_head_dim=128, lm_experts_held=16, lm_ffn_dim=768, **_EP),
        None),
    "smallthinker_needs_ep": (dict(lm_arch="smallthinker"),
                              "lm_parallelism=ep"),
    "kv_heads_divide_the_heads": (dict(lm_heads=4, lm_kv_heads=3),
                                  "lm_kv_heads"),
    "odd_head_dim": (dict(lm_head_dim=7), "lm_head_dim"),
    "held_divides_the_experts": (
        dict(lm_arch="smallthinker", lm_experts_held=5, **_EP),
        "lm_experts_held"),
    "held_needs_a_dropless_arch": (
        dict(lm_parallelism="ep", lm_experts_held=2), "dropless arch"),
    "tp_with_kv_heads": (dict(lm_parallelism="tp", lm_kv_heads=2), "tp and pp"),
    "pp_with_a_head_dim": (dict(lm_parallelism="pp", lm_head_dim=16),
                           "tp and pp"),
}


@pytest.mark.parametrize("name", sorted(CONFIG_CASES))
def test_config_validation(name):
    kw, error = CONFIG_CASES[name]
    if error is None:
        cfg = TrainConfig.from_json(TrainConfig(**kw).to_json())
        assert (cfg.lm_arch, cfg.lm_kv_heads, cfg.lm_head_dim,
                cfg.lm_experts_held) == ("smallthinker", 4, 128, 16)
    else:
        with pytest.raises(ValueError, match=error):
            TrainConfig(**kw)


def test_config_names_the_dropless_archs_the_models_have():
    from ps_pytorch_tpu import config
    assert config.LM_ARCHS == tuple(ARCHS)
    assert config._DROPLESS_ARCHS == tuple(
        name for name, row in ARCHS.items() if row.dropless)


def _refused_ring():
    q = jnp.zeros((1, 4, 8, 8))
    tr_mod.ring_attention(q, q[:, :2], q[:, :2], "data", causal=True)


def _refused_ring_window():
    q = jnp.zeros((1, 4, 8, 8))
    tr_mod.ring_attention(q, q, q, "data", causal=True, window=4)


def _refused_decode():
    model = TransformerLM(vocab_size=17, n_layers=1, n_heads=4, kv_heads=2,
                          d_model=16, max_seq_len=8, decode=True,
                          decode_cache_len=8)
    model.init(jax.random.key(0), jnp.zeros((1, 1), jnp.int32))


def _refused_decode_window():
    model = MoETransformerLM(
        vocab_size=17, n_layers=2, n_heads=4, d_model=16, n_experts=4,
        top_k=2, max_seq_len=32, arch="smallthinker", decode=True,
        decode_cache_len=32)
    model.init(jax.random.key(0), jnp.zeros((1, 1), jnp.int32))


def _refused_tp():
    from ps_pytorch_tpu.parallel.tp import make_tp_train_step
    make_tp_train_step(TransformerLM(n_heads=4, kv_heads=2), None, None, None)


def _refused_pp():
    from ps_pytorch_tpu.parallel.pp import make_pp_train_step
    make_pp_train_step(TransformerLM(n_heads=4, head_dim=16), None, None, None,
                       num_microbatches=1)


def _refused_window_without_causal():
    q = jnp.zeros((1, 2, 16, 8))
    flash_attention(q, q, q, causal=False, window=4)


REFUSALS = {
    "ring_with_fewer_kv_heads": (_refused_ring, "ring attention is not built"),
    "ring_with_a_window": (_refused_ring_window, "ring attention is not built"),
    "decode_with_fewer_kv_heads": (_refused_decode, "decode is not built"),
    "decode_with_a_window": (_refused_decode_window, "decode is not built"),
    "tp_with_fewer_kv_heads": (_refused_tp, "tensor parallelism is not built"),
    "pp_with_a_head_dim": (_refused_pp, "pipeline parallelism is not built"),
    "a_window_without_causal": (_refused_window_without_causal,
                                "needs causal=True"),
}


@pytest.mark.parametrize("name", sorted(REFUSALS))
def test_refused_with_one_message(name):
    call, message = REFUSALS[name]
    with pytest.raises(ValueError, match=message):
        call()
