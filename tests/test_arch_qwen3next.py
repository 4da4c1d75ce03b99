"""The ``qwen3next`` arch (Gated DeltaNet linear-attention layers with a chunked
delta rule and its hand-written backward, gated softmax attention with a
rotated quarter, zero-centred norms, top-k experts beside a gated shared
expert) against its plain reference
``benchmark/reference/qwen3_next_80b_a3b.py`` at a tiny float32 size: the
common suite (``tests/arch_suite.py``) and what is Qwen3-Next's alone: the
zero-centred norm, the rotated quarter, the kinds of layer by period, the
initialisers, the counter sown, what its controls cover and the ``KERNELS``
line. The delta rule itself is ``tests/test_gated_delta_rule.py``'s."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import arch_suite as suite
from ps_pytorch_tpu.models import transformer as tr_mod
from ps_pytorch_tpu.models.moe import DroplessMoE, MoETransformerLM
from ps_pytorch_tpu.models.transformer import (
    ARCHS, LAYER_KINDS, LM_COUNTERS, ZeroCentredRMSNorm, lm_counters,
    make_norm, refuse_hybrid, rope, rope_on_a_share,
)

S, VOCAB, D = 96, 97, 32
ROW = ARCHS["qwen3next"]
TINY_ROW = dict(gdn_key_heads=2, gdn_value_heads=4, gdn_key_dim=16,
                gdn_value_dim=16)


def _model(**kw):
    base = dict(vocab_size=VOCAB, n_layers=4, n_heads=4, kv_heads=2,
                head_dim=16, d_model=D, max_seq_len=S, arch="qwen3next",
                n_experts=16, top_k=3, ffn_dim=16, experts_held=4,
                experts_share=1)
    base.update(kw)
    return MoETransformerLM(**base)


def _shares(side):
    """One expert layer at the tiny size, all 16 experts' weights seeded: the
    routed parts of the four shares (4 of 16 experts held, share 0..3) and
    the GATED shared expert COUNTED ONCE, against the uncut reference
    layer's contribution."""
    model = _model(n_layers=1, experts_held=0, experts_share=0)
    with CASE.patched():
        params = suite.unsettled(
            jax.jit(model.init)(jax.random.key(3),
                       jnp.zeros((1, S), jnp.int32))["params"],
            jax.random.key(4))
    bp = params["block_0"]
    m = jax.random.normal(jax.random.key(5), (S, D))
    uncut = dict(TINY, num_experts=16, experts_held=16, experts_share=0)
    f_uncut, _ = REF.expert_layer(bp, m, uncut)
    shared = REF.shared_gate(m, bp) * REF._swiglu(bp["shared"], m)
    assert float(jnp.abs(shared).max()) > 0.01
    parts, held_total = [shared], 0.0
    for share in range(4):
        moe_s = {k: v[4 * share:4 * share + 4] if k.startswith("experts_")
                 else v for k, v in bp["moe"].items()}
        if side == "program":
            routed, stats = DroplessMoE(
                16, D, 16, top_k=3, gate_norm=True, n_held=4,
                share=share).apply({"params": moe_s}, m[None])
            routed = routed[0]
            assert float(stats["moe_dropped"]) == 0.0
            held_total += float(stats["moe_held_share"])
        else:
            f_s, _ = REF.expert_layer(
                {**bp, "moe": moe_s}, m,
                dict(uncut, num_experts=4, experts_held=4,
                     experts_share=share))
            routed = f_s - shared   # each share's f holds the shared expert whole
        parts.append(routed)
    if side == "program":
        np.testing.assert_allclose(held_total, 1.0, rtol=1e-6)
    return parts, f_uncut


def _refused_by_ring(case, tmp_path):
    refuse_hybrid("qwen3next", "ring attention")


# The tiny preset keeps the published ratios: d=32; linear layers of 2 key and
# 4 value heads of 16 (two value heads a key head); attention of 4 query heads
# on 2 key/value heads of 16, 4 of them rotated; 16 experts top-3 of width 16,
# experts 4..7 held (share 1 of 4); depth 4, one period (what a second period
# adds is an index, which the kinds-by-period case and the parameter case at
# depth 8 hold without a compile); S=96, a chunk and a half
# of the delta rule's 64; vocab 97: in the reference's (the published
# config's) keys.
CASE = suite.ArchCase(
    arch="qwen3next", parallelism="ep", config="qwen3_next_80b_a3b",
    controls=True,
    tiny=dict(hidden_size=D, head_dim=16, num_attention_heads=4,
              num_key_value_heads=2, linear_num_key_heads=2,
              linear_num_value_heads=4, linear_key_head_dim=16,
              linear_value_head_dim=16, moe_intermediate_size=16,
              shared_expert_intermediate_size=16, num_experts=4,
              num_experts_published=16, experts_held=4, experts_share=1,
              num_experts_per_tok=3, num_hidden_layers=4, vocab_size=VOCAB),
    flags=dict(lm_d_model=D, lm_head_dim=16, lm_heads=4, lm_kv_heads=2,
               lm_ffn_dim=16, lm_experts=16, lm_experts_held=4,
               lm_moe_top_k=3, lm_layers=4, lm_vocab=VOCAB, lm_seq_len=S),
    row=TINY_ROW, share=1, logit_tol=2e-4,
    tol_reason="float32 both sides, only the order of reductions differs: "
               "measured 1.3e-5 on logits up to 4; 2e-4 is far under what any "
               "control changes",
    counters={"gdn_state_abs_max": (0.1, 50)},
    scopes=suite.LM_SCOPES | suite.EXPERT_SCOPES
    | {"gdn_proj", "gdn_mix", "gdn_core", "moe_shared"},
    remat_scopes=frozenset({"moe_experts", "gdn_core"}), another_depth=8,
    refusals=suite.hybrid_refusals("qwen3next", "ep", (
        (suite.by_generate, "generate.py", "matrix state"),
        (suite.by_serve, "serve.py", "matrix state"),
        (suite.by_decode, "decode", "matrix state"),
        (suite.by_tp, "tensor parallelism", "model axis"),
        (suite.by_pp, "pipeline parallelism", "more than one kind"),
        (_refused_by_ring, "ring attention", "sequence shards"))),
    published_row=dict(
        gdn_key_heads="linear_num_key_heads",
        gdn_value_heads="linear_num_value_heads",
        gdn_key_dim="linear_key_head_dim", gdn_value_dim="linear_value_head_dim",
        gdn_conv="linear_conv_kernel_dim", norm_eps="rms_norm_eps",
        rope_theta="rope_theta", rope_share="partial_rotary_factor",
        aux_coef="router_aux_loss_coef"),
    shares=_shares)
REF, PUBLISHED, TINY = CASE.reference, CASE.published, CASE.tiny_config
CONTROLS = CASE.planted

suite.install(globals(), CASE)


# ---- the layers ------------------------------------------------------------------

def test_zero_centred_norm_scales_by_one_plus_w():
    x = jax.random.normal(jax.random.key(0), (3, 7, D)) * 4
    w = jax.random.normal(jax.random.key(1), (D,)) * 0.3
    norm = make_norm("qwen3next", jnp.float32)
    assert isinstance(norm, ZeroCentredRMSNorm) and norm.epsilon == 1e-6
    init = norm.init(jax.random.key(2), x)["params"]["scale"]
    assert float(jnp.abs(init).max()) == 0.0            # w starts at 0
    got = norm.apply({"params": {"scale": w}}, x)
    want = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-6) \
        * (1 + w)
    np.testing.assert_allclose(got, want, rtol=1e-6)
    np.testing.assert_allclose(got, REF.norm(x, {"scale": w}, 1e-6), rtol=1e-6)
    # float32 statistics under a narrower dtype, the result in that dtype
    out = make_norm("qwen3next", jnp.bfloat16).apply(
        {"params": {"scale": w}}, x.astype(jnp.bfloat16))
    assert out.dtype == jnp.bfloat16
    # the other archs' norms are what they were
    assert not isinstance(make_norm("olmoe", jnp.float32), ZeroCentredRMSNorm)


@pytest.mark.parametrize("wrong", ["whole_head", "last_quarter"])
def test_rope_rotates_the_first_quarter_of_a_head(wrong):
    x = jax.random.normal(jax.random.key(0), (1, 2, 12, 16))
    pos = jnp.arange(12)
    got = rope_on_a_share(x, pos, 1e7, 0.25)
    np.testing.assert_array_equal(got[..., 4:], x[..., 4:])
    np.testing.assert_allclose(got[..., :4], rope(x[..., :4], pos, 1e7),
                               rtol=1e-6)
    np.testing.assert_allclose(
        got[0, 1], REF._rope(x[0, 1], 1e7, REF.rotated_features(TINY)),
        rtol=1e-5, atol=1e-6)
    other = rope(x, pos, 1e7) if wrong == "whole_head" else jnp.concatenate(
        [x[..., :12], rope(x[..., 12:], pos, 1e7)], axis=-1)
    assert float(jnp.abs(got - other)[..., 1:, :].max()) > 0.1
    assert ROW.rope_share == PUBLISHED["partial_rotary_factor"] == 0.25
    assert ARCHS["olmoe"].rope_share == 1.0


@pytest.mark.parametrize("depth", [8, 48])
def test_layer_kinds_follow_the_published_period(depth):
    got = [ROW.layer_kind(i, depth) for i in range(depth)]
    assert got == ["gdn", "gdn", "gdn", "attention"] * (depth // 4)
    assert set(got) <= set(LAYER_KINDS)
    assert [k == "gdn" for k in got] == [
        REF.is_linear(dict(TINY, num_hidden_layers=depth), i)
        for i in range(depth)]
    assert PUBLISHED["full_attention_interval"] == len(ROW.mixer_layers)
    assert ARCHS["trinity"].layer_kind(2, depth) == "attention"


def test_the_depth_is_a_hybrids_to_give_and_a_counter_is_never_dropped():
    """A period's kinds need no depth; a hybrid's do, and say so. A block
    whose mixer counts and whose second half hands no statistics on (a
    leading dense layer here) raises where it would lose the counter."""
    assert [ROW.layer_kind(i) for i in range(4)] == list(ROW.mixer_layers)
    with pytest.raises(ValueError, match="multiple of 4"):
        ARCHS["phi4flash"].layer_kind(0)
    model = _model(n_layers=4, dense_layers=1, dense_ffn_dim=32)
    tokens = jnp.zeros((1, S), jnp.int32)
    with pytest.raises(NotImplementedError, match="gdn_state_abs_max"):
        jax.eval_shape(model.init, jax.random.key(0), tokens)


def test_parameters_by_kind_of_layer(tiny):
    _, variables, _ = tiny
    p = variables["params"]
    both = {"ZeroCentredRMSNorm_0", "ZeroCentredRMSNorm_1", "moe", "shared",
            "shared_gate"}
    assert set(p["block_0"]) == both | {
        "in_proj_qkvz", "in_proj_ba", "conv_weight", "A_log", "dt_bias",
        "gdn_norm", "out_proj"}
    assert set(p["block_3"]) == both | {
        "Dense_0", "Dense_1", "Dense_2", "Dense_3", "gate", "q_norm",
        "k_norm"}
    two_periods = jax.eval_shape(_model(n_layers=8).init, jax.random.key(0),
                                 jnp.zeros((1, S), jnp.int32))["params"]
    assert set(two_periods["block_4"]) == set(p["block_0"])
    assert set(two_periods["block_7"]) == set(p["block_3"])
    assert p["block_0"]["in_proj_qkvz"]["kernel"].shape == (D, 2 * 32 + 2 * 64)
    assert p["block_0"]["conv_weight"].shape == (4, 2 * 32 + 64)  # no z, no bias
    assert p["block_0"]["A_log"].shape == p["block_0"]["dt_bias"].shape == (4,)
    assert p["block_0"]["gdn_norm"]["scale"].shape == (16,)
    assert p["block_3"]["q_norm"]["scale"].shape == (16,)
    assert p["block_3"]["Dense_1"]["kernel"].shape == (D, 32)     # kv heads
    assert p["block_0"]["shared_gate"]["kernel"].shape == (D, 1)
    assert p["block_0"]["moe"]["router"]["kernel"].shape == (D, 16)
    assert p["block_0"]["moe"]["experts_down"].shape == (4, 16, D)
    n = sum(a.size for a in jax.tree.leaves(p))
    assert n == REF.param_count(TINY)


def test_the_linear_layers_initialisers():
    full = MoETransformerLM(arch="qwen3next", n_layers=1, n_experts=4,
                            top_k=1, d_model=16, n_heads=2, ffn_dim=8)
    tr_mod.ARCHS["qwen3next"] = ROW         # the published 32 value heads
    b0 = jax.jit(full.init)(jax.random.key(4), jnp.zeros((1, 8), jnp.int32))[
        "params"]["block_0"]
    a = np.exp(np.asarray(b0["A_log"]))
    assert a.shape == (32,) and 0 < a.min() and a.max() < 16 and a.std() > 2
    step = np.log1p(np.exp(np.asarray(b0["dt_bias"])))      # softplus
    assert 0.001 <= step.min() and step.max() <= 0.1001
    assert float(jnp.abs(b0["conv_weight"]).max()) <= 0.5
    assert float(jnp.abs(b0["gdn_norm"]["scale"] - 1).max()) == 0
    assert float(jnp.abs(b0["ZeroCentredRMSNorm_0"]["scale"]).max()) == 0


# ---- the step ---------------------------------------------------------------------

def test_the_step_reports_the_reference_balance_term():
    """The common step case holds every parameter's move to the gradient of
    the reference's loss (cross-entropy plus 0.001 of the load-balance term:
    through the delta rule's hand-written backward, the convolution, both
    gates, the zero-centred norms, the rotated quarter and the renormalised
    gates, over two periods of layers); here the term itself."""
    _, variables, tokens = suite.tiny(CASE)
    _, _, m = suite.first_step(CASE, False)
    assert ROW.aux_coef == TINY["router_aux_loss_coef"] == 0.001
    assert ROW.z_loss_coef == 0.0
    balance = jax.jit(lambda v: REF._forward(v, tokens, CASE.step_config)[1])(
        variables)
    np.testing.assert_allclose(float(m["aux"]), float(balance), rtol=1e-5)


def test_counters_are_sown_and_other_archs_return_none(tiny):
    model, variables, tokens = tiny
    (logits, _), sown = jax.jit(lambda v, t: model.apply(
        v, t, mutable=[LM_COUNTERS]))(variables, tokens)
    counters = lm_counters(sown)
    assert set(counters) == {"gdn_state_abs_max"} == set(CASE.counters)
    assert float(counters["gdn_state_abs_max"]) > 0
    # sowing changes no logit (two compiled programs: equal to rounding)
    np.testing.assert_allclose(logits, suite.logits(CASE)[0], atol=1e-6)
    olmoe = MoETransformerLM(vocab_size=VOCAB, n_layers=1, n_heads=2,
                             d_model=16, n_experts=4, top_k=2, arch="olmoe",
                             ffn_dim=8)
    v = {"params": olmoe.init(jax.random.key(0), tokens)["params"]}
    _, sown = olmoe.apply(v, tokens, mutable=[LM_COUNTERS])
    assert lm_counters(sown) == {}


def test_the_kernels_line_and_bfloat16_reach_the_layers(tmp_path):
    """``LMTrainer``'s ``KERNELS`` line prints the delta rule's schedule and
    the mixer ops' beside the flash record and the grouped matmul; and
    ``--compute-dtype bfloat16`` reaches the linear layers: their output
    leaves in it while the gate and the parameters stay float32 (shapes only:
    nothing is compiled)."""
    from ps_pytorch_tpu.runtime.lm_eval import build_lm_model
    kernels = suite.step(CASE, True).kernels
    assert kernels.count("flash_attention[") == 1     # one kind of attention layer
    assert "gated_delta_rule[chunk=64 chunks=2 group=2 grid=4x1 heads=2 solve_grid=4x1 " in kernels
    assert " gdn_mix[lanes=32 rows=96 chunk=96 halo=16 conv_grid=2x4x1 norm_grid=2x2x1 conv_fwd_bytes=" in kernels
    # 512 rows sized for 144 at balance (4 of 16 experts, 576 assignments)
    assert " grouped_matmul[fwd=512/32/16 dlhs=512/16/32 drhs=512/32/16 " \
        "row_tiles=1 row_tiles_at_balance=1 visits=5 weight_bytes=8192] " \
        "moe_rows[tokens_tile=192 seg=8 segs=32 cols=32 tiles=1 rows=512 " \
        "rows_at_balance=144 fwd_bytes=67584 bwd_bytes=43008] " \
        "mode=interpret dtype=float32" in kernels

    narrow = build_lm_model(CASE.train_config(compute_dtype="bfloat16",
                                              train_dir=str(tmp_path)))
    assert narrow.dtype == jnp.bfloat16
    tokens = jnp.zeros((1, S), jnp.int32)
    params = jax.eval_shape(narrow.init, jax.random.key(0), tokens)["params"]
    assert all(a.dtype == jnp.float32 for a in jax.tree.leaves(params))
    _, state = jax.eval_shape(
        lambda v: narrow.apply(v, tokens, capture_intermediates=True,
                               mutable=["intermediates"]),
        {"params": params})
    block = state["intermediates"]["block_0"]
    assert block["out_proj"]["__call__"][0].dtype == jnp.bfloat16
    # the output norm is ``ops/gdn_mix.gated_rms_norm`` since PR 41; the
    # module under its name only hands that op the float32 scale
    assert block["gdn_norm"]["__call__"][0].dtype == jnp.float32


# ---- planted mistakes ------------------------------------------------------------------

def test_the_controls_cover_what_the_issue_names():
    assert set(CONTROLS.CONTROLS) == {
        "beta_left_out", "decay_left_out", "key_l2_norm_left_out",
        "query_scale_left_out", "key_head_tiled_not_repeated",
        "conv_not_causal", "norm_not_zero_centred",
        "rope_over_the_whole_head", "attention_gate_left_out",
        "shared_gate_left_out", "gates_not_renormalised",
        *CONTROLS.PRECISION_CONTROLS}


# ---- the row ---------------------------------------------------------------------

def test_the_row_holds_the_published_switches():
    """The sizes and rates are held to the published config by the common
    config case; here the switches."""
    assert ROW.zero_centred_norm and ROW.shared_gate and ROW.attn_gate \
        and ROW.head_qk_norm and ROW.gate_norm and ROW.shared_experts == 1
    refuse_hybrid("qwen3next", "expert parallelism")    # its own path is not refused
