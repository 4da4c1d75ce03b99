"""The ``trinity`` arch (a leading dense layer, then expert layers with a shared
expert beside the routed ones; sigmoid scores, a top-k chosen under a bias, gates
renormalised and scaled; q/k norm a head, a gated attention output, a norm on
each sublayer's input and output, the embedding times sqrt(d); window layers
with RoPE and global layers without position encoding) against its plain
reference ``benchmark/reference/trinity_mini.py`` at a tiny size: the common
suite (``tests/arch_suite.py``) and what is
Trinity's alone: the bias (its step, what the optimizer leaves alone, a
checkpoint that holds it and an oracle that reads it), the flags of the dense
layer, and the counts of parameters and FLOPs."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import arch_suite as suite
from ps_pytorch_tpu.config import TrainConfig
from ps_pytorch_tpu.models.moe import (
    BIAS_STATS, DROPLESS_STATS, EXPERT_COUNTS, MOE_STATE, DroplessMoE,
    GatedFFN, MoETransformerLM, lm_variables, update_expert_bias,
)
from ps_pytorch_tpu.models.transformer import ARCHS

S, WINDOW = 32, 8
ROW = ARCHS["trinity"]      # as published, before the tiny window


def _model(**kw):
    base = dict(vocab_size=97, n_layers=5, n_heads=4, kv_heads=2, head_dim=8,
                d_model=24, max_seq_len=S, arch="trinity", ffn_dim=16,
                n_experts=8, top_k=3, experts_held=4, experts_share=1,
                dense_layers=1, dense_ffn_dim=40)
    base.update(kw)
    return MoETransformerLM(**base)


def _one_layer(key):
    """Block 1 of an uncut tiny model (16 experts top-3, so that 8 shares of
    2 exist), its bias seeded, and a normed stream m [S, d]."""
    model = _model(n_experts=16, experts_held=0, experts_share=0)
    variables = suite.unsettled(
        dict(jax.jit(model.init)(key, jnp.zeros((1, S), jnp.int32))),
        jax.random.fold_in(key, 1))
    m = jax.random.normal(jax.random.fold_in(key, 2), (S, 24))
    return (variables["params"]["block_1"],
            variables[MOE_STATE]["block_1"]["moe"], m)


def _share_of(moe_params, held, share):
    return {k: v[share * held:(share + 1) * held] if k.startswith("experts_")
            else v for k, v in moe_params.items()}


def _shares(side):
    """One expert layer at the tiny size, all 16 experts' weights seeded: the
    routed parts of the eight shares (2 of 16 experts held, share 0..7) and
    the shared expert COUNTED ONCE, against the uncut reference layer's f
    (before its output norm); and the eight blocks' counts add up to the
    layer's."""
    bp, bias, m = _one_layer(jax.random.key(3))
    uncut = dict(TINY, num_experts=16, num_experts_published=16,
                 experts_held=16, experts_share=0)
    f_uncut, w = REF._feed_forward(bp, bias["expert_bias"], m, uncut, 1)
    counts_uncut = jnp.sum(w > 0, axis=0)
    shared = REF._swiglu(bp["shared"], m)
    assert float(jnp.abs(shared).max()) > 0.1
    parts, counts = [shared], []
    for share in range(8):
        moe_s = _share_of(bp["moe"], 2, share)
        if side == "program":
            layer = DroplessMoE(16, 24, 16, top_k=3, gate_norm=True, n_held=2,
                                share=share, score="sigmoid",
                                select_bias=True, route_scale=TINY["route_scale"])
            routed, stats = layer.apply(
                {"params": moe_s, MOE_STATE: bias}, m[None])
            routed = routed[0]
            assert float(stats["moe_dropped"]) == 0.0
            all_counts = stats[EXPERT_COUNTS]["expert_bias"]
            np.testing.assert_array_equal(all_counts, counts_uncut)
            held = all_counts[2 * share:2 * share + 2]
            np.testing.assert_allclose(
                float(stats["moe_held_share"]) * S * 3, int(held.sum()))
        else:
            f_s, w_s = REF._feed_forward(
                {**bp, "moe": moe_s}, bias["expert_bias"], m,
                dict(uncut, num_experts=2, experts_held=2,
                     experts_share=share), 1)
            routed = f_s - shared       # each share's f holds the shared expert whole
            held = jnp.sum(w_s > 0, axis=0)[2 * share:2 * share + 2]
        parts.append(routed)
        counts.append(held)
    np.testing.assert_array_equal(jnp.concatenate(counts), counts_uncut)
    assert int(counts_uncut.sum()) == S * 3
    if side == "program":       # the program's shared expert is the reference's
        got = GatedFFN(16).apply({"params": bp["shared"]}, m)
        np.testing.assert_allclose(got, shared, atol=1e-6)
    return parts, f_uncut


# The tiny preset keeps every inequality of the real one: d=24 against 4 query
# heads of 8 (heads x head_dim = 32 != d) on 2 key/value heads, the published
# layers 0..4 (window, window, window, global, window) of which the first is
# dense (width 40), a window of 8 keys at S=32, 8 experts top-3 of width 16 of
# which experts 4..7 are held (share 1 of 2), one shared expert, vocab 97: in
# the reference's (the published config's) keys.
CASE = suite.ArchCase(
    arch="trinity", parallelism="ep", config="trinity_mini", controls=True,
    margins=(50, 50),
    tiny=dict(hidden_size=24, head_dim=8, num_attention_heads=4,
              num_key_value_heads=2, num_hidden_layers=5, num_dense_layers=1,
              sliding_window=WINDOW, intermediate_size=40,
              moe_intermediate_size=16, num_experts_per_tok=3, num_experts=4,
              num_experts_published=8, experts_held=4, experts_share=1,
              vocab_size=97),
    flags=dict(lm_d_model=24, lm_head_dim=8, lm_heads=4, lm_kv_heads=2,
               lm_layers=5, lm_dense_layers=1, lm_dense_ffn_dim=40,
               lm_ffn_dim=16, lm_moe_top_k=3, lm_experts=8, lm_experts_held=4,
               lm_vocab=97, lm_seq_len=S),
    row=dict(window=WINDOW), share=1, logit_tol=1e-4,
    tol_reason="float32 both sides, only the order of reductions differs: "
               "measured 4e-6 on logits up to 4; 1e-4 is far under what any "
               "of the controls changes",
    scopes=suite.LM_SCOPES | suite.EXPERT_SCOPES
    | {"ffn", "moe_shared", "router_bias"},
    remat_scopes=frozenset({"moe_experts"}), another_depth=2,
    refusals=(
        ("generate.py", suite.by_generate, ("lm_arch=trinity", "not built")),
        ("serve.py", suite.by_serve, ("lm_arch=trinity", "not built")),
        ("two chips", suite.by_two_chips,
         ("dropless routing across chips: not built",))),
    published_row=dict(router_bias_rate="load_balance_coeff",
                       route_scale="route_scale"),
    shares=_shares)
REF, PUBLISHED, TINY = CASE.reference, CASE.published, CASE.tiny_config
CONTROLS = CASE.planted

suite.install(globals(), CASE)


def _reference_counts(variables, tokens, config):
    """The reference's assignments a router output, as one compiled program."""
    return jax.jit(lambda v, t: REF.expert_counts(v, t, config))(
        variables, tokens)


def test_parameters_state_and_counts_by_kind_of_layer(tiny):
    """A dense first block, expert blocks with a shared expert, four norms a
    block, a gate on attention, a bias a layer in its own collection; the
    statistics hold the counts a bias's step reads, and they are the
    reference's."""
    _, variables, tokens = tiny
    stats = suite.logits(CASE)[1]
    assert set(stats) == {*DROPLESS_STATS, EXPERT_COUNTS}
    assert 0.3 < float(stats["moe_held_share"]) < 0.7
    p = variables["params"]
    assert set(p["block_0"]) == {
        "RMSNorm_0", "Dense_0", "Dense_1", "Dense_2", "Dense_3", "gate",
        "q_norm", "k_norm", "post_attn_norm", "RMSNorm_1", "mlp",
        "post_mlp_norm"}
    assert set(p["block_1"]) == (set(p["block_0"]) - {"mlp"}) | {
        "moe", "shared"}
    assert p["block_1"]["Dense_0"]["kernel"].shape == (24, 32)   # q: heads x head_dim
    assert p["block_1"]["gate"]["kernel"].shape == (24, 32)
    assert p["block_1"]["q_norm"]["scale"].shape == (8,)         # one head's features
    assert p["block_1"]["moe"]["router"]["kernel"].shape == (24, 8)
    assert p["block_1"]["moe"]["experts_gate"].shape == (4, 24, 16)
    assert p["block_1"]["shared"]["up"]["kernel"].shape == (24, 16)
    assert p["block_0"]["mlp"]["up"]["kernel"].shape == (24, 40)
    assert jax.tree.map(jnp.shape, variables[MOE_STATE]) == {
        f"block_{i}": {"moe": {"expert_bias": (8,)}} for i in range(1, 5)}
    # the reference's counts are the program's
    want_counts = _reference_counts(variables, tokens, TINY)
    for name, c in want_counts.items():
        np.testing.assert_array_equal(
            stats[EXPERT_COUNTS][name]["moe"]["expert_bias"], c)
        assert int(c.sum()) == 2 * S * 3


def test_the_step_moves_the_bias_by_the_references_step():
    """The common step case holds every parameter's move to the gradient of
    the reference's loss (the cross-entropy alone: the arch's load-balance
    coefficient is 0); here what no gradient moves: the bias goes by the
    reference's ``bias_step`` of the reference's counts, and the two figures
    the step reports of it are theirs."""
    _, variables, tokens = suite.tiny(CASE)
    _, new_state, m = suite.first_step(CASE, False)
    assert ROW.aux_coef == 0.0 == ROW.z_loss_coef
    config = CASE.step_config
    counts = _reference_counts(variables, tokens, config)
    biases = []
    for name, c in counts.items():
        want_b = REF.bias_step(variables[MOE_STATE][name]["moe"]["expert_bias"],
                               c, config)
        np.testing.assert_allclose(
            new_state.batch_stats[name]["moe"]["expert_bias"], want_b,
            atol=1e-7)
        biases.append(want_b)
    np.testing.assert_allclose(float(m["moe_bias_abs_max"]),
                               float(jnp.abs(jnp.stack(biases)).max()),
                               rtol=1e-6)
    np.testing.assert_allclose(
        float(m["moe_load_all_max_over_mean"]),
        max(float(c.max()) / (2 * S * 3 / 8) for c in counts.values()),
        rtol=1e-6)


BIAS_CASES = {
    # counts -> the centred step in units of the rate
    "one_over_one_under": ([5, 3, 4, 4], [-1, 1, 0, 0]),
    "one_overloaded": ([9, 1, 1, 1], [-1.5, 0.5, 0.5, 0.5]),   # signs -1 +1 +1 +1, mean 0.5
    "balanced": ([4, 4, 4, 4], [0, 0, 0, 0]),
    "two_idle": ([8, 8, 0, 0], [-1, -1, 1, 1]),
}


@pytest.mark.parametrize("name", sorted(BIAS_CASES))
def test_the_bias_step_is_the_centred_sign_of_the_load(name):
    counts, want = BIAS_CASES[name]
    b0 = jnp.asarray([0.1, -0.2, 0.0, 0.3])
    rate = 0.001
    got = update_expert_bias(b0, jnp.asarray(counts, jnp.int32), rate)
    # float32: 3e-8 is the rounding of a bias of 0.3
    np.testing.assert_allclose(got - b0, rate * np.asarray(want), atol=1e-7)
    np.testing.assert_allclose(
        got, REF.bias_step(b0, jnp.asarray(counts), dict(
            TINY, load_balance_coeff=rate)), atol=1e-7)
    np.testing.assert_allclose(float(jnp.mean(got)), float(jnp.mean(b0)),
                               atol=1e-7)


def test_momentum_and_weight_decay_leave_the_bias_alone():
    """Under the trainer's SGD with momentum and weight decay the bias moves
    by its own step and nothing else, two steps running; the optimizer's
    state holds no leaf for it."""
    built = suite.step(CASE, True)
    model = suite.tiny(CASE)[0].clone(experts_share=0)
    state = suite.tiny_state(CASE, True)
    tokens = suite.place(built.trainer, suite.tiny(CASE)[2])
    n_params = len(jax.tree.leaves(state.params))
    assert len(jax.tree.leaves(state.opt_state.momentum)) == n_params
    assert suite.WEIGHT_DECAY > 0 and suite.MOMENTUM > 0
    for _ in range(2):
        _, stats = model.apply(lm_variables(state.params, state.batch_stats),
                               tokens)
        want = jax.tree.map(
            lambda b, c: update_expert_bias(b, c, 0.001), state.batch_stats,
            stats[EXPERT_COUNTS])
        state, _ = built.step_fn(state, tokens)
        for a, b in zip(jax.tree.leaves(state.batch_stats),
                        jax.tree.leaves(want)):
            np.testing.assert_array_equal(a, b)


def test_the_checkpoint_holds_the_bias_and_the_oracle_reads_it():
    """The run the common case resumed (three steps, a checkpoint, a second
    trainer to step 6): the restored bias is not zero; its two counters are in
    every record and the registry; the checkpoint holds the bias and the
    dense layer's flags, and the standalone oracle reads that bias."""
    from ps_pytorch_tpu.data.text import TokenLoader
    from ps_pytorch_tpu.runtime import checkpoint as ckpt
    from ps_pytorch_tpu.runtime.lm_eval import (
        build_lm_oracle, build_lm_template,
    )
    _, resumed, _, restored, records = suite.trained(CASE)
    bias = jax.tree.leaves(restored.batch_stats)
    assert len(bias) == 4 and all(x.shape == (8,) for x in bias)
    assert max(float(np.abs(x).max()) for x in bias) >= 0.001
    for r in records:
        assert 0.001 <= r["moe_bias_abs_max"] <= 0.001 * 2 * r["step"]
        assert 1.0 <= r["moe_load_all_max_over_mean"] <= 8 / 3
    for name in BIAS_STATS:
        assert resumed.registry.get(name) == records[-1][name]

    train_dir = resumed.cfg.train_dir
    saved_cfg = TrainConfig.from_json(open(
        f"{ckpt.checkpoint_path(train_dir, 3)}/config.json").read())
    assert (saved_cfg.lm_dense_layers, saved_cfg.lm_dense_ffn_dim) == (1, 40)
    state, _, _ = ckpt.load_checkpoint(train_dir, 3,
                                       build_lm_template(saved_cfg))
    assert max(float(np.abs(x).max())
               for x in jax.tree.leaves(state.batch_stats)) > 0
    loss_fn, to_tree = build_lm_oracle(saved_cfg)
    tokens = jnp.asarray(TokenLoader(resumed.val_tokens, 2, S, seed=0,
                                     shuffle=False).next_batch())
    with_bias = float(loss_fn(to_tree(state.params), tokens,
                              state.batch_stats))
    assert np.isfinite(with_bias)
    shifted = jax.tree.map(lambda x: x + jnp.linspace(-1, 1, 8),
                           state.batch_stats)
    assert float(loss_fn(to_tree(state.params), tokens, shifted)) != with_bias
    with suite.one_device():
        r = resumed.evaluate(max_batches=1)
    assert np.isfinite(r["loss"])


CONFIG_CASES = {
    "dense_layers_under_gpt2": (dict(lm_arch="gpt2", lm_dense_layers=1,
                                     lm_moe_top_k=2, lm_experts_held=0),
                                "need a dropless lm_arch"),
    "more_dense_layers_than_layers": (dict(lm_dense_layers=6),
                                      r"must be in 0\.\.lm_layers=5"),
    "negative_dense_width": (dict(lm_dense_ffn_dim=-1), r"must be >= 0\)"),
    "not_under_ep": (dict(lm_parallelism="sp"), "is an MoE model"),
}


@pytest.mark.parametrize("name", sorted(CONFIG_CASES))
def test_config_validation(tmp_path, name):
    kw, message = CONFIG_CASES[name]
    with pytest.raises(ValueError, match=message):
        CASE.train_config(train_dir=str(tmp_path), **kw)


def test_the_flags_reach_the_model(tmp_path):
    from ps_pytorch_tpu.config import config_from_args
    from ps_pytorch_tpu.runtime.lm_eval import build_lm_model
    cfg = config_from_args(PUBLISHED["program_args"]
                           + ["--train-dir", str(tmp_path)])
    model = build_lm_model(cfg, attention_impl="flash", ep_axis="data")
    assert (model.arch, model.n_layers, model.dense_layers,
            model.dense_ffn_dim) == ("trinity", 5, 1, 6144)
    assert (model.n_experts, model.experts_held, model.top_k,
            model.ffn_dim) == (128, 16, 8, 1024)
    assert (model.n_heads, model.kv_heads, model.head_dim,
            model.vocab_size) == (32, 4, 128, 25024)
    row = ROW
    kinds = [(row.layer_window(i), row.layer_rope(i)) for i in range(5)]
    assert kinds == [(2048, True), (2048, True), (2048, True),
                     (None, False), (2048, True)]
    types = PUBLISHED["layer_types"]
    assert [t == "sliding_attention" for t in types] == \
        [row.layer_window(i) is not None for i in range(len(types))]


def test_param_count_published_as_run_and_tiny(tiny):
    _, variables, _ = tiny
    published = dict(PUBLISHED, **PUBLISHED["published"], experts_held=128)
    assert REF.param_count(published) == PUBLISHED["parameters_published"] \
        == 26_123_970_560
    assert REF.param_count(PUBLISHED) == PUBLISHED["parameters_as_run"] \
        == 705_473_792
    assert REF.param_count(TINY) == sum(
        a.size for a in jax.tree.leaves(variables["params"]))


@pytest.mark.parametrize("what", ["forward", "forward_and_backward"])
def test_closed_form_flops_against_the_jaxpr_walk(tiny, what):
    """The closed form charges attention by the pairs the masks admit, the
    routed experts at balance over the share held, the shared expert and the
    dense layer whole; the walk of the program finds the same projections,
    dense layer, shared expert, router and head, attention dense S x S
    (``full_attention`` multiplies what it then masks) and the experts on every
    sorted row the held part is sized for. With those two parts exchanged the
    forward agrees exactly; for training the closed form charges 3x the
    forward and the walk finds less by the gradient to the token ids."""
    from ps_pytorch_tpu.utils.flops import count_jaxpr_flops
    model, variables, tokens = tiny
    parts = REF.macs_per_token(TINY, S)
    assert REF.train_flops_per_sample(TINY, seq_len=S) \
        == 6 * sum(parts.values())
    assert parts["projections"] == 5 * (3 * 24 * 32 + 2 * 24 * 16)
    assert parts["attention"] == 2 * 32 * (
        REF.keys_per_query(S) + 4 * REF.keys_per_query(S, WINDOW))
    assert parts["dense"] == 3 * 24 * 40
    assert parts["shared"] == 4 * 3 * 24 * 16 and parts["router"] == 4 * 24 * 8
    assert parts["experts"] == 4 * 3 * (4 / 8) * 3 * 24 * 16
    # 1.5 x T*k*held/E rows in whole tiles of 512, capped at T*k = 192
    rows = 2 * S * 3
    walked_parts = dict(parts, attention=5 * 2 * 32 * S,
                        experts=4 * rows / (2 * S) * 3 * 24 * 16)
    per_token = 2 * sum(walked_parts.values())
    if what == "forward":
        walked = count_jaxpr_flops(jax.make_jaxpr(
            lambda v: suite.apply_logits(model, v, tokens))(
                variables).jaxpr)
        assert walked == per_token * tokens.size
    else:
        walked = count_jaxpr_flops(jax.make_jaxpr(jax.grad(
            lambda p: suite.apply_logits(model, {**variables, "params": p},
                              tokens).sum()))(variables["params"]).jaxpr)
        assert 0.9 * 3 * per_token * tokens.size < walked \
            <= 3 * per_token * tokens.size


def test_the_real_shapes_flops_are_the_honest_count():
    """369M multiply-adds a token at S=8192: 2.21 GFLOP forward and backward."""
    parts = REF.macs_per_token(PUBLISHED, 8192)
    assert REF.keys_per_query(8192, 2048) == 1792.125
    assert parts == {"projections": 136_314_880,
                     "attention": 2 * 4096 * (4 * 1792.125 + 4096.5),
                     "dense": 37_748_736, "shared": 25_165_824,
                     "router": 1_048_576, "experts": 25_165_824,
                     "head": 51_249_152}
    assert REF.train_flops_per_sample(PUBLISHED, seq_len=8192) \
        == 6 * sum(parts.values()) == 2_213_855_232
