"""The ``nemotronh`` arch (blocks that are ONE sublayer: Mamba-2 mixers by the
chunked state-space-dual kernel, attention mixers without position encoding,
expert layers of ungated relu^2 experts chosen under a bias beside a shared
expert) against its plain reference
``benchmark/reference/nemotron3_nano_30b_a3b.py`` at a tiny float32 size: the
common suite (``tests/arch_suite.py``) and what is Nemotron's alone: the kinds
of layer by the 52 published letters, the parameters by kind of layer at the
published widths, the gated group norm, the initialisers, the ungated experts'
transposed up projection, the counter sown, the bias's step, what its controls
cover and the ``KERNELS`` line. The kernel itself is ``tests/test_ssd.py``'s."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import arch_suite as suite
from ps_pytorch_tpu.models import transformer as tr_mod
from ps_pytorch_tpu.models.moe import (
    MOE_STATE, DroplessMoE, MoETransformerLM, update_expert_bias,
)
from ps_pytorch_tpu.models.transformer import (
    ACTS, ARCHS, LAYER_KINDS, LM_COUNTERS, PATTERN_KINDS, GatedFFN,
    lm_counters, refuse_hybrid,
)

S, VOCAB, D, DEPTH = 96, 97, 32, 6
ROW = ARCHS["nemotronh"]
TINY_ROW = dict(ssm_heads=4, ssm_head_dim=8, ssm_groups=2, ssm_state=16,
                ssm_chunk=32)


def _model(**kw):
    base = dict(vocab_size=VOCAB, n_layers=DEPTH, n_heads=4, kv_heads=2,
                head_dim=16, d_model=D, max_seq_len=S, arch="nemotronh",
                n_experts=16, top_k=3, ffn_dim=16, experts_held=4,
                experts_share=1)
    base.update(kw)
    return MoETransformerLM(**base)


def _shares(side):
    """One expert layer at the tiny size, all 16 experts' weights seeded and
    the bias off zero: the routed parts of the EIGHT shares (2 of 16 experts
    held, share 0..7) and the shared expert COUNTED ONCE, against the uncut
    reference layer's contribution."""
    # letter 1 of the pattern is an expert layer
    model = _model(n_layers=2, experts_held=0, experts_share=0)
    with CASE.patched():
        variables = suite.unsettled(
            dict(jax.jit(model.init)(jax.random.key(3),
                                     jnp.zeros((1, S), jnp.int32))),
            jax.random.key(4))
    bp = variables["params"]["block_1"]
    state = variables[MOE_STATE]["block_1"]["moe"]
    bias = state["expert_bias"]
    assert float(jnp.abs(bias).max()) > 0.05
    m = jax.random.normal(jax.random.key(5), (S, D))
    uncut = dict(TINY, n_routed_experts=16, experts_held=16, experts_share=0)
    f_uncut, _ = REF._expert_layer(bp, bias, m, uncut)
    shared = REF.shared_expert(bp, m)
    assert float(jnp.abs(shared).max()) > 0.01
    parts, held_total = [shared], 0.0
    for share in range(8):
        moe_s = {k: v[2 * share:2 * share + 2] if k.startswith("experts_")
                 else v for k, v in bp["moe"].items()}
        if side == "program":
            routed, stats = DroplessMoE(
                16, D, 16, top_k=3, act="relu2", gate_norm=True, n_held=2,
                share=share, score="sigmoid", select_bias=True,
                route_scale=2.5, gated=False).apply(
                    {"params": moe_s, MOE_STATE: state}, m[None])
            routed = routed[0]
            assert float(stats["moe_dropped"]) == 0.0
            held_total += float(stats["moe_held_share"])
        else:
            f_s, _ = REF._expert_layer(
                {**bp, "moe": moe_s}, bias, m,
                dict(uncut, n_routed_experts=2, experts_held=2,
                     experts_share=share))
            routed = f_s - shared   # each share's f holds the shared expert whole
        parts.append(routed)
    if side == "program":
        np.testing.assert_allclose(held_total, 1.0, rtol=1e-6)
    return parts, f_uncut


def _refused_by_ring(case, tmp_path):
    refuse_hybrid("nemotronh", "ring attention")


# The tiny preset keeps the published ratios: d=32; Mamba-2 layers of 4 heads
# of 8 with 16 states, B and C in 2 groups (two heads a group, and a slab),
# chunks of 32 (S=96 is three); attention of 4 query heads on 2 key/value
# heads of 16; 16 experts top-3 of width 16, experts 4..7 held (share 1 of 4),
# the shared expert twice as wide; depth 6, the published layers 0..5, MEMEM*:
# every kind (what the other 46 letters add is an index, which the
# kinds-by-letter case and the parameter case hold without a compile); vocab
# 97: in the reference's (the published config's) keys.
CASE = suite.ArchCase(
    arch="nemotronh", parallelism="ep", config="nemotron3_nano_30b_a3b",
    controls=True,
    tiny=dict(hidden_size=D, head_dim=16, num_attention_heads=4,
              num_key_value_heads=2, mamba_num_heads=4, mamba_head_dim=8,
              n_groups=2, ssm_state_size=16, chunk_size=32,
              moe_intermediate_size=16,
              moe_shared_expert_intermediate_size=32, n_routed_experts=4,
              n_routed_experts_published=16, experts_held=4, experts_share=1,
              num_experts_per_tok=3, num_hidden_layers=DEPTH,
              vocab_size=VOCAB),
    flags=dict(lm_d_model=D, lm_head_dim=16, lm_heads=4, lm_kv_heads=2,
               lm_ffn_dim=16, lm_experts=16, lm_experts_held=4,
               lm_moe_top_k=3, lm_layers=DEPTH, lm_vocab=VOCAB,
               lm_seq_len=S),
    row=TINY_ROW, share=1, logit_tol=5e-5,
    tol_reason="float32 both sides, only the order of reductions differs "
               "(the chunked form's sums against the recurrence's): measured "
               "2.4e-6 on logits up to 3.6; 5e-5 is a tenth of what the "
               "state rounded to bfloat16 at two chunk boundaries changes "
               "(5.2e-4), the smallest of the controls",
    counters={"ssd_state_abs_max": (0.01, 50)},
    scopes=suite.LM_SCOPES | suite.EXPERT_SCOPES
    | {"ssm_proj", "ssm_conv", "ssd_core", "moe_shared", "router_bias"},
    remat_scopes=frozenset({"moe_experts", "ssd_core"}), another_depth=9,
    refusals=suite.hybrid_refusals("nemotronh", "ep", (
        (suite.by_generate, "generate.py", "head-wise state"),
        (suite.by_serve, "serve.py", "head-wise state"),
        (suite.by_decode, "decode", "head-wise state"),
        (suite.by_tp, "tensor parallelism", "model axis"),
        (suite.by_pp, "pipeline parallelism",
         "a mixer or an expert layer alone"),
        (_refused_by_ring, "ring attention", "sequence shards"))),
    published_row=dict(
        layer_pattern="hybrid_override_pattern", ssm_heads="mamba_num_heads",
        ssm_head_dim="mamba_head_dim", ssm_groups="n_groups",
        ssm_state="ssm_state_size", ssm_conv="conv_kernel",
        ssm_chunk="chunk_size", norm_eps="layer_norm_epsilon",
        route_scale="routed_scaling_factor",
        router_bias_rate="router_bias_rate"),
    shares=_shares)
REF, PUBLISHED, TINY = CASE.reference, CASE.published, CASE.tiny_config
CONTROLS = CASE.planted

suite.install(globals(), CASE)


# ---- the layers ------------------------------------------------------------------

def test_layer_kinds_follow_the_52_published_letters():
    pattern = PUBLISHED["hybrid_override_pattern"]
    assert ROW.layer_pattern == pattern and len(pattern) == 52
    got = [ROW.layer_kind(i) for i in range(52)]
    assert got == [PATTERN_KINDS[c] for c in pattern]
    assert set(got) <= set(LAYER_KINDS)
    assert [got.count(k) for k in ("mamba2", "experts", "attention")] \
        == [23, 23, 6]
    assert got[:9] == ["mamba2", "experts", "mamba2", "experts", "mamba2",
                       "attention", "experts", "mamba2", "experts"]
    assert got == [REF.layer_kind(PUBLISHED, i) for i in range(52)]
    # the cell's nine layers hold the kinds 4 : 4 : 1
    assert PUBLISHED["num_hidden_layers"] == 9
    assert REF.layer_counts(PUBLISHED) == {"mamba2": 4, "attention": 1,
                                           "experts": 4}
    with pytest.raises(ValueError, match="52 layers"):
        ROW.layer_kind(52)
    # a period's kinds are what they were
    assert ARCHS["qwen3next"].layer_kind(7) == "attention"
    assert ARCHS["trinity"].layer_kind(60) == "attention"


def test_parameters_by_kind_of_layer_at_the_published_widths():
    """``jax.eval_shape`` of the cell's model: a layer's parameters by its
    kind, the whole model's, and the published model's by the reference's
    closed form."""
    c = PUBLISHED
    model = MoETransformerLM(
        vocab_size=c["vocab_size"], n_layers=c["num_hidden_layers"],
        n_heads=c["num_attention_heads"], kv_heads=c["num_key_value_heads"],
        head_dim=c["head_dim"], d_model=c["hidden_size"], arch="nemotronh",
        n_experts=c["n_routed_experts_published"],
        top_k=c["num_experts_per_tok"], ffn_dim=c["moe_intermediate_size"],
        experts_held=c["experts_held"])
    tr_mod.ARCHS["nemotronh"] = ROW         # the published sizes
    shapes = jax.eval_shape(model.init, jax.random.key(0),
                            jnp.zeros((1, 128), jnp.int32))
    count = lambda t: sum(int(np.prod(a.shape)) for a in jax.tree.leaves(t))
    p = shapes["params"]
    by_kind = {"mamba2": 38_744_896, "attention": 23_399_040,
               "experts": 179_948_160}
    assert REF.params_by_kind(c) == by_kind
    for i in range(9):
        assert count(p[f"block_{i}"]) == by_kind[ROW.layer_kind(i)], i
    assert count(p) == REF.param_count(c) == c["parameters_as_run"] \
        == 986_254_336
    assert p["block_0"]["in_proj"]["kernel"].shape == (2688, 10_304)
    assert p["block_0"]["conv_weight"].shape == (4, 6144)
    assert p["block_5"]["Dense_0"]["kernel"].shape == (2688, 32 * 128)
    assert p["block_5"]["Dense_1"]["kernel"].shape == (2688, 2 * 128)
    assert p["block_1"]["moe"]["experts_up"].shape == (16, 1856, 2688)
    assert p["block_1"]["moe"]["experts_down"].shape == (16, 1856, 2688)
    assert p["block_1"]["shared"]["up"]["kernel"].shape == (2688, 3712)
    assert shapes[MOE_STATE]["block_1"]["moe"]["expert_bias"].shape == (128,)
    published = dict(c, **c["published"])
    for key in ("experts_held", "n_routed_experts_published"):
        published.pop(key)
    assert REF.param_count(published) == c["parameters_published"] \
        == 31_577_937_344
    assert REF.params_by_kind(published)["experts"] == 1_297_468_032


def test_a_block_is_one_sublayer(tiny):
    _, variables, _ = tiny
    p = variables["params"]
    assert set(p["block_0"]) == {
        "RMSNorm_0", "in_proj", "conv_weight", "conv_bias", "dt_bias",
        "A_log", "D", "ssm_norm", "out_proj"}
    assert set(p["block_1"]) == {"RMSNorm_0", "moe", "shared"}
    assert set(p["block_5"]) == {"RMSNorm_0", "Dense_0", "Dense_1",
                                 "Dense_2", "Dense_3"}
    assert set(p["block_1"]["moe"]) == {"router", "experts_up",
                                        "experts_down"}       # no gate
    assert set(p["block_1"]["shared"]) == {"up", "down"}
    assert "pos_embed" not in p                               # no positions
    assert p["block_0"]["in_proj"]["kernel"].shape \
        == (D, 32 + (32 + 2 * 32) + 4)
    assert p["block_0"]["conv_weight"].shape == (4, 32 + 2 * 32)
    assert p["block_0"]["conv_bias"].shape == (32 + 2 * 32,)
    assert p["block_0"]["A_log"].shape == p["block_0"]["dt_bias"].shape \
        == p["block_0"]["D"].shape == (4,)
    assert p["block_0"]["ssm_norm"]["scale"].shape == (32,)
    assert p["block_1"]["moe"]["experts_up"].shape == (4, 16, D)
    assert p["block_1"]["shared"]["up"]["kernel"].shape == (D, 32)
    assert set(variables[MOE_STATE]) == {"block_1", "block_3"}
    n = sum(a.size for a in jax.tree.leaves(p))
    assert n == REF.param_count(TINY)


def test_the_gate_comes_before_the_group_norm():
    """``y silu(z)`` first, then RMSNorm over each group's features, one
    scale: against the reference, and far from Qwen3-Next's order."""
    y = jax.random.normal(jax.random.key(0), (S, 32))
    z = jax.random.normal(jax.random.key(1), (S, 32))
    scale = 1 + 0.2 * jax.random.normal(jax.random.key(2), (32,))
    want = REF.gated_norm(y, z, scale, 1e-5, 2)
    g = (y * jax.nn.silu(z)).reshape(S, 2, 16)
    plain = (g / jnp.sqrt(jnp.mean(g * g, -1, keepdims=True) + 1e-5)
             ).reshape(S, 32) * scale
    np.testing.assert_allclose(want, plain, rtol=1e-5, atol=1e-6)
    other = CONTROLS._norm_then_gate(y, z, scale, 1e-5, 2)
    assert float(jnp.abs(want - other).max()) > 0.5


def test_the_mamba2_layers_initialisers():
    with pytest.raises(ValueError, match="holds no expert layer"):
        jax.eval_shape(_model(n_layers=1).init, jax.random.key(4),
                       jnp.zeros((1, 8), jnp.int32))
    model = _model(n_layers=2)
    tr_mod.ARCHS["nemotronh"] = ROW         # the published 64 heads
    b0 = jax.jit(model.init)(jax.random.key(4), jnp.zeros((1, 8), jnp.int32))[
        "params"]["block_0"]
    a = np.exp(np.asarray(b0["A_log"]))
    assert a.shape == (64,) and 1 <= a.min() and a.max() <= 16 and a.std() > 2
    step = np.log1p(np.exp(np.asarray(b0["dt_bias"])))      # softplus
    assert PUBLISHED["time_step_min"] <= step.min() \
        and step.max() <= PUBLISHED["time_step_max"] * 1.001
    assert float(jnp.abs(b0["conv_weight"]).max()) <= 0.5
    assert 0 < float(jnp.abs(b0["conv_bias"]).max()) <= 0.5
    assert float(jnp.abs(b0["D"] - 1).max()) == 0
    assert float(jnp.abs(b0["ssm_norm"]["scale"] - 1).max()) == 0


def test_ungated_experts_are_two_matmuls_on_a_transposed_up_projection():
    """``down(relu(up x)^2)``: no ``experts_gate``; ``experts_up`` is [held,
    f, d] and its initialiser's fan-in is d (the last axis), as ``experts_
    down``'s stays f; the shared expert has no gate either; a gated arch's
    layer is what it was."""
    x = jax.random.normal(jax.random.key(0), (1, 64, D))
    moe = DroplessMoE(4, D, 48, top_k=2, act="relu2", gated=False)
    params = moe.init(jax.random.key(1), x)["params"]
    assert set(params) == {"router", "experts_up", "experts_down"}
    assert params["experts_up"].shape == (4, 48, D)
    assert float(jnp.std(params["experts_up"])) \
        == pytest.approx(D ** -0.5, rel=0.1)
    y, stats = moe.apply({"params": params}, x)
    gates = jax.nn.softmax(x[0] @ params["router"]["kernel"])
    kth = jax.lax.top_k(gates, 2)[0][:, -1:]
    w = jnp.where(gates >= kth, gates, 0.0)
    want = sum(w[:, e:e + 1] * (jnp.square(jax.nn.relu(
        x[0] @ params["experts_up"][e].T)) @ params["experts_down"][e])
        for e in range(4))
    np.testing.assert_allclose(y[0], want, atol=2e-5)
    assert float(stats["moe_dropped"]) == 0.0
    ffn = GatedFFN(48, act="relu2", gated=False)
    fp = ffn.init(jax.random.key(2), x)["params"]
    assert set(fp) == {"up", "down"}
    np.testing.assert_allclose(
        ffn.apply({"params": fp}, x),
        jnp.square(jax.nn.relu(x @ fp["up"]["kernel"])) @ fp["down"]["kernel"],
        atol=1e-5)
    np.testing.assert_array_equal(ACTS["relu2"](jnp.array([-2.0, 3.0])),
                                  jnp.array([0.0, 9.0]))
    gated = DroplessMoE(4, D, 48, top_k=2).init(jax.random.key(1), x)["params"]
    assert set(gated) == {"router", "experts_gate", "experts_up",
                          "experts_down"}
    assert gated["experts_up"].shape == (4, D, 48)


# ---- the step ---------------------------------------------------------------------

def test_the_bias_moves_by_the_reference_step_and_no_gradient_reaches_it():
    """After the optimizer's update every expert layer's bias moves by 0.001
    times the sign of (mean count - count), centred, from the step's
    assignments to all 16 outputs (the reference's counts on the same tokens);
    momentum and weight decay leave it alone."""
    _, variables, tokens = suite.tiny(CASE)
    state, new, m = suite.first_step(CASE, False)
    counts = jax.jit(lambda v: REF.expert_counts(v, tokens, CASE.step_config))(
        variables)
    assert set(counts) == {"block_1", "block_3"}
    for name, c in counts.items():
        assert int(jnp.sum(c)) == tokens.size * 3
        before = state.batch_stats[name]["moe"]["expert_bias"]
        after = new.batch_stats[name]["moe"]["expert_bias"]
        np.testing.assert_allclose(
            after, REF.bias_step(before, c, TINY), atol=1e-7)
        np.testing.assert_allclose(
            after, update_expert_bias(before, c, ROW.router_bias_rate),
            atol=1e-7)
    assert TINY["router_bias_rate"] == ROW.router_bias_rate == 0.001
    assert ROW.aux_coef == 0.0 and ROW.z_loss_coef == 0.0


def test_counters_are_sown_and_other_archs_return_none(tiny):
    model, variables, tokens = tiny
    (logits, _), sown = jax.jit(lambda v, t: model.apply(
        v, t, mutable=[LM_COUNTERS]))(variables, tokens)
    counters = lm_counters(sown)
    assert set(counters) == {"ssd_state_abs_max"} == set(CASE.counters)
    assert float(counters["ssd_state_abs_max"]) > 0
    # sowing changes no logit (two compiled programs: equal to rounding)
    np.testing.assert_allclose(logits, suite.logits(CASE)[0], atol=1e-6)
    olmoe = MoETransformerLM(vocab_size=VOCAB, n_layers=1, n_heads=2,
                             d_model=16, n_experts=4, top_k=2, arch="olmoe",
                             ffn_dim=8)
    v = {"params": olmoe.init(jax.random.key(0), tokens)["params"]}
    _, sown = olmoe.apply(v, tokens, mutable=[LM_COUNTERS])
    assert lm_counters(sown) == {}


def test_the_kernels_line_and_bfloat16_reach_the_layers(tmp_path):
    """``LMTrainer``'s ``KERNELS`` line prints the dual form's schedule beside
    the flash record and the grouped matmul; and ``--compute-dtype bfloat16``
    reaches the Mamba-2 layers: their output leaves in it while the
    parameters stay float32 (shapes only: nothing is compiled)."""
    from ps_pytorch_tpu.runtime.lm_eval import build_lm_model
    kernels = suite.step(CASE, True).kernels
    assert kernels.count("flash_attention[") == 1     # one kind of attention layer
    assert " ssd[chunk=32 chunks=3 group=3 grid=4x1 heads=2 slab=2 kept=" \
        in kernels
    # the mixer's elementwise chains: x of 32 and B, C of 32 in tiles of 32
    # lanes, both groups of 16 a step of the norm
    assert " ssm_mix[lanes=32 rows=96 chunk=96 halo=16 conv_grid=2x3x1 " \
        "norm_lanes=32 norm_rows=96 norm_chunk=96 norm_grid=2x1x1 " \
        "conv_fwd_bytes=" in kernels
    assert "gated_delta_rule" not in kernels and "selective_scan" not in kernels
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
    variables = jax.eval_shape(narrow.init, jax.random.key(0), tokens)
    assert all(a.dtype == jnp.float32
               for a in jax.tree.leaves(variables["params"]))
    _, state = jax.eval_shape(
        lambda v: narrow.apply(v, tokens, capture_intermediates=True,
                               mutable=["intermediates"]), variables)
    block = state["intermediates"]["block_0"]
    assert block["out_proj"]["__call__"][0].dtype == jnp.bfloat16
    assert block["in_proj"]["__call__"][0].dtype == jnp.bfloat16
    assert block["ssm_norm"]["__call__"][0].dtype == jnp.float32


def _equations(jaxpr, under=""):
    """Every equation of a jaxpr and of the jaxprs its equations hold (but a
    kernel's body), each with the name stack it lies under."""
    for eqn in jaxpr.eqns:
        stack = f"{under}/{eqn.source_info.name_stack}"
        yield eqn, stack
        if eqn.primitive.name != "pallas_call":
            for value in eqn.params.values():
                for inner in value if isinstance(value, (tuple, list)) \
                        else (value,):
                    inner = getattr(inner, "jaxpr", inner)
                    if hasattr(inner, "eqns"):
                        yield from _equations(inner, stack)


def test_the_mixers_chains_are_kernels_in_the_traced_step(tmp_path):
    """The bfloat16 step as ``LMTrainer`` jits it, traced (nothing compiled):
    its jaxpr holds the Mamba-2 mixer's Pallas calls by name, both ways, and
    under ``ssm_conv`` no float32 array a sequence long and ``xBC``, x or z
    wide: the convolution's products, the SiLU, the gate and the norm's
    statistics live inside the calls. (The softplus on ``dt`` stays XLA's:
    float32, a head wide.)"""
    built = suite.step(CASE, True)
    trainer, _ = suite._new_trainer(CASE, built.cfg.replace(
        compute_dtype="bfloat16", train_dir=str(tmp_path), metrics_file=""))
    tokens = suite.place(trainer, np.zeros(
        (built.cfg.batch_size, built.cfg.lm_seq_len), np.int32))
    with CASE.patched(), suite.one_device():
        jaxpr = jax.make_jaxpr(trainer.step_fn)(trainer.state, tokens)
    calls, wide = set(), []
    d_inner = TINY_ROW["ssm_heads"] * TINY_ROW["ssm_head_dim"]
    widths = {d_inner, d_inner + 2 * TINY_ROW["ssm_groups"]
              * TINY_ROW["ssm_state"]}
    for eqn, stack in _equations(jaxpr.jaxpr):
        if eqn.primitive.name == "pallas_call":
            calls.add(eqn.params["name"])
        elif "ssm_conv" in stack:
            wide += [(eqn.primitive.name, v.aval.shape) for v in eqn.outvars
                     if v.aval.dtype == jnp.float32 and v.aval.ndim >= 2
                     and v.aval.shape[-2] == S and v.aval.shape[-1] in widths]
    assert {f"ssm_conv_{way}_{seg}" for way in ("fwd", "bwd")
            for seg in "xbc"} | {"ssm_norm_fwd", "ssm_norm_bwd", "ssd_fwd",
                                 "ssd_bwd"} <= calls
    assert wide == []


# ---- planted mistakes ------------------------------------------------------------------

def test_the_controls_cover_what_the_issue_names():
    assert set(CONTROLS.CONTROLS) == {
        "norm_before_the_gate", "group_tiled_not_repeated", "skip_left_out",
        "decay_left_out", "step_not_multiplied_into_x", "conv_not_causal",
        "conv_bias_left_out", "relu_not_squared", "gates_not_renormalised",
        "route_scale_left_out", "shared_expert_left_out",
        *CONTROLS.PRECISION_CONTROLS}
    assert CONTROLS.CELL == "nemotron3nano_s16384_1chip"


# ---- the row ---------------------------------------------------------------------

def test_the_row_holds_the_published_switches():
    """The sizes and rates are held to the published config by the common
    config case; here the switches."""
    c = PUBLISHED
    assert ROW.rms_norm and ROW.no_positions and not ROW.rope_theta \
        and ROW.gate_norm == c["norm_topk_prob"] \
        and ROW.router_score == "sigmoid" and not ROW.expert_gated \
        and ROW.expert_act == c["mlp_hidden_act"] == "relu2"
    assert ROW.shared_experts * c["moe_intermediate_size"] \
        == c["moe_shared_expert_intermediate_size"]
    assert ROW.expert_down_std == pytest.approx(
        0.02 / (2 * c["published"]["num_hidden_layers"]) ** 0.5, rel=0.01)
    assert ROW.ssm_heads * ROW.ssm_head_dim == 4096 \
        != c["expand"] * c["hidden_size"]
    refuse_hybrid("nemotronh", "expert parallelism")    # its own path is not refused


def test_the_state_tables_are_one_and_other_archs_pass():
    """One table of what a recurrent state lacks, keyed by the kind of state;
    an arch without one is refused nowhere."""
    assert set(tr_mod._STATE_LACKS) == {"hybrid", "gdn", "mamba2",
                                        "mamba2_mixers", "eva"}
    assert [tr_mod._state_kind(ARCHS[a]) for a in
            ("phi4flash", "qwen3next", "nemotronh", "granite4h", "evabyte",
             "olmoe", "gpt2")] == ["hybrid", "gdn", "mamba2", "mamba2_mixers",
                                   "eva", None, None]
    for where in ("generate.py", "serve.py", "decode", "tensor parallelism",
                  "pipeline parallelism", "ring attention",
                  "expert parallelism"):
        refuse_hybrid("trinity", where)
        refuse_hybrid("gpt2", where)
    with pytest.raises(ValueError, match="lm_parallelism sp on one device"):
        refuse_hybrid("phi4flash", "expert parallelism")
