"""The ``granite4h`` arch (blocks that are a mixer AND an expert half: Mamba-2
mixers whose B, C and gated norm are ONE group, attention without positions
at the scores' own multiplier, SwiGLU experts under a softmax over the chosen
logits beside one shared expert, a tied head, four scalar multipliers) and ONE
chip's share of every layer (``mixer_shares``), against its plain reference
``benchmark/reference/granite_4_0_h_small.py`` at a tiny float32 size: the
common suite (``tests/arch_suite.py``) and what is Granite's alone: the kinds
of layer by the 40 published ``layer_types``, the parameters at the published
widths as run and uncut, the tie of the shares under a bound mesh axis to the
uncut layer, what one chip computes with no axis bound, the share flag at 1
leaving every other arch as it was, the counters and the ``KERNELS`` line."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax import linen as nn
from jax.sharding import Mesh, PartitionSpec as P

import arch_suite as suite
from ps_pytorch_tpu.config import TrainConfig
from ps_pytorch_tpu.models import transformer as tr_mod
from ps_pytorch_tpu.models.moe import (
    LOAD_ALL_STAT, DroplessMoE, MoEBlock, MoETransformerLM,
)
from ps_pytorch_tpu.models.transformer import ARCHS, refuse_hybrid

S, VOCAB, D, DEPTH = 96, 97, 32, 4
ROW = ARCHS["granite4h"]
# The tiny row keeps the published ratios at a period of four (M M * M: three
# Mamba-2 layers to one attention layer; the published ten are the kinds case's):
# 32 Mamba-2 heads of 8 with 16 states in ONE group, chunks of 32 (S=96 is
# three), a shared expert 32 wide; the embedding at the row's own std 0.05.
TINY_ROW = dict(ssm_heads=32, ssm_head_dim=8, ssm_state=16, ssm_chunk=32,
                shared_width=32,
                mixer_layers=("mamba2", "mamba2", "attention", "mamba2"))
TINY_TYPES = ["mamba", "mamba", "attention", "mamba"]


def _refused_by_ring(case, tmp_path):
    refuse_hybrid("granite4h", "ring attention")


def _a_loud_final_norm(params):
    """At d=32 the tied head's logits over 16 stay under 1: the final norm's
    scale times 24 brings them to the size the common suite asks for (above 2)
    and changes nothing before the head."""
    return {**params, "ln_f": {"scale": 24.0 * params["ln_f"]["scale"]}}


def _expert_shares(side):
    """One expert half at the tiny size, all 16 experts' weights seeded: the
    routed parts of the EIGHT shares (2 of 16 experts held, share 0..7) and
    the shared expert COUNTED ONCE, against the uncut reference's expert
    half."""
    model = MoETransformerLM(
        vocab_size=VOCAB, n_layers=1, n_heads=4, kv_heads=2, d_model=D,
        max_seq_len=S, arch="granite4h", n_experts=16, top_k=3, ffn_dim=16)
    with CASE.patched():
        variables = suite.unsettled(
            dict(jax.jit(model.init)(jax.random.key(3),
                                     jnp.zeros((1, S), jnp.int32))),
            jax.random.key(4))
    bp = variables["params"]["block_0"]
    m = jax.random.normal(jax.random.key(5), (S, D))
    uncut = dict(UNCUT, num_local_experts=16, experts_held=16,
                 experts_share=0)
    f_uncut, _ = REF.expert_half(bp, m, uncut)
    shared = REF.shared_expert(bp, m)
    assert float(jnp.abs(shared).max()) > 0.01
    parts, held_total = [shared], 0.0
    for share in range(8):
        moe_s = {k: v[2 * share:2 * share + 2] if k.startswith("experts_")
                 else v for k, v in bp["moe"].items()}
        if side == "program":
            routed, stats = DroplessMoE(
                16, D, 16, top_k=3, gate_norm=True, n_held=2, share=share,
                down_std=ROW.expert_down_std, load_all_stat=True).apply(
                    {"params": moe_s}, m[None])
            routed = routed[0]
            assert float(stats["moe_dropped"]) == 0.0
            assert float(stats[LOAD_ALL_STAT]) >= 1.0
            held_total += float(stats["moe_held_share"])
        else:
            f_s, _ = REF.expert_half(
                {**bp, "moe": moe_s}, m,
                dict(uncut, num_local_experts=2, experts_held=2,
                     experts_share=share))
            routed = f_s - shared   # each share's f holds the shared expert whole
        parts.append(routed)
    if side == "program":
        np.testing.assert_allclose(held_total, 1.0, rtol=1e-6)
    return parts, f_uncut


# d=32; ONE of TWO chips' share of every layer: 16 of 32 Mamba-2 heads, 2 of 4
# query heads on 1 of 2 key/value heads of 8, 16 of the shared expert's 32
# channels, experts 4..7 of 16 (share 1 of 4) top-3 of width 16; depth 4, vocab
# 97: in the reference's (the published config's) keys, whose head counts are
# the counts HELD.
CASE = suite.ArchCase(
    arch="granite4h", parallelism="ep", config="granite_4_0_h_small",
    controls=True,
    tiny=dict(hidden_size=D, intermediate_size=16,
              shared_intermediate_size=32, num_attention_heads=2,
              num_key_value_heads=1, mamba_n_heads=16, mamba_d_head=8,
              mamba_d_state=16, mamba_chunk_size=32, mixer_share=[0, 2],
              num_local_experts=4, num_local_experts_published=16,
              experts_held=4, experts_share=1, num_experts_per_tok=3,
              num_hidden_layers=DEPTH, layer_types=TINY_TYPES,
              vocab_size=VOCAB),
    flags=dict(lm_d_model=D, lm_heads=4, lm_kv_heads=2, lm_ffn_dim=16,
               lm_experts=16, lm_experts_held=4, lm_mixer_shares=2,
               lm_moe_top_k=3, lm_layers=DEPTH, lm_vocab=VOCAB,
               lm_seq_len=S),
    row=TINY_ROW, share=1, unsettle=_a_loud_final_norm, logit_tol=1e-5,
    tol_reason="float32 both sides, only the order of reductions differs "
               "(the chunked form's sums against the recurrence's, the "
               "grouped matmuls against a loop over experts): measured "
               "5.8e-7 on logits up to 2.7; 1e-5 is a twelfth of what the "
               "state rounded to bfloat16 at two chunk boundaries changes "
               "(1.2e-4), the smallest of the controls",
    counters={"ssd_state_abs_max": (0.01, 50)},
    scopes=suite.LM_SCOPES | suite.EXPERT_SCOPES
    | {"ssm_proj", "ssm_conv", "ssd_core", "moe_shared"},
    remat_scopes=frozenset({"moe_experts", "ssd_core"}), another_depth=8,
    refusals=suite.hybrid_refusals("granite4h", "ep", (
        (suite.by_generate, "generate.py", "head-wise state"),
        (suite.by_serve, "serve.py", "head-wise state"),
        (suite.by_decode, "decode", "head-wise state"),
        (suite.by_tp, "tensor parallelism", "mixer_axis"),
        (suite.by_pp, "pipeline parallelism", "tied to the first"),
        (_refused_by_ring, "ring attention", "sequence shards"))),
    published_row=dict(
        ssm_head_dim="mamba_d_head", ssm_groups="mamba_n_groups",
        ssm_state="mamba_d_state", ssm_conv="mamba_d_conv",
        ssm_chunk="mamba_chunk_size", norm_eps="rms_norm_eps",
        embed_multiplier="embedding_multiplier",
        attn_scale="attention_multiplier",
        residual_scale="residual_multiplier",
        logits_divisor="logits_scaling",
        shared_width="shared_intermediate_size",
        aux_coef="router_aux_loss_coef", tied_head="tie_word_embeddings"),
    shares=_expert_shares)
REF, PUBLISHED, TINY = CASE.reference, CASE.published, CASE.tiny_config
CONTROLS = CASE.planted
# the tiny model UNCUT: the head counts the model's, share (0, 1)
UNCUT = dict(TINY, mamba_n_heads=32, num_attention_heads=4,
             num_key_value_heads=2, mixer_share=[0, 1])

suite.install(globals(), CASE)


# ---- the layers ------------------------------------------------------------------

def test_layer_kinds_follow_the_40_published_layer_types():
    types = PUBLISHED["layer_types"]
    assert len(types) == 40 and types.count("attention") == 4
    got = [ROW.layer_kind(i) for i in range(40)]
    assert got == [{"mamba": "mamba2", "attention": "attention"}[t]
                   for t in types]
    assert [i for i, k in enumerate(got) if k == "attention"] \
        == [5, 15, 25, 35]
    assert [REF.is_mamba(PUBLISHED, i) for i in range(40)] \
        == [k == "mamba2" for k in got]
    # the cell's ten layers are one whole period, 9 : 1
    assert PUBLISHED["num_hidden_layers"] == 10 == len(ROW.mixer_layers)
    assert REF.layer_counts(PUBLISHED) == {"mamba": 9, "attention": 1}
    assert tr_mod._state_kind(ROW) == "mamba2_mixers"
    assert tr_mod._state_kind(ARCHS["nemotronh"]) == "mamba2"


def test_parameters_at_the_published_widths_as_run_and_uncut():
    """``jax.eval_shape`` of the cell's model: ONE chip's share of a layer by
    its kind, the whole model as run, and the published model's count by the
    reference's closed form."""
    c = PUBLISHED
    flags = dict(zip(c["program_args"][::2], c["program_args"][1::2]))
    model = MoETransformerLM(
        vocab_size=c["vocab_size"], n_layers=c["num_hidden_layers"],
        n_heads=int(flags["--lm-heads"]), kv_heads=int(flags["--lm-kv-heads"]),
        d_model=c["hidden_size"], arch="granite4h",
        n_experts=c["num_local_experts_published"],
        top_k=c["num_experts_per_tok"], ffn_dim=c["intermediate_size"],
        experts_held=c["experts_held"],
        mixer_shares=int(flags["--lm-mixer-shares"]))
    assert (model.n_heads, model.kv_heads, model.mixer_shares) == (32, 8, 8)
    assert c["mixer_share"] == [0, 8] and c["shared_channels_held"] == 192
    tr_mod.ARCHS["granite4h"] = ROW         # the published sizes
    shapes = jax.eval_shape(model.init, jax.random.key(0),
                            jnp.zeros((1, 128), jnp.int32))
    count = lambda t: sum(int(np.prod(a.shape)) for a in jax.tree.leaves(t))
    p = shapes["params"]
    assert set(shapes) == {"params", "lm_counters"}     # no state no gradient moves
    assert "lm_head" not in p and "pos_embed" not in p      # tied, no positions
    by_kind = REF.params_by_kind(c)
    kinds = c["parameters_by_kind"]
    assert by_kind["expert_half"] == kinds["expert_half_as_run"] == 87_592_960
    assert by_kind["mamba"] - by_kind["expert_half"] \
        == kinds["mamba2_mixer_as_run"] == 13_708_592
    assert by_kind["attention"] - by_kind["expert_half"] \
        == kinds["attention_mixer_as_run"] == 5_246_976
    for i in range(10):
        kind = "mamba" if REF.is_mamba(c, i) else "attention"
        assert count(p[f"block_{i}"]) == by_kind[kind], i
    assert count(p) == REF.param_count(c) == c["parameters_as_run"] \
        == 1_055_938_224
    b0, b5 = p["block_0"], p["block_5"]
    assert b0["in_proj"]["kernel"].shape == (4096, 1024 + 1024 + 256 + 16)
    assert b0["conv_weight"].shape == (4, 1280)
    assert b0["out_proj"]["kernel"].shape == (1024, 4096)
    assert b0["ssm_norm"]["scale"].shape == (1024,)
    assert b0["A_log"].shape == (16,)
    assert b5["Dense_0"]["kernel"].shape == (4096, 4 * 128)
    assert b5["Dense_1"]["kernel"].shape == (4096, 128)
    assert b5["Dense_3"]["kernel"].shape == (4 * 128, 4096)
    assert b0["moe"]["experts_gate"].shape == (9, 4096, 768)
    assert b0["moe"]["router"]["kernel"].shape == (4096, 72)
    assert b0["shared"]["up"]["kernel"].shape == (4096, 192)
    assert p["tok_embed"]["embedding"].shape == (12_544, 4096)
    # every reduced key with its published value beside it; no width among them
    assert set(c["reduced"]) == set(c["published"]) == {
        "num_hidden_layers", "num_local_experts", "vocab_size",
        "mamba_n_heads", "num_attention_heads", "num_key_value_heads"}
    for key in ("mamba_n_heads", "num_attention_heads",
                "num_key_value_heads"):
        assert c[key] * 8 == c["published"][key]
    uncut = dict(c, **c["published"], mixer_share=[0, 1], experts_held=72)
    uncut.pop("num_local_experts_published")
    assert REF.param_count(uncut) == c["parameters_published"] \
        == 32_207_337_984


def test_a_block_is_a_mixer_and_an_expert_half(tiny):
    _, variables, _ = tiny
    p = variables["params"]
    half = {"RMSNorm_0", "RMSNorm_1", "moe", "shared"}
    assert set(p["block_0"]) == half | {
        "in_proj", "conv_weight", "conv_bias", "dt_bias", "A_log", "D",
        "ssm_norm", "out_proj"}
    assert set(p["block_2"]) == half | {"Dense_0", "Dense_1", "Dense_2",
                                        "Dense_3"}
    assert set(p["block_0"]["moe"]) == {"router", "experts_gate",
                                        "experts_up", "experts_down"}
    assert set(p) == {"tok_embed", "ln_f", "block_0", "block_1", "block_2",
                      "block_3"}
    # ONE of two chips' share: 16 of 32 heads of 8, B and C (16 each) whole
    assert p["block_0"]["in_proj"]["kernel"].shape \
        == (D, 128 + (128 + 2 * 16) + 16)
    assert p["block_0"]["conv_weight"].shape == (4, 128 + 2 * 16)
    assert p["block_0"]["ssm_norm"]["scale"].shape == (128,)
    assert p["block_0"]["out_proj"]["kernel"].shape == (128, D)
    assert p["block_2"]["Dense_0"]["kernel"].shape == (D, 2 * 8)
    assert p["block_2"]["Dense_1"]["kernel"].shape == (D, 8)
    assert p["block_0"]["shared"]["up"]["kernel"].shape == (D, 16)
    assert sum(a.size for a in jax.tree.leaves(p)) == REF.param_count(TINY)


# ---- the shares' tie -----------------------------------------------------------

# the tie's uncut block: 8 query heads on 8 key/value heads of 4, so that
# two, four or eight chips can each hold whole heads
TIE_HEADS = 8


def _uncut_block(layer):
    """One uncut tiny block of the kind of ``layer`` (0: Mamba-2, 2:
    attention), every vector leaf off its initial value, and a stream."""
    block = MoEBlock(TIE_HEADS, D, 16, top_k=3, arch="granite4h", ffn_dim=16,
                     layer=layer, kv_heads=TIE_HEADS, experts_held=4)
    x = jax.random.normal(jax.random.key(7), (2, S, D))
    with CASE.patched():
        params = suite.unsettled(
            jax.jit(block.init)(jax.random.key(8), x)["params"],
            jax.random.key(9))
    return block, params, x


def _under_the_axis(block, params, x, of, layer):
    """The block's mixer half and shared expert over ``of`` CPU devices, each
    with its own share's parameters, the mesh axis bound: what every device
    returns (the shares' parts summed inside), from device 0."""
    config = dict(UNCUT, num_attention_heads=TIE_HEADS,
                  num_key_value_heads=TIE_HEADS)
    shares = [REF.share_of({f"block_{layer}": params}, config, i, of)
              [f"block_{layer}"] for i in range(of)]
    stacked = jax.tree.map(lambda *a: jnp.stack(a), *shares)
    mesh = Mesh(np.array(jax.devices()[:of]), ("model",))
    held = block.clone(mixer_shares=of, mixer_axis="model")

    def local(p, x):
        with CASE.patched():
            return held.apply({"params": jax.tree.map(lambda a: a[0], p)},
                              x)[0]

    return jax.jit(jax.shard_map(
        local, mesh=mesh, in_specs=(P("model"), P()), out_specs=P(),
        check_vma=False))(stacked, x)


@pytest.mark.parametrize("of", [2, 4, 8])
@pytest.mark.parametrize("layer", [0, 2], ids=["mamba2", "attention"])
def test_the_mixer_shares_under_a_bound_axis_add_up_to_the_uncut_layer(
        layer, of):
    """``shard_map`` over 2, 4 or 8 CPU devices, each holding its share of
    the mixer's heads and of the shared expert's channels (the SAME four held
    experts on each: their exchange is not built, so the routed part is what
    one device gives): the partial outputs, and a Mamba-2 layer's sum of
    squares, are summed over the axis inside the block, and the result is the
    UNCUT reference's layer. float32: 2e-5 is reduction order."""
    block, params, x = _uncut_block(layer)
    got = _under_the_axis(block, params, x, of, layer)
    config = dict(UNCUT, num_local_experts=4, experts_held=4, experts_share=0,
                  num_attention_heads=TIE_HEADS,
                  num_key_value_heads=TIE_HEADS)
    p32 = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    with jax.default_matmul_precision("highest"):
        want = jnp.stack([REF._layer(p32, x[b], config, layer)[0]
                          for b in range(x.shape[0])])
    assert float(jnp.abs(want - x).max()) > 0.1
    np.testing.assert_allclose(got, want, atol=2e-5)


def test_with_no_axis_bound_the_norm_is_over_the_held_channels_alone():
    """What the one-chip program computes: a Mamba-2 block that holds share 1
    of 2 with NO axis bound equals the reference given the same share (the
    gated norm's statistic over its own 128 channels), and is NOT that
    share's part of the uncut layer (whose statistic spans all 256)."""
    block, params, x = _uncut_block(0)
    config = dict(UNCUT, num_local_experts=4, experts_held=4, experts_share=0)
    share = REF.share_of({"block_0": params}, config, 1, 2)["block_0"]
    with CASE.patched():
        got = jax.jit(block.clone(mixer_shares=2).apply)(
            {"params": share}, x)[0]
    held = REF.share_config(config, 1, 2)
    p32 = jax.tree.map(lambda a: a.astype(jnp.float32), share)
    with jax.default_matmul_precision("highest"):
        want = jnp.stack([REF._layer(p32, x[b], held, 0)[0]
                          for b in range(2)])
        # the same share's rows of out_proj on the UNCUT layer's normed rows
        u = REF._rms(x[0], p32["RMSNorm_0"], held["rms_norm_eps"])
        full = jax.tree.map(lambda a: a.astype(jnp.float32), params)
        whole = REF._mamba2(full, u, config)
        mine = REF._mamba2(p32, u, held)
        other = REF._mamba2(jax.tree.map(
            lambda a: a.astype(jnp.float32),
            REF.share_of({"block_0": params}, config, 0, 2)["block_0"]),
            u, held)
    np.testing.assert_allclose(got, want, atol=2e-5)
    # two chips' own norms do not add up to the layer's one norm
    assert float(jnp.abs(mine + other - whole).max()) > 1e-2


def test_shares_of_one_leave_every_other_arch_as_it_was():
    """``mixer_shares=1`` (the default) is the model it was: the same
    parameters and the same logits, bit for bit, for an arch of every kind of
    block ``MoEBlock`` builds; and a share of linear-attention heads, or of
    Mamba-2 heads in several groups, is refused."""
    tokens = jnp.asarray(np.random.default_rng(2).integers(0, VOCAB, (1, 32)),
                         jnp.int32)
    for arch, kw in (("olmoe", {}), ("smallthinker", dict(kv_heads=2)),
                     ("trinity", dict(kv_heads=2, dense_layers=1))):
        base = dict(vocab_size=VOCAB, n_layers=2, n_heads=4, d_model=D,
                    n_experts=8, top_k=2, ffn_dim=16, arch=arch, **kw)
        plain, one = MoETransformerLM(**base), MoETransformerLM(
            **base, mixer_shares=1)
        va, vb = (jax.jit(m.init)(jax.random.key(0), tokens)
                  for m in (plain, one))
        assert jax.tree.structure(va) == jax.tree.structure(vb)
        for a, b in zip(jax.tree.leaves(va), jax.tree.leaves(vb)):
            np.testing.assert_array_equal(a, b)
        np.testing.assert_array_equal(plain.apply(va, tokens)[0],
                                      one.apply(va, tokens)[0])
    for arch in ("qwen3next", "nemotronh"):
        with pytest.raises(NotImplementedError, match="ONE group"):
            jax.eval_shape(MoETransformerLM(
                vocab_size=VOCAB, n_layers=2, n_heads=4, kv_heads=2,
                head_dim=16, d_model=D, n_experts=8, top_k=2, ffn_dim=16,
                arch=arch, mixer_shares=2).init, jax.random.key(0), tokens)
    with pytest.raises(ValueError, match="lm_mixer_shares=3"):
        CASE.train_config(lm_mixer_shares=3)
    with pytest.raises(ValueError, match="needs a dropless arch"):
        TrainConfig(lm_mixer_shares=2)


# ---- the multipliers and the head ----------------------------------------------

def test_the_four_multipliers_and_the_tied_head_are_the_rows():
    c = PUBLISHED
    assert (ROW.embed_multiplier, ROW.attn_scale, ROW.residual_scale,
            ROW.logits_divisor) == (12, 1 / 128, 0.22, 16) == (
        c["embedding_multiplier"], c["attention_multiplier"],
        c["residual_multiplier"], c["logits_scaling"])
    assert ROW.attn_scale != 128 ** -0.5
    assert ROW.tied_head and ROW.no_positions and not ROW.rope_theta \
        and c["position_embedding_type"] == "nope"
    assert ROW.gate_norm and ROW.router_score == "softmax" \
        and ROW.expert_gated and ROW.expert_act == c["hidden_act"] == "silu"
    assert not ROW.router_bias_rate and ROW.load_all_stat
    assert ROW.expert_down_std == pytest.approx(
        0.02 / (2 * c["published"]["num_hidden_layers"]) ** 0.5, rel=0.01)
    # an arch without them passes the default scale on, as before
    for name in ("olmoe", "nemotronh", "qwen3next"):
        a = ARCHS[name]
        assert (a.embed_multiplier, a.attn_scale, a.residual_scale,
                a.logits_divisor) == (0, 0, 1, 1)


def test_the_attention_scale_reaches_both_attention_paths(tiny):
    """The logits of the flash path and of the plain path agree with the
    reference at 1/128 (the common suite's case) and both move when the row's
    scale is taken away: neither path scores at ``hd ** -0.5``."""
    model, variables, tokens = tiny
    want = suite.logits(CASE)[0]
    tr_mod.ARCHS["granite4h"] = CASE.tiny_row._replace(attn_scale=0.0)
    for impl in ("full", "flash"):
        got = jax.jit(model.clone(attention_impl=impl).apply)(
            variables, tokens)[0]
        assert float(jnp.abs(got - want).max()) > 1e-3, impl


# ---- the step ---------------------------------------------------------------------

def test_the_load_over_all_outputs_comes_from_the_layers_own_counts():
    """``moe_load_all_max_over_mean`` without a bias: the busiest of all 16
    outputs over the mean, worst layer, at least the busiest HELD expert's;
    an arch that does not ask for it returns no such key."""
    _, _, m = suite.first_step(CASE, True)
    assert 1.0 <= float(m["expert_load_max_over_mean"]) \
        <= float(m[LOAD_ALL_STAT]) <= 16 / 3
    _, stats = suite.logits(CASE)
    assert LOAD_ALL_STAT in stats
    x = jax.random.normal(jax.random.key(0), (1, 32, D))
    moe = DroplessMoE(4, D, 16, top_k=2)
    _, stats = moe.apply({"params": moe.init(jax.random.key(1), x)["params"]},
                         x)
    assert LOAD_ALL_STAT not in stats


def test_the_kernels_line_the_held_share_and_bfloat16(tmp_path):
    """``LMTrainer``'s ``KERNELS`` line prints the schedules at the heads
    HELD; every record carries ``mixer_held_share`` and the registry its
    gauge; ``--compute-dtype bfloat16`` reaches the layers."""
    from ps_pytorch_tpu.runtime.lm_eval import build_lm_model
    kernels = suite.step(CASE, True).kernels
    assert kernels.count("flash_attention[") == 1
    # 2 sequences x 2 held query heads on 2 x 1 key/value heads
    assert "kv_heads=1 " in kernels
    assert " ssd[chunk=32 chunks=3 " in kernels and " heads=16 " in kernels
    # x of 128 and B, C of 16 in tiles of 16 lanes; ONE group of 128 a step
    assert " ssm_mix[lanes=16 " in kernels and " norm_lanes=128 " in kernels
    assert " grouped_matmul[" in kernels
    first, resumed, _, _, records = suite.trained(CASE)
    assert all(r["mixer_held_share"] == 0.5 for r in records)
    assert all(LOAD_ALL_STAT in r for r in records)
    assert resumed.registry.get("mixer_held_share") == 0.5
    assert first.mixer_held_share == 0.5

    narrow = build_lm_model(CASE.train_config(compute_dtype="bfloat16",
                                              train_dir=str(tmp_path)))
    assert narrow.dtype == jnp.bfloat16 and narrow.mixer_shares == 2
    tokens = jnp.zeros((1, S), jnp.int32)
    variables = jax.eval_shape(narrow.init, jax.random.key(0), tokens)
    assert all(a.dtype == jnp.float32
               for a in jax.tree.leaves(variables["params"]))
    (logits, _), state = jax.eval_shape(
        lambda v: narrow.apply(v, tokens, capture_intermediates=True,
                               mutable=["intermediates"]), variables)
    assert logits.dtype == jnp.bfloat16
    block = state["intermediates"]["block_0"]
    assert block["out_proj"]["__call__"][0].dtype == jnp.bfloat16


# ---- planted mistakes ------------------------------------------------------------------

def test_the_controls_cover_what_the_issue_names():
    assert set(CONTROLS.CONTROLS) == {
        "embedding_multiplier_left_out", "attention_scale_by_head_size",
        "residual_multiplier_left_out", "logits_scaling_left_out",
        "norm_before_the_gate", "norm_over_a_head", "norm_over_eight_groups",
        "b_c_a_head_not_shared", "rope_applied", "gates_not_renormalised",
        "shared_expert_left_out", "head_not_tied",
        *CONTROLS.PRECISION_CONTROLS}
    assert CONTROLS.CELL == "granite4h_small_tp8_1chip"
