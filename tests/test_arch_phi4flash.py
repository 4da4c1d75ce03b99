"""The ``phi4flash`` arch (Mamba-1 layers with a chunked selective scan,
window and full differential attention, a cross-decoder of gated memory units
and cross-attention layers that read one layer's scan output and one layer's
K and V; LayerNorm, SwiGLU, a tied head, no positions) against its plain
reference ``benchmark/reference/phi4_mini_flash.py`` at a tiny float32 size:
the common suite (``tests/arch_suite.py``) and what is Phi-4-flash's alone:
the loss and every parameter's gradient outside a step (the scan's
hand-written backward, the summed gradients of what is handed on, the tied
head), the kinds of layer by index and depth, the counters sown, and what its
controls cover. The scan itself is ``tests/test_selective_scan.py``'s."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import arch_suite as suite
from ps_pytorch_tpu.config import TrainConfig
from ps_pytorch_tpu.models.transformer import (
    ARCHS, COUNTER_NAMES, LAYER_KINDS, LM_COUNTERS, TransformerLM,
    lm_counters,
)

S, WINDOW, VOCAB = 24, 5, 53
ROW = ARCHS["phi4flash"]
# what this arch's layers count, of COUNTER_NAMES
HYBRID_COUNTERS = ("ssm_state_abs_max", "diff_lambda_max")
assert set(HYBRID_COUNTERS) < set(COUNTER_NAMES)


def _model(**kw):
    base = dict(vocab_size=VOCAB, n_layers=8, n_heads=4, kv_heads=2,
                head_dim=8, d_model=32, max_seq_len=S, arch="phi4flash",
                ffn_dim=48)
    base.update(kw)
    return TransformerLM(**base)


def _a_larger_embedding(params):
    """The tied embedding at a scale that gives logits of several units."""
    return {**params, "tok_embed": {
        "embedding": params["tok_embed"]["embedding"] * 20}}


def _refused_by_ring(case, tmp_path):
    tokens = jnp.zeros((1, S), jnp.int32)    # shapes alone: refused when traced
    variables = jax.eval_shape(_model().init, jax.random.key(0), tokens)
    jax.eval_shape(_model(attention_impl="ring").apply, variables, tokens)


def _refused_by_ep(case, tmp_path):
    from ps_pytorch_tpu.runtime.lm_eval import build_lm_model
    build_lm_model(TrainConfig(network="MoETransformerLM",
                               lm_arch="phi4flash"))


# The tiny preset keeps every inequality of the real one: d=32 in 4 query
# heads of 8 on 2 key/value heads (two query pairs, ONE key/value pair: the
# group of 2), depth 8 (every kind of layer), a window of 5 keys at S=24,
# d_inner 64 of 16 states, dt_rank 2, vocab 53: in the reference's (the
# published config's) keys.
CASE = suite.ArchCase(
    arch="phi4flash", parallelism="sp", config="phi4_mini_flash",
    controls=True,
    tiny=dict(hidden_size=32, num_attention_heads=4, num_key_value_heads=2,
              intermediate_size=48, vocab_size=VOCAB, sliding_window=WINDOW,
              num_hidden_layers=8,
              mamba=dict(d_state=16, d_conv=4, expand=2, dt_rank=2)),
    flags=dict(lm_d_model=32, lm_heads=4, lm_kv_heads=2, lm_head_dim=8,
               lm_ffn_dim=48, lm_vocab=VOCAB, lm_layers=8, lm_seq_len=S),
    row=dict(window=WINDOW), unsettle=_a_larger_embedding, logit_tol=2e-4,
    tol_reason="float32 both sides, only the order of reductions differs: "
               "measured 1.3e-5 on logits up to 11; 2e-4 is far under what "
               "any control changes",
    counters={"ssm_state_abs_max": (0, 100), "diff_lambda_max": (0.3, 1.5)},
    scopes=suite.LM_SCOPES | {"ffn", "ssm_proj", "ssm_conv", "ssm_scan",
                              "gmu"},
    remat_scopes=frozenset({"ssm_scan"}), another_depth=4,
    refusals=suite.hybrid_refusals("phi4flash", "sp on one device", (
        (suite.by_generate, "generate.py", "recurrent state"),
        (suite.by_serve, "serve.py", "recurrent state"),
        (suite.by_tp, "tensor parallelism", "model axis"),
        (suite.by_pp, "pipeline parallelism", "across stages"),
        (_refused_by_ring, "ring attention", "sequence shards"),
        (_refused_by_ep, "expert parallelism", "dense model"))),
    published_row=dict(window="sliding_window"))
REF, PUBLISHED, TINY = CASE.reference, CASE.published, CASE.tiny_config
CONTROLS = CASE.planted

suite.install(globals(), CASE)


@pytest.mark.parametrize("remat", [False, True])
def test_loss_and_every_gradient_agree_with_the_reference(remat):
    """``jax.grad`` of the reference's loss (a token-by-token scan, dense
    attention) against the program's: the scan's hand-written backward, the
    gradients of the handed-on ``m``, K and V summed over their readers, the
    tied embedding's two gradients; with and without per-block remat."""
    loss, grads = suite.grads(CASE, remat)
    want_loss, want = suite.reference_grads(CASE)
    assert abs(float(loss) - float(want_loss)) < 1e-5
    flat = lambda t: {jax.tree_util.keystr(k): v for k, v in
                      jax.tree_util.tree_leaves_with_path(t)}
    got, want = flat(grads), flat(want)
    assert set(got) == set(want)
    for name, g in got.items():
        scale = float(jnp.abs(want[name]).max())
        assert scale > 0, name       # every parameter is reached
        assert float(jnp.abs(g - want[name]).max()) < 2e-4 * max(scale, 1e-2), \
            name


def test_remat_gives_the_same_gradients():
    g = [suite.grads(CASE, remat)[1] for remat in (False, True)]
    worst = max(jax.tree.leaves(jax.tree.map(
        lambda a, b: float(jnp.abs(a - b).max()), *g)))
    assert worst < 1e-6


def test_the_tied_head_is_one_parameter_with_both_gradients(tiny):
    _, variables, tokens = tiny
    top = {k for k in variables["params"] if not k.startswith("block_")}
    assert top == {"tok_embed", "ln_f"}      # no lm_head, no pos_embed
    table = variables["params"]["tok_embed"]["embedding"]
    assert table.shape == (VOCAB, 32)
    grad = suite.grads(CASE, False)[1]["tok_embed"]["embedding"]
    seen = np.zeros(VOCAB, bool)
    seen[np.asarray(tokens)] = True
    rows = np.abs(np.asarray(grad)).max(axis=1)
    # a row no token looked up still gets the head's gradient; a row that was
    # looked up gets both, and the reference (which shares one table) agrees
    assert (~seen).any() and (rows[~seen] > 0).all() and (rows[seen] > 0).all()


@pytest.mark.parametrize("depth,kinds", [
    (8, "mamba window mamba window mamba_hands_memory full_hands_kv gmu cross"),
    (32, " ".join(["mamba window"] * 8 + ["mamba_hands_memory full_hands_kv"]
                  + ["gmu cross"] * 7)),
])
def test_layer_kinds_follow_the_published_rule(depth, kinds):
    got = [ROW.layer_kind(i, depth) for i in range(depth)]
    assert got == kinds.split() and set(got) <= set(LAYER_KINDS)
    assert got == [REF.layer_kind(dict(TINY, num_hidden_layers=depth), i)
                   for i in range(depth)]
    windows = [ROW.layer_window(i, depth) for i in range(depth)]
    assert windows == [512 if k == "window" else None for k in got]
    assert ARCHS["gpt2"].layer_kind(3, depth) == "attention"
    with pytest.raises(ValueError, match="multiple of 4"):
        ROW.layer_kind(0, 6)


def test_parameters_by_kind_of_layer(tiny):
    _, variables, _ = tiny
    p = variables["params"]
    assert set(p["block_0"]) == {
        "LayerNorm_0", "LayerNorm_1", "in_proj", "conv_weight", "conv_bias",
        "x_proj", "dt_proj", "dt_bias", "A_log", "D", "out_proj", "mlp"}
    attn = {"LayerNorm_0", "LayerNorm_1", "lambda_q1", "lambda_k1",
            "lambda_q2", "lambda_k2", "subln", "mlp"}
    assert set(p["block_1"]) == attn | {f"Dense_{i}" for i in range(4)}
    assert set(p["block_5"]) == set(p["block_1"])
    assert set(p["block_6"]) == {"LayerNorm_0", "LayerNorm_1", "in_proj",
                                 "out_proj", "mlp"}
    assert set(p["block_7"]) == attn | {"Dense_0", "Dense_1"}
    assert p["block_1"]["Dense_1"]["kernel"].shape == (32, 16)  # kv heads
    assert p["block_1"]["subln"]["scale"].shape == (16,)        # 2 hd
    n = sum(a.size for a in jax.tree.leaves(p))
    assert n == REF.param_count(TINY)
    assert REF.param_count(PUBLISHED) == PUBLISHED["parameters_as_run"]
    assert REF.param_count(dict(PUBLISHED, **PUBLISHED["published"])) \
        == PUBLISHED["parameters_published"]


# ---- planted mistakes ---------------------------------------------------------

def test_the_controls_cover_what_the_issue_names():
    assert set(CONTROLS.CONTROLS) == {
        "lambda_left_out", "lambda_init_of_another_layer", "window_ignored",
        "window_of_511", "window_of_513", "memory_taken_after_the_gate",
        "skip_dropped_from_the_memory", "cross_layers_read_their_own_kv",
        "conv_not_causal", "pairs_j_and_j_plus_half", "softplus_left_out",
        *CONTROLS.PRECISION_CONTROLS}


# ---- counters, the row, the kernels line ---------------------------------------

def test_counters_are_sown_and_change_no_logit(tiny):
    """The model sows what its layers count; the common step case holds that
    the sp step returns them with the loss."""
    model, variables, tokens = tiny
    logits, sown = model.apply(variables, tokens, mutable=[LM_COUNTERS])
    counters = lm_counters(sown)
    assert set(counters) == set(HYBRID_COUNTERS) == set(CASE.counters)
    assert float(counters["ssm_state_abs_max"]) > 0
    # lambda = exp(.) - exp(.) + lambda_init of the cross layer (the deepest)
    assert 0.3 < float(counters["diff_lambda_max"]) < 1.5
    assert jnp.array_equal(logits, model.apply(variables, tokens))


def test_the_row_holds_the_familys_defaults():
    cfg = TrainConfig(network="TransformerLM", lm_arch="phi4flash",
                      lm_layers=8, lm_heads=4, lm_kv_heads=2, lm_head_dim=8,
                      lm_d_model=32, lm_ffn_dim=48)
    assert cfg.lm_parallelism == "sp"
    row = ROW
    assert (row.ssm_state, row.ssm_conv, row.ssm_expand, row.window,
            row.norm_eps) == (16, 4, 2, 512, 1e-5)
    assert row.tied_head and row.no_positions and row.gated_ffn \
        and row.diff_attn


def test_the_kernels_line_prints_each_schedule():
    """``LMTrainer``'s ``KERNELS`` line: a flash record a kind of attention
    layer and the scan's schedule."""
    kernels = suite.step(CASE, True).kernels
    # S = 24 is over the tiny window of 5: a record for the window layers and
    # one for the full and cross layers, each a call of half the heads
    assert kernels.count("flash_attention[") == 2 and "window=5" in kernels
    assert "selective_scan[chunk=24 chunks=1 grid=2x1x1" in kernels
