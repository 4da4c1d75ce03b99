"""The ``evabyte`` arch (EVA layers: exact causal attention inside a window
that is a block of the diagonal, one softmax shared with the chunk summaries
of every earlier window; zero-centred RMSNorm, RoPE, SwiGLU, several
prediction heads on one trunk) against its plain reference
``benchmark/reference/evabyte_6_5b.py`` at a tiny float32 size with four
windows: the common suite (``tests/arch_suite.py``) and what is EvaByte's
alone: the parameters by kind at the published widths, a sequence no longer
than a window as plain causal attention, the heads' targets and weights, the
loss's own planted mistakes, the counter sown and what its controls cover. The
kernels themselves are ``tests/test_eva_attention.py``'s."""

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
from ps_pytorch_tpu.parallel import sp
from ps_pytorch_tpu.parallel.ring import full_attention

S, WINDOW, CHUNK, HEADS, VOCAB = 128, 32, 4, 3, 97
ROW = ARCHS["evabyte"]


def _model(**kw):
    base = dict(vocab_size=VOCAB, n_layers=2, n_heads=2, d_model=64,
                max_seq_len=S, arch="evabyte", ffn_dim=96)
    base.update(kw)
    return TransformerLM(**base)


def _refused_by_ring(case, tmp_path):
    tokens = jnp.zeros((1, S), jnp.int32)    # shapes alone: refused when traced
    variables = jax.eval_shape(_model().init, jax.random.key(0), tokens)
    jax.eval_shape(_model(attention_impl="ring").apply, variables, tokens)


def _refused_by_ep(case, tmp_path):
    from ps_pytorch_tpu.runtime.lm_eval import build_lm_model
    build_lm_model(TrainConfig(network="MoETransformerLM", lm_arch="evabyte"))


# The tiny preset keeps what the real one has: d=64 in 2 heads of 32, a window
# of 32 tokens in chunks of 4 (8 summaries a window) at S=128 (four windows:
# the last reads 24 summaries), 3 prediction heads, vocab 97; phi and mu drawn
# at unit scale (at the published 0.01275 a layer that dropped them would
# still agree), the embedding too: in the reference's (the published
# config's) keys.
CASE = suite.ArchCase(
    arch="evabyte", parallelism="sp", config="evabyte_6_5b", controls=True,
    tiny=dict(hidden_size=64, num_attention_heads=2, num_key_value_heads=2,
              intermediate_size=96, vocab_size=VOCAB, num_hidden_layers=2,
              window_size=WINDOW, chunk_size=CHUNK, num_pred_heads=HEADS),
    flags=dict(lm_d_model=64, lm_heads=2, lm_ffn_dim=96, lm_vocab=VOCAB,
               lm_layers=2, lm_seq_len=S),
    row=dict(eva_window=WINDOW, eva_chunk=CHUNK, pred_heads=HEADS,
             eva_std=1.0, embed_std=1.0),
    pred_heads=HEADS, logit_tol=5e-5,
    tol_reason="float32 both sides, only the order of reductions differs "
               "(the kernels' online softmax against one dense softmax): "
               "measured 2e-6 on logits up to 4; 5e-5 is far under what any "
               "control changes",
    counters={"eva_pool_weight_max": (1 / CHUNK, 1.0),
              "next_token_loss_head0": (3.0, 7.0)},
    scopes=suite.LM_SCOPES | {"ffn", "eva_pool"},
    remat_scopes=frozenset({"ffn"}), another_depth=4,
    refusals=suite.hybrid_refusals("evabyte", "sp on one device", (
        (suite.by_generate, "generate.py", "list of chunk summaries"),
        (suite.by_serve, "serve.py", "list of chunk summaries"),
        (suite.by_decode, "decode", "list of chunk summaries"),
        (suite.by_tp, "tensor parallelism", "model axis"),
        (suite.by_pp, "pipeline parallelism", "prediction head"),
        (_refused_by_ring, "ring attention", "sequence shards"),
        (_refused_by_ep, "expert parallelism", "dense model"))),
    published_row=dict(eva_window="window_size", eva_chunk="chunk_size",
                       pred_heads="num_pred_heads", norm_eps="rms_norm_eps",
                       rope_theta="rope_theta", embed_std="init_std",
                       eva_std="init_std"))
REF, PUBLISHED, TINY = CASE.reference, CASE.published, CASE.tiny_config
CONTROLS = CASE.planted

suite.install(globals(), CASE)


def test_parameters_by_kind_at_the_published_widths(tiny):
    """The tree of a layer, and the counts of the configuration at the
    published widths from shapes alone."""
    p = tiny[1]["params"]
    assert set(p["block_0"]) == {
        "ZeroCentredRMSNorm_0", "ZeroCentredRMSNorm_1", "Dense_0", "Dense_1",
        "Dense_2", "Dense_3", "adaptive_phi", "adaptive_mu_k", "mlp"}
    assert p["block_0"]["adaptive_phi"].shape == (2, 32)
    assert p["lm_head"]["kernel"].shape == (64, HEADS * VOCAB)
    assert {k for k in p if not k.startswith("block_")} \
        == {"tok_embed", "ln_f", "lm_head"}     # untied, no position table
    assert sum(a.size for a in jax.tree.leaves(p)) == REF.param_count(TINY)
    # the ARCHS row as published: the autouse fixture holds the tiny one
    suite.tr_mod.ARCHS["evabyte"] = CASE.published_arch_row
    a = dict(zip(PUBLISHED["program_args"][::2],
                 PUBLISHED["program_args"][1::2]))
    model = TransformerLM(
        vocab_size=int(a["--lm-vocab"]), n_layers=int(a["--lm-layers"]),
        n_heads=int(a["--lm-heads"]), d_model=int(a["--lm-d-model"]),
        ffn_dim=int(a["--lm-ffn-dim"]), arch="evabyte")
    shapes = jax.eval_shape(model.init, jax.random.key(0),
                            jnp.zeros((1, 32), jnp.int32))["params"]
    by = REF.params_by_kind(PUBLISHED)
    size = lambda t: sum(int(np.prod(x.shape)) for x in jax.tree.leaves(t))
    assert size(shapes["block_0"]) == by["layer"] == 202_391_552
    assert size(shapes["tok_embed"]) == by["embedding"] == 1_310_720
    assert size(shapes["lm_head"]) == by["head"] == 10_485_760
    assert size(shapes) == REF.param_count(PUBLISHED) == 821_366_784 \
        == PUBLISHED["parameters_as_run"]
    assert REF.param_count(dict(PUBLISHED, **PUBLISHED["published"])) \
        == PUBLISHED["parameters_published"] == 6_488_330_240
    assert by == {k: PUBLISHED["parameters_by_kind"][k] for k in by}


def test_a_sequence_no_longer_than_a_window_is_plain_causal_attention():
    """In window 0 no query reads a summary: the fused op and the plain form
    are ``full_attention`` under the causal mask, whatever phi and mu are."""
    from ps_pytorch_tpu.ops.eva_attention import eva_attention, eva_reference
    keys = jax.random.split(jax.random.key(2), 5)
    q, k, v = (jax.random.normal(key, (2, 2, WINDOW, 16)) for key in keys[:3])
    phi, mu = (jax.random.normal(key, (2, 16)) for key in keys[3:])
    want = full_attention(q, k, v, causal=True)
    for fn in (eva_attention, eva_reference):
        got, top = fn(q, k, v, phi, mu, window=WINDOW, chunk=CHUNK)
        np.testing.assert_allclose(got, want, atol=2e-6)
        assert 1 / CHUNK < float(top) <= 1.0


def test_head_i_predicts_byte_t_plus_1_plus_i():
    """The targets' shift and the weights: head i's target at position t is
    token t + 1 + i and its last i + 1 positions weigh 0; one head gives the
    arrays the step had before there were several, op for op."""
    tokens = jnp.arange(2 * 16).reshape(2, 16) * 3 % 31
    first_next = tokens[:, :1]      # one shard: the ppermute hands back its own
    positions = jnp.arange(16)
    targets, w = sp._targets_and_weights(tokens, first_next, positions, 16,
                                         HEADS)
    assert targets.shape == w.shape == (2, 16, HEADS)
    for i in range(HEADS):
        live = 16 - 1 - i
        np.testing.assert_array_equal(targets[:, :live, i],
                                      tokens[:, 1 + i:])
        np.testing.assert_array_equal(w[:, :live, i], 1.0)
        np.testing.assert_array_equal(w[:, live:, i], 0.0)

    def before(tokens, first_next, positions):      # the step's own lines, PR 46
        targets = jnp.concatenate([tokens[:, 1:], first_next], axis=1)
        is_global_last = positions == (16 - 1)
        return targets, jnp.broadcast_to(
            jnp.where(is_global_last, 0.0, 1.0), tokens.shape)

    now = lambda *a: sp._targets_and_weights(*a, 16)
    args = (tokens, first_next, positions)
    assert str(jax.make_jaxpr(now)(*args)) == str(jax.make_jaxpr(before)(*args))
    body = lambda f: jax.jit(f).lower(*args).as_text().split("\n", 1)[1]
    assert body(now) == body(before)    # all but the module's name


@pytest.mark.parametrize("name", sorted(CASE.planted.LOSS_CONTROLS))
def test_the_losss_own_mistakes_move_the_loss(name, tiny):
    """Which byte a head predicts and which heads count are the loss's: no
    logit moves, so the chip's comparison cannot see them; the reference's
    loss with the mistake in it is far from the step's: at a freshly
    initialised model every target costs about log(V), so far is many times
    the 1e-5 of the loss that the step's is held to, not a share of it."""
    _, variables, tokens = tiny
    loss = float(suite.first_step(CASE, True)[2]["loss"])
    want = float(suite.reference_grads(CASE)[0])
    tol = 1e-5 * want
    assert abs(loss - want) < tol
    over = CONTROLS.LOSS_CONTROLS[name]
    kept = {k: getattr(REF, k) for k in over}
    for k, v in over.items():
        setattr(REF, k, v)
    try:
        wrong = float(REF.loss(variables, tokens, TINY))
    finally:
        for k, v in kept.items():
            setattr(REF, k, v)
    assert abs(wrong - loss) > CASE.margins[0] * tol, (wrong, loss)
    assert float(REF.loss(variables, tokens, TINY)) == pytest.approx(want)


def test_the_first_heads_loss_is_the_plain_next_token_loss(tiny):
    """``next_token_loss_head0`` is the mean cross-entropy of head 0 against
    the next token, over the S - 1 positions that have one."""
    model, variables, tokens = tiny
    _, _, m = suite.first_step(CASE, True)
    logp = jax.nn.log_softmax(model.apply(variables, tokens)[:, :-1, 0])
    want = -jnp.mean(jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1))
    np.testing.assert_allclose(float(m["next_token_loss_head0"]), float(want),
                               rtol=1e-5)


def test_the_controls_cover_what_the_issue_names():
    assert set(CONTROLS.CONTROLS) == {
        "own_windows_chunks_counted_twice", "later_windows_chunks_read",
        "band_for_the_block", "two_softmaxes_averaged", "mu_left_out",
        "phi_ignored", "pooling_scale_left_out",
        "summaries_from_unrotated_keys", "chunk_twice_as_long",
        "norm_scale_w_for_1_plus_w", *CONTROLS.PRECISION_CONTROLS}
    assert set(CONTROLS.LOSS_CONTROLS) == {
        "head_i_predicts_byte_t_plus_i", "later_heads_left_out_of_the_loss"}
    # what the chip's limit cannot see is named where the limit is set
    why = PUBLISHED["reference_check"]["why"]
    for name in PUBLISHED["reference_check"]["unseen_on_the_chip"]:
        assert name in set(CONTROLS.CONTROLS) | set(CONTROLS.LOSS_CONTROLS)
        assert name in why


def test_the_counter_is_sown_and_changes_no_logit(tiny):
    model, variables, tokens = tiny
    logits, sown = model.apply({"params": variables["params"]}, tokens,
                               mutable=[LM_COUNTERS])
    counters = lm_counters(sown)
    assert set(counters) == {"eva_pool_weight_max"} < set(COUNTER_NAMES)
    assert 1 / CHUNK < float(counters["eva_pool_weight_max"]) <= 1.0
    assert jnp.array_equal(logits, model.apply(variables, tokens))


def test_the_row_and_a_length_that_is_no_whole_number_of_chunks():
    row = CASE.published_arch_row
    assert row.mixer_layers == ("eva",) and row.layer_kind(5) == "eva" \
        and "eva" in LAYER_KINDS
    assert (row.eva_window, row.eva_chunk, row.pred_heads, row.rope_theta,
            row.norm_eps) == (2048, 16, 8, 1e5, 1e-5)
    assert row.zero_centred_norm and row.gated_ffn and row.f32_logits \
        and row.counts and not row.tied_head
    assert TrainConfig(network="TransformerLM",
                       lm_arch="evabyte").lm_parallelism == "sp"
    tokens = jnp.zeros((1, S - 2), jnp.int32)
    for impl in ("full", "flash"):
        with pytest.raises(ValueError, match="refused, not padded"):
            jax.eval_shape(_model(attention_impl=impl).init,
                           jax.random.key(0), tokens)


def test_float32_logits_under_bfloat16_activations():
    model = _model(dtype=jnp.bfloat16)
    tokens = jnp.zeros((1, WINDOW), jnp.int32)
    out = jax.eval_shape(lambda: model.apply(
        model.init(jax.random.key(0), tokens), tokens))
    assert out.dtype == jnp.float32 and out.shape == (1, WINDOW, HEADS, VOCAB)


def test_the_kernels_line_prints_the_schedule():
    kernels = suite.step(CASE, True).kernels
    assert "flash_attention[" not in kernels
    # 4 heads (2 sequences x 2), four windows of one tile: 4 token tiles and
    # 0 + 1 + 2 + 3 summary tiles a head
    assert "eva_attention[window=32 chunk=4 bq=32 summaries=8/32 grid=4x4 " \
           "bwd_grid=4x4 pool_rows=32 token_tiles=4 summary_tiles=6 " in kernels
