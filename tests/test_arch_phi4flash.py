"""The ``phi4flash`` arch (Mamba-1 layers with a chunked selective scan,
window and full differential attention, a cross-decoder of gated memory units
and cross-attention layers that read one layer's scan output and one layer's
K and V; LayerNorm, SwiGLU, a tied head, no positions) against its plain
reference ``benchmark/reference/phi4_mini_flash.py`` at a tiny float32 size:
the common suite (``tests/arch_suite.py``) and what is Phi-4-flash's alone:
the loss and every parameter's gradient outside a step (the scan's
hand-written backward, the summed gradients of what is handed on, the tied
head), the kinds of layer by index and depth, the counters sown, what its
controls cover, and the form of a differential layer under ``flash`` (two
calls, a value twice the keys' width, nothing sliced by stride or joined; what
a layer hands on is what the calls take). The scan itself is
``tests/test_selective_scan.py``'s."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import arch_suite as suite
from ps_pytorch_tpu.config import TrainConfig
from ps_pytorch_tpu.models.transformer import (
    ARCHS, COUNTER_NAMES, LAYER_KINDS, LM_COUNTERS, Block, TransformerLM,
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
    # one for the full and cross layers, each a call of half the heads over
    # the pairs' values, two heads of 8 side by side
    assert kernels.count("flash_attention[") == 2
    assert kernels.count(" dv=16 tiles=") == 2
    assert kernels.count(" kv_heads=1 dv=16 ") == 1
    assert kernels.count(" kv_heads=1 window=5 dv=16 ") == 1
    assert "selective_scan[chunk=24 chunks=1 grid=2x1x1" in kernels


# ---- a differential layer under flash: two calls, nothing sliced or joined -----

def _eqns(jaxpr):
    """Every equation of a jaxpr and of the jaxprs it calls, a Pallas kernel's
    body apart (what a kernel does inside is the kernel's)."""
    from ps_pytorch_tpu.utils.flops import _sub_jaxprs
    for eqn in jaxpr.eqns:
        yield eqn
        if eqn.primitive.name != "pallas_call":
            for sub in _sub_jaxprs(eqn):
                yield from _eqns(sub)


def _flash_grads(remat):
    model, variables, tokens = suite.tiny(CASE)
    model = model.clone(attention_impl="flash", remat=remat)
    rest = {k: v for k, v in variables.items() if k != "params"}
    return jax.value_and_grad(lambda p: suite.model_loss(
        model, {**rest, "params": p}, tokens)), variables["params"]


@pytest.mark.parametrize("remat", [False, True])
def test_a_differential_layer_makes_two_calls_and_no_slices(remat):
    """The jaxpr of the loss and its gradient under ``flash``: each of the
    four attention layers (two window, one full, one cross) calls the forward
    kernel twice and the backward kernel twice, on a value twice as wide as
    the keys, and under ``attn_core`` nothing is joined, padded, gathered or
    sliced by stride, forward or backward."""
    fn, params = _flash_grads(remat)
    with CASE.patched():
        eqns = list(_eqns(jax.make_jaxpr(fn)(params).jaxpr))
    calls = [e for e in eqns if e.primitive.name == "pallas_call"
             and e.params["name"].startswith("flash_")]
    names = [e.params["name"] for e in calls]
    # the forward runs once a layer under remat too: its output is kept
    assert {n: names.count(n) for n in set(names)} == {
        "flash_win_fwd": 4, "flash_win_bwd_dkv": 4, "flash_fwd": 4,
        "flash_bwd_dkv": 4}
    for e in calls:
        q, k, v = (var.aval.shape for var in e.invars[:3])
        assert (q, k, v) == ((4, S, 8), (2, S, 8), (2, S, 16))   # B x heads
        assert e.outvars[0].aval.shape == (
            (4, S, 16) if e.params["name"].endswith("_fwd") else (1, 4, S, 8))
    core = [e for e in eqns if "attn_core" in str(e.source_info.name_stack)]
    assert {e.primitive.name for e in core} >= {"pallas_call"}
    for e in core:
        assert e.primitive.name not in (
            "concatenate", "pad", "gather", "scatter-add", "scatter",
            "dynamic_update_slice", "transpose"), e
        if e.primitive.name == "slice":
            assert not e.params["strides"] or set(e.params["strides"]) == {1}
    # the model's own slices and joins, whatever the scope: a pair's heads
    # come apart as two blocks of one array, and nothing is put together
    model_eqns = [e for e in eqns if "attn_" in str(e.source_info.name_stack)]
    assert not [e for e in model_eqns if e.primitive.name == "concatenate"]
    assert not [e for e in model_eqns if e.primitive.name == "slice"
                and e.params["strides"] and set(e.params["strides"]) != {1}]


def test_loss_and_gradients_under_flash_are_those_under_full():
    fn, params = _flash_grads(False)
    with CASE.patched():
        loss, grads = jax.jit(fn)(params)
    want_loss, want = suite.grads(CASE, False)
    assert abs(float(loss) - float(want_loss)) < 1e-5
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(grads),
                            jax.tree.leaves(want)):
        scale = float(jnp.abs(w).max())
        assert float(jnp.abs(g - w).max()) < 2e-4 * max(scale, 1e-2), \
            jax.tree_util.keystr(path)


def _block(model, layer, **kw):
    return Block(model.n_heads, model.d_model, model.dtype, arch=model.arch,
                 ffn_dim=model.ffn_dim, layer=layer, kv_heads=model.kv_heads,
                 head_dim=model.head_dim, n_layers=model.n_layers, **kw)


def test_what_a_layer_hands_on_is_what_the_calls_take(tiny):
    """Layer 5 hands on K as its pairs' heads apart, two arrays [B, pairs, S,
    hd], and V as the pairs' values [B, pairs, S, 2 hd]: the projections'
    features as they lie. The cross layer's two calls read exactly those
    arrays (a reshape to the kernels' three dimensions apart: no copy)."""
    model, variables, tokens = tiny
    p = variables["params"]
    x = jax.random.normal(jax.random.key(2), (2, S, model.d_model))
    with CASE.patched():
        _, out = _block(model, 5).apply({"params": p["block_5"]}, x, None, {})
    k, v = out["k"], out["v"]
    assert [t.shape for t in k] == [(2, 1, S, 8)] * 2 and v.shape == (2, 1, S, 16)
    ln = p["block_5"]["LayerNorm_0"]
    mean = x.mean(-1, keepdims=True)
    y = (x - mean) / jnp.sqrt(((x - mean) ** 2).mean(-1, keepdims=True)
                              + ROW.norm_eps) * ln["scale"] + ln["bias"]
    k_proj = y @ p["block_5"]["Dense_1"]["kernel"]      # [B, S, 2 heads x 8]
    v_proj = y @ p["block_5"]["Dense_2"]["kernel"]
    for i in (0, 1):                                    # head i of the pair
        np.testing.assert_allclose(k[i][:, 0], k_proj[..., 8 * i:8 * i + 8],
                                   rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(v[:, 0], v_proj, rtol=1e-5, atol=1e-5)

    handed = {"k": k, "v": v, "memory": jnp.zeros((2, S, 64))}
    cross = _block(model, 7, attention_impl="flash")
    with CASE.patched():
        jaxpr = jax.make_jaxpr(lambda h: cross.apply(
            {"params": p["block_7"]}, x, None, h))(handed).jaxpr
    made = {var: e for e in jaxpr.eqns for var in e.outvars}

    def source(var):
        while var in made and made[var].primitive.name == "reshape":
            var = made[var].invars[0]
        return var
    flash = [e for e in jaxpr.eqns if e.primitive.name == "custom_vjp_call"]
    assert len(flash) == 2
    leaves = dict(zip(jaxpr.invars, ("k0", "k1", "memory", "v")))
    assert [[leaves.get(source(var)) for var in e.invars[1:3]]
            for e in flash] == [["k0", "v"], ["k1", "v"]]
    # and they are read as what they are: the pair's heads exchanged is
    # another layer
    got = [cross.apply({"params": p["block_7"]}, x, None, h)[0]
           for h in (handed, {**handed, "k": k[::-1]})]
    assert float(jnp.abs(got[0] - got[1]).max()) > 1e-3
