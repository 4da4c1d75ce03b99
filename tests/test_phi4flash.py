"""The ``phi4flash`` arch (Mamba-1 layers with a chunked selective scan,
window and full differential attention, a cross-decoder of gated memory units
and cross-attention layers that read one layer's scan output and one layer's
K and V; LayerNorm, SwiGLU, a tied head, no positions) against its plain
reference ``benchmark/reference/phi4_mini_flash.py`` at a tiny float32 size:
logits, the loss and every parameter's gradient (the scan's hand-written
backward, the summed gradients of what is handed on), the chunked scan against
the token-by-token one, the planted mistakes of
``benchmark/controls/phi4_mini_flash.py``, and every entry point that refuses
the arch."""

import importlib.util
import json
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ps_pytorch_tpu.config import LM_ARCHS, TrainConfig
from ps_pytorch_tpu.models import transformer as tr_mod
from ps_pytorch_tpu.models.transformer import (
    ARCHS, COUNTER_NAMES, LAYER_KINDS, LM_COUNTERS, TransformerLM,
    lm_counters, refuse_hybrid,
)
from ps_pytorch_tpu.ops.selective_scan import (
    scan_schedule, selective_scan, selective_scan_reference,
)

REPO = pathlib.Path(__file__).resolve().parent.parent
# what this arch's layers count, of COUNTER_NAMES
HYBRID_COUNTERS = ("ssm_state_abs_max", "diff_lambda_max")
assert set(HYBRID_COUNTERS) < set(COUNTER_NAMES)


def _load(path):
    spec = importlib.util.spec_from_file_location(path.stem, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _load(REPO / "benchmark" / "reference" / "phi4_mini_flash.py")
CONTROLS = _load(REPO / "benchmark" / "controls" / "phi4_mini_flash.py")
PUBLISHED = json.loads((REPO / "benchmark" / "configs"
                        / "phi4_mini_flash.json").read_text())

# The tiny preset keeps every inequality of the real one: d=32 in 4 query
# heads of 8 on 2 key/value heads (two query pairs, ONE key/value pair: the
# group of 2), depth 8 (every kind of layer), a window of 5 keys at S=24,
# d_inner 64 of 16 states, dt_rank 2, vocab 53: in the reference's (the
# published config's) keys.
S, WINDOW, VOCAB = 24, 5, 53
TINY = dict(PUBLISHED, hidden_size=32, num_attention_heads=4,
            num_key_value_heads=2, intermediate_size=48, vocab_size=VOCAB,
            sliding_window=WINDOW, num_hidden_layers=8,
            mamba=dict(d_state=16, d_conv=4, expand=2, dt_rank=2))
# float32 on both sides, so only the order of reductions differs: measured
# 1.3e-5 on logits up to 11. 2e-4 is far under what any control changes.
LOGIT_TOL = 2e-4
ROW = ARCHS["phi4flash"]


@pytest.fixture(autouse=True)
def tiny_window(monkeypatch):
    """The window is the arch row's, not a flag: the tiny size takes a row
    with a window that closes at S=24."""
    monkeypatch.setitem(tr_mod.ARCHS, "phi4flash", ROW._replace(window=WINDOW))


def _model(**kw):
    base = dict(vocab_size=VOCAB, n_layers=8, n_heads=4, kv_heads=2,
                head_dim=8, d_model=32, max_seq_len=S, arch="phi4flash",
                ffn_dim=48)
    base.update(kw)
    return TransformerLM(**base)


@pytest.fixture(scope="module")
def tiny():
    """(model, variables, tokens): seeded weights, every vector leaf moved off
    its 0 or 1, the tied embedding at a scale that gives logits of several
    units."""
    model = _model()
    tokens = jnp.asarray(
        np.random.default_rng(1).integers(0, VOCAB, (2, S)), jnp.int32)
    params = model.init(jax.random.key(0), tokens)["params"]
    leaves, tree = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.key(5), len(leaves))
    params = jax.tree.unflatten(tree, [
        a + 0.2 * jax.random.normal(k, a.shape) if a.ndim == 1 else a
        for a, k in zip(leaves, keys)])
    params["tok_embed"]["embedding"] = params["tok_embed"]["embedding"] * 20
    return model, {"params": params}, tokens


def _model_loss(model, params, tokens):
    logits = model.apply({"params": params}, tokens).astype(jnp.float32)
    logp = jax.nn.log_softmax(logits[:, :-1], axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1))


@pytest.mark.parametrize("attention", ["full", "flash"])
def test_logits_agree_with_the_reference(tiny, attention):
    model, variables, tokens = tiny
    got = model.clone(attention_impl=attention).apply(variables, tokens)
    want = REF.forward(variables, tokens, TINY)
    assert got.shape == want.shape == (2, S, VOCAB)
    assert float(jnp.abs(want).max()) > 5
    assert float(jnp.abs(got - want).max()) < LOGIT_TOL


@pytest.mark.parametrize("remat", [False, True])
def test_loss_and_every_gradient_agree_with_the_reference(tiny, remat):
    """``jax.grad`` of the reference's loss (a token-by-token scan, dense
    attention) against the program's: the scan's hand-written backward, the
    gradients of the handed-on ``m``, K and V summed over their readers, the
    tied embedding's two gradients; with and without per-block remat."""
    model, variables, tokens = tiny
    model = model.clone(remat=remat)
    loss, grads = jax.value_and_grad(
        lambda p: _model_loss(model, p, tokens))(variables["params"])
    want_loss, want = jax.value_and_grad(
        lambda p: REF.loss({"params": p}, tokens, TINY))(variables["params"])
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


def test_remat_gives_the_same_gradients(tiny):
    model, variables, tokens = tiny
    g = [jax.grad(lambda p: _model_loss(model.clone(remat=r), p, tokens))(
        variables["params"]) for r in (False, True)]
    worst = max(jax.tree.leaves(jax.tree.map(
        lambda a, b: float(jnp.abs(a - b).max()), *g)))
    assert worst < 1e-6


def test_the_tied_head_is_one_parameter_with_both_gradients(tiny):
    model, variables, tokens = tiny
    top = {k for k in variables["params"] if not k.startswith("block_")}
    assert top == {"tok_embed", "ln_f"}      # no lm_head, no pos_embed
    table = variables["params"]["tok_embed"]["embedding"]
    assert table.shape == (VOCAB, 32)
    grad = jax.grad(lambda p: _model_loss(model, p, tokens))(
        variables["params"])["tok_embed"]["embedding"]
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


# ---- the scan ---------------------------------------------------------------

def _scan_inputs(bt=2, s=37, di=40, n=4, seed=0):
    rng = np.random.default_rng(seed)
    f = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)
    delta = jnp.asarray(rng.uniform(0.01, 0.3, (bt, s, di)), jnp.float32)
    a = -jnp.asarray(rng.uniform(0.5, 4, (di, n)), jnp.float32)
    return f(bt, s, di), delta, a, f(bt, s, n), f(bt, s, n), f(di)


@pytest.mark.parametrize("chunk", [8, 16, 37, None],
                         ids=lambda c: f"chunk_{c}")
def test_chunked_scan_agrees_with_the_token_by_token_scan(chunk):
    """S = 37 against chunks that do not divide it (8, 16: the tail is
    padded with tokens the state passes unchanged), one that is S itself and
    the default (cut to S); 40 channels padded to a block of 1024. Output and
    all six gradients, the hand-written backward against autodiff of the
    plain scan."""
    args = _scan_inputs()
    w = jnp.asarray(np.random.default_rng(9).normal(size=args[0].shape),
                    jnp.float32)
    y, state_max = selective_scan(*args, chunk=chunk)
    want = selective_scan_reference(*args)
    assert float(jnp.abs(y - want).max()) < 5e-6
    assert 0.5 < float(state_max) < 10
    got = jax.grad(lambda *a: jnp.sum(selective_scan(*a, chunk=chunk)[0] * w),
                   argnums=tuple(range(6)))(*args)
    want = jax.grad(lambda *a: jnp.sum(selective_scan_reference(*a) * w),
                    argnums=tuple(range(6)))(*args)
    for g, r in zip(got, want):
        assert float(jnp.abs(g - r).max()) < 1e-5 * float(jnp.abs(r).max())


def test_scan_state_is_float32_under_bfloat16_inputs():
    u, delta, a, b, c, d = _scan_inputs(bt=1, s=64)
    y, _ = selective_scan(u.astype(jnp.bfloat16), delta, a, b, c, d, chunk=16)
    assert y.dtype == jnp.bfloat16
    want = selective_scan_reference(u.astype(jnp.bfloat16), delta, a, b, c, d)
    # only the output's rounding: the state did not pass bfloat16
    assert float(jnp.abs(y.astype(jnp.float32) - want).max()) \
        < 2 ** -8 * float(jnp.abs(want).max())


def test_scan_schedule_says_what_a_call_holds():
    sc = scan_schedule(2, 8192, 5120, 16)
    assert (sc.chunk, sc.chunks, sc.blocks) == (128, 64, 5)
    assert sc.carried_bytes == 2 * 5120 * 16 * 4
    assert sc.kept_bytes == 64 * sc.carried_bytes
    assert sc.bwd_vmem_bytes == 129 * 1024 * 16 * 4
    assert "chunk=128 chunks=64" in sc.describe()
    assert scan_schedule(1, 100, 40, 4, 256).chunk == 100


# ---- planted mistakes ---------------------------------------------------------

@pytest.mark.parametrize("name", sorted(CONTROLS.CONTROLS))
def test_every_planted_mistake_fails_by_a_wide_margin(tiny, name):
    """The program as it is against the reference with one mistake in it
    (and, for the two precisions below the stated one, the reference computed
    coarser): each is far over the tolerance the true comparison keeps."""
    model, variables, tokens = tiny
    got = model.apply(variables, tokens)
    driver = object()
    with CONTROLS.planted(CONTROLS.CONTROLS[name], driver, REF, TINY) \
            as (_, ref):
        want = ref.forward(variables, tokens, TINY)
    # a mistake in the mathematics is 25 tolerances off or more; a coarser
    # precision (24 tokens of rounding: measured 13 tolerances) at least 5
    margin = 5 if name in CONTROLS.PRECISION_CONTROLS else 25
    # (not within it: without the softplus delta is negative and the wrong
    # model's state overflows, which is no agreement either)
    assert not float(jnp.abs(got - want).max()) <= margin * LOGIT_TOL, name
    # and the reference is itself again
    assert float(jnp.abs(got - REF.forward(variables, tokens, TINY)).max()) \
        < LOGIT_TOL


def test_the_controls_cover_what_the_issue_names():
    assert set(CONTROLS.CONTROLS) == {
        "lambda_left_out", "lambda_init_of_another_layer", "window_ignored",
        "window_of_511", "window_of_513", "memory_taken_after_the_gate",
        "skip_dropped_from_the_memory", "cross_layers_read_their_own_kv",
        "conv_not_causal", "pairs_j_and_j_plus_half", "softplus_left_out",
        *CONTROLS.PRECISION_CONTROLS}


# ---- counters and the step ------------------------------------------------------

def test_counters_are_sown_and_the_sp_step_returns_them(tiny):
    from jax.sharding import Mesh
    from ps_pytorch_tpu.optim.sgd import sgd
    from ps_pytorch_tpu.parallel.sp import (
        create_lm_train_state, make_sp_train_step,
    )
    model, variables, tokens = tiny
    logits, sown = model.apply(variables, tokens, mutable=[LM_COUNTERS])
    counters = lm_counters(sown)
    assert set(counters) == set(HYBRID_COUNTERS)
    assert float(counters["ssm_state_abs_max"]) > 0
    # lambda = exp(.) - exp(.) + lambda_init of the cross layer (the deepest)
    assert 0.3 < float(counters["diff_lambda_max"]) < 1.5
    assert jnp.array_equal(logits, model.apply(variables, tokens))

    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
    tx = sgd(lr=0.05, momentum=0.9)
    state = create_lm_train_state(model, tx, mesh, tokens.shape,
                                  jax.random.key(0))
    step = make_sp_train_step(model, tx, mesh, remat=True, donate=False)
    new, metrics = step(state, tokens)
    assert set(metrics) == {"loss", *HYBRID_COUNTERS}
    assert all(np.isfinite(float(v)) for v in metrics.values())
    # and a model without such layers returns the loss alone
    gpt2 = TransformerLM(vocab_size=VOCAB, n_layers=1, n_heads=2, d_model=16,
                         max_seq_len=S)
    gstate = create_lm_train_state(gpt2, tx, mesh, tokens.shape,
                                   jax.random.key(0))
    _, gm = make_sp_train_step(gpt2, tx, mesh, donate=False)(gstate, tokens)
    assert set(gm) == {"loss"}


def test_config_knows_the_arch_and_needs_no_new_field():
    assert "phi4flash" in LM_ARCHS and set(LM_ARCHS) == set(ARCHS)
    cfg = TrainConfig(network="TransformerLM", lm_arch="phi4flash",
                      lm_layers=8, lm_heads=4, lm_kv_heads=2, lm_head_dim=8,
                      lm_d_model=32, lm_ffn_dim=48)
    assert cfg.lm_parallelism == "sp"
    row = ROW
    assert (row.ssm_state, row.ssm_conv, row.ssm_expand, row.window,
            row.norm_eps) == (16, 4, 2, 512, 1e-5)
    assert row.tied_head and row.no_positions and row.gated_ffn \
        and row.diff_attn


# ---- refusals -------------------------------------------------------------------

def _tiny_checkpoint(tmp_path):
    from ps_pytorch_tpu.runtime import checkpoint as ckpt
    from ps_pytorch_tpu.runtime.lm_eval import build_lm_template
    cfg = TrainConfig(network="TransformerLM", lm_arch="phi4flash",
                      lm_vocab=VOCAB, lm_d_model=32, lm_layers=4, lm_heads=4,
                      lm_kv_heads=2, lm_head_dim=8, lm_ffn_dim=48,
                      lm_seq_len=S, train_dir=str(tmp_path))
    ckpt.save_checkpoint(cfg.train_dir, 1, build_lm_template(cfg),
                         config_json=cfg.to_json())
    return cfg


def _refused_by_generate(tmp_path):
    import generate
    cfg = _tiny_checkpoint(tmp_path)
    generate.main(["--train-dir", cfg.train_dir, "--prompt", "ab"])


def _refused_by_serve(tmp_path):
    import serve
    cfg = _tiny_checkpoint(tmp_path)
    serve.main(["--train-dir", cfg.train_dir, "--serve-port", "0"])


def _refused_by_tp(tmp_path):
    from ps_pytorch_tpu.parallel.tp import make_tp_train_step
    make_tp_train_step(_model(kv_heads=0, head_dim=0), None, None, None)


def _refused_by_pp(tmp_path):
    from ps_pytorch_tpu.parallel.pp import make_pp_train_step
    make_pp_train_step(_model(kv_heads=0, head_dim=0), None, None, None,
                       num_microbatches=1)


def _refused_by_ring(tmp_path):
    tokens = jnp.zeros((1, S), jnp.int32)
    model = _model(attention_impl="ring")
    variables = _model().init(jax.random.key(0), tokens)
    model.apply(variables, tokens)


def _refused_by_ep(tmp_path):
    from ps_pytorch_tpu.runtime.lm_eval import build_lm_model
    build_lm_model(TrainConfig(network="MoETransformerLM",
                               lm_arch="phi4flash"))


@pytest.mark.parametrize("entry,where,lacks", [
    (_refused_by_generate, "generate.py", "recurrent state"),
    (_refused_by_serve, "serve.py", "recurrent state"),
    (_refused_by_tp, "tensor parallelism", "model axis"),
    (_refused_by_pp, "pipeline parallelism", "across stages"),
    (_refused_by_ring, "ring attention", "sequence shards"),
    (_refused_by_ep, "expert parallelism", "dense model"),
], ids=lambda x: x if isinstance(x, str) else "")
def test_every_other_entry_point_refuses_the_arch_by_name(
        tmp_path, capsys, entry, where, lacks):
    """One function writes every refusal (``refuse_hybrid``); each entry point
    is a case, and the message says what is missing."""
    try:
        entry(tmp_path)
    except SystemExit as e:         # an argparse error: the message is on stderr
        assert e.code == 2
        message = capsys.readouterr().err
    except ValueError as e:
        message = str(e)
    else:
        pytest.fail(f"{where} did not refuse lm_arch=phi4flash")
    assert "lm_arch=phi4flash is not built for " + where in message
    assert lacks in message and "lm_parallelism sp on one device" in message
    refuse_hybrid("gpt2", where)        # and no other arch is refused there
