"""The ``trinity`` arch (a leading dense layer, then expert layers with a shared
expert beside the routed ones; sigmoid scores, a top-k chosen under a bias, gates
renormalised and scaled; q/k norm a head, a gated attention output, a norm on
each sublayer's input and output, the embedding times sqrt(d); window layers
with RoPE and global layers without position encoding) against its plain reference ``benchmark/reference/trinity_mini.py``
at a tiny size; the shares of an expert layer against the uncut layer; the
bias: its step, what the optimizer leaves alone, what it does to an overloaded
expert, and a checkpoint that resumes it bit for bit."""

import importlib.util
import json
import pathlib
import types

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from ps_pytorch_tpu.config import TrainConfig
from ps_pytorch_tpu.models import moe as moe_mod
from ps_pytorch_tpu.models import transformer as tr_mod
from ps_pytorch_tpu.models.moe import (
    BIAS_STATS, DROPLESS_STATS, EXPERT_COUNTS, MOE_STATE, DroplessMoE,
    GatedFFN, MoETransformerLM, lm_variables, update_expert_bias,
)
from ps_pytorch_tpu.models.transformer import ARCHS
from ps_pytorch_tpu.optim.sgd import sgd
from ps_pytorch_tpu.parallel import ep
from ps_pytorch_tpu.parallel.dp import TrainState
from ps_pytorch_tpu.parallel.ring import full_attention

REPO = pathlib.Path(__file__).resolve().parent.parent


def _load(path):
    spec = importlib.util.spec_from_file_location(path.stem, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _load(REPO / "benchmark" / "reference" / "trinity_mini.py")
CONTROLS = _load(REPO / "benchmark" / "controls" / "trinity_mini.py")
PLAIN = _load(REPO / "tests" / "dropless_plain.py")
PUBLISHED = json.loads((REPO / "benchmark" / "configs"
                        / "trinity_mini.json").read_text())

# The tiny preset keeps every inequality of the real one: d=24 against 4 query
# heads of 8 (heads x head_dim = 32 != d) on 2 key/value heads, the published
# layers 0..4 (window, window, window, global, window) of which the first is
# dense (width 40), a window of 8 keys at S=32, 8 experts top-3 of width 16 of
# which experts 4..7 are held (share 1 of 2), one shared expert, vocab 97: in
# the reference's (the published config's) keys.
S, WINDOW = 32, 8
TINY = dict(PUBLISHED, hidden_size=24, head_dim=8, num_attention_heads=4,
            num_key_value_heads=2, num_hidden_layers=5, num_dense_layers=1,
            sliding_window=WINDOW, intermediate_size=40,
            moe_intermediate_size=16, num_experts_per_tok=3, num_experts=4,
            num_experts_published=8, experts_held=4, experts_share=1,
            vocab_size=97)
# float32 on both sides, so only the order of reductions differs: measured
# 4e-6 on logits up to 4. 1e-4 is far under what any of the controls changes.
LOGIT_TOL = 1e-4
ROW = ARCHS["trinity"]      # as published, before the tiny window below


def _row(**kw):
    return ROW._replace(window=WINDOW, **kw)


@pytest.fixture(autouse=True)
def tiny_window(monkeypatch):
    """The window is the arch row's, not a flag: the tiny size takes a row
    with a window that closes at S=32."""
    monkeypatch.setitem(tr_mod.ARCHS, "trinity", _row())


def _model(**kw):
    base = dict(vocab_size=97, n_layers=5, n_heads=4, kv_heads=2, head_dim=8,
                d_model=24, max_seq_len=S, arch="trinity", ffn_dim=16,
                n_experts=8, top_k=3, experts_held=4, experts_share=1,
                dense_layers=1, dense_ffn_dim=40)
    base.update(kw)
    return MoETransformerLM(**base)


def _unsettled(variables, key):
    """Every vector leaf (norm scales, the bias) moved off its 1 or 0, so
    that one left out or applied in the wrong place shows."""
    leaves, tree = jax.tree.flatten(variables)
    keys = jax.random.split(key, len(leaves))
    return jax.tree.unflatten(tree, [
        a + 0.2 * jax.random.normal(k, a.shape) if a.ndim == 1 else a
        for a, k in zip(leaves, keys)])


@pytest.fixture(scope="module")
def tiny():
    """(model, variables, tokens) with seeded weights."""
    model = _model()
    tokens = jnp.asarray(
        np.random.default_rng(1).integers(0, 97, (2, S)), jnp.int32)
    variables = _unsettled(dict(model.init(jax.random.key(0), tokens)),
                           jax.random.key(5))
    return model, variables, tokens


def _logits(model, variables, tokens):
    return model.apply(variables, tokens)[0]


@pytest.mark.parametrize("attention", ["full", "flash"])
def test_logits_agree_with_the_reference(tiny, attention):
    model, variables, tokens = tiny
    got, stats = model.clone(attention_impl=attention).apply(variables, tokens)
    want = REF.forward(variables, tokens, TINY)
    assert got.shape == want.shape == (2, S, 97)
    assert float(jnp.abs(want).max()) > 2
    assert float(jnp.abs(got - want).max()) < LOGIT_TOL
    assert set(stats) == {*DROPLESS_STATS, EXPERT_COUNTS}
    assert float(stats["moe_dropped"]) == 0.0
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
    want_counts = REF.expert_counts(variables, tokens, TINY)
    for name, c in want_counts.items():
        np.testing.assert_array_equal(
            stats[EXPERT_COUNTS][name]["moe"]["expert_bias"], c)
        assert int(c.sum()) == 2 * S * 3


@pytest.mark.parametrize("name", sorted(CONTROLS.CONTROLS))
def test_the_logit_tolerance_catches(tiny, name):
    """Each mechanism left out or misplaced moves the logits by far more than
    LOGIT_TOL; so do the precisions below the one the configuration states
    (every parameter, or the blocks' alone, rounded to float8_e4m3fn). The
    controls are ``benchmark/controls/trinity_mini.py``'s, which reads the
    same ones at the cell's size on the chip."""
    model, variables, tokens = tiny
    driver = types.SimpleNamespace(
        system_forward=lambda trainer, v, x: _logits(trainer.model, v, x))
    with CONTROLS.planted(CONTROLS.CONTROLS[name], driver, REF, TINY) \
            as (wrong, ref):
        got = wrong.system_forward(types.SimpleNamespace(model=model),
                                   variables, tokens)
        want = ref.forward(variables, tokens, TINY)
    assert tr_mod.ARCHS["trinity"] == _row()        # put back
    assert float(jnp.abs(got - want).max()) > 50 * LOGIT_TOL


def _one_device_mesh():
    from jax.sharding import Mesh
    return Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("data", "model"))


def _state(variables, tx):
    return TrainState(step=jnp.zeros((), jnp.int32),
                      params=variables["params"],
                      opt_state=tx.init(variables["params"]),
                      batch_stats=variables[MOE_STATE])


def test_the_ep_step_descends_the_reference_loss_and_moves_the_bias(tiny):
    """One plain-SGD step of ``parallel/ep.py``'s step on one device moves
    every parameter by ``lr * jax.grad(reference.loss)`` (the cross-entropy
    alone: the arch's load-balance coefficient is 0), through the gate, the
    norms on the outputs, the scaled renormalised sigmoid gates, the shared
    expert, the dense layer and both kinds of attention layer; and the bias
    by the reference's ``bias_step`` of the reference's counts."""
    model, variables, tokens = tiny
    assert ROW.aux_coef == 0.0 == ROW.z_loss_coef
    assert ROW.router_bias_rate == PUBLISHED["load_balance_coeff"]
    assert ROW.route_scale == PUBLISHED["route_scale"]
    lr = 0.5
    tx = optax.sgd(lr)
    state = _state(variables, tx)
    step = ep.make_ep_train_step(model.clone(ep_axis="data"), tx,
                                 _one_device_mesh(), state, donate=False)
    new_state, m = step(state, tokens)
    want = jax.grad(lambda p: REF.loss({**variables, "params": p}, tokens,
                                       TINY))(variables["params"])
    got = jax.tree.map(lambda a, b: (a - b) / lr, state.params,
                       new_state.params)
    flat_got = jax.tree_util.tree_flatten_with_path(got)[0]
    flat_want = jax.tree.leaves(want)
    assert len(flat_got) == len(flat_want)
    for (path, g), w in zip(flat_got, flat_want):
        assert float(jnp.abs(w).max()) > 0, path
        np.testing.assert_allclose(g, w, atol=2e-5, rtol=1e-3,
                                   err_msg=jax.tree_util.keystr(path))
    np.testing.assert_allclose(float(m["loss"]),
                               float(REF.loss(variables, tokens, TINY)),
                               rtol=1e-5)
    assert set(m) == {"loss", *DROPLESS_STATS, *BIAS_STATS}
    assert float(m["moe_dropped"]) == 0.0
    counts = REF.expert_counts(variables, tokens, TINY)
    biases = []
    for name, c in counts.items():
        want_b = REF.bias_step(variables[MOE_STATE][name]["moe"]["expert_bias"],
                               c, TINY)
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


def _one_layer(key):
    """Block 1 of an uncut tiny model (16 experts top-3, so that 8 shares of
    2 exist), its bias seeded, and a normed stream m [S, d]."""
    model = _model(n_experts=16, experts_held=0, experts_share=0)
    variables = _unsettled(
        dict(model.init(key, jnp.zeros((1, S), jnp.int32))),
        jax.random.fold_in(key, 1))
    m = jax.random.normal(jax.random.fold_in(key, 2), (S, 24))
    return (variables["params"]["block_1"],
            variables[MOE_STATE]["block_1"]["moe"], m)


def _share_of(moe_params, held, share):
    return {k: v[share * held:(share + 1) * held] if k.startswith("experts_")
            else v for k, v in moe_params.items()}


@pytest.mark.parametrize("side", ["program", "reference"])
def test_the_shares_add_up_to_the_uncut_layer(side):
    """One expert layer at the tiny size, all 16 experts' weights seeded: the
    routed parts of the eight shares (2 of 16 experts held, share 0..7) plus
    the shared expert COUNTED ONCE add up to the uncut reference layer's f
    (before its output norm), and the eight blocks' counts to the layer's."""
    bp, bias, m = _one_layer(jax.random.key(3))
    uncut = dict(TINY, num_experts=16, num_experts_published=16,
                 experts_held=16, experts_share=0)
    f_uncut, w = REF._feed_forward(bp, bias["expert_bias"], m, uncut, 1)
    counts_uncut = jnp.sum(w > 0, axis=0)
    shared = REF._swiglu(bp["shared"], m)
    assert float(jnp.abs(shared).max()) > 0.1
    total, counts = shared, []
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
        total = total + routed
        counts.append(held)
    np.testing.assert_allclose(total, f_uncut, atol=5e-6)
    np.testing.assert_array_equal(jnp.concatenate(counts), counts_uncut)
    assert int(counts_uncut.sum()) == S * 3
    if side == "program":       # the program's shared expert is the reference's
        got = GatedFFN(16).apply({"params": bp["shared"]}, m)
        np.testing.assert_allclose(got, shared, atol=1e-6)


def _bias_case(monkeypatch, slack, **kw):
    """``tests/dropless_plain.py:tiny_case`` with Trinity's row (sigmoid
    scores, a top-3 chosen under a bias that is not all zeros, gates
    renormalised and scaled) holding experts 4..7 of 8; the main part sized
    for ``slack`` times the balanced share in tiles of 8 rows."""
    monkeypatch.setattr(moe_mod, "HELD_ROWS_SLACK", slack)
    monkeypatch.setattr(moe_mod, "HELD_ROWS_TILE", 8)
    return PLAIN.tiny_case(**{
        "top_k": 3, "gate_norm": True, "n_held": 4, "share": 1,
        "score": "sigmoid", "select_bias": True,
        "route_scale": TINY["route_scale"], **kw})


# name: (slack, whether the overflow part runs, the layer's other fields)
BIAS_PLAIN_CASES = {
    "overflow_not_taken": (1.5, False, {}),
    "overflow_taken": (0.5, True, {}),
    "the_other_share_overflow_taken": (0.5, True, dict(share=0)),
    "k_1_overflow_taken": (0.5, True, dict(top_k=1)),
    "every_expert_held": (1.5, None, dict(n_held=0, share=0)),
}


@pytest.mark.parametrize("name", sorted(BIAS_PLAIN_CASES))
def test_the_layer_is_the_plain_take_and_scatter_add(monkeypatch, name):
    """Trinity's row of the dropless layer: output, the gradients in the
    tokens, the router (through the sigmoid gates, their sum and the scale)
    and the held weights are those of the form the layer had before PR 38
    (``tests/dropless_plain.py``); ``moe_dropped``, the load figures and the
    counts it hands the bias's step under ``EXPERT_COUNTS`` exactly."""
    slack, taken, kw = BIAS_PLAIN_CASES[name]
    layer, variables, x = _bias_case(monkeypatch, slack, **kw)
    stats = PLAIN.assert_the_plain_form(layer, variables, x)
    t_k = 32 * layer.top_k
    assert int(stats[EXPERT_COUNTS]["expert_bias"].sum()) == t_k
    assert float(stats["moe_dropped"]) == 0.0
    if taken is not None:
        held, rows = PLAIN.held_and_main_rows(layer, stats, slack)
        assert 0 < held < t_k and (held > rows) == taken, (held, rows)


def test_the_compiled_route_holds_no_scatter(monkeypatch):
    """Forward and backward of a held share chosen under the bias, as the
    CPU's compiler leaves them: no ``scatter`` op under ``moe_route`` (the
    gates are gathered from the scores forward and their gradient goes back
    by a comparison; the counts the bias's step reads are comparisons); the
    six left are the two parts' rows, as in ``test_smallthinker.py``. With
    every expert held there is none at all."""
    layer, variables, x = _bias_case(monkeypatch, 0.5)
    _, names = PLAIN.scatters(PLAIN.steps(layer, variables)[0],
                              variables["params"], x)
    assert len(names) == 6
    assert all("moe_dispatch" in n and "moe_route" not in n for n in names)
    layer, variables, x = _bias_case(monkeypatch, 0.5, n_held=0, share=0)
    step, plain = PLAIN.steps(layer, variables)
    assert PLAIN.scatters(step, variables["params"], x) == (0, [])
    assert len(PLAIN.scatters(plain, variables["params"], x)[1]) >= 3


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


def test_momentum_and_weight_decay_leave_the_bias_alone(tiny):
    """Under SGD with momentum and weight decay the bias moves by its own
    step and nothing else, two steps running; the optimizer's state holds no
    leaf for it."""
    model, variables, tokens = tiny
    tx = sgd(lr=0.1, momentum=0.9, weight_decay=0.1)
    state = _state(variables, tx)
    n_params = len(jax.tree.leaves(state.params))
    assert len(jax.tree.leaves(state.opt_state.momentum)) == n_params
    step = ep.make_ep_train_step(model.clone(ep_axis="data"), tx,
                                 _one_device_mesh(), state, donate=False)
    for _ in range(2):
        _, stats = model.apply(lm_variables(state.params, state.batch_stats),
                               tokens)
        want = jax.tree.map(
            lambda b, c: update_expert_bias(b, c, 0.001), state.batch_stats,
            stats[EXPERT_COUNTS])
        state, _ = step(state, tokens)
        for a, b in zip(jax.tree.leaves(state.batch_stats),
                        jax.tree.leaves(want)):
            np.testing.assert_array_equal(a, b)


def test_an_overloaded_expert_loses_selections_at_fixed_weights():
    """A layer whose router prefers expert 0 (every token carries a constant
    feature that expert 0's column weighs), weights fixed, the bias stepped
    from the layer's own counts: expert 0's share of the assignments falls,
    the busiest-over-mean with it, and its bias goes negative."""
    layer = DroplessMoE(8, 16, 8, top_k=2, gate_norm=True, score="sigmoid",
                        select_bias=True, route_scale=2.0)
    x = jax.random.normal(jax.random.key(0), (1, 256, 16)).at[..., 0].set(1.0)
    variables = dict(layer.init(jax.random.key(1), x))
    router = variables["params"]["router"]["kernel"]
    variables["params"] = {**variables["params"],
                           "router": {"kernel": router.at[0, 0].add(1.5)}}
    rate = 0.01
    loads = []
    for _ in range(40):
        _, stats = layer.apply(variables, x)
        counts = stats[EXPERT_COUNTS]["expert_bias"]
        loads.append(np.asarray(counts))
        variables[MOE_STATE] = {"expert_bias": update_expert_bias(
            variables[MOE_STATE]["expert_bias"], counts, rate)}
    loads = np.stack(loads)
    assert loads.sum(axis=1).tolist() == [512] * 40
    assert loads[0, 0] > 1.5 * 64                      # overloaded to begin with
    assert loads[-1, 0] < 0.75 * loads[0, 0]           # ... and relieved
    assert loads[-1].max() / 64 < loads[0].max() / 64
    assert float(variables[MOE_STATE]["expert_bias"][0]) < -0.1


def _trainer_cfg(tmp_path, **kw):
    base = dict(batch_size=4, lr=0.05, momentum=0.9, weight_decay=0.01,
                eval_freq=0, log_every=1, lm_seq_len=32, lm_vocab=97,
                lm_d_model=24, lm_layers=5, lm_heads=4, lm_kv_heads=2,
                lm_head_dim=8, lm_ffn_dim=16, lm_dense_layers=1,
                lm_dense_ffn_dim=40, lm_experts=8,
                lm_experts_held=4, lm_moe_top_k=3, lm_arch="trinity",
                lm_parallelism="ep", lm_attention="full",
                compute_dtype="float32", lm_corpus_tokens=20_000,
                train_dir=str(tmp_path))
    base.update(kw)
    return TrainConfig(**base)


def test_a_checkpoint_mid_run_resumes_bit_for_bit_bias_included(
        tmp_path, monkeypatch):
    """Three steps, a checkpoint, and a second trainer that resumes from it:
    the restored parameters, momentum and bias are the saved ones bit for bit
    (the bias not zero), the same batch moves both states alike, and the run
    goes on to step 6; the new counters are in the JSONL record and the
    registry; the standalone oracle reads the saved bias. (The token loader
    starts over on a resume, for every arch: the batches after it are not
    those of an uninterrupted run.)"""
    from ps_pytorch_tpu.data.text import TokenLoader
    from ps_pytorch_tpu.runtime import checkpoint as ckpt
    from ps_pytorch_tpu.runtime.lm_eval import (
        build_lm_oracle, build_lm_template,
    )
    from ps_pytorch_tpu.runtime.lm_trainer import LMTrainer

    one = jax.devices()[:1]
    monkeypatch.setattr(jax, "devices", lambda *a, **k: one)
    metrics = tmp_path / "metrics.jsonl"
    cfg = _trainer_cfg(tmp_path, max_steps=3, eval_freq=3, donate=False,
                       metrics_file=str(metrics))
    first = LMTrainer(cfg)
    first.train()
    resumed = LMTrainer(cfg.replace(max_steps=6, eval_freq=0))
    assert resumed.maybe_resume() and resumed.start_step == 3
    a, b = jax.device_get((first.state, resumed.state))
    bias = jax.tree.leaves(b.batch_stats)
    assert len(bias) == 4 and all(x.shape == (8,) for x in bias)
    assert max(float(np.abs(x).max()) for x in bias) >= 0.001
    assert jax.tree.structure(a) == jax.tree.structure(b)
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(x, y)
    tokens = jnp.asarray(first.train_loader.next_batch())
    a, _ = first.step_fn(first.state, tokens)
    b, _ = resumed.step_fn(resumed.state, tokens)
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(x, y)
    resumed.train()
    assert int(resumed.state.step) == 6

    records = [json.loads(line) for line in metrics.read_text().splitlines()]
    assert [r["step"] for r in records] == [1, 2, 3, 4, 5, 6]
    for r in records:
        assert r["moe_dropped"] == 0.0 and r["aux"] > 0
        assert 0.001 <= r["moe_bias_abs_max"] <= 0.001 * 2 * r["step"]
        assert 1.0 <= r["moe_load_all_max_over_mean"] <= 8 / 3
    assert resumed.registry.get("moe_bias_abs_max") == \
        records[-1]["moe_bias_abs_max"]
    assert resumed.registry.get("moe_load_all_max_over_mean") == \
        records[-1]["moe_load_all_max_over_mean"]

    # the checkpoint holds the bias, and the oracle outside the trainer reads it
    saved_cfg = TrainConfig.from_json(open(
        f"{ckpt.checkpoint_path(str(tmp_path), 3)}/config.json").read())
    assert (saved_cfg.lm_dense_layers, saved_cfg.lm_dense_ffn_dim) == (1, 40)
    state, _, _ = ckpt.load_checkpoint(str(tmp_path), 3,
                                       build_lm_template(saved_cfg))
    assert max(float(np.abs(x).max())
               for x in jax.tree.leaves(state.batch_stats)) > 0
    loss_fn, to_tree = build_lm_oracle(saved_cfg)
    tokens = jnp.asarray(TokenLoader(resumed.val_tokens, 4, 32, seed=0,
                                     shuffle=False).next_batch())
    with_bias = float(loss_fn(to_tree(state.params), tokens,
                              state.batch_stats))
    assert np.isfinite(with_bias)
    shifted = jax.tree.map(lambda x: x + jnp.linspace(-1, 1, 8),
                           state.batch_stats)
    assert float(loss_fn(to_tree(state.params), tokens, shifted)) != with_bias
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
        _trainer_cfg(tmp_path, **kw)


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
            lambda v: _logits(model, v, tokens))(variables).jaxpr)
        assert walked == per_token * tokens.size
    else:
        walked = count_jaxpr_flops(jax.make_jaxpr(jax.grad(
            lambda p: _logits(model, {**variables, "params": p},
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
