"""The ``olmoe`` arch (RMSNorm, RoPE, q/k norm, dropless top-k SwiGLU experts)
against its plain reference ``benchmark/reference/olmoe_1b_7b.py`` at a tiny
size, the grouped matmul against a per-group loop, the routing counters, and
GPT-2's tree and numbers against golden values taken from the parent commit."""

import hashlib
import importlib.util
import json
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from ps_pytorch_tpu.config import LM_ARCHS, TrainConfig
from ps_pytorch_tpu.models import moe as moe_mod
from ps_pytorch_tpu.models import transformer as tr_mod
from ps_pytorch_tpu.models.moe import DROPLESS_STATS, DroplessMoE, MoETransformerLM
from ps_pytorch_tpu.models.transformer import ARCHS, TransformerLM, rope
from ps_pytorch_tpu.ops.grouped_matmul import gmm
from ps_pytorch_tpu.parallel import ep
from ps_pytorch_tpu.utils.flops import count_jaxpr_flops

REPO = pathlib.Path(__file__).resolve().parent.parent


def _load(path):
    spec = importlib.util.spec_from_file_location(path.stem, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _load(REPO / "benchmark" / "reference" / "olmoe_1b_7b.py")
PLAIN = _load(REPO / "tests" / "dropless_plain.py")
PUBLISHED = json.loads(
    (REPO / "benchmark" / "configs" / "olmoe_1b_7b.json").read_text())

# The tiny preset: d=64, 4 heads of 16, 8 experts top-4 of width 32, 2 layers,
# S=32, vocab 97 — in the reference's (the published config's) keys.
TINY = dict(PUBLISHED, hidden_size=64, intermediate_size=32,
            num_attention_heads=4, num_key_value_heads=4, num_experts=8,
            num_experts_per_tok=4, num_hidden_layers=2, vocab_size=97,
            max_position_embeddings=32)
S = 32
# float32 on both sides, so only the order of reductions differs (the sorted
# grouped matmul against a dense loop over experts, flax's norm against a
# hand-written one): measured 2e-6 on logits up to 4. 1e-4 is fifty times that
# and still far under what any of the MUTANTS below changes (0.02 to 1).
LOGIT_TOL = 1e-4


def _model(cls=MoETransformerLM, **kw):
    base = dict(vocab_size=97, n_layers=2, n_heads=4, d_model=64,
                max_seq_len=S, arch="olmoe", ffn_dim=32)
    if cls is MoETransformerLM:
        base.update(n_experts=8, top_k=4)
    base.update(kw)
    return cls(**base)


@pytest.fixture(scope="module")
def tiny():
    """(model, variables, tokens): seeded weights, every norm scale moved off
    1 so that a norm left out or applied in the wrong place shows."""
    model = _model()
    tokens = jnp.asarray(
        np.random.default_rng(1).integers(0, 97, (2, S)), jnp.int32)
    params = model.init(jax.random.key(0), tokens)["params"]
    leaves, tree = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.key(5), len(leaves))
    params = jax.tree.unflatten(tree, [
        a + 0.2 * jax.random.normal(k, a.shape) if a.ndim == 1 else a
        for a, k in zip(leaves, keys)])
    return model, {"params": params}, tokens


def _logits(model, variables, tokens):
    return model.apply(variables, tokens)[0]


def test_logits_agree_with_the_reference(tiny):
    model, variables, tokens = tiny
    got, stats = model.apply(variables, tokens)
    want = REF.forward(variables, tokens, TINY)
    assert got.shape == want.shape == (2, S, 97)
    assert float(jnp.abs(got - want).max()) < LOGIT_TOL
    assert set(stats) == set(DROPLESS_STATS)
    assert float(stats["moe_dropped"]) == 0.0


def _interleaved_rope(x, positions, theta):
    """RoPE with the GPT-J pairing (feature 2i with 2i+1): the wrong one."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[:, None] * inv[None]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     axis=-1).reshape(x.shape)


def _gmm_dropping_one_row(lhs, rhs, group_sizes):
    return gmm(lhs, rhs, group_sizes).at[-1].set(0.0)


MUTANTS = {
    # name -> (what to patch on the program's side, reference config)
    "renormalised_gate": (None, dict(TINY, norm_topk_prob=True)),
    "missing_qk_norm": ((tr_mod.ARCHS, "olmoe",
                         ARCHS["olmoe"]._replace(qk_norm=False)), TINY),
    "interleaved_rope": ((tr_mod, "rope", _interleaved_rope), TINY),
    "dropped_assignment": ((moe_mod, "gmm", _gmm_dropping_one_row), TINY),
}


@pytest.mark.parametrize("name", sorted(MUTANTS))
def test_the_logit_tolerance_catches(tiny, monkeypatch, name):
    """Each mistake the tolerance has to catch moves the logits by far more
    than LOGIT_TOL."""
    model, variables, tokens = tiny
    patch, config = MUTANTS[name]
    if patch is not None:
        target, attr, value = patch
        if isinstance(target, dict):
            monkeypatch.setitem(target, attr, value)
        else:
            monkeypatch.setattr(target, attr, value)
    got = _logits(model, variables, tokens)
    want = REF.forward(variables, tokens, config)
    assert float(jnp.abs(got - want).max()) > 50 * LOGIT_TOL


def test_the_ep_step_descends_the_reference_loss(tiny):
    """One plain-SGD step of ``parallel/ep.py``'s step on one device moves
    every leaf by ``lr * jax.grad(reference.loss)``: cross-entropy, the
    all-choices load-balance term and the z-loss, with the coefficients the
    configuration states, and the gradient through the sort, the grouped
    matmuls and the scatter-add. Tolerance: float32 both sides, gradients up
    to about 1; 2e-5 absolute is reduction order."""
    from jax.sharding import Mesh

    from ps_pytorch_tpu.parallel.dp import TrainState

    model, variables, tokens = tiny
    assert ARCHS["olmoe"].aux_coef == PUBLISHED["load_balance_coef_as_run"]
    assert ARCHS["olmoe"].z_loss_coef == PUBLISHED["z_loss_coef_as_run"]
    lr = 0.5
    tx = optax.sgd(lr)
    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("data", "model"))
    state = TrainState(step=jnp.zeros((), jnp.int32),
                       params=variables["params"],
                       opt_state=tx.init(variables["params"]), batch_stats={})
    step = ep.make_ep_train_step(model.clone(ep_axis="data"), tx, mesh, state,
                                 donate=False)
    new_state, m = step(state, tokens)
    want = jax.grad(lambda p: REF.loss({"params": p}, tokens, TINY))(
        variables["params"])
    got = jax.tree.map(lambda a, b: (a - b) / lr, state.params,
                       new_state.params)
    flat_got = jax.tree_util.tree_flatten_with_path(got)[0]
    flat_want = jax.tree.leaves(want)
    assert len(flat_got) == len(flat_want)
    for (path, g), w in zip(flat_got, flat_want):
        assert float(jnp.abs(w).max()) > 0, path
        np.testing.assert_allclose(g, w, atol=2e-5, rtol=1e-3,
                                   err_msg=jax.tree_util.keystr(path))
    ce, lb, z = REF.loss_terms(variables, tokens, TINY)
    np.testing.assert_allclose(float(m["loss"]), float(ce), rtol=1e-5)
    np.testing.assert_allclose(float(m["aux"]), float(lb), rtol=1e-5)
    np.testing.assert_allclose(float(m["z_loss"]), float(z), rtol=1e-5)
    assert float(m["moe_dropped"]) == 0.0


def test_dropless_across_chips_is_refused_with_one_message():
    from ps_pytorch_tpu.parallel.mesh import make_mesh
    model = _model(ep_axis="data")
    with pytest.raises(NotImplementedError,
                       match="dropless routing across chips: not built"):
        ep.make_ep_train_step(model, optax.sgd(0.1), make_mesh(data=2),
                              state=None)


GROUPS = {
    "ragged": [5, 0, 19, 3, 0, 13],
    "empty_first_and_last": [0, 17, 23, 0],
    "one_group_takes_every_row": [0, 0, 40, 0],
    "rows_past_the_groups": [7, 9, 0, 8],          # 24 of 40 rows covered
}


@pytest.mark.parametrize("name", sorted(GROUPS))
def test_gmm_and_both_gradients_against_a_loop(name):
    sizes = np.asarray(GROUPS[name], np.int32)
    m, k, n, g = 40, 16, 24, len(sizes)
    ks = jax.random.split(jax.random.key(3), 3)
    lhs = jax.random.normal(ks[0], (m, k))
    rhs = jax.random.normal(ks[1], (g, k, n))
    cot = jax.random.normal(ks[2], (m, n))
    off = np.concatenate([[0], np.cumsum(sizes)])

    def loop(lhs, rhs):
        out = jnp.zeros((m, n))
        for e in range(g):
            out = out.at[off[e]:off[e + 1]].set(
                lhs[off[e]:off[e + 1]] @ rhs[e])
        return out

    got, vjp = jax.vjp(lambda a, b: gmm(a, b, jnp.asarray(sizes)), lhs, rhs)
    want, vjp_want = jax.vjp(loop, lhs, rhs)
    np.testing.assert_allclose(got, want, atol=1e-5)
    assert not np.asarray(got[off[-1]:]).any()
    for a, b in zip(vjp(cot), vjp_want(cot)):
        np.testing.assert_allclose(a, b, atol=1e-5)


@pytest.mark.parametrize("k_tiles", [1, 2])
@pytest.mark.parametrize("name", sorted(GROUPS))
def test_gmm_bfloat16_rows_on_float32_weights(name, k_tiles, monkeypatch):
    """What a bfloat16 model hands the kernels: bfloat16 rows, the float32
    expert weights as they are (a tile is cast in VMEM). Forward and the
    gradient to the rows come back bfloat16, within its rounding of a float32
    ``einsum`` over the rounded operands; the gradient to the weights comes
    back FLOAT32 from the float32 accumulator, unrounded."""
    from ps_pytorch_tpu.ops import grouped_matmul
    sizes = np.asarray(GROUPS[name], np.int32)
    m, k, n, g = 40, 16, 24, len(sizes)
    if k_tiles > 1:     # several row, K and N tiles: the cast tiles by K tile
        monkeypatch.setitem(grouped_matmul._TILES, 2, (8, 128, 128))
        k, n = 128 * k_tiles, 256
    ks = jax.random.split(jax.random.key(5), 3)
    lhs = jax.random.normal(ks[0], (m, k)).astype(jnp.bfloat16)
    rhs = jax.random.normal(ks[1], (g, k, n))
    cot = jax.random.normal(ks[2], (m, n)).astype(jnp.bfloat16)
    group = np.repeat(np.arange(g + 1), np.append(sizes, m - sizes.sum()))
    onehot = jnp.asarray(group[:, None] == np.arange(g)[None], jnp.float32)
    rounded = rhs.astype(jnp.bfloat16).astype(jnp.float32)

    def reference(lhs, rhs):    # rows past the groups match no group: zeros
        return jnp.einsum("mk,mg,gkn->mn", lhs.astype(jnp.float32), onehot,
                          rhs, precision=jax.lax.Precision.HIGHEST)

    got, vjp = jax.vjp(lambda a, b: gmm(a, b, jnp.asarray(sizes)), lhs, rhs)
    want, vjp_want = jax.vjp(reference, lhs, rounded)
    assert got.dtype == jnp.bfloat16
    np.testing.assert_allclose(got.astype(jnp.float32), want,
                               atol=0.06 * k_tiles, rtol=2 ** -7)
    dlhs, drhs = vjp(cot)
    dlhs_want, drhs_want = vjp_want(cot.astype(jnp.float32))
    assert dlhs.dtype == jnp.bfloat16 and drhs.dtype == jnp.float32
    np.testing.assert_allclose(dlhs.astype(jnp.float32),
                               dlhs_want.astype(jnp.float32),
                               atol=0.06 * k_tiles, rtol=2 ** -7)
    # float32 out of the accumulator: products of bfloat16 values are exact
    # in float32, so only the order of the sum differs from the reference
    np.testing.assert_allclose(drhs, drhs_want, atol=1e-5, rtol=1e-6)
    assert np.abs(np.asarray(drhs) - np.asarray(
        drhs.astype(jnp.bfloat16).astype(jnp.float32))).max() > 0


def test_a_rigged_router_reads_load_two_and_drops_nothing():
    """Every token picks the same 4 of 8 experts: the busiest expert holds
    T assignments against a mean of T*4/8, so max over mean is 2."""
    layer = DroplessMoE(n_experts=8, d_model=16, d_hidden=8, top_k=4)
    x = 0.01 * jax.random.normal(jax.random.key(0), (2, 12, 16))
    x = x.at[..., 0].set(1.0)
    params = layer.init(jax.random.key(1), x)["params"]
    rig = jnp.zeros((16, 8)).at[0].set(
        jnp.array([10.0, 9.0, 8.0, 7.0, 0, 0, 0, 0]))
    params = {**params, "router": {"kernel": rig}}
    y, stats = layer.apply({"params": params}, x)
    assert float(stats["moe_dropped"]) == 0.0
    assert float(stats["expert_load_max_over_mean"]) == 2.0
    assert np.isfinite(np.asarray(y)).all() and float(jnp.abs(y).max()) > 0


PLAIN_CASES = {
    "every_expert_held": dict(top_k=4),
    "an_empty_group": dict(top_k=4, rig_out=5),
    "an_empty_first_group": dict(top_k=4, rig_out=0),
    "k_1": dict(top_k=1),
    "k_1_relu_renormalised": dict(top_k=1, act="relu", gate_norm=True),
    "k_8_of_8": dict(top_k=8),
}


@pytest.mark.parametrize("name", sorted(PLAIN_CASES))
def test_the_layer_is_the_plain_take_and_scatter_add(name):
    """Every expert held: output, the gradients in the tokens, the router
    (through the gates) and the three expert weights are those of the form
    the layer had before PR 38 (``tests/dropless_plain.py``: take, grouped
    matmuls, scatter-add, JAX's own derivative), the counts exactly."""
    layer, variables, x = PLAIN.tiny_case(**PLAIN_CASES[name])
    stats = PLAIN.assert_the_plain_form(layer, variables, x)
    assert float(stats["moe_held_share"]) == 1.0
    rig_out = PLAIN_CASES[name].get("rig_out")
    if rig_out is not None:
        idx = jax.lax.top_k(x.reshape(-1, 16).astype(jnp.float32)
                            @ variables["params"]["router"]["kernel"], 4)[1]
        assert rig_out not in np.asarray(idx)
        assert float(stats["expert_load_max_over_mean"]) >= 8 / 7


@pytest.mark.parametrize("name", ["every_expert_held", "k_1"])
def test_the_compiled_layer_holds_no_scatter(name):
    """Forward and backward of the layer, as lowered and as the CPU's
    compiler leaves them: no ``scatter`` op. The plain form's counts say the
    search finds one where it is (its combine, the transposes of its take, of
    ``top_k`` and of the gates' gather, its two counts)."""
    layer, variables, x = PLAIN.tiny_case(**PLAIN_CASES[name])
    step, plain = PLAIN.steps(layer, variables)
    assert PLAIN.scatters(step, variables["params"], x) == (0, [])
    lowered, compiled = PLAIN.scatters(plain, variables["params"], x)
    assert lowered >= 5 and len(compiled) >= 3


def test_param_count_published_and_tiny(tiny):
    _, variables, _ = tiny
    assert REF.param_count(dict(PUBLISHED, num_hidden_layers=16)) \
        == PUBLISHED["parameters_published"] == 6_919_161_856
    assert REF.param_count(TINY) == sum(
        a.size for a in jax.tree.leaves(variables["params"]))


@pytest.mark.parametrize("what", ["forward", "forward_and_backward"])
def test_closed_form_flops_against_the_jaxpr_walk(tiny, what):
    """The closed form counts every matmul of the forward (attention dense
    S x S) and nothing else, so the walk of the forward agrees exactly. For
    training it charges 3x the forward; the walk finds less by what the
    closed form does not leave out but autodiff never computes: the gradient
    to the token ids (the first layer's q, k, v and router have no input
    gradient to pass on)."""
    model, variables, tokens = tiny
    per_token = REF.train_flops_per_sample(TINY, seq_len=S)
    if what == "forward":
        walked = count_jaxpr_flops(jax.make_jaxpr(
            lambda v: _logits(model, v, tokens))(variables).jaxpr)
        assert walked == per_token // 3 * tokens.size
    else:
        walked = count_jaxpr_flops(jax.make_jaxpr(jax.grad(
            lambda v: _logits(model, v, tokens).sum()))(variables).jaxpr)
        assert 0.9 * per_token * tokens.size < walked \
            <= per_token * tokens.size


def test_rope_depends_on_relative_position_only():
    q = jax.random.normal(jax.random.key(0), (1, 2, 1, 16))
    k = jax.random.normal(jax.random.key(1), (1, 2, 1, 16))
    dots = [float(jnp.sum(rope(q, jnp.array([a]), 10000.0)
                          * rope(k, jnp.array([b]), 10000.0)))
            for a, b in ((5, 2), (13, 10), (3, 0))]
    np.testing.assert_allclose(dots, dots[0], rtol=1e-5)
    assert abs(dots[0] - float(jnp.sum(q * k))) > 1e-3


def test_both_lm_classes_reach_the_shared_block_pieces():
    """RMSNorm, RoPE and the q/k norm are written once, in the attention
    half both classes call: the dense class under the olmoe arch has the
    same attention leaves as the MoE class, no position table, and its
    logits move when the positions do."""
    tokens = jnp.arange(8, dtype=jnp.int32)[None] * 7 % 97
    dense = _model(TransformerLM)
    p_dense = dense.init(jax.random.key(0), tokens)["params"]
    p_moe = _model().init(jax.random.key(0), tokens)["params"]
    attn = {"RMSNorm_0", "RMSNorm_1", "Dense_0", "Dense_1", "Dense_2",
            "Dense_3", "q_norm", "k_norm"}
    assert attn <= set(p_dense["block_0"]) and attn <= set(p_moe["block_0"])
    assert "pos_embed" not in p_dense and "pos_embed" not in p_moe
    assert set(p_dense["ln_f"]) == {"scale"}
    assert p_dense["block_0"]["Dense_4"]["kernel"].shape == (64, 32)
    a = dense.apply({"params": p_dense}, tokens)
    b = dense.apply({"params": p_dense}, tokens, positions=jnp.arange(8) * 3)
    assert float(jnp.abs(a - b).max()) > 1e-4


# Taken from the parent commit (7e40887) with the script in this test's
# docstring; the bytes of the logits on this container's CPU backend.
GOLDEN = {
    "dense": ("3be40a762d856dc549057dc3ed6e97db992f221f77d36cc1589463d83b3f14e4",
              29, "d782c65fce5679c9300791f7642e74c11adf5d22dbe02839a0b65168516352d7",
              "0x1.25aff00000000p+12"),
    "moe": ("f1122c029a32e174e9d178ecc63412746fbffdc1727dbbbe83b421cc37c5f1de",
            31, "77787e920386921c41f0237880906242845f371b6328107671fc794f0c92ee6d",
            "0x1.3427000000000p+12"),
}


@pytest.mark.parametrize("family", sorted(GOLDEN))
def test_gpt2_tree_and_logits_are_the_parents(family):
    """``arch="gpt2"`` (the default) keeps the parent's parameter tree
    (names, order, shapes: sha256 of the sorted ``keystr(path) + shape``
    lines) and its logits bit for bit (sha256 of the float32 bytes; the sum
    of magnitudes beside it says by how much, should the bytes ever differ
    on another CPU): ``init(key(0))`` on tokens ``default_rng(7).integers(0,
    97, (2, 32))``, vocab 97, 2 layers, 4 heads, d=64, S=32; the MoE family
    with 8 experts top-2."""
    kw = dict(vocab_size=97, n_layers=2, n_heads=4, d_model=64, max_seq_len=32)
    model = TransformerLM(**kw) if family == "dense" else \
        MoETransformerLM(n_experts=8, top_k=2, **kw)
    tokens = jnp.asarray(
        np.random.default_rng(7).integers(0, 97, (2, 32)), jnp.int32)
    params = model.init(jax.random.key(0), tokens)["params"]
    out = model.apply({"params": params}, tokens)
    logits = np.asarray(out[0] if family == "moe" else out, np.float32)
    paths = sorted(jax.tree_util.keystr(p) + str(tuple(a.shape)) for p, a in
                   jax.tree_util.tree_flatten_with_path(params)[0])
    tree_sha, n_leaves, logits_sha, abs_sum = GOLDEN[family]
    assert len(paths) == n_leaves
    assert hashlib.sha256("\n".join(paths).encode()).hexdigest() == tree_sha
    assert float(np.abs(logits).sum()) == pytest.approx(
        float.fromhex(abs_sum), rel=1e-6)
    assert hashlib.sha256(logits.tobytes()).hexdigest() == logits_sha


CONFIG_CASES = {
    "olmoe_takes_any_k_up_to_the_experts": (
        dict(lm_arch="olmoe", lm_parallelism="ep", lm_experts=64,
             lm_moe_top_k=8, lm_ffn_dim=1024), None),
    "olmoe_k_above_the_experts": (
        dict(lm_arch="olmoe", lm_parallelism="ep", lm_experts=4,
             lm_moe_top_k=5), "lm_moe_top_k"),
    "olmoe_needs_ep": (dict(lm_arch="olmoe"), "lm_parallelism=ep"),
    "capacity_path_keeps_k_1_or_2": (
        dict(lm_parallelism="ep", lm_moe_top_k=3), "capacity"),
    "unknown_arch": (dict(lm_arch="llama"), "unknown lm_arch"),
    "negative_ffn_dim": (dict(lm_ffn_dim=-1), "lm_ffn_dim"),
}


@pytest.mark.parametrize("name", sorted(CONFIG_CASES))
def test_config_validation(name):
    kw, error = CONFIG_CASES[name]
    if error is None:
        cfg = TrainConfig(**kw)
        assert TrainConfig.from_json(cfg.to_json()).lm_arch == "olmoe"
    else:
        with pytest.raises(ValueError, match=error):
            TrainConfig(**kw)


def test_config_names_the_archs_the_models_have():
    assert LM_ARCHS == tuple(ARCHS)
