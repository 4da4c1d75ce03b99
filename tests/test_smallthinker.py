"""The ``smallthinker`` arch (grouped-query heads of their own size, window
layers with RoPE and global layers without position encoding, a dropless
ReLU-gated expert layer that holds a share of its experts, the router before
attention) against its plain reference
``benchmark/reference/smallthinker_21b_a3b.py`` at a tiny size; the flash
kernels under fewer key/value heads and a window against ``full_attention``;
the schedule's band against a brute-force count; the refusals; and the older
archs' trees, logits and gradients against golden values from the parent
commit."""

import hashlib
import importlib.util
import json
import pathlib
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from ps_pytorch_tpu.config import TrainConfig
from ps_pytorch_tpu.models import moe as moe_mod
from ps_pytorch_tpu.models import transformer as tr_mod
from ps_pytorch_tpu.models.moe import (
    DROPLESS_STATS, DroplessMoE, MoEBlock, MoETransformerLM,
)
from ps_pytorch_tpu.models.transformer import ARCHS, TransformerLM
from ps_pytorch_tpu.ops.flash_attention import flash_attention, flash_schedule
from ps_pytorch_tpu.ops.grouped_matmul import gmm
from ps_pytorch_tpu.parallel import ep
from ps_pytorch_tpu.parallel.ring import full_attention
from ps_pytorch_tpu.utils.flops import count_jaxpr_flops

REPO = pathlib.Path(__file__).resolve().parent.parent


def _load(path):
    spec = importlib.util.spec_from_file_location(path.stem, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _load(REPO / "benchmark" / "reference" / "smallthinker_21b_a3b.py")
PLAIN = _load(REPO / "tests" / "dropless_plain.py")
PUBLISHED = json.loads((REPO / "benchmark" / "configs"
                        / "smallthinker_21b_a3b.json").read_text())

# The tiny preset keeps every inequality of the real one: d=24 against 4
# query heads of 8 (heads x head_dim = 32 != d), 2 key/value heads, one period
# of 4 layers (global, window, window, window), a window of 8 keys at S=32,
# 8 experts top-3 of width 16 of which experts 4..7 are held (share 1 of 2),
# vocab 97 — in the reference's (the published config's) keys.
S, WINDOW = 32, 8
TINY = dict(PUBLISHED, hidden_size=24, head_dim=8, num_attention_heads=4,
            num_key_value_heads=2, num_hidden_layers=4, sliding_window_size=WINDOW,
            moe_ffn_hidden_size=16, moe_num_active_primary_experts=3,
            moe_num_primary_experts=4, moe_num_primary_experts_published=8,
            experts_held=4, experts_share=1, vocab_size=97,
            max_position_embeddings=S)
UNCUT = dict(TINY, moe_num_primary_experts=8, experts_held=8, experts_share=0)
# float32 on both sides, so only the order of reductions differs (the sorted
# grouped matmul against a dense loop over experts, flax's norm against a
# hand-written one): measured 3e-6 on logits up to 6. 1e-4 is thirty times
# that and far under what any of the MUTANTS below changes.
LOGIT_TOL = 1e-4


@pytest.fixture(autouse=True)
def tiny_window(monkeypatch):
    """The window is the arch row's, not a flag: the tiny size takes a row
    with a window that closes at S=32."""
    monkeypatch.setitem(tr_mod.ARCHS, "smallthinker",
                        ARCHS["smallthinker"]._replace(window=WINDOW))


def _model(**kw):
    base = dict(vocab_size=97, n_layers=4, n_heads=4, kv_heads=2, head_dim=8,
                d_model=24, max_seq_len=S, arch="smallthinker", ffn_dim=16,
                n_experts=8, top_k=3, experts_held=4, experts_share=1)
    base.update(kw)
    return MoETransformerLM(**base)


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


@pytest.mark.parametrize("attention", ["full", "flash"])
def test_logits_agree_with_the_reference(tiny, attention):
    model, variables, tokens = tiny
    got, stats = model.clone(attention_impl=attention).apply(variables, tokens)
    want = REF.forward(variables, tokens, TINY)
    assert got.shape == want.shape == (2, S, 97)
    assert float(jnp.abs(got - want).max()) < LOGIT_TOL
    assert set(stats) == set(DROPLESS_STATS)
    assert float(stats["moe_dropped"]) == 0.0
    assert 0.3 < float(stats["moe_held_share"]) < 0.7
    p = variables["params"]["block_1"]
    assert p["Dense_0"]["kernel"].shape == (24, 32)      # q: heads x head_dim
    assert p["Dense_1"]["kernel"].shape == (24, 16)      # k: kv heads x head_dim
    assert p["Dense_3"]["kernel"].shape == (32, 24)
    assert p["moe"]["router"]["kernel"].shape == (24, 8)  # all 8 outputs
    assert p["moe"]["experts_gate"].shape == (4, 24, 16)  # 4 held
    assert "pos_embed" not in variables["params"]


def _tiled_kv_attention(q, k, v, **kw):
    """Query head h reading key/value head h % kv_heads: the wrong one."""
    group = q.shape[1] // k.shape[1]
    return full_attention(q, jnp.tile(k, (1, group, 1, 1)),
                          jnp.tile(v, (1, group, 1, 1)), **kw)


def _gmm_skipping_the_first_expert(lhs, rhs, group_sizes):
    out = gmm(lhs, rhs, group_sizes)
    rows = jnp.arange(out.shape[0])[:, None]
    return jnp.where(rows < group_sizes[0], 0.0, out).astype(out.dtype)


def _row(**kw):
    return (tr_mod.ARCHS, "smallthinker",
            ARCHS["smallthinker"]._replace(window=WINDOW, **kw))


def _fp8(variables):
    return jax.tree.map(
        lambda a: a.astype(jnp.float8_e4m3fn).astype(jnp.float32), variables)


MUTANTS = {
    # name -> what to patch on the program's side: (target, name, value)
    "window_ignored": _row(window_layers=()),
    "rope_on_the_global_layer": _row(rope_layers=()),
    "rope_missing_on_a_window_layer": _row(rope_layers=(0, 0, 1, 1)),
    "kv_head_h_mod_4": (tr_mod, "full_attention", _tiled_kv_attention),
    "silu_for_relu": _row(expert_act="silu"),
    "gates_not_renormalised": _row(gate_norm=False),
    "router_after_attention": _row(early_router=False),
    "one_held_expert_skipped": (moe_mod, "gmm",
                                _gmm_skipping_the_first_expert),
    "parameters_in_float8": None,       # the reference's side, see below
}


@pytest.mark.parametrize("name", sorted(MUTANTS))
def test_the_logit_tolerance_catches(tiny, monkeypatch, name):
    """Each mistake the tolerance has to catch moves the logits by far more
    than LOGIT_TOL; so does the nearest precision below the one the
    configuration states (every parameter rounded to float8_e4m3fn)."""
    model, variables, tokens = tiny
    patch = MUTANTS[name]
    if patch is not None:
        target, attr, value = patch
        if isinstance(target, dict):
            monkeypatch.setitem(target, attr, value)
        else:
            monkeypatch.setattr(target, attr, value)
    got = _logits(model, variables, tokens)
    want = REF.forward(_fp8(variables) if patch is None else variables,
                       tokens, TINY)
    assert float(jnp.abs(got - want).max()) > 50 * LOGIT_TOL


def _block_one(variables, held, share):
    """block_1's parameters (a window layer) with the experts of one share."""
    bp = dict(variables["params"]["block_1"])
    return bp, {k: v[share * held:(share + 1) * held] if k.startswith(
        "experts_") else v for k, v in bp["moe"].items()}


@pytest.mark.parametrize("side", ["program", "reference"])
def test_the_shares_add_up_to_the_uncut_layer(side):
    """One layer at the tiny size, all 8 experts' weights seeded: the four
    shares' parts of the result (2 of 8 experts held, share 0..3) add up to
    what the uncut reference layer adds to the residual stream. The uncut
    ``y`` is the reference layer's output less the same layer's with its down
    projections zeroed (``x1``, the stream after attention)."""
    model = _model(experts_held=0, experts_share=0)
    tokens = jnp.zeros((1, S), jnp.int32)
    variables = model.init(jax.random.key(3), tokens)
    x = jax.random.normal(jax.random.key(4), (S, 24))
    bp, _ = _block_one(variables, 8, 0)
    zeroed = {**bp, "moe": {**bp["moe"], "experts_down":
                            jnp.zeros_like(bp["moe"]["experts_down"])}}
    x1 = REF._layer(zeroed, x, UNCUT, 1)[0]
    y_uncut = REF._layer(bp, x, UNCUT, 1)[0] - x1
    scale = float(jnp.abs(y_uncut).max())   # small: the arch's down-projection init
    assert scale > 5e-3
    total = jnp.zeros_like(y_uncut)
    for share in range(4):
        bp_s = {**bp, "moe": _block_one(variables, 2, share)[1]}
        if side == "program":
            block = MoEBlock(4, 24, 8, top_k=3, arch="smallthinker",
                             ffn_dim=16, layer=1, kv_heads=2, head_dim=8,
                             experts_held=2, experts_share=share)
            out, stats = block.apply({"params": bp_s}, x[None])
            out = out[0]
            assert float(stats["moe_dropped"]) == 0.0
        else:
            out = REF._layer(bp_s, x, dict(
                UNCUT, moe_num_primary_experts=2, experts_held=2,
                experts_share=share), 1)[0]
        total = total + (out - x1)
    # float32 sums of a stream of size 4: 5e-6 absolute is their rounding,
    # and a thousandth of what one share adds
    np.testing.assert_allclose(total, y_uncut, atol=5e-6)
    assert 5e-6 < 1e-3 * scale


@pytest.mark.parametrize("slack,tile", [(1.5, 512), (0.5, 8), (0.05, 8)])
def test_a_share_that_draws_more_than_its_rows_drops_nothing(
        monkeypatch, slack, tile):
    """The held experts' part runs over a static number of sorted rows; what
    the block draws beyond them goes through the overflow path, forward and
    backward: same output, same gradients, nothing dropped."""
    layer = DroplessMoE(n_experts=8, d_model=16, d_hidden=8, top_k=3,
                        act="relu", gate_norm=True, n_held=4, share=1)
    x = jax.random.normal(jax.random.key(0), (2, 16, 16))
    params = layer.init(jax.random.key(1), x)["params"]

    def run(params, x):
        y, stats = layer.apply({"params": params}, x)
        return jnp.sum(y ** 2), (y, stats)

    (_, (want, stats)), want_g = jax.value_and_grad(
        run, argnums=(0, 1), has_aux=True)(params, x)
    monkeypatch.setattr(moe_mod, "HELD_ROWS_SLACK", slack)
    monkeypatch.setattr(moe_mod, "HELD_ROWS_TILE", tile)
    (_, (got, got_stats)), got_g = jax.value_and_grad(
        run, argnums=(0, 1), has_aux=True)(params, x)
    rows = -(-int(slack * 96 * 4 / 8) // tile) * tile
    held = float(stats["moe_held_share"]) * 96
    assert (rows < held) == (slack < 1), (rows, held)  # the smaller bounds overflow
    np.testing.assert_allclose(got, want, atol=1e-6)
    for a, b in zip(jax.tree.leaves(got_g), jax.tree.leaves(want_g)):
        np.testing.assert_allclose(a, b, atol=1e-5)
    assert float(got_stats["moe_dropped"]) == 0.0
    assert got_stats["moe_held_share"] == stats["moe_held_share"]


def _held_case(monkeypatch, slack, **kw):
    """``tests/dropless_plain.py:tiny_case`` with SmallThinker's row holding
    experts 4..7 of 8; the main part sized for ``slack`` times the balanced
    share in tiles of 8 rows (the 512 that ship are more than all 96
    assignments)."""
    monkeypatch.setattr(moe_mod, "HELD_ROWS_SLACK", slack)
    monkeypatch.setattr(moe_mod, "HELD_ROWS_TILE", 8)
    return PLAIN.tiny_case(**{"top_k": 3, "act": "relu", "gate_norm": True,
                              "n_held": 4, "share": 1, **kw})


# name: (slack, whether the overflow part runs, the layer's other fields)
HELD_PLAIN_CASES = {
    "overflow_not_taken": (1.5, False, {}),
    "overflow_taken": (0.5, True, {}),
    "overflow_takes_nearly_all": (0.05, True, {}),
    "an_empty_held_group": (1.5, False, dict(rig_out=5)),
    "an_empty_held_group_overflow_taken": (0.5, True, dict(rig_out=6)),
    "k_1": (1.5, False, dict(top_k=1)),
    "k_1_overflow_taken": (0.5, True, dict(top_k=1)),
}


@pytest.mark.parametrize("name", sorted(HELD_PLAIN_CASES))
def test_a_share_is_the_plain_take_and_scatter_add(monkeypatch, name):
    """A held share, with and without its overflow part run: output, the
    gradients in the tokens, the router and the held weights are those of the
    form the layer had before PR 38 in one part over all sorted rows
    (``tests/dropless_plain.py``), the counts exactly."""
    slack, taken, kw = HELD_PLAIN_CASES[name]
    layer, variables, x = _held_case(monkeypatch, slack, **kw)
    stats = PLAIN.assert_the_plain_form(layer, variables, x)
    held, rows = PLAIN.held_and_main_rows(layer, stats, slack)
    assert 0 < held < 32 * layer.top_k and (held > rows) == taken, (held, rows)
    assert float(stats["moe_dropped"]) == 0.0


@pytest.mark.parametrize("slack", [1.5, 0.5])
def test_a_compiled_share_scatters_its_rows_and_nothing_else(
        monkeypatch, slack):
    """Forward and backward of a held share as the CPU's compiler leaves
    them: the scatters left are the three of a part (the combine, and the
    transposes of the rows' take and of the gates' take: a part covers fewer
    rows than there are assignments, where the gathers of PR 38 measured
    slower; PERF.md, Findings PR 38), once in the main part and once under
    the overflow ``cond``, all under ``moe_dispatch``. The counts and the
    gates' way back to the scores (``moe_route``) hold none, where the plain
    form has three more."""
    layer, variables, x = _held_case(monkeypatch, slack)
    step, plain = PLAIN.steps(layer, variables)
    _, names = PLAIN.scatters(step, variables["params"], x)
    assert len(names) == 6
    assert all("moe_dispatch" in n and "moe_route" not in n for n in names)
    assert len(PLAIN.scatters(plain, variables["params"], x)[1]) == 6


def test_the_ep_step_descends_the_reference_loss(tiny):
    """One plain-SGD step of ``parallel/ep.py``'s step on one device moves
    every leaf by ``lr * jax.grad(reference.loss)``: cross-entropy and the
    load-balance term over all 8 router outputs with the coefficient the
    configuration states, the gradient through the early router, the
    renormalised gates, the sort, the grouped matmuls over the held experts
    and the scatter-add, and through both kinds of attention layer.
    Tolerance: float32 both sides, gradients up to about 1; 2e-5 absolute is
    reduction order."""
    from jax.sharding import Mesh

    from ps_pytorch_tpu.parallel.dp import TrainState

    model, variables, tokens = tiny
    assert ARCHS["smallthinker"].aux_coef == PUBLISHED["load_balance_coef_as_run"]
    assert ARCHS["smallthinker"].z_loss_coef \
        == PUBLISHED["z_loss_coef_as_run"] == 0.0
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
    assert set(m) == {"loss", *DROPLESS_STATS}


def test_a_share_across_chips_is_still_refused():
    from ps_pytorch_tpu.parallel.mesh import make_mesh
    with pytest.raises(NotImplementedError,
                       match="dropless routing across chips: not built"):
        ep.make_ep_train_step(_model(ep_axis="data"), optax.sgd(0.1),
                              make_mesh(data=2), state=None)


# (b, heads, kv_heads, s, d, window, block kwargs): fewer key/value heads and
# a window that is, and is not, a multiple of the tile; S below the window; a
# grid that keeps a kv axis so that whole steps lie outside the band.
FLASH_CASES = {
    "window_of_3_tiles": (1, 4, 2, 256, 64, 96,
                          dict(block_q=32, block_kv=32, block_kv_major=64)),
    "window_off_the_tile": (2, 6, 2, 128, 64, 40,
                            dict(block_q=32, block_kv=16)),
    "window_under_a_tile": (1, 8, 2, 128, 64, 17,
                            dict(block_q=64, block_kv=32, block_kv_major=64)),
    "s_below_the_window": (1, 4, 2, 64, 64, 100, {}),
    "grouped_query_alone": (1, 6, 3, 128, 64, None,
                            dict(block_q=32, block_kv=32)),
    "one_kv_head_default_schedule": (1, 4, 1, 2048, 64, 300, {}),
    "window_alone": (1, 2, 2, 256, 64, 5, dict(block_q=64, block_kv=128)),
}


def _flash_case(name):
    b, h, h_kv, s, d, window, kw = FLASH_CASES[name]
    ks = jax.random.split(jax.random.key(3), 4)
    q = jax.random.normal(ks[0], (b, h, s, d))
    k = jax.random.normal(ks[1], (b, h_kv, s, d))
    v = jax.random.normal(ks[2], (b, h_kv, s, d))
    return q, k, v, jax.random.normal(ks[3], q.shape), window, kw


@pytest.mark.parametrize("name", sorted(FLASH_CASES))
def test_flash_with_fewer_kv_heads_and_a_window_against_full(name):
    """Output and all three gradients; dK and dV come back at the key/value
    heads' shape, summed over each head's group of query heads."""
    q, k, v, w, window, kw = _flash_case(name)
    flash = lambda q, k, v: flash_attention(q, k, v, causal=True,
                                            window=window, **kw)
    full = lambda q, k, v: full_attention(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(flash(q, k, v), full(q, k, v), rtol=2e-5,
                               atol=2e-5)
    got = jax.grad(lambda *a: jnp.sum(flash(*a) * w), argnums=(0, 1, 2))(
        q, k, v)
    want = jax.grad(lambda *a: jnp.sum(full(*a) * w), argnums=(0, 1, 2))(
        q, k, v)
    for a, b, leaf in zip(got, want, "qkv"):
        assert a.shape == b.shape
        np.testing.assert_allclose(a, b, rtol=5e-4, atol=5e-4,
                                   err_msg=f"{name} d{leaf}")


def test_flash_bfloat16_grouped_window_close():
    q, k, v, w, window, kw = _flash_case("window_of_3_tiles")
    qb, kb, vb = (t.astype(jnp.bfloat16) for t in (q, k, v))
    f32 = tuple(t.astype(jnp.float32) for t in (qb, kb, vb))
    loss = lambda fn: lambda q, k, v: jnp.sum(
        fn(q, k, v).astype(jnp.float32) * w)
    got = jax.grad(loss(lambda q, k, v: flash_attention(
        q, k, v, causal=True, window=window, **kw)), argnums=(0, 1, 2))(
        qb, kb, vb)
    want = jax.grad(loss(lambda q, k, v: full_attention(
        q, k, v, causal=True, window=window)), argnums=(0, 1, 2))(*f32)
    for a, b, leaf in zip(got, want, "qkv"):
        assert a.dtype == jnp.bfloat16 and a.shape == b.shape
        np.testing.assert_allclose(
            a.astype(jnp.float32), b, rtol=3e-2,
            atol=3e-2 * float(jnp.abs(b).max()), err_msg=f"d{leaf}")


SCHEDULE_BANDS = {
    # name -> (bh, bh_kv, s, d, itemsize, window, block kwargs)
    "the_cell_window_layer": (28, 4, 16384, 128, 2, 4096, {}),
    "the_cell_global_layer": (28, 4, 16384, 128, 2, None, {}),
    "trinity_window_layer": (64, 8, 8192, 128, 2, 2048, {}),
    "trinity_global_layer": (64, 8, 8192, 128, 2, None, {}),
    "window_off_the_tile": (8, 2, 1024, 64, 4, 300,
                            dict(block_q=128, block_kv=64)),
    "kv_axis": (4, 2, 256, 64, 4, 96,
                dict(block_q=32, block_kv=32, block_kv_major=64)),
    "window_that_never_closes": (16, 16, 4096, 128, 2, 4096, {}),
    "one_head_too_long_to_hold": (8, 2, 32768, 128, 4, 5000, {}),
}


@pytest.mark.parametrize("name", sorted(SCHEDULE_BANDS))
def test_schedule_live_tiles_are_the_bands(name):
    """``live_tiles`` is a brute-force count of the compute tiles that hold
    at least one (query, key) pair the mask admits; ``live`` and ``bwd_live``
    the same over each pass's own grid of blocks; and the pair slots the
    backward's loops visit (``bwd_visits``) are those tiles and no others,
    counted here by walking every step's kv tiles and asking the band."""
    bh, bh_kv, s, d, itemsize, window, kw = SCHEDULE_BANDS[name]
    sc = flash_schedule(bh, s, d, itemsize, True, window=window, bh_kv=bh_kv,
                        **kw)
    w = window if window is not None and window < s else s

    def sees(q0, q_rows, k0, kv_rows):
        # some query of the block sees some key of the block
        return (k0 <= q0 + q_rows - 1) & (q0 - (k0 + kv_rows - 1) < w)

    def band(q_rows, kv_rows):
        qi = np.arange(s // q_rows)[:, None] * q_rows
        kj = np.arange(s // kv_rows)[None, :] * kv_rows
        return int(sees(qi, q_rows, kj, kv_rows).sum())

    assert sc.group == bh // bh_kv and sc.g % sc.group == 0
    assert sc.window == (0 if w == s else w)
    assert sc.live_tiles == bh * band(sc.block_q, sc.block_kv)
    assert sc.tiles == bh * (s // sc.block_q) * (s // sc.block_kv)
    assert sc.live == bh // sc.g * band(sc.block_q, sc.block_kv_major)
    # a backward step: bwd_g K/V heads with one query head each
    assert sc.bwd_g == sc.g // sc.group
    assert sc.bwd_steps == sc.bwd_grid[0] * sc.bwd_grid[1] * sc.bwd_grid[2]
    assert sc.bwd_live == bh // sc.bwd_g * band(sc.block_q_major,
                                                sc.bwd_block_kv_major)
    # what the backward visits: in every live step, every kv tile its q rows
    # see, and for each of those the q tiles that see it (at least the visit)
    kvm, qm, bq, bkv = (sc.bwd_block_kv_major, sc.block_q_major, sc.block_q,
                        sc.block_kv)
    visits = 0
    for k0 in range(0, s, kvm):
        for q0 in range(0, s, qm):
            if not sees(q0, qm, k0, kvm):
                continue
            for ks in range(k0, k0 + kvm, bkv):
                if sees(q0, qm, ks, bkv):
                    visits += max(1, sum(bool(sees(qt, bq, ks, bkv))
                                         for qt in range(q0, q0 + qm, bq)))
    assert sc.bwd_visits == bh * visits == sc.live_tiles
    assert f"tiles={sc.live_tiles}/{sc.tiles}" in sc.describe()
    assert f"bwd_visits={sc.live_tiles}/{sc.bwd_visits}" in sc.describe()
    assert ("window=" in sc.describe()) == bool(sc.window)
    assert ("kv_heads=" in sc.describe()) == (sc.group > 1)


# Both passes of the two long-context cells, pinned (PR 34): the forward
# keeps a K/V head whole beside a q tile of its whole group (no kv axis); a
# backward step one query head's 4096 q/dO/dQ rows against 8192 K/V rows,
# the group along the innermost axis. name -> (g, block_h, K/V rows, grid),
# (bwd_g, block_h, K/V rows, q rows, grid), dQ partials, live tiles
CELL_SCHEDULES = {
    "the_cell_global_layer": ((7, 1, 16384, (4, 32, 1)),
                              (1, 1, 8192, 4096, (4, 2, 28)), 2, 14784),
    "the_cell_window_layer": ((7, 1, 16384, (4, 32, 1)),
                              (1, 1, 8192, 4096, (4, 2, 28)), 2, 7056),
    "trinity_global_layer": ((8, 1, 8192, (8, 16, 1)),
                             (1, 1, 8192, 4096, (8, 1, 16)), 1, 8704),
    "trinity_window_layer": ((8, 1, 8192, (8, 16, 1)),
                             (1, 1, 8192, 4096, (8, 1, 16)), 1, 4480),
}


@pytest.mark.parametrize("name", sorted(CELL_SCHEDULES))
def test_the_long_context_cells_schedules_are_pinned(name):
    from ps_pytorch_tpu.ops.flash_attention import VMEM_BUDGET_BYTES
    bh, bh_kv, s, d, itemsize, window, kw = SCHEDULE_BANDS[name]
    sc = flash_schedule(bh, s, d, itemsize, True, window=window, bh_kv=bh_kv)
    fwd, bwd, partials, live_tiles = CELL_SCHEDULES[name]
    assert (sc.block_q, sc.block_kv) == (512, 512)
    assert (sc.g, sc.block_h, sc.block_kv_major, sc.grid) == fwd
    assert (sc.bwd_g, sc.block_h, sc.bwd_block_kv_major,
            sc.block_q_major, sc.bwd_grid) == bwd
    # no kv axis: no dead forward step, m/l/acc never leave the step
    assert sc.grid[2] == 1 and sc.dead == 0
    assert sc.dq_partials == partials
    assert sc.bwd_visits == sc.live_tiles == live_tiles
    assert sc.vmem_bytes <= VMEM_BUDGET_BYTES
    assert sc.bwd_vmem_bytes <= VMEM_BUDGET_BYTES
    assert f"bwd_visits={live_tiles}/{live_tiles}" in sc.describe()


def test_the_window_layers_visit_at_most_half_the_global_layers_tiles():
    """At the cell's shape the band of 4096 keys is 44% of the causal
    triangle in pairs, 48% in 512 x 512 tiles; K and V keep their 4 heads."""
    glob = flash_schedule(28, 16384, 128, 2, True, bh_kv=4)
    win = flash_schedule(28, 16384, 128, 2, True, window=4096, bh_kv=4)
    assert 2 * win.live_tiles <= glob.live_tiles
    assert 2 * win.bwd_visits <= glob.bwd_visits
    assert win.live == glob.live and win.bwd_live < glob.bwd_live
    assert win.g == glob.g == 7 and win.group == 7
    assert win.bwd_g == glob.bwd_g == 1
    same = dict(live=0, bwd_live=0, live_tiles=0, bwd_visits=0)
    assert win._replace(window=0, **same) == glob._replace(**same)


def test_param_count_published_as_run_and_tiny(tiny):
    _, variables, _ = tiny
    published = dict(PUBLISHED, **PUBLISHED["published"], experts_held=64)
    assert REF.param_count(published) == PUBLISHED["parameters_published"] \
        == 21_506_562_560
    # as run (an eighth of the vocabulary, the memory rule's finding), and
    # the cut the rule tried first (a quarter)
    assert REF.param_count(PUBLISHED) == PUBLISHED["parameters_as_run"] \
        == 559_290_880
    assert REF.param_count(dict(PUBLISHED, vocab_size=37984)) == 656_529_920
    assert REF.param_count(TINY) == sum(
        a.size for a in jax.tree.leaves(variables["params"]))


@pytest.mark.parametrize("what", ["forward", "forward_and_backward"])
def test_closed_form_flops_against_the_jaxpr_walk(tiny, what):
    """The closed form charges attention by the pairs the masks admit and the
    experts at balance over the share held; the walk of the program finds the
    same projections, router and head, attention dense S x S (``full_attention``
    multiplies what it then masks) and the experts on every sorted row. With
    those two parts exchanged the forward agrees exactly; for training the
    closed form charges 3x the forward and the walk finds less by the gradient
    to the token ids."""
    model, variables, tokens = tiny
    parts = REF.macs_per_token(TINY, S)
    assert REF.train_flops_per_sample(TINY, seq_len=S) \
        == 6 * sum(parts.values())
    assert REF.keys_per_query(S) == (S + 1) / 2
    assert REF.keys_per_query(S, WINDOW) == sum(
        min(i + 1, WINDOW) for i in range(S)) / S
    assert parts["attention"] == 2 * 32 * (
        REF.keys_per_query(S) + 3 * REF.keys_per_query(S, WINDOW))
    assert parts["experts"] == 4 * 3 * (4 / 8) * 3 * 24 * 16
    walked_parts = dict(parts, attention=4 * 2 * 32 * S,
                        experts=4 * 3 * 3 * 24 * 16)
    per_token = 2 * sum(walked_parts.values())
    if what == "forward":
        walked = count_jaxpr_flops(jax.make_jaxpr(
            lambda v: _logits(model, v, tokens))(variables).jaxpr)
        assert walked == per_token * tokens.size
    else:
        walked = count_jaxpr_flops(jax.make_jaxpr(jax.grad(
            lambda v: _logits(model, v, tokens).sum()))(variables).jaxpr)
        assert 0.9 * 3 * per_token * tokens.size < walked \
            <= 3 * per_token * tokens.size


def test_the_real_shapes_flops_are_the_honest_count():
    """About 353M multiply-adds a token at S=16384 with a quarter of the
    vocabulary, 304M with the eighth the cell runs; the dense S x S charge
    the OLMoE reference makes would nearly double it."""
    parts = REF.macs_per_token(dict(PUBLISHED, vocab_size=37984), 16384)
    assert abs(REF.keys_per_query(16384, 4096) - 3584.125) < 1e-9
    assert abs(parts["experts"] - 4 * 1.5 * 3 * 2560 * 768) < 1e-6
    assert 352e6 < sum(parts.values()) < 354e6
    dense = dict(parts, attention=4 * 2 * 3584 * 16384)
    assert sum(dense.values()) > 1.9 * sum(parts.values())
    as_run = REF.macs_per_token(PUBLISHED, 16384)
    assert as_run["head"] == 2560 * 18992
    assert {k: v for k, v in as_run.items() if k != "head"} \
        == {k: v for k, v in parts.items() if k != "head"}
    assert 304e6 < sum(as_run.values()) < 305e6
    assert REF.train_flops_per_sample(PUBLISHED, seq_len=16384) \
        == 6 * sum(as_run.values())


_EP = dict(lm_parallelism="ep", lm_experts=64, lm_moe_top_k=6)
CONFIG_CASES = {
    "the_cells_flags": (
        dict(lm_arch="smallthinker", lm_heads=28, lm_kv_heads=4,
             lm_head_dim=128, lm_experts_held=16, lm_ffn_dim=768, **_EP),
        None),
    "smallthinker_needs_ep": (dict(lm_arch="smallthinker"),
                              "lm_parallelism=ep"),
    "kv_heads_divide_the_heads": (dict(lm_heads=4, lm_kv_heads=3),
                                  "lm_kv_heads"),
    "odd_head_dim": (dict(lm_head_dim=7), "lm_head_dim"),
    "held_divides_the_experts": (
        dict(lm_arch="smallthinker", lm_experts_held=5, **_EP),
        "lm_experts_held"),
    "held_needs_a_dropless_arch": (
        dict(lm_parallelism="ep", lm_experts_held=2), "dropless arch"),
    "tp_with_kv_heads": (dict(lm_parallelism="tp", lm_kv_heads=2), "tp and pp"),
    "pp_with_a_head_dim": (dict(lm_parallelism="pp", lm_head_dim=16),
                           "tp and pp"),
}


@pytest.mark.parametrize("name", sorted(CONFIG_CASES))
def test_config_validation(name):
    kw, error = CONFIG_CASES[name]
    if error is None:
        cfg = TrainConfig.from_json(TrainConfig(**kw).to_json())
        assert (cfg.lm_arch, cfg.lm_kv_heads, cfg.lm_head_dim,
                cfg.lm_experts_held) == ("smallthinker", 4, 128, 16)
    else:
        with pytest.raises(ValueError, match=error):
            TrainConfig(**kw)


def test_config_names_the_dropless_archs_the_models_have():
    from ps_pytorch_tpu import config
    assert config.LM_ARCHS == tuple(ARCHS)
    assert config._DROPLESS_ARCHS == tuple(
        name for name, row in ARCHS.items() if row.dropless)


def _refused_ring():
    q = jnp.zeros((1, 4, 8, 8))
    tr_mod.ring_attention(q, q[:, :2], q[:, :2], "data", causal=True)


def _refused_ring_window():
    q = jnp.zeros((1, 4, 8, 8))
    tr_mod.ring_attention(q, q, q, "data", causal=True, window=4)


def _refused_decode():
    model = TransformerLM(vocab_size=17, n_layers=1, n_heads=4, kv_heads=2,
                          d_model=16, max_seq_len=8, decode=True,
                          decode_cache_len=8)
    model.init(jax.random.key(0), jnp.zeros((1, 1), jnp.int32))


def _refused_decode_window():
    model = MoETransformerLM(
        vocab_size=17, n_layers=2, n_heads=4, d_model=16, n_experts=4,
        top_k=2, max_seq_len=32, arch="smallthinker", decode=True,
        decode_cache_len=32)
    model.init(jax.random.key(0), jnp.zeros((1, 1), jnp.int32))


def _refused_tp():
    from ps_pytorch_tpu.parallel.tp import make_tp_train_step
    make_tp_train_step(TransformerLM(n_heads=4, kv_heads=2), None, None, None)


def _refused_pp():
    from ps_pytorch_tpu.parallel.pp import make_pp_train_step
    make_pp_train_step(TransformerLM(n_heads=4, head_dim=16), None, None, None,
                       num_microbatches=1)


def _refused_window_without_causal():
    q = jnp.zeros((1, 2, 16, 8))
    flash_attention(q, q, q, causal=False, window=4)


REFUSALS = {
    "ring_with_fewer_kv_heads": (_refused_ring, "ring attention is not built"),
    "ring_with_a_window": (_refused_ring_window, "ring attention is not built"),
    "decode_with_fewer_kv_heads": (_refused_decode, "decode is not built"),
    "decode_with_a_window": (_refused_decode_window, "decode is not built"),
    "tp_with_fewer_kv_heads": (_refused_tp, "tensor parallelism is not built"),
    "pp_with_a_head_dim": (_refused_pp, "pipeline parallelism is not built"),
    "a_window_without_causal": (_refused_window_without_causal,
                                "needs causal=True"),
}


@pytest.mark.parametrize("name", sorted(REFUSALS))
def test_refused_with_one_message(name):
    call, message = REFUSALS[name]
    with pytest.raises(ValueError, match=message):
        call()


@pytest.mark.parametrize("script", ["generate.py", "serve.py"])
def test_the_decoders_refuse_the_arch(tmp_path, script):
    """A checkpoint of the arch is turned away in one line by both decoders,
    before any model is built."""
    from ps_pytorch_tpu.runtime import checkpoint as ckpt
    cfg = TrainConfig(network="MoETransformerLM", lm_arch="smallthinker",
                      lm_heads=4, lm_kv_heads=2, lm_head_dim=8, lm_d_model=24,
                      lm_vocab=97, lm_layers=4, lm_experts=8, lm_moe_top_k=3,
                      lm_experts_held=4, lm_parallelism="ep",
                      train_dir=str(tmp_path))
    ckpt.save_checkpoint(str(tmp_path), 1, {"x": jnp.zeros((1,))},
                         config_json=cfg.to_json())
    run = subprocess.run(
        [sys.executable, str(REPO / script), "--train-dir", str(tmp_path)],
        capture_output=True, text=True, timeout=300,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin",
             "PYTHONPATH": str(REPO)})
    assert run.returncode == 2
    assert "lm_arch=smallthinker" in run.stderr
    assert "Traceback" not in run.stderr


# Taken from the parent commit (f608fe3) with /root/scratch/golden.py's
# recipe, given in the test below; the bytes on this container's CPU backend.
# ``olmoe_moe`` was taken again in PR 38 from the new code: the dropless layer
# adds a token's k rows in another order (3e-7 of the parent's logits, 6e-7 of
# its gradients; ``tests/dropless_plain.py`` holds the parent's form and the
# tests beside it hold the layer to it). The other three rows are unedited.
GOLDEN = {
    "gpt2_dense": ("3be40a762d856dc549057dc3ed6e97db992f221f77d36cc1589463d83b3f14e4", 29,
                   "d782c65fce5679c9300791f7642e74c11adf5d22dbe02839a0b65168516352d7",
                   "f126957a5e7cab3eb9b48cc830687d5631886a5c38e546997ce52003b4359dac"),
    "gpt2_moe": ("f1122c029a32e174e9d178ecc63412746fbffdc1727dbbbe83b421cc37c5f1de", 31,
                 "77787e920386921c41f0237880906242845f371b6328107671fc794f0c92ee6d",
                 "f05bbe878f110b6731108557d226926deead65c6a0e1d84660dc3c8cc0c6d447"),
    "olmoe_dense": ("5355e4f2818573bf3ba245c7226c39f3ac2205422a0210b4f2386126cca130db", 27,
                    "85481556b45fdb9360fe921d1fb0fcd7a9f4454fcbec42a6ebc2b9a11e3a2568",
                    "5b95ac6bafd75f9a4a9fb14c569daf1d0a41747e2f22ac251e3aefe94e036aba"),
    "olmoe_moe": ("77b2683f8475c0519f84a406291b689e21152ad8dc5e40bf1c68139d1f09adaf", 27,
                  "94a34c2b7ef8697010c307af958d181d1b7aa93ddf6b95758415c69738d8d839",
                  "581e68b6e8e6b6c72babf8b9e099e3c7dd162a42d1104702393a262c70bbf97e"),
}


def _sha(arrays):
    h = hashlib.sha256()
    for a in arrays:
        h.update(np.asarray(a, np.float32).tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("family", sorted(GOLDEN))
def test_the_older_archs_are_the_parents_bit_for_bit(family):
    """``gpt2`` and ``olmoe``, dense and MoE class: the parameter tree (names,
    order, shapes), the logits and the gradients of ``1e-3 * sum(logits^2)``
    (+ the routing terms the MoE class returns) are the parent commit's
    bytes: ``init(key(0))`` on tokens ``default_rng(7).integers(0, 97, (2,
    32))``, vocab 97, 2 layers, 4 heads, d=64, S=32; the olmoe rows with
    ffn_dim 32, the MoE class with 8 experts top-2 (gpt2) or top-4 (olmoe).
    (``olmoe_moe``: PR 38's bytes, as the comment over ``GOLDEN`` says.)"""
    arch, cls = family.split("_")
    kw = dict(vocab_size=97, n_layers=2, n_heads=4, d_model=64, max_seq_len=32)
    if arch == "olmoe":
        kw.update(arch="olmoe", ffn_dim=32)
    model = TransformerLM(**kw) if cls == "dense" else MoETransformerLM(
        n_experts=8, top_k=2 if arch == "gpt2" else 4, **kw)
    tokens = jnp.asarray(
        np.random.default_rng(7).integers(0, 97, (2, 32)), jnp.int32)
    params = model.init(jax.random.key(0), tokens)["params"]

    def loss(p):
        out = model.apply({"params": p}, tokens)
        logits, extra = out if isinstance(out, tuple) else (out, 0.0)
        if isinstance(extra, dict):
            extra = extra["aux"] + extra["z_loss"]
        return jnp.sum(logits.astype(jnp.float32) ** 2) * 1e-3 + extra

    out = model.apply({"params": params}, tokens)
    logits = out[0] if isinstance(out, tuple) else out
    paths = sorted(jax.tree_util.keystr(p) + str(tuple(a.shape)) for p, a in
                   jax.tree_util.tree_flatten_with_path(params)[0])
    tree_sha, n_leaves, logits_sha, grads_sha = GOLDEN[family]
    assert len(paths) == n_leaves
    assert hashlib.sha256("\n".join(paths).encode()).hexdigest() == tree_sha
    assert _sha([logits]) == logits_sha
    assert _sha(jax.tree.leaves(jax.grad(loss)(params))) == grads_sha


def test_the_dropless_layer_with_every_expert_is_the_parents():
    """``DroplessMoE`` with OLMoE's row and every expert held: the counters
    are the parent commit's (f608fe3) to the bit; output and gradients are
    PR 38's bytes, taken from the new code, which adds a token's k rows in
    another order (1e-7 of the parent's; the parent's form is
    ``tests/dropless_plain.py``, and
    ``test_olmoe.py::test_the_layer_is_the_plain_take_and_scatter_add`` holds
    the layer to it)."""
    layer = DroplessMoE(n_experts=8, d_model=16, d_hidden=8, top_k=4)
    x = jax.random.normal(jax.random.key(0), (2, 12, 16))
    p = layer.init(jax.random.key(1), x)["params"]
    y, stats = layer.apply({"params": p}, x)
    g = jax.grad(lambda p, x: jnp.sum(layer.apply({"params": p}, x)[0] ** 2),
                 argnums=(0, 1))(p, x)
    assert _sha([y]) == \
        "b900ca25779415f88d93754fc7a65dbf02688bf2daa0d6a9d9492eb3810a7c0d"
    assert _sha(jax.tree.leaves(g)) == \
        "040c44166963eaebdf8d89e922860883ea035a268910047ace1c1dc4d9889a26"
    assert {k: float(v) for k, v in stats.items()} == {
        "aux": 4.017381191253662, "z_loss": 6.1970930099487305,
        "expert_load_max_over_mean": 1.1666666269302368, "moe_dropped": 0.0,
        "moe_held_share": 1.0}
