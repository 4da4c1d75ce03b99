"""Expert parallelism: sharded == unsharded exactly, routing behaves.

The equivalence oracle exploits the per-group dispatch design: the EP run
(each device one dispatch group, experts sharded, all_to_all routing) must
match the single-device model with ``n_groups = n_devices`` — identical
math, different placement."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from ps_pytorch_tpu.models.moe import MoEMLP, MoETransformerLM
from ps_pytorch_tpu.optim.sgd import sgd
from ps_pytorch_tpu.parallel.dp import TrainState
from ps_pytorch_tpu.parallel.ep import (
    create_ep_train_state, ep_param_specs, make_ep_train_step,
)
from ps_pytorch_tpu.parallel.mesh import make_mesh


def _moe_lm(**kw):
    kw.setdefault("vocab_size", 64)
    kw.setdefault("n_layers", 2)
    kw.setdefault("n_heads", 4)
    kw.setdefault("d_model", 64)
    kw.setdefault("n_experts", 8)
    kw.setdefault("max_seq_len", 32)
    return MoETransformerLM(**kw)


def test_moe_mlp_routes_and_balances():
    """Every kept token's output comes from exactly its argmax expert and
    is scaled by its gate; ample capacity drops nothing."""
    mlp = MoEMLP(n_experts=4, d_model=16, d_hidden=32, capacity_factor=4.0)
    x = jax.random.normal(jax.random.key(0), (2, 8, 16))
    params = mlp.init(jax.random.key(1), x)["params"]
    y, aux = mlp.apply({"params": params}, x)
    assert y.shape == x.shape and np.isfinite(float(aux))
    # Oracle: run each token through its own argmax expert directly.
    toks = x.reshape(-1, 16)
    router = toks @ np.asarray(params["router"]["kernel"])
    probs = jax.nn.softmax(router, axis=-1)
    idx = np.argmax(np.asarray(probs), axis=-1)
    gate = np.max(np.asarray(probs), axis=-1)
    w1, b1 = np.asarray(params["experts_w1"]), np.asarray(params["experts_b1"])
    w2, b2 = np.asarray(params["experts_w2"]), np.asarray(params["experts_b2"])
    want = np.stack([
        (np.asarray(jax.nn.gelu(t @ w1[e] + b1[e])) @ w2[e] + b2[e]) * g
        for t, e, g in zip(np.asarray(toks), idx, gate)])
    np.testing.assert_allclose(np.asarray(y.reshape(-1, 16)), want,
                               rtol=1e-5, atol=1e-5)


def test_moe_mlp_top2_gshard_routing():
    """top_k=2: each kept token's output is g1*E_i(x) + g2*E_j(x) with
    (i, j) its two best experts and gates renormalized over the pair."""
    mlp = MoEMLP(n_experts=4, d_model=16, d_hidden=32, capacity_factor=8.0,
                 top_k=2)
    x = jax.random.normal(jax.random.key(4), (2, 8, 16))
    params = mlp.init(jax.random.key(5), x)["params"]
    y, aux = mlp.apply({"params": params}, x)
    assert np.isfinite(float(aux))
    toks = np.asarray(x.reshape(-1, 16))
    probs = np.asarray(jax.nn.softmax(
        jnp.asarray(toks) @ params["router"]["kernel"], axis=-1))
    order = np.argsort(-probs, axis=-1)[:, :2]
    w1, b1 = np.asarray(params["experts_w1"]), np.asarray(params["experts_b1"])
    w2, b2 = np.asarray(params["experts_w2"]), np.asarray(params["experts_b2"])

    def expert(e, t):
        return np.asarray(jax.nn.gelu(t @ w1[e] + b1[e])) @ w2[e] + b2[e]

    want = []
    for t, (i, j) in zip(toks, order):
        g = probs[len(want)][[i, j]]
        g = g / g.sum()
        want.append(g[0] * expert(i, t) + g[1] * expert(j, t))
    np.testing.assert_allclose(np.asarray(y.reshape(-1, 16)),
                               np.stack(want), rtol=1e-4, atol=1e-4)


def test_moe_top2_first_choices_claim_capacity_first():
    """With capacity 1 per expert, a token's SECOND choice never evicts
    another token's first choice (rank-priority dispatch)."""
    mlp1 = MoEMLP(n_experts=2, d_model=8, d_hidden=16,
                  capacity_factor=2.0 / 8.0)           # cap = 1
    mlp2 = mlp1.clone(top_k=2)
    x = jax.random.normal(jax.random.key(6), (1, 8, 8))
    params = mlp2.init(jax.random.key(7), x)["params"]
    y1, _ = mlp1.apply({"params": params}, x)
    y2, _ = mlp2.apply({"params": params}, x)
    # Rank-0 dispatch identical => tokens kept by top-1 are also kept (with
    # the same expert) under top-2; their outputs differ only by the gate
    # renormalization and any second-choice addition, so nonzero rows of y1
    # must be nonzero in y2 as well.
    nz1 = np.any(np.asarray(y1.reshape(-1, 8)) != 0.0, axis=-1)
    nz2 = np.any(np.asarray(y2.reshape(-1, 8)) != 0.0, axis=-1)
    assert np.all(nz2[nz1])


def test_moe_capacity_drops_to_residual():
    """With capacity 1 per expert, overflow tokens get ZERO MLP output."""
    mlp = MoEMLP(n_experts=2, d_model=8, d_hidden=16,
                 capacity_factor=2.0 / 8.0)   # cap = max(8/2*0.25, 1) = 1
    x = jax.random.normal(jax.random.key(2), (1, 8, 8))
    params = mlp.init(jax.random.key(3), x)["params"]
    y, _ = mlp.apply({"params": params}, x)
    zero_rows = np.sum(np.all(np.asarray(y.reshape(-1, 8)) == 0.0, axis=-1))
    assert zero_rows >= 8 - 2  # at most cap x n_experts tokens kept


@pytest.mark.parametrize("n_dev,top_k", [(8, 1), (8, 2)])
def test_ep_step_matches_unsharded(n_dev, top_k):
    mesh = make_mesh(data=n_dev, model=1)
    ep_model = _moe_lm(ep_axis="data", top_k=top_k)
    oracle_model = _moe_lm(n_groups=n_dev, top_k=top_k)
    tx = sgd(lr=0.1, momentum=0.9, weight_decay=1e-4)
    rng = jax.random.key(7)
    batch, seq = 8, 32
    state = create_ep_train_state(ep_model, tx, mesh, (batch, seq), rng)
    step_fn = make_ep_train_step(ep_model, tx, mesh, state, donate=False)

    params = oracle_model.init(
        rng, jnp.zeros((batch, seq), jnp.int32),
        positions=jnp.arange(seq))["params"]
    ref = TrainState(step=jnp.zeros((), jnp.int32), params=params,
                     opt_state=tx.init(params), batch_stats={})

    @jax.jit
    def ref_step(state, tokens):
        def loss_fn(params):
            logits, aux = oracle_model.apply({"params": params}, tokens)
            per = optax.softmax_cross_entropy_with_integer_labels(
                logits[:, :-1], tokens[:, 1:])
            return per.mean() + 0.01 * aux, per.mean()
        (_, ce), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            state.params)
        updates, new_opt = tx.update(grads, state.opt_state, state.params)
        return state.replace(
            step=state.step + 1,
            params=optax.apply_updates(state.params, updates),
            opt_state=new_opt), ce

    tok_rng = np.random.default_rng(3)
    for _ in range(3):
        tokens = jnp.asarray(
            tok_rng.integers(0, 64, (batch, seq)).astype(np.int32))
        state, m = step_fn(state, tokens)
        ref, ref_ce = ref_step(ref, tokens)
        np.testing.assert_allclose(float(m["loss"]), float(ref_ce),
                                   rtol=2e-5, atol=2e-5)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=5e-4, atol=5e-5),
        jax.device_get(state.params), jax.device_get(ref.params))


def test_ep_param_specs():
    from jax.sharding import PartitionSpec as P
    model = _moe_lm()
    params = model.init(jax.random.key(0), jnp.zeros((2, 16), jnp.int32),
                        positions=jnp.arange(16))["params"]
    specs = ep_param_specs(params)
    moe = specs["block_0"]["moe"]
    assert moe["experts_w1"] == P("data")
    assert moe["experts_b2"] == P("data")
    assert moe["router"]["kernel"] == P()
    assert specs["tok_embed"]["embedding"] == P()


def test_ep_rejects_bad_config():
    mesh = make_mesh(data=8, model=1)
    tx = sgd(lr=0.1)
    with pytest.raises(ValueError, match="ep_axis"):
        make_ep_train_step(_moe_lm(), tx, mesh, None)
    with pytest.raises(ValueError, match="divisible"):
        make_ep_train_step(_moe_lm(ep_axis="data", n_experts=6), tx, mesh,
                           None)


# ---- PR 32: the step with ops/next_token_loss.py against the step it replaced ----

@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("arch", ["olmoe", "smallthinker", "trinity"])
def test_ep_step_is_the_one_with_the_loss_spelled_the_parents_way(arch, dtype):
    """One momentum-SGD step of ``make_ep_train_step`` on one device, each
    dropless arch at a tiny size, against the same step with the loss as
    ``parallel/ep.py`` spelled it until PR 32 (slice, astype, optax; kept in
    ``test_next_token_loss.parent_loss`` as the oracle): ``loss``, ``aux``,
    ``z_loss`` and every updated parameter."""
    from ps_pytorch_tpu.models.moe import lm_variables
    from ps_pytorch_tpu.models.transformer import ARCHS
    from test_next_token_loss import (
        arch_case, one_device_ep_step, parent_loss,
    )

    model, variables, tokens = arch_case(arch, batch=2,
                                         dtype=jnp.dtype(dtype))
    tx = sgd(lr=0.1, momentum=0.9, weight_decay=1e-4)
    step, state = one_device_ep_step(model, variables, tx)
    got_state, got = step(state, tokens)

    row = ARCHS[arch]

    @jax.jit
    def parent_step(state):
        def loss_fn(params):
            logits, stats = model.apply(
                lm_variables(params, state.batch_stats), tokens)
            ce_sum, count = parent_loss(logits, tokens)
            reg = row.aux_coef * stats["aux"] \
                + row.z_loss_coef * stats["z_loss"]
            return ce_sum + reg * count, (ce_sum, count, stats)
        (_, (ce_sum, count, stats)), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(state.params)
        grads = jax.tree.map(lambda g: g / count, grads)
        updates, _ = tx.update(grads, state.opt_state, state.params)
        return optax.apply_updates(state.params, updates), \
            {"loss": ce_sum / count, "aux": stats["aux"],
             "z_loss": stats["z_loss"]}

    want_params, want = parent_step(state)
    for k in want:
        np.testing.assert_allclose(float(got[k]), float(want[k]), rtol=1e-6)
    # float32: the two softmaxes differ in the last bit. bfloat16: dlogits is
    # the same rounding of it (test_next_token_loss.py); what is left is the
    # order XLA sums in. A parameter moves by lr * gradient = 1e-3..1e-2, and
    # a norm's scale near 1 has an ulp of 1.2e-7.
    atol = 5e-7 if dtype == "float32" else 2e-6
    flat = jax.tree_util.tree_flatten_with_path(got_state.params)[0]
    for (path, a), b in zip(flat, jax.tree.leaves(want_params)):
        np.testing.assert_allclose(a, b, atol=atol, rtol=0,
                                   err_msg=jax.tree_util.keystr(path))
    moved = jax.tree.map(lambda a, b: float(jnp.abs(a - b).max()),
                         got_state.params, state.params)
    assert min(jax.tree.leaves(moved)) > 0 and \
        max(jax.tree.leaves(moved)) > 1e-3
