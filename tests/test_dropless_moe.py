"""``models/moe.py:DroplessMoE``, the layer alone: against its plain form
(``tests/dropless_plain.py``: take, grouped matmuls, scatter-add, JAX's own
derivative) under each arch's row of fields, every expert held or a share of
them, with and without the overflow part run, an empty group, k = 1; the
scatters the compiler leaves (of a held share's rows: none); the counters of a rigged router; a bias that
relieves an overloaded expert; and golden bytes from before PR 38."""

import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import dropless_plain as PLAIN
from ps_pytorch_tpu.models import moe as moe_mod
from ps_pytorch_tpu.models.moe import (
    EXPERT_COUNTS, MOE_STATE, DroplessMoE, update_expert_bias,
)

# The rows of fields the archs give the layer: OLMoE's holds every expert
# (softmax scores, SiLU, gates as they are); SmallThinker's and Trinity's hold
# experts 4..7 of 8 (share 1 of 2), top-3 gates renormalised, the one
# ReLU-gated, the other with sigmoid scores, a top-3 chosen under a bias that
# is not all zeros and the gates scaled (benchmark/configs/trinity_mini.json's
# route_scale).
RELU_SHARE = dict(top_k=3, act="relu", gate_norm=True, n_held=4, share=1)
BIAS_SHARE = dict(top_k=3, gate_norm=True, n_held=4, share=1, score="sigmoid",
                  select_bias=True, route_scale=2.826)

# name: (HELD_ROWS_SLACK, whether the overflow part runs (None: every expert
# is held, there is none), the layer's fields). The main part of a share is
# sized for slack times the balanced share in tiles of 8 rows (the 512 that
# ship are more than all 96 assignments).
PLAIN_CASES = {
    "all_held-every_expert_held": (1.5, None, dict(top_k=4)),
    "all_held-an_empty_group": (1.5, None, dict(top_k=4, rig_out=5)),
    "all_held-an_empty_first_group": (1.5, None, dict(top_k=4, rig_out=0)),
    "all_held-k_1": (1.5, None, dict(top_k=1)),
    "all_held-k_1_relu_renormalised": (
        1.5, None, dict(top_k=1, act="relu", gate_norm=True)),
    "all_held-k_8_of_8": (1.5, None, dict(top_k=8)),
    "relu_share-overflow_not_taken": (1.5, False, RELU_SHARE),
    "relu_share-overflow_taken": (0.5, True, RELU_SHARE),
    "relu_share-overflow_takes_nearly_all": (0.05, True, RELU_SHARE),
    "relu_share-an_empty_held_group": (
        1.5, False, dict(RELU_SHARE, rig_out=5)),
    "relu_share-an_empty_held_group_overflow_taken": (
        0.5, True, dict(RELU_SHARE, rig_out=6)),
    "relu_share-k_1": (1.5, False, dict(RELU_SHARE, top_k=1)),
    "relu_share-k_1_overflow_taken": (0.5, True, dict(RELU_SHARE, top_k=1)),
    "bias_share-overflow_not_taken": (1.5, False, BIAS_SHARE),
    "bias_share-overflow_taken": (0.5, True, BIAS_SHARE),
    "bias_share-the_other_share_overflow_taken": (
        0.5, True, dict(BIAS_SHARE, share=0)),
    "bias_share-k_1_overflow_taken": (0.5, True, dict(BIAS_SHARE, top_k=1)),
    "bias_all_held-every_expert_held": (
        1.5, None, dict(BIAS_SHARE, n_held=0, share=0)),
}


def _case(monkeypatch, name, **kw):
    slack, taken, fields = PLAIN_CASES[name]
    monkeypatch.setattr(moe_mod, "HELD_ROWS_SLACK", slack)
    monkeypatch.setattr(moe_mod, "HELD_ROWS_TILE", 8)
    return PLAIN.tiny_case(**{**fields, **kw})


@pytest.mark.parametrize("name", sorted(PLAIN_CASES))
def test_the_layer_is_the_plain_take_and_scatter_add(monkeypatch, name):
    """Output, the gradients in the tokens, the router (through the gates:
    softmax or sigmoid, renormalised and scaled or not) and the held weights
    are those of the form the layer had before PR 38 in one part over all
    sorted rows; ``moe_dropped``, the load figures and the counts it hands a
    bias's step under ``EXPERT_COUNTS`` exactly. A case's name begins with the
    arch whose row of fields it runs (``all_held`` OLMoE's, ``relu_share``
    SmallThinker's, ``bias_*`` Trinity's)."""
    slack, taken, fields = PLAIN_CASES[name]
    layer, variables, x = _case(monkeypatch, name)
    stats = PLAIN.assert_the_plain_form(layer, variables, x)
    t_k = 32 * layer.top_k
    assert float(stats["moe_dropped"]) == 0.0
    if layer.select_bias:
        assert int(stats[EXPERT_COUNTS]["expert_bias"].sum()) == t_k
    if taken is None:
        assert float(stats["moe_held_share"]) == 1.0
        assert float(stats["moe_tail_rows_share"]) == 0.0
    else:
        held, rows = PLAIN.held_and_main_rows(layer, stats, slack)
        assert 0 < held < t_k and (held > rows) == taken, (held, rows)
        # the main part's rows that no held group owns, of those it is sized for
        assert float(stats["moe_tail_rows_share"]) == pytest.approx(
            max(rows - held, 0) / rows, rel=1e-6)
    rig_out = fields.get("rig_out")
    if rig_out is not None:
        idx = jax.lax.top_k(x.reshape(-1, 16).astype(jnp.float32)
                            @ variables["params"]["router"]["kernel"],
                            layer.top_k)[1]
        assert rig_out not in np.asarray(idx)
        assert float(stats["expert_load_max_over_mean"]) >= 8 / 7


@pytest.mark.parametrize("name", ["all_held-every_expert_held",
                                  "all_held-k_1"])
def test_the_compiled_layer_holds_no_scatter(monkeypatch, name):
    """Forward and backward of the layer with every expert held, as lowered
    and as the CPU's compiler leaves them: no ``scatter`` op. The plain form's
    counts say the search finds one where it is (its combine, the transposes
    of its take, of ``top_k`` and of the gates' gather, its two counts)."""
    layer, variables, x = _case(monkeypatch, name)
    step, plain = PLAIN.steps(layer, variables)
    assert PLAIN.scatters(step, variables["params"], x) == (0, [])
    lowered, compiled = PLAIN.scatters(plain, variables["params"], x)
    assert lowered >= 5 and len(compiled) >= 3


@pytest.mark.parametrize("name", ["relu_share-overflow_not_taken",
                                  "relu_share-overflow_taken"])
def test_a_compiled_share_holds_no_scatter_of_rows(monkeypatch, name):
    """Forward and backward of a held share as lowered and as the CPU's
    compiler leaves them: NO scatter of rows (the combine and the take's
    transpose are ``ops/moe_rows.py``'s kernel since PR 52, where the plain
    form and the layer before it scatter-added ``[rows, d]`` twice a part)
    and none under ``moe_route``. What is left is one scatter a part, under
    ``moe_dispatch``, of SCALARS: the gates' gradients of the part's rows on
    their way back to ``[T, k]`` (a kernel that formed them token by token
    would have to fetch a token's rows by their index, which Mosaic refuses;
    PERF.md, Findings PR 52), once in the main part and once under the
    overflow ``cond``."""
    layer, variables, x = _case(monkeypatch, name)
    step, plain = PLAIN.steps(layer, variables)
    left = PLAIN.compiled_scatters(step, variables["params"], x)
    assert len(left) == 2, left
    for op_name, shape in left:
        assert "moe_dispatch" in op_name and "moe_route" not in op_name
        assert "," not in shape, shape      # one dimension: no row
    assert len(PLAIN.scatters(plain, variables["params"], x)[1]) == 6


def test_the_compiled_route_holds_no_scatter(monkeypatch):
    """Forward and backward of a held share chosen under the bias, as the
    CPU's compiler leaves them: no ``scatter`` op under ``moe_route`` (the
    gates are gathered from the scores forward and their gradient goes back
    by a comparison; the counts the bias's step reads are comparisons, and the
    places the rows' kernel reads are counted, not sorted or scattered); the
    two left are the two parts' gate gradients, as above. With every expert
    held there is none at all."""
    layer, variables, x = _case(monkeypatch, "bias_share-overflow_taken")
    _, names = PLAIN.scatters(PLAIN.steps(layer, variables)[0],
                              variables["params"], x)
    assert len(names) == 2
    assert all("moe_dispatch" in n and "moe_route" not in n for n in names)
    layer, variables, x = _case(monkeypatch, "bias_share-overflow_taken",
                                n_held=0, share=0)
    step, plain = PLAIN.steps(layer, variables)
    assert PLAIN.scatters(step, variables["params"], x) == (0, [])
    assert len(PLAIN.scatters(plain, variables["params"], x)[1]) >= 3


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


def _sha(arrays):
    h = hashlib.sha256()
    for a in arrays:
        h.update(np.asarray(a, np.float32).tobytes())
    return h.hexdigest()


def test_the_dropless_layer_with_every_expert_is_the_parents():
    """``DroplessMoE`` with OLMoE's row and every expert held: the counters
    are PR 29's parent commit's (f608fe3) to the bit; output and gradients are
    PR 38's bytes, taken from the new code, which adds a token's k rows in
    another order (1e-7 of the parent's; the parent's form is
    ``tests/dropless_plain.py``, and the cases above hold the layer to it)."""
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
        "moe_held_share": 1.0, "moe_tail_rows_share": 0.0}
