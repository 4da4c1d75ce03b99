"""The dropless expert layer in its plain form, kept for the tests: what
``models/moe.py:DroplessMoE`` was before PR 38. Route, sort the assignments by
expert, TAKE their tokens' rows, three grouped matmuls, scale by the gates and
SCATTER-ADD to the tokens, with JAX's own derivative of all of it (the
transposes of the take, of ``top_k``'s and of the gates' gathers are
scatter-adds) and the counts by scatter as well. One part over all T*k sorted
rows: an assignment to an expert not held sorts past the last group, where the
grouped matmul writes zeros, so a held share needs no second part here.

``DroplessMoE`` counts by comparison, sends the gates' gradient back to the
scores by comparison and, where it holds every expert, moves the same rows by
gathers through the sort's inverse: same assignments, same gates, same float32
sums, a token's k terms added in another order. The tests hold it to this.
"""

import re

import jax
import jax.numpy as jnp

from ps_pytorch_tpu.models.moe import (
    _GATE_EPS, EXPERT_COUNTS, MOE_STATE, DroplessMoE,
)
from ps_pytorch_tpu.models.transformer import ACTS
from ps_pytorch_tpu.ops.grouped_matmul import gmm


def plain_dropless(layer, params, x, bias=None):
    """``(y, stats)`` of the ``DroplessMoE`` ``layer`` (its fields are read,
    its code is not run) under ``params`` and, with ``select_bias``, the
    ``expert_bias`` [E] ``bias``. ``stats`` holds what the layer's hold, the
    counts under ``EXPERT_COUNTS`` too."""
    b, s, d = x.shape
    e, k = layer.n_experts, layer.top_k
    held = layer.n_held or e
    first = layer.share * held
    tokens = x.reshape(-1, d)
    t = tokens.shape[0]
    router = jnp.dot(tokens.astype(jnp.float32), params["router"]["kernel"],
                     precision=jax.lax.Precision.HIGHEST)
    probs = jax.nn.sigmoid(router) if layer.score == "sigmoid" \
        else jax.nn.softmax(router, axis=-1)
    if layer.select_bias:
        _, idx = jax.lax.top_k(probs + bias, k)
        gates = jnp.take_along_axis(probs, idx, axis=-1)
    else:
        gates, idx = jax.lax.top_k(probs, k)
    if layer.gate_norm:
        total = jnp.sum(gates, axis=-1, keepdims=True)
        gates = gates / (total + _GATE_EPS if layer.score == "sigmoid"
                         else total)
    if layer.route_scale != 1.0:
        gates = gates * layer.route_scale

    flat_e = idx.reshape(-1)
    load = jnp.zeros((e,), jnp.int32).at[flat_e].add(1)
    sizes = load[first:first + held]
    key = jnp.where((flat_e >= first) & (flat_e < first + held),
                    flat_e - first, held)
    order = jnp.argsort(key, stable=True)
    tok = order // k
    xs = tokens[tok].astype(layer.dtype)
    act = ACTS[layer.act]
    h = act(gmm(xs, params["experts_gate"], sizes)) \
        * gmm(xs, params["experts_up"], sizes)
    out = gmm(h, params["experts_down"], sizes)
    out = out.astype(jnp.float32) * gates.reshape(-1)[order][:, None]
    y = jnp.zeros((t, d), jnp.float32).at[tok].add(out)

    n_held_rows = jnp.sum(sizes)
    added = jnp.zeros((t,), jnp.int32).at[tok].add(
        (jnp.arange(t * k) < n_held_rows).astype(jnp.int32))
    stats = {
        "aux": e * jnp.sum((load.astype(jnp.float32) / t)
                           * jnp.mean(probs, axis=0)),
        "z_loss": jnp.mean(jax.nn.logsumexp(router, axis=-1) ** 2),
        "expert_load_max_over_mean":
            jnp.max(sizes).astype(jnp.float32) * e / (t * k),
        "moe_dropped": (n_held_rows - jnp.sum(added)).astype(jnp.float32),
        "moe_held_share": n_held_rows.astype(jnp.float32) / (t * k),
        EXPERT_COUNTS: {"expert_bias": load},
    }
    return y.reshape(b, s, d).astype(x.dtype), stats


def tiny_case(rig_out=None, **fields):
    """A ``DroplessMoE`` of 8 experts at d=16 (width 8) with ``fields``, its
    variables and an input of 2 x 16 tokens. ``rig_out`` names an expert no
    token may choose (an empty group); a layer that chooses under a bias gets
    one that is not all zeros."""
    layer = DroplessMoE(n_experts=8, d_model=16, d_hidden=8, **fields)
    x = jax.random.normal(jax.random.key(0), (2, 16, 16))
    variables = dict(layer.init(jax.random.key(1), x))
    if rig_out is not None:
        x = x.at[..., 0].set(1.0)
        params = variables["params"]
        variables["params"] = {**params, "router": {
            "kernel": params["router"]["kernel"].at[0, rig_out].set(-50.0)}}
    if layer.select_bias:
        variables[MOE_STATE] = {"expert_bias": 0.05 * jax.random.normal(
            jax.random.key(3), (8,))}
    return layer, variables, x


def held_and_main_rows(layer, stats, slack, tile=8):
    """Assignments the held block drew (from ``moe_held_share``) and the rows
    its main part is sized for under ``HELD_ROWS_SLACK`` = ``slack`` in tiles
    of ``tile``, for ``tiny_case``'s 32 tokens: more drawn than rows means
    the overflow part ran."""
    t_k = 32 * layer.top_k
    rows = min(t_k, -(-int(slack * t_k * layer.n_held / layer.n_experts)
                      // tile) * tile)
    return round(float(stats["moe_held_share"]) * t_k), rows


def steps(layer, variables):
    """``DroplessMoE`` and the plain form as two functions ``(params, x) ->
    ((loss, (y, stats)), grads)``: value and gradients, in the parameters and
    ``x``, of ``sum(y * r) + aux + z_loss`` (``r`` fixed normal draws). The
    router's gradient is what reaches it through the gates, the load-balance
    term and the z-loss."""
    state = {c: v for c, v in variables.items() if c != "params"}
    bias = jax.tree.leaves(state)[0] if layer.select_bias else None

    def step(fn):
        def loss(params, x):
            y, stats = fn(params, x)
            r = jax.random.normal(jax.random.key(99), x.shape, jnp.float32)
            return (jnp.sum(y.astype(jnp.float32) * r) + stats["aux"]
                    + stats["z_loss"]), (y, stats)
        return jax.value_and_grad(loss, argnums=(0, 1), has_aux=True)

    return (step(lambda p, x: layer.apply({**state, "params": p}, x)),
            step(lambda p, x: plain_dropless(layer, p, x, bias)))


def scatters(step, *args):
    """The ``scatter`` ops of ``step(*args)``: how many as lowered, and the
    ``op_name`` (the scopes it sits under) of each that this backend's
    compiler leaves."""
    lowered = jax.jit(step).lower(*args)
    return (len(re.findall(r"\bstablehlo\.scatter\b", lowered.as_text())),
            [name for name, _ in compiled_scatters(step, *args)])


def compiled_scatters(step, *args):
    """``(op_name, result shape)`` of each ``scatter`` op this backend's
    compiler leaves in ``step(*args)``; a shape as HLO writes it,
    ``f32[96]``."""
    text = jax.jit(step).lower(*args).compile().as_text()
    return [(re.search(r'op_name="([^"]*)"', line).group(1),
             re.search(r"= (\w+\[[\d,]*\])", line).group(1))
            for line in text.splitlines() if re.search(r" scatter\(", line)]


def _worst_relative(got, want):
    """Largest ``|got - want|`` over the largest ``|want|``, leaf by leaf."""
    worst = 0.0
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want), strict=True):
        a, b = jnp.asarray(a, jnp.float32), jnp.asarray(b, jnp.float32)
        worst = max(worst, float(jnp.abs(a - b).max() / jnp.abs(b).max()))
    return worst


# float32 on both sides and the same grouped matmuls: what differs is the
# order in which a token's k rows are added (measured 1.4e-7 at most).
TOL = 1e-5
COUNTS = ("moe_dropped", "expert_load_max_over_mean", "moe_held_share")


def assert_the_plain_form(layer, variables, x):
    """Output, the gradients in ``x``, the router (all that reaches it through
    the gates) and the three expert weights within ``TOL`` of the plain
    form's, relative to each array's largest entry; the counts, made of
    integers on both sides, exactly. Returns the layer's ``stats``."""
    ((_, (y, stats)), (d_params, d_x)), \
        ((_, (y_want, want)), (d_params_want, d_x_want)) = (
            step(variables["params"], x) for step in steps(layer, variables))
    assert _worst_relative(y, y_want) <= TOL
    assert _worst_relative(d_x, d_x_want) <= TOL
    assert set(d_params) == {"router", "experts_gate", "experts_up",
                             "experts_down"}
    for name, grad in d_params.items():
        assert all(float(jnp.abs(g).max()) > 0 for g in jax.tree.leaves(grad))
        assert _worst_relative(grad, d_params_want[name]) <= TOL, name
    for name in COUNTS:
        assert float(stats[name]) == float(want[name]), name
    if layer.select_bias:
        assert stats[EXPERT_COUNTS]["expert_bias"].tolist() == \
            want[EXPERT_COUNTS]["expert_bias"].tolist()
    for name in ("aux", "z_loss"):
        assert abs(float(stats[name]) - float(want[name])) \
            <= 1e-6 * abs(float(want[name])), name
    return stats
