"""The ``qwen3next`` arch (Gated DeltaNet linear-attention layers with a chunked
delta rule and its hand-written backward, gated softmax attention with a
rotated quarter, zero-centred norms, top-k experts beside a gated shared
expert) against its plain reference
``benchmark/reference/qwen3_next_80b_a3b.py`` at a tiny float32 size: the
kernels against the token-by-token recurrence, forward and five gradients;
logits; the ep step's loss and every parameter's gradient; the shares of an
expert layer against the uncut layer; the planted mistakes of
``benchmark/controls/qwen3_next_80b_a3b.py``; and every entry point that
refuses the arch."""

import importlib.util
import json
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import Mesh

from ps_pytorch_tpu.config import LM_ARCHS, TrainConfig
from ps_pytorch_tpu.models import transformer as tr_mod
from ps_pytorch_tpu.models.moe import (
    DROPLESS_STATS, DroplessMoE, MoETransformerLM,
)
from ps_pytorch_tpu.models.transformer import (
    ARCHS, LAYER_KINDS, LM_COUNTERS, ZeroCentredRMSNorm, lm_counters,
    make_norm, refuse_hybrid, rope, rope_on_a_share,
)
from ps_pytorch_tpu.ops import gated_delta_rule as gdr
from ps_pytorch_tpu.ops.gated_delta_rule import (
    gated_delta_rule, gated_delta_rule_reference, gdr_schedule,
)
from ps_pytorch_tpu.parallel import ep
from ps_pytorch_tpu.parallel.dp import TrainState

REPO = pathlib.Path(__file__).resolve().parent.parent


def _load(path):
    spec = importlib.util.spec_from_file_location(path.stem, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _load(REPO / "benchmark" / "reference" / "qwen3_next_80b_a3b.py")
CONTROLS = _load(REPO / "benchmark" / "controls" / "qwen3_next_80b_a3b.py")
PUBLISHED = json.loads((REPO / "benchmark" / "configs"
                        / "qwen3_next_80b_a3b.json").read_text())

# The tiny preset keeps the published ratios: d=32; linear layers of 2 key and
# 4 value heads of 16 (two value heads a key head); attention of 4 query heads
# on 2 key/value heads of 16, 4 of them rotated; 16 experts top-3 of width 16,
# experts 4..7 held (share 1 of 4); depth 8, so that two periods run; S=96, a
# chunk and a half of the delta rule's 64; vocab 97: in the reference's (the
# published config's) keys.
S, VOCAB, D = 96, 97, 32
TINY = dict(PUBLISHED, hidden_size=D, head_dim=16, num_attention_heads=4,
            num_key_value_heads=2, linear_num_key_heads=2,
            linear_num_value_heads=4, linear_key_head_dim=16,
            linear_value_head_dim=16, moe_intermediate_size=16,
            shared_expert_intermediate_size=16, num_experts=4,
            num_experts_published=16, experts_held=4, experts_share=1,
            num_experts_per_tok=3, num_hidden_layers=8, vocab_size=VOCAB)
# float32 on both sides, so only the order of reductions differs: measured
# 1.3e-5 on logits up to 4. 2e-4 is far under what any control changes.
LOGIT_TOL = 2e-4
ROW = ARCHS["qwen3next"]
TINY_ROW = ROW._replace(gdn_key_heads=2, gdn_value_heads=4, gdn_key_dim=16,
                        gdn_value_dim=16)


def _reference(variables, tokens, ref=REF, config=TINY):
    """The reference's logits, as one compiled program: taken eagerly its
    token-by-token scans and its loops over heads and experts compile one by
    one, forty seconds a forward."""
    return jax.jit(lambda v, t: ref.forward(v, t, config))(variables, tokens)


@pytest.fixture(autouse=True)
def tiny_linear_layers(monkeypatch):
    """The linear layers' sizes are the arch row's, not flags: the tiny size
    takes a row with small ones."""
    monkeypatch.setitem(tr_mod.ARCHS, "qwen3next", TINY_ROW)


def _model(**kw):
    base = dict(vocab_size=VOCAB, n_layers=8, n_heads=4, kv_heads=2,
                head_dim=16, d_model=D, max_seq_len=S, arch="qwen3next",
                n_experts=16, top_k=3, ffn_dim=16, experts_held=4,
                experts_share=1)
    base.update(kw)
    return MoETransformerLM(**base)


def _unsettled(params, key):
    """Every vector leaf (norm offsets and scales, A_log, dt_bias) off its
    initial value."""
    leaves, tree = jax.tree.flatten(params)
    keys = jax.random.split(key, len(leaves))
    return jax.tree.unflatten(tree, [
        a + 0.2 * jax.random.normal(k, a.shape) if a.ndim == 1 else a
        for a, k in zip(leaves, keys)])


@pytest.fixture(scope="module")
def tiny():
    """(model, variables, tokens): seeded weights, every vector leaf moved
    off its 0 or 1. Built once for the file: the initialisation is most of a
    test's time, and no test changes what it is handed."""
    model = _model()
    tokens = jnp.asarray(
        np.random.default_rng(1).integers(0, VOCAB, (2, S)), jnp.int32)
    with pytest.MonkeyPatch.context() as patch:
        patch.setitem(tr_mod.ARCHS, "qwen3next", TINY_ROW)
        params = jax.jit(model.init)(jax.random.key(0), tokens)["params"]
    return model, {"params": _unsettled(params, jax.random.key(5))}, tokens


@pytest.fixture(scope="module")
def tiny_logits(tiny):
    """(the program's logits, the plain reference's): computed once for the
    tests that hold something else against them."""
    model, variables, tokens = tiny
    with pytest.MonkeyPatch.context() as patch:
        patch.setitem(tr_mod.ARCHS, "qwen3next", TINY_ROW)
        got = jax.jit(lambda v, t: model.apply(v, t)[0])(variables, tokens)
    return got, _reference(variables, tokens)


# ---- the delta rule -----------------------------------------------------------

def _rule_inputs(seed=0, b=2, s=100, hk=2, hv=4, dk=16, dv=16, gate=0.1):
    ks = jax.random.split(jax.random.key(seed), 5)
    unit = lambda t: t / jnp.linalg.norm(t, axis=-1, keepdims=True)
    q = unit(jax.random.normal(ks[0], (b, s, hk, dk))) * dk ** -0.5
    k = unit(jax.random.normal(ks[1], (b, s, hk, dk)))
    v = jax.random.normal(ks[2], (b, s, hv, dv))
    g = -gate * jax.nn.softplus(jax.random.normal(ks[3], (b, s, hv)))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (b, s, hv)))
    return q, k, v, g, beta


def _agrees(args, tol=2e-5):
    """The chunked kernels (interpreted) against the recurrence: output and
    all five gradients, the hand-written backward against autodiff of the
    token-by-token scan."""
    o, state_max = gated_delta_rule(*args)
    want, last = gated_delta_rule_reference(*args)
    assert o.shape == want.shape and bool(jnp.isfinite(o).all())
    assert float(jnp.abs(o - want).max()) < tol
    assert float(state_max) >= float(jnp.abs(last).max()) * (1 - 1e-5)
    w = jax.random.normal(jax.random.key(9), want.shape)
    got = jax.grad(lambda *a: jnp.sum(gated_delta_rule(*a)[0] * w),
                   argnums=tuple(range(5)))(*args)
    ref = jax.grad(lambda *a: jnp.sum(gated_delta_rule_reference(*a)[0] * w),
                   argnums=tuple(range(5)))(*args)
    for name, a, r in zip(("q", "k", "v", "g", "beta"), got, ref):
        assert bool(jnp.isfinite(a).all()), name
        assert float(jnp.abs(a - r).max()) \
            < tol * max(float(jnp.abs(r).max()), 1.0), name


@pytest.mark.parametrize("s", [37, 64, 100, 256, 576, 704],
                         ids=lambda s: f"S_{s}")
def test_chunked_rule_agrees_with_the_recurrence(s):
    """S under one chunk, one chunk, S that is no whole number of chunks (the
    tail is padded with tokens the state passes unchanged), four chunks; then
    the two where the walk crosses grid steps, so that the states and ``dS``
    pass through the kernels' scratch: nine chunks (``group`` 3, three grid
    steps) and eleven (``group`` 1, eleven)."""
    groups = {576: (3, 3), 704: (1, 11)}
    if s in groups:
        assert gdr_schedule(1, s, 4, 16, 16, k_heads=2)[2:4] \
            == (groups[s][0], (2, groups[s][1]))
    _agrees(_rule_inputs(s=s, b=1 if s >= 256 else 2))


@pytest.mark.parametrize("s", [128, 160, 200], ids=lambda s: f"S_{s}")
def test_state_maximum_is_the_largest_state_at_a_chunk_boundary(s):
    """The counter is the kernel's running maximum beside the state: the
    largest |S| over the states the recurrence holds after every 64 tokens
    and after the last one (two whole chunks; a third that is half padding;
    at S = 200 values are written in the second chunk alone, so the largest
    state stands at a middle boundary and the last is the smallest)."""
    args = _rule_inputs(s=s, b=1)
    if s == 200:
        written = (jnp.arange(s) >= 64) & (jnp.arange(s) < 128)
        args = args[:4] + (args[4] * written[None, :, None],)
    _, state_max = gated_delta_rule(*args)
    ends = sorted(set(range(gdr.CHUNK, s, gdr.CHUNK)) | {s})
    tops = [float(jnp.abs(gated_delta_rule_reference(
        *(a[:, :n] for a in args))[1]).max()) for n in ends]
    assert float(state_max) == pytest.approx(max(tops), rel=1e-5)
    if s == 200:
        assert tops.index(max(tops)) == 1 and tops[-1] < 0.5 * max(tops)


def test_strong_decay_is_finite_and_equal():
    """A = 16 and a softplus of 1.31 (dt_bias = 1): g = -21 a token, a chunk's
    total decay exp(-1344), whose inverse float32 does not hold: every
    exponent the chunked form takes is <= 0."""
    q, k, v, g, beta = _rule_inputs(s=160)
    g = jnp.full_like(g, -16.0 * float(jax.nn.softplus(1.0)))
    assert float(jnp.sum(g[0, :64, 0])) < -1300
    _agrees((q, k, v, g, beta))


def test_beta_zero_only_decays_and_no_gate_is_the_plain_delta_rule():
    q, k, v, g, beta = _rule_inputs(s=100)
    o, state_max = gated_delta_rule(q, k, v, g, jnp.zeros_like(beta))
    assert float(jnp.abs(o).max()) == 0.0 == float(state_max)
    # g = 0, beta = 1: S <- S + k (v - S^T k)^T, after which S^T k_t = v_t
    args = (k, k, v, jnp.zeros_like(g), jnp.ones_like(beta))
    o, _ = gated_delta_rule(*args)
    np.testing.assert_allclose(o, v, atol=2e-5)     # reads back what it wrote
    _agrees((q, k, v, jnp.zeros_like(g), jnp.ones_like(beta)))


def test_state_is_float32_under_bfloat16_inputs():
    q, k, v, g, beta = _rule_inputs(b=1, s=128)
    bf = lambda t: t.astype(jnp.bfloat16)
    o, _ = gated_delta_rule(bf(q), bf(k), bf(v), g, beta)
    assert o.dtype == jnp.bfloat16
    want, _ = gated_delta_rule_reference(bf(q), bf(k), bf(v), g, beta)
    # the operands' and the output's rounding, chunk by chunk; a state carried
    # in bfloat16 would lose 2^-8 of itself at each of the boundaries as well
    err = float(jnp.abs(o.astype(jnp.float32) - want).max())
    assert err < 2 ** -6 * float(jnp.abs(want).max())
    hs = _kept_states(bf(q), bf(k), bf(v), g, beta)
    assert hs.dtype == jnp.float32
    assert float(jnp.abs(hs - hs.astype(jnp.bfloat16).astype(jnp.float32))
                 .max()) > 0        # it holds more bits than bfloat16 has


def test_bfloat16_gradients_stay_near_the_float32_recurrence():
    """bfloat16 q, k, v under float32 g and beta, two chunks: all five
    gradients of the kernels against autodiff of the float32 recurrence on
    the same (rounded) inputs. What is rounded on the way: the matmuls'
    operands (``T``, ``beta v``, ``beta exp(gamma) k``, the state's copy,
    ``V'``, the gradients that feed the MXU) and the gradients handed out in
    bfloat16, each 2^-9 of its size, through two chunks of 64: 2^-6 of a
    gradient's largest entry, the limit the output has in the test above,
    holds all five with room (read 0.2-0.7% over three seeds; the gates'
    gradients are float32 and are held to the same)."""
    q, k, v, g, beta = _rule_inputs(b=1, s=128)
    bf = lambda t: t.astype(jnp.bfloat16)
    args = (bf(q), bf(k), bf(v), g, beta)
    w = jax.random.normal(jax.random.key(9), v.shape)
    loss = lambda f: lambda *a: jnp.sum(f(*a)[0].astype(jnp.float32) * w)
    got = jax.grad(loss(gated_delta_rule), argnums=tuple(range(5)))(*args)
    ref = jax.grad(loss(gated_delta_rule_reference),
                   argnums=tuple(range(5)))(*args)
    for name, a, r in zip(("q", "k", "v", "g", "beta"), got, ref):
        assert a.dtype == (jnp.float32 if name in ("g", "beta")
                           else jnp.bfloat16), name
        a, r = a.astype(jnp.float32), r.astype(jnp.float32)
        assert bool(jnp.isfinite(a).all()), name
        assert float(jnp.abs(a - r).max()) \
            < 2 ** -6 * float(jnp.abs(r).max()), name


def _kept_states(q, k, v, g, beta):
    """The entering states the forward keeps for the backward."""
    _, vjp = jax.vjp(lambda *a: gated_delta_rule(*a)[0], q, k, v, g, beta)
    kept = [a for a in jax.tree.leaves(vjp) if getattr(a, "ndim", 0) == 4
            and a.shape[-2:] == (k.shape[-1], v.shape[-1])]
    assert len(kept) == 1
    return kept[0]


def test_value_heads_2j_and_2j_plus_1_read_key_head_j():
    """``repeat_interleave``, not ``tile``: with four value heads on two key
    heads, value head 1 reads key head 0 (a tile would hand it key head 1)."""
    q, k, v, g, beta = _rule_inputs(s=64)
    o, _ = gated_delta_rule(q, k, v, g, beta)
    want, _ = gated_delta_rule_reference(q, k, v, g, beta)
    tiled, _ = gated_delta_rule_reference(
        jnp.tile(q, (1, 1, 2, 1)), jnp.tile(k, (1, 1, 2, 1)), v, g, beta)
    assert float(jnp.abs(o - want).max()) < 2e-5
    assert float(jnp.abs(o - tiled)[:, :, 1:3].max()) > 0.05
    np.testing.assert_allclose(o[:, :, (0, 3)], tiled[:, :, (0, 3)],
                               atol=2e-5)


def test_the_triangular_inverse_is_forward_substitution():
    """What solves a chunk's system (``gdr_solve``: ``X`` formed from a
    chunk's k rows, gamma and beta, the chunks along the lanes, forward
    substitution, and back) against a dense inverse of ``I + X`` built here
    from the same rows, on random systems (three chunks of two key heads,
    two value heads each: twelve systems beside the padded ones, which must
    come back as the identity) and where every key is the same (X all ones
    under the diagonal: its inverse is bidiagonal, and a product of powers
    of X would lose every digit)."""
    c, dk, n, hk, r = 64, 16, 3, 2, 2
    strict = jnp.tril(jnp.ones((c, c)), -1)
    ks = jax.random.split(jax.random.key(0), 3)
    k = jax.random.normal(ks[0], (hk, n * c, dk)) * 0.5
    g = -0.05 * jax.nn.softplus(jax.random.normal(ks[1], (hk * r, n, c)))
    beta = jax.nn.sigmoid(jax.random.normal(ks[2], (hk * r, n, c)))
    gam = jnp.cumsum(g, axis=-1)

    def systems(t):         # [Hk, n' C, r C] -> [Hk, r, n', C, C]
        return t.reshape(hk, -1, c, r, c).transpose(0, 3, 1, 2, 4)

    t = systems(gdr._solved(k, gam, beta, True))
    kc = k.reshape(hk, 1, n, c, dk)
    kk = jnp.einsum("hrncd,hrnkd->hrnck", kc, kc, precision="highest")
    gm, bt = gam.reshape(hk, r, n, c), beta.reshape(hk, r, n, c)
    x = strict * bt[..., None] * kk \
        * jnp.exp(strict * (gm[..., :, None] - gm[..., None, :]))
    assert float(jnp.abs(x).max()) > 1.0        # no easy systems
    np.testing.assert_allclose(t[:, :, :n], jnp.linalg.inv(jnp.eye(c) + x),
                               atol=1e-4, rtol=1e-4)
    np.testing.assert_array_equal(
        t[:, :, n:], jnp.broadcast_to(jnp.eye(c), t[:, :, n:].shape))
    same = jnp.zeros((hk, n * c, dk)).at[..., 0].set(1.0)
    ones = systems(gdr._solved(same, jnp.zeros_like(gam),
                               jnp.ones_like(beta), True))[:, :, :n]
    np.testing.assert_array_equal(
        ones, jnp.broadcast_to(jnp.eye(c) - jnp.eye(c, k=-1), ones.shape))
    # and its derivative is -T^T dT T^T under the mask
    x, t = x.reshape(-1, c, c), t[:, :, :n].reshape(-1, c, c)
    w = jax.random.normal(jax.random.key(1), x.shape)
    got = jax.vmap(gdr._solve_pullback)(t, w)
    ref = jax.grad(lambda a: jnp.sum(
        jnp.linalg.inv(jnp.eye(c) + a * strict) * w))(x)
    np.testing.assert_allclose(got, ref * strict, atol=2e-4, rtol=1e-3)


def test_schedule_says_what_a_call_holds():
    sc = gdr_schedule(1, 16384, 32, 128, 128)
    assert (sc.chunk, sc.chunks, sc.group, sc.grid) == (64, 256, 8, (32, 32))
    assert sc.kept_bytes == 32 * 256 * 128 * 128 * 4      # 512 MiB a layer
    assert "chunk=64 chunks=256 group=8 grid=32x32" in sc.describe()
    # the cell's calls: 16 key heads, two value heads a grid step, bfloat16
    # rows. gdr_solve reads k (64 MiB), gamma and beta (2 each) and writes T
    # (128, float32); a forward call reads q, k a key head (64 MiB each), v
    # (128), gamma, beta, T and a decay a chunk (32 KiB), and writes o (128)
    # and the entering states; a backward call reads those, the states and
    # dO and writes the five gradients. Nothing but the states is kept
    # beside the inputs.
    sc = gdr_schedule(1, 16384, 32, 128, 128, k_heads=16, itemsize=2)
    mib, lam = 2 ** 20, 32 * 2 ** 10
    assert (sc.grid, sc.heads_a_step, sc.solve_grid) == ((16, 32), 2, (16, 2))
    assert (sc.kept_bytes, sc.kept_other_bytes) == (512 * mib, 0)
    assert sc.solve_bytes == (64 + 4 + 128) * mib
    assert sc.fwd_bytes == (2 * 64 + 2 * 128 + 4 + 128 + 512) * mib + lam
    assert sc.bwd_bytes == (4 * 64 + 3 * 128 + 8 + 128 + 512) * mib + lam
    assert sc.describe().endswith(
        f"heads=2 solve_grid=16x2 kept={512 * mib}+0 solve_bytes={196 * mib} "
        f"fwd_bytes={1028 * mib + lam} bwd_bytes={1288 * mib + lam}")
    # a short sequence is solved as one whole block of 128 chunks
    assert gdr_schedule(2, 100, 4, 16, 16, k_heads=2).solve_grid == (4, 1)
    assert gdr_schedule(2, 100, 4, 16, 16).chunks == 2
    with pytest.raises(ValueError, match="multiple of Hk"):
        gated_delta_rule(*_rule_inputs(hk=3, hv=4, s=8))


# ---- the layers ------------------------------------------------------------------

def test_zero_centred_norm_scales_by_one_plus_w():
    x = jax.random.normal(jax.random.key(0), (3, 7, D)) * 4
    w = jax.random.normal(jax.random.key(1), (D,)) * 0.3
    norm = make_norm("qwen3next", jnp.float32)
    assert isinstance(norm, ZeroCentredRMSNorm) and norm.epsilon == 1e-6
    init = norm.init(jax.random.key(2), x)["params"]["scale"]
    assert float(jnp.abs(init).max()) == 0.0            # w starts at 0
    got = norm.apply({"params": {"scale": w}}, x)
    want = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-6) \
        * (1 + w)
    np.testing.assert_allclose(got, want, rtol=1e-6)
    np.testing.assert_allclose(got, REF.norm(x, {"scale": w}, 1e-6), rtol=1e-6)
    # float32 statistics under a narrower dtype, the result in that dtype
    out = make_norm("qwen3next", jnp.bfloat16).apply(
        {"params": {"scale": w}}, x.astype(jnp.bfloat16))
    assert out.dtype == jnp.bfloat16
    # the other archs' norms are what they were
    assert not isinstance(make_norm("olmoe", jnp.float32), ZeroCentredRMSNorm)


@pytest.mark.parametrize("wrong", ["whole_head", "last_quarter"])
def test_rope_rotates_the_first_quarter_of_a_head(wrong):
    x = jax.random.normal(jax.random.key(0), (1, 2, 12, 16))
    pos = jnp.arange(12)
    got = rope_on_a_share(x, pos, 1e7, 0.25)
    np.testing.assert_array_equal(got[..., 4:], x[..., 4:])
    np.testing.assert_allclose(got[..., :4], rope(x[..., :4], pos, 1e7),
                               rtol=1e-6)
    np.testing.assert_allclose(
        got[0, 1], REF._rope(x[0, 1], 1e7, REF.rotated_features(TINY)),
        rtol=1e-5, atol=1e-6)
    other = rope(x, pos, 1e7) if wrong == "whole_head" else jnp.concatenate(
        [x[..., :12], rope(x[..., 12:], pos, 1e7)], axis=-1)
    assert float(jnp.abs(got - other)[..., 1:, :].max()) > 0.1
    assert ROW.rope_share == PUBLISHED["partial_rotary_factor"] == 0.25
    assert ARCHS["olmoe"].rope_share == 1.0


def test_no_layer_reads_a_later_token(tiny):
    """The convolution, the delta rule and the attention mask: a change to
    the last third of the tokens moves no logit before it."""
    model, variables, tokens = tiny
    cut = 2 * S // 3
    other = tokens.at[:, cut:].set((tokens[:, cut:] + 1) % VOCAB)
    logits = jax.jit(lambda t: model.apply(variables, t)[0])
    a, b = logits(tokens), logits(other)
    np.testing.assert_array_equal(a[:, :cut], b[:, :cut])
    assert float(jnp.abs(a - b)[:, cut:].max()) > 0.1


@pytest.mark.parametrize("attention", ["full", "flash"])
def test_logits_agree_with_the_reference(tiny, tiny_logits, attention):
    model, variables, tokens = tiny
    got, stats = jax.jit(model.clone(attention_impl=attention).apply)(
        variables, tokens)
    want = tiny_logits[1]
    assert got.shape == want.shape == (2, S, VOCAB)
    assert float(jnp.abs(want).max()) > 2
    assert float(jnp.abs(got - want).max()) < LOGIT_TOL
    assert float(stats["moe_dropped"]) == 0.0
    assert 0.1 < float(stats["moe_held_share"]) < 0.5


@pytest.mark.parametrize("depth", [8, 48])
def test_layer_kinds_follow_the_published_period(depth):
    got = [ROW.layer_kind(i, depth) for i in range(depth)]
    assert got == ["gdn", "gdn", "gdn", "attention"] * (depth // 4)
    assert set(got) <= set(LAYER_KINDS)
    assert [k == "gdn" for k in got] == [
        REF.is_linear(dict(TINY, num_hidden_layers=depth), i)
        for i in range(depth)]
    assert PUBLISHED["full_attention_interval"] == len(ROW.mixer_layers)
    assert ARCHS["trinity"].layer_kind(2, depth) == "attention"


def test_the_depth_is_a_hybrids_to_give_and_a_counter_is_never_dropped():
    """A period's kinds need no depth; a hybrid's do, and say so. A block
    whose mixer counts and whose second half hands no statistics on (a
    leading dense layer here) raises where it would lose the counter."""
    assert [ROW.layer_kind(i) for i in range(4)] == list(ROW.mixer_layers)
    with pytest.raises(ValueError, match="multiple of 4"):
        ARCHS["phi4flash"].layer_kind(0)
    model = _model(n_layers=4, dense_layers=1, dense_ffn_dim=32)
    tokens = jnp.zeros((1, S), jnp.int32)
    with pytest.MonkeyPatch.context() as patch:
        patch.setitem(tr_mod.ARCHS, "qwen3next", TINY_ROW)
        with pytest.raises(NotImplementedError, match="gdn_state_abs_max"):
            jax.eval_shape(model.init, jax.random.key(0), tokens)


def test_parameters_by_kind_of_layer(tiny):
    _, variables, _ = tiny
    p = variables["params"]
    both = {"ZeroCentredRMSNorm_0", "ZeroCentredRMSNorm_1", "moe", "shared",
            "shared_gate"}
    assert set(p["block_0"]) == both | {
        "in_proj_qkvz", "in_proj_ba", "conv_weight", "A_log", "dt_bias",
        "gdn_norm", "out_proj"}
    assert set(p["block_3"]) == both | {
        "Dense_0", "Dense_1", "Dense_2", "Dense_3", "gate", "q_norm",
        "k_norm"}
    assert set(p["block_4"]) == set(p["block_0"])
    assert set(p["block_7"]) == set(p["block_3"])
    assert p["block_0"]["in_proj_qkvz"]["kernel"].shape == (D, 2 * 32 + 2 * 64)
    assert p["block_0"]["conv_weight"].shape == (4, 2 * 32 + 64)  # no z, no bias
    assert p["block_0"]["A_log"].shape == p["block_0"]["dt_bias"].shape == (4,)
    assert p["block_0"]["gdn_norm"]["scale"].shape == (16,)
    assert p["block_3"]["q_norm"]["scale"].shape == (16,)
    assert p["block_3"]["Dense_1"]["kernel"].shape == (D, 32)     # kv heads
    assert p["block_0"]["shared_gate"]["kernel"].shape == (D, 1)
    assert p["block_0"]["moe"]["router"]["kernel"].shape == (D, 16)
    assert p["block_0"]["moe"]["experts_down"].shape == (4, 16, D)
    n = sum(a.size for a in jax.tree.leaves(p))
    assert n == REF.param_count(TINY)


def test_the_linear_layers_initialisers():
    params = jax.jit(_model(n_layers=1).init)(
        jax.random.key(3), jnp.zeros((1, 8), jnp.int32))["params"]
    full = MoETransformerLM(arch="qwen3next", n_layers=1, n_experts=4,
                            top_k=1, d_model=16, n_heads=2, ffn_dim=8)
    tr_mod.ARCHS["qwen3next"] = ROW         # the published 32 value heads
    b0 = full.init(jax.random.key(4), jnp.zeros((1, 8), jnp.int32))[
        "params"]["block_0"]
    a = np.exp(np.asarray(b0["A_log"]))
    assert a.shape == (32,) and 0 < a.min() and a.max() < 16 and a.std() > 2
    step = np.log1p(np.exp(np.asarray(b0["dt_bias"])))      # softplus
    assert 0.001 <= step.min() and step.max() <= 0.1001
    assert float(jnp.abs(b0["conv_weight"]).max()) <= 0.5
    assert float(jnp.abs(params["block_0"]["gdn_norm"]["scale"] - 1).max()) == 0
    assert float(jnp.abs(
        params["block_0"]["ZeroCentredRMSNorm_0"]["scale"]).max()) == 0


# ---- the step ---------------------------------------------------------------------

def _one_device_mesh():
    return Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("data", "model"))


@pytest.mark.parametrize("remat", [False, True])
def test_the_ep_step_descends_the_reference_loss(tiny, remat):
    """One plain-SGD step of ``parallel/ep.py``'s step on one device moves
    every parameter by ``lr * jax.grad(reference.loss)`` (cross-entropy plus
    0.001 of the load-balance term): through the delta rule's hand-written
    backward, the convolution, both gates, the zero-centred norms, the rotated
    quarter and the renormalised gates, over two periods of layers; with and
    without per-block remat. Its counter comes with the loss."""
    model, variables, tokens = tiny
    assert TINY_ROW.aux_coef == TINY["router_aux_loss_coef"] == 0.001
    assert TINY_ROW.z_loss_coef == 0.0
    lr = 0.5
    tx = optax.sgd(lr)
    state = TrainState(step=jnp.zeros((), jnp.int32),
                       params=variables["params"],
                       opt_state=tx.init(variables["params"]), batch_stats={})
    step = ep.make_ep_train_step(model.clone(ep_axis="data"), tx,
                                 _one_device_mesh(), state, remat=remat,
                                 donate=False)
    new_state, m = step(state, tokens)
    want = jax.jit(jax.grad(lambda p: REF.loss({"params": p}, tokens, TINY)))(
        variables["params"])
    got = jax.tree.map(lambda a, b: (a - b) / lr, state.params,
                       new_state.params)
    flat_got = jax.tree_util.tree_flatten_with_path(got)[0]
    flat_want = jax.tree.leaves(want)
    assert len(flat_got) == len(flat_want)
    for (path, g), w in zip(flat_got, flat_want):
        assert float(jnp.abs(w).max()) > 0, path       # every parameter is reached
        np.testing.assert_allclose(g, w, atol=2e-5, rtol=2e-3,
                                   err_msg=jax.tree_util.keystr(path))
    balance = jax.jit(lambda v: REF._forward(v, tokens, TINY)[1])(variables)
    np.testing.assert_allclose(
        float(m["loss"]) + 0.001 * float(balance),
        float(jax.jit(lambda v: REF.loss(v, tokens, TINY))(variables)),
        rtol=1e-5)
    np.testing.assert_allclose(float(m["aux"]), float(balance), rtol=1e-5)
    assert set(m) == {"loss", *DROPLESS_STATS, "gdn_state_abs_max"}
    assert float(m["moe_dropped"]) == 0.0
    assert 0.1 < float(m["gdn_state_abs_max"]) < 50


def test_counters_are_sown_and_other_archs_return_none(tiny, tiny_logits):
    model, variables, tokens = tiny
    (logits, _), sown = jax.jit(lambda v, t: model.apply(
        v, t, mutable=[LM_COUNTERS]))(variables, tokens)
    counters = lm_counters(sown)
    assert set(counters) == {"gdn_state_abs_max"}
    assert float(counters["gdn_state_abs_max"]) > 0
    # sowing changes no logit (two compiled programs: equal to rounding)
    np.testing.assert_allclose(logits, tiny_logits[0], atol=1e-6)
    olmoe = MoETransformerLM(vocab_size=VOCAB, n_layers=1, n_heads=2,
                             d_model=16, n_experts=4, top_k=2, arch="olmoe",
                             ffn_dim=8)
    v = {"params": olmoe.init(jax.random.key(0), tokens)["params"]}
    _, sown = olmoe.apply(v, tokens, mutable=[LM_COUNTERS])
    assert lm_counters(sown) == {}


# ---- a share of the experts ----------------------------------------------------------

@pytest.mark.parametrize("side", ["program", "reference"])
def test_the_shares_add_up_to_the_uncut_layer(side):
    """One expert layer at the tiny size, all 16 experts' weights seeded: the
    routed parts of the four shares (4 of 16 experts held, share 0..3) plus
    the GATED shared expert COUNTED ONCE add up to the uncut reference
    layer's contribution."""
    model = _model(n_layers=1, experts_held=0, experts_share=0)
    params = _unsettled(model.init(jax.random.key(3),
                                   jnp.zeros((1, S), jnp.int32))["params"],
                        jax.random.key(4))
    bp = params["block_0"]
    m = jax.random.normal(jax.random.key(5), (S, D))
    uncut = dict(TINY, num_experts=16, experts_held=16, experts_share=0)
    f_uncut, _ = REF.expert_layer(bp, m, uncut)
    shared = REF.shared_gate(m, bp) * REF._swiglu(bp["shared"], m)
    assert float(jnp.abs(shared).max()) > 0.01
    total, held_total = shared, 0.0
    for share in range(4):
        moe_s = {k: v[4 * share:4 * share + 4] if k.startswith("experts_")
                 else v for k, v in bp["moe"].items()}
        if side == "program":
            routed, stats = DroplessMoE(
                16, D, 16, top_k=3, gate_norm=True, n_held=4,
                share=share).apply({"params": moe_s}, m[None])
            routed = routed[0]
            assert float(stats["moe_dropped"]) == 0.0
            held_total += float(stats["moe_held_share"])
        else:
            f_s, _ = REF.expert_layer(
                {**bp, "moe": moe_s}, m,
                dict(uncut, num_experts=4, experts_held=4,
                     experts_share=share))
            routed = f_s - shared   # each share's f holds the shared expert whole
        total = total + routed
    np.testing.assert_allclose(total, f_uncut, atol=5e-6)
    if side == "program":
        np.testing.assert_allclose(held_total, 1.0, rtol=1e-6)


# ---- planted mistakes ------------------------------------------------------------------

@pytest.mark.parametrize("name", sorted(CONTROLS.CONTROLS))
def test_every_planted_mistake_fails_by_a_wide_margin(tiny, tiny_logits,
                                                       name):
    """The program as it is against the reference with one mistake in it
    (and, for the two precisions below the stated one, the reference computed
    coarser): each is far over the tolerance the true comparison keeps."""
    model, variables, tokens = tiny
    got, true = tiny_logits
    assert float(jnp.abs(got - true).max()) < LOGIT_TOL
    control = CONTROLS.CONTROLS[name]
    kept = {k: getattr(REF, k) for k in control.get("ref", {})}
    with CONTROLS.planted(control, object(), REF, TINY) as (_, ref):
        want = _reference(variables, tokens, ref)
    # a mistake in the mathematics is 25 tolerances off or more; a coarser
    # precision (one boundary at S = 96) at least 5
    margin = 5 if name in CONTROLS.PRECISION_CONTROLS else 25
    assert not float(jnp.abs(got - want).max()) <= margin * LOGIT_TOL, name
    # and the reference is itself again
    assert all(getattr(REF, k) is v for k, v in kept.items())


def test_the_controls_cover_what_the_issue_names():
    assert set(CONTROLS.CONTROLS) == {
        "beta_left_out", "decay_left_out", "key_l2_norm_left_out",
        "query_scale_left_out", "key_head_tiled_not_repeated",
        "conv_not_causal", "norm_not_zero_centred",
        "rope_over_the_whole_head", "attention_gate_left_out",
        "shared_gate_left_out", "gates_not_renormalised",
        *CONTROLS.PRECISION_CONTROLS}


# ---- config and refusals -----------------------------------------------------------------

def test_config_knows_the_arch_and_needs_no_new_field():
    assert "qwen3next" in LM_ARCHS and set(LM_ARCHS) == set(ARCHS)
    with pytest.raises(ValueError, match="lm_parallelism=ep"):
        TrainConfig(network="TransformerLM", lm_arch="qwen3next")
    cfg = TrainConfig(lm_arch="qwen3next", lm_parallelism="ep", lm_experts=16,
                      lm_moe_top_k=3, lm_experts_held=4)
    assert cfg.lm_experts_held == 4
    assert (ROW.gdn_key_heads, ROW.gdn_value_heads, ROW.gdn_key_dim,
            ROW.gdn_value_dim, ROW.gdn_conv) == tuple(PUBLISHED[k] for k in (
                "linear_num_key_heads", "linear_num_value_heads",
                "linear_key_head_dim", "linear_value_head_dim",
                "linear_conv_kernel_dim"))
    assert ROW.norm_eps == PUBLISHED["rms_norm_eps"]
    assert ROW.rope_theta == PUBLISHED["rope_theta"]
    assert ROW.zero_centred_norm and ROW.shared_gate and ROW.attn_gate \
        and ROW.head_qk_norm and ROW.gate_norm and ROW.shared_experts == 1


def _tiny_checkpoint(tmp_path):
    from ps_pytorch_tpu.runtime import checkpoint as ckpt
    from ps_pytorch_tpu.runtime.lm_eval import build_lm_template
    cfg = TrainConfig(network="MoETransformerLM", lm_arch="qwen3next",
                      lm_parallelism="ep", lm_vocab=VOCAB, lm_d_model=D,
                      lm_layers=4, lm_heads=4, lm_kv_heads=2, lm_head_dim=16,
                      lm_ffn_dim=16, lm_experts=16, lm_moe_top_k=3,
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


def _dense(**kw):
    from ps_pytorch_tpu.models.transformer import TransformerLM
    return TransformerLM(vocab_size=VOCAB, n_layers=4, n_heads=4, d_model=D,
                         arch="qwen3next", **kw)


def _refused_by_tp(tmp_path):
    from ps_pytorch_tpu.parallel.tp import make_tp_train_step
    make_tp_train_step(_dense(), None, None, None)


def _refused_by_pp(tmp_path):
    from ps_pytorch_tpu.parallel.pp import make_pp_train_step
    make_pp_train_step(_dense(), None, None, None, num_microbatches=1)


def _refused_by_ring(tmp_path):
    refuse_hybrid("qwen3next", "ring attention")


def _refused_by_decode(tmp_path):
    tokens = jnp.zeros((1, 1), jnp.int32)
    model = _model(n_layers=1, decode=True, decode_cache_len=8)
    model.init(jax.random.key(0), tokens)


@pytest.mark.parametrize("entry,where,lacks", [
    (_refused_by_generate, "generate.py", "matrix state"),
    (_refused_by_serve, "serve.py", "matrix state"),
    (_refused_by_decode, "decode", "matrix state"),
    (_refused_by_tp, "tensor parallelism", "model axis"),
    (_refused_by_pp, "pipeline parallelism", "more than one kind"),
    (_refused_by_ring, "ring attention", "sequence shards"),
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
        pytest.fail(f"{where} did not refuse lm_arch=qwen3next")
    assert "lm_arch=qwen3next is not built for " + where in message
    assert lacks in message and "lm_parallelism ep" in message
    refuse_hybrid("olmoe", where)       # and no arch without such layers is
    refuse_hybrid("qwen3next", "expert parallelism")    # nor is its own path
