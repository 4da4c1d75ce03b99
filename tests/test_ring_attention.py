"""Ring attention + sequence-parallel LM tests on the 8-device CPU mesh.

Oracle: the unsharded full-attention implementation. The ring path must match
it numerically with the sequence sharded 8 ways — including causal masking
across shard boundaries and gradient flow through the ppermute ring.
"""

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from ps_pytorch_tpu.models.transformer import cached_attention
from ps_pytorch_tpu.parallel.ring import full_attention, make_ring_attention

B, H, S, D = 2, 4, 64, 16


def seq_mesh():
    return Mesh(np.array(jax.devices()[:8]), ("data",))


def _qkv(rng):
    mk = lambda: jnp.asarray(rng.normal(size=(B, H, S, D)).astype(np.float32))
    return mk(), mk(), mk()


@pytest.mark.parametrize("causal", [False, True])
def test_ring_matches_full(rng, causal):
    from ps_pytorch_tpu.parallel.ring import full_attention, make_ring_attention

    q, k, v = _qkv(rng)
    want = full_attention(q, k, v, causal=causal)
    got = make_ring_attention(seq_mesh(), causal=causal)(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_ring_gradients_match_full(rng):
    """Gradients w.r.t. q/k/v must flow correctly through the ring
    (ppermute transposes)."""
    from functools import partial
    from ps_pytorch_tpu.parallel.ring import full_attention, ring_attention
    from jax.sharding import PartitionSpec as P

    q, k, v = _qkv(rng)
    mesh = seq_mesh()
    spec = P(None, None, "data", None)

    def loss_ring(q, k, v):
        out = jax.shard_map(
            partial(ring_attention, axis_name="data", causal=True),
            mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
            check_vma=False)(q, k, v)
        return jnp.sum(out ** 2)

    def loss_full(q, k, v):
        return jnp.sum(full_attention(q, k, v, causal=True) ** 2)

    g_ring = jax.jit(jax.grad(loss_ring, argnums=(0, 1, 2)))(q, k, v)
    g_full = jax.grad(loss_full, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_ring, g_full):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-5, atol=5e-5)


def test_transformer_ring_matches_full(rng):
    """Same params: sharded ring-attention forward == unsharded forward."""
    from functools import partial
    from jax.sharding import PartitionSpec as P
    from ps_pytorch_tpu.models.transformer import TransformerLM

    mesh = seq_mesh()
    tokens = jnp.asarray(rng.integers(0, 256, size=(2, S)).astype(np.int32))
    full = TransformerLM(attention_impl="full", max_seq_len=S)
    ring = TransformerLM(attention_impl="ring", axis_name="data", max_seq_len=S)
    variables = full.init(jax.random.key(0), tokens)
    want = full.apply(variables, tokens)

    def shard_fwd(params, toks):
        idx = jax.lax.axis_index("data")
        s_local = toks.shape[1]
        positions = idx * s_local + jnp.arange(s_local)
        return ring.apply({"params": params}, toks, positions=positions)

    got = jax.jit(jax.shard_map(
        shard_fwd, mesh=mesh, in_specs=(P(), P(None, "data")),
        out_specs=P(None, "data"), check_vma=False,
    ))(variables["params"], tokens)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


def test_sp_train_step_matches_single_device(rng):
    """One sequence-parallel train step == the same step computed unsharded."""
    import optax
    from ps_pytorch_tpu.models.transformer import TransformerLM
    from ps_pytorch_tpu.optim import sgd
    from ps_pytorch_tpu.parallel.sp import (
        create_lm_train_state, make_sp_train_step,
    )

    mesh = seq_mesh()
    tokens = jnp.asarray(rng.integers(0, 256, size=(2, S)).astype(np.int32))
    tx = sgd(lr=0.1, momentum=0.9)
    ring = TransformerLM(attention_impl="ring", axis_name="data", max_seq_len=S)
    full = TransformerLM(attention_impl="full", max_seq_len=S)

    state = create_lm_train_state(ring, tx, mesh, (2, S))
    step_fn = make_sp_train_step(ring, tx, mesh, donate=False)
    new_state, m = step_fn(state, tokens)
    sp_loss = float(m["loss"])

    # Unsharded oracle with identical init (same key/shapes -> same params).
    params0 = jax.device_get(state.params)
    opt0 = tx.init(params0)

    def loss_fn(params):
        logits = full.apply({"params": params}, tokens)
        per_tok = optax.softmax_cross_entropy_with_integer_labels(
            logits[:, :-1], tokens[:, 1:])
        return per_tok.mean()

    want_loss, grads = jax.value_and_grad(loss_fn)(params0)
    updates, _ = tx.update(grads, opt0, params0)
    want_params = optax.apply_updates(params0, updates)

    assert sp_loss == pytest.approx(float(want_loss), abs=2e-5)
    for a, b in zip(jax.tree.leaves(jax.device_get(new_state.params)),
                    jax.tree.leaves(want_params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4)


# ---- bfloat16 inputs: float32 statistics in the paths no cell runs ----

class _Cached(nn.Module):
    length: int

    @nn.compact
    def __call__(self, q, k, v):
        return cached_attention(self, q, k, v, self.length)


def _bf16_attention(path, q, k, v):
    if path == "full":
        return full_attention(q, k, v, causal=True)
    if path == "ring":
        mesh = Mesh(np.array(jax.devices()), ("data",))
        return make_ring_attention(mesh, "data", causal=True)(q, k, v)
    out, _ = _Cached(q.shape[2]).apply({}, q, k, v, mutable=["cache"])
    return out


@pytest.mark.parametrize("path", ["full", "ring", "cached"])
def test_bfloat16_inputs_stay_within_one_rounding_of_float32(path):
    """bfloat16 q, k, v at S=1024: scores, max, sum and the running values
    are float32, so the output (in ``q.dtype``) is within one bfloat16 ulp at
    the output's scale of the float32 result on the same rounded inputs.
    With scores and the softmax in bfloat16 (before PR 28) the error was 3.5
    times that: a score near 8 is good to 0.03, 3% of its exponential."""
    ks = jax.random.split(jax.random.key(0), 3)
    q, k, v = ((jax.random.normal(kk, (1, 2, 1024, 64)) * 1.5)
               .astype(jnp.bfloat16) for kk in ks)
    want = full_attention(*(x.astype(jnp.float32) for x in (q, k, v)),
                          causal=True)
    got = _bf16_attention(path, q, k, v)
    assert got.dtype == jnp.bfloat16
    err = float(jnp.abs(got.astype(jnp.float32) - want).max())
    assert err <= float(jnp.abs(want).max()) * 2 ** -8, err
