"""Flash attention (ops/flash_attention.py) vs the materializing oracle.

The oracle is ``ring.full_attention`` — the same reference the ring kernel
is tested against (test_ring_attention.py), so all three attention paths
(full / ring / flash) are pinned to one definition of correctness.
Runs in Pallas interpreter mode on the CPU mesh; the TPU path compiles the
identical kernels under Mosaic.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ps_pytorch_tpu.ops.flash_attention import flash_attention
from ps_pytorch_tpu.parallel.ring import full_attention


def _qkv(b=2, h=2, s=256, d=64, dtype=jnp.float32, seed=0):
    ks = jax.random.split(jax.random.key(seed), 3)
    shape = (b, h, s, d)
    return tuple(jax.random.normal(k, shape, dtype) for k in ks)


@pytest.mark.parametrize("causal", [False, True])
def test_forward_matches_oracle(causal):
    q, k, v = _qkv()
    got = flash_attention(q, k, v, causal=causal, block_q=128, block_kv=128)
    want = full_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


def test_forward_uneven_blocks():
    # block_q != block_kv exercises the partially-masked diagonal tiles
    q, k, v = _qkv(s=256)
    got = flash_attention(q, k, v, causal=True, block_q=128, block_kv=64)
    want = full_attention(q, k, v, causal=True)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


def test_forward_single_block():
    # S == block: the online-softmax loop degenerates to one tile
    q, k, v = _qkv(s=128)
    got = flash_attention(q, k, v, causal=True, block_q=256, block_kv=256)
    want = full_attention(q, k, v, causal=True)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_gradients_match_oracle(causal):
    q, k, v = _qkv(s=256)
    w = jax.random.normal(jax.random.key(9), q.shape, jnp.float32)

    def loss(fn):
        return lambda q, k, v: jnp.sum(fn(q, k, v) * w)

    f = lambda q, k, v: flash_attention(q, k, v, causal=causal,
                                        block_q=128, block_kv=128)
    g = lambda q, k, v: full_attention(q, k, v, causal=causal)
    got = jax.grad(loss(f), argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(loss(g), argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(got, want, "qkv"):
        np.testing.assert_allclose(a, b, rtol=5e-4, atol=5e-4,
                                   err_msg=f"d{name}")


def test_bf16_forward_close():
    q, k, v = _qkv(dtype=jnp.bfloat16)
    got = flash_attention(q, k, v, causal=True, block_q=128, block_kv=128)
    want = full_attention(q.astype(jnp.float32), k.astype(jnp.float32),
                          v.astype(jnp.float32), causal=True)
    assert got.dtype == jnp.bfloat16
    np.testing.assert_allclose(got.astype(jnp.float32), want,
                               rtol=2e-2, atol=2e-2)


def test_unalignable_seq_raises():
    # S with no power-of-two block divisor >= 8: no quiet materializing path
    q, k, v = _qkv(s=36, d=64)
    with pytest.raises(ValueError, match="S=36"):
        flash_attention(q, k, v, causal=True)


def test_pp_flash_matches_pp_full():
    # flash inside the per-stage shard_map: the newly-legal PP path must
    # equal the full-attention PP step (same init) to fp tolerance.
    from ps_pytorch_tpu.runtime.lm_trainer import LMTrainer
    tok = np.random.default_rng(3).integers(0, 256, (8, 256))
    tokens = jnp.asarray(tok, jnp.int32)
    losses = {}
    for impl in ("full", "flash"):
        tr = LMTrainer(_lm_cfg(lm_parallelism="pp", lm_attention=impl))
        st = tr.state
        for i in range(3):
            st, m = tr.step_fn(st, tokens)
        losses[impl] = float(m["loss"])
    np.testing.assert_allclose(losses["flash"], losses["full"],
                               rtol=1e-4, atol=1e-5)


def test_moe_flash_matches_moe_full():
    from ps_pytorch_tpu.models.moe import MoETransformerLM
    tok = jnp.asarray(np.random.default_rng(4).integers(0, 64, (2, 128)),
                      jnp.int32)
    kw = dict(vocab_size=64, d_model=64, n_layers=2, n_heads=2,
              n_experts=4, max_seq_len=128)
    m_full = MoETransformerLM(attention_impl="full", **kw)
    m_flash = MoETransformerLM(attention_impl="flash", **kw)
    params = m_full.init(jax.random.key(0), tok)
    lg_full, aux_full = m_full.apply(params, tok)
    lg_flash, aux_flash = m_flash.apply(params, tok)
    np.testing.assert_allclose(lg_flash, lg_full, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(aux_flash, aux_full, rtol=1e-5, atol=1e-6)


def _lm_cfg(**kw):
    from ps_pytorch_tpu.config import TrainConfig
    base = dict(dataset="synthetic", network="LeNet", batch_size=8, lr=0.1,
                momentum=0.9, lm_seq_len=256, lm_layers=8, lm_heads=4,
                lm_d_model=64)
    base.update(kw)
    return TrainConfig(**base)


def test_config_rejects_unknown_attention():
    with pytest.raises(ValueError, match="lm_attention"):
        _lm_cfg(lm_attention="turbo")


def test_tp_rejects_flash():
    # GSPMD cannot partition the fused kernel over heads (lm_trainer guard)
    from ps_pytorch_tpu.runtime.lm_trainer import LMTrainer
    with pytest.raises(ValueError, match="flash.*not supported.*tp"):
        LMTrainer(_lm_cfg(lm_parallelism="tp", lm_attention="flash"))


def test_sp_multidevice_rejects_sequence_local_attention():
    # sp over >1 device shards the sequence; full/flash are sequence-local
    from ps_pytorch_tpu.runtime.lm_trainer import LMTrainer
    for impl in ("flash", "full"):
        with pytest.raises(ValueError, match="sequence-local"):
            LMTrainer(_lm_cfg(lm_parallelism="sp", lm_attention=impl))


def test_model_flash_impl_matches_full():
    # end-to-end: TransformerLM(attention_impl="flash") == ("full"), fwd+grad
    from ps_pytorch_tpu.models.transformer import TransformerLM

    def build(impl):
        return TransformerLM(vocab_size=64, d_model=64, n_layers=2,
                             n_heads=2, max_seq_len=128, attention_impl=impl)

    tok = jax.random.randint(jax.random.key(1), (2, 128), 0, 64)
    m_full, m_flash = build("full"), build("flash")
    params = m_full.init(jax.random.key(0), tok)

    def loss(m, p):
        logits = m.apply(p, tok)
        tgt = jnp.roll(tok, -1, axis=1)
        lp = jax.nn.log_softmax(logits.astype(jnp.float32), -1)
        return -jnp.mean(jnp.take_along_axis(lp, tgt[..., None], -1))

    l_full, g_full = jax.value_and_grad(lambda p: loss(m_full, p))(params)
    l_flash, g_flash = jax.value_and_grad(lambda p: loss(m_flash, p))(params)
    np.testing.assert_allclose(l_flash, l_full, rtol=1e-5, atol=1e-5)
    flat_f, _ = jax.flatten_util.ravel_pytree(g_full)
    flat_x, _ = jax.flatten_util.ravel_pytree(g_flash)
    np.testing.assert_allclose(flat_x, flat_f, rtol=1e-3, atol=1e-4)
