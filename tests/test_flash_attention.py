"""Flash attention (ops/flash_attention.py) vs the materializing oracle.

The oracle is ``ring.full_attention`` — the same reference the ring kernel
is tested against (test_ring_attention.py), so all three attention paths
(full / ring / flash) are pinned to one definition of correctness.
Runs in Pallas interpreter mode on the CPU mesh; the TPU path compiles the
identical kernels under Mosaic.

Last in the file: the kernels
under fewer key/value heads than query heads and under a window, output and
all three gradients against the same oracle; the schedule's band against a
brute-force count; both passes of the long-context cells' schedules,
pinned; and a value wider than the keys (a differential pair's two value
heads side by side), against the same oracle on the same ``(q, k, v)``, with
the one-width schedules held where they were.
"""

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ps_pytorch_tpu.ops.flash_attention import (
    VMEM_BUDGET_BYTES, VMEM_LIMIT_BYTES, FlashSchedule, _flash, _schedule,
    flash_attention, flash_schedule,
)
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


# What the schedule adds (PR 26): several heads a step, a K/V block of several
# compute tiles, a grid that keeps a kv axis with causally dead blocks, the
# single backward pass at each. (b, h, s, d, causal, block kwargs)
SCHEDULE_CASES = {
    "g3_bh_not_pow2": (3, 8, 64, 64, True, dict(block_q=32, block_kv=32)),
    "kv_block_of_4_tiles": (1, 2, 256, 64, True,
                            dict(block_q=64, block_kv=64)),
    "kv_axis_dead_blocks": (1, 4, 256, 64, True,
                            dict(block_q=64, block_kv=32,
                                 block_kv_major=64)),
    "kv_axis_noncausal": (1, 2, 128, 64, False,
                          dict(block_q=64, block_kv=32, block_kv_major=64)),
    "s8": (2, 3, 8, 64, True, {}),
    "noncausal_default": (2, 2, 128, 64, False, {}),
    "head_dim_128": (1, 2, 128, 128, True, dict(block_q=64, block_kv=64)),
    "tall_q_wide_kv": (1, 2, 256, 64, True, dict(block_q=128, block_kv=32)),
    "wide_q_tall_kv": (1, 2, 256, 64, True, dict(block_q=32, block_kv=128)),
}


def _case(name):
    b, h, s, d, causal, kw = SCHEDULE_CASES[name]
    return _qkv(b=b, h=h, s=s, d=d, seed=3), causal, kw


@pytest.mark.parametrize("name", SCHEDULE_CASES)
def test_schedule_case_is_what_it_says(name):
    (q, _, _), causal, kw = _case(name)
    b, h, s, d = q.shape
    sc = flash_schedule(b * h, s, d, 4, causal, **kw)
    assert (b * h) % sc.g == 0 and s % sc.block_kv_major == 0
    assert sc.bwd_g == sc.g and s % sc.bwd_block_kv_major == 0
    if name == "g3_bh_not_pow2":
        assert sc.g == 3
    if name == "kv_block_of_4_tiles":
        assert sc.block_kv_major == sc.bwd_block_kv_major == 4 * sc.block_kv
    if name.startswith("kv_axis"):
        assert sc.grid[2] == s // 64 and sc.bwd_grid[1] == s // 64
        assert sc.dq_partials == s // 64
        assert (sc.dead > 0) == causal


@pytest.mark.parametrize("name", SCHEDULE_CASES)
def test_schedule_forward_matches_oracle(name):
    (q, k, v), causal, kw = _case(name)
    got = flash_attention(q, k, v, causal=causal, **kw)
    want = full_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("name", SCHEDULE_CASES)
def test_schedule_gradients_match_oracle(name):
    (q, k, v), causal, kw = _case(name)
    w = jax.random.normal(jax.random.key(9), q.shape, jnp.float32)
    f = lambda q, k, v: jnp.sum(
        flash_attention(q, k, v, causal=causal, **kw) * w)
    g = lambda q, k, v: jnp.sum(full_attention(q, k, v, causal=causal) * w)
    got = jax.grad(f, argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(g, argnums=(0, 1, 2))(q, k, v)
    for a, b, leaf in zip(got, want, "qkv"):
        np.testing.assert_allclose(a, b, rtol=5e-4, atol=5e-4,
                                   err_msg=f"{name} d{leaf}")


@pytest.mark.parametrize("name", ["g3_bh_not_pow2", "kv_axis_dead_blocks"])
def test_schedule_bf16_close(name):
    (q, k, v), causal, kw = _case(name)
    qb, kb, vb = (t.astype(jnp.bfloat16) for t in (q, k, v))
    got = flash_attention(qb, kb, vb, causal=causal, **kw)
    want = full_attention(*(t.astype(jnp.float32) for t in (qb, kb, vb)),
                          causal=causal)
    assert got.dtype == jnp.bfloat16
    np.testing.assert_allclose(got.astype(jnp.float32), want,
                               rtol=2e-2, atol=2e-2)
    # gradients come back in the input dtype (dQ through its f32 scratch,
    # or through f32 partials where the grid keeps a kv axis)
    loss = lambda fn: lambda q, k, v: jnp.sum(
        fn(q, k, v, causal=causal).astype(jnp.float32))
    got = jax.grad(loss(partial(flash_attention, **kw)),
                   argnums=(0, 1, 2))(qb, kb, vb)
    want = jax.grad(loss(full_attention), argnums=(0, 1, 2))(
        *(t.astype(jnp.float32) for t in (qb, kb, vb)))
    for a, b, leaf in zip(got, want, "qkv"):
        assert a.dtype == jnp.bfloat16
        np.testing.assert_allclose(
            a.astype(jnp.float32), b, rtol=3e-2,
            atol=3e-2 * float(jnp.abs(b).max()), err_msg=f"{name} d{leaf}")


# What only a sequence too long for the VMEM budget reaches through
# flash_schedule: the backward grid keeps a q axis too, so whole steps are
# dead, dK/dV build up over steps and dQ comes as partials; and, under
# grouped-query heads, the group's query heads as further positions of that
# axis. name -> (heads, kv_heads, window, [(bq, bkv, forward's kv rows,
# backward's kv rows, backward's q rows)])
BACKWARD_AXES = {
    "equal_heads": (4, 4, 0, [(64, 64, 64, 64, 64), (32, 64, 128, 128, 64)]),
    "group_along_the_grid": (4, 2, 0, [(64, 64, 256, 64, 64),
                                       (32, 64, 128, 128, 64)]),
    "group_whole_kv_head": (6, 2, 0, [(32, 32, 256, 256, 64)]),
    "group_window_of_3_tiles": (4, 2, 96, [(32, 32, 256, 64, 64),
                                           (32, 32, 64, 128, 128)]),
    "group_window_off_the_tile": (4, 1, 40, [(32, 16, 256, 64, 128),
                                             (64, 32, 128, 32, 64)]),
    "group_window_under_a_tile": (8, 2, 17, [(64, 32, 256, 64, 64)]),
    "window_equal_heads": (2, 2, 100, [(32, 64, 128, 64, 128)]),
}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("name", sorted(BACKWARD_AXES))
def test_backward_with_q_and_kv_axes(name, dtype):
    h, h_kv, window, blocks = BACKWARD_AXES[name]
    s, d = 256, 64
    q, _, _ = _qkv(b=1, h=h, s=s, seed=5)
    _, k, v = _qkv(b=1, h=h_kv, s=s, seed=6)
    q, k, v = (t.astype(dtype) for t in (q, k, v))
    for bq, bkv, fwd_kvm, kvm, qm in blocks:
        sc = _schedule(h, s, d, q.dtype.itemsize, True, min(h_kv, 2), bq, bkv,
                       fwd_kvm, kvm, qm, group=h // h_kv, window=window)
        assert (sc.bwd_live < sc.bwd_steps) == (kvm < s)
        assert sc.dq_partials == s // kvm
        assert sc.bwd_grid == (h_kv // min(h_kv, 2), s // kvm,
                               (h // h_kv) * (s // qm))
        assert (sc.live < sc.steps) == (fwd_kvm < s)
        assert sc.bwd_visits == sc.live_tiles

        def flash(q, k, v):
            r = lambda t: t.reshape(-1, s, d)
            return _flash(r(q), r(k), r(v), True, 0.125, sc,
                          True).reshape(q.shape).astype(jnp.float32)
        f32 = tuple(t.astype(jnp.float32) for t in (q, k, v))
        tol = 5e-4 if dtype == jnp.float32 else 3e-2
        got = jax.grad(lambda *a: jnp.sum(flash(*a) ** 2),
                       argnums=(0, 1, 2))(q, k, v)
        want = jax.grad(lambda *a: jnp.sum(full_attention(
            *a, causal=True, window=window or None) ** 2),
            argnums=(0, 1, 2))(*f32)
        for a, b, leaf in zip(got, want, "qkv"):
            assert a.dtype == dtype and a.shape == b.shape
            np.testing.assert_allclose(
                a.astype(jnp.float32), b, rtol=tol,
                atol=tol * max(1.0, float(jnp.abs(b).max())),
                err_msg=f"{(bq, bkv, fwd_kvm, kvm, qm)} d{leaf}")


# The three LM cells of BENCHMARK.json: (bh, s, d) -> what a later
# change must not quietly bring back (1,024 / 512 / 4,096 steps a call).
CELL_SHAPES = {
    "gpt2m_s1024": (64, 1024, 64),
    "gpt2m_s128": (512, 128, 64),
    "olmoe_s4096": (16, 4096, 128),
}
CELL_STEPS = {  # item size -> forward steps, backward steps
    "gpt2m_s1024": {4: (32, 32), 2: (16, 16)},
    "gpt2m_s128": {4: (16, 16), 2: (8, 8)},
    "olmoe_s4096": {4: (128, 16), 2: (64, 8)},
}
# Heads a step / a tile at item size 2, what the cells run since PR 28 (the
# model feeds the kernels bfloat16): within 3% of the best of its own
# candidates on a v5e at all three shapes (PERF.md section 6, PR 28).
CELL_HEADS_2 = {"gpt2m_s1024": (4, 1), "gpt2m_s128": (64, 8),
                "olmoe_s4096": (2, 1)}
# ... and every block and grid of both passes there, as PR 33 ran them: the
# compute tile, K/V rows a step, q/dO rows of a backward step, the two grids.
CELL_BLOCKS_2 = {
    "gpt2m_s1024": (1024, 1024, 1024, 1024, (16, 1, 1), (16, 1, 1)),
    "gpt2m_s128": (128, 128, 128, 128, (8, 1, 1), (8, 1, 1)),
    "olmoe_s4096": (512, 512, 4096, 4096, (8, 8, 1), (8, 1, 1)),
}


@pytest.mark.parametrize("itemsize", [4, 2])
@pytest.mark.parametrize("cell", CELL_SHAPES)
def test_schedule_of_the_cells(cell, itemsize):
    bh, s, d = CELL_SHAPES[cell]
    sc = flash_schedule(bh, s, d, itemsize, True)
    if itemsize == 2:
        assert (sc.g, sc.block_h) == CELL_HEADS_2[cell]
        assert (sc.block_q, sc.block_kv, sc.block_kv_major, sc.block_q_major,
                sc.grid, sc.bwd_grid) == CELL_BLOCKS_2[cell]
    # equal heads: the backward holds the forward's heads and K/V rows
    assert (sc.bwd_g, sc.bwd_block_kv_major) == (sc.g, sc.block_kv_major)
    assert bh % sc.g == 0
    for block in (sc.block_q, sc.block_kv, sc.block_kv_major,
                  sc.block_q_major):
        assert s % block == 0
    assert sc.block_kv_major % sc.block_kv == 0
    assert sc.block_q_major % sc.block_q == 0
    assert sc.vmem_bytes <= sc.bwd_vmem_bytes <= VMEM_BUDGET_BYTES \
        < VMEM_LIMIT_BYTES
    assert sc.grid == (bh // sc.g, s // sc.block_q, s // sc.block_kv_major)
    assert sc.steps == sc.grid[0] * sc.grid[1] * sc.grid[2]
    assert (sc.steps, sc.bwd_steps) == CELL_STEPS[cell][itemsize]
    assert sc.live == sc.steps and sc.bwd_live == sc.bwd_steps
    # one kv block, one q block: dQ leaves as dQ, every kv tile is visited
    assert sc.dq_partials == 1 and sc.bwd_visits == sc.live_tiles
    assert sc.describe().startswith(f"g={sc.g}/{sc.block_h} bq={sc.block_q} ")
    assert f" bwd g={sc.bwd_g}/{sc.block_h} " in sc.describe()


def test_schedule_counts_dead_steps_and_halves_to_fit():
    # a forced kv axis: S/bq x S/kvm blocks, the ones above the diagonal dead
    sc = flash_schedule(4, 1024, 64, 4, True, block_q=256,
                        block_kv_major=256)
    assert sc.grid[1:] == (4, 4) and sc.steps == 16 * sc.grid[0]
    assert sc.live == 10 * sc.grid[0] and sc.dead == 6 * sc.grid[0]
    assert flash_schedule(4, 1024, 64, 4, False, block_q=256,
                          block_kv_major=256).dead == 0
    # a sequence whose one head does not fit whole: blocks halve, g stays 1
    big = flash_schedule(8, 32768, 128, 4, True)
    assert big.g == 1 and big.vmem_bytes <= VMEM_BUDGET_BYTES
    assert big.bwd_g == 1 and big.bwd_vmem_bytes <= VMEM_BUDGET_BYTES
    assert big.block_kv_major < 32768 and big.bwd_live < big.bwd_steps
    # each pass by its own blocks: the forward's K/V rows are not the
    # backward's, which also keeps q/dO/dQ rows and the dK/dV sums
    assert big.bwd_block_kv_major < big.block_kv_major


def test_schedule_is_pure_and_adapts_to_itemsize():
    a = flash_schedule(64, 1024, 64, 4, True)
    assert a == flash_schedule(64, 1024, 64, 4, True)
    assert flash_schedule(64, 1024, 64, 2, True).g >= a.g


def test_unalignable_seq_raises():
    # S with no power-of-two block divisor >= 8: no quiet materializing path
    q, k, v = _qkv(s=36, d=64)
    with pytest.raises(ValueError, match="S=36"):
        flash_attention(q, k, v, causal=True)
    with pytest.raises(ValueError, match="S=36"):
        flash_schedule(4, 36, 64, 4, True)


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


# ---- fewer key/value heads than query heads, and a window -----------------------

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


# ---- a value wider than the keys ---------------------------------------------------

# q, k [1, heads | kv_heads, 256, 64] and v [1, kv_heads, 256, 128], the
# widths of the Phi-4-flash cell's calls: name -> (heads, kv_heads, window,
# (bq, bkv, forward's kv rows, backward's kv rows, backward's q rows)), every
# one with several compute tiles a step and, bar the last (the cell's form: a
# K/V head whole), several kv blocks a call, the grouped ones with dK/dV
# summed over the steps of a group and of q blocks.
WIDE_VALUE = {
    "causal_group_1": (2, 2, 0, (64, 64, 128, 128, 256)),
    "causal_group_2": (4, 2, 0, (32, 64, 128, 128, 64)),
    "window_group_1": (2, 2, 100, (32, 64, 128, 64, 128)),
    "window_group_2": (4, 2, 40, (64, 32, 128, 64, 128)),
    "window_group_2_whole_kv_head": (4, 2, 96, (32, 32, 256, 256, 64)),
}


def _wide_case(name, dtype):
    h, h_kv, window, blocks = WIDE_VALUE[name]
    s, d, dv = 256, 64, 128
    ks = jax.random.split(jax.random.key(11), 4)
    q, k, v = (jax.random.normal(key, (1, n, s, w)).astype(dtype)
               for key, n, w in zip(ks, (h, h_kv, h_kv), (d, d, dv)))
    sc = _schedule(h, s, d, q.dtype.itemsize, True, 1, *blocks,
                   group=h // h_kv, window=window, dv=dv)

    def flash(q, k, v):
        r = lambda t: t.reshape((-1,) + t.shape[2:])
        return _flash(r(q), r(k), r(v), True, d ** -0.5, sc,
                      True).reshape(1, h, s, dv)
    full = partial(full_attention, causal=True, window=window or None)
    return (q, k, v), jax.random.normal(ks[3], (1, h, s, dv)), sc, flash, full


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("name", sorted(WIDE_VALUE))
def test_a_value_twice_the_keys_width_against_full(name, dtype):
    """Output [B, H, S, dv] and all three gradients (dQ and dK as wide as the
    keys, dV as wide as the value) against ``full_attention`` on the same
    ``(q, k, v)``; the scale is the keys' width's."""
    qkv, w, sc, flash, full = _wide_case(name, dtype)
    bkv = sc.block_kv
    assert sc.dv == 128 and " dv=128 " in sc.describe()
    assert sc.block_kv_major > bkv or sc.bwd_block_kv_major > bkv
    # several kv blocks a call in either pass, bar the cell's own form
    assert (sc.grid[2] > 1 and sc.bwd_grid[1] > 1) \
        == ("whole_kv_head" not in name)
    f32 = tuple(t.astype(jnp.float32) for t in qkv)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    got, want = flash(*qkv), full(*f32)
    assert got.dtype == dtype and got.shape == want.shape == w.shape
    np.testing.assert_allclose(got.astype(jnp.float32), want, rtol=tol,
                               atol=tol)
    loss = lambda fn: lambda *a: jnp.sum(fn(*a).astype(jnp.float32) * w)
    got = jax.grad(loss(flash), argnums=(0, 1, 2))(*qkv)
    want = jax.grad(loss(full), argnums=(0, 1, 2))(*f32)
    tol = 5e-4 if dtype == jnp.float32 else 3e-2
    for a, b, t, leaf in zip(got, want, qkv, "qkv"):
        assert a.dtype == dtype and a.shape == b.shape == t.shape
        np.testing.assert_allclose(
            a.astype(jnp.float32), b, rtol=tol,
            atol=tol * max(1.0, float(jnp.abs(b).max())),
            err_msg=f"{name} d{leaf}")


@pytest.mark.parametrize("window", [None, 40])
def test_a_wide_value_by_the_default_schedule(window):
    """``flash_attention`` reads the value's width from ``v``: the public
    call, the schedule its own (a whole row of scores a tile at this S)."""
    ks = jax.random.split(jax.random.key(12), 3)
    q, k, v = (jax.random.normal(key, (2, n, 128, w))
               for key, n, w in zip(ks, (4, 2, 2), (16, 16, 32)))
    got = flash_attention(q, k, v, causal=True, window=window)
    want = full_attention(q, k, v, causal=True, window=window)
    assert got.shape == (2, 4, 128, 32)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    # the same call with the value cut to the keys' width is the old call
    np.testing.assert_allclose(
        flash_attention(q, k, v[..., :16], causal=True, window=window),
        got[..., :16], rtol=2e-5, atol=2e-5)


ONE_WIDTH_SHAPES = {
    **{f"{cell}_itemsize_{i}": (bh, None, s, d, i, None)
       for cell, (bh, s, d) in CELL_SHAPES.items() for i in (4, 2)},
    **{name: SCHEDULE_BANDS[name][:6] for name in CELL_SCHEDULES},
}


@pytest.mark.parametrize("name", sorted(ONE_WIDTH_SHAPES))
def test_the_keys_width_given_as_the_values_is_the_one_width_schedule(name):
    """``dv`` defaults to ``d``, and given as ``d`` it leaves nothing behind:
    the record is equal field for field and prints no ``dv=``, at every shape
    ``test_schedule_of_the_cells`` and
    ``test_the_long_context_cells_schedules_are_pinned`` pin."""
    bh, bh_kv, s, d, itemsize, window = ONE_WIDTH_SHAPES[name]
    one = flash_schedule(bh, s, d, itemsize, True, window=window, bh_kv=bh_kv)
    assert flash_schedule(bh, s, d, itemsize, True, window=window,
                          bh_kv=bh_kv, dv=d) == one
    assert one.dv == 0 and "dv=" not in one.describe()
    # a wider value is another record, sized to the same budget
    wide = flash_schedule(bh, s, d, itemsize, True, window=window,
                          bh_kv=bh_kv, dv=2 * d)
    assert wide.dv == 2 * d and wide != one
    assert wide.vmem_bytes <= VMEM_BUDGET_BYTES
    assert wide.bwd_vmem_bytes <= VMEM_BUDGET_BYTES


# phi4flash_s8192_1chip since PR 50: a call is one head of each differential
# pair, 20 query heads of 64 over 10 K/V heads and the pairs' values, 10 heads
# of 128. A block's last dimension pads to 128 lanes, so a step holds what
# the call of 64-wide values held (PR 35) and the schedule is that call's.
PHI4FLASH_CALLS = {
    0: FlashSchedule(
        512, 512, 2, 1, 8192, (10, 16, 1), 160, 160, 13697024,
        8192, 4096, (10, 1, 4), 40, 40, 38273024,
        group=2, window=0, tiles=5120, live_tiles=2720, bwd_visits=2720,
        dv=128),
    512: FlashSchedule(
        512, 512, 2, 1, 8192, (10, 16, 1), 160, 160, 13697024,
        8192, 4096, (10, 1, 4), 40, 40, 38273024,
        group=2, window=512, tiles=5120, live_tiles=620, bwd_visits=620,
        dv=128),
}


@pytest.mark.parametrize("window", sorted(PHI4FLASH_CALLS))
def test_the_differential_cells_call_is_pinned(window):
    sc = flash_schedule(20, 8192, 64, 2, True, window=window or None,
                        bh_kv=10, dv=128)
    assert sc == PHI4FLASH_CALLS[window]
    assert sc._replace(dv=0) == flash_schedule(
        20, 8192, 64, 2, True, window=window or None, bh_kv=10)
    kind = " kv_heads=1" + (f" window={window}" if window else "") + " dv=128 "
    assert kind + f"tiles={sc.live_tiles}/5120 " in sc.describe()
