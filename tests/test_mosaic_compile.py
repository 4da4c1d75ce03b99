"""The OLMoE cell's grouped matmuls and the SmallThinker cell's flash kernels
compile under Mosaic for a described v5e (no chip): what the Pallas
interpreter cannot show — VMEM over the limit, a
slice off the tiling, a DMA the compiler refuses. One file, one fixture: only
the worker that runs it loads the TPU compiler (on-chip-measurement guide,
section 2)."""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from ps_pytorch_tpu.ops import grouped_matmul
from ps_pytorch_tpu.ops.grouped_matmul import (
    VMEM_LIMIT_BYTES, WEIGHTS_VMEM_BYTES, _tiles, gmm,
)

# olmoe_s4096_1chip: 4096 tokens x top-8 rows, 64 experts of 2048 x 1024
M, D, F, E = 32768, 2048, 1024, 64


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a program compiled for a described chip cannot be read back from the
    # persistent cache without one: keep these compiles out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("rows", ["bfloat16", "float32"])
def test_expert_ffn_forward_and_backward_compile_at_the_cells_shape(
        one_chip, monkeypatch, rows):
    """silu(x Wgate) * (x Wup) Wdown over ragged groups and its gradients:
    nine kernels (fwd, dlhs, drhs at both weight shapes), float32 weights
    under ``rows``, as ``models/moe.DroplessMoE`` calls them."""
    monkeypatch.setattr(grouped_matmul, "interpret_default", lambda: False)

    def loss(xs, wg, wu, wd, gs):
        h = jax.nn.silu(gmm(xs, wg, gs)) * gmm(xs, wu, gs)
        return jnp.sum(gmm(h, wd, gs).astype(jnp.float32))

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3))).lower(
        arg((M, D), rows), arg((E, D, F), jnp.float32),
        arg((E, D, F), jnp.float32), arg((E, F, D), jnp.float32),
        arg((E,), jnp.int32)).compile()
    text = compiled.as_text()
    for name in ("moe_gmm_fwd", "moe_gmm_dlhs", "moe_gmm_drhs"):
        assert name in text
    grads = compiled.output_shardings     # one per argument differentiated
    assert len(grads) == 4


@pytest.mark.parametrize("window", [None, 4096])
def test_flash_grouped_query_window_compiles_at_the_cells_shape(one_chip,
                                                                 window):
    """smallthinker_s16384_1chip: 28 query heads over 4 key/value heads of
    128 at S=16384, bfloat16, forward and the one backward kernel, with and
    without the window; dK and dV come back at the 4 heads."""
    from ps_pytorch_tpu.ops.flash_attention import flash_attention

    def loss(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True, window=window,
                                       interpret=False).astype(jnp.float32))

    def arg(heads):
        return jax.ShapeDtypeStruct((1, heads, 16384, 128), jnp.bfloat16,
                                    sharding=one_chip)

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        arg(28), arg(4), arg(4)).compile()
    text = compiled.as_text()
    names = ("flash_win_fwd", "flash_win_bwd_dkv") if window else \
        ("flash_fwd", "flash_bwd_dkv")
    for name in names:      # a bare kernel's call is named jvp_<name>_
        assert f"{name}_" in text
    assert ("flash_win_" in text) == bool(window)
    assert "bf16[4,16384,128]" in text and "bf16[28,16384,128]" in text


def test_tiles_keep_the_weight_buffers_inside_their_budget():
    """tn follows from K: two float32 [K, tn] buffers and the bfloat16 copy
    stay under WEIGHTS_VMEM_BYTES, whatever the table's target."""
    assert _tiles(M, D, F, jnp.bfloat16, jnp.float32) == (256, 2048, 1024)
    assert _tiles(M, F, D, jnp.bfloat16, jnp.float32) == (256, 1024, 2048)
    for k in (2048, 4096, 8192, 16384):
        for rows in (jnp.bfloat16, jnp.float32):
            tm, tk, tn = _tiles(M, k, 4096, rows, jnp.float32)
            cast = jnp.dtype(rows).itemsize if rows != jnp.float32 else 0
            assert tn % 128 == 0 and 4096 % tn == 0
            assert k * tn * (2 * 4 + cast) <= WEIGHTS_VMEM_BYTES \
                < VMEM_LIMIT_BYTES
    # without weights to hold (moe_gmm_drhs) the table's target stands
    assert _tiles(M, 8192, 4096, jnp.bfloat16) == (256, 2048, 2048)
