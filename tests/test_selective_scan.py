"""``ops/selective_scan.py`` (interpreted): the chunked scan against the
token-by-token one, output and all six gradients; the float32 state under
bfloat16 inputs; what the schedule says a call holds."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ps_pytorch_tpu.ops.selective_scan import (
    scan_schedule, selective_scan, selective_scan_reference,
)


def _scan_inputs(bt=2, s=37, di=40, n=4, seed=0):
    rng = np.random.default_rng(seed)
    f = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)
    delta = jnp.asarray(rng.uniform(0.01, 0.3, (bt, s, di)), jnp.float32)
    a = -jnp.asarray(rng.uniform(0.5, 4, (di, n)), jnp.float32)
    return f(bt, s, di), delta, a, f(bt, s, n), f(bt, s, n), f(di)


@pytest.mark.parametrize("chunk", [8, 16, 37, None],
                         ids=lambda c: f"chunk_{c}")
def test_chunked_scan_agrees_with_the_token_by_token_scan(chunk):
    """S = 37 against chunks that do not divide it (8, 16: the tail is
    padded with tokens the state passes unchanged), one that is S itself and
    the default (cut to S); 40 channels padded to a block of 1024. Output and
    all six gradients, the hand-written backward against autodiff of the
    plain scan."""
    args = _scan_inputs()
    w = jnp.asarray(np.random.default_rng(9).normal(size=args[0].shape),
                    jnp.float32)
    y, state_max = selective_scan(*args, chunk=chunk)
    want = selective_scan_reference(*args)
    assert float(jnp.abs(y - want).max()) < 5e-6
    assert 0.5 < float(state_max) < 10
    got = jax.grad(lambda *a: jnp.sum(selective_scan(*a, chunk=chunk)[0] * w),
                   argnums=tuple(range(6)))(*args)
    want = jax.grad(lambda *a: jnp.sum(selective_scan_reference(*a) * w),
                    argnums=tuple(range(6)))(*args)
    for g, r in zip(got, want):
        assert float(jnp.abs(g - r).max()) < 1e-5 * float(jnp.abs(r).max())


def test_scan_state_is_float32_under_bfloat16_inputs():
    u, delta, a, b, c, d = _scan_inputs(bt=1, s=64)
    y, _ = selective_scan(u.astype(jnp.bfloat16), delta, a, b, c, d, chunk=16)
    assert y.dtype == jnp.bfloat16
    want = selective_scan_reference(u.astype(jnp.bfloat16), delta, a, b, c, d)
    # only the output's rounding: the state did not pass bfloat16
    assert float(jnp.abs(y.astype(jnp.float32) - want).max()) \
        < 2 ** -8 * float(jnp.abs(want).max())


def test_scan_schedule_says_what_a_call_holds():
    sc = scan_schedule(2, 8192, 5120, 16)
    assert (sc.chunk, sc.chunks, sc.blocks) == (128, 64, 5)
    assert sc.carried_bytes == 2 * 5120 * 16 * 4
    assert sc.kept_bytes == 64 * sc.carried_bytes
    assert sc.bwd_vmem_bytes == 129 * 1024 * 16 * 4
    assert "chunk=128 chunks=64" in sc.describe()
    assert scan_schedule(1, 100, 40, 4, 256).chunk == 100
