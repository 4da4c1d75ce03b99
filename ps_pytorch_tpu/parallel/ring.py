"""Ring attention — sequence/context parallelism over the device mesh.

The reference has no attention or sequence axis at all (CNNs on 32px images,
SURVEY §5.7); this module is where the framework goes beyond parity: long
sequences are sharded over a mesh axis and attention runs as a ring of
``jax.lax.ppermute`` steps over ICI, overlapping each neighbor exchange of
K/V blocks with the local attention block — the blockwise-attention
formulation in which softmax is computed online (running max + running
normalizer, flash-attention style), so no device ever materializes the full
[S, S] score matrix or the full K/V.

Memory per device: O(S/N * d) for K/V plus O(S/N * S/N) per block product;
communication: (N-1) ppermute hops of the local K/V shard per layer —
bandwidth-optimal on a ring. The loop is structured so each hop's permute
is independent of that iteration's block computation, which lets XLA's
scheduler overlap them; the overlap itself is not yet trace-verified here
(needs a real multi-chip slice; PERF.md §7).

Used by ``models/transformer.py``'s sequence-parallel mode; correctness is
tested against full (unsharded) attention on the 8-device CPU mesh.
"""

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def _block_attn(q, k, v, mask_bias):
    """One attention block: q [B,H,Sq,D] x k,v [B,H,Sk,D] -> (scores-stats,
    weighted values) with numerically safe online-softmax pieces. Scores,
    max, sum and the weighted values are float32 whatever the inputs' dtype
    (a bfloat16 score is good to 0.03 at 8: 3% of its exponential); the
    probabilities enter the PV product in ``v.dtype``, as in the flash
    kernels."""
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                   preferred_element_type=jnp.float32) + mask_bias
    m = jnp.max(s, axis=-1)                                # [B,H,Sq]
    # Fully masked row (m = -inf, e.g. a whole future block under causal
    # masking): exp(s - (-inf)) would be NaN; substitute 0 so p = exp(-inf)=0.
    m_safe = jnp.where(jnp.isfinite(m), m, 0.0)
    p = jnp.exp(s - m_safe[..., None])
    l = jnp.sum(p, axis=-1)                                # [B,H,Sq]
    o = jnp.einsum("bhqk,bhkd->bhqd", p.astype(v.dtype), v,
                   preferred_element_type=jnp.float32)
    return m, l, o


def ring_attention(q, k, v, axis_name: str, *, causal: bool = False,
                   window: Optional[int] = None,
                   scale: Optional[float] = None):
    """Blockwise ring attention over mesh axis ``axis_name``.

    Args (per-device shards, inside shard_map/pjit):
      q, k, v: [B, H, S_local, D] — the sequence axis is sharded over
        ``axis_name``; shard i holds tokens [i*S_local, (i+1)*S_local).
      causal: apply a causal mask over the GLOBAL sequence positions.
    Returns: [B, H, S_local, D] attention output for the local queries, in
    ``q.dtype``; the running max, sum and weighted values cross the hops in
    float32. Fewer key/value heads than query heads, or a ``window``, are
    refused: the hops know equal head counts and the one causal mask.
    """
    if window is not None or k.shape[1] != q.shape[1]:
        raise ValueError(
            f"ring attention is not built for grouped-query heads or a "
            f"window (kv heads {k.shape[1]} of {q.shape[1]}, window "
            f"{window}): use lm_attention full or flash on one device")
    n = jax.lax.axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    s_local = q.shape[2]
    if scale is None:
        scale = q.shape[-1] ** -0.5
    out_dtype = q.dtype
    q = q * scale
    stats = q.shape[:3]

    q_pos = idx * s_local + jax.lax.broadcasted_iota(
        jnp.int32, (s_local, 1), 0).squeeze(-1)            # global q positions

    def kv_positions(src_idx):
        return src_idx * s_local + jax.lax.broadcasted_iota(
            jnp.int32, (s_local, 1), 0).squeeze(-1)

    def mask_bias_for(src_idx):
        if not causal:
            return jnp.zeros((1, 1, s_local, s_local), jnp.float32)
        ok = q_pos[:, None] >= kv_positions(src_idx)[None, :]
        return jnp.where(ok, 0.0, -jnp.inf)[None, None]     # float32

    neg_inf = jnp.full(stats, -jnp.inf, jnp.float32)

    def block_or_skip(k_cur, v_cur, t):
        """Attention block for the K/V currently held (arrived from shard
        (idx - t) mod n); under causal masking a strictly-future source block
        is all-masked, so skip its FLOPs entirely (~halves attention compute
        at large n)."""
        src = (idx - t) % n
        if not causal:
            return _block_attn(q, k_cur, v_cur, mask_bias_for(src))
        return jax.lax.cond(
            src <= idx,
            lambda: _block_attn(q, k_cur, v_cur, mask_bias_for(src)),
            lambda: (neg_inf, jnp.zeros(stats, jnp.float32),
                     jnp.zeros(q.shape, jnp.float32)))

    def merge(m_run, l_run, o_run, m_blk, l_blk, o_blk):
        # Online softmax merge (flash-attention update rule).
        m_new = jnp.maximum(m_run, m_blk)
        # Guard fully-masked blocks (m = -inf): exp(-inf - finite) = 0.
        a = jnp.where(jnp.isfinite(m_run), jnp.exp(m_run - m_new), 0.0)
        b = jnp.where(jnp.isfinite(m_blk), jnp.exp(m_blk - m_new), 0.0)
        return (m_new, a * l_run + b * l_blk,
                a[..., None] * o_run + b[..., None] * o_blk)

    def step(carry, t):
        k_cur, v_cur, m_run, l_run, o_run = carry
        # Kick off the hop to the right neighbor; XLA overlaps it with the
        # block compute below (which reads the pre-hop buffers).
        perm = [(j, (j + 1) % n) for j in range(n)]
        k_nxt = jax.lax.ppermute(k_cur, axis_name, perm)
        v_nxt = jax.lax.ppermute(v_cur, axis_name, perm)
        m_new, l_new, o_new = merge(
            m_run, l_run, o_run, *block_or_skip(k_cur, v_cur, t))
        return (k_nxt, v_nxt, m_new, l_new, o_new), ()

    # n-1 hops: the scan permutes while computing blocks 0..n-2; the last
    # received block is consumed outside the loop with no further hop.
    init = (k, v, neg_inf, jnp.zeros(stats, jnp.float32),
            jnp.zeros(q.shape, jnp.float32))
    (k_f, v_f, m_run, l_run, o_run), _ = jax.lax.scan(
        step, init, jnp.arange(n - 1), length=n - 1)
    m_f, l_f, o_f = merge(
        m_run, l_run, o_run, *block_or_skip(k_f, v_f, n - 1))
    # Fully-masked rows (can't happen for causal with local queries, but keep
    # the kernel total): avoid 0/0.
    l_safe = jnp.where(l_f == 0, 1.0, l_f)
    return (o_f / l_safe[..., None]).astype(out_dtype)


def make_ring_attention(mesh: Mesh, axis_name: str = "data",
                        causal: bool = False):
    """Host-callable wrapper: global [B, H, S, D] q/k/v (S sharded over
    ``axis_name``) -> global [B, H, S, D] output, jitted over the mesh."""
    spec = P(None, None, axis_name, None)

    @jax.jit
    def fn(q, k, v):
        return jax.shard_map(
            partial(ring_attention, axis_name=axis_name, causal=causal),
            mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
            check_vma=False,
        )(q, k, v)

    return fn


def full_attention(q, k, v, *, causal: bool = False,
                   window: Optional[int] = None,
                   scale: Optional[float] = None):
    """Unsharded reference implementation (materializes [S, S]) — the oracle
    ring_attention is tested against. Scores and softmax are float32 for any
    input dtype; the output is ``q.dtype``. ``k`` and ``v`` may hold fewer
    heads than ``q`` (query head h reads head ``h // (H / Hkv)``: repeated
    here, plainly); ``window``: query i sees keys ``i - window < j <= i``."""
    if scale is None:
        scale = q.shape[-1] ** -0.5
    group = q.shape[1] // k.shape[1]
    if group > 1:
        k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    s = jnp.einsum("bhqd,bhkd->bhqk", q * scale, k,
                   preferred_element_type=jnp.float32)
    if causal:
        sq, sk = s.shape[-2], s.shape[-1]
        dist = jnp.arange(sq)[:, None] - jnp.arange(sk)[None, :]
        ok = dist >= 0 if window is None else (dist >= 0) & (dist < window)
        s = jnp.where(ok[None, None], s, -jnp.inf)
    elif window is not None:
        raise ValueError("a window needs causal=True")
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p.astype(v.dtype), v,
                      preferred_element_type=jnp.float32).astype(q.dtype)
