"""Ring attention: exact attention over a sequence-sharded mesh axis.

Long-context support the reference never had (SURVEY.md §5.7 notes its only
answer to sequence length was bucketing): queries stay put while key/value
blocks rotate around the ``seq`` mesh axis via ``ppermute`` — each of the N
ring steps overlaps a local blockwise-attention matmul with the transfer of
the next block over ICI. Softmax is accumulated online (running max + running
denominator, flash-attention style), so the result is EXACT full attention
while no device ever materializes more than (T/N)² scores.

Usage: arrays sharded (B, T/N, H, D) on a mesh with a ``seq`` axis; call
``ring_attention(q, k, v, mesh, seq_axis='seq', causal=...)``.
"""
from __future__ import annotations


import numpy as np

__all__ = ["ring_attention", "local_blockwise_attention"]


def _block_attend(q, k, v, scale, mask):
    """One blockwise contribution: returns (unnormalized out, running max,
    running denom) pieces for online-softmax accumulation."""
    import jax.numpy as jnp

    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale  # (B,H,t,t')
    s = jnp.where(mask, s, -jnp.inf)
    m = jnp.max(s, axis=-1)  # (B,H,t)
    # guard all-masked rows (exp(-inf - -inf))
    m_safe = jnp.where(jnp.isfinite(m), m, 0.0)
    p = jnp.exp(s - m_safe[..., None])
    p = jnp.where(mask, p, 0.0)
    l = jnp.sum(p, axis=-1)  # (B,H,t)
    o = jnp.einsum("bhqk,bkhd->bqhd", p, v)
    return o, m_safe, l


def local_blockwise_attention(q, k, v, scale, causal, q_block, kv_block, block):
    """Attention of one query block against one kv block with global causal
    positions (q starts at q_block·block, k at kv_block·block)."""
    import jax.numpy as jnp

    t, s = q.shape[1], k.shape[1]
    if causal:
        q_pos = q_block * block + jnp.arange(t)
        k_pos = kv_block * block + jnp.arange(s)
        mask = (k_pos[None, :] <= q_pos[:, None])[None, None]
    else:
        mask = jnp.ones((1, 1, t, s), bool)
    return _block_attend(q, k, v, scale, mask)


def ring_attention(q, k, v, mesh, seq_axis="seq", causal=False, scale=None,
                   batch_axis=None):
    """Exact attention with q/k/v sharded on the sequence axis.

    q, k, v: (B, T, H, D) jax arrays (global view), T divisible by the size of
    ``seq_axis``. Returns (B, T, H, D) with the same sharding as q.
    ``batch_axis`` additionally keeps dim 0 sharded (dp x sp execution —
    without it a batch-sharded operand would be gathered at the shard_map
    boundary)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    n = mesh.shape[seq_axis]
    d = q.shape[-1]
    if scale is None:
        scale = 1.0 / np.sqrt(d)
    block = q.shape[1] // n

    def local(qb, kb, vb):
        # qb/kb/vb: (B, T/n, H, D) local shards
        my = jax.lax.axis_index(seq_axis)

        def step(carry, i):
            o, m, l, k_cur, v_cur = carry
            kv_idx = (my - i) % n  # block index currently held
            bo, bm, bl = local_blockwise_attention(
                qb, k_cur, v_cur, scale, causal, my, kv_idx, block)
            # online softmax merge
            m_new = jnp.maximum(m, bm)
            c1 = jnp.exp(m - m_new)
            c2 = jnp.exp(bm - m_new)
            o = o * c1[..., None].swapaxes(1, 2) + bo * c2[..., None].swapaxes(1, 2)
            l = l * c1 + bl * c2
            # rotate kv to the next device (overlaps with the next matmul)
            perm = [(j, (j + 1) % n) for j in range(n)]
            k_next = jax.lax.ppermute(k_cur, seq_axis, perm)
            v_next = jax.lax.ppermute(v_cur, seq_axis, perm)
            return (o, m_new, l, k_next, v_next), None

        B, t, H, D = qb.shape
        # initial accumulators are constants; mark them device-varying so the
        # scan carry type matches the per-shard outputs (shard_map vma check)
        pvary = lambda x, axes: jax.lax.pcast(x, axes, to="varying")
        vary_axes = (seq_axis,) + ((batch_axis,) if batch_axis else ())
        o0 = pvary(jnp.zeros((B, t, H, D), "float32"), vary_axes)
        m0 = pvary(jnp.full((B, H, t), -jnp.inf, "float32"), vary_axes)
        l0 = pvary(jnp.zeros((B, H, t), "float32"), vary_axes)
        (o, m, l, _, _), _ = jax.lax.scan(
            step, (o0, m0, l0, kb.astype("float32"), vb.astype("float32")),
            jnp.arange(n))
        denom = jnp.where(l > 0, l, 1.0)
        out = o / denom[..., None].swapaxes(1, 2)
        return out.astype(qb.dtype)

    spec = P(batch_axis, seq_axis, None, None)
    fn = jax.shard_map(local, mesh=mesh, in_specs=(spec, spec, spec),
                       out_specs=spec)
    return fn(q, k, v)
