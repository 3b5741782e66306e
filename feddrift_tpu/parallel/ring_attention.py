"""Ring attention: exact causal attention with the sequence axis sharded
over the device mesh.

The reference has no attention anywhere (its only sequence models are
2-layer LSTMs at seq len 80, fedml_api/model/nlp/rnn.py:4-67; SURVEY.md §5
declares sequence parallelism new design territory). This module makes
long-context a first-class capability of the TPU framework:

- ``blockwise_attention``: flash-style online-softmax attention over key/value
  blocks (activation memory O(L_q * block) instead of O(L^2)), single device.
- ``ring_attention``: the same accumulation with K/V blocks living on
  different devices of a ``seq`` mesh axis; each ring step overlaps the
  partial attention matmul with a ``ppermute`` that rotates the K/V shard to
  the next neighbor over ICI. After ``seq`` steps every query shard has seen
  every key shard — exact attention, never materialising the full sequence
  on any chip.

Layout: [batch, heads, seq, head_dim]; the seq axis of Q/K/V is sharded by
the caller (shard_map over the 'seq' mesh axis). Causal masking uses global
position offsets derived from ``lax.axis_index``.
"""

from __future__ import annotations


import jax
import jax.numpy as jnp
from jax import lax

NEG_INF = -1e30


def _block_attn(q, k, v, acc, m, l, q_off, k_off, causal: bool, scale: float,
                k_len=None):
    """One online-softmax accumulation step.

    q: [B, H, Lq, D]; k, v: [B, H, Lk, D]; acc: [B, H, Lq, D];
    m, l: [B, H, Lq] running max / denominator; q_off, k_off: global offsets
    of the first query / key position in this pair of blocks; k_len masks
    global key positions >= k_len (padding).
    """
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    Lq, Lk = q.shape[2], k.shape[2]
    qpos = q_off + jnp.arange(Lq)[:, None]
    kpos = k_off + jnp.arange(Lk)[None, :]
    if causal:
        scores = jnp.where(kpos > qpos, NEG_INF, scores)
    if k_len is not None:
        scores = jnp.where(kpos >= k_len, NEG_INF, scores)
    m_new = jnp.maximum(m, scores.max(axis=-1))
    # guard fully-masked rows (can only occur for non-causal callers passing
    # disjoint offsets); exp(NEG_INF - NEG_INF) would be 1, so clamp.
    p = jnp.exp(scores - m_new[..., None])
    correction = jnp.exp(m - m_new)
    l_new = l * correction + p.sum(axis=-1)
    acc_new = acc * correction[..., None] + jnp.einsum("bhqk,bhkd->bhqd", p, v)
    return acc_new, m_new, l_new


def block_diffusion_mask(half: int, block: int):
    """Whether query position i may see key position j, over a noisy copy
    (positions 0..half-1) followed by a clean one (half..2 half-1), where
    the block of a position is ``(i mod half) // block``: a noisy query sees
    the noisy keys of its own block and the clean keys of earlier blocks; a
    clean query sees the clean keys of its own and earlier blocks and never
    a noisy one. Elementwise over broadcast position arrays."""
    def live(qpos, kpos):
        q_noisy, k_noisy = qpos < half, kpos < half
        qb, kb = (qpos % half) // block, (kpos % half) // block
        return jnp.where(q_noisy, jnp.where(k_noisy, kb == qb, kb < qb),
                         ~k_noisy & (kb <= qb))
    return live


def live_pairs(half: int, block: int) -> int:
    """(query, key) pairs that ``block_diffusion_mask`` lets through, a
    head: the noisy copy's own blocks (half x block), noisy queries on the
    clean blocks before theirs and clean queries on their own and earlier
    clean blocks (half ** 2 together)."""
    return half * block + half * half


def blockwise_attention(q, k, v, *, causal: bool = True,
                        block_size: int = 512,
                        diffusion_block: int = 0) -> jnp.ndarray:
    """Single-device flash-style attention via lax.scan over key blocks.

    ``v`` may be narrower or wider than ``q`` and ``k`` ([B, H, L, Dv]): the
    result has the value's width, the scores are scaled by the key's.
    ``diffusion_block`` > 0 takes the positions as a noisy and a clean copy
    of L / 2 each and masks by ``block_diffusion_mask`` with blocks of that
    length, in place of ``causal``; the mask is computed a key block at a
    time from the positions. Then ``k`` and ``v`` may hold fewer heads than
    ``q`` ([B, KV, L, D], KV dividing H): query head h reads key/value head
    h // (H / KV), grouped-query attention with no copy of a key head."""
    if diffusion_block:
        return _masked_blockwise(
            q, k, v, block_diffusion_mask(q.shape[2] // 2, diffusion_block),
            block_size)
    B, H, L, D = q.shape
    Dv = v.shape[-1]
    scale = 1.0 / jnp.sqrt(D).astype(q.dtype)
    bs = min(block_size, L)
    nblocks = -(-L // bs)
    pad = nblocks * bs - L
    if pad:
        # padded keys are masked out via NEG_INF scores (kpos >= L)
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
    k_blocks = k.reshape(B, H, nblocks, bs, D).transpose(2, 0, 1, 3, 4)
    v_blocks = v.reshape(B, H, nblocks, bs, Dv).transpose(2, 0, 1, 3, 4)

    acc = jnp.zeros((B, H, L, Dv), q.dtype)
    m = jnp.full((B, H, L), NEG_INF, dtype=q.dtype)
    l = jnp.zeros((B, H, L), dtype=q.dtype)

    def step(carry, inp):
        acc, m, l = carry
        (kb, vb, b_idx) = inp
        acc, m, l = _block_attn(q, kb, vb, acc, m, l,
                                q_off=0, k_off=b_idx * bs,
                                causal=causal, scale=scale, k_len=L)
        return (acc, m, l), None

    (acc, m, l), _ = lax.scan(step, (acc, m, l),
                              (k_blocks, v_blocks, jnp.arange(nblocks)))
    return acc / jnp.maximum(l[..., None], 1e-30)


def _masked_blockwise(q, k, v, live, block_size: int) -> jnp.ndarray:
    """``blockwise_attention`` under the mask ``live(qpos, kpos)``, with a
    query head's group folded into the query axis: [B, KV, G x L, D]
    against each key/value head once. A score the mask drops adds nought to
    the running sum, whatever block it falls in."""
    B, H, L, D = q.shape
    KV, Dv = k.shape[1], v.shape[-1]
    G = H // KV
    q = q.reshape(B, KV, G * L, D)
    qpos = jnp.tile(jnp.arange(L), G)[:, None]
    scale = 1.0 / jnp.sqrt(D).astype(q.dtype)
    bs = min(block_size, L)
    nblocks = -(-L // bs)
    pad = nblocks * bs - L
    if pad:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
    k_blocks = k.reshape(B, KV, nblocks, bs, D).transpose(2, 0, 1, 3, 4)
    v_blocks = v.reshape(B, KV, nblocks, bs, Dv).transpose(2, 0, 1, 3, 4)

    def step(carry, inp):
        acc, m, l = carry
        kb, vb, b_idx = inp
        kpos = (b_idx * bs + jnp.arange(bs))[None, :]
        ok = (kpos < L) & live(qpos, kpos)
        s = jnp.where(ok, jnp.einsum("bhqd,bhkd->bhqk", q, kb) * scale,
                      NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.where(ok, jnp.exp(s - m_new[..., None]), 0.0)
        correction = jnp.exp(m - m_new)
        return (acc * correction[..., None]
                + jnp.einsum("bhqk,bhkd->bhqd", p, vb),
                m_new, l * correction + p.sum(axis=-1)), None

    acc = jnp.zeros((B, KV, G * L, Dv), q.dtype)
    m = jnp.full((B, KV, G * L), NEG_INF, dtype=q.dtype)
    l = jnp.zeros((B, KV, G * L), dtype=q.dtype)
    (acc, m, l), _ = lax.scan(step, (acc, m, l),
                              (k_blocks, v_blocks, jnp.arange(nblocks)))
    return (acc / jnp.maximum(l[..., None], 1e-30)).reshape(B, H, L, Dv)


def ring_attention(q, k, v, *, axis_name: str,
                   causal: bool = True) -> jnp.ndarray:
    """Exact attention with sequence sharded over ``axis_name``.

    Must be called inside shard_map/pjit with q, k, v holding this device's
    sequence shard [B, H, L_shard, D]. K/V rotate around the ring; each step
    attends the local queries against the visiting key block with global
    causal offsets, so the result equals full attention over the gathered
    sequence.
    """
    n = lax.psum(1, axis_name)
    idx = lax.axis_index(axis_name)
    B, H, Ls, D = q.shape
    scale = 1.0 / jnp.sqrt(D).astype(q.dtype)
    q_off = idx * Ls

    acc = jnp.zeros_like(q)
    m = jnp.full((B, H, Ls), NEG_INF, dtype=q.dtype)
    l = jnp.zeros((B, H, Ls), dtype=q.dtype)
    perm = [(i, (i + 1) % n) for i in range(n)]

    def step(carry, s):
        kb, vb, acc, m, l = carry
        # block that arrived after s rotations started at device idx - s
        src = jnp.mod(idx - s, n)
        acc, m, l = _block_attn(q, kb, vb, acc, m, l,
                                q_off=q_off, k_off=src * Ls,
                                causal=causal, scale=scale)
        kb = lax.ppermute(kb, axis_name, perm)
        vb = lax.ppermute(vb, axis_name, perm)
        return (kb, vb, acc, m, l), None

    # lax.scan (not fori_loop) so the ring is reverse-mode differentiable
    (_, _, acc, m, l), _ = lax.scan(step, (k, v, acc, m, l), jnp.arange(n))
    # causal + ring: every query saw its own diagonal block at s=0, so l > 0
    return acc / jnp.maximum(l[..., None], 1e-30)


# ----------------------------------------------------------------------
def make_seq_mesh(n_data: int, n_seq: int):
    """('data', 'seq') mesh: batch over 'data' (DCN-friendly), sequence ring
    over 'seq' (ICI-friendly — the ppermute rides neighbor links)."""
    import numpy as np
    from jax.sharding import Mesh
    devs = np.asarray(jax.devices()[: n_data * n_seq]).reshape(n_data, n_seq)
    return Mesh(devs, ("data", "seq"))


def ring_self_attention(x_qkv, *, axis_name: str, causal: bool = True):
    """Convenience wrapper: (q, k, v) tuple -> attention output."""
    q, k, v = x_qkv
    return ring_attention(q, k, v, axis_name=axis_name, causal=causal)
