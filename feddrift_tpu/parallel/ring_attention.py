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
import numpy as np
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


def clean_key_tiles(half: int, block: int, tile: int) -> np.ndarray:
    """[n]: how many tiles of clean keys the query tile t (positions t x
    tile .. of either copy) reads under ``block_diffusion_mask``: tiles 0
    up to the one holding the last key of its last position's block,
    which a clean query there sees. Each tile of that range holds a pair
    the mask lets through, and none after it does."""
    last = np.minimum(np.arange(1, -(-half // tile) + 1) * tile, half) - 1
    return (np.minimum((last // block + 1) * block, half) - 1) // tile + 1


def pairs_computed(half: int, block: int, tile: int) -> int:
    """(query, key) pairs that ``blockwise_attention`` forms under the
    block-diffusion mask with tiles of ``tile`` positions, a head: the
    noisy copy's own blocks (half x block) and, for both copies, each query
    tile against the clean key tiles ``clean_key_tiles`` names, padding
    included."""
    tile = min(tile, half)
    return half * block \
        + 2 * tile * tile * int(clean_key_tiles(half, block, tile).sum())


def blockwise_attention(q, k, v, *, causal: bool = True,
                        block_size: int = 512,
                        diffusion_block: int = 0) -> jnp.ndarray:
    """Single-device flash-style attention via lax.scan over key blocks.

    ``v`` may be narrower or wider than ``q`` and ``k`` ([B, H, L, Dv]): the
    result has the value's width, the scores are scaled by the key's.
    ``diffusion_block`` > 0 takes the positions as a noisy and a clean copy
    of L / 2 each and masks by ``block_diffusion_mask`` with blocks of that
    length, in place of ``causal``; only the tiles of ``block_size``
    positions that the mask leaves a pair in are formed
    (``_block_diffusion_attention``). Then ``k`` and ``v`` may hold fewer
    heads than ``q`` ([B, KV, L, D], KV dividing H): query head h reads
    key/value head h // (H / KV), grouped-query attention with no copy of a
    key head."""
    if diffusion_block:
        return _block_diffusion_attention(q, k, v, diffusion_block,
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


def _tile_lanes(counts):
    """The (query tile, key tile) steps of every query tile t, which reads
    key tiles 0 .. counts[t] - 1, dealt into lanes that run side by side:
    tile t shares a lane with tile n - 1 - t, so where t reads t + 1 tiles
    every lane takes n + 1 steps. A shorter lane repeats its last step,
    marked dead. [S, W] arrays of the query tile, the key tile, whether
    the step starts its query tile and whether it is live; and [n, 2] the
    (step, lane) of each query tile's last step."""
    n = len(counts)
    lanes = [[(t, u) for t in sorted({p, n - 1 - p})
              for u in range(counts[t])] for p in range(-(-n // 2))]
    steps = max(map(len, lanes))
    grid = np.array([[lane[min(s, len(lane) - 1)] for lane in lanes]
                     for s in range(steps)])
    live = np.array([[s < len(lane) for lane in lanes]
                     for s in range(steps)])
    ends = {t: (s, w) for w, lane in enumerate(lanes)
            for s, (t, _) in enumerate(lane)}
    return (grid[..., 0], grid[..., 1], (grid[..., 1] == 0) & live, live,
            np.array([ends[t] for t in range(n)]))


def _block_diffusion_attention(q, k, v, block: int,
                               tile: int) -> jnp.ndarray:
    """``blockwise_attention`` under ``block_diffusion_mask`` with blocks of
    ``block``, forming only what the mask leaves a pair in, with a query
    head's group sharing each key/value head:

    1. a noisy query against the noisy keys of its own block, a product
       over blocks of ``block`` positions (the mask lets no other noisy key
       through, and a clean query none);
    2. the queries of both copies against the clean keys, by tiles of
       ``tile`` positions: query tile t (both copies, every head of the
       group) reads the key tiles ``clean_key_tiles`` names, the mask
       applied a pair at a time; the tiles run in lanes (``_tile_lanes``)
       of one scan, each lane's online-softmax state started afresh at
       each of its query tiles;
    3. a noisy query's two states merged exactly, as ``_block_attn``'s
       correction merges two key blocks.
    """
    B, H, P, D = q.shape
    KV, Dv = k.shape[1], v.shape[-1]
    G, L = H // KV, P // 2
    scale = 1.0 / jnp.sqrt(D).astype(q.dtype)
    q = q.reshape(B, KV, G, 2, L, D)                 # [..., copy, position]

    # 1. noisy queries, noisy keys of their own block
    N = L // block
    s = (q[:, :, :, 0].reshape(B, KV, G, N, block, 1, D)
         * k[:, :, None, :L].reshape(B, KV, 1, N, 1, block, D)).sum(-1) \
        * scale
    m_own = s.max(-1)
    p = jnp.exp(s - m_own[..., None])
    l_own = p.sum(-1).reshape(B, KV, G, L)
    acc_own = (p[..., None] * v[:, :, None, :L].reshape(
        B, KV, 1, N, 1, block, Dv)).sum(-2).reshape(B, KV, G, L, Dv)
    m_own = m_own.reshape(B, KV, G, L)

    # 2. both copies' queries, clean keys, by tiles; rows [G, copy, tile]
    bs = min(tile, L)
    n = -(-L // bs)
    pad = ((0, n * bs - L), (0, 0))                 # positions, features
    q_tiles = jnp.pad(q, ((0, 0),) * 4 + pad).reshape(B, KV, G, 2, n, bs, D)
    q_tiles = jnp.moveaxis(q_tiles, 4, 0).reshape(n, B, KV, G * 2 * bs, D)
    k_tiles, v_tiles = (jnp.moveaxis(jnp.pad(
        a[:, :, L:], ((0, 0),) * 2 + pad).reshape(B, KV, n, bs, a.shape[-1]),
        2, 0) for a in (k, v))
    t_q, t_k, start, live, ends = _tile_lanes(clean_key_tiles(L, block, bs))
    rows = np.arange(G * 2 * bs)
    row_pos = (rows // bs % 2) * L + rows % bs        # position at tile 0
    mask = block_diffusion_mask(L, block)

    def step(carry, x):
        qb, kb, vb, tq, tk, fresh, on = x
        fresh = fresh[:, None, None, None]
        acc, m, l = (jnp.where(fresh[..., None], 0.0, carry[0]),
                     jnp.where(fresh, NEG_INF, carry[1]),
                     jnp.where(fresh, 0.0, carry[2]))
        qpos = (row_pos + tq[:, None] * bs)[:, :, None]
        kpos = (tk[:, None] * bs + jnp.arange(bs))[:, None, :]
        ok = (on[:, None, None] & (kpos < L)
              & mask(qpos, L + kpos))[:, None, None]
        s = jnp.where(ok, jnp.einsum("wbkrd,wbkcd->wbkrc", qb, kb) * scale,
                      NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.where(ok, jnp.exp(s - m_new[..., None]), 0.0)
        correction = jnp.exp(m - m_new)
        out = (acc * correction[..., None]
               + jnp.einsum("wbkrc,wbkcd->wbkrd", p, vb),
               m_new, l * correction + p.sum(axis=-1))
        return out, out

    W = t_q.shape[1]
    init = (jnp.zeros((W, B, KV, G * 2 * bs, Dv), q.dtype),
            jnp.full((W, B, KV, G * 2 * bs), NEG_INF, q.dtype),
            jnp.zeros((W, B, KV, G * 2 * bs), q.dtype))
    _, states = lax.scan(step, init, (q_tiles[t_q], k_tiles[t_k],
                                      v_tiles[t_k], t_q, t_k, start, live))

    def untiled(a):
        """[S, W, B, KV, rows, ...] -> [B, KV, G, copy, L, ...] of each
        query tile's last step."""
        a = a[ends[:, 0], ends[:, 1]].reshape(n, B, KV, G, 2, bs,
                                              *a.shape[5:])
        return jnp.moveaxis(a, 0, 4).reshape(B, KV, G, 2, n * bs,
                                             *a.shape[6:])[:, :, :, :, :L]
    acc, m, l = map(untiled, states)

    # 3. a noisy query's own block merged with the clean keys before it
    m_all = jnp.maximum(m_own, m[:, :, :, 0])
    c_own, c = jnp.exp(m_own - m_all), jnp.exp(m[:, :, :, 0] - m_all)
    noisy = (acc_own * c_own[..., None] + acc[:, :, :, 0] * c[..., None]) \
        / (l_own * c_own + l[:, :, :, 0] * c)[..., None]
    clean = acc[:, :, :, 1] / l[:, :, :, 1][..., None]
    return jnp.stack([noisy, clean], 3).reshape(B, H, P, Dv)


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
    from jax.sharding import Mesh
    devs = np.asarray(jax.devices()[: n_data * n_seq]).reshape(n_data, n_seq)
    return Mesh(devs, ("data", "seq"))


def ring_self_attention(x_qkv, *, axis_name: str, causal: bool = True):
    """Convenience wrapper: (q, k, v) tuple -> attention output."""
    q, k, v = x_qkv
    return ring_attention(q, k, v, axis_name=axis_name, causal=causal)
