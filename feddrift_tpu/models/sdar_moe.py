"""Block-diffusion decoder with grouped-query attention and softmax-routed
experts (``model_type: sdar_moe``, a Qwen3-MoE layer), trained on the
masked-diffusion objective over blocks, cut to one chip's share of each
layer.

A preset holds the published sizes once and, beside them, the cut this
process holds: the first ``layers_held`` layers, query heads [0,
``heads_held``) with key/value heads [0, ``kv_heads_held``) (query head h
reads key/value head h // (published heads / published key/value heads)),
the routed experts ``experts_held`` = [first, end) of the router's outputs
and ``vocab_rows_held`` rows of the vocabulary, the last of which is the
mask id. The router keeps its published width and routes over ALL experts;
the chip computes what its own experts give for the tokens routed to them,
and nothing stands in for the other chips: their part is left out.

Input: tokens [B, 2L], a noisy copy x_t (positions 0..L-1) and the clean
copy x_0 (L..2L-1); both take rotary position ``i mod L``. Equations
(pre-norm, no biases):

    x   = E[tokens]
    h   = x + Attn(RMSNorm(x))
    x'  = h + MoE(RMSNorm(h))
    logits = RMSNorm(x_noisy) W_head         untied, over the held rows

``Attn``: ``q = W_q u`` -> H x d, ``k = W_k u``, ``v = W_v u`` -> KV x d;
per-head RMSNorm of q and of k with a learned scale of d, then rotary
embedding (rotate-half); ``softmax(q k / sqrt(d))`` under the block-diffusion
mask (``parallel/ring_attention.py::block_diffusion_mask``); ``W_o`` over
the H x d values. ``MoE``: ``p = softmax(W_g u)`` over all experts in
float32; the k largest p, weighted ``p_e / (sum of the chosen p)``;
``y = sum over chosen and held e of w_e SwiGLU_e(u)``; no shared expert.

Objective (BD3-LM, SDAR): a noisy position carries a label where it holds
the mask id: the clean id and the noise level t of its block, packed into
one int32 (``data/text.py::generate_token_diffusion``); the loss of a sequence is the sum over its
masked positions of CE / t, divided by L (the masked-diffusion bound a
token). Evaluation counts a hit where the first choice at a masked position
is the clean id, and a label a masked position.
"""

from __future__ import annotations

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax

from feddrift_tpu.data.text import NOISE_LEVELS
from feddrift_tpu.models.mla_moe import BLOCK_ROWS, rms_norm, routed_experts
from feddrift_tpu.parallel.ring_attention import (blockwise_attention,
                                                  live_pairs, pairs_computed)

PRESETS: dict[str, dict] = {
    # https://huggingface.co/JetLM/SDAR-30B-A3B-Chat/blob/main/config.json
    # sixteen chips share each layer: 8 of 128 experts, 2 of 32 query heads
    # with their key/value head (1 of 4), 18,992 of 151,936 vocabulary rows;
    # layers 0-3 of 48
    "sdar_30b_a3b_chat": dict(
        hidden_size=2048, moe_intermediate_size=768, num_experts=128,
        num_experts_per_tok=8, norm_topk_prob=True, num_hidden_layers=48,
        num_attention_heads=32, num_key_value_heads=4, head_dim=128,
        rope_theta=1e6, vocab_size=151936, rms_norm_eps=1e-6,
        block_length=4, layers_held=4, heads_held=2, kv_heads_held=1,
        experts_held=(0, 8), vocab_rows_held=18992),
    # the CPU tests' and the rehearsal's size: every mechanism, no width of
    # any model; four chips share each layer
    "sdar_moe_tiny": dict(
        hidden_size=64, moe_intermediate_size=32, num_experts=16,
        num_experts_per_tok=4, norm_topk_prob=True, num_hidden_layers=8,
        num_attention_heads=8, num_key_value_heads=2, head_dim=16,
        rope_theta=1e4, vocab_size=256, rms_norm_eps=1e-6, block_length=4,
        layers_held=2, heads_held=2, kv_heads_held=1, experts_held=(0, 4),
        vocab_rows_held=64),
}

_INIT = nn.initializers.normal(0.02)
# positions a tile of the attention's queries and keys
# (parallel/ring_attention.py::_block_diffusion_attention)
ATTENTION_TILE = 512


def unpack_labels(y, rows: int):
    """(masked, clean id, noise level t) of packed labels ``y``: ``clean +
    rows x level`` at a masked position, t = (level + 1) / NOISE_LEVELS, and
    -1 at the others; ``rows`` is the vocabulary's held rows."""
    masked = y >= 0
    ids = jnp.where(masked, y % rows, 0)
    t = (jnp.where(masked, y // rows, 0) + 1).astype(jnp.float32) \
        / NOISE_LEVELS
    return masked, ids, t


def masked_nll(logits, y):
    """[..., L]: CE / t at a masked position, nought at the others."""
    masked, ids, t = unpack_labels(y, logits.shape[-1])
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    ce = -jnp.take_along_axis(logp, ids[..., None], axis=-1)[..., 0]
    return jnp.where(masked, ce / t, 0.0)


def diffusion_loss(logits, y):
    """The training objective: the masked-diffusion bound a noisy position,
    averaged over the batch."""
    return masked_nll(logits, y).mean()


def diffusion_counts(logits, y):
    """(hits, summed loss, labels) of a batch: a label is a masked
    position, a hit one whose first choice is the clean id."""
    masked, ids, _ = unpack_labels(y, logits.shape[-1])
    hits = (masked & (logits.argmax(-1) == ids)).sum(dtype=jnp.int32)
    return hits, masked_nll(logits, y).sum(), masked.sum(dtype=jnp.int32)


def rope_half(x, pos, theta: float):
    """Rotary embedding (rotate-half) over the last axis of ``x`` [B, P, H,
    d] at positions ``pos`` [P]: channel i and i + d/2 turn together by
    pos x theta ** (-2i / d)."""
    d = x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = pos.astype(jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[None, :, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[None, :, None, :]
    x32 = x.astype(jnp.float32)
    turned = jnp.concatenate([-x32[..., d // 2:], x32[..., : d // 2]], -1)
    return (x32 * cos + turned * sin).astype(x.dtype)


# ----------------------------------------------------------------------
class BlockDiffusionAttention(nn.Module):
    heads: int
    kv_heads: int
    head_dim: int
    theta: float
    eps: float
    block: int

    @nn.compact
    def __call__(self, u):
        B, P, D = u.shape
        H, KV, d = self.heads, self.kv_heads, self.head_dim
        wq = self.param("wq", _INIT, (D, H * d))
        wk = self.param("wk", _INIT, (D, KV * d))
        wv = self.param("wv", _INIT, (D, KV * d))
        wo = self.param("wo", _INIT, (H * d, D))
        q_norm = self.param("q_norm", nn.initializers.ones, (d,))
        k_norm = self.param("k_norm", nn.initializers.ones, (d,))
        with jax.named_scope("bd_attention"):
            pos = jnp.arange(P) % (P // 2)
            q = rope_half(rms_norm((u @ wq).reshape(B, P, H, d), q_norm,
                                   self.eps), pos, self.theta)
            k = rope_half(rms_norm((u @ wk).reshape(B, P, KV, d), k_norm,
                                   self.eps), pos, self.theta)
            v = (u @ wv).reshape(B, P, KV, d)
            # the softmax's running maximum and sum in float32
            o = blockwise_attention(
                *(a.astype(jnp.float32).transpose(0, 2, 1, 3)
                  for a in (q, k, v)), diffusion_block=self.block,
                block_size=ATTENTION_TILE)
            o = o.transpose(0, 2, 1, 3).reshape(B, P, H * d).astype(u.dtype)
            return o @ wo


class SoftmaxExperts(nn.Module):
    """The routed experts held here; also returns the tokens each held
    expert got [G] and the blocks of rows that went through them."""
    n_experts: int
    top_k: int
    held: tuple[int, int]            # [first, end) of the router's outputs
    width: int
    norm_topk: bool

    @nn.compact
    def __call__(self, u):
        B, P, D = u.shape
        T, F = B * P, self.width
        lo, hi = self.held
        G = hi - lo
        router = self.param("router", _INIT, (D, self.n_experts))
        w_gate = self.param("w_gate", _INIT, (G, D, F))
        w_up = self.param("w_up", _INIT, (G, D, F))
        w_down = self.param("w_down", _INIT, (G, F, D))
        x = u.reshape(T, D)
        with jax.named_scope("expert_layer"):
            p = jax.nn.softmax(jnp.dot(
                x.astype(jnp.float32), router.astype(jnp.float32),
                precision=lax.Precision.HIGHEST), axis=-1)
            p_top, top = lax.top_k(p, self.top_k)                 # [T, K]
            if self.norm_topk:
                p_top = p_top / p_top.sum(-1, keepdims=True)
            here = top[:, :, None] == (lo + jnp.arange(G))[None, None, :]
            weight = (p_top[:, :, None] * here).sum(axis=1)       # [T, G]
            chosen = here.any(axis=1)
            block = min(BLOCK_ROWS, -(-T // 16 // 8) * 8)
            y = routed_experts(x, weight, chosen, w_gate, w_up, w_down, block)
            load = chosen.sum(axis=0, dtype=jnp.int32)
        return y.astype(u.dtype).reshape(B, P, D), load, \
            (-(-load // block)).sum()


class Block(nn.Module):
    preset: str

    @nn.compact
    def __call__(self, x):
        z = PRESETS[self.preset]
        eps, D = z["rms_norm_eps"], x.shape[-1]
        attn = BlockDiffusionAttention(
            z["heads_held"], z["kv_heads_held"], z["head_dim"],
            z["rope_theta"], eps, z["block_length"], name="attn")
        h = x + attn(rms_norm(
            x, self.param("attn_norm", nn.initializers.ones, (D,)), eps))
        y, load, blocks = SoftmaxExperts(
            z["num_experts"], z["num_experts_per_tok"],
            tuple(z["experts_held"]), z["moe_intermediate_size"],
            z["norm_topk_prob"], name="moe")(rms_norm(
                h, self.param("ffn_norm", nn.initializers.ones, (D,)), eps))
        return h + y, load, blocks


class SDARDecoder(nn.Module):
    """Block-diffusion decoder: tokens [B, 2L] (noisy ; clean) -> logits
    [B, L, held rows] of the noisy copy. With ``return_stats`` also the
    counts of the call: those of the expert layers as ``MLAMoEDecoder``
    gives them (over all 2L positions), and ``bd_noisy_positions``,
    ``bd_masked_positions`` (noisy positions that hold the mask id) and
    ``bd_live_attention_pairs`` ((query, key) pairs the mask lets through,
    a head and a layer, over the sequences) and
    ``bd_attention_pairs_computed`` (the pairs the attention forms, counted
    alike)."""
    preset: str = "sdar_30b_a3b_chat"
    remat: bool = True

    # what the runner reads (simulation/runner.py)
    returns_stats = True            # a forward that counts (stats_fn)
    jit_init = True                 # core/pool.py: init under jit
    attention_impl = "blockwise"    # run_start.attention_impl

    # what the round programs read (core/step.py): the model's own
    # objective and evaluation counts, in place of a cross-entropy a label
    @staticmethod
    def objective(logits, y):
        return diffusion_loss(logits, y)

    @staticmethod
    def eval_counts(logits, y):
        return diffusion_counts(logits, y)

    @property
    def remat_blocks(self) -> bool:
        return self.remat

    @property
    def experts_held(self) -> tuple[int, int]:
        return tuple(PRESETS[self.preset]["experts_held"])

    @property
    def mask_id(self) -> int:
        return PRESETS[self.preset]["vocab_rows_held"] - 1

    @nn.compact
    def __call__(self, tokens, return_stats: bool = False):
        z = PRESETS[self.preset]
        V, D = z["vocab_rows_held"], z["hidden_size"]
        B, P = tokens.shape
        L = P // 2
        tokens = tokens.astype(jnp.int32)
        embed = self.param("embed", _INIT, (V, D))
        # rows read through float32, so that the backward adds an id's
        # occurrences up in float32 (models/mla_moe.py says why)
        x = embed.astype(jnp.float32)[tokens].astype(embed.dtype)
        block = nn.remat(Block) if self.remat else Block
        lo, hi = z["experts_held"]
        load = jnp.zeros((hi - lo,), jnp.int32)
        blocks = jnp.zeros((), jnp.int32)
        for i in range(z["layers_held"]):
            x, load_i, blocks_i = block(self.preset, name=f"layer_{i}")(x)
            load, blocks = load + load_i, blocks + blocks_i
        x = rms_norm(x[:, :L],
                     self.param("final_norm", nn.initializers.ones, (D,)),
                     z["rms_norm_eps"])
        with jax.named_scope("lm_head"):
            logits = jnp.dot(x, self.param("head", _INIT, (D, V)),
                             preferred_element_type=jnp.float32)
        if not return_stats:
            return logits
        layers = z["layers_held"]
        return logits, {
            "expert_tokens": jnp.asarray(B * P * layers, jnp.int32),
            "expert_load": load, "expert_blocks": blocks,
            "bd_noisy_positions": jnp.asarray(B * L, jnp.int32),
            "bd_masked_positions": (tokens[:, :L] == V - 1).sum(
                dtype=jnp.int32),
            "bd_live_attention_pairs": jnp.asarray(
                B * live_pairs(L, z["block_length"]), jnp.int32),
            "bd_attention_pairs_computed": jnp.asarray(
                B * pairs_computed(L, z["block_length"], ATTENTION_TILE),
                jnp.int32)}


def parameter_count(preset: str) -> int:
    """The closed form of what a preset's cut holds."""
    z = PRESETS[preset]
    D, d = z["hidden_size"], z["head_dim"]
    H, KV = z["heads_held"], z["kv_heads_held"]
    G = z["experts_held"][1] - z["experts_held"][0]
    attn = D * H * d + 2 * D * KV * d + H * d * D + 2 * d
    layer = attn + 2 * D + D * z["num_experts"] \
        + G * 3 * D * z["moe_intermediate_size"]
    return z["layers_held"] * layer + 2 * z["vocab_rows_held"] * D + D


def check_heads(preset: str) -> None:
    """The held query heads read the held key/value heads as the published
    grouping has them: head h reads h // (heads / key/value heads)."""
    z = PRESETS[preset]
    group = z["num_attention_heads"] // z["num_key_value_heads"]
    here = z["heads_held"] // z["kv_heads_held"]
    if any(h // group != h // here for h in range(z["heads_held"])):
        raise ValueError(f"preset {preset!r}: {z['heads_held']} query heads "
                         f"over {z['kv_heads_held']} key/value heads is not "
                         f"the published grouping of {group}")

