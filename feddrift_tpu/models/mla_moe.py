"""Latent-attention, sparse-expert decoder (``model_type: deepseek_v3``), cut
to one chip's share of each layer.

A preset holds an architecture's published sizes once and, beside them, the
cut this process holds: the first ``layers_held`` layers, ``heads_held`` of
the attention heads, the routed experts ``experts_held`` = [first, end) of
the router's outputs, and ``vocab_rows_held`` rows of the vocabulary. The
router keeps its published width and routes over ALL experts; the chip
computes what its own experts give for the tokens routed to them, and nothing
stands in for the other chips: their part of the sum is left out. The latent
projection, the shared experts and the dense layer's MLP are what every chip
computes alike and are held whole.

Equations (no biases but the router's selection bias; pre-norm residual):

    x   = E[tokens]
    h   = x + Attn(RMSNorm(x))
    x'  = h + FFN(RMSNorm(h))            FFN: SwiGLU MLP in the leading dense
                                         layers, the expert layer after them
    logits = RMSNorm(x_last) W_head      untied head over the held rows

``Attn`` (latent attention, no query rank): ``q = W_q u`` -> H x (nope +
rope); ``[c; k_r] = W_kva u`` with one ``k_r`` for all heads; ``c <-
RMSNorm(c)``; ``[k_n; v] = W_kvb c`` -> H x (nope + v); rotary embedding
(interleaved pairs) on the rope channels of ``q`` and on ``k_r``; ``k = [k_n;
k_r]``; causal softmax of ``q k / sqrt(nope + rope)``; ``W_o`` over the H x v
values. The core is ``parallel/ring_attention.py::blockwise_attention`` (the
value is narrower than the key; the Pallas forward takes one width only).

Expert layer: ``s = sigmoid(W_g u)`` over all experts in float32; the k
experts with the largest ``s + b`` (``b`` enters the choice alone);
weights ``s_e / (sum of the chosen s + 1e-20) x routed_scaling_factor``;
``y = sum over chosen and held e of w_e SwiGLU_e(u) + SwiGLU_shared(u)``.
"""

from __future__ import annotations

import math
from functools import partial

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax

from feddrift_tpu.parallel.ring_attention import blockwise_attention

PRESETS: dict[str, dict] = {
    # https://huggingface.co/kakaocorp/kanana-2-30b-a3b-instruct-2601/blob/main/config.json
    # sixteen chips share each layer: 8 of 128 routed experts, 2 of 32 heads,
    # 16,032 of 128,256 vocabulary rows; layers 0-4 of 48
    "kanana2_30b_a3b_cut16": dict(
        hidden_size=2048, intermediate_size=6144, moe_intermediate_size=768,
        n_shared_experts=2, n_routed_experts=128, num_experts_per_tok=6,
        routed_scaling_factor=2.448, first_k_dense_replace=1,
        num_hidden_layers=48, num_attention_heads=32, kv_lora_rank=512,
        qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
        rope_theta=1e6, vocab_size=128256, rms_norm_eps=1e-6,
        layers_held=5, heads_held=2, experts_held=(0, 8),
        vocab_rows_held=16032),
    # the CPU tests' and the rehearsal's size: every mechanism, no width of
    # any model; four chips share each layer
    "mla_moe_tiny": dict(
        hidden_size=64, intermediate_size=128, moe_intermediate_size=32,
        n_shared_experts=2, n_routed_experts=16, num_experts_per_tok=2,
        routed_scaling_factor=2.448, first_k_dense_replace=1,
        num_hidden_layers=6, num_attention_heads=8, kv_lora_rank=16,
        qk_nope_head_dim=8, qk_rope_head_dim=8, v_head_dim=8,
        rope_theta=1e4, vocab_size=256, rms_norm_eps=1e-6,
        layers_held=3, heads_held=2, experts_held=(0, 4),
        vocab_rows_held=64),
}

_INIT = nn.initializers.normal(0.02)
# rows that go through a held expert at a time: an even share of a local
# step's choices is 192 rows an expert (4,096 tokens, 6 of 128)
BLOCK_ROWS = 256


def rms_norm(x, scale, eps: float):
    x32 = x.astype(jnp.float32)
    x32 = x32 * lax.rsqrt((x32 * x32).mean(-1, keepdims=True) + eps)
    return (x32 * scale.astype(jnp.float32)).astype(x.dtype)


def rope_interleaved(x, theta: float):
    """Rotary embedding over the last axis of ``x`` [B, L, ..., R]: channel
    pairs (2i, 2i + 1) turn by position x theta ** (-2i / R)."""
    L, R = x.shape[1], x.shape[-1]
    inv = theta ** (-jnp.arange(0, R, 2, dtype=jnp.float32) / R)
    ang = jnp.arange(L, dtype=jnp.float32)[:, None] * inv[None, :]
    shape = (1, L) + (1,) * (x.ndim - 3) + (R // 2,)
    cos, sin = jnp.cos(ang).reshape(shape), jnp.sin(ang).reshape(shape)
    x32 = x.astype(jnp.float32)
    even, odd = x32[..., 0::2], x32[..., 1::2]
    out = jnp.stack([even * cos - odd * sin, even * sin + odd * cos], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


def swiglu(x, w_gate, w_up, w_down):
    return (jax.nn.silu(x @ w_gate) * (x @ w_up)) @ w_down


# ----------------------------------------------------------------------
def _by_expert(on, block: int):
    """Of one held expert: how many tokens chose it, and its tokens first and
    in their own order, padded by a block past the last row (so that the
    last block's slice of ``block`` rows stays in range, and its padding
    rows land nowhere)."""
    T = on.shape[0]
    order = jnp.concatenate([jnp.argsort(~on, stable=True),
                             T + jnp.arange(block, dtype=jnp.int32)])
    return on.sum(), order


def _block_rows(order, count, k, block: int):
    """Block k's rows of the expert's order, and which of them are its
    tokens: the rest are other tokens' rows or past the last row."""
    rows = lax.dynamic_slice(order, (k * block,), (block,))
    return rows, k * block + jnp.arange(block) < count


def _add_rows(acc, rows, live, y):
    """``acc`` with ``y`` added into ``rows`` where ``live``, and nought
    elsewhere; rows past the last are dropped."""
    live = live.reshape(live.shape + (1,) * (y.ndim - 1))
    return acc.at[rows].add(jnp.where(live, y, 0), mode="drop")


@partial(jax.custom_vjp, nondiff_argnums=(6,))
def routed_experts(x, weight, chosen, w_gate, w_up, w_down, block: int):
    """The held experts' part of a routed layer, [T, D] float32.

    ``x`` [T, D]; ``chosen`` [T, G] whether a token chose held expert g;
    ``weight`` [T, G] its routing weight there (nought where not chosen);
    the experts' weights stacked on a leading [G].

    A grouped product over the tokens sorted by expert, one held expert at
    a time: the expert's tokens are brought together in their own order and
    go through the expert ``block`` rows at a time, as many blocks as the
    expert got tokens for (a loop whose length the router sets: the cost
    follows the assignments held here, whatever their spread over the
    experts). Each block's result, weighted, is added straight into its
    tokens' rows of the float32 accumulator, so an expert reads and writes
    the rows it got and no others: a pass over all T rows for each held
    expert reads and writes 16 x the rows it changes where an expert gets
    512 of 8,192 rows (PERF.md section 5). Each row receives its experts'
    terms in the held experts' order. No token is dropped and no capacity
    is set. A loop of that kind has no automatic transpose, so the backward
    is written out below: the same blocks, each recomputed. (A tile of fixed
    rows an expert with a masked pass past it under a ``lax.cond`` is
    differentiable as it stands, and cost 5 s of a 14 s time step on the
    chip: a ``cond`` writes what its branch's backward needs whether taken
    or not; PERF.md section 6.)
    """
    def one(out, e):
        wg, wu, wd, w_e, on = e
        count, order = _by_expert(on, block)

        def run(k, out):
            rows, live = _block_rows(order, count, k, block)
            y = swiglu(x[rows], wg, wu, wd).astype(jnp.float32)
            return _add_rows(out, rows, live, y * w_e[rows][:, None])

        return lax.fori_loop(0, -(-count // block), run, out), None

    return lax.scan(one, jnp.zeros(x.shape, jnp.float32),
                    (w_gate, w_up, w_down, weight.T, chosen.T))[0]


def _routed_fwd(x, weight, chosen, w_gate, w_up, w_down, block):
    return (routed_experts(x, weight, chosen, w_gate, w_up, w_down, block),
            (x, weight, chosen, w_gate, w_up, w_down))


def _routed_bwd(block, res, g):
    """``g`` [T, D] float32. Per held expert and block of its rows: the
    block's SwiGLU again, then the products' transposes; the experts'
    gradients add up over the blocks in float32, and the rows' gradients
    (of ``x`` and of the routing weight) go into their own rows, as the
    forward's results do."""
    x, weight, chosen, w_gate, w_up, w_down = res
    T = x.shape[0]
    f32 = jnp.float32

    def mm(a, b):
        return jnp.dot(a, b, preferred_element_type=f32)

    def one(dx, e):
        wg, wu, wd, w_e, on = e
        count, order = _by_expert(on, block)

        def run(k, c):
            dx, dw, dwg, dwu, dwd = c
            rows, live = _block_rows(order, count, k, block)
            xs, gs = x[rows], jnp.where(live[:, None], g[rows], 0.0)
            hg, hu = xs @ wg, xs @ wu
            sig = jax.nn.sigmoid(hg.astype(f32))
            act = (hg.astype(f32) * sig).astype(x.dtype)       # silu
            a = act * hu
            # the routing weight's share: <g_t, SwiGLU_e(x_t)>
            dw = _add_rows(dw, rows, live, (gs * mm(a, wd)).sum(-1))
            gy = (gs * w_e[rows][:, None]).astype(x.dtype)
            da = mm(gy, wd.T)
            dhu = (da * act.astype(f32)).astype(x.dtype)
            dhg = (da * hu.astype(f32) * sig
                   * (1.0 + hg.astype(f32) * (1.0 - sig))).astype(x.dtype)
            dx = _add_rows(dx, rows, live, (mm(dhg, wg.T) + mm(dhu, wu.T))
                           .astype(x.dtype).astype(f32))
            return (dx, dw, dwg + mm(xs.T, dhg), dwu + mm(xs.T, dhu),
                    dwd + mm(a.T, gy))

        dx, dw, dwg, dwu, dwd = lax.fori_loop(
            0, -(-count // block), run,
            (dx, jnp.zeros((T,), f32), jnp.zeros(wg.shape, f32),
             jnp.zeros(wu.shape, f32), jnp.zeros(wd.shape, f32)))
        return dx, (dw, dwg.astype(wg.dtype), dwu.astype(wu.dtype),
                    dwd.astype(wd.dtype))

    dx, (dw, dwg, dwu, dwd) = lax.scan(
        one, jnp.zeros(x.shape, f32),
        (w_gate, w_up, w_down, weight.T, chosen.T))
    return (dx.astype(x.dtype), dw.T.astype(weight.dtype), None, dwg, dwu,
            dwd)


routed_experts.defvjp(_routed_fwd, _routed_bwd)


# ----------------------------------------------------------------------
class LatentAttention(nn.Module):
    heads: int
    nope: int
    rope: int
    v_dim: int
    kv_rank: int
    theta: float
    eps: float

    @nn.compact
    def __call__(self, u):
        B, L, D = u.shape
        H, N, R, V = self.heads, self.nope, self.rope, self.v_dim
        wq = self.param("wq", _INIT, (D, H * (N + R)))
        wkv_a = self.param("wkv_a", _INIT, (D, self.kv_rank + R))
        kv_norm = self.param("kv_norm", nn.initializers.ones, (self.kv_rank,))
        wkv_b = self.param("wkv_b", _INIT, (self.kv_rank, H * (N + V)))
        wo = self.param("wo", _INIT, (H * V, D))
        with jax.named_scope("mla_attention"):
            q = (u @ wq).reshape(B, L, H, N + R)
            kv = u @ wkv_a
            c = rms_norm(kv[..., : self.kv_rank], kv_norm, self.eps)
            k_r = rope_interleaved(kv[..., self.kv_rank:], self.theta)
            kn_v = (c @ wkv_b).reshape(B, L, H, N + V)
            q = jnp.concatenate(
                [q[..., :N], rope_interleaved(q[..., N:], self.theta)], -1)
            k = jnp.concatenate(
                [kn_v[..., :N],
                 jnp.broadcast_to(k_r[:, :, None, :], (B, L, H, R))], -1)
            v = kn_v[..., N:]
            # the softmax's running maximum and sum in float32
            o = blockwise_attention(
                *(a.astype(jnp.float32).transpose(0, 2, 1, 3)
                  for a in (q, k, v)), causal=True)
            o = o.transpose(0, 2, 1, 3).reshape(B, L, H * V).astype(u.dtype)
            return o @ wo


class DenseMLP(nn.Module):
    width: int

    @nn.compact
    def __call__(self, u):
        D = u.shape[-1]
        return swiglu(u, self.param("w_gate", _INIT, (D, self.width)),
                      self.param("w_up", _INIT, (D, self.width)),
                      self.param("w_down", _INIT, (self.width, D)))


class ExpertLayer(nn.Module):
    """Routed experts held here plus the shared experts; also returns the
    tokens each held expert got [G] and the blocks of rows that went through
    them (what the routed part cost)."""
    n_routed: int
    top_k: int
    held: tuple[int, int]            # [first, end) of the router's outputs
    width: int
    shared_width: int
    scaling: float

    @nn.compact
    def __call__(self, u):
        B, L, D = u.shape
        T, K, F = B * L, self.top_k, self.width
        lo, hi = self.held
        G = hi - lo
        router = self.param("router", _INIT, (D, self.n_routed))
        # small and not nought, so that the choice is not that of s alone
        bias = self.param("router_bias", nn.initializers.normal(0.01),
                          (self.n_routed,))
        w_gate = self.param("w_gate", _INIT, (G, D, F))
        w_up = self.param("w_up", _INIT, (G, D, F))
        w_down = self.param("w_down", _INIT, (G, F, D))
        x = u.reshape(T, D)
        with jax.named_scope("expert_layer"):
            s = jax.nn.sigmoid(jnp.dot(
                x.astype(jnp.float32), router.astype(jnp.float32),
                precision=lax.Precision.HIGHEST))
            _, top = lax.top_k(s + bias.astype(jnp.float32), K)   # [T, K]
            s_top = jnp.take_along_axis(s, top, axis=-1)
            w_top = s_top / (s_top.sum(-1, keepdims=True) + 1e-20) \
                * self.scaling
            here = top[:, :, None] == (lo + jnp.arange(G))[None, None, :]
            weight = (w_top[:, :, None] * here).sum(axis=1)       # [T, G]
            chosen = here.any(axis=1)
            # a sixteenth of the rows at the tests' sizes, so that an expert
            # there takes several blocks too
            block = min(BLOCK_ROWS, -(-T // 16 // 8) * 8)
            y = routed_experts(x, weight, chosen, w_gate, w_up, w_down, block)
            load = chosen.sum(axis=0, dtype=jnp.int32)
            y = y.astype(u.dtype) + swiglu(
                x, self.param("shared_gate", _INIT, (D, self.shared_width)),
                self.param("shared_up", _INIT, (D, self.shared_width)),
                self.param("shared_down", _INIT, (self.shared_width, D)))
        return y.reshape(B, L, D), load, (-(-load // block)).sum()


class Block(nn.Module):
    preset: str
    dense: bool

    @nn.compact
    def __call__(self, x):
        z = PRESETS[self.preset]
        eps = z["rms_norm_eps"]
        D = x.shape[-1]
        attn = LatentAttention(
            z["heads_held"], z["qk_nope_head_dim"], z["qk_rope_head_dim"],
            z["v_head_dim"], z["kv_lora_rank"], z["rope_theta"], eps,
            name="attn")
        h = x + attn(rms_norm(
            x, self.param("attn_norm", nn.initializers.ones, (D,)), eps))
        u = rms_norm(h, self.param("ffn_norm", nn.initializers.ones, (D,)),
                     eps)
        lo, hi = z["experts_held"]
        if self.dense:
            y = DenseMLP(z["intermediate_size"], name="mlp")(u)
            load = jnp.zeros((hi - lo,), jnp.int32)
            blocks = jnp.zeros((), jnp.int32)
        else:
            y, load, blocks = ExpertLayer(
                z["n_routed_experts"], z["num_experts_per_tok"], (lo, hi),
                z["moe_intermediate_size"],
                z["n_shared_experts"] * z["moe_intermediate_size"],
                z["routed_scaling_factor"], name="moe")(u)
        return h + y, load, blocks


class MLAMoEDecoder(nn.Module):
    """Next-token decoder: tokens [B, L] -> logits [B, L, held rows]. With
    ``return_stats`` also the counts of the call's expert layers:
    ``expert_tokens`` (tokens through them, summed over the layers),
    ``expert_load`` [G] (those of them that each held expert got) and
    ``expert_blocks`` (blocks of rows that went through them, over the
    layers)."""
    preset: str = "kanana2_30b_a3b_cut16"
    remat: bool = True

    # what the runner reads (simulation/runner.py)
    returns_stats = True            # a forward that counts (stats_fn)
    jit_init = True                 # core/pool.py: init under jit
    attention_impl = "blockwise"    # run_start.attention_impl

    @property
    def remat_blocks(self) -> bool:
        return self.remat

    @property
    def experts_held(self) -> tuple[int, int]:
        return tuple(PRESETS[self.preset]["experts_held"])

    @nn.compact
    def __call__(self, tokens, return_stats: bool = False):
        z = PRESETS[self.preset]
        V, D = z["vocab_rows_held"], z["hidden_size"]
        embed = self.param("embed", _INIT, (V, D))
        # the rows are read through float32, so that the backward adds the
        # occurrences of an id up in float32: a frequent id has a thousand
        # of them in a batch, and summed in bfloat16 (where the apply
        # boundary hands the table over in it) the table's gradient came
        # out 17 % short (CPU, bfloat16 emulated, PR 29)
        x = embed.astype(jnp.float32)[tokens.astype(jnp.int32)] \
            .astype(embed.dtype)
        block = nn.remat(Block) if self.remat else Block
        lo, hi = z["experts_held"]
        load = jnp.zeros((hi - lo,), jnp.int32)
        blocks = jnp.zeros((), jnp.int32)
        expert_layers = 0
        for i in range(z["layers_held"]):
            dense = i < z["first_k_dense_replace"]
            x, load_i, blocks_i = block(self.preset, dense,
                                        name=f"layer_{i}")(x)
            load, blocks = load + load_i, blocks + blocks_i
            expert_layers += not dense
        x = rms_norm(x, self.param("final_norm", nn.initializers.ones, (D,)),
                     z["rms_norm_eps"])
        with jax.named_scope("lm_head"):
            logits = jnp.dot(x, self.param("head", _INIT, (D, V)),
                             preferred_element_type=jnp.float32)
        if not return_stats:
            return logits
        tokens_through = math.prod(tokens.shape) * expert_layers
        return logits, {"expert_tokens": jnp.asarray(tokens_through,
                                                     jnp.int32),
                        "expert_load": load, "expert_blocks": blocks}


def parameter_count(preset: str) -> int:
    """The closed form of what a preset's cut holds."""
    z = PRESETS[preset]
    D, H = z["hidden_size"], z["heads_held"]
    N, R, V = z["qk_nope_head_dim"], z["qk_rope_head_dim"], z["v_head_dim"]
    attn = D * H * (N + R) + D * (z["kv_lora_rank"] + R) + z["kv_lora_rank"] \
        + z["kv_lora_rank"] * H * (N + V) + H * V * D
    G = z["experts_held"][1] - z["experts_held"][0]
    F = z["moe_intermediate_size"]
    dense = attn + 3 * D * z["intermediate_size"] + 2 * D
    expert = attn + 2 * D + D * z["n_routed_experts"] \
        + z["n_routed_experts"] + 3 * D * F * z["n_shared_experts"] \
        + G * 3 * D * F
    k = z["first_k_dense_replace"]
    return k * dense + (z["layers_held"] - k) * expert \
        + 2 * z["vocab_rows_held"] * D + D
