"""Decoder-only Transformer LM with optional sequence-parallel ring attention.

A model family the reference lacks (its sequence ceiling is a 2-layer LSTM at
seq len 80, fedml_api/model/nlp/rnn.py:4-33); added so the drift pipeline and
the long-context path share one architecture. With ``seq_axis=None`` the model
runs single-device blockwise (flash-style) attention; inside a shard_map over
a ('data', 'seq') mesh it uses ring attention and never materialises the full
sequence per chip. Blocks are wrapped in ``jax.checkpoint`` (remat) so long
sequences trade FLOPs for HBM.
"""

from __future__ import annotations

from typing import Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from feddrift_tpu.parallel.ring_attention import (blockwise_attention,
                                                  ring_attention)

ATTENTION_IMPLS = ("auto", "pallas", "blockwise")


def resolve_attention_impl(impl: str) -> str:
    """The implementation ``impl`` runs as in THIS process.

    ``auto`` is the Pallas flash kernel only on a TPU process with a single
    device. GSPMD cannot partition a Mosaic kernel ("Mosaic kernels cannot
    be automatically partitioned. Please wrap the call in a shard_map" —
    v5e 2x2, PR 21), and the round programs shard the client axis over
    every device of the default mesh, so on more than one chip ``auto`` is
    the jnp blockwise path. The choice is never silent: the runner writes
    the resolved name into ``run_start``. ``pallas`` / ``blockwise`` force
    an implementation; forcing ``pallas`` on a sharded program raises at
    compile time.
    """
    if impl not in ATTENTION_IMPLS:
        raise ValueError(f"attention_impl must be auto|pallas|blockwise, "
                         f"got {impl!r}")
    if impl != "auto":
        return impl
    if jax.default_backend() == "tpu" and jax.device_count() == 1:
        return "pallas"
    return "blockwise"


class MultiHeadAttention(nn.Module):
    num_heads: int
    seq_axis: Optional[str] = None      # mesh axis name for ring attention
    causal: bool = True
    attention_impl: str = "auto"        # see resolve_attention_impl

    @nn.compact
    def __call__(self, x):
        B, L, E = x.shape
        H = self.num_heads
        D = E // H
        qkv = nn.Dense(3 * E, use_bias=False, name="qkv")(x)
        q, k, v = jnp.split(qkv, 3, axis=-1)
        q = q.reshape(B, L, H, D).transpose(0, 2, 1, 3)
        k = k.reshape(B, L, H, D).transpose(0, 2, 1, 3)
        v = v.reshape(B, L, H, D).transpose(0, 2, 1, 3)
        impl = resolve_attention_impl(self.attention_impl)
        if self.seq_axis is not None:
            out = ring_attention(q, k, v, axis_name=self.seq_axis,
                                 causal=self.causal)
        elif impl == "pallas":
            # Mosaic flash kernel at O(L * block) memory
            # (parallel/pallas_attention.py); speed vs the jnp path on the
            # chip: not measured
            from feddrift_tpu.parallel.pallas_attention import flash_attention
            out = flash_attention(q, k, v, self.causal)
        else:
            out = blockwise_attention(q, k, v, causal=self.causal)
        out = out.transpose(0, 2, 1, 3).reshape(B, L, E)
        return nn.Dense(E, use_bias=False, name="proj")(out)


class Block(nn.Module):
    num_heads: int
    mlp_ratio: int = 4
    seq_axis: Optional[str] = None
    attention_impl: str = "auto"

    @nn.compact
    def __call__(self, x):
        E = x.shape[-1]
        h = MultiHeadAttention(self.num_heads, self.seq_axis,
                               attention_impl=self.attention_impl)(
            nn.LayerNorm()(x))
        x = x + h
        y = nn.LayerNorm()(x)
        y = nn.Dense(self.mlp_ratio * E)(y)
        y = nn.gelu(y)
        y = nn.Dense(E)(y)
        return x + y


class TransformerLM(nn.Module):
    """Next-token LM. Matches the drift pipeline's (tokens [B, L]) -> logits
    contract of CharLSTM (last-position prediction) when ``last_only=True``;
    with ``last_only=False`` returns per-position logits for long-context
    training."""

    vocab_size: int = 90
    d_model: int = 128
    num_heads: int = 4
    num_layers: int = 2
    max_len: int = 4096
    seq_axis: Optional[str] = None
    last_only: bool = True
    remat: bool = True
    attention_impl: str = "auto"        # auto | pallas | blockwise

    @nn.compact
    def __call__(self, tokens):
        B, L = tokens.shape
        x = nn.Embed(self.vocab_size, self.d_model, name="tok_embed")(
            tokens.astype(jnp.int32))
        # position offset: under sequence parallelism each shard's positions
        # start at axis_index * L
        if self.seq_axis is not None:
            off = jax.lax.axis_index(self.seq_axis) * L
        else:
            off = 0
        pos = off + jnp.arange(L)
        x = x + nn.Embed(self.max_len, self.d_model, name="pos_embed")(pos)[None]
        block_cls = Block
        if self.remat:
            block_cls = nn.remat(Block)
        for i in range(self.num_layers):
            x = block_cls(self.num_heads, seq_axis=self.seq_axis,
                          attention_impl=self.attention_impl,
                          name=f"block_{i}")(x)
        x = nn.LayerNorm()(x)
        if self.last_only:
            x = x[:, -1]
        return nn.Dense(self.vocab_size, name="lm_head")(x)
