"""Pallas TPU flash-attention kernel for the long-context subsystem.

The jnp online-softmax path (parallel/ring_attention.py:blockwise_attention)
leaves XLA to schedule the per-block matmuls through HBM; this kernel keeps
the whole q-block accumulation in VMEM next to the MXU: one grid program per
(batch*head, q-block) computes scores, online softmax, and the PV
accumulation without materialising the [Lq, Lk] score matrix in HBM.

Layout [B, H, L, D] (as ring_attention.py). Causal masking uses global
positions; the k-loop upper bound is trimmed to the diagonal so fully-masked
key blocks are never read. Sequence lengths are padded to the block size and
masked by static length — same contract as blockwise_attention.

Backward: jax.custom_vjp whose bwd recomputes gradients through the jnp
blockwise implementation (rematerialisation — the standard flash-attention
trade of FLOPs for memory). Forward-only callers (inference, the M x C eval
matrices) never pay that cost.

Tests run the kernel in interpret mode on the CPU mesh; on a TPU backend
the Mosaic compiler lowers it natively, and ``chip_smoke.py`` (step
``kernel``) holds the compiled kernel against ``blockwise_attention`` there.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                  block_q: int, block_k: int, L_valid: int, causal: bool,
                  sm_scale: float):
    """Grid (BH, nq, nk) with nk innermost: Mosaic double-buffers the
    [block_k, D] K/V fetches while the MXU works, and the online-softmax
    state lives in VMEM scratch across the k sweep of one q block.

    q_ref/o_ref: [1, block_q, D]; k_ref/v_ref: [1, block_k, D];
    acc_ref: [block_q, D], m_ref/l_ref: [block_q, 1] scratch.
    """
    bq, D = q_ref.shape[1], q_ref.shape[2]
    iq = pl.program_id(1)
    j = pl.program_id(2)
    nk = pl.num_programs(2)
    q_off = iq * block_q
    k_off = j * block_k

    @pl.when(j == 0)
    def _():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    # causal: key blocks fully above the diagonal contribute nothing
    live = (k_off <= q_off + bq - 1) if causal else True

    @pl.when(live)
    def _():
        q = q_ref[0, :, :].astype(jnp.float32) * sm_scale
        k_blk = k_ref[0, :, :].astype(jnp.float32)
        v_blk = v_ref[0, :, :].astype(jnp.float32)
        s = jax.lax.dot_general(q, k_blk, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        qpos = q_off + jax.lax.broadcasted_iota(jnp.int32, (bq, block_k), 0)
        kpos = k_off + jax.lax.broadcasted_iota(jnp.int32, (bq, block_k), 1)
        mask = kpos >= L_valid
        if causal:
            mask = jnp.logical_or(mask, kpos > qpos)
        s = jnp.where(mask, NEG_INF, s)
        m = m_ref[:]
        m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m - m_new)
        l_ref[:] = l_ref[:] * corr + p.sum(axis=-1, keepdims=True)
        acc_ref[:] = acc_ref[:] * corr + jnp.dot(
            p, v_blk, preferred_element_type=jnp.float32)
        m_ref[:] = m_new

    @pl.when(j == nk - 1)
    def _():
        o_ref[0, :, :] = (acc_ref[:] /
                          jnp.maximum(l_ref[:], 1e-30)).astype(o_ref.dtype)


def _flash_forward(q, k, v, causal: bool, block_q: int, block_k: int,
                   interpret: bool):
    B, H, L, D = q.shape
    sm_scale = float(1.0 / (D ** 0.5))
    bq = min(block_q, max(8, L))
    bk = min(block_k, max(8, L))
    Lq_pad = -(-L // bq) * bq
    Lk_pad = -(-L // bk) * bk
    pad_q = Lq_pad - L
    pad_k = Lk_pad - L

    qf = q.reshape(B * H, L, D)
    kf = k.reshape(B * H, L, D)
    vf = v.reshape(B * H, L, D)
    if pad_q:
        qf = jnp.pad(qf, ((0, 0), (0, pad_q), (0, 0)))
    if pad_k:
        kf = jnp.pad(kf, ((0, 0), (0, pad_k), (0, 0)))
        vf = jnp.pad(vf, ((0, 0), (0, pad_k), (0, 0)))

    kernel = functools.partial(_flash_kernel, block_q=bq, block_k=bk,
                               L_valid=L, causal=causal, sm_scale=sm_scale)
    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((B * H, Lq_pad, D), q.dtype),
        grid=(B * H, Lq_pad // bq, Lk_pad // bk),
        in_specs=[
            pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, bk, D), lambda b, i, j: (b, j, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, bk, D), lambda b, i, j: (b, j, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0),
                               memory_space=pltpu.VMEM),
        scratch_shapes=[
            pltpu.VMEM((bq, D), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
        ],
        interpret=interpret,
    )(qf, kf, vf)
    return out[:, :L, :].reshape(B, H, L, D)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def flash_attention(q, k, v, causal: bool = True, block_q: int = 512,
                    block_k: int = 512, interpret: bool = False):
    """Fused causal attention: [B, H, L, D] -> [B, H, L, D].

    Default 512-blocks, O(L * block) activation memory. On TPU v5e the
    kernel compiles and agrees with ``blockwise_attention`` (f32, highest
    precision) to 0.012 max abs error at B=4, H=8, L=2048, D=64 and at the
    transformer's L=80, D=32, in f32 and bf16 (chip_smoke.py, PR 21). Its
    speed against the jnp paths on the chip: not measured.
    """
    return _flash_forward(q, k, v, causal, block_q, block_k, interpret)


def _fwd(q, k, v, causal, block_q, block_k, interpret):
    out = _flash_forward(q, k, v, causal, block_q, block_k, interpret)
    return out, (q, k, v)


def _bwd(causal, block_q, block_k, interpret, residuals, g):
    # Rematerialise through the jnp online-softmax path — identical math,
    # and XLA fuses its backward well; the kernel stays forward-only.
    from feddrift_tpu.parallel.ring_attention import blockwise_attention
    q, k, v = residuals
    _, vjp = jax.vjp(
        lambda q_, k_, v_: blockwise_attention(q_, k_, v_, causal=causal,
                                               block_size=block_k), q, k, v)
    return vjp(g)


flash_attention.defvjp(_fwd, _bwd)
