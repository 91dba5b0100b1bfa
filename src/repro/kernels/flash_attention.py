"""Flash attention (prefill) Pallas TPU kernel.

Grid: (batch*heads, num_q_blocks, num_k_blocks) — k innermost, so the
running-softmax state lives in VMEM scratch across k steps (TPU grids are
sequential).  Blocks are (BLOCK_Q, head_dim) / (BLOCK_K, head_dim) VMEM
tiles; head_dim is MXU-aligned (128/256).  GQA is handled by the k/v
index_map (q head h reads kv head h // group).  Causal + sliding-window
masking is applied inside the kernel; fully-masked k blocks are skipped
via the grid-pruning predicate in ops.py (we simply mask — XLA-side
pruning would need a custom grid; noted in EXPERIMENTS.md §Perf).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_K = 128
NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  scale: float, causal: bool, window: int,
                  block_q: int, block_k: int, seq_len: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0].astype(jnp.float32)          # (bq, d)
    k = k_ref[0].astype(jnp.float32)          # (bk, d)
    v = v_ref[0].astype(jnp.float32)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale

    qpos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32,
                                                   (block_q, block_k), 0)
    kpos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32,
                                                   (block_q, block_k), 1)
    ok = kpos < seq_len
    if causal:
        ok = ok & (kpos <= qpos)
        if window > 0:
            ok = ok & (kpos > qpos - window)
    s = jnp.where(ok, s, NEG_INF)

    m_prev = m_scr[...]                        # (bq, 1)
    m_cur = jnp.max(s, axis=-1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_new = alpha * l_scr[...] + jnp.sum(p, axis=-1, keepdims=True)
    acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_scr[...] = m_new
    l_scr[...] = l_new

    @pl.when(ki == nk - 1)
    def _finish():
        o_ref[0] = (acc_scr[...]
                    / jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


def flash_attention_pallas(q, k, v, *, causal: bool = True, window: int = 0,
                           scale=None, block_q: int = DEFAULT_BLOCK_Q,
                           block_k: int = DEFAULT_BLOCK_K,
                           interpret: bool = True):
    """q: (B,H,S,D); k/v: (B,KV,S,D).  Returns (B,H,S,D)."""
    b, h, s, d = q.shape
    kv = k.shape[1]
    g = h // kv
    scale = d ** -0.5 if scale is None else scale
    block_q = min(block_q, s)
    block_k = min(block_k, s)
    nq = -(-s // block_q)
    nk = -(-s // block_k)

    qf = q.reshape(b * h, s, d)
    kf = k.reshape(b * kv, s, d)
    vf = v.reshape(b * kv, s, d)

    kernel = functools.partial(
        _flash_kernel, scale=scale, causal=causal, window=window,
        block_q=block_q, block_k=block_k, seq_len=s)

    out = pl.pallas_call(
        kernel,
        grid=(b * h, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, block_k, d),
                         lambda bh, qi, ki, g=g: (bh // g, ki, 0)),
            pl.BlockSpec((1, block_k, d),
                         lambda bh, qi, ki, g=g: (bh // g, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d),
                               lambda bh, qi, ki: (bh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, s, d), q.dtype),
        scratch_shapes=[
            pl_scratch((block_q, 1)),
            pl_scratch((block_q, 1)),
            pl_scratch((block_q, d)),
        ],
        interpret=interpret,
    )(qf, kf, vf)
    return out.reshape(b, h, s, d)


def pl_scratch(shape):
    """f32 VMEM scratch accumulator (interpret mode emulates it)."""
    return pltpu.VMEM(shape, jnp.float32)
