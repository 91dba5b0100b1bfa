"""Flash-decode Pallas TPU kernels: one query token vs. a long KV cache.

Two layouts share the running-softmax structure (grid (batch*heads,
num_s_blocks), cache blocks innermost, (m, l, acc) in VMEM scratch):

* :func:`decode_attention_pallas` — dense contiguous caches
  (B, KV, S, D); the per-batch valid length (`pos`, in scalar prefetch)
  masks stale slots.
* :func:`paged_decode_attention_pallas` — block-pool caches
  (KV, NB, bs, D) addressed through per-request block tables
  (`models/kvcache.py`).  The tables and `pos` ride in scalar prefetch
  (``PrefetchScalarGridSpec``), so the *index map itself* performs the
  block-table gather: grid step (bh, si) DMAs physical block
  ``tables[b, si]`` — the kernel never materializes a request's
  logical view, which is the point of paging (on real TPU the map can
  additionally skip blocks past ``pos`` entirely).

The dense kernel is the single-chip building block of the seq-parallel
distributed decode in repro.serving.decode (shard_map over the `model`
axis + psum-combine of (m, l, acc)).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.flash_attention import pl_scratch

DEFAULT_BLOCK_S = 256
NEG_INF = -1e30


def _decode_kernel(pos_ref, q_ref, k_ref, v_ref, o_ref,
                   m_scr, l_scr, acc_scr, *,
                   scale: float, block_s: int, seq_len: int, heads: int):
    bh = pl.program_id(0)
    si = pl.program_id(1)
    ns = pl.num_programs(1)
    b = bh // heads

    @pl.when(si == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0].astype(jnp.float32)           # (1, d)
    k = k_ref[0].astype(jnp.float32)           # (bs, d)
    v = v_ref[0].astype(jnp.float32)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale

    kpos = si * block_s + jax.lax.broadcasted_iota(jnp.int32, (1, block_s), 1)
    valid = (kpos <= pos_ref[b]) & (kpos < seq_len)
    s = jnp.where(valid, s, NEG_INF)

    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_scr[...] = alpha * l_scr[...] + jnp.sum(p, axis=-1, keepdims=True)
    acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_scr[...] = m_new

    @pl.when(si == ns - 1)
    def _finish():
        o_ref[0] = (acc_scr[...]
                    / jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


def decode_attention_pallas(q, k_cache, v_cache, pos, *, scale=None,
                            block_s: int = DEFAULT_BLOCK_S,
                            interpret: bool = True):
    """q: (B,H,D); caches: (B,KV,S,D); pos: (B,) int32.  -> (B,H,D)."""
    b, h, d = q.shape
    kv, s = k_cache.shape[1], k_cache.shape[2]
    g = h // kv
    scale = d ** -0.5 if scale is None else scale
    block_s = min(block_s, s)
    ns = -(-s // block_s)

    qf = q.reshape(b * h, 1, d)
    kf = k_cache.reshape(b * kv, s, d)
    vf = v_cache.reshape(b * kv, s, d)

    kernel = functools.partial(
        _decode_kernel, scale=scale, block_s=block_s, seq_len=s, heads=h)

    # pos rides in scalar prefetch (SMEM): a (B,) int32 VMEM block read
    # at a dynamic row is refused by the TPU compiler (unaligned index)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b * h, ns),
        in_specs=[
            pl.BlockSpec((1, 1, d), lambda bh, si, pos: (bh, 0, 0)),
            pl.BlockSpec((1, block_s, d),
                         lambda bh, si, pos, g=g: (bh // g, si, 0)),
            pl.BlockSpec((1, block_s, d),
                         lambda bh, si, pos, g=g: (bh // g, si, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, d), lambda bh, si, pos: (bh, 0, 0)),
        scratch_shapes=[
            pl_scratch((1, 1)), pl_scratch((1, 1)), pl_scratch((1, d)),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b * h, 1, d), q.dtype),
        interpret=interpret,
    )(pos.astype(jnp.int32), qf, kf, vf)
    return out.reshape(b, h, d)


def _paged_decode_kernel(tables_ref, pos_ref, q_ref, k_ref, v_ref, o_ref,
                         m_scr, l_scr, acc_scr, *,
                         scale: float, block_s: int, heads: int):
    """Body is the dense running softmax; the block-table indirection
    happened in the index maps (k_ref/v_ref already hold the physical
    block tables_ref[b, si] selected)."""
    bh = pl.program_id(0)
    si = pl.program_id(1)
    ns = pl.num_programs(1)
    b = bh // heads

    @pl.when(si == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0].astype(jnp.float32)           # (1, d)
    k = k_ref[0, 0].astype(jnp.float32)        # (bs, d)
    v = v_ref[0, 0].astype(jnp.float32)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale

    kpos = si * block_s + jax.lax.broadcasted_iota(jnp.int32, (1, block_s), 1)
    s = jnp.where(kpos <= pos_ref[b], s, NEG_INF)

    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_scr[...] = alpha * l_scr[...] + jnp.sum(p, axis=-1, keepdims=True)
    acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_scr[...] = m_new

    @pl.when(si == ns - 1)
    def _finish():
        o_ref[0] = (acc_scr[...]
                    / jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


def paged_decode_attention_pallas(q, k_pool, v_pool, block_tables, pos, *,
                                  scale=None, interpret: bool = True):
    """Flash decode over paged block pools.

    q: (B,H,D); pools: (KV, NB, bs, D); block_tables: (B, nb) int32
    (entries past a request's length may point anywhere in range —
    ``pos`` masks them); pos: (B,) valid-length-1.  -> (B, H, D).

    The logical KV view is never materialized: each grid step's
    BlockSpec index map reads ``block_tables[b, si]`` from scalar
    prefetch and DMAs that physical block.
    """
    b, h, d = q.shape
    kv, _, block_s, _ = k_pool.shape
    nb = block_tables.shape[1]
    g = h // kv
    scale = d ** -0.5 if scale is None else scale

    qf = q.reshape(b * h, 1, d)
    kernel = functools.partial(
        _paged_decode_kernel, scale=scale, block_s=block_s, heads=h)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,   # block_tables, pos feed the index maps
        grid=(b * h, nb),
        in_specs=[
            pl.BlockSpec((1, 1, d), lambda bh, si, tbl, pos: (bh, 0, 0)),
            pl.BlockSpec(
                (1, 1, block_s, d),
                lambda bh, si, tbl, pos, g=g, h=h:
                    ((bh % h) // g, tbl[bh // h, si], 0, 0)),
            pl.BlockSpec(
                (1, 1, block_s, d),
                lambda bh, si, tbl, pos, g=g, h=h:
                    ((bh % h) // g, tbl[bh // h, si], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, d), lambda bh, si, tbl, pos: (bh, 0, 0)),
        scratch_shapes=[
            pl_scratch((1, 1)), pl_scratch((1, 1)), pl_scratch((1, d)),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b * h, 1, d), q.dtype),
        interpret=interpret,
    )(block_tables.astype(jnp.int32), pos.astype(jnp.int32), qf,
      k_pool, v_pool)
    return out.reshape(b, h, d)
