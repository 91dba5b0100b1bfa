"""Flash-decode Pallas TPU kernels: one query token vs. a long KV cache.

Both keep a running softmax in float32:

* :func:`decode_attention_pallas` — dense contiguous caches
  (B, KV, S, D); grid (batch*heads, num_s_blocks), cache blocks
  innermost, the per-batch valid length (`pos`, in scalar prefetch)
  masks stale slots.
* :func:`paged_decode_attention_pallas` — the paged engines' decode
  attention (`models/attention.py`): a segment's stacked block pools
  (L, NB, bs, KV*hd) addressed through per-request block tables
  (`models/kvcache.py`).  One grid step per row; the row's live blocks,
  and only those, are copied from HBM by hand, a chunk of blocks at a
  time, double-buffered.  The logical view is never materialized.

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
#: blocks a paged decode step copies and scores at once (256 tokens of
#: 16-token blocks)
DEFAULT_CHUNK_BLOCKS = 16
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


def _paged_decode_kernel(layer_ref, tables_ref, lengths_ref,
                         q_ref, k_hbm, v_hbm, o_ref,
                         k_buf, v_buf, sems, state, *,
                         scale: float, chunk: int, nb: int, rows: int):
    """One grid step per row: the row's live blocks of layer
    ``layer_ref[0]``, ``chunk`` blocks at a time, through a two-slot
    VMEM ring.  The last chunk of a row starts the next row's first
    one, so a row's first copy waits only behind the row before it.
    ``state`` (SMEM, kept across grid steps): [0] the ring slot of the
    next chunk, [1] whether that chunk's copies were started."""
    b = pl.program_id(0)
    bs = k_buf.shape[2]
    tokens = chunk * bs
    layer = layer_ref[0]

    @pl.when(b == 0)
    def _init():
        state[0] = 0
        state[1] = 0

    def n_chunks(row):
        return jax.lax.div(lengths_ref[row] + tokens - 1, tokens)

    def blocks(row):
        return jax.lax.div(lengths_ref[row] + bs - 1, bs)

    def copies(row, c, slot, i):
        phys = tables_ref[row * nb + c * chunk + i]
        return (pltpu.make_async_copy(k_hbm.at[layer, phys],
                                      k_buf.at[slot, i], sems.at[0, slot]),
                pltpu.make_async_copy(v_hbm.at[layer, phys],
                                      v_buf.at[slot, i], sems.at[1, slot]))

    def each_live_block(row, c, slot, fn):
        def one(i, carry):
            for cp in copies(row, c, slot, i):
                fn(cp)
            return carry

        # a loop, not ``chunk`` unrolled branches: every decode program
        # traces and lowers the kernel at set-up, and unrolled it cost
        # several times as long
        live = jnp.minimum(blocks(row) - c * chunk, chunk)
        jax.lax.fori_loop(0, live, one, 0)

    n = n_chunks(b)

    @pl.when((n > 0) & (state[1] == 0))
    def _first():
        each_live_block(b, 0, state[0], lambda cp: cp.start())

    q = q_ref[0]                                     # (H, W)
    length = lengths_ref[b]

    def body(c, carry):
        m, l, acc = carry
        slot = jax.lax.rem(state[0] + c, 2)

        @pl.when(c + 1 < n)
        def _next_chunk():
            each_live_block(b, c + 1, 1 - slot, lambda cp: cp.start())

        @pl.when((c + 1 == n) & (b + 1 < rows))
        def _next_row():
            @pl.when(n_chunks(b + 1) > 0)
            def _():
                each_live_block(b + 1, 0, 1 - slot, lambda cp: cp.start())

        each_live_block(b, c, slot, lambda cp: cp.wait())
        k = k_buf[slot].reshape(tokens, -1)          # (T, W)
        v = v_buf[slot].reshape(tokens, -1)
        kpos = c * tokens + jax.lax.broadcasted_iota(jnp.int32,
                                                     (1, tokens), 1)
        valid = kpos < length                        # (1, T)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        s = jnp.where(valid, s, NEG_INF)             # (H, T)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        l = alpha * l + jnp.sum(p, axis=-1, keepdims=True)
        # slots past the live blocks hold whatever the ring held last:
        # zero them, so that 0 * stale cannot be NaN
        vpos = c * tokens + jax.lax.broadcasted_iota(jnp.int32,
                                                     (tokens, 1), 0)
        v = jnp.where(vpos < length, v, jnp.zeros_like(v))
        acc = acc * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return m_new, l, acc

    h, w = q.shape
    m0 = jnp.full((h, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((h, 1), jnp.float32)
    acc0 = jnp.zeros((h, w), jnp.float32)
    _, l, acc = jax.lax.fori_loop(0, n, body, (m0, l0, acc0))
    o_ref[0] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)

    nxt = jnp.where(b + 1 < rows, n_chunks(jnp.minimum(b + 1, rows - 1)), 0)
    state[0] = jax.lax.rem(state[0] + n, 2)
    state[1] = ((n > 0) & (nxt > 0)).astype(jnp.int32)


# jitted, so that a process traces the kernel once however many decode
# programs hold it
@functools.partial(jax.jit, static_argnames=("kv_heads", "chunk",
                                             "interpret"))
def paged_decode_attention_pallas(q, k_pool, v_pool, tables, lengths, layer,
                                  *, kv_heads: int,
                                  chunk: int = DEFAULT_CHUNK_BLOCKS,
                                  interpret: bool = True):
    """One query token per row against its live blocks of one layer.

    q: (B, H, hd); pools: (L, NB, bs, W), a segment's stacked pools,
    each slot one row of its ``kv_heads`` heads side by side in its
    first KV*hd columns (W a multiple of 128 on the TPU, whose copies
    move whole lane tiles), read in place (they stay in HBM,
    ``memory_space=pl.ANY``); tables: (B, nb) int32 physical block of
    each logical block; lengths: (B,) int32 slots each row attends to,
    logical slots ``[0, length)`` (0: the row reads nothing and gets
    zeros); layer: int32 scalar, the layer of the pools to read.
    -> (B, H, hd) in ``q.dtype``.

    A row costs ``ceil(length / bs)`` block copies, ``chunk`` blocks a
    step.  One copy moves a whole ``(bs, W)`` block, every head in it.
    So a head scores against all W columns with a block-diagonal
    query: head ``j``'s values sit in its KV group's ``hd`` columns of
    an (H, W) row and zeros elsewhere, which makes ``q_bd @ K.T``
    exactly its scores against its group, and head ``j`` takes its
    group's ``hd`` columns of ``P @ V``.  The softmax is online, in
    float32.
    """
    b, h, hd = q.shape
    _, _, bs, w = k_pool.shape
    kv = kv_heads
    nb = tables.shape[1]
    chunk = min(chunk, nb)
    group = jnp.arange(h) // (h // kv)
    q_bd = (q[:, :, None, :] * (group[:, None] == jnp.arange(kv))[
        None, :, :, None].astype(q.dtype)).reshape(b, h, kv * hd)
    q_bd = jnp.pad(q_bd, ((0, 0), (0, 0), (0, w - kv * hd)))
    lengths = jnp.clip(lengths.astype(jnp.int32), 0, nb * bs)
    kernel = functools.partial(_paged_decode_kernel, scale=hd ** -0.5,
                               chunk=chunk, nb=nb, rows=b)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,   # layer, tables, lengths
        grid=(b,),
        in_specs=[pl.BlockSpec((1, h, w), lambda r, *_: (r, 0, 0)),
                  pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((1, h, w), lambda r, *_: (r, 0, 0)),
        scratch_shapes=[pltpu.VMEM((2, chunk, bs, w), k_pool.dtype),
                        pltpu.VMEM((2, chunk, bs, w), v_pool.dtype),
                        pltpu.SemaphoreType.DMA((2, 2)),
                        pltpu.SMEM((2,), jnp.int32)])
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, w), q.dtype),
        # rows run in order: a row's last chunk starts the next row's
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(jnp.reshape(layer, (1,)).astype(jnp.int32),
      tables.astype(jnp.int32).reshape(-1), lengths, q_bd, k_pool, v_pool)
    # head j's own group's columns
    out = out[..., :kv * hd].reshape(b, kv, h // kv, kv, hd)
    return jnp.moveaxis(jnp.diagonal(out, axis1=1, axis2=3), -1, 1
                        ).reshape(b, h, hd)
