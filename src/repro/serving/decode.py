"""Distributed flash-decode: seq-parallel KV cache via shard_map.

The KV cache shards along the *sequence* dim over the `model` mesh axis
(spec ``P(batch, None, "model", None)`` for (B, KV, S, D)).  Each device
runs a local flash-decode over its cache slice (the single-chip Pallas
kernel in repro.kernels.decode_attention is the on-device body), then the
partial softmax states (m, l, acc) combine with one tiny pmax + two psums
— O(B·H·D) bytes on the wire instead of all-gathering O(B·KV·S·D) cache.

This is the explicit form of §Perf iteration D1; under plain GSPMD the
same layout already compiles (launch/dryrun.py --layout seq), but the
shard_map version pins the communication schedule instead of hoping.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

NEG_INF = -1e30


def _local_flash_decode(q, k, v, pos, *, s_start, scale):
    """q: (B,H,D); k/v: (B,KV,S_loc,D); pos: (B,).  Returns partial
    (acc: (B,H,D), m: (B,H,1), l: (B,H,1)) softmax state."""
    b, h, d = q.shape
    kv, s_loc = k.shape[1], k.shape[2]
    g = h // kv
    qg = q.reshape(b, kv, g, d).astype(jnp.float32)
    scores = jnp.einsum("bngd,bnsd->bngs", qg,
                        k.astype(jnp.float32)) * scale
    kpos = s_start + jnp.arange(s_loc)
    valid = kpos[None, :] <= pos[:, None]                    # (B,S_loc)
    scores = jnp.where(valid[:, None, None, :], scores, NEG_INF)
    m = jnp.max(scores, axis=-1, keepdims=True)              # (B,KV,G,1)
    p = jnp.exp(scores - m)
    p = jnp.where(valid[:, None, None, :], p, 0.0)
    l = jnp.sum(p, axis=-1, keepdims=True)
    acc = jnp.einsum("bngs,bnsd->bngd", p, v.astype(jnp.float32))
    return (acc.reshape(b, h, d), m.reshape(b, h, 1), l.reshape(b, h, 1))


def distributed_decode_attention(q, k_cache, v_cache, pos, mesh: Mesh,
                                 axis: str = "model",
                                 batch_axes=("data",), scale=None):
    """q: (B,H,D); caches: (B,KV,S,D) seq-sharded over `axis`;
    pos: (B,).  Returns (B,H,D)."""
    b, h, d = q.shape
    s = k_cache.shape[2]
    n_shards = mesh.shape[axis]
    s_loc = s // n_shards
    scale = d ** -0.5 if scale is None else scale
    ba = batch_axes if all(a in mesh.axis_names for a in batch_axes) else ()

    def body(q, k, v, pos):
        idx = jax.lax.axis_index(axis)
        acc, m, l = _local_flash_decode(
            q, k, v, pos, s_start=idx * s_loc, scale=scale)
        # combine partial softmax states across the seq shards
        m_glob = jax.lax.pmax(m, axis)
        corr = jnp.exp(m - m_glob)
        acc = jax.lax.psum(acc * corr, axis)
        l = jax.lax.psum(l * corr, axis)
        return (acc / jnp.maximum(l, 1e-30)).astype(q.dtype)

    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(ba, None, None), P(ba, None, axis, None),
                  P(ba, None, axis, None), P(ba)),
        out_specs=P(ba, None, None),
        check_vma=False,
    )(q, k_cache, v_cache, pos)
