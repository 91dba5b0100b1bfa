"""Pure-jnp oracles for every Pallas kernel (the allclose ground truth)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def flash_attention_ref(q, k, v, *, causal: bool = True, window: int = 0,
                        scale: float | None = None):
    """q: (B,H,S,D), k/v: (B,KV,S,D) — GQA when H > KV."""
    b, h, s, d = q.shape
    kv = k.shape[1]
    g = h // kv
    scale = d ** -0.5 if scale is None else scale
    qg = q.reshape(b, kv, g, s, d).astype(jnp.float32)
    scores = jnp.einsum("bngqd,bnkd->bngqk", qg,
                        k.astype(jnp.float32)) * scale
    if causal:
        qpos = jnp.arange(s)[:, None]
        kpos = jnp.arange(s)[None, :]
        ok = kpos <= qpos
        if window > 0:
            ok = ok & (kpos > qpos - window)
        scores = jnp.where(ok, scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bngqk,bnkd->bngqd", probs, v.astype(jnp.float32))
    return out.reshape(b, h, s, d).astype(q.dtype)


def decode_attention_ref(q, k_cache, v_cache, pos, *,
                         scale: float | None = None):
    """q: (B,H,D); caches: (B,KV,S,D); pos: (B,) valid-length-1 indices."""
    b, h, d = q.shape
    kv, s = k_cache.shape[1], k_cache.shape[2]
    g = h // kv
    scale = d ** -0.5 if scale is None else scale
    qg = q.reshape(b, kv, g, d).astype(jnp.float32)
    scores = jnp.einsum("bngd,bnkd->bngk", qg,
                        k_cache.astype(jnp.float32)) * scale
    valid = jnp.arange(s)[None, :] <= pos[:, None]           # (B,S)
    scores = jnp.where(valid[:, None, None, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bngk,bnkd->bngd", probs,
                     v_cache.astype(jnp.float32))
    return out.reshape(b, h, d).astype(q.dtype)


def paged_decode_attention_ref(q, k_pool, v_pool, tables, lengths, layer,
                               *, kv_heads: int):
    """Paged flash-decode oracle: gather the logical view, then score.

    q: (B,H,D); pools: (L, NB, bs, W) stacked block pools, a slot's
    ``kv_heads`` heads side by side in its first KV*D columns; tables:
    (B, nb) int32 physical ids per logical block (entries past a row's
    length may point anywhere in range); lengths: (B,) slots each row
    attends to (0: zeros); layer: the pools' layer to read.
    """
    b, _, d = q.shape
    bs, kv, nb = k_pool.shape[2], kv_heads, tables.shape[1]

    def view(pool):   # (B, nb, bs, W) -> (B, KV, S, D)
        return jnp.moveaxis(pool[layer, tables][..., :kv * d].reshape(
            b, nb * bs, kv, d), 2, 1)

    out = decode_attention_ref(q, view(k_pool), view(v_pool), lengths - 1)
    return jnp.where((lengths > 0)[:, None, None], out, 0).astype(q.dtype)


def selective_scan_ref(dt, b_mat, c_mat, x, a_neg, h0):
    """Mamba1 recurrence oracle.

    dt, x: (B,T,DI); b_mat, c_mat: (B,T,DS); a_neg: (DI,DS);
    h0: (B,DI,DS).  Returns (y: (B,T,DI), h_T).
    """
    def step(h, inp):
        dt_t, b_t, c_t, x_t = inp
        decay = jnp.exp(dt_t[..., None] * a_neg[None])
        h = decay * h + (dt_t * x_t)[..., None] * b_t[:, None, :]
        y = jnp.einsum("bds,bs->bd", h, c_t)
        return h, y

    xs = (jnp.moveaxis(dt, 1, 0), jnp.moveaxis(b_mat, 1, 0),
          jnp.moveaxis(c_mat, 1, 0), jnp.moveaxis(x, 1, 0))
    h_t, ys = jax.lax.scan(step, h0.astype(jnp.float32), xs)
    return jnp.moveaxis(ys, 0, 1), h_t


def quant_matmul_int8_ref(x, q, s):
    """x (..., K) @ dequant(q (K, N) int8, s (1, N) f32) -> (..., N).

    Per-output-channel symmetric scales: w = q * s.  Dequant-then-dot
    in f32, result cast back to x.dtype — the numeric contract the
    fused Pallas kernel must reproduce to f32 round-off.
    """
    w = q.astype(jnp.float32) * s
    return (x.astype(jnp.float32) @ w).astype(x.dtype)


def unpack_int4_ref(packed):
    """(K//2, N) uint8 -> (K, N) int8 in [-8, 7].

    Packed row r holds k=2r in the low nibble and k=2r+1 in the high
    nibble; stored nibbles are biased by +8 (see models/quantize.py).
    """
    lo = (packed & 0xF).astype(jnp.int8) - 8
    hi = (packed >> 4).astype(jnp.int8) - 8
    k2, n = packed.shape
    return jnp.stack([lo, hi], axis=1).reshape(2 * k2, n)


def quant_matmul_int4_ref(x, q, s):
    """x (..., K) @ dequant(q (K//2, N) packed uint8, s (K//G, N) f32).

    Per-group scales along K (G inferred from the shapes):
    w[k] = (nibble[k] - 8) * s[k // G].
    """
    k = q.shape[-2] * 2
    g = k // s.shape[-2]
    w = unpack_int4_ref(q).astype(jnp.float32) * jnp.repeat(s, g, axis=0)
    return (x.astype(jnp.float32) @ w).astype(x.dtype)


def rmsnorm_ref(x, scale, eps: float = 1e-5):
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    return (x32 * jax.lax.rsqrt(var + eps)
            * scale.astype(jnp.float32)).astype(x.dtype)
