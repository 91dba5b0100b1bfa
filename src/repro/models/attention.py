"""Grouped-query attention: full / sliding-window / cross, train + decode.

Rotary is applied to K at *write* time, so decode attention over a cache
(ring buffer for SWA) is permutation-safe.  Score math is fp32.

Decode paths are the body of the engines' fused macro-step
(``Model.decode_steps``, a ``lax.scan`` carrying the cache): ``pos`` may
be *frozen* for rows the scheduler has masked (a finished or empty batch
row keeps re-writing its last slot from token 0 — the same ops the
per-token host loop always ran for inactive rows).  This relies on the
invariants documented in `src/repro/models/kvcache.py`: stale KV is
position-masked, unallocated paged slots resolve to the never-read
scratch block.

The paged paths take a segment's *stacked* pools (leading layer dim)
and the layer's index in ``paged["layer"]``: the layer scan carries the
pools (`src/repro/models/transformer.py::apply_segments`), each layer
scatters its new K/V at ``pool.at[layer, phys, off]`` and gathers its
view with one ``pool[layer, tables]``; a pool stores each slot as one
row of ``kv_heads * head_dim``.  A loop carry updated by indexed
scatters is what keeps the writes in place; buffer donation alone did
not (a pool threaded through the scan as ``xs``/``ys`` was sliced out,
written and stacked back, whole, every layer of every iteration).
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models.layers import _dense_init, rotary
from repro.models.quantize import qdot
from repro.sharding.specs import constrain

NEG_INF = -1e30


def attention_init(key, cfg, dtype, cross: bool = False) -> dict:
    d, h, kv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 4)
    p = {
        "wq": _dense_init(ks[0], (d, h * hd), dtype),
        "wk": _dense_init(ks[1], (d, kv * hd), dtype),
        "wv": _dense_init(ks[2], (d, kv * hd), dtype),
        "wo": _dense_init(ks[3], (h * hd, d), dtype),
    }
    if cfg.qkv_bias and not cross:
        p["bq"] = jnp.zeros((h * hd,), dtype)
        p["bk"] = jnp.zeros((kv * hd,), dtype)
        p["bv"] = jnp.zeros((kv * hd,), dtype)
    return p


def _proj_q(params, x, cfg):
    # qdot == the einsum these projections always ran for plain
    # arrays; packed weight leaves (models/quantize.py) take the
    # dequant-fused path — biases stay in the model dtype either way
    q = qdot(x, params["wq"])
    if "bq" in params:
        q = q + params["bq"]
    q = q.reshape(*x.shape[:-1], cfg.n_heads, cfg.head_dim)
    return constrain(q, "act_bthd")


def _proj_kv(params, x, cfg):
    k = qdot(x, params["wk"])
    v = qdot(x, params["wv"])
    if "bk" in params:
        k = k + params["bk"]
        v = v + params["bv"]
    k = k.reshape(*x.shape[:-1], cfg.n_kv_heads, cfg.head_dim)
    v = v.reshape(*x.shape[:-1], cfg.n_kv_heads, cfg.head_dim)
    return constrain(k, "act_btkv"), constrain(v, "act_btkv")


def _gqa_scores(q, k, cfg):
    """q: (B,Q,H,hd), k: (B,S,KV,hd) -> (B,KV,G,Q,S) fp32 scores."""
    b, qlen, h, hd = q.shape
    kvh = cfg.n_kv_heads
    g = h // kvh
    qg = q.reshape(b, qlen, kvh, g, hd)
    scores = jnp.einsum("bqngh,bsnh->bngqs", qg.astype(jnp.float32),
                        k.astype(jnp.float32))
    return scores * (hd ** -0.5)


def _gqa_out(probs, v, params, cfg, out_dtype):
    """probs: (B,KV,G,Q,S), v: (B,S,KV,hd) -> (B,Q,D)."""
    b = probs.shape[0]
    out = jnp.einsum("bngqs,bsnh->bqngh", probs, v.astype(jnp.float32))
    out = out.reshape(b, out.shape[1], cfg.n_heads * cfg.head_dim)
    out = out.astype(out_dtype)
    return qdot(out, params["wo"])


def _causal_mask(qlen: int, klen: int, q_offset, window: int = 0):
    """(Q, S) additive mask; window>0 limits lookback."""
    qpos = jnp.arange(qlen)[:, None] + q_offset
    kpos = jnp.arange(klen)[None, :]
    ok = kpos <= qpos
    if window > 0:
        ok = ok & (kpos > qpos - window)
    return jnp.where(ok, 0.0, NEG_INF).astype(jnp.float32)


Q_CHUNK = 4096  # max query-block width for the unrolled blockwise attention


def self_attention(params, x, positions, cfg, kind: str,
                   causal: bool = True) -> Tuple[jnp.ndarray, dict]:
    """Full-sequence self-attention (train / prefill).

    Long sequences are processed in *statically unrolled* query blocks
    (Python loop, not lax.scan) so (a) the S x S score buffer never
    materializes — per-block peak is (B, H, Q_CHUNK, S) — and (b) HLO
    cost_analysis still counts every block's FLOPs (scan bodies are
    counted once; unrolled blocks are not).  Sliding-window blocks
    additionally slice K/V to the reachable window.  This is the jnp
    analogue of the Pallas flash kernel in repro.kernels.

    Returns (out, {"k","v"}) so prefill can populate the cache.
    """
    q = _proj_q(params, x, cfg)
    k, v = _proj_kv(params, x, cfg)
    q = rotary(q, positions, cfg.rope_theta)
    k = rotary(k, positions, cfg.rope_theta)
    s = x.shape[-2]
    window = cfg.window if kind == "swa" else 0

    if s <= Q_CHUNK:
        scores = _gqa_scores(q, k, cfg)
        if causal:
            scores = scores + _causal_mask(s, s, 0, window)
        probs = jax.nn.softmax(scores, axis=-1)
        out = _gqa_out(probs, v, params, cfg, x.dtype)
        return out, {"k": k, "v": v}

    outs = []
    for q0 in range(0, s, Q_CHUNK):
        q1 = min(q0 + Q_CHUNK, s)
        qb = q[:, q0:q1]
        if causal:
            k0 = max(0, q0 - window + 1) if window else 0
            k1 = q1  # keys beyond the block are masked anyway
        else:
            k0, k1 = 0, s
        kb, vb = k[:, k0:k1], v[:, k0:k1]
        scores = _gqa_scores(qb, kb, cfg)
        if causal:
            qpos = jnp.arange(q0, q1)[:, None]
            kpos = jnp.arange(k0, k1)[None, :]
            ok = kpos <= qpos
            if window:
                ok = ok & (kpos > qpos - window)
            scores = scores + jnp.where(ok, 0.0, NEG_INF).astype(jnp.float32)
        probs = jax.nn.softmax(scores, axis=-1)
        outs.append(_gqa_out(probs, vb, params, cfg, x.dtype))
    return jnp.concatenate(outs, axis=1), {"k": k, "v": v}


def cross_attention(params, x, kv: dict, cfg) -> jnp.ndarray:
    """x attends to precomputed (k, v) from another modality/stack."""
    q = _proj_q(params, x, cfg)  # no rotary across modalities
    scores = _gqa_scores(q, kv["k"], cfg)
    probs = jax.nn.softmax(scores, axis=-1)
    return _gqa_out(probs, kv["v"], params, cfg, x.dtype)


def make_cross_kv(params, src, cfg) -> dict:
    k, v = _proj_kv(params, src, cfg)
    return {"k": k, "v": v}


def chunk_self_attention(params, x, cache: dict, pos, cfg,
                         kind: str) -> Tuple[jnp.ndarray, dict]:
    """C-token cache-resuming attention (chunked prefill).

    x: (B,C,D) tokens at absolute positions pos[b] .. pos[b]+C-1;
    cache {"k","v"}: (B,S,KV,hd) holding all positions < pos[b]
    (ring-buffered for swa).  Returns (out, updated cache) such that the
    cache afterwards equals what C successive ``decode_self_attention``
    calls would have produced; out matches them token-for-token.
    """
    b, c, _ = x.shape
    cache_len = cache["k"].shape[1]
    q = _proj_q(params, x, cfg)
    k_new, v_new = _proj_kv(params, x, cfg)
    positions = pos[:, None] + jnp.arange(c)[None, :]          # (B,C)
    q = rotary(q, positions, cfg.rope_theta)
    k_new = rotary(k_new, positions, cfg.rope_theta)
    qpos = positions[:, None, :, None]                         # (B,1,C,1)

    if kind == "swa" and cfg.window:
        # --- ring buffer: future in-chunk writes may clobber slots a
        # query earlier in the chunk must still see, so score against
        # [old ring ; chunk keys] with analytic old positions instead of
        # write-then-mask.  Old slot j holds the most recent position
        # p < pos with p % W == j, i.e. p_old = pos - W + ((j - pos) mod W).
        w = cache_len
        j = jnp.arange(w)[None, :]
        p_old = pos[:, None] - w + (j - pos[:, None]) % w      # (B,W)
        k_all = jnp.concatenate([cache["k"], k_new], axis=1)
        v_all = jnp.concatenate([cache["v"], v_new], axis=1)
        kpos = jnp.concatenate(
            [p_old, positions], axis=1)[:, None, None, :]      # (B,1,1,W+C)
        valid = (kpos >= 0) & (kpos <= qpos) & (kpos > qpos - w)
        scores = _gqa_scores(q, k_all, cfg)
        scores = scores + jnp.where(valid, 0.0, NEG_INF).astype(
            jnp.float32)[:, :, None]                 # (B,1,1,C,W+C)
        probs = jax.nn.softmax(scores, axis=-1)
        out = _gqa_out(probs, v_all, params, cfg, x.dtype)
        # ring write: the last min(C, W) chunk keys land in the cache
        # (earlier ones would be clobbered; slicing avoids duplicate
        # scatter indices, whose write order is unspecified)
        keep = min(c, w)
        slots = positions[:, -keep:] % w
        bidx = jnp.arange(b)[:, None]
        k = cache["k"].at[bidx, slots].set(k_new[:, -keep:])
        v = cache["v"].at[bidx, slots].set(v_new[:, -keep:])
        return out, {"k": k, "v": v}

    # --- linear cache: write the chunk, then mask.  Slot index ==
    # position, so keys at slots >= pos[b]+i (in-chunk future or stale
    # entries from a previous occupant of this batch row) mask out and
    # slots < pos hold the true prefix.
    slots = jnp.minimum(positions, cache_len - 1)
    bidx = jnp.arange(b)[:, None]
    k = cache["k"].at[bidx, slots].set(k_new)
    v = cache["v"].at[bidx, slots].set(v_new)
    scores = _gqa_scores(q, k, cfg)                            # (B,KV,G,C,S)
    kpos = jnp.arange(cache_len)[None, None, None, :]
    valid = kpos <= qpos                                       # (B,1,C,S)
    scores = scores + jnp.where(valid, 0.0, NEG_INF).astype(
        jnp.float32)[:, :, None]                     # (B,1,1,C,S)
    probs = jax.nn.softmax(scores, axis=-1)
    out = _gqa_out(probs, v, params, cfg, x.dtype)
    return out, {"k": k, "v": v}


# ----------------------------------------------------------------------
# Paged variants: block pools + block tables (see models/kvcache.py).
# Same math as the dense paths below, addressed through per-request
# block tables; greedy outputs are bit-identical because the gathered
# view reproduces the dense cache's logical slot order and every
# stale/unallocated slot is masked exactly where the dense path masks
# its zero-initialised slots.
# ----------------------------------------------------------------------
def _paged_gather(pool, layer, tables, cfg, take: Optional[int] = None):
    """Layer ``layer`` of the stacked pool (L, NB, bs, KV*hd) gathered
    through tables (B, nb) into the logical view (B, nb*bs, KV, hd),
    optionally truncated to ``take`` slots (SWA ring / cross source
    shorter than the block grid)."""
    g = pool[layer, tables]                          # (B, nb, bs, KV*hd)
    b, nb, bs = g.shape[:3]
    g = g.reshape(b, nb * bs, cfg.n_kv_heads, cfg.head_dim)
    return g if take is None else g[:, :take]


def _slot_rows(kv):
    """(..., KV, hd) new keys or values -> (..., KV*hd) pool slot rows."""
    return kv.reshape(*kv.shape[:-2], -1)


def _decode_valid(pos, s: int, ring: bool):
    """(B, S) bool validity of cache slots for one-token decode: slot
    index <= pos, plus the ring's all-slots-valid regime once a SWA ring
    has fully wrapped (pos >= window - 1).  Shared by the dense and
    paged decode paths so their masking stays bit-for-bit aligned."""
    sidx = jnp.arange(s)
    valid = sidx[None, :] <= pos[:, None]
    if ring:
        valid = valid | (pos[:, None] >= s - 1)
    return valid


def paged_cross_view(cache: dict, paged: dict, src: int, cfg) -> dict:
    """Cross-KV logical view of each row's cross blocks at layer
    ``paged["layer"]`` (zeroed at admission, so this matches the dense
    engines' zero cross rows).  Read-only: the pools pass through."""
    layer, tables = paged["layer"], paged["cross_tables"]
    return {"k": _paged_gather(cache["xk"], layer, tables, cfg, src),
            "v": _paged_gather(cache["xv"], layer, tables, cfg, src)}


def paged_decode_self_attention(params, x, cache: dict, paged: dict, pos,
                                cfg, kind: str) -> Tuple[jnp.ndarray, dict]:
    """One-token decode against paged block pools.

    x: (B,1,D); cache {"k","v"}: (L, NB_phys, bs, KV*hd) stacked pools;
    paged carries the layer index ``layer`` into them and the block
    tables (``tables`` always; ``swa_tables`` for ring segments).
    Mirrors :func:`decode_self_attention` slot-for-slot: the new K/V
    lands at the physical home of the dense slot and scores run over
    the gathered logical view.  Returns the updated stacked pools.
    """
    b = x.shape[0]
    layer = paged["layer"]
    bs = cache["k"].shape[-2]
    max_len = paged["tables"].shape[1] * bs
    q = _proj_q(params, x, cfg)
    k_new, v_new = _proj_kv(params, x, cfg)
    q = rotary(q, pos[:, None], cfg.rope_theta)
    k_new = rotary(k_new, pos[:, None], cfg.rope_theta)

    if kind == "swa" and cfg.window:
        tables = paged["swa_tables"]
        s = min(cfg.window, max_len)       # dense ring size min(W, seq_len)
        slot = pos % s
    else:
        tables = paged["tables"]
        s = max_len
        slot = jnp.minimum(pos, s - 1)
    bidx = jnp.arange(b)
    phys = tables[bidx, slot // bs]
    off = slot % bs
    # rows of a decode batch own disjoint blocks; only inactive rows
    # share the scratch block (id 0), whose content is never read
    k_pool = cache["k"].at[layer, phys, off].set(_slot_rows(k_new[:, 0]))
    v_pool = cache["v"].at[layer, phys, off].set(_slot_rows(v_new[:, 0]))

    kg = _paged_gather(k_pool, layer, tables, cfg, s)
    vg = _paged_gather(v_pool, layer, tables, cfg, s)
    scores = _gqa_scores(q, kg, cfg)                 # (B,KV,G,1,S)
    valid = _decode_valid(pos, s, ring=(kind == "swa" and bool(cfg.window)))
    mask = jnp.where(valid, 0.0, NEG_INF).astype(jnp.float32)
    scores = scores + mask[:, None, None, None, :]
    probs = jax.nn.softmax(scores, axis=-1)
    out = _gqa_out(probs, vg, params, cfg, x.dtype)
    return out, {"k": k_pool, "v": v_pool}


def paged_chunk_self_attention(params, x, cache: dict, paged: dict, pos,
                               cfg, kind: str) -> Tuple[jnp.ndarray, dict]:
    """C-token cache-resuming attention against paged pools (chunked
    prefill of ONE request — tables in ``paged`` are the row's slices,
    batch dim 1).  Mirrors :func:`chunk_self_attention` branch-for-
    branch: linear segments write-then-mask through the table, SWA
    scores [old ring ∪ chunk keys] with analytic old-ring positions
    and ring-writes the last ``min(C, W)`` keys.  Pools are stacked
    and addressed at ``paged["layer"]``, as in
    :func:`paged_decode_self_attention`."""
    b, c, _ = x.shape
    layer = paged["layer"]
    bs = cache["k"].shape[-2]
    max_len = paged["tables"].shape[1] * bs
    q = _proj_q(params, x, cfg)
    k_new, v_new = _proj_kv(params, x, cfg)
    positions = pos[:, None] + jnp.arange(c)[None, :]          # (B,C)
    q = rotary(q, positions, cfg.rope_theta)
    k_new = rotary(k_new, positions, cfg.rope_theta)
    qpos = positions[:, None, :, None]                         # (B,1,C,1)
    bidx = jnp.arange(b)[:, None]

    if kind == "swa" and cfg.window:
        tables = paged["swa_tables"]
        w = min(cfg.window, max_len)
        j = jnp.arange(w)[None, :]
        p_old = pos[:, None] - w + (j - pos[:, None]) % w      # (B,W)
        k_old = _paged_gather(cache["k"], layer, tables, cfg, w)
        v_old = _paged_gather(cache["v"], layer, tables, cfg, w)
        k_all = jnp.concatenate([k_old, k_new], axis=1)
        v_all = jnp.concatenate([v_old, v_new], axis=1)
        kpos = jnp.concatenate(
            [p_old, positions], axis=1)[:, None, None, :]      # (B,1,1,W+C)
        valid = (kpos >= 0) & (kpos <= qpos) & (kpos > qpos - w)
        scores = _gqa_scores(q, k_all, cfg)
        scores = scores + jnp.where(valid, 0.0, NEG_INF).astype(
            jnp.float32)[:, :, None]
        probs = jax.nn.softmax(scores, axis=-1)
        out = _gqa_out(probs, v_all, params, cfg, x.dtype)
        keep = min(c, w)
        slots = positions[:, -keep:] % w
        phys = tables[bidx, slots // bs]
        off = slots % bs
        k = cache["k"].at[layer, phys, off].set(_slot_rows(k_new[:, -keep:]))
        v = cache["v"].at[layer, phys, off].set(_slot_rows(v_new[:, -keep:]))
        return out, {"k": k, "v": v}

    tables = paged["tables"]
    slots = jnp.minimum(positions, max_len - 1)
    phys = tables[bidx, slots // bs]
    off = slots % bs
    k = cache["k"].at[layer, phys, off].set(_slot_rows(k_new))
    v = cache["v"].at[layer, phys, off].set(_slot_rows(v_new))
    kg = _paged_gather(k, layer, tables, cfg)        # (B, max_len, KV, hd)
    vg = _paged_gather(v, layer, tables, cfg)
    scores = _gqa_scores(q, kg, cfg)
    kpos = jnp.arange(max_len)[None, None, None, :]
    valid = kpos <= qpos
    scores = scores + jnp.where(valid, 0.0, NEG_INF).astype(
        jnp.float32)[:, :, None]
    probs = jax.nn.softmax(scores, axis=-1)
    out = _gqa_out(probs, vg, params, cfg, x.dtype)
    return out, {"k": k, "v": v}


def decode_self_attention(params, x, cache: dict, pos, cfg,
                          kind: str) -> Tuple[jnp.ndarray, dict]:
    """One-token decode against a KV cache.

    x: (B,1,D); cache {"k","v"}: (B,S,KV,hd) (S = window for swa);
    pos: (B,) absolute position of the new token.
    """
    b, _, _ = x.shape
    cache_len = cache["k"].shape[1]
    q = _proj_q(params, x, cfg)
    k_new, v_new = _proj_kv(params, x, cfg)
    q = rotary(q, pos[:, None], cfg.rope_theta)
    k_new = rotary(k_new, pos[:, None], cfg.rope_theta)

    if kind == "swa":
        slot = pos % cache_len
    else:
        slot = jnp.minimum(pos, cache_len - 1)
    bidx = jnp.arange(b)
    k = cache["k"].at[bidx, slot].set(k_new[:, 0])
    v = cache["v"].at[bidx, slot].set(v_new[:, 0])

    scores = _gqa_scores(q, k, cfg)  # (B,KV,G,1,S)
    # swa: ring buffer — every slot valid once pos >= window-1
    valid = _decode_valid(pos, cache_len, ring=(kind == "swa"))
    mask = jnp.where(valid, 0.0, NEG_INF).astype(jnp.float32)
    scores = scores + mask[:, None, None, None, :]
    probs = jax.nn.softmax(scores, axis=-1)
    out = _gqa_out(probs, v, params, cfg, x.dtype)
    return out, {"k": k, "v": v}
