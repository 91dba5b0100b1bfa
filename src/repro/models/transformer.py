"""Blocks + segment-scan stacking.

A model is a ``block_pattern`` (one kind per layer).  Contiguous runs of the
same kind are *segments*: their params are stacked with a leading layer dim
and applied with ``lax.scan`` — this keeps lowering/compile time roughly
O(#segments), not O(#layers), which matters for the 512-device dry-run of
80–100-layer models.

Weight-shared blocks (zamba2) draw params from a single ``shared`` set and
are applied outside the scan.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import attention as attn_mod
from repro.models import moe as moe_mod
from repro.models import ssm as ssm_mod
from repro.models.layers import mlp, mlp_init, rmsnorm, rmsnorm_init
from repro.sharding.specs import constrain


@dataclass(frozen=True)
class Segment:
    kind: str
    length: int
    shared: bool


def build_segments(cfg) -> List[Segment]:
    segs: List[Segment] = []
    for b in cfg.block_pattern:
        shared = b == cfg.shared_block_kind
        if segs and segs[-1].kind == b and not shared and not segs[-1].shared:
            segs[-1] = Segment(b, segs[-1].length + 1, False)
        else:
            segs.append(Segment(b, 1, shared))
    return segs


# ----------------------------------------------------------------------
# Single block
# ----------------------------------------------------------------------
def _has_mlp(kind: str, cfg) -> bool:
    return kind in ("attn", "swa", "cross") and cfg.mlp_kind != "none"


def block_init(key, kind: str, cfg, dtype, has_enc_cross: bool = False) -> dict:
    ks = jax.random.split(key, 6)
    d = cfg.d_model
    p = {"ln1": rmsnorm_init(d, dtype)}
    if kind in ("attn", "swa"):
        p["attn"] = attn_mod.attention_init(ks[0], cfg, dtype)
    elif kind == "cross":
        p["xattn"] = attn_mod.attention_init(ks[0], cfg, dtype, cross=True)
    elif kind == "mamba1":
        p["mamba"] = ssm_mod.mamba1_init(ks[0], cfg, dtype)
    elif kind == "mamba2":
        p["mamba"] = ssm_mod.mamba2_init(ks[0], cfg, dtype)
    if has_enc_cross and kind in ("attn", "swa"):
        p["ln_x"] = rmsnorm_init(d, dtype)
        p["enc_xattn"] = attn_mod.attention_init(ks[1], cfg, dtype, cross=True)
    if _has_mlp(kind, cfg):
        p["ln2"] = rmsnorm_init(d, dtype)
        if cfg.mlp_kind == "moe":
            p["moe"] = moe_mod.moe_init(ks[2], cfg, dtype)
        else:
            p["mlp"] = mlp_init(ks[2], d, cfg.d_ff, dtype)
    return p


def _empty_aux():
    return {"moe_aux_loss": jnp.zeros((), jnp.float32),
            "moe_drop_frac": jnp.zeros((), jnp.float32)}


def block_apply(params: dict, x, *, kind: str, cfg, mode: str,
                positions=None, pos=None, cache: Optional[dict] = None,
                frontend=None, enc_src=None, causal: bool = True,
                paged: Optional[dict] = None,
                qformat: Optional[str] = None,
                ) -> Tuple[jnp.ndarray, Optional[dict], dict]:
    """Apply one block.  Returns (x, cache_out, aux).

    ``paged`` switches the decode/chunk cache paths to block-pool
    addressing (block tables from ``models.kvcache.PagedCache.meta``,
    plus ``layer``, this layer's index into the segment's stacked
    pools, which ``cache`` then holds whole); train/prefill modes are
    dense-only.

    ``qformat`` tags the weight format the params were packed to
    ("int8"/"int4", `models/quantize.py`).  Numeric dispatch is
    *structural* — ``qdot`` routes on packed-leaf-vs-array, so a block
    whose weights stayed dense (SSM, MoE, odd-K) runs the exact dense
    math — but the tag travels with the call so jit keys, stage
    slices, and the roofline audit all see which format they measure.
    """
    aux = _empty_aux()
    h = rmsnorm(params["ln1"], x, cfg.norm_eps)
    cache_out = None

    if kind in ("attn", "swa"):
        if mode == "decode":
            if paged is not None:
                a, kv = attn_mod.paged_decode_self_attention(
                    params["attn"], h, cache, paged, pos, cfg, kind)
            else:
                a, kv = attn_mod.decode_self_attention(
                    params["attn"], h, {"k": cache["k"], "v": cache["v"]},
                    pos, cfg, kind)
            cache_out = dict(cache, **kv)
        elif mode == "chunk":
            if paged is not None:
                a, kv = attn_mod.paged_chunk_self_attention(
                    params["attn"], h, cache, paged, pos, cfg, kind)
            else:
                a, kv = attn_mod.chunk_self_attention(
                    params["attn"], h, {"k": cache["k"], "v": cache["v"]},
                    pos, cfg, kind)
            cache_out = dict(cache, **kv)
        else:
            a, kv = attn_mod.self_attention(params["attn"], h, positions,
                                            cfg, kind, causal=causal)
            if mode == "prefill":
                cache_out = _seed_attn_cache(kv, cache, kind, cfg)
        x = x + a
        if "enc_xattn" in params:  # enc-dec decoder block
            hx = rmsnorm(params["ln_x"], x, cfg.norm_eps)
            if mode in ("decode", "chunk"):
                xkv = (attn_mod.paged_cross_view(cache, paged,
                                                 cfg.encoder_seq, cfg)
                       if paged is not None
                       else {"k": cache["xk"], "v": cache["xv"]})
            else:
                xkv = attn_mod.make_cross_kv(params["enc_xattn"], enc_src, cfg)
                if mode == "prefill":
                    cache_out = dict(cache_out or cache,
                                     xk=xkv["k"], xv=xkv["v"])
            x = x + attn_mod.cross_attention(params["enc_xattn"], hx, xkv, cfg)
    elif kind == "cross":
        if mode in ("decode", "chunk"):
            if paged is not None:
                src = cfg.n_image_tokens or cfg.encoder_seq
                xkv = attn_mod.paged_cross_view(cache, paged, src, cfg)
            else:
                xkv = {"k": cache["xk"], "v": cache["xv"]}
            cache_out = cache
        else:
            xkv = attn_mod.make_cross_kv(params["xattn"], frontend, cfg)
            if mode == "prefill":
                cache_out = {"xk": xkv["k"], "xv": xkv["v"]}
        x = x + attn_mod.cross_attention(params["xattn"], h, xkv, cfg)
    elif kind in ("mamba1", "mamba2"):
        fn_seq = ssm_mod.mamba1_seq if kind == "mamba1" else ssm_mod.mamba2_seq
        fn_step = ssm_mod.mamba1_step if kind == "mamba1" else ssm_mod.mamba2_step
        if mode == "decode":
            a, (hs, cs) = fn_step(params["mamba"], h, (cache["h"], cache["conv"]),
                                  cfg)
            cache_out = {"h": hs, "conv": cs}
        elif mode == "chunk":
            a, (hs, cs) = fn_seq(params["mamba"], h, cfg,
                                 h0=cache["h"], conv_state=cache["conv"])
            cache_out = {"h": hs, "conv": cs}
        else:
            a, (hs, cs) = fn_seq(params["mamba"], h, cfg)
            if mode == "prefill":
                cache_out = {"h": hs, "conv": cs}
        x = x + a
    else:
        raise ValueError(kind)

    if _has_mlp(kind, cfg):
        h2 = rmsnorm(params["ln2"], x, cfg.norm_eps)
        if cfg.mlp_kind == "moe":
            m, moe_aux = moe_mod.moe_apply(params["moe"], h2, cfg)
            aux = {k: aux[k] + moe_aux[k] for k in aux}
        else:
            m = mlp(params["mlp"], h2)
        x = x + m
    return constrain(x, "act_btd"), cache_out, aux


def _seed_attn_cache(kv, cache, kind, cfg):
    """Write prefill K/V into a fixed-size cache buffer."""
    if cache is None:
        return kv
    k, v = kv["k"], kv["v"]
    s_cache = cache["k"].shape[-3]
    s_new = k.shape[-3]
    if kind == "swa" and s_new > s_cache:
        # keep last `window` entries; ring-consistent because slot = pos % W
        # and after a full wrap the ring holds exactly the last W positions
        # in rotated order (attention is permutation-invariant post-rope).
        start = s_new - s_cache
        shift = start % s_cache
        k_tail = jnp.roll(k[..., start:, :, :], shift, axis=-3)
        v_tail = jnp.roll(v[..., start:, :, :], shift, axis=-3)
        return dict(cache, k=k_tail, v=v_tail)
    pad = s_cache - min(s_new, s_cache)
    k_new = jnp.pad(k[..., -s_cache:, :, :], _pad_spec(k, pad))
    v_new = jnp.pad(v[..., -s_cache:, :, :], _pad_spec(v, pad))
    return dict(cache, k=k_new.astype(cache["k"].dtype),
                v=v_new.astype(cache["v"].dtype))


def _pad_spec(arr, pad):
    spec = [(0, 0)] * arr.ndim
    spec[-3] = (0, pad)
    return spec


# ----------------------------------------------------------------------
# Segment init / apply
# ----------------------------------------------------------------------
def init_segments(key, cfg, dtype, has_enc_cross: bool = False):
    segs = build_segments(cfg)
    keys = jax.random.split(key, len(segs) + 1)
    seg_params = []
    shared_params = None
    for seg, k in zip(segs, keys):
        if seg.shared:
            if shared_params is None:
                shared_params = block_init(keys[-1], seg.kind, cfg, dtype,
                                           has_enc_cross)
            seg_params.append(None)
        elif seg.length == 1:
            seg_params.append(block_init(k, seg.kind, cfg, dtype,
                                         has_enc_cross))
        else:
            ks = jax.random.split(k, seg.length)
            seg_params.append(
                jax.vmap(lambda kk: block_init(kk, seg.kind, cfg, dtype,
                                               has_enc_cross))(ks))
    return {"segments": seg_params, "shared": shared_params}


# ----------------------------------------------------------------------
# Layer-range restriction (pipeline-parallel stages)
# ----------------------------------------------------------------------
def segment_slices(cfg, lo: int, hi: int):
    """Map decoder layers [lo, hi) onto the segment list.

    Returns [(seg_index, a, b)]: full-model segment ``seg_index``
    contributes its local layers [a, b).  Stage boundaries may fall
    inside a segment, in which case the stacked params/caches are sliced
    along their leading layer dim.
    """
    assert 0 <= lo < hi <= cfg.n_layers, (lo, hi, cfg.n_layers)
    out = []
    base = 0
    for i, seg in enumerate(build_segments(cfg)):
        a, b = max(lo, base), min(hi, base + seg.length)
        if a < b:
            out.append((i, a - base, b - base))
        base += seg.length
    return out


def segment_range(cfg, lo: int, hi: int) -> List[Segment]:
    """Segment list restricted to decoder layers [lo, hi)."""
    segs = build_segments(cfg)
    return [Segment(segs[i].kind, b - a, segs[i].shared)
            for i, a, b in segment_slices(cfg, lo, hi)]


def slice_blocks(blocks: dict, cfg, lo: int, hi: int) -> dict:
    """Restrict a ``{"segments", "shared"}`` param tree to layers [lo, hi).

    The result aligns with :func:`segment_range` and holds *only* the
    stage's parameters (plus the shared set, which weight-tied layers
    draw from wherever they run) — a pipeline stage sliced this way owns
    nothing outside its layer range.
    """
    segs = build_segments(cfg)
    sub = []
    for i, a, b in segment_slices(cfg, lo, hi):
        p = blocks["segments"][i]
        if segs[i].shared or p is None:
            sub.append(None)
        elif segs[i].length == 1:
            sub.append(p)                      # unstacked single layer
        elif b - a == 1:
            sub.append(jax.tree.map(lambda t: t[a], p))  # noqa: B023
        else:
            sub.append(jax.tree.map(lambda t: t[a:b], p))  # noqa: B023
    return {"segments": sub, "shared": blocks["shared"]}


def apply_segments(blocks, x, *, cfg, mode, segs=None, positions=None,
                   pos=None, caches=None, frontend=None, enc_src=None,
                   causal=True, remat=None, unroll=False, paged=None,
                   qformat=None):
    """Run all segments.  caches: list aligned with segments (or None).

    remat: checkpoint each block in training so backward recomputes
    activations (defaults to True for mode=="train").
    unroll: replace lax.scan with a Python loop (used by the roofline cost
    audit, where scan bodies would be counted once by cost_analysis).
    paged: block-table metadata dict for paged decode/chunk caches —
    shared by every segment (tables are per-request, not per-layer), so
    it rides in the closure, not through the scan.  A segment's paged
    pools (attn/swa/cross leaves) are the scan's *carry*, not its
    ``xs``/``ys``: each layer gets its index as ``paged["layer"]`` and
    writes and reads its slots of the stacked pool in place, so no
    layer is sliced out of the pool or stacked back into it.  Dense
    caches and SSM state rows keep the ``xs``/``ys`` path.
    qformat: weight-format tag for packed params (models/quantize.py) —
    rides in the closure like ``paged``; packed {"q","s"} leaves stack
    and slice through the scan exactly like dense weights.
    """
    segs = segs if segs is not None else build_segments(cfg)
    remat = (mode == "train") if remat is None else remat
    aux_total = _empty_aux()
    new_caches = []
    for i, seg in enumerate(segs):
        params = blocks["shared"] if seg.shared else blocks["segments"][i]
        cache = caches[i] if caches is not None else None
        kw = dict(kind=seg.kind, cfg=cfg, mode=mode, positions=positions,
                  pos=pos, frontend=frontend, enc_src=enc_src, causal=causal,
                  paged=paged, qformat=qformat)

        def apply_one(p, xx, c):
            return block_apply(p, xx, cache=c, **kw)

        if remat:
            apply_one = jax.checkpoint(apply_one)

        if (paged is not None and cache is not None
                and seg.kind in ("attn", "swa", "cross")):
            x, c_out, aux = _apply_pooled(params, x, cache, seg, paged, kw)
        elif seg.length == 1 or seg.shared:
            c0 = (None if cache is None
                  else jax.tree.map(lambda a: a[0], cache))
            x, c_out, aux = apply_one(params, x, c0)
            if c_out is not None:
                c_out = jax.tree.map(lambda a: a[None], c_out)
        elif unroll:
            c_outs, auxes = [], []
            for j in range(seg.length):
                pj = jax.tree.map(lambda a: a[j], params)
                cj = None if cache is None else jax.tree.map(
                    lambda a: a[j], cache)
                x, c_out, aux = apply_one(pj, x, cj)
                c_outs.append(c_out)
                auxes.append(aux)
            c_out = (None if c_outs[0] is None else jax.tree.map(
                lambda *a: jnp.stack(a), *c_outs))
            aux = jax.tree.map(lambda *a: sum(a), *auxes)
        else:
            def body(carry, slices):
                p, c = slices
                y, c_out, aux = apply_one(p, carry, c)
                return y, (c_out, aux)
            x, (c_out, aux_stack) = jax.lax.scan(body, x, (params, cache))
            aux = jax.tree.map(jnp.sum, aux_stack)
        aux_total = {k: aux_total[k] + aux[k] for k in aux_total}
        new_caches.append(c_out)
    return x, new_caches, aux_total


def _apply_pooled(params, x, pools, seg, paged, kw):
    """One segment against its stacked paged pools: the pools ride in
    the layer scan's carry and layer ``l`` addresses them at
    ``paged["layer"] = l`` (`models/attention.py`).  Returns
    (x, pools, aux) like the ``xs``/``ys`` branches of
    :func:`apply_segments`.  Always a scan: ``unroll`` serves the
    roofline audit, which runs dense caches."""
    def apply_one(p, xx, c, layer):
        return block_apply(p, xx, cache=c,
                           **dict(kw, paged=dict(paged, layer=layer)))

    if seg.length == 1 or seg.shared:
        return apply_one(params, x, pools, 0)

    def body(carry, slices):
        xx, c = carry
        p, layer = slices
        y, c, aux = apply_one(p, xx, c, layer)
        return (y, c), aux
    (x, pools), aux_stack = jax.lax.scan(
        body, (x, pools), (params, jnp.arange(seg.length)))
    return x, pools, jax.tree.map(jnp.sum, aux_stack)
