"""Operations and HBM bytes a Llama-style decoder's served programs need,
counted from shapes: the work of the algorithm, not of one way to run it.

- A decode iteration reads every layer's weights and the head once, the
  key/value entries of each live row's context, and writes each live
  row's new entry.  Padded rows, positions past a row's own and any
  gathered copy of the cache are not counted.
- A prefill chunk of ``c`` tokens at position ``p0`` reads the layer
  weights once and the ``p0`` earlier entries, and writes ``c`` entries.
  It computes no head (the program discards prompt logits).

Weights and the cache are in the model's type (2 bytes for bfloat16);
norm scales are counted with the weights.  FLOPs count a multiply-add as
two; attention costs 4 * heads * head_dim per key.
"""
from __future__ import annotations

import numpy as np


def _sizes(m):
    d, h, kv, hd, ff, L = (m["d_model"], m["n_heads"], m["n_kv_heads"],
                           m["head_dim"], m["d_ff"], m["n_layers"])
    mm = d * h * hd + 2 * d * kv * hd + h * hd * d + 3 * d * ff
    return {"layer_mm": mm, "layer": mm + 2 * d, "L": L, "d": d,
            "head": m["vocab_size"] * d, "kv_tok": 2 * kv * hd,
            "attn": 4 * h * hd}


def decode_iteration(m, ctx, width=2):
    """(flops, bytes) of one decode iteration whose live rows attend over
    ``ctx`` keys each (a row at position p attends over p + 1)."""
    s = _sizes(m)
    ctx = np.asarray(ctx, dtype=np.float64)
    n = len(ctx)
    flops = (n * 2 * (s["L"] * s["layer_mm"] + s["head"])
             + s["L"] * s["attn"] * ctx.sum())
    byts = width * (s["L"] * s["layer"] + s["head"] + s["d"]
                    + n * s["d"]                        # embedding rows
                    + s["L"] * s["kv_tok"] * (ctx.sum() + n))
    return float(flops), float(byts)


def prefill_chunk(m, c, p0, width=2):
    """(flops, bytes) of one prefill chunk of ``c`` tokens at ``p0``."""
    s = _sizes(m)
    keys = c * p0 + c * (c + 1) / 2       # sum over the chunk of (p + 1)
    flops = c * 2 * s["L"] * s["layer_mm"] + s["L"] * s["attn"] * keys
    byts = width * (s["L"] * s["layer"] + c * s["d"]
                    + s["L"] * s["kv_tok"] * (p0 + c))
    return float(flops), float(byts)
