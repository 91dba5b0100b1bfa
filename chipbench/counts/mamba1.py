"""Operations and HBM bytes a Mamba1 model's served programs need,
counted from shapes: the work of the algorithm, not of one way to run it.

- A decode iteration reads every layer's weights and the head once, and
  reads and writes each live row's recurrent state (float32) and
  convolution window (the model's type).
- A prefill chunk of ``c`` tokens reads the layer weights once and the
  row's state, and writes the state back.  It computes no head.

FLOPs count the projections (a multiply-add as two), the depthwise
convolution, and 7 operations per state element per token for the
recurrence (discretise, decay, input, accumulate, read-out).  ``A_log``
and ``D`` are float32 in the model, everything else in its type.
"""
from __future__ import annotations


def _sizes(m, w):
    """Sizes for a model whose type takes ``w`` bytes."""
    d, di, ds, cw, L = (m["d_model"], m["d_inner"], m["ssm_state"],
                        m["conv_width"], m["n_layers"])
    r = -(-d // 16)
    mm = d * 2 * di + di * (r + 2 * ds) + r * di + di * d
    return {
        "L": L, "d": d, "head": m["vocab_size"] * d,
        # projections, conv_w, conv_b, dt_bias, norm; A_log and D float32
        "layer_bytes": w * (mm + cw * di + 2 * di + d) + 4 * di * (ds + 1),
        "state_bytes": 4 * di * ds + w * (cw - 1) * di,
        "tok_flops": 2 * mm + 2 * cw * di + 7 * di * ds,
    }


def decode_iteration(m, ctx, width=2):
    """(flops, bytes) of one decode iteration over ``len(ctx)`` live rows
    (the context length does not change a recurrence's work)."""
    s = _sizes(m, width)
    n = len(ctx)
    flops = n * (s["L"] * s["tok_flops"] + 2 * s["head"])
    byts = (s["L"] * s["layer_bytes"] + width * (s["head"] + s["d"])
            + n * (width * s["d"] + 2 * s["L"] * s["state_bytes"]))
    return float(flops), float(byts)


def prefill_chunk(m, c, p0, width=2):
    """(flops, bytes) of one prefill chunk of ``c`` tokens."""
    s = _sizes(m, width)
    flops = c * s["L"] * s["tok_flops"]
    byts = (s["L"] * s["layer_bytes"] + width * c * s["d"]
            + 2 * s["L"] * s["state_bytes"])
    return float(flops), float(byts)
