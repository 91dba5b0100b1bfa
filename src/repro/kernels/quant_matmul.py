"""Fused dequantize-matmul Pallas kernels (weight-only int8 / int4).

Decode is memory-bandwidth-bound, so the tokens/s lever is bytes moved
per weight: int8 streams 2x fewer bytes than bf16, packed int4 ~3.6x
(half a byte per weight plus one f32 scale per 64-group).  The
dequantize happens *inside* the matmul tile — the f32 weight tile
exists only in VMEM, never in HBM — which is what makes the format a
bandwidth win rather than a convert-then-matmul wash.

Layouts (produced by ``models/quantize.py``):

* int8 — ``q`` (K, N) int8, ``s`` (1, N) f32: per-output-channel
  symmetric scales, ``w = q * s``.
* int4 — ``q`` (K//2, N) uint8 packing two biased nibbles per byte
  (packed row r holds k=2r in the low nibble, k=2r+1 in the high
  nibble; value = nibble - 8), ``s`` (K//G, N) f32 per-group scales
  along K: ``w[k] = (nibble[k] - 8) * s[k // G]``.

Tolerances: the Pallas kernels match the ``ref.py`` oracles to f32
round-off (different accumulation order; allclose atol 1e-3 at unit
scale) — both dequantize to f32 before the dot.  Against the
*unquantized* dense matmul the error is the quantization error
itself: rel-RMS ~1e-2 for int8, ~1e-1 for int4 on Gaussian weights
(tests/test_quant_matmul.py pins both).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

DEFAULT_BLOCK_M = 256
DEFAULT_BLOCK_N = 256


def _qmm_int8_kernel(x_ref, q_ref, s_ref, o_ref):
    x = x_ref[...].astype(jnp.float32)
    w = q_ref[...].astype(jnp.float32)          # dequant in-tile (VMEM)
    o_ref[...] = ((x @ w) * s_ref[...]).astype(o_ref.dtype)


def _qmm_int4_kernel(x_ref, q_ref, s_ref, o_ref, *, group: int):
    # unpack in int32: the TPU compiler cannot lower 8-bit subtraction
    packed = q_ref[...].astype(jnp.int32)        # (K//2, bn)
    lo = (packed & 0xF) - 8
    hi = (packed >> 4) - 8
    k2, bn = packed.shape
    w = jnp.stack([lo, hi], axis=1).reshape(2 * k2, bn).astype(jnp.float32)
    w = w * jnp.repeat(s_ref[...], group, axis=0)
    o_ref[...] = (x_ref[...].astype(jnp.float32) @ w).astype(o_ref.dtype)


def quant_matmul_pallas(x, q, s, block_m: int = DEFAULT_BLOCK_M,
                        block_n: int = DEFAULT_BLOCK_N,
                        interpret: bool = True):
    """x (..., K) @ dequant(q, s) -> (..., N) in x.dtype.

    Format is inferred from ``q.dtype``: int8 = per-channel, uint8 =
    packed int4 per-group (see module docstring for layouts).
    """
    orig_shape = x.shape
    k = orig_shape[-1]
    n = q.shape[-1]
    int4 = q.dtype == jnp.uint8
    if int4:
        assert q.shape[-2] * 2 == k, (q.shape, k)
        group = k // s.shape[-2]
    else:
        assert q.shape[-2] == k, (q.shape, k)

    xf = x.reshape(-1, k)
    rows = xf.shape[0]
    bm = min(block_m, rows)
    nm = -(-rows // bm)
    pad_m = nm * bm - rows
    if pad_m:
        xf = jnp.pad(xf, ((0, pad_m), (0, 0)))
    bn = min(block_n, n)
    nn = -(-n // bn)
    pad_n = nn * bn - n
    if pad_n:
        q = jnp.pad(q, ((0, 0), (0, pad_n)))
        s = jnp.pad(s, ((0, 0), (0, pad_n)))

    if int4:
        kernel = functools.partial(_qmm_int4_kernel, group=group)
        q_rows = k // 2
    else:
        kernel = _qmm_int8_kernel
        q_rows = k
    out = pl.pallas_call(
        kernel,
        grid=(nm, nn),
        in_specs=[
            pl.BlockSpec((bm, k), lambda i, j: (i, 0)),
            pl.BlockSpec((q_rows, bn), lambda i, j: (0, j)),
            pl.BlockSpec((s.shape[0], bn), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((xf.shape[0], nn * bn), x.dtype),
        interpret=interpret,
    )(xf, q, s)
    out = out[:rows, :n]
    return out.reshape(*orig_shape[:-1], n)
