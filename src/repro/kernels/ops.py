"""Public jit'd wrappers for the Pallas kernels.

On the CPU backend kernels run in the Pallas interpreter (interpret
mode, for correctness tests); on any other backend they compile.  An
explicit ``interpret=`` overrides that choice.  Each op falls back to
the ref.py oracle with use_pallas=False.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.decode_attention import decode_attention_pallas
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.quant_matmul import quant_matmul_pallas
from repro.kernels.rmsnorm import rmsnorm_pallas
from repro.kernels.selective_scan import selective_scan_pallas


def _interpret_default() -> bool:
    return jax.default_backend() == "cpu"


@functools.partial(jax.jit, static_argnames=("causal", "window",
                                             "use_pallas", "interpret"))
def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    use_pallas: bool = True, interpret: bool | None = None):
    if not use_pallas:
        return ref.flash_attention_ref(q, k, v, causal=causal, window=window)
    interpret = _interpret_default() if interpret is None else interpret
    return flash_attention_pallas(q, k, v, causal=causal, window=window,
                                  interpret=interpret)


# reprolint: disable-next=jit-donation -- read-only KV view: returns
# attention output, not an updated cache; donating would invalidate
# the caller's live cache buffers (engines donate at their own jits)
@functools.partial(jax.jit, static_argnames=("use_pallas", "interpret"))
def decode_attention(q, k_cache, v_cache, pos, *, use_pallas: bool = True,
                     interpret: bool | None = None):
    if not use_pallas:
        return ref.decode_attention_ref(q, k_cache, v_cache, pos)
    interpret = _interpret_default() if interpret is None else interpret
    return decode_attention_pallas(q, k_cache, v_cache, pos,
                                   interpret=interpret)


@functools.partial(jax.jit, static_argnames=("use_pallas", "interpret"))
def selective_scan(dt, b_mat, c_mat, x, a_neg, h0, *,
                   use_pallas: bool = True, interpret: bool | None = None):
    if not use_pallas:
        return ref.selective_scan_ref(dt, b_mat, c_mat, x, a_neg, h0)
    interpret = _interpret_default() if interpret is None else interpret
    return selective_scan_pallas(dt, b_mat, c_mat, x, a_neg, h0,
                                 interpret=interpret)


@functools.partial(jax.jit, static_argnames=("use_pallas", "interpret"))
def quant_matmul(x, q, s, *, use_pallas: bool = True,
                 interpret: bool | None = None):
    """Weight-only dequant-fused matmul; format inferred from q.dtype
    (int8 = per-channel, uint8 = packed int4 per-group — layouts in
    kernels/quant_matmul.py; producer in models/quantize.py)."""
    if not use_pallas:
        if q.dtype == jnp.int8:
            return ref.quant_matmul_int8_ref(x, q, s)
        return ref.quant_matmul_int4_ref(x, q, s)
    interpret = _interpret_default() if interpret is None else interpret
    return quant_matmul_pallas(x, q, s, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("eps", "use_pallas",
                                             "interpret"))
def rmsnorm(x, scale, eps: float = 1e-5, *, use_pallas: bool = True,
            interpret: bool | None = None):
    if not use_pallas:
        return ref.rmsnorm_ref(x, scale, eps)
    interpret = _interpret_default() if interpret is None else interpret
    return rmsnorm_pallas(x, scale, eps, interpret=interpret)
