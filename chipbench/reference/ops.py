"""Float32 building blocks of the references.

Every matrix product runs at ``Precision.HIGHEST``: on a TPU a float32
product is otherwise computed in bfloat16 passes.  ``mode="fp8"`` is the
control of the correctness check: both operands of every weight product
are rounded to float8 e4m3 first (weights per output channel, activations
per row, each scaled to the format's largest value), the precision one
step below the bfloat16 the configurations state.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
F8_MAX = 448.0  # largest finite float8 e4m3fn


def fp8_round(a, axis):
    """``a`` rounded to float8 e4m3, scaled per slice along ``axis``."""
    amax = jnp.max(jnp.abs(a), axis=axis, keepdims=True)
    s = jnp.where(amax > 0, amax / F8_MAX, 1.0)
    return (a / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def mm(x, w, mode: str = "f32"):
    """x (..., k) @ w (k, n) in float32 (or the fp8 control)."""
    x = x.astype(jnp.float32)
    w = w.astype(jnp.float32)
    if mode == "fp8":
        x = fp8_round(x, -1)
        w = fp8_round(w, 0)
    return jnp.matmul(x, w, precision=HIGHEST)


def rmsnorm(x, scale, eps):
    x = x.astype(jnp.float32)
    y = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return y * scale.astype(jnp.float32)


def silu(x):
    return x * jax.nn.sigmoid(x)


def normal(key, shape, scale, dtype):
    """Seeded normal weights made in their served type."""
    return (jax.random.normal(key, shape, dtype) * scale).astype(dtype)


def bucket(n: int, size: int = 256) -> int:
    """Padded sequence length: the references run right-padded
    sequences (causal, so padding never reaches an earlier position),
    one compiled program per bucket."""
    return -(-n // size) * size


def logit_gaps(ref_logits, tokens):
    """Per position, by how much the reference's best logit lies above
    its logit of ``tokens`` (the served ones, or the control's choice),
    and whether ``tokens`` is the reference's top token."""
    best = jnp.max(ref_logits, axis=-1)
    at = jnp.take_along_axis(ref_logits, tokens[:, None], axis=-1)[:, 0]
    return best - at, jnp.argmax(ref_logits, axis=-1) == tokens
