"""Plain float32 reference of a Mamba1 language model (Falcon-Mamba
family): RMSNorm, then the selective-scan mixer of Gu and Dao (2023)
with a residual around it, a final RMSNorm and an untied head.

The mixer, per token t of one sequence:

    x, z   = split(in_proj(h))                 # d_inner each
    x      = silu(causal depthwise conv(x) + conv_b)
    r, B, C = split(x_proj(x))                 # dt_rank, state, state
    dt     = softplus(dt_proj(r) + dt_bias)
    s_t    = exp(dt * A) * s_{t-1} + (dt * x) B    with A = -exp(A_log)
    y      = (s_t . C + D * x) * silu(z)
    out    = out_proj(y)

Falcon-Mamba also RMS-normalises B, C and dt (``mixer_rms``); the
configuration says whether the served model does, and this follows it.
One sequence at a time, a plain scan over time, no cache, no batching.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from chipbench.reference.ops import mm, normal, rmsnorm, silu


def dt_rank(m) -> int:
    return -(-m["d_model"] // 16)


def padded_vocab(m) -> int:
    return -(-m["vocab_size"] // 256) * 256


def _layer_weights(m, key):
    d, di, ds, cw = m["d_model"], m["d_inner"], m["ssm_state"], \
        m["conv_width"]
    r = dt_rank(m)
    dt = jnp.dtype(m["dtype"])
    f32 = jnp.float32
    k = jax.random.split(key, 10)
    return {
        "norm": 1.0 + normal(k[0], (d,), 0.1, dt),
        "in_proj": normal(k[1], (d, 2 * di), d ** -0.5, dt),
        "conv_w": normal(k[2], (cw, di), 0.5, dt),
        "conv_b": normal(k[3], (di,), 0.1, dt),
        "x_proj": normal(k[4], (di, r + 2 * ds), di ** -0.5, dt),
        "dt_proj": normal(k[5], (r, di), r ** -0.5, dt),
        "dt_bias": -2.0 + normal(k[6], (di,), 0.5, dt),
        "A_log": (jnp.log(jnp.arange(1, ds + 1, dtype=f32))[None, :]
                  + normal(k[7], (di, ds), 0.1, f32)),
        "D": 1.0 + normal(k[8], (di,), 0.1, f32),
        "out_proj": normal(k[9], (di, d), di ** -0.5, dt),
    }


def make_weights(m, key):
    """All weights from ``key``, layer by layer inside one program (so no
    temporary larger than a layer's is ever live)."""
    dt = jnp.dtype(m["dtype"])
    d = m["d_model"]
    k_emb, k_layers, k_norm, k_head = jax.random.split(key, 4)
    w = {
        "embed": normal(k_emb, (padded_vocab(m), d), d ** -0.5, dt),
        "layers": jax.lax.map(lambda k: _layer_weights(m, k),
                              jax.random.split(k_layers, m["n_layers"])),
        "final_norm": 1.0 + normal(k_norm, (d,), 0.1, dt),
    }
    if not m["tie_embeddings"]:
        w["lm_head"] = normal(k_head, (padded_vocab(m), d), d ** -0.5, dt)
    return w


MIXER = ("in_proj", "conv_w", "conv_b", "x_proj", "dt_proj", "dt_bias",
         "A_log", "D", "out_proj")


def to_program(m, w):
    """The same arrays in the program's parameter tree."""
    L = w["layers"]
    seg = {"ln1": {"scale": L["norm"]}, "mamba": {k: L[k] for k in MIXER}}
    p = {"embed": {"w": w["embed"]},
         "blocks": {"segments": [seg], "shared": None},
         "final_norm": {"scale": w["final_norm"]}}
    if "lm_head" in w:
        p["lm_head"] = {"w": w["lm_head"]}
    return p


def head_weight(m, w):
    return w["embed"] if m["tie_embeddings"] else w["lm_head"]


def layer(m, layers, i, x, mode="f32"):
    """Layer ``i`` over one sequence x (T, d), float32."""
    p = jax.tree.map(lambda a: a[i].astype(jnp.float32), layers)
    t = x.shape[0]
    di, ds, cw = m["d_inner"], m["ssm_state"], m["conv_width"]
    r = dt_rank(m)
    h = rmsnorm(x, p["norm"], m["norm_eps"])
    xz = mm(h, p["in_proj"], mode)
    xi, z = xz[:, :di], xz[:, di:]
    pad = jnp.concatenate([jnp.zeros((cw - 1, di), jnp.float32), xi])
    conv = sum(pad[j:j + t] * p["conv_w"][j] for j in range(cw))
    xc = silu(conv + p["conv_b"])
    proj = mm(xc, p["x_proj"], mode)
    dr, b, c = proj[:, :r], proj[:, r:r + ds], proj[:, r + ds:]
    if m.get("mixer_rms"):
        eps = m["mixer_rms_eps"]
        one = jnp.ones((), jnp.float32)
        dr, b, c = (rmsnorm(v, one, eps) for v in (dr, b, c))
    dt = jax.nn.softplus(mm(dr, p["dt_proj"], mode) + p["dt_bias"])
    a = -jnp.exp(p["A_log"])                                   # (di, ds)

    def step(s, inp):
        dt_t, b_t, c_t, x_t = inp
        s = jnp.exp(dt_t[:, None] * a) * s + (dt_t * x_t)[:, None] * b_t
        return s, jnp.sum(s * c_t, axis=-1)

    _, y = jax.lax.scan(step, jnp.zeros((di, ds), jnp.float32),
                        (dt, b, c, xc))
    y = (y + p["D"] * xc) * silu(z)
    return x + mm(y, p["out_proj"], mode)
