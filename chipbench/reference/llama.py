"""Plain float32 reference of a Llama-style decoder (SmolLM family):
RMSNorm, grouped-query attention with rotary embeddings (the half-split
rotation of the Hugging Face Llama code), SwiGLU MLP, final RMSNorm and
a tied or untied head.  One sequence at a time, no cache, no batching.

Weights are made here from the seed (``make_weights``), in the benchmark's
own layout; ``to_program`` only re-nests the same arrays into the tree
the program takes.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from chipbench.reference.ops import HIGHEST, mm, normal, rmsnorm, silu


def _layer_weights(m, key):
    d, h, kv, hd, ff = (m["d_model"], m["n_heads"], m["n_kv_heads"],
                        m["head_dim"], m["d_ff"])
    dt = jnp.dtype(m["dtype"])
    k = jax.random.split(key, 9)
    return {
        "attn_norm": 1.0 + normal(k[0], (d,), 0.1, dt),
        "wq": normal(k[1], (d, h * hd), d ** -0.5, dt),
        "wk": normal(k[2], (d, kv * hd), d ** -0.5, dt),
        "wv": normal(k[3], (d, kv * hd), d ** -0.5, dt),
        "wo": normal(k[4], (h * hd, d), (h * hd) ** -0.5, dt),
        "mlp_norm": 1.0 + normal(k[5], (d,), 0.1, dt),
        "w_gate": normal(k[6], (d, ff), d ** -0.5, dt),
        "w_up": normal(k[7], (d, ff), d ** -0.5, dt),
        "w_down": normal(k[8], (ff, d), ff ** -0.5, dt),
    }


def padded_vocab(m) -> int:
    return -(-m["vocab_size"] // 256) * 256


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


def to_program(m, w):
    """The same arrays in the program's parameter tree."""
    L = w["layers"]
    seg = {"ln1": {"scale": L["attn_norm"]},
           "attn": {k: L[k] for k in ("wq", "wk", "wv", "wo")},
           "ln2": {"scale": L["mlp_norm"]},
           "mlp": {k: L[k] for k in ("w_gate", "w_up", "w_down")}}
    p = {"embed": {"w": w["embed"]},
         "blocks": {"segments": [seg], "shared": None},
         "final_norm": {"scale": w["final_norm"]}}
    if "lm_head" in w:
        p["lm_head"] = {"w": w["lm_head"]}
    return p


def head_weight(m, w):
    return w["embed"] if m["tie_embeddings"] else w["lm_head"]


def rotate(x, pos, theta):
    """Rotary embedding, half-split form. x (T, heads, hd), pos (T,)."""
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos[:, None].astype(jnp.float32) * freq
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def layer(m, layers, i, x, mode="f32"):
    """Decoder layer ``i`` over one sequence x (T, d), float32."""
    p = jax.tree.map(lambda a: a[i], layers)
    t = x.shape[0]
    h, kv, hd = m["n_heads"], m["n_kv_heads"], m["head_dim"]
    eps = m["norm_eps"]
    pos = jnp.arange(t)
    a = rmsnorm(x, p["attn_norm"], eps)
    q = rotate(mm(a, p["wq"], mode).reshape(t, h, hd), pos, m["rope_theta"])
    k = rotate(mm(a, p["wk"], mode).reshape(t, kv, hd), pos, m["rope_theta"])
    v = mm(a, p["wv"], mode).reshape(t, kv, hd)
    k = jnp.repeat(k, h // kv, axis=1)       # query head j reads kv j // g
    v = jnp.repeat(v, h // kv, axis=1)
    s = jnp.einsum("qhd,khd->hqk", q, k, precision=HIGHEST) * hd ** -0.5
    s = jnp.where(pos[None, :, None] >= pos[None, None, :], s, -jnp.inf)
    o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v,
                   precision=HIGHEST).reshape(t, h * hd)
    x = x + mm(o, p["wo"], mode)
    b = rmsnorm(x, p["mlp_norm"], eps)
    g = mm(b, p["w_gate"], mode)
    u = mm(b, p["w_up"], mode)
    return x + mm(silu(g) * u, p["w_down"], mode)
