"""Per-kernel shape/dtype sweeps vs the ref.py oracles (interpret mode)."""
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import ref
from repro.kernels.decode_attention import (decode_attention_pallas,
                                            paged_decode_attention_pallas)
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.rmsnorm import rmsnorm_pallas
from repro.kernels.selective_scan import selective_scan_pallas
from repro.models import attention

TOL = {jnp.float32: 2e-5, jnp.bfloat16: 2e-2}
# the TPU interpreter, which runs a kernel's hand-made copies only when
# it waits on them: a read before its wait sees the buffer's old bytes
ON_WAIT = pltpu.InterpretParams(dma_execution_mode="on_wait")


def _tol(dtype):
    return TOL[jnp.bfloat16] if dtype == jnp.bfloat16 else TOL[jnp.float32]


@pytest.mark.parametrize("b,h,kv,s,d", [
    (1, 4, 4, 128, 64),    # MHA
    (2, 8, 2, 256, 64),    # GQA
    (1, 4, 1, 192, 128),   # MQA, ragged seq vs 128 blocks
    (2, 2, 2, 64, 256),    # wide head (gemma3-like)
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("causal,window", [(True, 0), (True, 64),
                                           (False, 0)])
def test_flash_attention(b, h, kv, s, d, dtype, causal, window):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (b, h, s, d), dtype)
    k = jax.random.normal(ks[1], (b, kv, s, d), dtype)
    v = jax.random.normal(ks[2], (b, kv, s, d), dtype)
    out = flash_attention_pallas(q, k, v, causal=causal, window=window,
                                 block_q=64, block_k=64, interpret=True)
    exp = ref.flash_attention_ref(q, k, v, causal=causal, window=window)
    assert out.shape == exp.shape and out.dtype == q.dtype
    err = jnp.max(jnp.abs(out.astype(jnp.float32)
                          - exp.astype(jnp.float32)))
    assert float(err) < _tol(dtype) * 10, float(err)


@pytest.mark.parametrize("b,h,kv,s,d", [
    (1, 4, 4, 256, 64),
    (2, 8, 2, 512, 64),
    (3, 4, 1, 384, 128),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_attention(b, h, kv, s, d, dtype):
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (b, h, d), dtype)
    kc = jax.random.normal(ks[1], (b, kv, s, d), dtype)
    vc = jax.random.normal(ks[2], (b, kv, s, d), dtype)
    pos = jnp.arange(b, dtype=jnp.int32) * (s // max(b, 1)) + 5
    out = decode_attention_pallas(q, kc, vc, pos, block_s=128,
                                  interpret=True)
    exp = ref.decode_attention_ref(q, kc, vc, pos)
    err = jnp.max(jnp.abs(out.astype(jnp.float32)
                          - exp.astype(jnp.float32)))
    assert float(err) < _tol(dtype) * 10, float(err)


def _paged_rows(bs, nb, nb_phys, ring):
    """Block tables and lengths of seven rows over ``nb`` table blocks:
    lengths 0, 1, bs, bs+1 and the whole table; a free row on the
    scratch block 0 (its frozen position still counts slots); a row
    that shares the full row's first two physical blocks.  On a SWA
    ring of ``s`` < nb*bs slots the last three have wrapped
    (length s).  Returns (tables, lengths, pos), ``pos`` a position
    whose :func:`attention._decode_valid` mask admits those slots."""
    s = nb * bs - 3 if ring else nb * bs
    rng = np.random.default_rng(0)
    ids = rng.permutation(np.arange(1, nb_phys))[:7 * nb].reshape(7, nb)
    ids[5] = 0
    ids[6, :2] = ids[4, :2]
    lengths = np.array([0, 1, bs, bs + 1, s, 2 * bs + 3, 2 * bs + 5])
    pos = lengths - 1
    if ring:
        lengths[4:] = s
        pos[4:] = s + np.array([0, 5, 11])   # wrapped: every slot valid
    return (jnp.asarray(ids, jnp.int32), jnp.asarray(lengths, jnp.int32),
            jnp.asarray(pos, jnp.int32), s)


@pytest.mark.parametrize("h,kv,d", [
    (4, 4, 32),     # MHA
    (15, 5, 16),    # GQA, SmolLM's 15/5 heads at a small head
    (4, 1, 64),     # MQA
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("ring", [False, True])
def test_paged_decode_attention(h, kv, d, dtype, ring):
    """The paged decode kernel on stacked pools at a nonzero layer
    against the engines' gather path (``_paged_gather`` + float32
    softmax) and the ref.py oracle.  Pool columns past the heads and
    slots past a row's length hold noise that must not leak in; a row
    of length 0 gets zeros."""
    cfg = SimpleNamespace(n_heads=h, n_kv_heads=kv, head_dim=d)
    w = attention.slot_width(cfg)
    n_layers, layer, bs, nb = 3, 2, 8, 4
    nb_phys = 7 * nb + 2
    ks = jax.random.split(jax.random.PRNGKey(4), 3)
    q = jax.random.normal(ks[0], (7, h, d), dtype)
    kp = jax.random.normal(ks[1], (n_layers, nb_phys, bs, w), dtype)
    vp = jax.random.normal(ks[2], (n_layers, nb_phys, bs, w), dtype)
    tables, lengths, pos, s = _paged_rows(bs, nb, nb_phys, ring)

    # the TPU interpreter's callbacks run JAX ops of their own: let them
    # finish before this thread dispatches more, or the two can deadlock
    out = jax.block_until_ready(paged_decode_attention_pallas(
        q, kp, vp, tables, lengths, layer, kv_heads=kv, chunk=2,
        interpret=ON_WAIT))
    assert out.shape == q.shape and out.dtype == q.dtype
    oracle = ref.paged_decode_attention_ref(q, kp, vp, tables, lengths,
                                            layer, kv_heads=kv)
    gathered = attention._gathered_decode(
        q[:, None], kp, vp, tables, pos, layer, cfg=cfg, s=s, ring=ring,
        dtype=jnp.float32).reshape(q.shape)
    live = (lengths > 0)[:, None, None]
    tol = _tol(dtype) * 10
    for exp in (oracle, jnp.where(live, gathered, 0)):
        err = jnp.max(jnp.abs(out.astype(jnp.float32)
                              - exp.astype(jnp.float32)))
        assert float(err) < tol, float(err)
    assert not jnp.any(out[0])


@pytest.mark.parametrize("b,t,di,ds", [
    (1, 64, 128, 16),
    (2, 100, 256, 16),     # t not a multiple of the chunk
    (2, 128, 512, 8),
])
def test_selective_scan(b, t, di, ds):
    ks = jax.random.split(jax.random.PRNGKey(2), 6)
    dt = jax.nn.softplus(jax.random.normal(ks[0], (b, t, di)))
    bm = jax.random.normal(ks[1], (b, t, ds))
    cm = jax.random.normal(ks[2], (b, t, ds))
    x = jax.random.normal(ks[3], (b, t, di))
    a_neg = -jnp.abs(jax.random.normal(ks[4], (di, ds)))
    h0 = jax.random.normal(ks[5], (b, di, ds))
    y, h_t = selective_scan_pallas(dt, bm, cm, x, a_neg, h0,
                                   block_di=128, chunk_t=64, interpret=True)
    y_exp, h_exp = ref.selective_scan_ref(dt, bm, cm, x, a_neg, h0)
    assert float(jnp.max(jnp.abs(y - y_exp))) < 1e-3
    assert float(jnp.max(jnp.abs(h_t - h_exp))) < 1e-3


@pytest.mark.parametrize("shape", [(8, 128), (3, 37, 256), (2, 5, 7, 512)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rmsnorm(shape, dtype):
    k1, k2 = jax.random.split(jax.random.PRNGKey(3))
    x = jax.random.normal(k1, shape, dtype)
    scale = jax.random.normal(k2, shape[-1:], dtype)
    out = rmsnorm_pallas(x, scale, interpret=True)
    exp = ref.rmsnorm_ref(x, scale)
    err = jnp.max(jnp.abs(out.astype(jnp.float32)
                          - exp.astype(jnp.float32)))
    assert float(err) < _tol(dtype) * 5
