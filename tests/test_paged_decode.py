"""Which paged decode attention runs where.

``attention._paged_decode_attend`` picks by the platform a program is
lowered for: the TPU runs the paged decode kernel
(`src/repro/kernels/decode_attention.py`), every other platform the
gather of the logical view.  On the CPU the engines' token streams
are the gather's, which the parity tests pin; the kernel, run here in
interpret mode in the gather's place, serves the same streams.
"""
import functools

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_smoke_config
from repro.models import attention
from repro.serving import PagedServingEngine, Request

# six requests through three rows of 64 positions: rows are freed and
# reused (a free row keeps its frozen position on the scratch block),
# and the longest prompts span several 8-token blocks
PROMPTS = [[5, 6, 7, 2, 9, 3, 8, 1], [9, 10, 4], [11, 3, 5, 7, 2],
           list(range(1, 30)), [3] * 17, [7, 1] * 9]


def _streams(k):
    eng = PagedServingEngine(get_smoke_config("smollm-360m"), max_rows=3,
                             max_len=64, block_size=8, prefill_chunk=4,
                             decode_steps=k)
    for i, p in enumerate(PROMPTS):
        eng.submit(Request(id=i, prompt=list(p), max_new_tokens=12))
    return {r.id: r.out_tokens for r in eng.run()}


def _decode_hlo(platform):
    """The K=2 paged macro-step of the smoke config, lowered for
    ``platform``."""
    eng = PagedServingEngine(get_smoke_config("smollm-360m"), max_rows=3,
                             max_len=64, block_size=8, decode_steps=2)
    batch = {n: jnp.zeros((3,) + s, jnp.int32)
             for n, s in (("token", (1,)), ("pos", ()), ("budget", ()))}
    return eng._decode_jit(2).trace(
        eng.params, eng.caches, batch, eng.pc.meta()).lower(
        lowering_platforms=(platform,)).as_text()


def _gathered_only(*args, ring, **kw):
    return attention._gathered_decode(*args, ring=ring, **kw)


def _kernel_interpreted(*args, ring, **kw):
    return attention._kernel_decode(*args, interpret=True, **kw)


@pytest.mark.parametrize("k", [1, 8])
def test_cpu_decode_runs_the_gather(monkeypatch, k):
    """On the CPU the platform choice takes the gather: the same streams
    as the gather called directly, and no kernel in the CPU program,
    while the program lowered for the TPU holds it."""
    assert "tpu_custom_call" not in _decode_hlo("cpu")
    assert "tpu_custom_call" in _decode_hlo("tpu")
    got = _streams(k)
    monkeypatch.setattr(attention, "_paged_decode_attend", _gathered_only)
    assert got == _streams(k)


@pytest.mark.parametrize("k", [1, 8])
def test_kernel_branch_streams_match(monkeypatch, k):
    """The kernel branch, interpreted, serves the gather's streams."""
    want = _streams(k)
    monkeypatch.setattr(attention, "_paged_decode_attend",
                        _kernel_interpreted)
    jax.clear_caches()     # the engines' programs close over the choice
    try:
        assert _streams(k) == want
    finally:
        jax.clear_caches()


def test_kernel_reads_nothing_for_a_free_row():
    """A row whose table starts at the scratch block holds no request:
    the kernel branch gives it zeros whatever its frozen position."""
    cfg = get_smoke_config("smollm-360m")
    h, kv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    w = attention.slot_width(cfg)
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (2, 1, h, hd))
    pools = [jax.random.normal(k_, (2, 5, 8, w)) for k_ in ks[1:]]
    tables = jnp.asarray([[1, 2, 3, 4], [0, 0, 0, 0]], jnp.int32)
    pos = jnp.asarray([20, 27], jnp.int32)
    run = functools.partial(attention._kernel_decode, cfg=cfg, s=32,
                            dtype=jnp.float32, interpret=True)
    out = run(q, *pools, tables, pos, jnp.int32(1))
    gathered = attention._gathered_decode(
        q, *pools, tables, pos, jnp.int32(1), cfg=cfg, s=32, ring=False,
        dtype=jnp.float32)
    assert float(jnp.max(jnp.abs(out[0] - gathered[0]))) < 1e-5
    assert not jnp.any(out[1])
