"""Paged pools as the layer scan's carry: the same tokens and pools as
the per-layer-slice formulation.

``transformer.apply_segments`` carries a segment's stacked paged pools
through its layer loop, and each layer writes and gathers its slots at
``paged["layer"]`` (`src/repro/models/attention.py`).  The reference
here is the formulation it replaced, kept in this file: unrolled over
layers, each layer handed its own one-layer slice of the pool (written,
then gathered through the tables) and the slices stacked back.  Both
must agree bit for bit on ``Model.decode_steps`` and
``Model.paged_prefill_chunk``, over pools filled with random stale KV
so that every gathered slot matters.

The block tables cover: a row masked mid-scan (its ``pos`` freezes), an
empty row whose table is all scratch block 0, two rows that share their
first blocks (a cached prefix), and SWA rings that have wrapped.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.models import build_model
from repro.models import transformer as tfm
from repro.models.kvcache import PagedCache

ROWS, MAX_LEN, BS, K, CHUNK = 4, 64, 8, 8, 8
POOL_KINDS = ("attn", "swa", "cross")

# name -> arch: smollm scans a linear attn segment; mixtral scans SWA
# rings (window 32 < MAX_LEN, so positions past 32 have wrapped);
# llama-3.2-vision has one-layer attn and cross segments; seamless
# carries read-only cross pools beside the self-attention pools;
# zamba2 runs a weight-shared attn segment after SSM state rows
CASES = {"linear": "smollm-360m", "swa-ring": "mixtral-8x7b",
         "cross": "llama-3.2-vision-90b", "enc-dec": "seamless-m4t-medium",
         "shared-ssm": "zamba2-7b"}


def _slice_apply_segments(blocks, x, *, cfg, mode, segs=None, pos=None,
                          caches=None, paged=None, qformat=None, **kw):
    """The per-layer-slice reference of ``apply_segments`` (paged decode
    and chunk modes): layer j of a pooled segment sees only its slice
    ``pool[j:j+1]`` (addressed at layer 0), SSM state rows ``a[j]``."""
    segs = segs if segs is not None else tfm.build_segments(cfg)
    new_caches = []
    for i, seg in enumerate(segs):
        params = blocks["shared"] if seg.shared else blocks["segments"][i]
        pooled = seg.kind in POOL_KINDS
        outs = []
        for j in range(seg.length):
            pj = (params if seg.length == 1 or seg.shared
                  else jax.tree.map(lambda a: a[j], params))  # noqa: B023
            lo, hi = (j, j + 1) if pooled else (j, None)
            cj = jax.tree.map(
                lambda a: a[lo:hi] if hi else a[lo], caches[i])  # noqa: B023
            x, c_out, _ = tfm.block_apply(
                pj, x, kind=seg.kind, cfg=cfg, mode=mode, pos=pos, cache=cj,
                paged=dict(paged, layer=0), qformat=qformat)
            outs.append(c_out)
        new_caches.append(jax.tree.map(
            (lambda *a: jnp.concatenate(a)) if pooled
            else (lambda *a: jnp.stack(a)), *outs))
    return x, new_caches, tfm._empty_aux()


def _setup(arch):
    cfg = get_smoke_config(arch)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    pc = PagedCache(cfg, max_rows=ROWS, max_len=MAX_LEN, block_size=BS)
    keys = iter(jax.random.split(jax.random.PRNGKey(1), 64))
    caches = jax.tree.map(
        lambda a: jax.random.normal(next(keys), a.shape).astype(a.dtype),
        pc.struct(model.dtype))
    nb = pc.nb_logical
    # row 0 owns blocks 1..nb; row 1 shares row 0's first two blocks
    # (16 cached prefix tokens) and owns the rest; row 2 owns its own;
    # row 3 is empty, every entry the scratch block 0
    tables = np.zeros((ROWS, nb), np.int32)
    tables[0] = np.arange(1, nb + 1)
    tables[1] = np.concatenate([[1, 2], np.arange(nb + 1, 2 * nb - 1)])
    tables[2] = np.arange(2 * nb - 1, 3 * nb - 1)
    meta = {"tables": tables}
    for group, width in (("swa", pc.nb_swa), ("cross", pc.nb_cross)):
        if width:
            t = np.zeros((ROWS, width), np.int32)
            t[:3] = np.arange(1, 3 * width + 1).reshape(3, width)
            meta[f"{group}_tables"] = t
    return model, params, caches, meta


def _assert_same(a, b):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b), strict=True):
        assert x.shape == y.shape and x.dtype == y.dtype
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def _both(monkeypatch, fn):
    """fn() under the carried pools, then under the slice reference."""
    got = fn()
    with monkeypatch.context() as m:
        m.setattr(tfm, "apply_segments", _slice_apply_segments)
        want = fn()
    return got, want


@pytest.mark.parametrize("case", sorted(CASES))
def test_carried_pools_match_per_layer_slices(case, monkeypatch):
    model, params, caches, meta = _setup(CASES[case])
    meta_dev = {k: jnp.asarray(v) for k, v in meta.items()}

    # decode: row 2 runs out of budget after 3 of the K steps, row 3 is
    # masked throughout; rows 0 and 1 sit past the shared prefix and,
    # for the SWA ring, past its first wrap
    batch = {"token": jnp.array([[3], [7], [11], [0]], jnp.int32),
             "pos": jnp.array([40, 35, 10, 0], jnp.int32),
             "budget": jnp.array([K, K, 3, 0], jnp.int32)}

    def decode():
        return jax.jit(lambda p, c, b, t: model.decode_steps(
            p, c, b, t, k=K))(params, caches, batch, meta_dev)

    got, want = _both(monkeypatch, decode)
    _assert_same(got, want)
    toks = np.asarray(got[0])
    assert (toks[2, 3:] == -1).all() and (toks[3] == -1).all()
    assert (toks[:2] >= 0).all()

    # one prefill chunk of row 1, wrapping its ring at position 40
    row = 1
    row_meta = {k: jnp.asarray(v[row:row + 1]) for k, v in meta.items()}
    tokens = jnp.arange(5, 5 + CHUNK, dtype=jnp.int32)[None]

    def chunk():
        return jax.jit(model.paged_prefill_chunk)(
            params, caches, tokens, jnp.int32(36), jnp.int32(row), row_meta)

    _assert_same(*_both(monkeypatch, chunk))
