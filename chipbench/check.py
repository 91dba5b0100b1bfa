"""The comparison that decides ``correct``.

Once the window has closed and the program's state is freed, a sample of
the requests the window finished, drawn from the seed and holding the
longest of them, is run through the plain float32 reference
(``reference/<kind>.py``) over each prompt and its served tokens, one
sequence and one layer at a time.  At every served position the check
reads by how much the reference's best logit lies above its logit of the
token the program served.  The number compared is the widest such gap
(``logit_gap_max``) against the configuration's limit.  Greedy decoding
serves the top token, so a sound program reads a gap only where rounding
has swapped near-ties; a wrong cache, state or token reads far more.

``compare(..., control=True)`` puts the control in the program's place:
the reference computed in float8 (``reference/ops.py``) over the same
prompts and served tokens, judged at each position by the token it puts
first.  Its gap goes through the same limit, so the control reads as not
correct; the program's own gap is kept beside it.
"""
from __future__ import annotations

from functools import partial
from typing import List

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import bench
from chipbench.gen.openloop import rng
from chipbench.reference import ops


def sample(served, seed: int, min_tokens: int) -> List:
    """The longest finished request, then others in a seeded order until
    the sample holds ``min_tokens`` served tokens."""
    done = [s for s in served if s.finished]
    if not done:
        return []
    done.sort(key=lambda s: (len(s.prompt) + s.n_out, s.id), reverse=True)
    picked, rest = [done[0]], done[1:]
    order = rng(seed, 5).permutation(len(rest))
    n = done[0].n_out
    for i in order:
        if n >= min_tokens:
            break
        picked.append(rest[i])
        n += rest[i].n_out
    return picked


def _head(h, final_norm, head, m, mode):
    x = ops.rmsnorm(h, final_norm, m["norm_eps"])
    return ops.mm(x, head.T, mode)[:, :m["vocab_size"]]


def compare(config: dict, weights, picked, control: bool = False) -> dict:
    """Reference gaps over ``picked``: the widest gap of a served token,
    or, with ``control``, of the float8 forward's choice at each served
    position (the program's gap then goes under ``program_gap_max``)."""
    ref = bench.reference(config)
    m = config["model"]
    modes = ("f32", "fp8") if control else ("f32",)
    layer = {mode: jax.jit(partial(ref.layer, m, mode=mode))
             for mode in modes}
    final = {mode: jax.jit(partial(_head, m=m, mode=mode)) for mode in modes}
    head = ref.head_weight(m, weights)
    judged = "control" if control else "served"
    widest = {"served": 0.0, "control": 0.0}
    agree, n = 0, 0
    for s in picked:
        toks = list(s.prompt) + list(s.out[:-1])
        t = len(toks)
        padded = np.zeros(ops.bucket(t), np.int32)
        padded[:t] = toks
        first = len(s.prompt) - 1          # predicts the first served token
        rows = ops.bucket(s.n_out, 64)
        sel = np.minimum(np.arange(first, first + rows), t - 1)
        out = np.zeros(rows, np.int32)
        out[:s.n_out] = s.out
        logits = {}
        for mode in modes:
            x = jnp.take(weights["embed"], jnp.asarray(padded),
                         axis=0).astype(jnp.float32)
            for i in range(m["n_layers"]):
                x = layer[mode](weights["layers"], i, x)
            logits[mode] = final[mode](x[sel], weights["final_norm"], head)
        chosen = {"served": jnp.asarray(out)}
        if control:
            chosen["control"] = jnp.argmax(logits["fp8"], axis=-1)
        for who, tokens in chosen.items():
            gap, hit = ops.logit_gaps(logits["f32"], tokens)
            widest[who] = max(widest[who],
                              float(np.asarray(gap)[:s.n_out].max()))
            if who == judged:
                agree += int(np.asarray(hit)[:s.n_out].sum())
        n += s.n_out
    out = {"logit_gap_max": widest[judged], "tokens": n,
           "requests": len(picked), "top1_agree": agree / max(n, 1)}
    if control:
        out["program_gap_max"] = widest["served"]
    return out
