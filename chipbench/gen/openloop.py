"""Open-loop traffic from a mix file: arrivals in seconds, heavy-tailed
lengths, optional shared document prefixes.

The amount of work is fixed by the mix, the rate and the window, and the
seed only orders it.  ``n = round(rate * seconds)`` requests arrive.
Their gaps are the ``n`` quantiles of a unit exponential and their
prompt and output lengths the ``n`` quantiles of each clipped lognormal,
and each list is shuffled by the seed.  So every seed offers the same
request count, the same lengths and the same gaps, in another order, and
runs on different seeds differ by how the queue meets that order, not by
how much work arrived.  Token ids come from the seed too.

This is a stand-in for Poisson arrivals, and a smoother one: the count
in a window never varies, and gaps cluster only as far as the shuffled
quantiles happen to.  It is chosen so that a run's tail moves with the
system and not with how much work a seed drew; bursts come from the
``square`` profile, not from the draw.

Mix keys (all lengths in tokens):

- ``arrivals``: ``{"kind": "exp_quantiles"}`` (the shuffled exponential
  quantiles above, at a constant mean rate), or ``{"kind": "square",
  "period_s", "high_s", "high", "low"}``: a rate of ``high`` x the mean
  for the first ``high_s`` seconds of each fixed period and ``low`` x
  the mean for the rest (normalised so the mean is the cell's rate), the
  same quantile gaps laid over that profile;
- ``prompt`` / ``output``: ``{"median", "sigma", "min", "max"}`` of a
  lognormal clipped to [min, max];
- ``prefix`` (optional): ``{"docs", "tokens", "zipf_s"}``: each prompt
  opens with one of ``docs`` shared documents of ``tokens`` tokens, the
  counts per document proportional to ``rank ** -zipf_s``; ``prompt``
  is then the length of the question after it.
"""
from __future__ import annotations

from dataclasses import dataclass
from statistics import NormalDist
from typing import List

import numpy as np


@dataclass
class Arrival:
    due: float        # seconds after the window opens
    prompt: List[int]
    max_new: int
    doc: int = -1     # shared document index, -1 for none


def rng(seed: int, stream: int) -> np.random.Generator:
    """Independent numpy stream ``stream`` of ``seed`` (any integer)."""
    return np.random.default_rng(np.random.SeedSequence(
        [int(seed) % 2 ** 64, stream]))


def lognormal_quantiles(spec: dict, n: int) -> np.ndarray:
    """The ``n`` mid-quantiles of the clipped lognormal ``spec``."""
    nd = NormalDist()
    z = np.array([nd.inv_cdf((i + 0.5) / n) for i in range(n)])
    x = np.exp(np.log(spec["median"]) + spec["sigma"] * z)
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(int)


def unit_gaps(n: int) -> np.ndarray:
    """The ``n`` mid-quantiles of a unit exponential."""
    return -np.log1p(-(np.arange(n) + 0.5) / n)


def rate_profile(arrivals: dict, seconds: float, grid: int = 4096):
    """(t, cumulative intensity) on a grid over [0, seconds], for a mean
    rate of 1 per second."""
    t = np.linspace(0.0, seconds, grid + 1)
    if arrivals["kind"] == "exp_quantiles":
        return t, t.copy()
    if arrivals["kind"] != "square":
        raise ValueError(f"unknown arrival kind {arrivals['kind']!r}")
    p, h = arrivals["period_s"], arrivals["high_s"]
    mean = (arrivals["high"] * h + arrivals["low"] * (p - h)) / p
    lam = np.where(np.mod(t, p) < h, arrivals["high"], arrivals["low"]) / mean
    cum = np.concatenate([[0.0], np.cumsum(0.5 * (lam[1:] + lam[:-1])
                                           * np.diff(t))])
    return t, cum


def zipf_counts(n: int, docs: int, s: float) -> np.ndarray:
    """Requests per document, proportional to rank**-s, summing to n
    (largest remainders)."""
    w = np.arange(1, docs + 1, dtype=float) ** -s
    exact = n * w / w.sum()
    counts = np.floor(exact).astype(int)
    order = np.argsort(-(exact - counts), kind="stable")
    counts[order[:n - counts.sum()]] += 1
    return counts


def schedule(mix: dict, rate: float, seconds: float, seed: int,
             vocab: int, stream: int = 0) -> List[Arrival]:
    """The requests due in a window of ``seconds`` at a mean of ``rate``
    per second, sorted by due time.  ``stream`` separates independent
    draws of one seed (the warm-up uses another stream than the window).
    """
    n = max(1, int(round(rate * seconds)))
    g = rng(seed, 1000 + stream)
    gaps = g.permutation(unit_gaps(n))
    t, cum = rate_profile(mix["arrivals"], seconds)
    # arrival k sits at the middle of its gap, scaled into the window
    u = (np.cumsum(gaps) - 0.5 * gaps) * (cum[-1] / gaps.sum())
    due = np.interp(u, cum, t)
    plen = g.permutation(lognormal_quantiles(mix["prompt"], n))
    olen = g.permutation(lognormal_quantiles(mix["output"], n))
    prefix = mix.get("prefix")
    docs, doc_of = [], np.full(n, -1)
    if prefix:
        docs = [g.integers(1, vocab, size=prefix["tokens"]).tolist()
                for _ in range(prefix["docs"])]
        counts = zipf_counts(n, prefix["docs"], prefix["zipf_s"])
        doc_of = g.permutation(np.repeat(np.arange(prefix["docs"]), counts))
    out = []
    for k in range(n):
        tail = g.integers(1, vocab, size=int(plen[k])).tolist()
        head = docs[doc_of[k]] if doc_of[k] >= 0 else []
        out.append(Arrival(float(due[k]), head + tail, int(olen[k]),
                           int(doc_of[k])))
    return out
